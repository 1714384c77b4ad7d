"""The yardstick's arithmetic against hand-worked values, the peak table,
and the generators' determinism under the seed."""

import numpy as np
import pytest

import costs
import peaks
import spec

ROOTS = spec.Roots()


def test_lm_flops_per_token_by_hand():
    # 100 parameters, 2 layers, 8 positions, width 4:
    # 6*100 + 6*2*8*4 = 600 + 384
    assert costs.dense_lm_train_flops_per_token(100, 2, 8, 4) == 984.0


def test_gpt2_medium_parameters_and_flops():
    fam = ROOTS.module("families", "transformer_lm")
    cfg = ROOTS.json("configs", "gpt2-medium.json")
    d, ff = 1024, 4096
    block = 2 * d + 3 * d * d + d * d + 2 * d + d * ff + ff + ff * d + d
    by_hand = 50304 * d + 1024 * d + 24 * block + 2 * d
    assert fam.n_params(cfg) == by_hand == 354_772_992
    assert fam.model_flops_per_sample(cfg, {}) == \
        6.0 * by_hand + 6.0 * 24 * 1024 * 1024 == 2_279_632_896.0


def test_causal_attention_cost_by_hand():
    # B=1, H=1, T=4, D=2: forward 2*1*1*16*2 = 64 flops, x3.5 = 224;
    # one tensor 1*4*1*2 elements * 2 bytes = 16 bytes, 12 of them = 192
    assert costs.causal_attention_train_cost(1, 1, 4, 2) == (224.0, 192.0)
    # the podshare cell's chip: 4 x 16 heads x 1024^2 x 64, one layer
    flops, nbytes = costs.causal_attention_train_cost(4, 16, 1024, 64)
    assert flops == 7 * 4 * 16 * 1024 * 1024 * 64
    assert nbytes == 12 * 4 * 1024 * 16 * 64 * 2


def test_resnet50_macs_by_hand():
    # stem: 112*112*7*7*3*64; first block of stage 1 at 56x56:
    # 1x1 64->64, 3x3 64->64, 1x1 64->256, projection 64->256
    stem = 112 * 112 * 49 * 3 * 64
    b0 = 56 * 56 * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    one = costs.resnet_bottleneck_forward_macs((1,), 64, 224, 1000)
    assert one == stem + b0 + 256 * 1000
    full = costs.resnet_bottleneck_forward_macs((3, 4, 6, 3), 64, 224, 1000)
    assert full == 4_089_184_256  # the v1.5 figure, 4.09 GMAC
    fam = ROOTS.module("families", "resnet")
    cfg = ROOTS.json("configs", "resnet50.json")
    assert fam.model_flops_per_sample(cfg, {}) == 6.0 * full


def test_roofline_says_which_bound_holds():
    peak = peaks.lookup("TPU v5 lite")
    assert peak["bf16_flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert costs.roofline_seconds(197e12, 1.0, peak) == (1.0, "compute")
    assert costs.roofline_seconds(1.0, 819e9, peak) == (1.0, "memory")
    lm = ROOTS.module("families", "transformer_lm")
    cfg = ROOTS.json("configs", "gpt2-medium.json")
    cost = lm.kernel_costs(cfg, {"per_chip_batch": 4})["flash"]
    seconds, bound = costs.roofline_seconds(*cost, peak)
    assert bound == "compute"
    assert seconds == pytest.approx(24 * 7 * 4 * 16 * 2**20 * 64 / 197e12)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.lookup("cpu")
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9")
    assert all("source" in row for row in peaks.PEAKS.values())


TOKENS = {"pool_batches": 3, "doc_len_median": 40, "doc_len_sigma": 1.0,
          "zipf_exponent": 1.0}


def test_token_generator_is_a_function_of_the_seed():
    gen = ROOTS.module("traffic", "gen_tokens")
    kw = dict(rows=4, seq_len=64, vocab_size=500, eos_id=499)
    a, b, c = (gen.pool(s, TOKENS, **kw) for s in (7, 7, 8))
    assert all((x == y).all() for x, y in zip(a, b))
    assert any((x != y).any() for x, y in zip(a, c))
    assert len(a) == 3 and a[0].shape == (4, 64) and a[0].dtype == np.int32
    flat = np.concatenate(a).ravel()
    assert flat.min() >= 0 and flat.max() <= 499
    assert 0 < (flat == 499).mean() < 0.1  # documents end, and are long
    # Zipf: the commonest id is far commoner than the median one
    counts = np.bincount(flat[flat != 499], minlength=499)
    assert counts.max() > 20 * max(1, np.median(counts[counts > 0]))


def test_image_generator_is_a_function_of_the_seed():
    gen = ROOTS.module("traffic", "gen_images")
    kw = dict(rows=2, image_size=8, channels=3, num_classes=10)
    a, b, c = (gen.pool(s, {"pool_batches": 2}, **kw) for s in (1, 1, 2))
    assert all((x[0] == y[0]).all() and (x[1] == y[1]).all()
               for x, y in zip(a, b))
    assert (a[0][0] != c[0][0]).any()
    assert a[0][0].shape == (2, 8, 8, 3) and a[0][0].dtype == np.uint8
    assert a[0][1].dtype == np.int32 and a[0][1].max() < 10
    assert (a[0][0] != a[1][0]).any()  # the pool's batches are distinct
