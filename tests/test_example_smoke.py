"""Smoke coverage for the examples without their own example-level test:
tiny configs, a handful of iterations — proof every documented CLI still
runs end to end on the 8-way CPU mesh (the reference ran its examples
under MPI as its de-facto integration suite, SURVEY.md section 2.8)."""

import pytest
from conftest import load_example as _load_example


def test_transformer_example_smoke():
    ex = _load_example("transformer", "train_transformer_lm.py")
    ex.main([
        "--iterations", "4", "--batchsize", "8", "--seq-len", "32",
        "--num-layers", "1", "--d-model", "32",
    ])


def test_transformer_example_sequence_parallel_smoke():
    ex = _load_example("transformer", "train_transformer_lm.py")
    ex.main([
        "--iterations", "3", "--batchsize", "8", "--seq-len", "32",
        "--num-layers", "1", "--d-model", "32", "--sequence-parallel",
    ])


def test_transformer_example_rope_sp_smoke():
    """RoPE + ring sequence parallelism through the CLI (per-shard global
    positions, no table rolling)."""
    ex = _load_example("transformer", "train_transformer_lm.py")
    ex.main([
        "--iterations", "3", "--seq-len", "32", "--num-layers", "1",
        "--d-model", "32", "--sequence-parallel", "--pos-encoding", "rope",
    ])


def test_transformer_example_packed_smoke():
    """Packed-sequence LM with segment-masked flash attention AND GQA
    (VERDICT r2 item 5's done-condition: a packed-sequence LM example
    trains with flash)."""
    ex = _load_example("transformer", "train_transformer_lm.py")
    ex.main([
        "--iterations", "3", "--batchsize", "8", "--seq-len", "64",
        "--num-layers", "1", "--d-model", "32", "--packed",
        "--num-kv-heads", "2",
    ])


def test_seq2seq_example_smoke_with_bleu():
    import examples.seq2seq.seq2seq as ex

    ex.main([
        "--iterations", "30", "--batchsize", "16", "--eval",
        "--eval-size", "32",
    ])


def test_parallel_conv_example_smoke():
    import examples.parallel_convolution.train_parallel_conv as ex

    ex.main(["--iterations", "5"])


def test_imagenet_example_native_loader(tmp_path):
    """ImageNet example fed by the C++ threaded prefetch loader end to end
    (VERDICT r2 item 6: the MultiprocessIterator role exercised through the
    benchmark workload, not just unit-tested)."""
    import numpy as np

    from chainermn_tpu.native.data_loader import write_fixed_records

    hw, n = 32, 128
    rng = np.random.default_rng(0)
    path = str(tmp_path / "records.bin")
    write_fixed_records(
        path,
        rng.integers(0, 256, size=(n, hw, hw, 3), dtype=np.uint8),
        rng.integers(0, 1000, size=(n,)).astype(np.int32),
    )
    ex = _load_example("imagenet", "train_imagenet.py")
    ex.main([
        "--arch", "resnet50", "--communicator", "naive", "--iterations", "2",
        "--batchsize", "1", "--image-size", str(hw),
        "--native-loader", path,
        # the roofline's byte-cutting remat mode rides along so the
        # documented CLI path stays wired (round-4)
        "--remat", "conv",
    ])


def test_transformer_example_mlm_smoke():
    """--mlm: the bidirectional-encoder pretraining mode (round 5)."""
    ex = _load_example("transformer", "train_transformer_lm.py")
    ex.main([
        "--iterations", "3", "--batchsize", "8", "--seq-len", "32",
        "--num-layers", "1", "--d-model", "32", "--mlm",
    ])


def test_mnist_example_local_sgd_smoke():
    """--local-sgd: periodic parameter averaging through the standard
    trainer (round 5)."""
    ex = _load_example("mnist", "train_mnist.py")
    ex.main([
        "--communicator", "naive", "--iterations", "12",
        "--local-sgd", "3", "--batchsize", "64",
    ])


@pytest.mark.parametrize("model", ["olmoe-tiny", "gpt2-tiny", "lfm2-tiny",
                                   "sdar-tiny", "dsv2-tiny"])
def test_transformer_example_named_model_smoke(model, monkeypatch, capsys):
    """``--model <name> --layers N`` builds a published architecture
    through the model description (ISSUE 25); here three tiny stand-ins
    under the published ones' ``model_type``s (LFM2's first three layers:
    two short convolutions and an attention layer, the third with
    experts; SDAR's trained by block diffusion, its noise drawn inside
    the step; DeepSeek-V2's first two: latent attention under a dense
    layer, then under routed experts beside the shared one)."""
    ex = _load_example("transformer", "train_transformer_lm.py")
    monkeypatch.setitem(ex.MODEL_CONFIGS, "olmoe-tiny", dict(
        ex.MODEL_CONFIGS["olmoe-1b-7b"], num_hidden_layers=4,
        hidden_size=32, num_attention_heads=2, num_key_value_heads=2,
        intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        vocab_size=1024, max_position_embeddings=64))
    monkeypatch.setitem(ex.MODEL_CONFIGS, "gpt2-tiny", dict(
        ex.MODEL_CONFIGS["gpt2-medium"], n_layer=4, n_embd=32, n_head=2,
        n_inner=64, n_positions=64, vocab_size=1024))
    monkeypatch.setitem(ex.MODEL_CONFIGS, "lfm2-tiny", dict(
        ex.MODEL_CONFIGS["lfm2-8b-a1b"], hidden_size=32,
        num_attention_heads=2, num_key_value_heads=1, intermediate_size=48,
        moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        vocab_size=1024, max_position_embeddings=64))
    monkeypatch.setitem(ex.MODEL_CONFIGS, "sdar-tiny", dict(
        ex.MODEL_CONFIGS["sdar-30b-a3b"], hidden_size=32,
        num_attention_heads=2, num_key_value_heads=1, head_dim=16,
        moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
        vocab_size=1025, max_position_embeddings=64))
    monkeypatch.setitem(ex.MODEL_CONFIGS, "dsv2-tiny", dict(
        ex.MODEL_CONFIGS["deepseek-v2-lite"], hidden_size=32,
        num_attention_heads=2, num_key_value_heads=2, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        intermediate_size=48, moe_intermediate_size=16, n_routed_experts=4,
        num_experts_per_tok=2, vocab_size=1024,
        max_position_embeddings=64))
    layers = {"lfm2-tiny": 3, "dsv2-tiny": 2}.get(model, 1)
    ex.main(["--iterations", "10", "--batchsize", "8", "--seq-len", "32",
             "--model", model, "--layers", str(layers)])
    out = capsys.readouterr().out
    assert f"done ({model}, {layers} layers)" in out
    assert ("load_balance=" in out) == (model in (
        "olmoe-tiny", "sdar-tiny", "dsv2-tiny"))  # a softmax router's
    assert ("seq_aux=" in out) == (model == "dsv2-tiny")
    assert ("masked_share=" in out) == (model == "sdar-tiny")
    assert ("rows_held=" in out) == (model != "gpt2-tiny")
