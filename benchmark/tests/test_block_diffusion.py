"""What PR 42 added for the SDAR cell: its files, the cost functions
against a count by hand, the readers of the ``bd_attention`` and
``bd_noise`` scopes on a step and a trace made by hand and on a run whose
program has no such scope (its parent), and the ``block_diffusion_moe_lm``
family with its reference through the harness at a tiny size on the
CPU."""

import copy
import json
import os
import time

import pytest

import hlo
import spec
import xplane

ROOTS = spec.Roots()
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}  # no chip's
CELL = "sdar-hostfill-1chip"
FAMILY = "block_diffusion_moe_lm"


def _op(name, source, custom=False):
    if custom:
        return (f"  %{name} = f32[4] custom-call(%a), "
                'custom_call_target="tpu_custom_call", '
                f'metadata={{op_name="jit(step)/{source}"}}\n')
    return (f"  %{name} = f32[4] fusion(%a), kind=kLoop, calls=%f, "
            f'metadata={{op_name="jit(step)/{source}"}}\n')


FWD = "loss_and_grad/jvp(M)/block_0/"
BWD = "loss_and_grad/transpose(jvp(M))/block_0/"
HLO = (
    "HloModule jit_step\n\n"
    "ENTRY %main (a: f32[4]) -> f32[4] {\n"
    "  %a = f32[4] parameter(0)\n"
    + _op("draw", "loss_and_grad/jvp(bd_noise)/uniform")
    + _op("qkv", FWD + "qkv/dot_general")
    + _op("fwd_k", FWD + "bd_attention/flash_fwd/pallas_call", custom=True)
    + _op("merge", FWD + "bd_attention/exp")
    + _op("dq_k", BWD + "bd_attention/flash_bwd_dq/pallas_call",
          custom=True)
    + _op("in_block", BWD + "bd_attention/mul")
    + _op("adam", "optimizer_update/add")
    + "}\n"
)
_STEP = [("draw", 1), ("qkv", 30), ("fwd_k", 20), ("merge", 4),
         ("dq_k", 40), ("in_block", 6), ("adam", 10)]
KERNELS_NS, SCOPE_NS, NOISE_NS = 20 + 40, 20 + 4 + 40 + 6, 1


def _table():
    ops, t = [], 0
    for _ in range(2):
        for name, dur in _STEP:
            ops.append([name, t, dur])
            t += dur
        t += 5
    step = sum(d for _, d in _STEP) + 5
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "async": [],
        "modules": [["jit_step(1)", 0, step], ["jit_step(1)", step, step]]}},
        "host_spans": [["bench.wait", 0, 50]]}


def _ctx(hlo_text=HLO):
    cell = spec.load_cell(ROOTS, CELL)
    return {
        "trace": xplane.reduce(_table(), hlo.categorize(hlo_text),
                               "jit_step"),
        "hlo_text": hlo_text, "host_spans": [], "program_metrics": {},
        "cell": cell, "loop": {"mosaic_calls": 2}, "peak": PEAK,
        "family": ROOTS.module("families", FAMILY),
    }


def _read(name, ctx):
    return ROOTS.module("layer_metrics", name).read(ctx)


def test_bd_readers_by_hand():
    ctx = _ctx()
    assert _read("bd_attention_ms", ctx) == pytest.approx(SCOPE_NS / 1e6)
    assert _read("bd_noise_ms", ctx) == pytest.approx(NOISE_NS / 1e6)
    # 5 layers x 3.5 x 4 x 32 heads x (8192^2 + 8192 x 4) pairs x 128
    flops = 5 * 3.5 * 4 * 32 * (8192 * 8192 + 8192 * 4) * 128
    assert _read("bd_flash_roofline_pct", ctx) == pytest.approx(
        100 * (flops / 1e12 * 1e3) / (KERNELS_NS / 1e6))


@pytest.mark.parametrize("name", ["bd_attention_ms", "bd_noise_ms",
                                  "bd_flash_roofline_pct"])
def test_a_program_without_the_scopes_reports_none(name):
    """The parent of the PR that named them, or a cell of another family:
    the reader returns nothing and does not raise; nor without a trace."""
    bare = HLO.replace("bd_attention/", "attn/").replace("bd_noise", "n")
    assert _read(name, _ctx(bare)) is None
    assert _read(name, {**_ctx(), "trace": {}}) is None


def test_costs_are_a_count_by_hand():
    import bd_costs
    import moe_costs

    config = ROOTS.json("configs", "sdar-30b-a3b.json")
    job = ROOTS.json("workloads", CELL + ".json")["job"]
    fam = ROOTS.module("families", FAMILY)
    d, L = 2048, 8192
    assert bd_costs.mask_support(L, 4) == L * L + 4 * L
    # the dense mask's own count, at a size that can be enumerated
    ref = ROOTS.module("reference", FAMILY)
    assert int(ref.mask_rows(0, 48, 24, 4).sum()) == \
        bd_costs.mask_support(24, 4)
    attn = d * (32 + 2 * 4) * 128 + 32 * 128 * d
    assert attn == 18_874_368
    # one expert a row in expectation: 8 x 16 / 128
    ffn = d * 128 + 1 * 3 * d * 768
    scores = 3.5 * 4.0 * 32 * (L * L + 4 * L) * 128
    want = 6.0 * (10 * attn + 9 * ffn + d * 18992) + 5 * scores / L
    assert fam.model_flops_per_sample(config, job) == pytest.approx(want)
    # the issue's 32.7 TFLOP a step, the masked attention ~58% of it
    assert want * L == pytest.approx(32.7e12, rel=5e-3)
    assert 5 * scores / (want * L) == pytest.approx(0.59, abs=0.01)
    costs = fam.kernel_costs(config, job)
    q, kv = 2 * L * 32 * 128 * 2, 2 * L * 4 * 128 * 2
    assert costs["bd_flash"] == (5 * scores, 5 * 6.0 * (q + kv))
    both = moe_costs.gated_experts_train_cost(16384, 16, d, 768)
    last = moe_costs.gated_experts_train_cost(8192, 16, d, 768)
    assert costs["moe_gmm"] == (4 * both[0] + last[0],
                                4 * both[1] + last[1])


def test_the_cells_files_load_and_the_model_is_the_issues():
    import jax

    cell = spec.load_cell(ROOTS, CELL)
    assert cell["job"] == {"per_chip_batch": 1, "seq_len": 8192,
                           "remat": "dots", "head_chunks": 8}
    config = cell["config_spec"]
    fam = ROOTS.module("families", config["family"]).build(
        config, cell["job"])
    arch, model = fam.model.arch, fam.model
    assert (arch.n_experts, arch.experts_held, arch.experts_per_token,
            arch.router_score, arch.renormalise_gates, arch.qk_norm,
            arch.tied_head, arch.diffusion_block, arch.rope_base) == (
        128, (0, 16), 8, "softmax", True, "head", False, 4, 1e6)
    assert (model.num_layers, model.num_heads, model.num_kv_heads,
            model.head_dim, model.d_model) == (5, 32, 4, 128, 2048)
    params, state, check = jax.eval_shape(lambda: fam.init(1))
    assert check is None and sorted(state) == ["draw", "seed"]
    assert sum(x.size for x in jax.tree.leaves(params)) == 550_984_960
    block = params["block_0"]
    assert block["qkv"]["kernel"].shape == (2048, 5120)
    assert block["proj"]["kernel"].shape == (4096, 2048)
    assert block["q_norm"]["scale"].shape == (128,)
    assert block["moe_w_gate_up"].shape == (16, 2048, 1536)
    assert block["moe_router"].shape == (2048, 128)
    assert params["lm_head"]["embedding"].shape == (18992, 2048)
    assert fam.pool_args(1) == dict(rows=1, seq_len=8192, vocab_size=18991,
                                    eos_id=7)
    assert config["mask_token_id"] == config["vocab_size"] - 1


def test_the_committed_cell_lists_what_the_issue_names():
    b = spec.load_benchmark()
    assert len(b["workloads"]) == 8 and \
        sum(w["chips"] == 4 for w in b["workloads"]) == 1
    entry = [w for w in b["workloads"] if w["name"] == CELL][0]
    assert (entry["chips"], entry["traffic"]) == (1, "hostfill")
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {
        "tokens_per_s", "device_idle_pct", "forward_ms", "backward_ms",
        "recompute_ms", "optimizer_ms", "head_ms", "flash_fwd_ms",
        "flash_dq_ms", "flash_dkv_ms", "moe_experts_ms", "moe_dispatch_ms",
        "moe_gmm_roofline_pct", "bd_attention_ms", "bd_noise_ms",
        "bd_flash_roofline_pct"}
    config = ROOTS.json("configs", "sdar-30b-a3b.json")
    centry = [c for c in b["configs"] if c["name"] == "sdar-30b-a3b"][0]
    assert centry["source"] == config["source"] == \
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
    # the catalog row's config: every key but the three reduced
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "sdar_moe", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False}
    assert {k: config[k] for k in published} == published
    assert config["reduced"] == centry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert (config["num_experts"], config["experts_published"],
            config["experts_held_range"]) == (16, 128, [0, 16])
    assert "eight chips share each layer" in config["reduced_why"]
    assumed = config["assumed"]
    assert (assumed["block_length"], assumed["t_min"],
            assumed["router_aux_loss_coef"]) == (4, 0.05, 0.001)


# -- the family and its reference through the harness ----------------------

TINY_BD = {
    "source": "throw-away", "family": FAMILY, "model_type": "sdar_moe",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 4, "experts_published": 8,
    "experts_held_range": [2, 6], "num_experts_per_tok": 2,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "tie_word_embeddings": False, "vocab_size": 384, "block_length": 4,
    "mask_token_id": 383, "max_position_embeddings": 128,
    "eos_token_id": 7, "reduced": [],
    "assumed": {"t_min": 0.05, "router_aux_loss_coef": 0.001,
                "used_token_ids": 300},
    "training": {
        "compute_dtype": "float32", "param_dtype": "float32",
        "attention": "pallas_flash_block_mask", "head": "fused_chunked",
        "experts": "dropless_grouped_matmul",
        "optimizer": {"name": "adamw", "learning_rate": 3e-4,
                      "warmup_steps": 100, "b1": 0.9, "b2": 0.95,
                      "weight_decay": 0.1},
        "allreduce_grad_dtype": "bfloat16"},
}
TINY_CELL = {"config": "tiny-bd", "traffic": "tiny-bd-mix", "chips": 1,
             "why": "x", "job": {"per_chip_batch": 2, "remat": "dots",
                                 "head_chunks": 2}}
TINY_MIX = {
    "what": "throw-away", "loop": "train", "feed": {"depth": 2},
    "warmup_steps": 2,
    "samples": {"tokens": {"pool_batches": 4, "doc_len_median": 40,
                           "doc_len_sigma": 1.0, "zipf_exponent": 1.0}},
}


def added_root(root):
    """``(roots, benchmark)`` with a tiny block-diffusion configuration,
    mix and cell written under ``root`` beside the benchmark's own, and
    ``BENCHMARK.json``'s content with their entries appended (the new cell
    on every list the committed cell is on)."""
    for rel, body in (("configs/tiny-bd.json", TINY_BD),
                      ("traffic/tiny-bd-mix.json", TINY_MIX),
                      ("workloads/tiny-bd.json", TINY_CELL)):
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(body, f)
    new = copy.deepcopy(spec.load_benchmark())
    new["configs"].append({"name": "tiny-bd", "source": "throw-away",
                           "file": "benchmark/configs/tiny-bd.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": "tiny-bd", **{
        k: TINY_CELL[k] for k in ("config", "traffic", "chips", "why")}})
    for m in new["end_to_end"] + new["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["tiny-bd"]
    return spec.Roots((str(root),)), new


@pytest.fixture(scope="module")
def added_bd(tmp_path_factory):
    return added_root(str(tmp_path_factory.mktemp("added_bd")))


def test_tiny_bd_cell_end_to_end_traced(added_bd):
    import jax

    import run

    roots, benchmark = added_bd
    line = run.run_cell("tiny-bd", seed=4_242_000_101, seconds=0.5,
                        trace=True, devices=jax.devices()[:1], peak=PEAK,
                        roots=roots, benchmark=benchmark,
                        t0=time.perf_counter())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    got = set(line["metrics"])
    assert {"compile_s", "peak_hbm_gb", "input_wait_ms"} <= got
    # no device plane in a CPU trace: the scope readers have nothing
    assert not got & {"bd_attention_ms", "bd_noise_ms",
                      "bd_flash_roofline_pct"}


def test_the_noise_is_state_counted_up_and_the_check_is_handed_the_draw():
    """The step draws its noise from ``model_state`` (the seed and a
    count of draws); the comparison's rows carry the first draw's ``m``
    and ``t`` as data, and on them the family's loss is the step's own
    first loss; a row a held expert loses leaves no finite loss."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.parallel import moe

    fam = ROOTS.module("families", FAMILY).build(
        TINY_BD, {**TINY_CELL["job"], "seq_len": 32})
    params, state, check = fam.init(4_242_000_102)
    assert check is None and float(state["draw"]) == 0.0
    assert int(state["seed"][0]) * 65536 + int(state["seed"][1]) \
        == 4_242_000_102
    tokens = np.asarray(jax.random.randint(jax.random.key(8), (2, 32), 0,
                                           300))
    loss, (metrics, after) = fam.loss_fn(params, jnp.asarray(tokens), state)
    assert np.isfinite(float(loss)) and float(after["draw"]) == 1.0
    assert float(metrics["moe/dropped"]) == 0.0
    assert 0 < float(metrics["moe/rows_held"]) < 2 * 2 * 64 * 2
    rows = fam.take_rows(tokens, 0, 2)
    assert rows["masked"].shape == (2, 32) and rows["t"].shape == (2, 8)
    same, (_, unchanged) = fam.loss_fn(params, rows, state)
    assert float(same) == float(loss) and unchanged is state
    again, _ = fam.loss_fn(params, jnp.asarray(tokens), after)
    assert float(again) != float(loss)  # the next step, another draw

    real = moe.dropless_topk

    def loses_a_row(*a, **kw):
        r = real(*a, **kw)
        last = jnp.argmax(r.group_sizes > 0)
        return r._replace(group_sizes=r.group_sizes.at[last].add(-1))

    moe.dropless_topk = loses_a_row
    try:
        loss, (metrics, _) = fam.loss_fn(params, rows, state)
    finally:
        moe.dropless_topk = real
    assert float(metrics["moe/dropped"]) == 2.0  # one a layer
    assert np.isnan(float(loss))


#: (loss, whole gradient, worst leaf) read on the v5e at the published
#: widths on the drawn tree (my chip runs, PR 42; PERF.md section 6 has the
#: seeds): the largest of each over the sound system's readings (14 of
#: check (a), 21 of the loss with check (b)'s), the least of each over the
#: reference computed in bf16's 7, and the targets shifted by one (1 seed)
SOUND_LARGEST = (2.02e-4, 0.0098, 0.2661)
BF16_REFERENCE_LEAST = (8.29e-4, 0.0073, 0.0592)
BF16_REFERENCE_LARGEST = (5.51e-3, 0.0092, 0.1255)
SHIFTED_TARGETS = (1.80e-4, 0.2266, 0.3869)


def test_the_limits_lie_where_the_recorded_readings_put_them():
    """Whoever moves a limit of ``reference/block_diffusion_moe_lm.py``
    moves it against what the chip read. The loss limit lies between the
    sound system's largest and the bf16 reference's least with a factor 2
    on either side; the gradient limits stand twice over the sound
    system's largest and refuse a changed equation, and are known not to
    part the precisions: the bf16 reference reads inside the sound
    system's own range there, which this test states so that nobody
    reads the limits as a guard they are not."""
    tol = ROOTS.module("reference", FAMILY).TOLERANCES
    limits = (tol["loss_rtol"], tol["grad_tree_rtol"], tol["grad_leaf_rtol"])
    assert all(limit >= 1.9 * read for limit, read in
               zip(limits, SOUND_LARGEST))
    assert BF16_REFERENCE_LEAST[0] >= 2 * tol["loss_rtol"]
    assert SHIFTED_TARGETS[1] > 10 * tol["grad_tree_rtol"]
    # the gradient norms do not part the precisions here
    assert BF16_REFERENCE_LARGEST[1] < SOUND_LARGEST[1]
    assert BF16_REFERENCE_LARGEST[2] < SOUND_LARGEST[2]
