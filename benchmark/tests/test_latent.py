"""What PR 47 added for the DeepSeek-V2-Lite cell: its files, the cost
functions against a count by hand, the readers of the ``mla_attention``
and ``moe_shared`` scopes on a step and a trace made by hand and on a run
whose program has no such scope (its parent), and the ``latent_moe_lm``
family with its reference through the harness at a tiny size on the
CPU."""

import copy
import itertools
import json
import os
import time

import pytest

import hlo
import spec
import xplane

ROOTS = spec.Roots()
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}  # no chip's
CELL = "dsv2lite-hostfill-1chip"
CONFIG = "deepseek-v2-lite"
NEW_METRICS = ("mla_attention_ms", "mla_flash_roofline_pct", "moe_shared_ms")


def _op(name, source, custom=False):
    if custom:
        return (f"  %{name} = f32[4] custom-call(%a), "
                f'custom_call_target="tpu_custom_call", '
                f'metadata={{op_name="jit(step)/{source}"}}\n')
    return (f"  %{name} = f32[4] fusion(%a), kind=kLoop, calls=%f, "
            f'metadata={{op_name="jit(step)/{source}"}}\n')


FWD = "loss_and_grad/jvp(M)/block_1/"
BWD = "loss_and_grad/transpose(jvp(M))/block_1/"
MLA = "block_1._latent_attention/mla_attention/"
HLO = (
    "HloModule jit_step\n\n"
    "ENTRY %main (a: f32[4]) -> f32[4] {\n"
    "  %a = f32[4] parameter(0)\n"
    + _op("q_proj", FWD + MLA + "q_proj/dot_general")
    + _op("rope", FWD + MLA + "concatenate")
    + _op("fwd_k", FWD + MLA + "jit(_flash_core)/flash_fwd/flash_fwd/"
          "pallas_call", custom=True)
    + _op("dq_k", BWD + MLA + "jit(_flash_core)/flash_bwd_dq/flash_bwd_dq/"
          "pallas_call", custom=True)
    + _op("dproj", BWD + MLA + "proj/dot_general")
    + _op("shared_up", FWD + "moe_shared/shared_gate_up/dot_general")
    + _op("dshared", BWD + "moe_shared/mul")
    + _op("gmm", FWD + "moe_experts/pallas_call", custom=True)
    + _op("adam", "optimizer_update/add")
    + "}\n"
)
_STEP = [("q_proj", 30), ("rope", 4), ("fwd_k", 20), ("dq_k", 30),
         ("dproj", 16), ("shared_up", 12), ("dshared", 3), ("gmm", 9),
         ("adam", 10)]
MLA_NS, MLA_MOSAIC_NS, SHARED_NS = 30 + 4 + 20 + 30 + 16, 20 + 30, 12 + 3
STEP_NS = sum(d for _, d in _STEP)


def _table():
    ops, t = [], 0
    for _ in range(2):
        for name, dur in _STEP:
            ops.append([name, t, dur])
            t += dur
        t += 5
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "async": [],
        "modules": [["jit_step(1)", 0, STEP_NS + 5],
                    ["jit_step(1)", STEP_NS + 5, STEP_NS + 5]]}},
        "host_spans": [["bench.wait", 0, 50]]}


def _ctx(hlo_text=HLO):
    cell = spec.load_cell(ROOTS, CELL)
    return {
        "trace": xplane.reduce(_table(), hlo.categorize(hlo_text),
                               "jit_step"),
        "hlo_text": hlo_text, "host_spans": [], "program_metrics": {},
        "cell": cell, "loop": {"mosaic_calls": 3}, "peak": PEAK,
        "family": ROOTS.module("families", "latent_moe_lm"),
    }


def _read(name, ctx):
    return ROOTS.module("layer_metrics", name).read(ctx)


def test_the_new_readers_by_hand():
    ctx = _ctx()
    assert _read("mla_attention_ms", ctx) == pytest.approx(MLA_NS / 1e6)
    assert _read("moe_shared_ms", ctx) == pytest.approx(SHARED_NS / 1e6)
    # six layers of 16 heads over half of 8192^2 pairs, 2 (192 + 128)
    # flops a pair forward and 2 (3 x 192 + 2 x 128) backward, at 1e12
    flops = 6 * 16 * 8192 * 8192 / 2 * 2 * (320 + 832)
    assert _read("mla_flash_roofline_pct", ctx) == pytest.approx(
        100 * flops / 1e12 * 1e3 / (MLA_MOSAIC_NS / 1e6))
    # the experts' kernel lies under another scope and is not counted
    assert _read("moe_experts_ms", ctx) == pytest.approx(9 / 1e6)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_reports_none(name):
    """The parent of the PR that named them, or a cell of another family:
    the reader returns nothing and does not raise; nor without a trace."""
    bare = HLO.replace("mla_attention/", "attn/").replace(
        "moe_shared/", "ffn/")
    assert _read(name, _ctx(bare)) is None
    assert _read(name, {**_ctx(), "trace": {}}) is None


def test_costs_are_a_count_by_hand():
    import mla_costs

    config = ROOTS.json("configs", CONFIG + ".json")
    job = ROOTS.json("workloads", CELL + ".json")["job"]
    fam = ROOTS.module("families", "latent_moe_lm")
    d = 2048
    attn = d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d
    assert attn == 13_762_560
    # 6 x 8 / 64 routed experts a token in expectation, the shared whole
    expert_layer = d * 64 + 3 * d * 2816 + 0.75 * 3 * d * 1408
    active = 6 * attn + 3 * d * 10944 + 5 * expert_layer + d * 12800
    assert fam.n_active_params(config) == active
    assert active == pytest.approx(295.6e6, rel=1e-3)
    per_pair = 2 * (320 + 3 * 192 + 2 * 128)
    attention = 16 * 8192 * 8192 / 2 * per_pair
    want = 6.0 * active + 6 * attention / 8192
    assert fam.model_flops_per_sample(config, job) == pytest.approx(
        want, rel=1e-12)
    assert want == pytest.approx(2.68e9, rel=2e-3)
    costs = fam.kernel_costs(config, job)
    rows = 8192 * 2
    nbytes = 3 * rows * 16 * 192 + 3 * rows * (16 * 128 + 64) \
        + 6 * rows * 16 * 128
    assert costs["mla_flash"] == (6 * attention, 6 * nbytes)
    assert mla_costs.mla_attention_train_cost(1, 16, 8192, 128, 64, 128) \
        == (attention, nbytes)
    # at one width it is costs.py's count: 3.5 x the forward
    assert mla_costs.mla_attention_train_cost(2, 4, 64, 24, 8, 32)[0] == \
        __import__("costs").causal_attention_train_cost(2, 4, 64, 32)[0]
    import moe_costs
    one = moe_costs.gated_experts_train_cost(6144, 8, d, 1408)
    assert costs["moe_gmm"] == (5 * one[0], 5 * one[1])
    assert set(costs) == {"mla_flash", "moe_gmm"}


def test_the_cells_files_load_and_the_model_is_the_issues():
    import jax

    cell = spec.load_cell(ROOTS, CELL)
    assert cell["job"] == {"per_chip_batch": 1, "seq_len": 8192,
                           "remat": "dots", "head_chunks": 8}
    config = cell["config_spec"]
    fam = ROOTS.module("families", config["family"]).build(
        config, cell["job"])
    arch = fam.model.arch
    assert arch.layers == (("latent_attention", "dense"),) \
        + (("latent_attention", "experts"),) * 5
    assert (arch.n_experts, arch.experts_held, arch.experts_per_token,
            arch.router_score, arch.renormalise_gates,
            arch.shared_expert_width, arch.seq_aux) == (
        64, (0, 8), 6, "softmax", False, 2816, True)
    assert (arch.latent_rank, arch.qk_nope_dim, arch.qk_rope_dim,
            arch.v_head_dim) == (512, 128, 64, 128)
    assert arch.rope_scaling.correction_range(64, 10000.0) == (10, 23)
    # the shapes alone (of the drawn tree: the choice of the held experts
    # moves a router's columns and no shape)
    drawn = ROOTS.module("families", "moe_lm").Family.init
    params, state, _ = jax.eval_shape(lambda seed: drawn(fam, seed), 1)
    assert state == ()
    assert sum(x.size for x in jax.tree.leaves(params)) == 635_466_752
    block = params["block_1"]
    assert block["moe_w_gate_up"].shape == (8, 2048, 2816)
    assert block["moe_router"].shape == (2048, 64)
    assert block["shared_gate_up"]["kernel"].shape == (2048, 5632)
    assert block["q_proj"]["kernel"].shape == (2048, 3072)
    assert block["kv_a"]["kernel"].shape == (2048, 576)
    assert block["kv_b"]["kernel"].shape == (512, 4096)
    assert block["proj"]["kernel"].shape == (2048, 2048)
    assert params["block_0"]["ff_up"]["kernel"].shape == (2048, 10944)
    assert params["lm_head"]["embedding"].shape == (12800, 2048)
    assert fam.pool_args(1) == dict(rows=1, seq_len=8192, vocab_size=12800,
                                    eos_id=1)


def test_the_committed_cell_lists_what_the_issue_names():
    b = spec.load_benchmark()
    entry = [w for w in b["workloads"] if w["name"] == CELL][0]
    assert (entry["chips"], entry["traffic"], entry["config"]) == (
        1, "hostfill", CONFIG)
    assert entry["why"] == ROOTS.json("workloads", CELL + ".json")["why"]
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == {
        "tokens_per_s", "device_idle_pct", "forward_ms", "backward_ms",
        "recompute_ms", "optimizer_ms", "head_ms", "flash_fwd_ms",
        "flash_dq_ms", "flash_dkv_ms", "moe_experts_ms", "moe_dispatch_ms",
        "moe_gmm_roofline_pct", *NEW_METRICS}
    for m in b["per_layer"]:
        if m["name"] in NEW_METRICS:
            mod = ROOTS.module("layer_metrics", m["name"])
            assert (m["layer"], m["unit"], m["better"], m["source"],
                    m["moves"], m["workloads"]) == (
                mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES,
                [CELL])
    assert len(b["workloads"]) >= 9  # later PRs add cells
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    config = ROOTS.json("configs", CONFIG + ".json")
    centry = [c for c in b["configs"] if c["name"] == CONFIG][0]
    assert centry["source"] == config["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
        "config.json")
    # the catalog row's config: every key but the three reduced
    from chainermn_tpu.models import MODEL_CONFIGS
    published = MODEL_CONFIGS[CONFIG]
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert {k: config[k] for k in published if k not in reduced} == {
        k: v for k, v in published.items() if k not in reduced}
    assert config["reduced"] == centry["reduced"] == reduced
    assert config["published"] == {k: published[k] for k in reduced} == {
        "num_hidden_layers": 27, "n_routed_experts": 64,
        "vocab_size": 102400}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["experts_published"], config["experts_held_range"],
            config["vocab_size"]) == (6, 8, 64, [0, 8], 12800)
    assert "eight chips share each layer" in config["reduced_why"]
    assert {"equations", "assumed", "departures", "deployment"} <= set(
        config)
    assert config["assumed"]["aux_loss_alpha"] == 0.001


# -- the family and its reference through the harness ----------------------

TINY_LATENT = {
    "source": "throw-away", "family": "latent_moe_lm",
    "model_type": "deepseek_v2", "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": None,
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 96, "moe_intermediate_size": 16,
    "n_routed_experts": 4, "experts_published": 8,
    "experts_held_range": [2, 6], "n_shared_experts": 2,
    "num_experts_per_tok": 3, "norm_topk_prob": False,
    "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
    "topk_group": 1, "routed_scaling_factor": 1, "seq_aux": True,
    "hidden_act": "silu", "attention_bias": False, "rms_norm_eps": 1e-6,
    "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 40, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16},
    "tie_word_embeddings": False, "vocab_size": 384,
    "max_position_embeddings": 128, "eos_token_id": 7, "reduced": [],
    "assumed": {"aux_loss_alpha": 0.001, "used_token_ids": 300,
                "check_router_scale": 0.05, "balance_batches": 3,
                "balance_sample": {"pool_batches": 4, "doc_len_median": 40,
                                   "doc_len_sigma": 1.0,
                                   "zipf_exponent": 1.0}},
    "training": {
        "compute_dtype": "float32", "param_dtype": "float32",
        "attention": "pallas_flash", "head": "fused_chunked",
        "experts": "dropless_grouped_matmul",
        "optimizer": {"name": "adamw", "learning_rate": 3e-4,
                      "warmup_steps": 100, "b1": 0.9, "b2": 0.95,
                      "weight_decay": 0.1},
        "allreduce_grad_dtype": "bfloat16"},
}
TINY_CELL = {"config": "tiny-latent", "traffic": "tiny-latent-mix",
             "chips": 1, "why": "x",
             "job": {"per_chip_batch": 2, "remat": "none",
                     "head_chunks": 2}}
TINY_MIX = {
    "what": "throw-away", "loop": "train", "feed": {"depth": 2},
    "warmup_steps": 2,
    "samples": {"tokens": {"pool_batches": 4, "doc_len_median": 40,
                           "doc_len_sigma": 1.0, "zipf_exponent": 1.0}},
}


def added_root(root):
    """``(roots, benchmark)`` with a tiny latent configuration, mix and
    cell written under ``root`` beside the benchmark's own, and
    ``BENCHMARK.json``'s content with their entries appended (the new cell
    on every list the committed cell is on)."""
    for rel, body in (("configs/tiny-latent.json", TINY_LATENT),
                      ("traffic/tiny-latent-mix.json", TINY_MIX),
                      ("workloads/tiny-latent.json", TINY_CELL)):
        path = os.path.join(root, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            json.dump(body, f)
    new = copy.deepcopy(spec.load_benchmark())
    new["configs"].append({"name": "tiny-latent", "source": "throw-away",
                           "file": "benchmark/configs/tiny-latent.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": "tiny-latent", **{
        k: TINY_CELL[k] for k in ("config", "traffic", "chips", "why")}})
    for m in new["end_to_end"] + new["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["tiny-latent"]
    return spec.Roots((str(root),)), new


@pytest.fixture(scope="module")
def added_latent(tmp_path_factory):
    return added_root(str(tmp_path_factory.mktemp("added_latent")))


@pytest.mark.parametrize("remat", ["none", "dots"])
def test_tiny_latent_cell_end_to_end_traced(added_latent, remat,
                                            monkeypatch):
    import jax

    import run

    roots, benchmark = added_latent
    cell = roots.json("workloads", "tiny-latent.json")
    monkeypatch.setattr(
        spec, "load_cell", lambda r, n, real=spec.load_cell: {
            **real(r, n), "job": {**cell["job"], "remat": remat}})
    line = run.run_cell("tiny-latent", seed=4_747_000_101, seconds=0.5,
                        trace=True, devices=jax.devices()[:1], peak=PEAK,
                        roots=roots, benchmark=benchmark,
                        t0=time.perf_counter())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    got = set(line["metrics"])
    assert {"compile_s", "peak_hbm_gb", "input_wait_ms"} <= got
    # no device plane in a CPU trace: the scope readers have nothing
    assert not got & set(NEW_METRICS)


def test_a_dropped_row_is_nan_and_the_balance_loss_is_in_the_loss():
    """A row routed to a held expert that lies in no group leaves the step
    no finite loss; the loss carries ``aux_loss_alpha`` x the
    per-sequence balance loss."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.parallel import moe

    job = {**TINY_CELL["job"], "seq_len": 32}
    mod = ROOTS.module("families", "latent_moe_lm")
    fam = mod.build(TINY_LATENT, job)
    params, state, _ = fam.init(7)
    assert state == ()
    tokens = jax.random.randint(jax.random.key(8), (2, 32), 0, 300)
    loss, metrics = fam.loss_fn(params, tokens)
    assert np.isfinite(float(loss))
    assert float(metrics["moe/dropped"]) == 0.0
    assert 0 < float(metrics["moe/rows_held"]) < 2 * 2 * 32 * 3
    without = mod.build({**TINY_LATENT, "assumed": {
        **TINY_LATENT["assumed"], "aux_loss_alpha": 0.0}}, job)
    assert float(loss) - float(without.loss_fn(params, tokens)[0]) == \
        pytest.approx(0.001 * float(metrics["moe/seq_aux"]), rel=1e-3)

    real = moe.dropless_topk

    def loses_a_row(*a, **kw):
        r = real(*a, **kw)
        last = jnp.argmax(r.group_sizes > 0)
        return r._replace(group_sizes=r.group_sizes.at[last].add(-1))

    moe.dropless_topk = loses_a_row
    try:
        loss, metrics = fam.loss_fn(params, tokens)
    finally:
        moe.dropless_topk = real
    assert float(metrics["moe/dropped"]) == 2.0  # one a layer
    assert np.isnan(float(loss))


def test_the_chip_holds_the_experts_a_balanced_router_would_give_it():
    """The cell trains the program's own initialisation but that each
    router's columns are so ordered that the held range is the experts
    a balanced router would give it (together ``held / experts`` of the
    rows, each near ``tokens x k / experts``) on the first batches of the
    run's own pool, a layer at a time; check (a) runs on the same arrays
    but that every router's kernel is at ``assumed.check_router_scale``."""
    import jax
    import numpy as np

    job = {**TINY_CELL["job"], "seq_len": 32}
    fam = ROOTS.module("families", "latent_moe_lm").build(TINY_LATENT, job)
    drawn = ROOTS.module("families", "moe_lm").Family.init(fam, 3)[0]
    params, state, check = fam.init(3)
    assert state == ()
    # what the loop trains on: three batches of two rows (five rows a
    # call of expert_loads would do as well as four and two)
    pool = ROOTS.module("traffic", "gen_tokens").pool(
        3, TINY_MIX["samples"]["tokens"], **fam.pool_args(2))
    assert TINY_LATENT["assumed"]["balance_sample"] == \
        TINY_MIX["samples"]["tokens"]
    rows = np.concatenate(pool[:3])

    def loads_of(tree):
        return tuple(np.asarray(x) for x in fam.expert_loads(tree, rows))

    loads, counted = loads_of(params)
    assert loads.shape == (2, 8) and loads.sum(1).tolist() == [576, 576]
    assert (loads[:, 2:6] == counted).all()
    # no four of a layer's eight come nearer four balanced loads of 72
    # rows together (at the cell's sizes: of the 24 nearest the mean)
    for layer in loads:
        assert abs(layer[2:6].sum() - 288) == min(
            abs(sum(four) - 288)
            for four in itertools.combinations(layer.tolist(), 4))
    was = loads_of(drawn)[0]
    assert np.abs(loads[:, 2:6].sum(1) - 288).sum() \
        < np.abs(was[:, 2:6].sum(1) - 288).sum()
    routers = 0
    for name, block in params.items():
        for leaf, value in block.items():
            if leaf == "moe_router":
                routers += 1
                # the drawn columns, each once, in another order
                a, b = np.asarray(value), np.asarray(drawn[name][leaf])
                assert sorted(map(tuple, a.T)) == sorted(map(tuple, b.T))
                np.testing.assert_allclose(
                    np.asarray(check[name][leaf]), 0.05 * a, rtol=1e-6)
            else:
                assert jax.tree.all(jax.tree.map(
                    lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()),
                    value, drawn[name][leaf]))
                assert jax.tree.all(jax.tree.map(
                    lambda a, b: a is b, check[name][leaf], value))
    assert routers == 2


@pytest.mark.parametrize("seed", range(4))
def test_balanced_gives_a_chip_its_share_of_skewed_loads(seed):
    """Loads as uneven as an untrained router deals them (lognormal, a
    factor e apart): the eight chosen of 64 sum to an eighth within a
    thousandth, and each lies among the 24 nearest the mean."""
    import numpy as np

    rng = np.random.default_rng(seed)
    load = (rng.lognormal(0.0, 1.0, 64) * 1e4).astype(np.int64)
    held = ROOTS.module("families", "latent_moe_lm").balanced(load, 8)
    assert held.shape == (8,) and (np.diff(held) > 0).all()
    assert abs(load[held].sum() / (8 * load.mean()) - 1) < 1e-3
    near = np.argsort(np.abs(load - load.mean()), kind="stable")[:24]
    assert set(held) <= set(near)


def test_the_balance_sample_is_the_cells_traffic():
    """The family is not handed the mix, so the configuration carries
    hostfill's token parameters: the pool the family draws from the seed
    is the run's, batch for batch."""
    config = ROOTS.json("configs", CONFIG + ".json")
    cell = spec.load_cell(ROOTS, CELL)
    assert config["assumed"]["balance_sample"] == \
        cell["mix"]["samples"]["tokens"]
    assert cell["chips"] == 1  # the family draws one chip's pool
    # the warm-up's batches and a window's: 3 + 1 + 29 to 31 steps
    assert 36 == config["assumed"]["balance_batches"] <= \
        config["assumed"]["balance_sample"]["pool_batches"]


#: (loss, whole gradient, worst leaf) read on the v5e at the published
#: widths (my chip runs, PR 47; PERF.md section 6 has the seeds): the
#: largest of each over the sound system's readings (the loss over 126 of
#: checks (a) and (b); the gradient over check (a)'s 63 on ``check_params``
#: at router scales 0.2 to 0.005, where it reads alike), the least of each
#: over the reference computed in bf16's 17 of check (a) at
#: ``check_router_scale`` 0.01, and each changed equation's there
SOUND_LARGEST = (2.80e-4, 0.01651, 0.02939)
BF16_REFERENCE_LEAST = (9.41e-5, 0.01569, 0.09419)
EQUATIONS = {
    "mscale_squared_left_out": (4.34e-3, 0.7643, 0.9685),
    "rope_key_not_rotated": (3.83e-4, 0.3417, 0.6882),
    "latent_norm_skipped": (2.30e-4, 0.1272, 1.0),
    "shared_expert_left_out": (4.14e-3, 0.9255, 1.2998),
}


@pytest.mark.parametrize("name", ["sound", "bf16"] + sorted(EQUATIONS))
def test_the_limits_lie_between_the_recorded_readings(name):
    """Whoever moves a limit of ``reference/latent_moe_lm.py`` moves it
    between what the chip read: the worst leaf between the sound system
    and the reference computed in bf16 on ``check_params`` (the limit that
    refuses the lower precision), the whole gradient between the sound
    system and the changed equations, the loss over the sound system's
    largest."""
    tol = ROOTS.module("reference", "latent_moe_lm").TOLERANCES
    limits = (tol["loss_rtol"], tol["grad_tree_rtol"], tol["grad_leaf_rtol"])
    if name == "sound":
        assert all(limit >= 1.5 * r for limit, r in
                   zip(limits, SOUND_LARGEST))
    elif name == "bf16":
        assert BF16_REFERENCE_LEAST[2] >= 1.5 * limits[2]
        # said plainly: the whole gradient does not refuse it
        assert BF16_REFERENCE_LEAST[1] < limits[1]
        assert ROOTS.json("configs", CONFIG + ".json")["assumed"][
            "check_router_scale"] == 0.01
    else:
        _, tree, leaf = EQUATIONS[name]
        assert tree >= 4 * limits[1] and leaf >= 4 * limits[2]
