"""What PR 30 added for the looped cell: its files, the cost function
against a count by hand, the readers of the ``loop_stack`` and
``exit_gate`` scopes on a step and a trace made by hand, on a run whose
program has no such scope (its parent), and the ``loop_lm`` family with
its reference through the harness at a tiny size on the CPU."""

import copy
import json
import time

import pytest

import hlo
import spec
import xplane

ROOTS = spec.Roots()
PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}  # no chip's
CELL = "ouro-hostfill-1chip"


def _op(name, opcode, source, extra=""):
    meta = f', metadata={{op_name="jit(step)/{source}"}}' if source else ""
    return f"  %{name} = f32[4] {opcode}(%a){extra}{meta}\n"


FWD = "loss_and_grad/jvp(M)/loop_stack/"
BWD = "loss_and_grad/transpose(jvp(M))/loop_stack/"
REMAT = "loss_and_grad/transpose(jvp(M))/loop_stack/pass1/block_0/" \
    "checkpoint/rematted_computation/"
HLO = (
    "HloModule jit_step\n\n"
    "ENTRY %main (a: f32[4]) -> f32[4] {\n"
    "  %a = f32[4] parameter(0)\n"
    + _op("emb", "gather", "loss_and_grad/jvp(M)/take")
    + _op("qkv0", "fusion", FWD + "pass0/block_0/qkv/dot_general")
    + _op("norm0", "fusion", FWD + "pass0/RMSNorm_0/mul")
    + _op("qkv1", "fusion", FWD + "pass1/block_0/qkv/dot_general")
    + _op("gate", "fusion", "loss_and_grad/jvp(M)/exit_gate/dot_general")
    + _op("dist", "fusion", "loss_and_grad/jvp(exit_gate)/cumsum")
    + _op("head", "fusion", "loss_and_grad/jvp(lm_head)/dot_general")
    + _op("dhead", "fusion", "loss_and_grad/transpose(jvp(lm_head))/dot")
    + _op("ddist", "fusion", "loss_and_grad/transpose(jvp(exit_gate))/mul")
    + _op("again", "fusion", REMAT + "mul")
    + _op("dqkv1", "fusion", BWD + "pass1/block_0/qkv/dot_general")
    + _op("dqkv0", "fusion", BWD + "pass0/block_0/qkv/dot_general")
    + _op("adam", "add", "optimizer_update/add")
    + "}\n"
)
#: ns a step
_STEP = [("emb", 2), ("qkv0", 20), ("norm0", 3), ("qkv1", 21), ("gate", 4),
         ("dist", 1), ("head", 30), ("dhead", 60), ("ddist", 2),
         ("again", 7), ("dqkv1", 40), ("dqkv0", 41), ("adam", 10)]
STACK_NS = 20 + 3 + 21 + 7 + 40 + 41
GATE_NS = 4 + 1 + 2


def _table():
    ops, t = [], 0
    for _ in range(2):
        for name, dur in _STEP:
            ops.append([name, t, dur])
            t += dur
        t += 9
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "async": [],
        "modules": [["jit_step(1)", 0, 250], ["jit_step(1)", 250, 250]]}},
        "host_spans": [["bench.wait", 0, 50]]}


def _ctx(hlo_text=HLO):
    return {
        "trace": xplane.reduce(_table(), hlo.categorize(hlo_text),
                               "jit_step"),
        "hlo_text": hlo_text, "host_spans": [], "program_metrics": {},
        "cell": {"name": "by-hand", "config_spec": {}, "job": {}},
        "loop": {"mosaic_calls": 0}, "peak": PEAK,
    }


def _read(name, ctx):
    return ROOTS.module("layer_metrics", name).read(ctx)


BY_HAND = {"loop_stack_ms": STACK_NS / 1e6, "exit_gate_ms": GATE_NS / 1e6}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_loop_readers_by_hand(name):
    assert _read(name, _ctx()) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_program_without_the_scopes_reports_none(name):
    """The parent of the PR that named them, or a cell whose model is not
    looped: the reader returns nothing and does not raise; nor without a
    trace."""
    bare = HLO.replace("loop_stack/", "").replace("exit_gate", "gate")
    assert _read(name, _ctx(bare)) is None
    assert _read(name, {**_ctx(), "trace": {}}) is None


def test_the_stack_splits_into_forward_backward_and_recomputed():
    ctx = _ctx()
    assert _read("forward_ms", ctx) == pytest.approx(
        (2 + 20 + 3 + 21 + 4 + 1 + 30) / 1e6)
    assert _read("backward_ms", ctx) == pytest.approx(
        (60 + 2 + 40 + 41) / 1e6)
    assert _read("recompute_ms", ctx) == pytest.approx(7 / 1e6)
    assert _read("head_ms", ctx) == pytest.approx(90 / 1e6)


def test_costs_are_a_count_by_hand():
    import loop_costs

    config = ROOTS.json("configs", "ouro-2.6b.json")
    job = ROOTS.json("workloads", CELL + ".json")["job"]
    fam = ROOTS.module("families", "loop_lm")
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert fam.layer_params(config) == layer == 51_380_224
    head = 49152 * 2048
    # the stack and the head once a pass, attention a layer and pass
    want = 6.0 * (4 * 6 * layer + 4 * head) + 6.0 * 4 * 6 * 4096 * 2048
    assert fam.model_flops_per_sample(config, job) == want
    assert want == pytest.approx(11.02e9, rel=2e-3)
    assert loop_costs.looped_lm_train_flops_per_token(
        10, 7, layers=3, passes=2, seq_len=5, d_model=4) == \
        6 * 2 * (3 * 10 + 7) + 6 * 2 * 3 * 5 * 4
    # one pass of one layer is the dense model's count
    import costs
    assert loop_costs.looped_lm_train_flops_per_token(
        layer, head, layers=1, passes=1, seq_len=4096, d_model=2048) == \
        costs.dense_lm_train_flops_per_token(layer + head, 1, 4096, 2048)
    flash = fam.kernel_costs(config, job)["flash"]
    assert flash[0] == 24 * 3.5 * 2.0 * 1 * 16 * 4096 * 4096 * 128


def test_the_cells_files_load_and_the_model_is_the_issues():
    import jax

    cell = spec.load_cell(ROOTS, CELL)
    assert cell["job"] == {"per_chip_batch": 1, "seq_len": 4096,
                           "remat": "dots", "head_chunks": 16}
    config = cell["config_spec"]
    fam = ROOTS.module("families", config["family"]).build(
        config, cell["job"])
    assert (fam.model.num_layers, fam.model.total_ut_steps,
            fam.model.remat, fam.model.remat_policy) == (6, 4, True, "dots")
    params = jax.eval_shape(fam.init, 1)[0]
    assert sum(k.startswith("block_") for k in params) == 6
    assert sum(x.size for x in jax.tree.leaves(params)) == 509_661_185
    assert fam.pool_args(1) == dict(rows=1, seq_len=4096, vocab_size=49152,
                                    eos_id=0)


def test_the_committed_cell_lists_what_the_issue_names():
    b = spec.load_benchmark()
    assert [w for w in b["workloads"] if w["name"] == CELL][0]["chips"] == 1
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", ())}
    # ISSUE 30's ten and two, and flash's time and roofline share (the
    # step's 72 Mosaic calls are the flash kernels and nothing else)
    assert listed == {
        "tokens_per_s", "device_idle_pct", "forward_ms", "backward_ms",
        "recompute_ms", "optimizer_ms", "head_ms", "flash_fwd_ms",
        "flash_dq_ms", "flash_dkv_ms", "loop_stack_ms", "exit_gate_ms",
        "flash_ms", "flash_roofline_pct"}
    config = ROOTS.json("configs", "ouro-2.6b.json")
    entry = [c for c in b["configs"] if c["name"] == "ouro-2.6b"][0]
    assert entry["source"] == config["source"] == \
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
    # the catalog row's config, every key but the one reduced (48)
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 6
    assert config["reduced"] == entry["reduced"] == ["num_hidden_layers"]


# -- the family and its reference through the harness ----------------------

TINY_LOOP = {
    "source": "throw-away", "family": "loop_lm", "model_type": "ouro",
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 2,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 96,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "rope_scaling": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "vocab_size": 384,
    "max_position_embeddings": 128, "total_ut_steps": 3,
    "eos_token_id": 0, "reduced": [],
    "assumed": {"exit_entropy_beta": 0.1, "check_gate_scale": 0.1,
                "used_token_ids": 300},
    "training": {
        "compute_dtype": "float32", "param_dtype": "float32",
        "attention": "pallas_flash", "head": "fused_chunked",
        "optimizer": {"name": "adamw", "learning_rate": 3e-4, "b1": 0.9,
                      "b2": 0.95, "weight_decay": 0.1},
        "allreduce_grad_dtype": "bfloat16"},
}
TINY_CELL = {"config": "tiny-loop", "traffic": "tiny-loop-mix", "chips": 1,
             "why": "x", "job": {"per_chip_batch": 2, "remat": "dots",
                                 "head_chunks": 2}}
TINY_MIX = {
    "what": "throw-away", "loop": "train", "feed": {"depth": 2},
    "warmup_steps": 2,
    "samples": {"tokens": {"pool_batches": 4, "doc_len_median": 40,
                           "doc_len_sigma": 1.0, "zipf_exponent": 1.0}},
}


@pytest.fixture(scope="module")
def added_loop(tmp_path_factory):
    """A root with a tiny looped configuration, mix and cell beside the
    benchmark's own, and ``BENCHMARK.json`` with their entries appended
    (the new cell on every list the committed looped cell is on)."""
    root = tmp_path_factory.mktemp("added_loop")
    for rel, body in (("configs/tiny-loop.json", TINY_LOOP),
                      ("traffic/tiny-loop-mix.json", TINY_MIX),
                      ("workloads/tiny-loop.json", TINY_CELL)):
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(body))
    new = copy.deepcopy(spec.load_benchmark())
    new["configs"].append({"name": "tiny-loop", "source": "throw-away",
                           "file": "benchmark/configs/tiny-loop.json",
                           "reduced": [], "why": "x"})
    new["workloads"].append({"name": "tiny-loop", **{
        k: TINY_CELL[k] for k in ("config", "traffic", "chips", "why")}})
    for m in new["end_to_end"] + new["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + ["tiny-loop"]
    return spec.Roots((str(root),)), new


def test_tiny_loop_cell_end_to_end_traced(added_loop):
    import jax

    import run

    roots, benchmark = added_loop
    line = run.run_cell("tiny-loop", seed=3_030_000_101, seconds=0.5,
                        trace=True, devices=jax.devices()[:1], peak=PEAK,
                        roots=roots, benchmark=benchmark,
                        t0=time.perf_counter())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 2
    got = set(line["metrics"])
    assert {"compile_s", "peak_hbm_gb", "input_wait_ms"} <= got
    # no device plane in a CPU trace: the scope readers have nothing
    assert not got & set(BY_HAND)


def test_the_comparison_runs_on_the_trained_tree_but_for_the_gates_kernel():
    """``check_params`` (checks (a) and (c)) shares every array with the
    parameters the cell trains; the exit gate's kernel alone is multiplied
    by ``assumed.check_gate_scale``."""
    import jax
    import numpy as np

    fam = ROOTS.module("families", "loop_lm").build(
        TINY_LOOP, {**TINY_CELL["job"], "seq_len": 32})
    params, state, check = fam.init(7)
    assert state == ()
    flat, flat_check = (dict(jax.tree_util.tree_flatten_with_path(t)[0])
                        for t in (params, check))
    differ = [jax.tree_util.keystr(k) for k in flat
              if flat[k] is not flat_check[k]]
    assert differ == ["['exit_gate']['kernel']"]
    np.testing.assert_allclose(
        check["exit_gate"]["kernel"], 0.1 * params["exit_gate"]["kernel"])
    assert ROOTS.json("configs", "ouro-2.6b.json")["assumed"][
        "check_gate_scale"] == 0.1


def test_the_reference_computed_in_bf16_strays_at_the_tiny_size():
    """The control in the precision below (``loss(dtype=bfloat16)``) runs
    in bf16 and strays from the float32 reference a hundred times further
    than the float32 system does. Whether the limits refuse it is a
    question of the published widths (the recorded readings below): a
    64-wide model's bf16 noise says nothing."""
    import jax
    import jax.numpy as jnp

    import correct

    ref = ROOTS.module("reference", "loop_lm")
    fam = ROOTS.module("families", "loop_lm").build(
        TINY_LOOP, {**TINY_CELL["job"], "seq_len": 32})
    params, _, _ = fam.init(7)
    tokens = jax.random.randint(jax.random.key(8), (2, 32), 0, 300)
    want, want_g = jax.value_and_grad(
        lambda p: ref.loss(p, (), tokens, TINY_LOOP))(params)
    got, got_g = jax.value_and_grad(lambda p: ref.loss(
        p, (), tokens, TINY_LOOP, dtype=jnp.bfloat16))(params)
    assert got.dtype == jnp.bfloat16
    sys_g = jax.grad(lambda p: fam.loss_fn(p, tokens)[0])(params)
    low = correct.compare_grads("x", got_g, want_g, ref.TOLERANCES)
    sound = correct.compare_grads("x", sys_g, want_g, ref.TOLERANCES)
    assert sound["ok"] and sound["tree_rel_err"] < 1e-4
    assert low["tree_rel_err"] > 100 * sound["tree_rel_err"]


#: (loss, whole gradient, worst leaf) read on the v5e at the published
#: widths on the family's ``check_params`` (my chip runs, PR 30, call 5;
#: PERF.md section 6 has the seeds): the largest of each over the sound
#: system's 30 seeds, and for every control the seed on which its whole
#: gradient read least
SOUND_LARGEST = (1.72e-4, 0.01906, 0.06141)
CONTROLS = {
    "reference_computed_in_bf16": (8.51e-4, 0.02542, 0.15840),
    "gates_gradient_stopped": (1.06e-4, 0.03798, 0.33476),
    "three_passes_for_four": (1.50e-3, 0.22385, 1.27996),
    "norm_between_passes_left_out": (1.74e-4, 0.62860, 0.71063),
    "entropy_term_left_out": (1.10e-2, 0.05348, 0.92816),
    "norm_after_a_sublayer_left_out": (2.38e-3, 0.76233, 3.50656),
}
#: the bf16 reference's least loss and least worst leaf over its 30 seeds:
#: under the sound system's largest, so neither limit can hold the
#: precision (said in reference/loop_lm.py); the whole gradient does
BF16_LEAST = (7.8e-6, 0.02542, 0.05707)


def _refused(reading):
    tol = ROOTS.module("reference", "loop_lm").TOLERANCES
    return [r > tol[k] for r, k in zip(
        reading, ("loss_rtol", "grad_tree_rtol", "grad_leaf_rtol"))]


@pytest.mark.parametrize("name", ["sound"] + sorted(CONTROLS))
def test_the_limits_lie_between_the_recorded_readings(name):
    """Whoever moves a limit of ``reference/loop_lm.py`` moves it between
    what the chip read for the sound system and for the controls: every
    control is refused by the whole gradient alone, on its best seed."""
    tol = ROOTS.module("reference", "loop_lm").TOLERANCES
    if name == "sound":
        assert not any(_refused(SOUND_LARGEST))
        # and with room: a fresh seed reads higher
        assert tol["grad_tree_rtol"] >= 1.15 * SOUND_LARGEST[1]
        assert tol["grad_leaf_rtol"] >= 1.9 * SOUND_LARGEST[2]
        assert tol["loss_rtol"] >= 3 * SOUND_LARGEST[0]
    else:
        assert _refused(CONTROLS[name])[1]
        assert CONTROLS[name][1] >= 1.15 * tol["grad_tree_rtol"]


def test_the_precision_is_held_by_the_whole_gradient_alone():
    """The sound system's largest loss and worst leaf lie above the bf16
    reference's least: a limit on either that lets the sound system pass
    lets that seed of the control pass too. Not so the whole gradient."""
    assert BF16_LEAST[0] < SOUND_LARGEST[0]
    assert BF16_LEAST[2] < SOUND_LARGEST[2]
    assert BF16_LEAST[1] > SOUND_LARGEST[1]
    assert _refused(BF16_LEAST) == [False, True, False]
