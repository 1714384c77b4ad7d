"""The readers of what the program names from the inside (``scopes.py`` and
the per-layer metrics over it): on a step and a trace made by hand, whose
answers can be worked by hand; on a run with nothing named (the parent of
the PR that brought the names); through the harness at a tiny size; and on
the tables cut from traced runs of ``gpt2m-podshare-1chip`` and
``gpt2m-podshare-dp4`` on the v5e with the names in the program
(``data/*.scopes.table.json.gz``, cut by ``scope_table.py``), whose numbers
are pinned."""

import os
import time

import pytest

import hlo
import scope_table
import scopes
import spec
import xplane

ROOTS = spec.Roots()


def _op(name, opcode, source, operands="%a", extra=""):
    meta = f', metadata={{op_name="jit(step)/{source}"}}' if source else ""
    return f"  %{name} = f32[4] {opcode}({operands}){extra}{meta}\n"


MOSAIC = ', custom_call_target="tpu_custom_call"'
#: one instruction of each kind the readers tell apart
HLO = (
    "HloModule jit_step\n\n"
    "%fused_mm (p: f32[4]) -> f32[4] {\n"
    "  %p = f32[4] parameter(0)\n"
    + _op("d", "dot", "loss_and_grad/jvp(M)/dot_general", "%p, %p")
    + "}\n\n"
    "ENTRY %main (a: f32[4]) -> f32[4] {\n"
    "  %a = f32[4] parameter(0)\n"
    + _op("fwd", "fusion", "loss_and_grad/jvp(M)/block_0/dot_general",
          extra=", kind=kOutput, calls=%fused_mm")
    + _op("ffwd", "custom-call",
          "loss_and_grad/jvp(M)/block_0/flash_fwd/flash_fwd/pallas_call",
          extra=MOSAIC)
    + _op("head", "add", "loss_and_grad/jvp(lm_head)/while/body/add")
    + _op("headb", "add", "loss_and_grad/transpose(jvp(lm_head))/while/mul")
    + _op("remat", "custom-call",
          "loss_and_grad/transpose(jvp(M))/checkpoint/rematted_computation"
          "/block_0/flash_fwd/flash_fwd/pallas_call", extra=MOSAIC)
    + _op("dq", "custom-call",
          "loss_and_grad/transpose(jvp(M))/block_0/flash_bwd_dq/"
          "flash_bwd_dq/pallas_call", extra=MOSAIC)
    + _op("dkv", "custom-call",
          "loss_and_grad/transpose(jvp(M))/block_0/flash_bwd_dkv/"
          "flash_bwd_dkv/pallas_call", extra=MOSAIC)
    + _op("bwd", "multiply", "loss_and_grad/transpose(jvp(M))/block_0/mul")
    + _op("cast", "convert", "grad_reduce/convert_element_type")
    + _op("ar", "all-reduce", "grad_reduce/psum")
    + _op("adam", "add", "optimizer_update/add")
    + _op("copy.1", "copy", "")
    + "}\n"
)
#: two whole steps of 100 ns on one device; per step: fwd 10, ffwd 8,
#: head 4, headb 6, remat 8, dq 9, dkv 11, bwd 20, cast 3, ar 5, adam 12,
#: copy 2 (98 busy, 2 idle)
_STEP = [("fwd", 10), ("ffwd", 8), ("head", 4), ("headb", 6), ("remat", 8),
         ("dq", 9), ("dkv", 11), ("bwd", 20), ("cast", 3), ("ar", 5),
         ("adam", 12), ("copy.1", 2)]


def _table():
    ops, t = [], 0
    for _ in range(2):
        for name, dur in _STEP:
            ops.append([name, t, dur])
            t += dur
        t += 2
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "async": [],
        "modules": [["jit_step(1)", 0, 100], ["jit_step(1)", 100, 100]]}},
        "host_spans": [["bench.wait", 0, 50]]}


def _registry(**values):
    return {name: {"type": "counter", "help": "",
                   "values": [{"labels": {}, "value": v}]}
            for name, v in values.items()}


def _ctx(hlo_text=HLO, **program):
    return {
        "trace": xplane.reduce(_table(), hlo.categorize(hlo_text),
                               "jit_step"),
        "hlo_text": hlo_text, "host_spans": [["bench.wait", 0, 50]],
        "program_metrics": _registry(**program), "program_spans": [],
        "cell": {"name": "made-by-hand"}, "loop": {"mosaic_calls": 4},
    }


def _read(name, ctx):
    return ROOTS.module("layer_metrics", name).read(ctx)


BY_HAND_NS = {
    "forward_ms": 10 + 8 + 4,            # fwd, ffwd, head
    "backward_ms": 6 + 9 + 11 + 20,      # headb, dq, dkv, bwd
    "recompute_ms": 8,
    "optimizer_ms": 12,
    "head_ms": 4 + 6,
    "flash_fwd_ms": 8 + 8,               # the recomputed one counts again
    "flash_dq_ms": 9,
    "flash_dkv_ms": 11,
    "grad_pack_ms": 3,                   # the cast, not the all-reduce
}


@pytest.mark.parametrize("name", sorted(BY_HAND_NS))
def test_scope_readers_by_hand(name):
    assert _read(name, _ctx()) == pytest.approx(BY_HAND_NS[name] / 1e6)


def test_the_scopes_cover_the_step_but_for_xlas_own_copies():
    ctx = _ctx()
    named = sum(scopes.scope_ms(ctx, (s,)) for s in (
        scopes.LOSS_AND_GRAD, scopes.GRAD_REDUCE, scopes.OPTIMIZER_UPDATE))
    busy = xplane.per_step_ms(ctx["trace"], lambda r: r["busy_ns"])
    assert busy - named == pytest.approx(2 / 1e6)  # copy.1 has no op_name
    # the three kernels add up to what flash_ms sums by category
    assert sum(_read(n, ctx) for n in (
        "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms")) == \
        pytest.approx(_read("flash_ms", ctx))


def test_a_scope_fused_into_another_ops_reads_zero_not_nothing():
    """Where XLA fuses a scope's instructions into ops it files under
    another name, the scope is in the step and takes no time of its own."""
    text = HLO.replace(
        _op("cast", "convert", "grad_reduce/convert_element_type"),
        _op("cast", "convert", "optimizer_update/mul")).replace(
        _op("ar", "all-reduce", "grad_reduce/psum"),
        _op("ar", "add", "optimizer_update/add")).replace(
        _op("d", "dot", "loss_and_grad/jvp(M)/dot_general", "%p, %p"),
        _op("d", "dot", "grad_reduce/convert_element_type", "%p, %p"))
    assert _read("grad_pack_ms", _ctx(text)) == 0.0


def test_a_run_with_nothing_named_reports_none_of_them():
    """The parent of the PR that named the program: no scope in the step,
    no counter in the registry, no span in the trace."""
    import re

    bare = re.sub(r', metadata=\{[^}]*\}', "", HLO)
    ctx = _ctx(bare)
    for name in list(BY_HAND_NS) + [
            "wire_mb", "allreduce_gbps", "trace_lower_s",
            "backend_compile_s", "programs_compiled", "feed_put_ms",
            "feed_not_ready_pct"]:
        assert _read(name, ctx) is None, name
    # and with no device trace at all (a CPU run) the trace's readers
    # have nothing either
    ctx = {**_ctx(), "trace": {}}
    for name in BY_HAND_NS:
        assert _read(name, ctx) is None, name


def test_counter_readers_by_hand():
    ctx = _ctx(grad_wire_bytes_per_step=709_545_984.0,
               jax_trace_seconds_total=20.5, jax_lower_seconds_total=4.25,
               jax_backend_compile_seconds_total=7.5,
               programs_compiled_total=40.0, compile_cache_hits_total=12.0,
               feed_batches_total=200.0, feed_not_ready_total=3.0)
    assert _read("wire_mb", ctx) == pytest.approx(709.545984)
    # 5 ns of all-reduce a step in the hand-made trace
    assert _read("allreduce_gbps", ctx) == pytest.approx(
        709_545_984.0 * 8 / 5e-9 / 1e9)
    assert _read("trace_lower_s", ctx) == 24.75
    assert _read("backend_compile_s", ctx) == 7.5
    assert _read("programs_compiled", ctx) == 28.0
    assert _read("feed_not_ready_pct", ctx) == 1.5
    # a gauge with several wire dtypes is summed over them
    ctx["program_metrics"]["grad_wire_bytes_per_step"]["values"] = [
        {"labels": {"wire": "bfloat16"}, "value": 6e6},
        {"labels": {"wire": "int32"}, "value": 1e6}]
    assert _read("wire_mb", ctx) == 7.0
    # one device: the gauge reads 0 and there is no bandwidth to speak of
    ctx["program_metrics"]["grad_wire_bytes_per_step"]["values"] = [
        {"labels": {"wire": "bfloat16"}, "value": 0.0}]
    assert _read("wire_mb", ctx) == 0.0
    assert _read("allreduce_gbps", ctx) is None


def test_span_reader_by_hand_and_from_a_profile(tmp_path):
    ctx = _ctx()
    ctx["program_spans"] = [["chainermn.feed.next", 0, 1_000],
                            ["chainermn.feed.put", 1_000, 300_000],
                            ["chainermn.feed.put", 900_000, 500_000]]
    assert _read("feed_put_ms", ctx) == pytest.approx(0.4)
    # the program's spans are read from the profiler's own file
    import jax

    jax.profiler.start_trace(str(tmp_path))
    for name in ("chainermn.feed.put", "bench.wait", "chainermn.feed.put"):
        with jax.profiler.TraceAnnotation(name):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    spans = scopes.program_spans(xplane.find_xplane(str(tmp_path)))
    assert [s[0] for s in spans] == ["chainermn.feed.put"] * 2
    assert all(s[2] >= 2_000_000 for s in spans) and spans[0][1] < spans[1][1]
    # an untraced run has none, whatever an older run left on disk
    del ctx["program_spans"]
    ctx["host_spans"] = []
    assert scopes.spans_of_run(ctx) == []


def test_tiny_dp4_cell_traced_reads_the_programs_counters_and_spans(added):
    import jax

    import run

    roots, benchmark = added
    line = run.run_cell(
        "tiny-lm-dp4", seed=5, seconds=0.5, trace=True,
        devices=jax.devices()[:4],
        peak={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}, roots=roots,
        benchmark=benchmark, t0=time.perf_counter())
    got = {k: v["value"] for k, v in line["metrics"].items()}
    # 99,328 float32 parameters cross the wire as bfloat16
    assert got["wire_mb"] == pytest.approx(99_328 * 2 / 1e6)
    assert got["feed_put_ms"] > 0 and 0 <= got["feed_not_ready_pct"] <= 100
    # no device plane in a CPU trace: the scope readers have nothing
    assert not set(got) & set(BY_HAND_NS)


# -- on recorded runs of the named program on the v5e --------------------

DATA = os.path.join(os.path.dirname(__file__), "data")


def _recorded_ctx(cell):
    path = os.path.join(DATA, cell + ".scopes.table.json.gz")
    if not os.path.exists(path):
        pytest.skip(f"no recorded table for {cell}")
    return scope_table.ctx_of(path)


def _all(ctx, names):
    return {n: _read(n, ctx) for n in names}


def test_pinned_on_the_recorded_one_chip_run():
    """Two steps of ``gpt2m-podshare-1chip`` on the v5e with the names in
    the program (PR 23). The sweep's 2.3 ms is not the whole sweep: XLA
    fuses AdamW's update of each large matrix into the matmul that makes
    its gradient and files the fusion under the backward pass (PERF.md)."""
    ctx = _recorded_ctx("gpt2m-podshare-1chip")
    got = _all(ctx, ["forward_ms", "backward_ms", "recompute_ms",
                     "optimizer_ms", "head_ms", "flash_fwd_ms",
                     "flash_dq_ms", "flash_dkv_ms", "grad_pack_ms",
                     "feed_put_ms"])
    assert got == pytest.approx({
        "forward_ms": 28.5145815, "backward_ms": 60.5800075,
        "recompute_ms": 3.3482435, "optimizer_ms": 2.2774375,
        "head_ms": 12.560803, "flash_fwd_ms": 8.314091,
        "flash_dq_ms": 7.4787425, "flash_dkv_ms": 9.967871,
        "grad_pack_ms": 0.0,  # named in the step, fused into the sweep
        "feed_put_ms": 0.3865735})
    # the three kernels are flash_ms, to the nanosecond
    assert got["flash_fwd_ms"] + got["flash_dq_ms"] + got["flash_dkv_ms"] \
        == pytest.approx(_read("flash_ms", ctx), rel=1e-9)
    # the three step scopes hold 96% of the busy time; the rest is XLA's
    # own copies and slices, which carry no op_name
    named = sum(scopes.scope_ms(ctx, (s,)) for s in (
        scopes.LOSS_AND_GRAD, scopes.GRAD_REDUCE, scopes.OPTIMIZER_UPDATE))
    busy = xplane.per_step_ms(ctx["trace"], lambda r: r["busy_ns"])
    assert named / busy == pytest.approx(0.96, abs=0.005)
    assert got["forward_ms"] + got["backward_ms"] + got["recompute_ms"] \
        == pytest.approx(scopes.scope_ms(ctx, (scopes.LOSS_AND_GRAD,)),
                         rel=1e-6)
    assert _read("wire_mb", ctx) == 0.0  # one chip: nothing leaves it
    assert _read("allreduce_gbps", ctx) is None


def test_pinned_on_the_recorded_dp4_run():
    """Two steps on each of the four chips of ``gpt2m-podshare-dp4``
    (PR 23). With the all-reduce between a gradient and its update the
    sweep cannot ride in the gradient's matmul: ``optimizer_ms`` is 5.3 ms
    over the twin's and ``backward_ms`` 5.1 under it. The program's own
    packing is one convert fusion (0.45 ms); what else the wire costs is
    XLA's copies and slices round its combined buffers, which carry no
    op_name (8% of the busy time here, 4% on one chip)."""
    ctx = _recorded_ctx("gpt2m-podshare-dp4")
    assert sorted(ctx["trace"]) == [f"/device:TPU:{i}" for i in range(4)]
    got = _all(ctx, ["forward_ms", "backward_ms", "optimizer_ms", "head_ms",
                     "flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms",
                     "grad_pack_ms", "wire_mb", "allreduce_gbps",
                     "feed_put_ms"])
    assert got == pytest.approx({
        "forward_ms": 28.56079025, "backward_ms": 55.455424375,
        "optimizer_ms": 7.60567025, "head_ms": 12.39896475,
        "flash_fwd_ms": 8.31434225, "flash_dq_ms": 7.48199575,
        "flash_dkv_ms": 9.972374375, "grad_pack_ms": 0.452859,
        "wire_mb": 709.545984, "allreduce_gbps": 461.0623186851194,
        "feed_put_ms": 0.8697599166666666})
    assert got["flash_fwd_ms"] + got["flash_dq_ms"] + got["flash_dkv_ms"] \
        == pytest.approx(_read("flash_ms", ctx), rel=1e-9)
    # the bandwidth is the gauge over allreduce_ms
    assert got["allreduce_gbps"] == pytest.approx(
        709.545984e6 * 8 / (_read("allreduce_ms", ctx) * 1e-3) / 1e9)
    named = sum(scopes.scope_ms(ctx, (s,)) for s in (
        scopes.LOSS_AND_GRAD, scopes.GRAD_REDUCE, scopes.OPTIMIZER_UPDATE))
    busy = xplane.per_step_ms(ctx["trace"], lambda r: r["busy_ns"])
    assert named / busy == pytest.approx(0.921, abs=0.005)
    # grad_reduce is the collectives and the packing, nothing else
    assert scopes.scope_ms(ctx, (scopes.GRAD_REDUCE,)) == pytest.approx(
        _read("allreduce_ms", ctx) + got["grad_pack_ms"], rel=1e-3)
