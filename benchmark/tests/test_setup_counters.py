"""The readers of the program's set-up counters (``setup_counters.py`` and
the four per-layer metrics over it): on a registry made by hand, whose
answers can be worked by hand; on the registry of a program without the
``program`` label (the parent of the PR that brought it); through the
harness at a tiny size; and on the registry a cached traced run of
``gpt2m-hostfill-1chip`` on the v5e left (``data/*.setup.registry.json``),
whose numbers are pinned."""

import json
import os
import time

import pytest

import setup_counters
import spec

ROOTS = spec.Roots()
NAMES = ("step_trace_lower_s", "step_backend_s", "cache_load_s",
         "uncached_compile_s")


def _family(by_program):
    return {"type": "counter", "help": "", "values": [
        {"labels": {"program": p} if p is not None else {}, "value": v}
        for p, v in by_program.items()]}


def _ctx(**families):
    return {"program_metrics": {name: _family(rows)
                                for name, rows in families.items()}}


def _read(name, ctx):
    return ROOTS.module("layer_metrics", name).read(ctx)


#: a cached run by hand: the step and ``init`` loaded, two lambdas of which
#: one was loaded (1.5 s of the label's 2.0), ``sys_loss`` compiled and
#: written, ``small`` compiled three times and never written
BY_HAND = dict(
    jax_trace_seconds_total={"local_step": 12.0, "init": 2.0,
                             "<lambda>": 6.0, "sys_loss": 3.0,
                             "small": 0.25, "traced_only": 0.5},
    jax_lower_seconds_total={"local_step": 4.0, "init": 0.5,
                             "<lambda>": 1.0, "sys_loss": 0.75,
                             "small": 0.125},
    jax_backend_compile_seconds_total={"local_step": 3.0, "init": 1.0,
                                       "<lambda>": 2.0, "sys_loss": 9.0,
                                       "small": 0.375},
    programs_compiled_total={"local_step": 1.0, "init": 1.0, "<lambda>": 2.0,
                             "sys_loss": 1.0, "small": 3.0},
    compile_cache_hits_total={"local_step": 1.0, "init": 1.0,
                              "<lambda>": 1.0},
    compile_cache_misses_total={"sys_loss": 1.0},
    compile_cache_retrieval_seconds_total={"local_step": 2.75, "init": 0.875,
                                           "<lambda>": 1.5},
    process_runqueue_wait_seconds_total={None: 4.5},
    process_cpu_seconds_total={None: 61.25},
)


def test_readers_by_hand(capsys):
    ctx = _ctx(**BY_HAND)
    assert {n: _read(n, ctx) for n in NAMES} == {
        "step_trace_lower_s": 16.0, "step_backend_s": 3.0,
        "cache_load_s": 5.125,
        # the unloaded lambda 0.5, sys_loss 9.0, small 0.375
        "uncached_compile_s": 9.875}
    # what the three older readers sum is what the table sums
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["sums"] == {
        "trace_lower_s": _read("trace_lower_s", ctx),
        "backend_compile_s": _read("backend_compile_s", ctx),
        "through_backend": 8, "loaded": 3}
    assert said["sums"]["through_backend"] - said["sums"]["loaded"] == \
        _read("programs_compiled", ctx)
    # the host's clocks ride in the log, each where the kernel gives it
    assert said["host"] == {"cpu_s": 61.25, "runqueue_wait_s": 4.5}
    del ctx["program_metrics"]["process_runqueue_wait_seconds_total"]
    assert setup_counters.host(ctx) == {"cpu_s": 61.25}
    rows = said["setup_by_program"]
    assert [r["program"] for r in rows] == [
        "local_step", "sys_loss", "<lambda>", "init", "small", "traced_only"]
    assert rows[0] == {"program": "local_step", "trace_s": 12.0,
                       "lower_s": 4.0, "backend_s": 3.0, "load_s": 2.75,
                       "compiled": 1, "loaded": 1, "written": 0, "neither": 0}
    kinds = {r["program"]: (r["loaded"], r["written"], r["neither"])
             for r in rows}
    assert kinds["<lambda>"] == (1, 0, 1) and kinds["sys_loss"] == (0, 1, 0)
    assert kinds["small"] == (0, 0, 3) and kinds["traced_only"] == (0, 0, 0)


def test_a_first_run_loads_nothing_and_compiles_everything():
    cold = {k: v for k, v in BY_HAND.items()
            if "hits" not in k and "retrieval" not in k}
    ctx = _ctx(**cold)
    assert _read("cache_load_s", ctx) == 0.0
    assert _read("uncached_compile_s", ctx) == \
        _read("backend_compile_s", ctx) == 15.375


def test_a_program_without_the_label_reports_none_of_them(capsys):
    """The parent of the PR that brought the label: the compile counters
    are one unlabelled series each, and there is no host counter."""
    ctx = _ctx(**{name: {None: sum(rows.values())}
                  for name, rows in BY_HAND.items()
                  if not name.startswith(("process_", "compile_cache_r"))})
    assert _read("trace_lower_s", ctx) == 30.125  # the older readers do
    for name in NAMES:
        assert _read(name, ctx) is None, name
    assert setup_counters.table(ctx) == []
    for name in NAMES:
        assert _read(name, {"program_metrics": {}}) is None, name
    assert capsys.readouterr().out == ""


def test_tiny_cell_traced_reports_the_step_and_the_rest(added, capsys):
    import jax

    import run
    from chainermn_tpu.utils import compile_cache

    compile_cache._count_compiles()  # as run.main's use_compile_cache()
    roots, benchmark = added
    line = run.run_cell(
        "tiny-lm", seed=7, seconds=0.5, trace=True,
        devices=jax.devices()[:1],
        peak={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}, roots=roots,
        benchmark=benchmark, t0=time.perf_counter())
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["step_trace_lower_s"] > 0 and got["step_backend_s"] > 0
    # the step's own seconds account for the host clock round its
    # lower().compile(), and are a part of the process's
    assert got["step_trace_lower_s"] + got["step_backend_s"] <= \
        got["compile_s"]
    assert got["step_trace_lower_s"] < got["trace_lower_s"]
    assert got["step_backend_s"] < got["backend_compile_s"]
    assert 0 <= got["cache_load_s"] <= got["backend_compile_s"]
    assert 0 <= got["uncached_compile_s"] <= got["backend_compile_s"]
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith('{"setup_by_program"')]
    assert len(said) == 1
    sums, rows = said[0]["sums"], said[0]["setup_by_program"]
    assert sums["trace_lower_s"] == pytest.approx(got["trace_lower_s"])
    assert sums["backend_compile_s"] == \
        pytest.approx(got["backend_compile_s"])
    assert sums["through_backend"] - sums["loaded"] == \
        got["programs_compiled"]
    # nothing but the step carries the step's label: one program of it
    step = [r for r in rows if r["program"] == "local_step"]
    assert len(step) == 1 and step[0]["compiled"] == 1
    assert said[0]["host"]["cpu_s"] > 0
    assert ("runqueue_wait_s" in said[0]["host"]) == \
        os.path.exists("/proc/self/schedstat")


# -- on the registry of a recorded run on the v5e -------------------------

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_pinned_on_the_recorded_cached_run(capsys):
    """The registry after a cached traced run of ``gpt2m-hostfill-1chip``
    on the v5e (PR 38, seed 3838000102; ``compile_s`` read 19.694 on the
    host's clock, ``trace_lower_s`` 27.230, ``backend_compile_s`` 15.458,
    ``programs_compiled`` 19 in the same run's line)."""
    with open(os.path.join(
            DATA, "gpt2m-hostfill-1chip.setup.registry.json")) as f:
        ctx = {"program_metrics": json.load(f)}
    got = {n: _read(n, ctx) for n in NAMES}
    assert got == pytest.approx({
        "step_trace_lower_s": 15.7921433, "step_backend_s": 3.3786917,
        "cache_load_s": 13.2893238, "uncached_compile_s": 2.0764446})
    # the step's own seconds account for the host clock round its
    # lower().compile() to within a second
    assert 0 < 19.69446902 - got["step_trace_lower_s"] \
        - got["step_backend_s"] < 1.0
    # what is no loading and no compiling of the uncached: finding the
    # cache's key and the entry, 0.09 s over six programs
    assert _read("backend_compile_s", ctx) - got["cache_load_s"] \
        - got["uncached_compile_s"] == pytest.approx(0.0926470, abs=1e-6)
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["sums"] == {
        "trace_lower_s": pytest.approx(_read("trace_lower_s", ctx)),
        "backend_compile_s": pytest.approx(_read("backend_compile_s", ctx)),
        "through_backend": 25, "loaded": 6}
    assert said["sums"]["trace_lower_s"] == pytest.approx(27.2296162)
    assert _read("programs_compiled", ctx) == 19.0
    rows = {r["program"]: r for r in said["setup_by_program"]}
    assert [r["program"] for r in said["setup_by_program"]][:4] == \
        ["local_step", "sys_loss", "<lambda>", "init"]
    # nothing else of the run carries the step's label
    assert rows["local_step"]["compiled"] == rows["local_step"]["loaded"] == 1
    # the 19 the cache never keeps: jnp functions called eagerly
    assert {p: r["neither"] for p, r in rows.items() if r["neither"]} == {
        "true_divide": 8, "broadcast_in_dim": 8, "convert_element_type": 2,
        "_threefry_seed": 1}
    assert said["host"] == {"cpu_s": pytest.approx(62.92)}
