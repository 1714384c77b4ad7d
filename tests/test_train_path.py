"""The train path named from the inside (ISSUE 23): device scopes in the
lowered step, the gradient-wire gauge, compile and feed counters, and host
spans on the profiler's clock. Everything here is structural (names and
counts), on the CPU; times come from the chip (PERF.md)."""

import functools
import glob
import json
import os
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import chainermn_tpu
from chainermn_tpu import create_multi_node_optimizer as mno
from chainermn_tpu.models import TransformerLM, lm_loss_fused
from chainermn_tpu.observability import metrics, trace, train_path
from chainermn_tpu.ops.flash_attention import flash_attention
from chainermn_tpu.training import make_train_step
from chainermn_tpu.training.prefetch import prefetch_to_device
from chainermn_tpu.training.train_step import create_train_state

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OPTIMIZERS = {
    "default": dict(),
    "flat": dict(reduction_schedule="flat"),
    "double_buffered": dict(double_buffering=True),
    "error_feedback": dict(allreduce_grad_dtype=jnp.int8,
                           error_feedback=True),
}
STEP_SCOPES = (train_path.LOSS_AND_GRAD, train_path.GRAD_REDUCE,
               train_path.OPTIMIZER_UPDATE)


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _params():
    rng = np.random.default_rng(0)
    return {"w": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),
            "b": jnp.zeros((8,), jnp.float32)}


def _loss(params, batch):
    out = batch @ params["w"] + params["b"]
    return jnp.mean(out ** 2)


@functools.lru_cache(maxsize=None)
def _lowered(kind, n, all_to_all_min_bytes=None):
    """``(op_names of the lowered step, registry snapshot after tracing
    it)`` for one optimizer on ``n`` virtual devices;
    ``all_to_all_min_bytes`` 0 sends these toy leaves where the default
    reduction sends a real model's matrices."""
    if all_to_all_min_bytes is not None:
        from chainermn_tpu.parallel import collectives

        keep = collectives.ALL_TO_ALL_MIN_BYTES
        collectives.ALL_TO_ALL_MIN_BYTES = all_to_all_min_bytes
        try:
            return _lowered.__wrapped__(kind, n)
        finally:
            collectives.ALL_TO_ALL_MIN_BYTES = keep
    metrics.reset()
    comm = chainermn_tpu.create_communicator(
        "naive", devices=jax.devices("cpu")[:n],
        allreduce_grad_dtype=jnp.bfloat16)
    opt = mno(optax.adam(1e-3), comm, **OPTIMIZERS[kind])
    state = create_train_state(_params(), opt, comm)
    step = make_train_step(_loss, opt, comm)
    if not hasattr(step, "lower"):  # error feedback: a checking wrapper
        step = jax.jit(step)
    lowered = step.lower(state, jnp.ones((4 * n, 16), jnp.float32))
    names = _op_names(lowered.as_text(dialect="hlo", debug_info=True))
    return names, metrics.registry().snapshot()


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("kind", list(OPTIMIZERS))
def test_step_scopes_in_the_lowered_step(kind, n):
    names, _ = _lowered(kind, n)
    for scope in STEP_SCOPES:
        assert any(scope in name for name in names), (scope, kind, n)
    # forward and backward of the loss come apart by JAX's own marker
    inside = [x for x in names if train_path.LOSS_AND_GRAD in x]
    assert any(train_path.BACKWARD_MARKER in x for x in inside)
    assert any(train_path.BACKWARD_MARKER not in x for x in inside)
    # a packed schedule names its buckets inside the reduction
    if kind in ("flat", "double_buffered") and n > 1:
        assert any(f"{train_path.GRAD_REDUCE}/{train_path.bucket_scope(0)}"
                   in x for x in names)
    # nothing of the optimizer's sweep sits inside the reduction's scope
    assert not any(train_path.GRAD_REDUCE in x
                   and train_path.OPTIMIZER_UPDATE in x for x in names)


def _wire_bytes(snapshot):
    rows = snapshot[train_path.GRAD_WIRE_BYTES]["values"]
    return {r["labels"]["wire"]: r["value"] for r in rows}


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("kind", list(OPTIMIZERS))
def test_grad_wire_bytes_are_the_leaves_at_the_wire_dtype(kind, n):
    _, snap = _lowered(kind, n)
    wire = "int8" if kind == "error_feedback" else "bfloat16"
    expect = {wire: (16 * 8 + 8) * jnp.dtype(wire).itemsize}
    got = _wire_bytes(snap)
    assert set(got) == set(expect)
    if n == 1:  # nothing leaves the device
        assert set(got.values()) == {0.0}
        assert snap[train_path.GRAD_REDUCE_BUCKETS]["values"][0]["value"] \
            == 0.0
    else:
        assert got == expect
        assert snap[train_path.GRAD_REDUCE_BUCKETS]["values"][0]["value"] \
            >= 1.0


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("kind", list(OPTIMIZERS))
def test_the_default_reductions_all_to_alls_carry_its_scope(kind, n):
    """The default optimizer on several devices averages a large leaf
    with two all_to_alls: they sit in the reduction's scope, after the
    backward and before the sweep, so ``allreduce_ms`` and
    ``grad_pack_ms`` read them and ``backward_ms`` does not. The packed
    schedules and one device hold none."""
    names, _ = _lowered(kind, n, 0)
    moved = [x for x in names if "all_to_all" in x]
    if kind == "default" and n > 1:
        assert moved and all(
            train_path.GRAD_REDUCE in x
            and train_path.LOSS_AND_GRAD not in x
            and train_path.OPTIMIZER_UPDATE not in x for x in moved)
    elif kind != "error_feedback":  # whose int8 wire is all_to_alls too
        assert not moved


def test_grad_wire_bytes_zero_outside_any_axis():
    metrics.reset()
    comm = chainermn_tpu.create_communicator(
        "naive", devices=jax.devices("cpu")[:4])
    chainermn_tpu.optimizers.allreduce_gradients(
        _params(), comm, compress_dtype=jnp.bfloat16)  # eager: unbound
    assert set(_wire_bytes(metrics.registry().snapshot()).values()) == {0.0}


def test_pack_event_and_gauge_share_one_computation():
    """The ``pack`` trace event's ``nbytes`` is the gauge's sum."""
    metrics.reset()
    rec = trace.enable(None)
    try:
        _lowered.cache_clear()
        _, snap = _lowered("flat", 4)
    finally:
        trace.disable()
        _lowered.cache_clear()
    packs = [e for e in rec.events if e["kind"] == "pack"]
    assert packs and packs[-1]["nbytes"] == sum(_wire_bytes(snap).values())


# -- the model's and the kernels' scopes --------------------------------

def _flash(q, k, v, *, causal, scale):
    return flash_attention(q, k, v, causal=causal, scale=scale)


@pytest.mark.parametrize("fused", [True, False])
def test_lm_head_scope(fused):
    model = TransformerLM(vocab_size=64, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_len=16,
                          return_hidden=fused)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]

    def loss(p):
        out = model.apply({"params": p}, tokens)
        if fused:
            return lm_loss_fused(out, p["tok_emb"]["embedding"], tokens,
                                 n_chunks=2)
        return out.sum()

    names = _op_names(jax.jit(jax.grad(loss)).lower(params).as_text(
        dialect="hlo", debug_info=True))
    head = [x for x in names if train_path.LM_HEAD in x]
    assert any(train_path.BACKWARD_MARKER in x for x in head)
    assert any(train_path.BACKWARD_MARKER not in x for x in head)
    if fused:  # no chunk is computed again: the loop's forward makes the
        # gradient (ISSUE 31; tests/test_fused_head_grad.py counts its
        # matmuls)
        assert not any(train_path.REMAT_MARKER in x for x in names)


def test_flash_kernels_are_scoped_and_named_forward_and_backward():
    q = jnp.ones((1, 128, 2, 32), jnp.float32)

    def f(q):
        return _flash(q, q, q, causal=True, scale=1.0).sum()

    fwd = _op_names(jax.jit(f).lower(q).as_text(dialect="hlo",
                                                debug_info=True))
    assert any(train_path.FLASH_FWD in x for x in fwd)
    assert not any(train_path.FLASH_BWD_DQ in x or
                   train_path.FLASH_BWD_DKV in x for x in fwd)
    bwd = _op_names(jax.jit(jax.grad(f)).lower(q).as_text(
        dialect="hlo", debug_info=True))
    for scope in (train_path.FLASH_FWD, train_path.FLASH_BWD_DQ,
                  train_path.FLASH_BWD_DKV):
        assert any(scope in x for x in bwd), scope
    # the Mosaic kernels themselves carry the same names
    kernels = set(re.findall(r"name=(\w+)", str(jax.make_jaxpr(
        jax.grad(f))(q))))
    assert {train_path.FLASH_FWD, train_path.FLASH_BWD_DQ,
            train_path.FLASH_BWD_DKV} <= kernels


def test_ring_attention_blocks_carry_the_flash_scopes():
    from chainermn_tpu.ops.flash_attention import (
        flash_block_bwd,
        flash_block_fwd,
    )

    q = jnp.ones((1, 128, 2, 32), jnp.float32)  # BTHD
    kw = dict(causal=False, scale=1.0, block_q=128, block_k=128,
              interpret=True)

    def both(q):
        o, lse = flash_block_fwd(q, q, q, **kw)
        return flash_block_bwd(q, q, q, o, lse, o, **kw)

    names = _op_names(jax.jit(both).lower(q).as_text(dialect="hlo",
                                                     debug_info=True))
    for scope in (train_path.FLASH_FWD, train_path.FLASH_BWD_DQ,
                  train_path.FLASH_BWD_DKV):
        assert any(scope in x for x in names), scope


def test_scopes_are_metadata_only():
    """The recorder changes nothing of the lowered step (the
    ``test_trace.py`` certificate, on the scoped step), and the scopes sit
    only in metadata: stripped of it the text names none of them."""
    def lower(kind):
        _lowered.cache_clear()
        comm = chainermn_tpu.create_communicator(
            "naive", devices=jax.devices("cpu")[:4],
            allreduce_grad_dtype=jnp.bfloat16)
        opt = mno(optax.adam(1e-3), comm, **OPTIMIZERS[kind])
        state = create_train_state(_params(), opt, comm)
        step = make_train_step(_loss, opt, comm)
        return step.lower(state, jnp.ones((16, 16), jnp.float32))

    off = lower("flat")
    trace.enable(None)
    try:
        on = lower("flat")
    finally:
        trace.disable()
    bare = off.as_text(dialect="hlo")
    assert on.as_text(dialect="hlo") == bare
    assert _op_names(on.as_text(dialect="hlo", debug_info=True)) == \
        _op_names(off.as_text(dialect="hlo", debug_info=True))
    assert not any(scope in bare for scope in STEP_SCOPES)


# -- compile counters ----------------------------------------------------

def _counter(name):
    fam = metrics.registry().snapshot().get(name)
    return sum(r["value"] for r in fam["values"]) if fam else 0.0


def test_compile_counters_rise_on_a_compile():
    from chainermn_tpu.utils import compile_cache

    compile_cache._count_compiles()
    compile_cache._count_compiles()  # once a process: no second listener
    x = jnp.ones((7, 3))
    before = {n: _counter(n) for n in (
        train_path.JAX_TRACE_SECONDS, train_path.JAX_LOWER_SECONDS,
        train_path.JAX_BACKEND_COMPILE_SECONDS,
        train_path.PROGRAMS_COMPILED)}
    jax.jit(lambda x: jnp.tanh(x) * 3.25 + 0.125)(x)
    for name, was in before.items():
        assert _counter(name) > was, name
    assert _counter(train_path.PROGRAMS_COMPILED) == \
        before[train_path.PROGRAMS_COMPILED] + 1


def test_nested_trace_spans_count_once():
    from jax import monitoring

    from chainermn_tpu.utils import compile_cache

    compile_cache._count_compiles()
    event = compile_cache._TRACE_EVENT
    was = _counter(train_path.JAX_TRACE_SECONDS)
    t = time.time() + 1000.0  # after every span JAX itself has reported
    # as JAX emits them: the inner spans close before the outer one
    monitoring.record_event_time_span(event, t + 1.0, t + 2.0)
    monitoring.record_event_time_span(event, t + 3.0, t + 3.5)
    assert _counter(train_path.JAX_TRACE_SECONDS) == pytest.approx(was + 1.5)
    monitoring.record_event_time_span(event, t, t + 4.0)
    assert _counter(train_path.JAX_TRACE_SECONDS) == pytest.approx(was + 4.0)
    monitoring.record_event_time_span(event, t + 5.0, t + 6.0)  # a sibling
    assert _counter(train_path.JAX_TRACE_SECONDS) == pytest.approx(was + 5.0)


_CACHE_CHILD = """
import json, sys
import jax, jax.numpy as jnp
from chainermn_tpu.utils.compile_cache import use_compile_cache
from chainermn_tpu.observability.metrics import registry
assert use_compile_cache() == sys.argv[1]
jax.jit(lambda x: jnp.sin(x) @ x.T + 2.5)(jnp.ones((8, 8))).block_until_ready()
snap = registry().snapshot()
print(json.dumps({k: sum(r["value"] for r in v["values"])
                  for k, v in snap.items()}))
"""


def test_a_second_process_on_a_warm_cache_counts_hits(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
           "PYTHONPATH": REPO}

    def child():
        out = subprocess.run(
            [sys.executable, "-c", _CACHE_CHILD, str(tmp_path)], env=env,
            capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold, warm = child(), child()
    assert cold[train_path.COMPILE_CACHE_MISSES] >= 1
    assert cold.get(train_path.COMPILE_CACHE_HITS, 0) == 0
    assert warm[train_path.COMPILE_CACHE_HITS] >= 1
    assert warm[train_path.COMPILE_CACHE_HITS] == \
        cold[train_path.COMPILE_CACHE_MISSES]
    assert warm[train_path.PROGRAMS_COMPILED] == \
        cold[train_path.PROGRAMS_COMPILED]



# -- compile counters by program -----------------------------------------

_STAGES = (train_path.JAX_TRACE_SECONDS, train_path.JAX_LOWER_SECONDS,
           train_path.JAX_BACKEND_COMPILE_SECONDS,
           train_path.PROGRAMS_COMPILED)
_CACHE = (train_path.COMPILE_CACHE_HITS, train_path.COMPILE_CACHE_MISSES,
          train_path.COMPILE_CACHE_RETRIEVAL_SECONDS)


def _by_program(name):
    fam = metrics.registry().snapshot().get(name)
    return {r["labels"]["program"]: r["value"]
            for r in fam["values"]} if fam else {}


@pytest.fixture
def counts():
    """Listeners of their own on an empty registry: a test replays events
    into them as JAX would call them, and the process's own label set
    stays as it was."""
    from chainermn_tpu.utils import compile_cache

    metrics.reset()
    fresh = compile_cache._CompileCounts()
    metrics.registry().register_collect(fresh.collect)
    yield fresh
    metrics.reset()


def _replay(counts, events):
    """``events`` as JAX's three kinds of listener receive them."""
    for kind, event, *args, kw in events:
        {"span": counts.on_span, "duration": counts.on_duration,
         "event": counts.on_event}[kind](event, *args, **kw)


def _a_compile(name, t, *, trace=1.0, lower=0.25, backend=0.5, nested=(),
               cache=()):
    """What JAX reports, in its order, when a jitted function called
    ``name`` is traced (holding the traces of ``nested`` functions),
    lowered and compiled at time ``t``."""
    from chainermn_tpu.utils import compile_cache as cc

    inner = trace / (2 * len(nested) + 1)
    events = [("span", cc._TRACE_EVENT, t + (2 * i + 1) * inner,
               t + (2 * i + 2) * inner, {"fun_name": n})
              for i, n in enumerate(nested)]
    events.append(("span", cc._TRACE_EVENT, t, t + trace,
                   {"fun_name": name}))
    events.append(("duration", cc._LOWER_EVENT, lower,
                   {"fun_name": f"jit({name})"}))
    for kind in cache:
        if kind == "hit":
            events.append(("event", cc._HIT_EVENT, {}))
            events.append(("duration", cc._RETRIEVAL_EVENT, backend / 2, {}))
        else:
            events.append(("event", cc._MISS_EVENT, {}))
    events.append(("duration", cc._BACKEND_EVENT, backend,
                   {"fun_name": f"jit({name})"}))
    return events


def _parents_totals(events):
    """The unlabelled counters as the listeners before the ``program``
    label counted them: a traced span takes the place of those it holds,
    every other event adds to its one number."""
    from chainermn_tpu.utils import compile_cache as cc

    totals = dict.fromkeys(_STAGES + _CACHE[:2], 0.0)
    counted = []
    for kind, event, *args, _ in events:
        if kind == "span" and event == cc._TRACE_EVENT:
            start, end = args
            held = 0.0
            while counted and counted[-1][0] >= start:
                s, e = counted.pop()
                held += e - s
            counted.append((start, end))
            totals[train_path.JAX_TRACE_SECONDS] += end - start - held
        elif kind == "duration" and event == cc._LOWER_EVENT:
            totals[train_path.JAX_LOWER_SECONDS] += args[0]
        elif kind == "duration" and event == cc._BACKEND_EVENT:
            totals[train_path.JAX_BACKEND_COMPILE_SECONDS] += args[0]
            totals[train_path.PROGRAMS_COMPILED] += 1
        elif kind == "event" and event == cc._HIT_EVENT:
            totals[train_path.COMPILE_CACHE_HITS] += 1
        elif kind == "event" and event == cc._MISS_EVENT:
            totals[train_path.COMPILE_CACHE_MISSES] += 1
    return totals


def test_program_labels_sum_to_the_unlabelled_totals(counts):
    """On one recorded sequence (a real compile with a jitted function
    inside another, then made-up programs that hit, wrote and did
    neither), each counter summed over ``program`` is the number the
    unlabelled counter held."""
    from jax import monitoring

    from chainermn_tpu.utils import compile_cache as cc

    heard = (cc._TRACE_EVENT, cc._LOWER_EVENT, cc._BACKEND_EVENT,
             cc._HIT_EVENT, cc._MISS_EVENT, cc._RETRIEVAL_EVENT)
    recorded = []

    def record(kind):
        def listener(event, *args, **kw):
            if event in heard:
                recorded.append((kind, event, *args, kw))
        return listener

    span, duration, plain = record("span"), record("duration"), \
        record("event")
    monitoring.register_event_time_span_listener(span)
    monitoring.register_event_duration_secs_listener(duration)
    monitoring.register_event_listener(plain)
    inner = jax.jit(lambda x: jnp.tanh(x) * 1.75)
    try:
        jax.jit(lambda x: inner(x) + jnp.cos(x) * 0.375)(jnp.ones((5, 3)))
    finally:
        monitoring.unregister_event_time_span_listener(span)
        monitoring.unregister_event_duration_listener(duration)
        monitoring.unregister_event_listener(plain)
    assert {e for _, e, *_ in recorded} >= set(heard[:3])
    # the process's own listeners heard that compile too
    metrics.reset()
    metrics.registry().register_collect(counts.collect)
    t = time.time() + 1000.0
    events = recorded \
        + _a_compile("loaded", t, nested=("a", "b"), cache=("hit",)) \
        + _a_compile("written", t + 2, cache=("miss",)) \
        + _a_compile("neither", t + 4, nested=("a",))
    _replay(counts, events)
    for name, total in _parents_totals(events).items():
        assert sum(_by_program(name).values()) == pytest.approx(total), name


def test_a_nested_trace_files_under_the_outermost_program_once(counts):
    _replay(counts, _a_compile("outermost", 1000.0, trace=3.0,
                               nested=("held", "held_too")))
    assert _by_program(train_path.JAX_TRACE_SECONDS) == \
        {"outermost": pytest.approx(3.0)}
    # and as JAX itself reports a jitted function called inside another
    from chainermn_tpu.utils import compile_cache

    compile_cache._count_compiles()

    @jax.jit
    def held_inside_a_probe(x):
        return jnp.tanh(x) * 2.625

    def outermost_probe(x):
        return held_inside_a_probe(x) + 0.875

    jax.jit(outermost_probe)(jnp.ones((3, 7)))
    traced = _by_program(train_path.JAX_TRACE_SECONDS)
    assert traced["outermost_probe"] > 0
    assert "held_inside_a_probe" not in traced and "tanh" not in traced


@pytest.mark.parametrize("wrapper", ["jit", "pmap"])
def test_the_three_stages_of_a_program_share_a_label(counts, wrapper):
    from chainermn_tpu.utils import compile_cache as cc

    _replay(counts, [
        ("span", cc._TRACE_EVENT, 10.0, 11.0, {"fun_name": "probe"}),
        ("duration", cc._LOWER_EVENT, 0.5,
         {"fun_name": f"{wrapper}(probe)"}),
        ("duration", cc._BACKEND_EVENT, 2.0,
         {"fun_name": f"{wrapper}(probe)"}),
    ])
    assert [_by_program(name) for name in _STAGES] == \
        [{"probe": 1.0}, {"probe": 0.5}, {"probe": 2.0}, {"probe": 1.0}]


def test_jax_names_the_three_stages_of_a_jitted_function_alike():
    from chainermn_tpu.utils import compile_cache

    compile_cache._count_compiles()

    def three_stage_probe(x):
        return jnp.sinh(x) * 0.5625

    jax.jit(three_stage_probe)(jnp.ones((2, 9)))
    for name in _STAGES:
        assert _by_program(name).get("three_stage_probe", 0) > 0, name


def test_the_65th_program_lands_in_other(counts):
    from chainermn_tpu.utils import compile_cache as cc

    n = cc.MAX_PROGRAMS + 6
    for i in range(n):
        _replay(counts, _a_compile(f"program_{i}", 1000.0 + 2 * i))
    # a name the process has met keeps its label past the cap
    _replay(counts, _a_compile("program_0", 5000.0))
    for name in _STAGES:
        series = _by_program(name)
        assert len(series) == cc.MAX_PROGRAMS + 1, name
        assert f"program_{cc.MAX_PROGRAMS}" not in series
    compiled = _by_program(train_path.PROGRAMS_COMPILED)
    assert compiled[train_path.OTHER_PROGRAM] == 6
    assert compiled["program_0"] == 2 and sum(compiled.values()) == n + 1


def test_a_retrieval_is_filed_under_the_backend_event_of_its_own_thread(
        counts):
    """Two threads load a program each, their events interleaved: each
    retrieval goes to the backend event that closes round it on the
    thread it arrived on, whichever closes first."""
    import threading

    from chainermn_tpu.utils import compile_cache as cc

    turn = threading.Barrier(2, timeout=30)

    def load(name, seconds, closes_first):
        counts.on_event(cc._HIT_EVENT)
        counts.on_duration(cc._RETRIEVAL_EVENT, seconds)
        turn.wait()  # both retrievals are in, no backend event yet
        if not closes_first:
            turn.wait()
        counts.on_duration(cc._BACKEND_EVENT, seconds + 0.125,
                           fun_name=f"jit({name})")
        if closes_first:
            turn.wait()

    threads = [threading.Thread(target=load, args=args) for args in
               (("slow_load", 0.5, False), ("quick_load", 0.25, True))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert not any(th.is_alive() for th in threads)
    assert _by_program(train_path.COMPILE_CACHE_RETRIEVAL_SECONDS) == \
        {"slow_load": 0.5, "quick_load": 0.25}
    assert _by_program(train_path.COMPILE_CACHE_HITS) == \
        {"slow_load": 1.0, "quick_load": 1.0}
    assert counts._held == {}  # nothing of a closed compile is kept


@pytest.mark.parametrize("cache,loaded,written,neither", [
    (("hit",), 1, 0, 0), (("miss",), 0, 1, 0), ((), 0, 0, 1)])
def test_loaded_written_and_neither_are_told_apart(counts, cache, loaded,
                                                   written, neither):
    """Per program: loaded from the cache, compiled and written to it, or
    compiled and never written (under JAX's thresholds)."""
    _replay(counts, _a_compile("bystander", 900.0, cache=("miss",))
            + _a_compile("probe", 1000.0, cache=cache))
    hits = _by_program(train_path.COMPILE_CACHE_HITS).get("probe", 0)
    misses = _by_program(train_path.COMPILE_CACHE_MISSES).get("probe", 0)
    through = _by_program(train_path.PROGRAMS_COMPILED)["probe"]
    assert (hits, misses, through - hits - misses) == \
        (loaded, written, neither)
    retrieved = _by_program(train_path.COMPILE_CACHE_RETRIEVAL_SECONDS)
    assert retrieved == ({"probe": 0.25} if loaded else {})


def test_closed_roots_are_folded_and_a_read_in_between_counts_once(counts):
    """The traced spans a thread holds back go when it lowers; a read of
    the registry in the middle of an outer trace counts what has closed,
    and the outer span then adds only the rest."""
    from chainermn_tpu.utils import compile_cache as cc

    for i in range(50):
        counts.on_span(cc._TRACE_EVENT, 100.0 + i, 100.5 + i,
                       fun_name="traced_only")
    assert len(counts._held[next(iter(counts._held))].roots) == 50
    assert _by_program(train_path.JAX_TRACE_SECONDS) == {"traced_only": 25.0}
    counts.on_span(cc._TRACE_EVENT, 99.0, 151.0, fun_name="round_them")
    counts.on_duration(cc._LOWER_EVENT, 1.0, fun_name="jit(round_them)")
    assert counts._held[next(iter(counts._held))].roots == []
    assert _by_program(train_path.JAX_TRACE_SECONDS) == \
        {"traced_only": 25.0, "round_them": 27.0}


def test_listeners_keep_their_sums_under_many_threads(counts):
    import threading

    per_thread, n_threads = 200, 16
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def compile_many(k):
            for i in range(per_thread):
                _replay(counts, _a_compile(
                    f"program_{i % 8}", 1000.0 * k + 2 * i, nested=("n",),
                    cache=("hit",) if i % 2 else ("miss",)))

        threads = [threading.Thread(target=compile_many, args=(k,))
                   for k in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(was)
    n = per_thread * n_threads
    want = {train_path.JAX_TRACE_SECONDS: n * 1.0,
            train_path.JAX_LOWER_SECONDS: n * 0.25,
            train_path.JAX_BACKEND_COMPILE_SECONDS: n * 0.5,
            train_path.PROGRAMS_COMPILED: n,
            train_path.COMPILE_CACHE_HITS: n / 2,
            train_path.COMPILE_CACHE_MISSES: n / 2,
            train_path.COMPILE_CACHE_RETRIEVAL_SECONDS: n / 2 * 0.25}
    for name, total in want.items():
        assert sum(_by_program(name).values()) == pytest.approx(total), name
    assert counts._held == {}


def test_the_train_step_names_itself():
    """``make_train_step``'s program carries ``TRAIN_STEP_PROGRAM`` through
    every stage of a compile, and its module's name (part of the cache's
    key) is the same name."""
    from chainermn_tpu.utils import compile_cache

    compile_cache._count_compiles()
    before = {n: _by_program(n).get(train_path.TRAIN_STEP_PROGRAM, 0.0)
              for n in _STAGES}
    comm = chainermn_tpu.create_communicator(
        "naive", devices=jax.devices("cpu")[:1])
    opt = mno(optax.sgd(0.0625), comm)
    state = create_train_state(_params(), opt, comm)
    step = make_train_step(_loss, opt, comm)
    compiled = step.lower(state, jnp.ones((6, 16), jnp.float32)).compile()
    assert compiled.as_text().startswith(
        f"HloModule jit_{train_path.TRAIN_STEP_PROGRAM},")
    for name, was in before.items():
        assert _by_program(name)[train_path.TRAIN_STEP_PROGRAM] > was, name
    assert _by_program(train_path.PROGRAMS_COMPILED)[
        train_path.TRAIN_STEP_PROGRAM] == \
        before[train_path.PROGRAMS_COMPILED] + 1


# -- what the host made the process wait ----------------------------------

def _schedstat(proc, thread, text):
    os.makedirs(os.path.join(proc, "task", thread))
    with open(os.path.join(proc, "task", thread, "schedstat"), "w") as f:
        f.write(text)


def test_host_hook_sums_the_threads_runqueue_wait(tmp_path):
    from chainermn_tpu.utils import compile_cache

    metrics.reset()
    proc = str(tmp_path)
    with open(os.path.join(proc, "schedstat"), "w") as f:
        f.write("39744403 2500000000 7\n")  # the main thread's again
    _schedstat(proc, "101", "39744403 2500000000 7\n")
    _schedstat(proc, "102", "1200 500000000 3\n")
    _schedstat(proc, "103", "")  # a thread on its way out
    os.makedirs(os.path.join(proc, "task", "104"))  # one that has gone
    reg = metrics.registry()
    compile_cache._collect_host(reg, proc)
    wait = reg.counter(train_path.PROCESS_RUNQUEUE_WAIT_SECONDS)
    assert wait.value() == pytest.approx(3.0)
    _schedstat(proc, "105", "5 250000000 1\n")
    compile_cache._collect_host(reg, proc)
    assert wait.value() == pytest.approx(3.25)
    # a thread that exits takes its share along: the counter stays
    os.remove(os.path.join(proc, "task", "101", "schedstat"))
    compile_cache._collect_host(reg, proc)
    assert wait.value() == pytest.approx(3.25)
    assert reg.counter(train_path.PROCESS_CPU_SECONDS).value() > 0


@pytest.mark.parametrize("there", ["no_proc", "no_schedstat"])
def test_host_hook_publishes_nothing_where_the_kernel_gives_none(
        tmp_path, there):
    """A kernel without scheduler statistics (the chip tool's sandbox is
    one) has the threads' directories and no ``schedstat`` in them."""
    from chainermn_tpu.utils import compile_cache

    metrics.reset()
    proc = str(tmp_path / "self")
    if there == "no_schedstat":
        os.makedirs(os.path.join(proc, "task", "101"))
    compile_cache._collect_host(metrics.registry(), proc)
    snap = metrics.registry().snapshot()
    assert train_path.PROCESS_RUNQUEUE_WAIT_SECONDS not in snap
    assert snap[train_path.PROCESS_CPU_SECONDS]["values"][0]["value"] > 0


def test_use_compile_cache_hangs_the_hooks_on_the_registry(monkeypatch,
                                                           tmp_path):
    from chainermn_tpu.utils import compile_cache

    metrics.reset()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    snap = metrics.registry().snapshot()
    assert snap[train_path.PROCESS_CPU_SECONDS]["values"][0]["value"] > 0
    assert (train_path.PROCESS_RUNQUEUE_WAIT_SECONDS in snap) == \
        os.path.exists("/proc/self/schedstat")

# -- the feed ------------------------------------------------------------

def test_feed_counters_on_a_host_iterator():
    metrics.reset()
    from chainermn_tpu.training import prefetch

    base = (prefetch._FEED.batches, prefetch._FEED.nbytes)
    batches = [{"x": np.full((4, 3), i, np.float32),
                "y": np.arange(4, dtype=np.int32)} for i in range(5)]
    got = list(prefetch_to_device(iter(batches), size=2))
    assert [int(b["x"][0, 0]) for b in got] == [0, 1, 2, 3, 4]
    assert prefetch._FEED.batches - base[0] == 5
    assert prefetch._FEED.nbytes - base[1] == 5 * (4 * 3 * 4 + 4 * 4)
    # a scrape publishes the process's totals; a second one adds nothing
    for _ in range(2):
        snap = metrics.registry().snapshot()
        assert snap[train_path.FEED_BATCHES]["values"][0]["value"] == \
            prefetch._FEED.batches
        assert snap[train_path.FEED_BYTES]["values"][0]["value"] == \
            prefetch._FEED.nbytes
        assert 0 <= snap[train_path.FEED_NOT_READY]["values"][0]["value"] \
            <= prefetch._FEED.batches


# -- host spans on the profiler's clock ----------------------------------

def _host_events(logdir):
    from jax.profiler import ProfileData

    pb = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return [(e.name, int(e.start_ns), int(e.duration_ns),
             # only the step annotation's stats are read (step_num)
             dict(e.stats) if e.name == train_path.TRAINER_STEP else {})
            for plane in ProfileData.from_file(pb).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("recorder", [False, True])
def test_span_lands_in_a_cpu_profile(tmp_path, recorder):
    rec = trace.enable(None) if recorder else None
    try:
        jax.profiler.start_trace(str(tmp_path))
        with trace.span("chainermn.test.phase", detail=7):
            jnp.ones((4,)).block_until_ready()
        jax.profiler.stop_trace()
    finally:
        trace.disable()
    named = [e for e in _host_events(str(tmp_path))
             if e[0] == "chainermn.test.phase"]
    assert len(named) == 1 and named[0][2] > 0
    if recorder:
        spans = [e for e in rec.events if e["kind"] == "span"]
        assert [e["name"] for e in spans] == ["chainermn.test.phase"]
        assert spans[0]["ok"] and spans[0]["detail"] == 7
    else:
        assert trace.active() is None


def test_feed_spans_in_a_profile(tmp_path):
    batches = [np.ones((2, 2), np.float32)] * 3
    jax.profiler.start_trace(str(tmp_path))
    list(prefetch_to_device(iter(batches), size=1))
    jax.profiler.stop_trace()
    names = [e[0] for e in _host_events(str(tmp_path))]
    assert names.count(train_path.FEED_PUT) == 3
    assert names.count(train_path.FEED_NEXT) == 4  # the last finds the end


def test_trainer_profile_groups_by_step(tmp_path):
    from chainermn_tpu.training.trainer import Trainer

    comm = chainermn_tpu.create_communicator(
        "naive", devices=jax.devices("cpu")[:2])
    opt = mno(optax.sgd(0.1), comm)
    state = create_train_state(
        {"w": jnp.ones((16, 8)), "b": jnp.zeros((8,))}, opt, comm)
    step = make_train_step(_loss, opt, comm)
    data = [np.ones((4, 16), np.float32)] * 3
    trainer = Trainer(step, state, data, comm,
                      collate=lambda b: b, log_interval=1,
                      out=open(os.devnull, "w"))
    jax.profiler.start_trace(str(tmp_path))
    trainer.run(3)
    jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    steps = sorted(int(stats["step_num"]) for name, _, _, stats in events
                   if name == train_path.TRAINER_STEP)
    assert steps[:3] == [1, 2, 3]
    names = [e[0] for e in events]
    for span in (train_path.TRAINER_DATA_WAIT, train_path.TRAINER_H2D,
                 train_path.TRAINER_LOG):
        assert names.count(span) >= 3, span
