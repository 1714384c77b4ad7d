"""The training loop: how a training cell is set up, checked, driven and
timed.

Set-up (all of it counted in ``setup_s``): data pool from the seed on the
host, weights from the seed on the device in one jitted call, the
comparison with the plain reference (``correct.py``), the program's own
communicator -> multi-node optimizer -> train state -> train step,
lowered and compiled ahead of time (or loaded from the persistent cache),
the feed through the program's ``prefetch_to_device``, warm-up steps.

The measured window: each turn of the loop takes a batch from the feed,
dispatches the compiled step, and only then waits for the loss of the step
*before* the one it just dispatched and stamps its completion. One step is
always in flight ahead, so the device never waits for the host's stamp,
every step gives a sample, and nothing compiles (the executable is AOT).
The window opens on a completion stamp after warm-up and closes on the
first completion at or after ``seconds``.
"""

from __future__ import annotations

import itertools
import json
import math
import os
import shutil
import statistics
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import correct  # benchmark/ is on the path of whoever loads a loop
import hlo
import xplane

#: the traced stretch of a ``--trace 1`` run: about ten steps, at most 3 s
TRACE_STEPS = 10
TRACE_MAX_S = 3.0
#: steps of the window that run before the traced stretch starts
TRACE_AFTER_STEPS = 3


def _say(**kw) -> None:
    print(json.dumps(kw, default=str), flush=True)


def _reference_checks(env, fam, ref, comm, params, check_params, model_state,
                      first_batch):
    """Checks (a), (c) and the reference side of (b), before the optimizer
    state exists, so that the float32 reference has room. (a) and (c) run
    on ``check_params`` (the family says where they differ from the
    parameters the cell trains), (b) on ``params``."""
    from chainermn_tpu.optimizers import allreduce_gradients
    from jax import shard_map

    cell, config = env["cell"], env["cell"]["config_spec"]
    n = comm.size
    checks, tol = [], ref.TOLERANCES
    check_batch = env["gen"].pool(
        env["seed"] + 1_000_003, {**env["samples"], "pool_batches": 1},
        **fam.pool_args(n * fam.check_rows))[0]

    # model_state travels as an argument: closed over, its values would
    # be constants of the program and no seed would find another's compile
    def sys_loss(p, batch, mstate):
        out = fam.loss_fn(p, batch, mstate) if jax.tree.leaves(mstate) \
            else fam.loss_fn(p, batch)
        return out[0] if isinstance(out, tuple) else out

    def highest(fn):
        """The reference runs its float32 matmuls at full precision; the
        system's own programs are traced outside this."""
        def call(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)
        return call

    ref_vg = highest(jax.jit(jax.value_and_grad(
        lambda p, b, m: ref.loss(p, m, b, config))))
    ref_fwd = highest(jax.jit(lambda p, b, m: ref.loss(p, m, b, config)))
    # the reference runs replicated on the cell's mesh, from the
    # parameters where they already are: no second copy of them
    replicated = NamedSharding(comm.mesh, P())

    def put(rows):
        return jax.device_put(rows, replicated)

    # the reference's loss and gradient on each chip's rows, and their mean
    def rows_of_chip(c):
        return fam.take_rows(check_batch, c * fam.check_rows,
                             (c + 1) * fam.check_rows)

    ref_l, ref_g = ref_vg(check_params, put(rows_of_chip(0)), model_state)
    for c in range(1, n):
        l, g = ref_vg(check_params, put(rows_of_chip(c)), model_state)
        ref_l, ref_g = ref_l + l, jax.tree.map(jnp.add, ref_g, g)
        del g
    ref_l, ref_g = ref_l / n, jax.tree.map(lambda x: x / n, ref_g)

    if n == 1:
        # (a) the system's loss function against the reference
        sys_l, sys_g = jax.jit(jax.value_and_grad(sys_loss))(
            check_params, put(rows_of_chip(0)), model_state)
        grads_check = "a.grads_vs_reference"
    else:
        # (a) and (c) in one program: each chip's loss and gradient on its
        # own rows, the gradient through the reduction at the cell's wire
        # dtype, against the reference's mean over the chips
        wire = config["training"]["allreduce_grad_dtype"]
        axes = comm.grad_axes

        def local(p, batch, mstate):
            loss, grads = jax.value_and_grad(sys_loss)(p, batch, mstate)
            return jax.lax.pmean(loss, axes), allreduce_gradients(
                grads, comm,
                compress_dtype=jnp.dtype(wire).type if wire else None)

        sys_l, sys_g = jax.jit(shard_map(
            local, mesh=comm.mesh, in_specs=(P(), P(axes), P()),
            out_specs=P(), check_vma=False,
        ))(check_params, jax.device_put(
            check_batch, NamedSharding(comm.mesh, P(axes))), model_state)
        grads_check = "c.allreduce_gradients_vs_reference_mean"
    checks.append(correct.compare_loss(
        "a.loss_fn_vs_reference", float(sys_l), float(ref_l), tol))
    checks.append(correct.compare_grads(grads_check, sys_g, ref_g, tol))
    del sys_g, ref_g

    # (b), reference side: forward only on the run's first batch, in
    # blocks of rows (a block is one chip's rows where the batch
    # statistics couple them)
    per_chip = cell["job"]["per_chip_batch"]
    block = fam.reference_block or per_chip
    total = fam.rows_of(first_batch)
    losses = [float(ref_fwd(
        params, put(fam.take_rows(first_batch, s, s + block)), model_state))
        for s in range(0, total, block)]
    ref_first = sum(losses) / len(losses)
    return checks, ref_first


def run(env: dict) -> dict:
    import chainermn_tpu
    from chainermn_tpu import tuning
    from chainermn_tpu.observability.metrics import (
        registry as metrics_registry,
    )
    from chainermn_tpu.training import make_train_step
    from chainermn_tpu.training.prefetch import prefetch_to_device
    from chainermn_tpu.training.train_step import create_train_state

    cell, roots = env["cell"], env["roots"]
    config, mix, job = cell["config_spec"], cell["mix"], cell["job"]
    chips, seed = cell["chips"], env["seed"]
    fam_mod = roots.module("families", config["family"])
    ref = roots.module("reference", config["family"])
    fam = fam_mod.build(config, job)
    env["samples"] = mix["samples"][fam_mod.SAMPLE_KIND]
    env["gen"] = gen = roots.module("traffic", "gen_" + fam_mod.SAMPLE_KIND)
    marks = {"start": env["t0"]}

    wire = config["training"]["allreduce_grad_dtype"]
    comm = chainermn_tpu.create_communicator(
        "xla", devices=env["devices"][:chips], allreduce_grad_dtype=wire)
    if comm.size != chips:
        raise RuntimeError(f"the communicator spans {comm.size} devices, "
                           f"the cell asks for {chips}")

    # -- data and weights, from the seed
    rows = chips * job["per_chip_batch"]
    pool = gen.pool(seed, env["samples"], **fam.pool_args(rows))
    marks["data"] = time.perf_counter()
    params, model_state, check_params = fam.init(seed)
    params = comm.bcast_data(params)
    if jax.tree.leaves(model_state):
        model_state = comm.bcast_data(model_state)
    check_params = params if check_params is None \
        else comm.bcast_data(check_params)
    jax.block_until_ready((params, check_params))
    marks["weights"] = time.perf_counter()

    # -- the comparison with the reference, while there is room
    checks, ref_first = _reference_checks(
        env, fam, ref, comm, params, check_params, model_state, pool[0])
    del check_params
    marks["reference"] = time.perf_counter()
    for c in checks:
        _say(**c)

    # -- the program's own front door
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        fam.inner_optimizer(), comm)
    state = create_train_state(params, optimizer, comm,
                               model_state=model_state)
    del params, model_state
    step = make_train_step(fam.loss_fn, optimizer, comm)
    batch_sharding = NamedSharding(comm.mesh, P(comm.grad_axes))
    feed = prefetch_to_device(itertools.cycle(pool),
                              size=int(mix["feed"]["depth"]),
                              sharding=batch_sharding)
    first = next(feed)
    t = time.perf_counter()
    lowered = step.lower(state, first)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t
    marks["compiled"] = time.perf_counter()
    hlo_text = compiled.as_text()
    mem = compiled.memory_analysis()
    all_reduces = hlo.all_reduce_count(hlo_text)
    mosaic_calls = hlo.mosaic_call_count(hlo_text)
    _say(compile_s=compile_s, all_reduces_in_step=all_reduces,
         mosaic_calls_in_step=mosaic_calls,
         decisions=tuning.decisions_taken())
    # the step is the program the cell is about: on a TPU a family with a
    # hand-written kernel runs it compiled (an interpreted one lowers to
    # plain HLO), and several chips reduce their gradients
    if env["devices"][0].platform == "tpu" and \
            fam_mod.kernel_costs(config, job):
        checks.append({"check": "f.kernel_compiled_by_mosaic",
                       "ok": mosaic_calls > 0})
        _say(**checks[-1])
    if chips > 1:
        checks.append({"check": "g.all_reduce_in_step",
                       "ok": all_reduces > 0})
        _say(**checks[-1])

    # -- warm-up; its first step is check (b)
    losses = []
    batch = first
    for i in range(int(mix["warmup_steps"])):
        state, metrics = compiled(state, batch)
        losses.append(float(metrics["loss"]))
        batch = next(feed)
    checks.append(correct.compare_loss(
        "b.first_step_loss_vs_reference_forward", losses[0], ref_first,
        ref.TOLERANCES))
    _say(**checks[-1])

    # -- the measured window
    per_step = rows * fam.samples_per_row
    annotate = jax.profiler.TraceAnnotation
    trace_dir = os.path.join(env["out_dir"], "trace", cell["name"])
    tracing, traced, trace_t0, trace_from = False, False, 0.0, 0
    stamps, waits, failed = [], [], 0
    state, metrics = compiled(state, batch)  # the step in flight ahead
    pending = metrics["loss"]
    while True:
        t = time.perf_counter()
        with annotate("bench.next_batch"):
            batch = next(feed)
        wait = time.perf_counter() - t
        with annotate("bench.dispatch"):
            state, metrics = compiled(state, batch)
        with annotate("bench.wait"):
            loss = float(pending)
        now = time.perf_counter()
        pending = metrics["loss"]
        losses.append(loss)
        stamps.append(now)
        if len(stamps) == 1:
            continue  # this completion opens the window
        waits.append(wait)
        failed += not math.isfinite(loss)
        done = len(stamps) - 1
        if env["trace"]:
            if not traced and not tracing and done >= TRACE_AFTER_STEPS:
                shutil.rmtree(trace_dir, ignore_errors=True)
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0  # bench.* spans suffice
                # (the host tracer still stalls a feed of tens of MB a
                # step: PERF.md, PR 22, the ResNet cell's traced run)
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                tracing, trace_t0, trace_from = True, now, done
            elif tracing and (done - trace_from >= TRACE_STEPS + 2
                              or now - trace_t0 >= TRACE_MAX_S):
                jax.block_until_ready(pending)
                jax.profiler.stop_trace()
                tracing, traced = False, True
        if now - stamps[0] >= env["seconds"] and not tracing:
            break
    losses.append(float(pending))  # drain the step in flight; not counted
    jax.block_until_ready(state)
    t_open = marks["window"] = stamps[0]

    # -- after the window: (d) finite losses, (e) identical replicas
    checks.append({"check": "d.every_loss_finite", "ok": failed == 0
                   and all(math.isfinite(x) for x in losses)})
    _say(**checks[-1])
    if chips > 1:
        checks.append({"check": "e.replicas_bit_identical",
                       "ok": correct.replicas_identical(
                           state.params, comm.mesh, comm.grad_axes)})
        _say(**checks[-1])

    n_steps = len(stamps) - 1
    span = stamps[-1] - stamps[0]
    gaps_ms = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    rate = n_steps * per_step / span
    unit = fam_mod.SAMPLE_UNIT
    flops = fam_mod.model_flops_per_sample(config, job)
    values = {
        f"{unit}_per_s": rate,
        "step_ms": statistics.median(gaps_ms),
        "mfu_pct": 100.0 * rate * flops / (chips * env["peak"]["bf16_flops"]),
        "setup_s": t_open - env["t0"],
    }
    _say(samples=n_steps, step_ms_median=values["step_ms"],
         step_ms_min=min(gaps_ms), step_ms_max=max(gaps_ms),
         window_s=span, loss_first=losses[0], loss_last=losses[-1],
         setup_parts={k: marks[k] - marks[p] for p, k in
                      zip(list(marks), list(marks)[1:])})

    stats = [d.memory_stats() or {} for d in comm.mesh.devices.flat]
    mem_total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    context = {
        "cell": cell, "family": fam_mod, "peak": env["peak"],
        "hlo_text": hlo_text, "memory_analysis": mem,
        "step_memory_bytes": mem_total,
        "decisions": tuning.decisions_taken(),
        "program_metrics": metrics_registry().snapshot(),
        "loop": {"compile_s": compile_s, "input_wait_s": waits,
                 "steps": n_steps, "all_reduces": all_reduces,
                 "mosaic_calls": mosaic_calls},
        "trace": {}, "host_spans": [],
    }
    if env["trace"] and traced:
        # the step's HLO text stays beside the trace it explains
        # (tests/trace_table.py cuts a recorded table from the two)
        with open(os.path.join(trace_dir, "step.hlo.txt"), "w") as f:
            f.write(hlo_text)
        table = xplane.event_table(xplane.find_xplane(trace_dir))
        context["host_spans"] = table["host_spans"]
        context["trace"] = xplane.reduce(
            table, hlo.categorize(hlo_text), hlo.module_name(hlo_text))
    return {
        "correct": all(c["ok"] for c in checks),
        "attempted": n_steps, "failed": failed,
        "values": values, "context": context,
        "memory_peak_bytes": max(
            [s.get("peak_bytes_in_use", 0) for s in stats] + [mem_total]),
    }
