"""The program's set-up counters, read by program: whose tracing, whose
compile and what the persistent cache loaded. The names are spelled again
here as ``scopes.py`` spells the others (a rename in the program shows as
a missing metric), and every reader returns ``None`` where the run's
registry has nothing under them: the parent of the PR that brought the
``program`` label publishes the compile counters as one unlabelled series
each.

What the host gave the process and made it wait (``process_*``) goes into
the log with the table and is no metric: the kernel of the machine the
benchmark is measured on keeps no scheduler statistics (no ``schedstat``,
and a ``/proc/stat`` of zeros), so the wait has nothing to read there, and
a metric no cell can report is not declared.

``ctx["program_metrics"]`` is the snapshot of the program's metrics
registry the loop takes after the window, so the numbers cover the whole
process: the step, the program's other programs (``init``, the
optimizer's, the feed's) and the harness's own (the reference checks).
"""

from __future__ import annotations

import json

import scopes

#: the label ``make_train_step``'s program carries
#: (``train_path.TRAIN_STEP_PROGRAM``)
TRAIN_STEP_PROGRAM = "local_step"
PROGRAM = "program"

TRACE = "jax_trace_seconds_total"
LOWER = "jax_lower_seconds_total"
BACKEND = "jax_backend_compile_seconds_total"
COMPILED = "programs_compiled_total"
HITS = "compile_cache_hits_total"
MISSES = "compile_cache_misses_total"
RETRIEVAL = "compile_cache_retrieval_seconds_total"
RUNQUEUE_WAIT = "process_runqueue_wait_seconds_total"
CPU = "process_cpu_seconds_total"


def by_program(ctx, name: str):
    """``{program: value}`` of one counter; ``None`` where the program
    publishes none of that name, or publishes it without the label."""
    family = ctx.get("program_metrics", {}).get(name)
    rows = [r for r in (family or {}).get("values", ())
            if PROGRAM in r["labels"]]
    if not rows:
        return None
    return {r["labels"][PROGRAM]: float(r["value"]) for r in rows}


def _of_step(ctx, *names: str):
    series = [by_program(ctx, name) for name in names]
    if all(s is None for s in series):
        return None
    return sum((s or {}).get(TRAIN_STEP_PROGRAM, 0.0) for s in series)


def step_trace_lower_s(ctx):
    """Seconds tracing and lowering the train step's program."""
    return _of_step(ctx, TRACE, LOWER)


def step_backend_s(ctx):
    """Seconds of the backend's compile-or-load of that program."""
    return _of_step(ctx, BACKEND)


def cache_load_s(ctx):
    """Seconds reading and loading what the persistent cache held, every
    program of the process together. 0.0 in a run that hit nothing, if the
    program counts by program at all."""
    loaded = by_program(ctx, RETRIEVAL)
    if loaded is None:
        return None if by_program(ctx, BACKEND) is None else 0.0
    return sum(loaded.values())


def uncached_compile_s(ctx):
    """Backend seconds of the programs that were not loaded from the
    cache: compiled and written to it, or compiled and never written. A
    label all of whose programs were loaded counts nothing, one with no
    hit counts whole; where one name stands for several programs of which
    some were loaded (the harness's lambdas), what their loading took is
    taken off the label's seconds."""
    backend = by_program(ctx, BACKEND)
    if backend is None:
        return None
    compiled = by_program(ctx, COMPILED) or {}
    hits = by_program(ctx, HITS) or {}
    loaded = by_program(ctx, RETRIEVAL) or {}
    total = 0.0
    for program, seconds in backend.items():
        if hits.get(program, 0.0) >= compiled.get(program, 0.0):
            continue
        total += max(0.0, seconds - loaded.get(program, 0.0))
    return total


def host(ctx) -> dict:
    """What the host's clocks read at the snapshot, process start to the
    end of the window: seconds of CPU the process used and, where the
    kernel keeps scheduler statistics, seconds its threads stood runnable
    with no core to run on. Two runs that differ in ``setup_s`` and not
    in ``cpu_s`` did the same work on a host that gave them less."""
    read = {"cpu_s": scopes.counter(ctx, CPU),
            "runqueue_wait_s": scopes.counter(ctx, RUNQUEUE_WAIT)}
    return {key: value for key, value in read.items() if value is not None}


def table(ctx) -> list[dict]:
    """One row a program, longest first: seconds of each stage, programs
    through the backend, and of those how many were loaded from the
    cache, compiled and written, or compiled and never written."""
    columns = {name: by_program(ctx, name) or {} for name in
               (TRACE, LOWER, BACKEND, RETRIEVAL, COMPILED, HITS, MISSES)}
    rows = []
    for program in sorted(set().union(*columns.values())):
        trace, lower, backend, load, compiled, hits, misses = (
            columns[name].get(program, 0.0) for name in columns)
        rows.append({
            "program": program, "trace_s": trace, "lower_s": lower,
            "backend_s": backend, "load_s": load, "compiled": int(compiled),
            "loaded": int(hits), "written": int(misses),
            "neither": int(compiled - hits - misses)})
    return sorted(rows, key=lambda r: -(r["trace_s"] + r["lower_s"]
                                        + r["backend_s"]))


def say_table(ctx) -> None:
    """The table into the traced run's log, one JSON line, with the sums
    the three totals of the benchmark should equal and the host's clocks."""
    rows = table(ctx)
    if not rows:
        return
    print(json.dumps({"setup_by_program": rows, "sums": {
        "trace_lower_s": sum(r["trace_s"] + r["lower_s"] for r in rows),
        "backend_compile_s": sum(r["backend_s"] for r in rows),
        "through_backend": sum(r["compiled"] for r in rows),
        "loaded": sum(r["loaded"] for r in rows)}, "host": host(ctx)}),
        flush=True)
