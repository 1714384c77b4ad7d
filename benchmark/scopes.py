"""Reading what the program names from the inside: its device scopes, its
host spans and its counters.

**Device scopes.** The program puts ``jax.named_scope`` names round the
parts of a training step; they reach the compiled step as the ``op_name``
of each instruction's metadata (``hlo.op_names``). JAX wraps a name in its
own markers where it differentiates or recomputes the code inside
(``loss_and_grad/transpose(jvp(lm_head))/...``,
``.../checkpoint/rematted_computation/...``), so a name is looked for
anywhere in an ``op_name``, never as a path component. The time of a
scope is, per step and device, the self time (``xplane.reduce``: an op's
time less that of the ops nested in it) of the traced ops whose own
``op_name`` holds the name. A fusion counts where XLA's metadata for the
fusion says, whatever it fused; XLA's own copies carry no ``op_name`` and
count under no scope.

**Host spans.** The program's spans (``chainermn.*``) are
``jax.profiler.TraceAnnotation``s in the run's ``.xplane.pb``, on the
clock of the device's ops. ``xplane.event_table`` keeps only the
benchmark's own (``bench.*``), so they are read again from the file the
traced run left under ``.benchmark_out/trace/<cell>/``.

**Counters.** ``ctx["program_metrics"]`` is the snapshot of the program's
metrics registry the loop takes after the window.

The names are spelled here, not imported from the program: a scope,
span or counter the program renames shows as a missing metric, and every
reader returns ``None`` where the run (the parent of the PR that brought
the name, say) has nothing under it.
"""

from __future__ import annotations

import functools
import os

import hlo
import spec
import xplane

LOSS_AND_GRAD = "loss_and_grad"
GRAD_REDUCE = "grad_reduce"
OPTIMIZER_UPDATE = "optimizer_update"
LM_HEAD = "lm_head"
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"
#: JAX's markers of the transposed (backward) and the recomputed code
BACKWARD = "transpose("
REMAT = "rematted_computation"

PROGRAM_SPAN_PREFIX = "chainermn."
FEED_PUT = "chainermn.feed.put"


@functools.lru_cache(maxsize=2)
def _op_names(hlo_text: str) -> dict:
    return hlo.op_names(hlo_text)


def scope_ms(ctx, holds, lacks=(), *, category=None, not_category=None):
    """Per step and device, ms of self time of the traced ops whose
    ``op_name`` holds every name in ``holds`` and none in ``lacks``,
    optionally of (or not of) one of ``hlo.categorize``'s categories.
    ``None`` where the run has no device trace, or where no instruction of
    the compiled step carries the names at all (0.0 where some do and none
    of them is a traced op of its own: fused into another scope's op). A
    test of a recorded run hands the names in as ``ctx["op_names"]``."""
    names = ctx.get("op_names") or _op_names(ctx["hlo_text"])

    def wanted(source: str) -> bool:
        return all(h in source for h in holds) and \
            not any(x in source for x in lacks)

    if not ctx["trace"] or not any(wanted(s) for s in names.values()):
        return None

    def time_of(r):
        return sum(
            dur for name, cat, dur in r["ops"]
            if wanted(names.get(name, ""))
            and (category is None or cat == category)
            and (not_category is None or cat != not_category))

    return xplane.per_step_ms(ctx["trace"], time_of)


def program_spans(xplane_path: str) -> list[list]:
    """``[name, start_ns, duration_ns]`` of the program's own host spans
    in a trace, by start."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(PROGRAM_SPAN_PREFIX))
    return sorted(spans, key=lambda s: s[1])


def spans_of_run(ctx) -> list[list]:
    """The program's spans of this run's traced stretch; ``[]`` where the
    run left no trace. A test hands them in as ``ctx["program_spans"]``."""
    if "program_spans" in ctx:
        return ctx["program_spans"]
    if not ctx.get("host_spans"):  # no traced stretch in this run
        return []
    trace_dir = os.path.join(spec.CHECKOUT, ".benchmark_out", "trace",
                             ctx["cell"]["name"])
    try:
        return program_spans(xplane.find_xplane(trace_dir))
    except FileNotFoundError:
        return []


def span_mean_ms(ctx, name: str):
    """Mean duration in ms of the program's spans called ``name`` in the
    traced stretch; ``None`` where there is none."""
    durations = [d for n, _, d in spans_of_run(ctx) if n == name]
    if not durations:
        return None
    return sum(durations) / len(durations) / 1e6


def counter(ctx, name: str):
    """A counter or gauge of the program's registry, summed over its
    labels; ``None`` where the program publishes none of that name."""
    family = ctx.get("program_metrics", {}).get(name)
    if not family or not family.get("values"):
        return None
    return float(sum(row["value"] for row in family["values"]))
