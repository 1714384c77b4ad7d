"""From the profiler's ``.xplane.pb`` to what the per-layer readers get.

Two steps, kept apart so that the second can be checked off the chip:

``event_table(path)`` reads the trace with ``jax.profiler.ProfileData``
and keeps, for each device, the XLA ops and the runs of whole modules
(name, start, duration in ns), and the benchmark's own host spans
(``bench.*``). That table is plain JSON; recorded ones live under
``tests/data`` (``tests/trace_table.py`` cuts them).

``reduce(table, categories, module)`` turns a table into, for each device:
the traced window (first start to last end of the whole runs of the step's
module inside the trace), the steps in it, busy time as the union of op
intervals, time per category, the time collectives were in flight and the
part of it in which nothing else ran, the ops that took most time, and the
longest idle gaps with the host span each falls in.
"""

from __future__ import annotations

import re
from collections import defaultdict

#: lines of a TPU device plane that the reduction reads: every op for
#: its time on the core; asynchronous ops (copies, collectives) from
#: their start to their done; the runs of whole modules
LINES = {"XLA Ops": "ops", "Async XLA Ops": "async", "XLA Modules": "modules"}
HOST_SPAN_PREFIX = "bench."


def find_xplane(logdir: str) -> str:
    import glob
    import os

    found = sorted(glob.glob(
        os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def event_table(xplane_path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices: dict[str, dict] = {}
    host_spans: list[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(
                plane.name, {key: [] for key in LINES.values()})
            for line in plane.lines:
                key = LINES.get(line.name)
                if key is None:
                    continue
                # an op event is named by its whole HLO instruction:
                # keep the instruction's name
                dev[key].extend(
                    [e.name if key == "modules" else hlo_name(e.name),
                     int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                )
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans.extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(HOST_SPAN_PREFIX)
                )
    return {"devices": devices, "host_spans": sorted(host_spans,
                                                     key=lambda s: s[1])}


def hlo_name(event_name: str) -> str:
    """The HLO instruction an op event is named after."""
    return event_name.lstrip("%").split(" ")[0].split("(")[0]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Length of the part of ``a`` (a union) not covered by ``b`` (a union)."""
    covered = 0
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return _length(a) - covered


def _self_times(ops: list) -> list[int]:
    """Each op's duration less that of the ops nested directly inside its
    interval: a ``while`` or a ``call`` is an event that spans its body's
    events, and would otherwise count their time twice. ``ops`` is sorted
    by start (ties: the longer first)."""
    own = [d for _, _, d in ops]
    stack: list[int] = []  # indices of the open enclosing ops
    for i, (_, start, dur) in enumerate(ops):
        while stack and start >= ops[stack[-1]][1] + ops[stack[-1]][2]:
            stack.pop()
        if stack and start + dur <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= dur
        stack.append(i)
    return own


def _collective_flights(dev: dict, w0: int, w1: int, categories
                        ) -> list[tuple[int, int]]:
    """Intervals in which a collective is in flight. The runtime's line of
    asynchronous ops gives each from its start to its done. Where that
    line has none, a synchronous collective flies for its own duration and
    an asynchronous pair on the op line from the start of its ``-start``
    to the end of the matching ``-done`` (in order, per kind)."""
    def inside(events):
        return [e for e in events if e[1] >= w0 and e[1] + e[2] <= w1
                and categories.get(hlo_name(e[0])) == "collective"]

    flights = [(s, s + d) for _, s, d in inside(dev.get("async", []))]
    pair_up = not flights
    open_starts: dict[str, list[int]] = defaultdict(list)
    for name, start, dur in sorted(inside(dev["ops"]), key=lambda o: o[1]):
        base = re.sub(r"[.\d]+$", "", hlo_name(name))
        if base.endswith("-start"):
            open_starts[base[:-6]].append(start)
        elif base.endswith("-done"):
            begun = open_starts[base[:-5]]
            if begun and pair_up:
                flights.append((begun.pop(0), start + dur))
        else:
            flights.append((start, start + dur))
    return flights


def _host_span_at(host_spans, t: int) -> str:
    inner = None
    for name, start, dur in host_spans:
        if start <= t < start + dur and (inner is None or dur < inner[1]):
            inner = (name, dur)
    return inner[0] if inner else "(no bench span)"


def reduce_device(dev: dict, categories: dict, module: str,
                  host_spans: list) -> dict | None:
    runs = [m for m in dev["modules"] if module and module in m[0]]
    if not runs:
        runs = dev["modules"]
    if not runs:
        return None
    runs = sorted(runs, key=lambda m: m[1])
    if len(runs) > 3:
        # the first run after the profiler started follows a drained
        # pipeline and may be recorded in part: leave it out
        runs = runs[1:]
    w0 = min(s for _, s, _ in runs)
    w1 = max(s + d for _, s, d in runs)
    ops = sorted((o for o in dev["ops"]
                  if o[1] >= w0 and o[1] + o[2] <= w1),
                 key=lambda o: (o[1], -o[2]))
    if not ops:
        return None
    n_steps = len(runs)

    by_cat: dict[str, int] = defaultdict(int)
    by_op: dict[tuple[str, str], int] = defaultdict(int)
    compute: list[tuple[int, int]] = []
    for (name, start, dur), own in zip(ops, _self_times(ops)):
        hlo = hlo_name(name)
        cat = categories.get(hlo, "other")
        by_cat[cat] += own
        by_op[(hlo, cat)] += own
        if cat != "collective":
            compute.append((start, start + dur))
    flights = _union(_collective_flights(dev, w0, w1, categories))
    # a collective in flight is the device at work, even between the
    # events of an asynchronous pair
    busy = _union([(s, s + d) for _, s, d in ops] + flights)
    exposed = _subtract(flights, _union(compute))

    gaps = []
    edges = [(w0, w0)] + busy + [(w1, w1)]
    for (_, e0), (s1, _) in zip(edges, edges[1:]):
        if s1 > e0:
            gaps.append((s1 - e0, e0))
    gaps.sort(reverse=True)
    return {
        "window_ns": w1 - w0,
        "busy_ns": _length(busy),
        "steps": n_steps,
        "category_ns": dict(by_cat),
        "collective_flight_ns": _length(flights),
        "collective_exposed_ns": exposed,
        "ops": sorted(((n, c, d) for (n, c), d in by_op.items()),
                      key=lambda x: -x[2]),
        "gaps": [(_host_span_at(host_spans, t0 + g // 2), g)
                 for g, t0 in gaps[:5]],
    }


def reduce(table: dict, categories: dict, module: str = "") -> dict:
    """Device name -> reduction (see module docstring). Devices with no
    whole run of the module in the trace are left out."""
    out = {}
    for name, dev in sorted(table["devices"].items()):
        r = reduce_device(dev, categories, module, table["host_spans"])
        if r is not None:
            out[name] = r
    return out


def mean_over_devices(reduced: dict, value) -> float | None:
    """Mean over the traced devices of ``value(device_reduction)``."""
    vals = [value(r) for r in reduced.values()]
    return sum(vals) / len(vals) if vals else None


def per_step_ms(reduced: dict, nanoseconds) -> float | None:
    """Mean over the traced devices of ``nanoseconds(device_reduction)``
    a step, in ms: what most trace readers report."""
    v = mean_over_devices(reduced, lambda r: nanoseconds(r) / r["steps"])
    return None if v is None else v / 1e6


def op_class(name: str, source: str) -> str:
    """What a breakdown sums over: the source an op was traced from with
    layer and instance numbers wiped (24 unrolled blocks give 24 fusions of
    one kind), or, where the HLO text names no source, the op's own name
    without its number."""
    if source:
        return re.sub(r"(block|Block|layer|_)_?\d+", r"\1*", source)[-90:]
    return re.sub(r"[.\d]+$", "", name)


def breakdown(reduced: dict, sources: dict | None = None) -> dict:
    """The contract's ``breakdown``: per-step seconds of the ten classes of
    op that took most device time (mean over devices; ``[category]
    class``, see :func:`op_class`) and the longest idle gaps by the host
    span they fall in."""
    if not reduced:
        return {}
    sources = sources or {}
    n = len(reduced)
    ops: dict[str, float] = defaultdict(float)
    gaps: list[tuple[str, float]] = []
    for r in reduced.values():
        for name, cat, dur in r["ops"]:
            label = f"[{cat}] {op_class(name, sources.get(name, ''))}"
            ops[label] += dur / r["steps"] / n / 1e9
        gaps.extend((span, g / 1e9) for span, g in r["gaps"])
    return {
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda x: -x[1])[:10]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps, key=lambda x: -x[1])[:5]],
    }
