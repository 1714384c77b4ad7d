"""Recorded event tables for the tests of the trace reduction, and a look
at a raw trace by hand.

    python3 benchmark/tests/trace_table.py cut <cell> <out.table.json.gz>
    python3 benchmark/tests/trace_table.py describe <cell>

Both read what a ``--trace 1`` run of ``<cell>`` left in
``.benchmark_out/trace/<cell>/``: the profiler's ``.xplane.pb`` and the
compiled step's HLO text. ``cut`` writes the table that ``data/`` keeps;
``describe`` prints a trace's planes, lines and a few events of each:
what to look at before trusting the reduction on a new runtime.
"""

from __future__ import annotations

import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import hlo  # noqa: E402
import xplane  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def cut(table: dict, path: str, runs: int = 2) -> None:
    """Write a table small enough to keep under ``data/``: on each device
    the second to the ``runs + 1``-th run of ``table["module"]`` and the
    events inside them, times counted from the first kept run."""
    out = {k: v for k, v in table.items()
           if k not in ("devices", "host_spans")}
    out["devices"] = {}
    t0 = None
    for name, dev in table["devices"].items():
        mods = sorted(dev["modules"], key=lambda m: m[1])
        if table.get("module"):
            mods = [m for m in mods if table["module"] in m[0]]
        keep = mods[1:runs + 1]
        if not keep:
            continue
        w0, w1 = keep[0][1], keep[-1][1] + keep[-1][2]
        t0 = w0 if t0 is None else min(t0, w0)
        out["devices"][name] = {
            key: [e for e in dev.get(key, ())
                  if e[1] >= w0 and e[1] + e[2] <= w1]
            for key in xplane.LINES.values()}
    t0 = t0 or 0
    for dev in out["devices"].values():
        for key in dev:
            dev[key] = [[n, s - t0, d] for n, s, d in dev[key]]
    out["host_spans"] = [[n, s - t0, d] for n, s, d in table["host_spans"]
                         if s - t0 >= 0]
    with gzip.open(path, "wt") as f:
        json.dump(out, f, separators=(",", ":"))


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def describe(xplane_path: str, per_line: int = 4) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane_path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:per_line]:
                stats = {k: v for k, v in list(e.stats)[:8]}
                print(f"    {e.name!r} start={e.start_ns} "
                      f"dur={e.duration_ns} {stats}")


def main(argv) -> int:
    verb, cell = argv[0], argv[1]
    trace_dir = os.path.join(CHECKOUT, ".benchmark_out", "trace", cell)
    pb = xplane.find_xplane(trace_dir)
    if verb == "describe":
        describe(pb)
        return 0
    with open(os.path.join(trace_dir, "step.hlo.txt")) as f:
        hlo_text = f.read()
    cut({**xplane.event_table(pb), "module": hlo.module_name(hlo_text),
         "categories": {k: v for k, v in hlo.categorize(hlo_text).items()
                        if v != hlo.OTHER}}, argv[2])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
