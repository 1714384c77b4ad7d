"""Recorded tables for the tests of the scope readers.

    python3 benchmark/tests/scope_table.py cut <cell> <out.table.json.gz> \
        [counter=value ...]

Reads what a ``--trace 1`` run of ``<cell>`` left in
``.benchmark_out/trace/<cell>/`` and writes ``trace_table.cut``'s cut of it
with, beside the events, what ``scopes.py`` reads: the ``op_name`` of every
traced op (and, for the instructions that are not traced ops of their own,
one example of each combination of the program's names they hold, so that
"named in the step but fused away" still reads 0 and not nothing), the
program's host spans, and the counters given on the command line (from the
run's own result line: the registry is gone with the process).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import hlo  # noqa: E402
import scopes  # noqa: E402
import trace_table  # noqa: E402
import xplane  # noqa: E402

NAMES = (scopes.LOSS_AND_GRAD, scopes.GRAD_REDUCE, scopes.OPTIMIZER_UPDATE,
         scopes.LM_HEAD, scopes.FLASH_FWD, scopes.FLASH_BWD_DQ,
         scopes.FLASH_BWD_DKV, scopes.BACKWARD, scopes.REMAT)


def op_names_to_keep(table: dict, names: dict) -> dict:
    traced = {e[0] for dev in table["devices"].values() for e in dev["ops"]}
    keep = {n: names[n] for n in traced if n in names}
    seen = set()
    for name, source in names.items():
        held = tuple(s for s in NAMES if s in source)
        if name not in traced and held and held not in seen:
            seen.add(held)
            keep[f"elsewhere.{len(seen)}"] = source
    return keep


def ctx_of(path: str) -> dict:
    """What a reader gets, rebuilt from a recorded table."""
    table = trace_table.load(path)
    return {
        "trace": xplane.reduce(table, table["categories"], table["module"]),
        "op_names": table["op_names"], "hlo_text": "",
        "host_spans": table["host_spans"],
        "program_spans": table["program_spans"],
        "program_metrics": {
            name: {"values": [{"labels": {}, "value": value}]}
            for name, value in table["counters"].items()},
        "cell": {"name": table["cell"]},
        "loop": {"mosaic_calls": sum(
            v == hlo.MOSAIC for v in table["categories"].values())},
    }


def main(argv) -> int:
    verb, cell, out = argv[:3]
    if verb != "cut":
        raise SystemExit(__doc__)
    trace_dir = os.path.join(trace_table.CHECKOUT, ".benchmark_out",
                             "trace", cell)
    pb = xplane.find_xplane(trace_dir)
    with open(os.path.join(trace_dir, "step.hlo.txt")) as f:
        hlo_text = f.read()
    table = xplane.event_table(pb)
    trace_table.cut({
        **table, "cell": cell, "module": hlo.module_name(hlo_text),
        "categories": {k: v for k, v in hlo.categorize(hlo_text).items()
                       if v != hlo.OTHER},
        "op_names": op_names_to_keep(table, hlo.op_names(hlo_text)),
        "program_spans": scopes.program_spans(pb),
        "counters": {k: float(v) for k, v in
                     (arg.split("=", 1) for arg in argv[3:])},
    }, out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
