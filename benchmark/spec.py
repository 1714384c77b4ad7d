"""Finding the benchmark's data by name.

Everything that belongs to one cell, configuration, traffic mix, family,
loop or per-layer metric sits in a file of its own under a *root*
(``benchmark/`` itself, and in the tests a throw-away directory beside
it). A later PR adds files and entries of ``BENCHMARK.json``; nothing
here names a cell, a model or a metric.

    workloads/<cell>.json      config, traffic, chips, why, job
    configs/<config>.json      source, sizes, assumed, reduced, family
    traffic/<mix>.json         what the job is; feed; samples.<kind>; loop
    traffic/gen_<kind>.py      the generator of one kind of sample
    families/<family>.py       model, loss, FLOPs through the program's API
    reference/<family>.py      the plain float32 reference
    loops/<loop>.py            how a cell is driven and timed
    layer_metrics/<name>.py    one per-layer metric's reader

What a mix decides today: the loop, the feed's depth, the warm-up steps
and the parameters of the sample generators (pool size, document
lengths, token skew). The three mixes that exist choose the same values
for all of these, so between them a mix is so far a label for the job a
cell stands for; what does differ between those jobs (per-chip batch,
remat) depends on the configuration too and sits in the cell's file. A
mix with another feed, pool or loop is a new file here and no code.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


class Roots:
    """The directories searched for a named file, first hit wins. The
    benchmark's own directory comes first, so a test's throw-away root
    adds files and cannot shadow one that is there."""

    def __init__(self, extra: tuple[str, ...] = ()):
        self.dirs = (HERE,) + tuple(extra)
        self._modules: dict[str, object] = {}

    def path(self, *parts: str) -> str:
        for d in self.dirs:
            p = os.path.join(d, *parts)
            if os.path.exists(p):
                return p
        raise FileNotFoundError(
            f"no {os.path.join(*parts)} under {', '.join(self.dirs)}"
        )

    def json(self, *parts: str) -> dict:
        with open(self.path(*parts)) as f:
            return json.load(f)

    def module(self, *parts: str):
        """Import ``<root>/<parts>.py`` by path, once."""
        p = self.path(*parts[:-1], parts[-1] + ".py")
        if p not in self._modules:
            name = "benchmark_" + "_".join(parts)
            spec = importlib.util.spec_from_file_location(name, p)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[name] = mod
            spec.loader.exec_module(mod)
            self._modules[p] = mod
        return self._modules[p]


def load_benchmark(path: str | None = None) -> dict:
    with open(path or os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def metrics_of(cell: str, entries: list[dict]) -> list[dict]:
    """The entries of ``end_to_end`` or ``per_layer`` that this cell
    reports: all without a ``workloads`` list, and those that name it."""
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(roots: Roots, name: str) -> dict:
    """A cell with its configuration and mix resolved."""
    cell = roots.json("workloads", name + ".json")
    cell["name"] = name
    cell["config_spec"] = roots.json("configs", cell["config"] + ".json")
    cell["mix"] = roots.json("traffic", cell["traffic"] + ".json")
    return cell
