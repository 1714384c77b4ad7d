"""What the controls tools of the benchmark's families do alike
(``moe_controls.py``, ``loop_controls.py``): their arguments, the cell
at the published widths or the benchmark tests' throw-away one, the JSON
lines they write, and a reading taken as ``correct`` takes check (a)."""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_HERE, "benchmark"))
sys.path.insert(0, _HERE)


def parser(doc: str) -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; a seed is one check row")
    ap.add_argument("--out", required=True, help="JSON lines, appended")
    ap.add_argument("--tiny", action="store_true")
    return ap


def benchmark_test(name: str):
    """``benchmark/tests/<name>.py`` as a module: the tiny configurations
    live there."""
    where = importlib.util.spec_from_file_location(
        "benchmark_tests_" + name,
        os.path.join(_HERE, "benchmark", "tests", name + ".py"))
    tests = importlib.util.module_from_spec(where)
    where.loader.exec_module(tests)
    return tests


def writer(path: str):
    """``say(**row)``: a JSON line on the output and appended to ``path``."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    out = open(path, "a")

    def say(**row):
        line = json.dumps(row, default=str)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()
    return say


def highest(fn):
    """The float32 reference runs its matmuls at full precision."""
    import jax

    def call(*a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)
    return call


class Patched:
    """``obj.name = value`` for a ``with`` block. A ``jax.jit`` traces at
    its first call: that call has to sit inside the block."""

    def __init__(self, obj, name, value):
        self.obj, self.name, self.value = obj, name, value

    def __enter__(self):
        self.old = getattr(self.obj, self.name)
        setattr(self.obj, self.name, self.value)

    def __exit__(self, *exc):
        setattr(self.obj, self.name, self.old)


def check_batch(gen, fam, samples, seed: int):
    """Check (a)'s batch of this seed, as ``loops/train.py`` draws it."""
    import jax.numpy as jnp

    return jnp.asarray(gen.pool(
        seed + 1_000_003, {**samples, "pool_batches": 1},
        **fam.pool_args(fam.check_rows))[0])


def reading(tol, what, seed, got, want, t0, **extra) -> dict:
    """One row: ``got = (loss, grads)`` against ``want``, by
    ``correct.py``'s norms under the limits ``tol``."""
    import correct

    a = correct.compare_loss("loss", float(got[0]), float(want[0]), tol)
    b = correct.compare_grads("grads", got[1], want[1], tol)
    return dict(what=what, seed=seed, loss=float(got[0]),
                loss_rel_err=a["rel_err"], tree_rel_err=b["tree_rel_err"],
                worst_leaf=b["worst_leaf"],
                worst_leaf_rel_err=b["worst_leaf_rel_err"],
                loss_ok=a["ok"], grads_ok=b["ok"],
                refused=not (a["ok"] and b["ok"]),
                seconds=time.perf_counter() - t0, **extra)
