"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on, which
must hold a TPU with at least the chips the cell asks for, and prints as
the last line of its output one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``. Anywhere else it exits non-zero and prints no result; it
never falls back to another device.

The cell, its configuration, traffic mix, family, reference, loop and
per-layer readers are found by name in files of their own (``spec.py``).
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python allows

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))  # the checkout: the program

import hlo  # noqa: E402
import spec  # noqa: E402
import xplane  # noqa: E402

#: where a run leaves its profiler trace: inside the checkout, fixed
OUT_DIR = os.path.join(spec.CHECKOUT, ".benchmark_out")


def fail(why: str, code: int = 1) -> int:
    print(f"benchmark/run.py: {why}", file=sys.stderr)
    return code


def run_cell(cell_name: str, *, seed: int, seconds: float, trace: bool,
             devices, peak: dict, roots: spec.Roots | None = None,
             benchmark: dict | None = None, t0: float | None = None) -> dict:
    """Run one cell on ``devices`` and return the result line as a dict.
    ``main`` hands it the TPU's devices and the published peak; the tests
    hand it CPU devices, a made-up peak and throw-away cells, and never
    print what comes back as a result."""
    roots = roots or spec.Roots()
    benchmark = benchmark or spec.load_benchmark()
    cell = spec.load_cell(roots, cell_name)
    loop = roots.module("loops", cell["mix"]["loop"])
    out = loop.run({
        "cell": cell, "roots": roots, "seed": seed, "seconds": seconds,
        "trace": trace, "t0": _T0 if t0 is None else t0,
        "devices": list(devices), "peak": peak, "out_dir": OUT_DIR,
    })

    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "used": cell["chips"],
        "memory_peak_bytes": int(out["memory_peak_bytes"]),
    }
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {}, "device": device}
    if not trace:
        for m in spec.metrics_of(cell_name, benchmark["end_to_end"]):
            line["metrics"][m["name"]] = {
                "value": out["values"][m["name"]], "unit": m["unit"]}
        return line

    ctx = out["context"]
    reported = {m["name"] for m in
                spec.metrics_of(cell_name, benchmark["end_to_end"])}
    for m in spec.metrics_of(cell_name, benchmark["per_layer"]):
        if m["moves"] not in reported:
            continue
        value = roots.module("layer_metrics", m["name"]).read(ctx)
        if value is not None:
            line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    reduced = ctx["trace"]
    if reduced:
        device["busy_s"] = xplane.mean_over_devices(
            reduced, lambda r: r["busy_ns"] / 1e9)
        device["window_s"] = xplane.mean_over_devices(
            reduced, lambda r: r["window_ns"] / 1e9)
        line["breakdown"] = xplane.breakdown(
            reduced, hlo.op_names(ctx["hlo_text"]))
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    if not os.path.isfile(os.path.join(spec.CHECKOUT, "chainermn_tpu",
                                       "__init__.py")):
        return fail(f"no program to measure: {spec.CHECKOUT} holds no "
                    "chainermn_tpu package", 2)
    benchmark = spec.load_benchmark()
    if args.workload not in {w["name"] for w in benchmark["workloads"]}:
        return fail(f"BENCHMARK.json has no workload {args.workload!r}", 2)
    chips = next(w["chips"] for w in benchmark["workloads"]
                 if w["name"] == args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU; JAX found platform "
                    f"{devices[0].platform!r} ({devices[0].device_kind} "
                    f"x{len(devices)})")
    if len(devices) < chips:
        return fail(f"{args.workload} needs {chips} chips; JAX found "
                    f"{len(devices)}")
    import peaks

    try:
        peak = peaks.lookup(devices[0].device_kind)
    except KeyError as e:
        return fail(str(e))

    from chainermn_tpu.utils.compile_cache import use_compile_cache

    # where programs are cached and which are worth caching is the
    # program's policy (utils/compile_cache.py): the benchmark measures it
    # as it is. One thing it takes from the measuring machine: a cap on
    # the cache's size. The chip tool's machine sets 192 MiB
    # (JAX_COMPILATION_CACHE_MAX_SIZE); a language-model cell's programs
    # are larger together, so under the cap each run evicts its own first
    # entries before it writes its last and no run ever hits: every run
    # compiles for 225-278 s, the four-chip cell takes 314 of the 360 s a
    # run may take, and the contract's "only the first run of a cell
    # compiles" cannot hold. JAX's own default, which a user of the
    # program gets, is no cap (PERF.md, PR 22).
    cache_dir = use_compile_cache()
    jax.config.update("jax_compilation_cache_max_size", -1)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "seconds": args.seconds, "trace": args.trace,
                      "compile_cache": cache_dir,
                      "jax": jax.__version__}), flush=True)

    line = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devices=devices, peak=peak,
                    benchmark=benchmark)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
