"""ResNet-50 MFU sweep — perf methodology tool for the tracked
``resnet50_images_per_sec`` / ``mfu`` headline (SURVEY.md section 6,
BASELINE.json's benchmark workload; reference:
``examples/imagenet/train_imagenet.py`` †).

The b128 v5e train step is HBM-bandwidth-bound (see the remat note in
:mod:`chainermn_tpu.models.resnet`: ~46 GB touched/step vs ~15 ms of
pure FLOPs), so the knobs that matter are the ones that cut *bytes*:

  - remat mode: ``none`` | ``full`` (save nothing per block — measured
    r2: loses, 57->66 ms) | ``conv`` (save conv outputs, recompute only
    the elementwise BN/relu chain — cuts ~2/3 of saved-activation bytes
    for VPU-trivial recompute). MXU FLOPs are free when bandwidth gates;
    remat trades them for the bytes that actually gate throughput.
  - per-device batch: amortizes fixed per-step costs; changes the
    compiler's fusion/layout choices.
  - stem: ``standard`` (headline, weight-compatible) vs
    ``space_to_depth`` (MLPerf-era TPU stem, reported separately).
  - donation: in-place state buffers remove a params-sized copy.

Prints one JSON line per variant plus a ranked summary. Run on chip:

    python examples/imagenet/sweep_mfu.py
    python examples/imagenet/sweep_mfu.py --batches 128,256 --steps 10

MFU convention: MODEL flops (3x the forward conv/matmul FLOPs of the
un-rematerialized network), so remat recompute counts as price, not
useful work — directly comparable to bench.py's ``mfu``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

from bench import _fetch_scalar, _peak_flops

from chainermn_tpu import create_communicator, create_multi_node_optimizer
from chainermn_tpu.models import ResNet50
from chainermn_tpu.training.train_step import (
    create_train_state,
    make_train_step,
)


# MODEL flops per (per-device batch, stem): captured from XLA
# cost_analysis of the remat=False program — remat recompute is price,
# not useful work, so rematerialized variants are scored against the
# plain program's flops (same convention as bench.py's mfu).
_MODEL_FLOPS: dict = {}


def time_variant(comm, args, *, remat: str, per_device_batch: int,
                 stem: str, donate: bool) -> dict:
    on_cpu = jax.devices()[0].platform == "cpu"
    model = ResNet50(
        num_classes=1000, stem=stem, remat=remat != "none",
        remat_policy="conv" if remat == "conv" else None,
        compute_dtype=jnp.float32 if on_cpu else jnp.bfloat16,
    )
    hw = 64 if on_cpu else 224
    batch = per_device_batch * comm.size
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (batch, hw, hw, 3), jnp.bfloat16)
    y = jax.random.randint(rng, (batch,), 0, 1000)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        from jax.sharding import PartitionSpec as P

        x, y = multihost_utils.host_local_array_to_global_array(
            (x, y), comm.mesh, P()
        )
    variables = jax.jit(lambda k, xb: model.init(k, xb, train=True))(
        jax.random.PRNGKey(42), x[:2]
    )

    def loss_fn(params, batch_, model_state):
        xb, yb = batch_
        logits, mutated = model.apply(
            {"params": params, "batch_stats": model_state}, xb,
            train=True, mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, yb
        ).mean()
        return loss, ({}, mutated["batch_stats"])

    optimizer = create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm,
        allreduce_grad_dtype=jnp.bfloat16,
    )
    state = create_train_state(
        variables["params"], optimizer, comm,
        model_state=variables["batch_stats"],
    )
    step = make_train_step(loss_fn, optimizer, comm, donate=donate)

    t_c0 = time.perf_counter()
    compiled = step.lower(state, (x, y)).compile()
    compile_s = time.perf_counter() - t_c0
    hw_flops = None
    try:
        a = compiled.cost_analysis()
        a = a[0] if isinstance(a, (list, tuple)) else a
        hw_flops = float(a.get("flops", 0.0)) or None
    except Exception:
        pass
    if hw_flops and remat == "none":
        _MODEL_FLOPS[(per_device_batch, stem)] = hw_flops

    state, m = compiled(state, (x, y))
    for _ in range(2):  # warm
        state, m = compiled(state, (x, y))
    _fetch_scalar(m["loss"])
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, m = compiled(state, (x, y))
    _fetch_scalar(m["loss"])
    dt = (time.perf_counter() - t0) / args.steps

    out = {
        "remat": remat, "batch": per_device_batch, "stem": stem,
        "donate": donate,
        "step_ms": round(dt * 1e3, 2),
        "images_per_sec": round(batch / dt, 2),
        "compile_s": round(compile_s, 1),
    }
    peak = _peak_flops(jax.devices()[0].device_kind)
    model_flops = _MODEL_FLOPS.get((per_device_batch, stem), hw_flops)
    if peak and model_flops:
        out["mfu"] = round(model_flops / dt / peak, 4)
        if hw_flops and model_flops and hw_flops > model_flops * 1.01:
            out["recompute_flops_ratio"] = round(hw_flops / model_flops, 3)
    return out


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--communicator", default="xla")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batches", type=str, default="128,256",
                   help="comma list of per-device batch sizes")
    p.add_argument("--remat", type=str, default="none,conv,full",
                   help="comma list of none|conv|full")
    p.add_argument("--stems", type=str, default="standard,space_to_depth")
    p.add_argument("--donate", type=str, default="true")
    args = p.parse_args(argv)

    comm = create_communicator(args.communicator)

    def bools(s, flag):
        out = []
        for v in s.split(","):
            v = v.strip().lower()
            if v not in ("true", "false"):
                p.error(f"{flag} values must be true/false, got {v!r}")
            out.append(v == "true")
        return out

    batches = [int(s) for s in args.batches.split(",")]
    results = []
    remats = [s.strip() for s in args.remat.split(",")]
    for r_ in remats:
        if r_ not in ("none", "conv", "full"):
            p.error(f"--remat values must be none|conv|full, got {r_!r}")
    for remat, b, stem, donate in itertools.product(
        remats, batches,
        args.stems.split(","), bools(args.donate, "--donate"),
    ):
        try:
            r = time_variant(comm, args, remat=remat, per_device_batch=b,
                             stem=stem, donate=donate)
        except Exception as e:  # OOM: keep sweeping
            r = {"remat": remat, "batch": b, "stem": stem, "donate": donate,
                 "error": f"{type(e).__name__}: {e}"[:160]}
        print(json.dumps(r), flush=True)
        results.append(r)

    ok = [r for r in results if "step_ms" in r]
    # Best by MFU (fallback throughput): batch is a grid dimension, so
    # step_ms ordering would rank the smallest batch first regardless of
    # efficiency. The fallback is PER-RUN, not per-row — mixing mfu
    # (<=1) with raw throughput (thousands) would rank any mfu-less row
    # first; a row missing mfu in an mfu-bearing run ranks last (0).
    if any("mfu" in r for r in ok):
        ok.sort(key=lambda r: -r.get("mfu", 0))
    else:
        ok.sort(key=lambda r: -r.get("images_per_sec", 0))
    if ok:
        print(json.dumps({"best": ok[0], "n_variants": len(results)}))
    return ok


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
