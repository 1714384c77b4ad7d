#!/usr/bin/env python
"""The two readings every limit of ``benchmark/reference/loop_lm.py`` lies
between, taken the way ``correct`` takes check (a) of the cell
``ouro-hostfill-1chip``: one 4096-token row a seed at the published
widths, the family's loss and its gradient against the float32 reference,
on the family's ``check_params`` (the exit gate's kernel multiplied by
``assumed.check_gate_scale``; ``--gate-scales`` reads other scales, the
changed equations at the first of them).

A seed gives one row of each:

- the **sound** system against the reference;
- **the reference computed in bf16** (``loss(..., dtype=bfloat16)``)
  against the reference: the precision below the configuration's;
- the system with **the gate's gradient stopped** (the fused head's
  per-row weights taken as constants) against the reference;
- the sound system against the reference with an equation changed, as
  if that were the model: **three passes for four** (one fewer than the
  configuration's), **the norm between passes left out** (the final norm
  for the exits only), **the entropy term left out**, **the norm after a
  sub-layer left out**.

Every control has to be refused by a limit; a limit moves only between
the largest ``sound`` reading over the seeds and the smallest control's.
PERF.md section 6 (PR 30) and the comment above ``TOLERANCES`` carry the
last readings; ``benchmark/tests/test_loop.py`` holds the limits to them.

Usage (through the chip tool)::

    python tools/loop_controls.py --seeds 3030000701,3030000702 \\
        --out chiprun_out/loop_controls.jsonl

``--tiny`` swaps in the benchmark tests' throw-away configuration (bf16
compute) for a run of the tool itself on a CPU; its numbers mean nothing.
"""

from __future__ import annotations

import itertools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _controls  # noqa: E402  (puts the checkout and benchmark/ on the path)

CELL = "ouro-hostfill-1chip"


def main(argv=None) -> int:
    ap = _controls.parser(__doc__)
    ap.add_argument("--gate-scales", default=None,
                    help="comma-separated; what the exit gate's kernel is "
                    "multiplied by for the comparison (default: the "
                    "configuration's assumed.check_gate_scale; 1 is the "
                    "program's own initialisation)")
    ap.add_argument("--equations-on", type=int, default=None,
                    help="read the controls other than the precision's on "
                    "the first N seeds of the first scale only")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import spec
    from chainermn_tpu.models import transformer
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    roots = spec.Roots()
    cell = spec.load_cell(roots, CELL)
    config, job = cell["config_spec"], cell["job"]
    samples = cell["mix"]["samples"]["tokens"]
    if args.tiny:
        tests = _controls.benchmark_test("test_loop")
        config = {**tests.TINY_LOOP, "training": {
            **tests.TINY_LOOP["training"], "compute_dtype": "bfloat16"}}
        job = {**tests.TINY_CELL["job"], "seq_len": 128}
    fam = roots.module("families", "loop_lm").build(config, job)
    ref = roots.module("reference", "loop_lm")
    gen = roots.module("traffic", "gen_tokens")
    tol = ref.TOLERANCES
    say, highest = _controls.writer(args.out), _controls.highest

    def ref_vg(**kw):
        return highest(jax.jit(jax.value_and_grad(
            lambda p, b: ref.loss(p, (), b, config, **kw))))

    def constant_weights(*a, weights=None, **kw):
        """The fused head as it was before its weights carried a
        gradient."""
        return real_fused(*a, weights=jax.lax.stop_gradient(weights), **kw)

    real_fused = transformer.lm_loss_fused
    gate_gradient_stopped = _controls.Patched(
        transformer, "lm_loss_fused", constant_weights)

    def sys_vg():
        return jax.jit(jax.value_and_grad(fam.loss_fn, has_aux=True))

    sound_vg, stopped_vg, reference = sys_vg(), sys_vg(), ref_vg()
    ref_bf16_vg = jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss(p, (), b, config, dtype=jnp.bfloat16)))
    #: the reference with an equation changed: what the sound system is
    #: held against, as if that were the model
    equations = {
        "three passes for four": ref_vg(passes=config["total_ut_steps"] - 1),
        "the norm between passes left out": ref_vg(
            norm_between_passes=False),
        "the entropy term left out": ref_vg(beta=0.0),
        "the norm after a sub-layer left out": ref_vg(sublayer_norms=False),
    }

    seeds = [int(s) for s in args.seeds.split(",")]
    scales = [float(s) for s in args.gate_scales.split(",")] \
        if args.gate_scales else [config["assumed"]["check_gate_scale"]]
    say(device=jax.devices()[0].device_kind, tolerances=tol,
        tiny=args.tiny, seeds=args.seeds, gate_scales=scales)
    for scale, (n, seed) in itertools.product(scales, enumerate(seeds)):
        # what check (a) runs on: the family's ``check_params``
        params = jax.block_until_ready(fam.init(seed, scale)[2])
        batch = _controls.check_batch(gen, fam, samples, seed)

        def compare(what, got, want, t0, **extra):
            say(**_controls.reading(tol, what, seed, got, want, t0,
                                    gate_scale=scale, **extra))

        t0 = time.perf_counter()
        want = reference(params, batch)
        (loss, metrics), grads = sound_vg(params, batch)
        sound = (loss, grads)
        compare("sound", sound, want, t0,
                **{k: float(v) for k, v in metrics.items()})
        t0 = time.perf_counter()
        compare("reference computed in bf16", ref_bf16_vg(params, batch),
                want, t0)
        if scale == scales[0] and (args.equations_on is None
                                   or n < args.equations_on):
            t0 = time.perf_counter()
            with gate_gradient_stopped:
                (loss, _), grads = stopped_vg(params, batch)
            compare("the gate's gradient stopped", (loss, grads), want, t0)
            del grads, want
            for what, changed in equations.items():
                t0 = time.perf_counter()
                compare(what, sound, changed(params, batch), t0)
        want = sound = grads = params = None  # room for the next seed's
    return 0


if __name__ == "__main__":
    sys.exit(main())
