#!/usr/bin/env python
"""The two readings every limit of
``benchmark/reference/block_diffusion_moe_lm.py`` lies between, taken the
way ``correct`` takes check (a) of the cell ``sdar-hostfill-1chip``: one
8192-token sequence (16,384 rows ``[x ; x~]``) a seed at the published
widths, the family's loss and its gradient against the float32 reference
on the program's own initialisation, the noise handed to both as data.

A seed gives one row of each:

- the **sound** system;
- the control in the precision below the configuration's: **the
  reference computed in bf16** (``loss(..., dtype=bfloat16)``).

The first seeds (``--equations-on``) also change the equations, the
system alone: the noised rows see their own clean block (the strict mask
made inclusive), and the targets shifted by one. A limit moves only
between the largest ``sound`` reading over the seeds and the smallest
control's; PERF.md section 6 (PR 42) and the comment above ``TOLERANCES``
carry the last readings, ``benchmark/tests/test_block_diffusion.py`` holds
the limits to them.

Usage (through the chip tool)::

    python tools/bd_controls.py --seeds 4242000701,4242000702 \\
        --out chiprun_out/bd_controls.jsonl

``--through-cell`` runs the cell itself instead (``run_cell``, the first
seed, a 1 s window) with the reference computed in bf16 and exits 0 where
``correct`` came out false: the harness's own verdict.

``--tiny`` swaps in the benchmark tests' throw-away configuration (bf16
compute) for a run of the tool itself on a CPU; its numbers mean nothing.
"""

from __future__ import annotations

import contextlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _controls  # noqa: E402  (puts the checkout and benchmark/ on the path)

CELL = "sdar-hostfill-1chip"
FAMILY = "block_diffusion_moe_lm"


def through_cell(args) -> int:
    """The harness's own verdict on the control: ``run_cell`` on the first
    seed with the reference computed in bf16."""
    import functools
    import tempfile

    import jax
    import jax.numpy as jnp

    import run
    import spec

    roots, cell, benchmark = spec.Roots(), CELL, None
    if args.tiny:
        tiny = _controls.benchmark_test("test_block_diffusion")
        roots, benchmark = tiny.added_root(tempfile.mkdtemp())
        cell, peak = "tiny-bd", tiny.PEAK
    else:
        import peaks
        peak = peaks.lookup(jax.devices()[0].device_kind)
    ref = roots.module("reference", FAMILY)
    # as benchmark/run.py's main: no cap on the compile cache's size
    jax.config.update("jax_compilation_cache_max_size", -1)
    with _controls.Patched(ref, "loss", functools.partial(
            ref.loss, dtype=jnp.bfloat16)):
        line = run.run_cell(
            cell, seed=int(args.seeds.split(",")[0]), seconds=1.0,
            trace=False, devices=jax.devices()[:1], peak=peak, roots=roots,
            benchmark=benchmark)
    _controls.writer(args.out)(
        through_cell="reference computed in bf16", **line)
    return 0 if line["correct"] is False else 1


def main(argv=None) -> int:
    ap = _controls.parser(__doc__)
    ap.add_argument("--equations-on", type=int, default=1,
                    help="read the changed equations on the first N seeds")
    ap.add_argument("--through-cell", action="store_true",
                    help="run the cell itself (benchmark/run.py's run_cell, "
                    "the first seed, a 1 s window) with the reference "
                    "computed in bf16 and print its line: `correct` has "
                    "to come out false")
    args = ap.parse_args(argv)
    if args.through_cell:
        from chainermn_tpu.utils.compile_cache import use_compile_cache

        use_compile_cache()
        return through_cell(args)

    import jax
    import jax.numpy as jnp

    import spec
    from chainermn_tpu.ops import block_diffusion
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    roots = spec.Roots()
    cell = spec.load_cell(roots, CELL)
    config, job = cell["config_spec"], cell["job"]
    samples = cell["mix"]["samples"]["tokens"]
    if args.tiny:
        tiny = _controls.benchmark_test("test_block_diffusion")
        config = {**tiny.TINY_BD, "training": {
            **tiny.TINY_BD["training"], "compute_dtype": "bfloat16"}}
        job = {**tiny.TINY_CELL["job"], "seq_len": 128}
    fam_mod = roots.module("families", FAMILY)
    ref = roots.module("reference", FAMILY)
    gen = roots.module("traffic", "gen_tokens")
    tol = ref.TOLERANCES
    say, highest = _controls.writer(args.out), _controls.highest
    fam = fam_mod.build(config, job)

    def value_and_grad():
        return jax.jit(jax.value_and_grad(
            lambda p, b, s: fam.loss_fn(p, b, s)[0]))

    real_fwd, real_bwd = (block_diffusion.flash_block_fwd,
                          block_diffusion.flash_block_bwd)

    def inclusive(fn):
        return lambda *a, **kw: fn(*a, **{**kw, "causal_strict": False})

    sound = value_and_grad()
    every_seed = {"sound": (sound, (), None)}
    first_seed = {
        "noised rows see their own clean block": (
            value_and_grad(),
            ((block_diffusion, "flash_block_fwd", inclusive(real_fwd)),
             (block_diffusion, "flash_block_bwd", inclusive(real_bwd))),
            None),
        "targets shifted by one": (
            sound, (), lambda b: {**b, "tokens": jnp.roll(
                b["tokens"], -1, axis=1)}),
    }
    ref_vg = highest(jax.jit(jax.value_and_grad(
        lambda p, b, s: ref.loss(p, s, b, config))))
    ref_bf16_vg = jax.jit(jax.value_and_grad(
        lambda p, b, s: ref.loss(p, s, b, config, dtype=jnp.bfloat16)))
    step_metrics = jax.jit(lambda p, b, s: fam.loss_fn(p, b, s)[1][0])

    say(device=jax.devices()[0].device_kind, tolerances=tol,
        tiny=args.tiny, seeds=args.seeds)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params, state, _ = jax.block_until_ready(fam.init(seed))
        batch = fam.take_rows(
            _controls.check_batch(gen, fam, samples, seed), 0,
            fam.check_rows)
        want = ref_vg(params, batch, state)

        def compare(what, loss, grads, t0, **extra):
            say(**_controls.reading(tol, what, seed, (loss, grads), want,
                                    t0, **extra))

        variants = {**every_seed,
                    **(first_seed if i < args.equations_on else {})}
        for what, (vg, patches, change) in variants.items():
            t0 = time.perf_counter()
            b = change(batch) if change else batch
            with contextlib.ExitStack() as stack:
                for patch in patches:
                    stack.enter_context(_controls.Patched(*patch))
                loss, grads = vg(params, b, state)
            extra = {}
            if what == "sound":
                m = step_metrics(params, batch, state)
                extra = {"rows_held": float(m["moe/rows_held"]),
                         "masked_share": float(m["bd/masked_share"])}
            compare(what, loss, grads, t0, **extra)
            del grads
        t0 = time.perf_counter()
        loss, grads = ref_bf16_vg(params, batch, state)
        compare("reference computed in bf16", loss, grads, t0)
        del grads, want, params
    return 0


if __name__ == "__main__":
    sys.exit(main())
