#!/usr/bin/env python
"""The two readings every limit of ``benchmark/reference/latent_moe_lm.py``
lies between, taken the way ``correct`` takes check (a) of the cell
``dsv2lite-hostfill-1chip``: one 8192-token row a seed at the published
widths, the family's loss and its gradient against the float32 reference,
on the family's ``check_params``: the drawn tree with every router's
kernel at ``assumed.check_router_scale`` (``--router-scales`` reads other
scales; 1 is the program's own initialisation, which the cell trains; the
family's choice of the held experts, a reordering of the routers' columns,
is in both).

A seed gives one row of each:

- the **sound** system;
- the control in the precision below the configuration's: **the
  reference computed in bf16** (``loss(..., dtype=bfloat16)``).

The first seeds (``--equations-on``) also change the equations, the
system alone: YaRN's ``mscale ** 2`` left out of the softmax scale, the
rope key not rotated, the latent's norm skipped, the shared expert left
out. A limit moves only between the largest ``sound`` reading over the
seeds and the smallest control's; PERF.md section 6 (PR 47) and the
comment above ``TOLERANCES`` carry the last readings.

Usage (through the chip tool)::

    python tools/latent_controls.py --seeds 4747000701,4747000702 \\
        --out chiprun_out/latent_controls.jsonl

``--through-cell`` runs the cell itself instead (``run_cell``, a 1 s
window, each seed in turn) with the reference computed in bf16 in the
float32 one's place and exits 0 where ``correct`` came out false on every
seed: the harness's own verdict.

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

CELL = "dsv2lite-hostfill-1chip"
FAMILY = "latent_moe_lm"


def through_cell(args, roots, ref) -> int:
    """The harness's own verdict on the control, a seed at a time."""
    import functools
    import tempfile

    import jax
    import jax.numpy as jnp

    import run

    cell, benchmark = CELL, None
    if args.tiny:
        tiny = _controls.benchmark_test("test_latent")
        roots, benchmark = tiny.added_root(tempfile.mkdtemp())
        ref, cell, peak = roots.module("reference", FAMILY), \
            "tiny-latent", tiny.PEAK
    else:
        import peaks
        peak = peaks.lookup(jax.devices()[0].device_kind)
    # as benchmark/run.py's main: no cap on the compile cache's size
    jax.config.update("jax_compilation_cache_max_size", -1)
    say, refused = _controls.writer(args.out), True
    with _controls.Patched(ref, "loss", functools.partial(
            ref.loss, dtype=jnp.bfloat16)):
        for seed in (int(s) for s in args.seeds.split(",")):
            line = run.run_cell(
                cell, seed=seed, seconds=1.0, trace=False,
                devices=jax.devices()[:1], peak=peak, roots=roots,
                benchmark=benchmark)
            say(through_cell="reference computed in bf16", **line)
            refused &= line["correct"] is False
    return 0 if refused else 1


def main(argv=None) -> int:
    ap = _controls.parser(__doc__)
    ap.add_argument("--through-cell", action="store_true",
                    help="run the cell itself (benchmark/run.py's "
                    "run_cell, a 1 s window) on each seed with the "
                    "reference computed in bf16: `correct` has to come "
                    "out false")
    ap.add_argument("--router-scales", default=None,
                    help="comma-separated; what every router's kernel is "
                    "multiplied by for the comparison (default: the "
                    "configuration's assumed.check_router_scale; 1 is the "
                    "program's own initialisation)")
    ap.add_argument("--equations-on", type=int, default=1,
                    help="read the changed equations on the first N seeds")
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
        tiny = _controls.benchmark_test("test_latent")
        config = {**tiny.TINY_LATENT, "training": {
            **tiny.TINY_LATENT["training"], "compute_dtype": "bfloat16"}}
        job = {**tiny.TINY_CELL["job"], "seq_len": 128}
    fam_mod = roots.module("families", FAMILY)
    ref = roots.module("reference", FAMILY)
    gen = roots.module("traffic", "gen_tokens")
    with_router_scale = roots.module(
        "families", "hybrid_moe_lm").with_router_scale
    tol = ref.TOLERANCES
    if args.through_cell:
        return through_cell(args, roots, ref)
    say, highest = _controls.writer(args.out), _controls.highest
    fam = fam_mod.build(config, job)

    def value_and_grad():
        return jax.jit(jax.value_and_grad(lambda p, b: fam.loss_fn(p, b)[0]))

    def not_rotated(real):
        def apply_rope(x, positions, base=10000.0, scaling=None):
            return x if x.shape[2] == 1 \
                else real(x, positions, base, scaling)
        return apply_rope

    def norm_skipped(real):
        def norm_layer(arch, dtype, name=None):
            return (lambda x: x) if name == "kv_a_norm" \
                else real(arch, dtype, name)
        return norm_layer

    def shared_left_out(real):
        return lambda self, h, width: jnp.zeros_like(real(self, h, width))

    block = transformer.TransformerBlock
    every_seed = {"sound": ()}
    first_seed = {
        "mscale squared left out of the softmax scale": (
            (transformer.Yarn, "softmax_scale", property(lambda self: 1.0)),),
        "rope key not rotated": ((transformer, "apply_rope", not_rotated(
            transformer.apply_rope)),),
        "latent norm skipped": ((transformer, "_norm_layer", norm_skipped(
            transformer._norm_layer)),),
        "shared expert left out": ((block, "_shared_expert", shared_left_out(
            block._shared_expert)),),
    }
    # a jit traces at its first call, inside the patch: one a variant
    programs = {what: value_and_grad()
                for what in {**every_seed, **first_seed}}
    ref_vg = highest(jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss(p, (), b, config))))
    ref_bf16_vg = jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss(p, (), b, config, dtype=jnp.bfloat16)))
    step_metrics = jax.jit(lambda p, b: fam.loss_fn(p, b)[1])

    scales = [float(x) for x in args.router_scales.split(",")] \
        if args.router_scales \
        else [config["assumed"]["check_router_scale"]]
    say(device=jax.devices()[0].device_kind, tolerances=tol,
        tiny=args.tiny, seeds=args.seeds, router_scales=scales)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        drawn, _, _ = jax.block_until_ready(fam.init(seed))
        batch = fam.take_rows(
            _controls.check_batch(gen, fam, samples, seed), 0,
            fam.check_rows)
        variants = {**every_seed,
                    **(first_seed if i < args.equations_on else {})}
        for scale in scales:
            # what check (a) runs on: the family's ``check_params``
            params = with_router_scale(drawn, scale)
            t0 = time.perf_counter()
            want = jax.block_until_ready(ref_vg(params, batch))
            say(what="float32 reference", seed=seed, router_scale=scale,
                loss=float(want[0]), seconds=time.perf_counter() - t0)

            def compare(what, loss, grads, t0, **extra):
                say(**_controls.reading(tol, what, seed, (loss, grads),
                                        want, t0, router_scale=scale,
                                        **extra))

            for what, patches in variants.items():
                t0 = time.perf_counter()
                with contextlib.ExitStack() as stack:
                    for patch in patches:
                        stack.enter_context(_controls.Patched(*patch))
                    loss, grads = programs[what](params, batch)
                extra = {}
                if what == "sound":
                    m = step_metrics(params, batch)
                    extra = {"rows_held": float(m["moe/rows_held"]),
                             "seq_aux": float(m["moe/seq_aux"])}
                compare(what, loss, grads, t0, **extra)
                del grads
            t0 = time.perf_counter()
            loss, grads = ref_bf16_vg(params, batch)
            compare("reference computed in bf16", loss, grads, t0)
            del grads, want, params
        del drawn
    return 0


if __name__ == "__main__":
    sys.exit(main())
