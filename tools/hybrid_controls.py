#!/usr/bin/env python
"""The two readings every limit of ``benchmark/reference/hybrid_moe_lm.py``
lies between, taken the way ``correct`` takes check (a) of the cell
``lfm2-hostfill-1chip``: one 8192-token row a seed at the published
widths, the family's loss and its gradient against the float32 reference,
on the family's ``check_params``: the drawn tree with every router's
kernel at ``assumed.check_router_scale`` (``--router-scales`` reads other
scales; 1 is the program's own initialisation).

A seed gives one row of each:

- the **sound** system;
- the controls in the precision below the configuration's: **the
  reference computed in bf16** (``loss(..., dtype=bfloat16)``), the system
  on bf16 parameters, and the system with its router rounded to bf16.

The first seeds (``--equations-on``) also change the equations, the
system alone: the gates weighed by score + bias, top-3 for top-4, the
gates not renormalised, the softmax for the sigmoid, the selection bias
left out. A limit moves only
between the largest ``sound`` reading over the seeds and the smallest
control's; PERF.md section 6 (PR 40) and the comment above ``TOLERANCES``
carry the last readings, ``benchmark/tests/test_hybrid.py`` holds the
limits to them.

Usage (through the chip tool)::

    python tools/hybrid_controls.py --seeds 4040000701,4040000702 \\
        --out chiprun_out/hybrid_controls.jsonl

``--through-cell CONTROL`` runs the cell itself instead (``run_cell``, the
first seed) with one control in place and exits 0 where ``correct`` came
out false: the harness's own verdict.

``--tiny`` swaps in the benchmark tests' throw-away configuration (bf16
compute) for a run of the tool itself on a CPU; its numbers mean nothing.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _controls  # noqa: E402  (puts the checkout and benchmark/ on the path)

CELL = "lfm2-hostfill-1chip"


def through_cell(args, roots, ref, equations) -> int:
    """The harness's own verdict on one control: ``run_cell`` on the first
    seed with the reference computed in bf16, or with the router patched
    as a changed equation has it (in check (a) and in the step alike)."""
    import functools
    import tempfile

    import jax
    import jax.numpy as jnp

    import run

    what, cell, benchmark = args.through_cell, CELL, None
    if args.tiny:
        tiny = _controls.benchmark_test("test_hybrid")
        roots, benchmark = tiny.added_root(tempfile.mkdtemp())
        ref, cell, peak = roots.module("reference", "hybrid_moe_lm"), \
            "tiny-hybrid", tiny.PEAK
    else:
        import peaks
        peak = peaks.lookup(jax.devices()[0].device_kind)
    if what == "reference computed in bf16":
        patch = (ref, "loss", functools.partial(ref.loss,
                                                dtype=jnp.bfloat16))
    elif what in equations and equations[what][1] is not None:
        patch = equations[what][1]
    else:
        raise SystemExit(f"--through-cell takes 'reference computed in "
                         f"bf16' or a router's patch, not {what!r}")
    # as benchmark/run.py's main: no cap on the compile cache's size
    jax.config.update("jax_compilation_cache_max_size", -1)
    with _controls.Patched(*patch):
        line = run.run_cell(
            cell, seed=int(args.seeds.split(",")[0]), seconds=1.0,
            trace=False, devices=jax.devices()[:1], peak=peak, roots=roots,
            benchmark=benchmark)
    _controls.writer(args.out)(through_cell=what, **line)
    return 0 if line["correct"] is False else 1


def main(argv=None) -> int:
    ap = _controls.parser(__doc__)
    ap.add_argument("--router-scales", default=None,
                    help="comma-separated; what every router's kernel is "
                    "multiplied by for the comparison (default: the "
                    "configuration's assumed.check_router_scale; 1 is the "
                    "program's own initialisation)")
    ap.add_argument("--equations-on", type=int, default=1,
                    help="read the changed equations on the first N seeds")
    ap.add_argument("--through-cell", default=None, metavar="CONTROL",
                    help="run the cell itself (benchmark/run.py's "
                    "run_cell, the first seed, a 1 s window) with this "
                    "control in place, 'reference computed in bf16' or "
                    "one of the changed equations that patch the router, "
                    "and print its line: `correct` has to come out false")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import spec
    from chainermn_tpu.parallel import moe
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    roots = spec.Roots()
    cell = spec.load_cell(roots, CELL)
    config, job = cell["config_spec"], cell["job"]
    samples = cell["mix"]["samples"]["tokens"]
    if args.tiny:
        tiny = _controls.benchmark_test("test_hybrid")
        config = {**tiny.TINY_HYBRID, "training": {
            **tiny.TINY_HYBRID["training"], "compute_dtype": "bfloat16"}}
        job = {**tiny.TINY_CELL["job"], "seq_len": 128}
    fam_mod = roots.module("families", "hybrid_moe_lm")
    ref = roots.module("reference", "hybrid_moe_lm")
    gen = roots.module("traffic", "gen_tokens")
    tol = ref.TOLERANCES
    say, highest = _controls.writer(args.out), _controls.highest

    def value_and_grad(cfg=config):
        fam = fam_mod.build(cfg, job)
        return jax.jit(jax.value_and_grad(
            lambda p, b, s: fam.loss_fn(p, b, s)[0]))

    def topk_with(bf16=False, weigh_by_choice=False, **changes):
        """``dropless_topk`` with some of its keyword arguments replaced
        (``None``: left out), its operands rounded to bf16, or its gates
        taken from score + bias."""
        real = moe.dropless_topk

        def dropless_topk(u, router_w, k, renormalise=False, **kw):
            if bf16:
                u, router_w = (x.astype(jnp.bfloat16).astype(jnp.float32)
                               for x in (u, router_w))
            kw = {a: b for a, b in {**kw, **changes}.items()
                  if b is not None}
            r = real(u, router_w, k, renormalise, **kw)
            if weigh_by_choice:
                # g from s + b: what the published code does not do
                s = jax.nn.sigmoid(r.logits) + kw["select_bias"]
                g = jnp.take_along_axis(s, r.experts, axis=-1)
                r = r._replace(gates=g / (g.sum(-1, keepdims=True) + 1e-6))
            return r
        return (moe, "dropless_topk", dropless_topk)

    sound = value_and_grad()  # traced again for bf16 parameters
    rows_held = jax.jit(lambda p, b, s: fam_mod.build(config, job).loss_fn(
        p, b, s)[1][0]["moe/rows_held"])
    every_seed = {
        "sound": (sound, None, False),
        "system on bf16 parameters": (sound, None, True),
        "bf16 router": (value_and_grad(), topk_with(bf16=True), False),
    }
    first_seed = {
        "gates weighed by score + bias": (
            value_and_grad(), topk_with(weigh_by_choice=True), False),
        "top-k less one": (value_and_grad({
            **config,
            "num_experts_per_tok": config["num_experts_per_tok"] - 1}),
            None, False),
        "gates not renormalised": (value_and_grad(
            {**config, "norm_topk_prob": False}), None, False),
        "softmax for the sigmoid": (
            value_and_grad(), topk_with(score="softmax"), False),
        "selection bias left out": (
            value_and_grad(), topk_with(select_bias=None), False),
    }

    if args.through_cell:
        return through_cell(args, roots, ref, first_seed)

    ref_vg = highest(jax.jit(jax.value_and_grad(
        lambda p, b, s: ref.loss(p, s, b, config))))
    ref_bf16_vg = jax.jit(jax.value_and_grad(
        lambda p, b, s: ref.loss(p, s, b, config, dtype=jnp.bfloat16)))

    scales = [float(x) for x in args.router_scales.split(",")] \
        if args.router_scales \
        else [config["assumed"]["check_router_scale"]]
    say(device=jax.devices()[0].device_kind, tolerances=tol,
        tiny=args.tiny, seeds=args.seeds, router_scales=scales)
    fam = fam_mod.build(config, job)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        drawn, state, _ = jax.block_until_ready(fam.init(seed))
        batch = _controls.check_batch(gen, fam, samples, seed)
        variants = {**every_seed,
                    **(first_seed if i < args.equations_on else {})}
        for scale in scales:
            # what check (a) runs on: the family's ``check_params``
            params = fam_mod.with_router_scale(drawn, scale)
            want = ref_vg(params, batch, state)

            def compare(what, loss, grads, t0, **extra):
                say(**_controls.reading(tol, what, seed, (loss, grads),
                                        want, t0, router_scale=scale,
                                        **extra))

            for what, (vg, patch, on_bf16) in variants.items():
                t0 = time.perf_counter()
                p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params) \
                    if on_bf16 else params
                if patch is None:
                    loss, grads = vg(p, batch, state)
                else:
                    with _controls.Patched(*patch):
                        loss, grads = vg(p, batch, state)
                compare(what, loss, grads, t0, **(
                    {"rows_held": float(rows_held(p, batch, state))}
                    if what == "sound" else {}))
                del grads, p
            t0 = time.perf_counter()
            loss, grads = ref_bf16_vg(params, batch, state)
            compare("reference computed in bf16", loss, grads, t0)
            del grads, want, params
        del drawn
    return 0


if __name__ == "__main__":
    sys.exit(main())
