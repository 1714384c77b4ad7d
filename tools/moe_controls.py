#!/usr/bin/env python
"""The two readings every limit of ``benchmark/reference/moe_lm.py`` lies
between, taken the way ``correct`` takes check (a) of the cell
``olmoe-hostfill-1chip``: one 4096-token row a seed at the published
widths, the family's loss and its gradient against the float32 reference.

A seed gives one row of each:

- the **sound** system;
- the controls in the precision below the configuration's: **the
  reference computed in bf16** (``loss(..., dtype=bfloat16)``), the
  system on bf16 parameters, and the embedding's gradient summed in bf16
  (the fault the comparison found in PR 25);
- what the norms cannot tell from the sound system: a router rounded to
  bf16, and one (token, slot) row in no expert's group: read twice, once
  through the family's loss (NaN: that is what refuses it) and once
  through the program's loss alone (the norms hardly move).

The first seed also changes the equations, the system alone: gates
renormalised, top-7, either auxiliary loss left out, 1% of the rows in no
group. A limit moves only between the largest ``sound`` reading over the
seeds and the smallest control's; PERF.md section 6 (PR 25) and the
comment above ``TOLERANCES`` carry the last readings,
``benchmark/tests/test_moe.py`` holds the limits to them.

Usage (through the chip tool; six minutes for four seeds)::

    python tools/moe_controls.py --seeds 2525000701,2525000702 \\
        --out chiprun_out/controls.jsonl

``--tiny`` swaps in the benchmark tests' throw-away configuration (bf16
compute) for a run of the tool itself on a CPU; its numbers mean nothing.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _controls  # noqa: E402  (puts the checkout and benchmark/ on the path)

CELL = "olmoe-hostfill-1chip"


def main(argv=None) -> int:
    args = _controls.parser(__doc__).parse_args(argv)

    import jax
    import jax.numpy as jnp

    import spec
    from chainermn_tpu.models import lm_loss_moe, transformer
    from chainermn_tpu.parallel import moe
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    roots = spec.Roots()
    cell = spec.load_cell(roots, CELL)
    config, job = cell["config_spec"], cell["job"]
    samples = cell["mix"]["samples"]["tokens"]
    if args.tiny:
        TINY_MOE = _controls.benchmark_test("test_moe").TINY_MOE
        config = {**TINY_MOE, "training": {**TINY_MOE["training"],
                                           "compute_dtype": "bfloat16"}}
        job = {"per_chip_batch": 2, "head_chunks": 2, "seq_len": 128}
    fam_mod = roots.module("families", "moe_lm")
    ref = roots.module("reference", "moe_lm")
    gen = roots.module("traffic", "gen_tokens")
    tol = ref.TOLERANCES
    say, highest = _controls.writer(args.out), _controls.highest

    def build(cfg=config):
        return fam_mod.build(cfg, job)

    def value_and_grad(fam, *, raw=False, coefs=None):
        """Of the family's loss; ``raw`` or other ``coefs``: of the
        program's loss, without the family's NaN on a dropped row."""
        if raw or coefs:
            assumed = config["assumed"]
            kw = {"load_balance_coef": assumed["router_aux_loss_coef"],
                  "z_loss_coef": assumed["router_z_loss_coef"],
                  **(coefs or {})}

            def fn(p, b):
                return lm_loss_moe(fam.model, p, b,
                                   n_chunks=int(job["head_chunks"]), **kw)
        else:
            fn = fam.loss_fn
        return jax.jit(jax.value_and_grad(fn, has_aux=True))

    def rows_in_no_group(n):
        """What a capacity does to its overflow: the last ``n`` rows of
        the last expert that has them lie in no group."""
        real = moe.dropless_topk

        def dropless_topk(u, router_w, k, renormalise=False):
            r = real(u, router_w, k, renormalise)
            last = r.group_sizes.shape[0] - 1 - jnp.argmax(
                r.group_sizes[::-1] >= n)
            return r._replace(group_sizes=r.group_sizes.at[last].add(-n))
        return (moe, "dropless_topk", dropless_topk)

    def bf16_router():
        real = moe.dropless_topk

        def dropless_topk(u, router_w, k, renormalise=False):
            return real(u.astype(jnp.bfloat16).astype(jnp.float32),
                        router_w.astype(jnp.bfloat16).astype(jnp.float32),
                        k, renormalise)
        return (moe, "dropless_topk", dropless_topk)

    class Bf16Take:
        """``jax.numpy``, but ``take`` gathers from the table cast to
        bf16, as flax's ``Embed`` does: the table's gradient is then a
        scatter-add in bf16."""

        def __getattr__(self, name):
            return getattr(jnp, name)

        @staticmethod
        def take(table, idx, axis=0):
            return jnp.take(table.astype(jnp.bfloat16), idx, axis=axis)

    fam = build()
    one_pct = max(1, fam.T * config["num_experts_per_tok"] // 100)
    #: name -> (value_and_grad, patch at the tracing call, on bf16 params)
    every_seed = {
        "sound": (value_and_grad(fam), None, False),
        "system on bf16 parameters": (value_and_grad(fam), None, True),
        "embedding gradient summed in bf16": (
            value_and_grad(build()), (transformer, "jnp", Bf16Take()), False),
        "bf16 router": (value_and_grad(build()), bf16_router(), False),
        "one row in no group, the family's loss": (
            value_and_grad(build()), rows_in_no_group(1), False),
        "one row in no group, the norms alone": (
            value_and_grad(build(), raw=True), rows_in_no_group(1), False),
    }
    first_seed = {
        "1% of the rows in no group, the norms alone": (
            value_and_grad(build(), raw=True), rows_in_no_group(one_pct),
            False),
        "gates renormalised": (value_and_grad(
            build({**config, "norm_topk_prob": True})), None, False),
        "top-k less one": (value_and_grad(build({
            **config,
            "num_experts_per_tok": config["num_experts_per_tok"] - 1})),
            None, False),
        "load balance left out": (value_and_grad(
            fam, coefs={"load_balance_coef": 0.0}), None, False),
        "z-loss left out": (value_and_grad(
            fam, coefs={"z_loss_coef": 0.0}), None, False),
    }

    ref_vg = highest(jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss(p, (), b, config))))
    ref_bf16_vg = jax.jit(jax.value_and_grad(
        lambda p, b: ref.loss(p, (), b, config, dtype=jnp.bfloat16)))

    say(device=jax.devices()[0].device_kind, tolerances=tol,
        tiny=args.tiny, seeds=args.seeds)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        params = jax.block_until_ready(fam.init(seed)[0])
        batch = _controls.check_batch(gen, fam, samples, seed)
        want = ref_vg(params, batch)

        def compare(what, loss, grads, t0, **extra):
            say(**_controls.reading(tol, what, seed, (loss, grads), want,
                                    t0, **extra))

        variants = {**every_seed, **(first_seed if i == 0 else {})}
        for what, (vg, patch, on_bf16) in variants.items():
            t0 = time.perf_counter()
            p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params) \
                if on_bf16 else params
            if patch is None:
                (loss, metrics), grads = vg(p, batch)
            else:
                with _controls.Patched(*patch):
                    (loss, metrics), grads = vg(p, batch)
            compare(what, loss, grads, t0,
                    dropped=float(metrics["moe/dropped"]))
            del grads, p
        t0 = time.perf_counter()
        loss, grads = ref_bf16_vg(params, batch)
        compare("reference computed in bf16", loss, grads, t0)
        del grads, want, params
    return 0


if __name__ == "__main__":
    sys.exit(main())
