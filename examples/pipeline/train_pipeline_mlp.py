"""Pipeline-parallel training — the micro-batched engine, end to end.

The reference only showed the *pattern* (chained send/recv via
``MultiNodeChainList``, one rank computing while the rest idled — SURVEY.md
section 2.2); this example runs the real GPipe engine
(:mod:`chainermn_tpu.parallel.pipeline`): a deep residual MLP split into
``n_stages`` homogeneous stages over a ``'stage'`` mesh axis, micro-batched
fill/steady/drain schedule in ONE jitted program, backward = the
automatically transposed reverse schedule.

    python examples/pipeline/train_pipeline_mlp.py --iterations 100
    python examples/pipeline/train_pipeline_mlp.py --remat-stages
    # (--remat-stages: recompute stage-internal activations in backward)
    python examples/pipeline/train_pipeline_mlp.py --schedule 1f1b
    # (1f1b: interleaved one-forward-one-backward engine — O(stages)
    #  saved activations at any microbatch count; embed trains through
    #  the engine's input grads, the softmax head through head grads)

The task (10-blob classification, same as the mnist example's synthetic
data) converges within ~100 iterations, so accuracy is a real signal that
gradients flow correctly through the pipeline.
"""

from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

import chainermn_tpu
from chainermn_tpu import global_except_hook
from chainermn_tpu.parallel.pipeline import make_pipeline, stack_stage_params


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: GPipe pipeline parallelism"
    )
    p.add_argument("--communicator", default="naive")
    p.add_argument("--batchsize", type=int, default=128)
    p.add_argument("--iterations", type=int, default=150)
    p.add_argument("--width", type=int, default=128)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--microbatches", type=int, default=None,
                   help="default: 2x the stage count")
    p.add_argument("--remat-stages", action="store_true",
                   help="recompute stage-internal activations in the "
                        "backward (saves memory for deep stages)")
    p.add_argument("--schedule", choices=("gpipe", "1f1b", "hetero"),
                   default="gpipe",
                   help="gpipe: differentiable apply + autodiff backward; "
                        "1f1b: interleaved fwd/bwd engine, O(stages) "
                        "activation memory at any microbatch count; "
                        "hetero: per-stage functions — embed and head "
                        "run INSIDE the pipeline")
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(args.communicator)
    global_except_hook._add_hook()
    n_stages = comm.size
    from jax.sharding import Mesh

    mesh = Mesh(np.array(comm.mesh.devices.flat).reshape(n_stages), ("stage",))
    n_micro = args.microbatches or 2 * n_stages
    if comm.rank == 0:
        print(f"pipeline: {n_stages} stages x {n_micro} microbatches "
              f"(remat={args.remat_stages})")

    W = args.width

    def stage_fn(params, x):
        # one residual block per stage: homogeneous in/out shape [mb, W]
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        return x + h @ params["w2"]

    keys = jax.random.split(jax.random.key(0), n_stages)
    stacked = stack_stage_params([
        {
            "w1": jax.random.normal(k, (W, W)) * (1.0 / np.sqrt(W)),
            "b1": jnp.zeros((W,)),
            "w2": jax.random.normal(jax.random.fold_in(k, 1), (W, W))
            * (0.5 / np.sqrt(W)),
        }
        for k in keys
    ])
    # Embed/head live OUTSIDE the pipelined region (data-sharded on real
    # meshes; replicated here) — the documented composition rule.
    w_in = jax.random.normal(jax.random.key(1), (784, W)) * 0.05
    w_out = jax.random.normal(jax.random.key(2), (W, 10)) * 0.05

    opt = optax.adam(args.lr)
    params = (stacked, w_in, w_out)
    opt_state = opt.init(params)

    if args.schedule == "gpipe":
        pipe = make_pipeline(
            stage_fn, mesh, n_microbatches=n_micro,
            remat_stages=args.remat_stages,
        )

        def loss_fn(params, batch):
            stacked, w_in, w_out = params
            x, y = batch
            h = jnp.tanh(x @ w_in)
            h = pipe(stacked, h)
            logits = h @ w_out
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            acc = (logits.argmax(-1) == y).mean()
            return loss, acc

        @jax.jit
        def step(params, opt_state, batch):
            (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss, acc

    elif args.schedule == "hetero":
        # Embed and head INSIDE the pipeline: stage 0 maps [mb, 784] ->
        # [mb, W], the last stage banks [mb, 10] logits — no outside
        # composition rule. Per-stage params are a replicated tuple.
        from chainermn_tpu.parallel.pipeline import make_pipeline_hetero

        def embed_fn(p, x):
            return jnp.tanh(x @ p["w_in"])

        def head_fn(p, h):
            return h @ p["w_out"]

        fns = [embed_fn] + [stage_fn] * (n_stages - 2) + [head_fn]
        blocks = [
            jax.tree.map(lambda l: l[i], stacked)
            for i in range(n_stages - 2)
        ]
        params = tuple(
            [{"w_in": w_in}] + blocks + [{"w_out": w_out}]
        )
        opt_state = opt.init(params)
        pipe = make_pipeline_hetero(
            fns, mesh, n_microbatches=n_micro,
            remat_stages=args.remat_stages,
        )

        def loss_fn(params, batch):
            x, y = batch
            logits = pipe(params, x)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            acc = (logits.argmax(-1) == y).mean()
            return loss, acc

        @jax.jit
        def step(params, opt_state, batch):
            (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss, acc

    else:  # 1f1b: the engine IS the fwd+bwd; embed trains via input
        # grads, the softmax head via head grads.
        from chainermn_tpu.parallel.pipeline import make_pipeline_1f1b

        def head_loss(w_out, h_mb, y_mb):
            logits = h_mb @ w_out
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y_mb
            ).mean()

        def loss_grad_fn(w_out, h_mb, y_mb):
            loss, (dw, dh) = jax.value_and_grad(
                head_loss, argnums=(0, 1)
            )(w_out, h_mb, y_mb)
            return loss, (dw, dh)

        engine = make_pipeline_1f1b(
            stage_fn, loss_grad_fn, mesh, n_microbatches=n_micro,
        )
        # Forward-only apply for the accuracy metric (the engine returns
        # loss+grads, not the final-stage activations).
        pipe_apply = make_pipeline(stage_fn, mesh, n_microbatches=n_micro)

        @jax.jit
        def step(params, opt_state, batch):
            stacked, w_in, w_out = params
            x, y = batch

            def embed(w_in):
                return jnp.tanh(x @ w_in)

            h, embed_vjp = jax.vjp(embed, w_in)
            loss, g_stages, g_head, dh = engine(
                stacked, h, y, w_out, collect_input_grads=True
            )
            (g_in,) = embed_vjp(dh)
            grads = (g_stages, g_in, g_head)
            updates, opt_state = opt.update(grads, opt_state, params)
            logits = pipe_apply(stacked, h) @ w_out
            acc = (logits.argmax(-1) == y).mean()
            return optax.apply_updates(params, updates), opt_state, loss, acc

    rng = np.random.RandomState(0)
    centers = rng.randn(10, 784).astype(np.float32)
    for it in range(1, args.iterations + 1):
        y = rng.randint(0, 10, size=args.batchsize)
        x = centers[y] + 0.5 * rng.randn(args.batchsize, 784).astype(np.float32)
        params, opt_state, loss, acc = step(
            params, opt_state, (jnp.asarray(x), jnp.asarray(y))
        )
        if comm.rank == 0 and it % 50 == 0:
            print(f"iter {it}/{args.iterations} "
                  f"loss={float(loss):.4f} acc={float(acc):.4f}")
    if comm.rank == 0:
        print(f"final: loss={float(loss):.4f} acc={float(acc):.4f}")
    return float(acc)


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
