"""Tensor-parallel transformer block training — Megatron-style, end to end.

The reference's only intra-layer parallelism was the channel-split
convolution example (``examples/parallel_convolution`` (dagger)); this is
the general form on the :mod:`chainermn_tpu.parallel.tensor` library: a
transformer block with heads-sharded attention and hidden-sharded MLP over
a ``('data', 'model')`` mesh — exactly one ``psum`` per column→row pair,
gradients taken inside ``shard_map`` (the library's usage contract), data
parallelism composed on the second mesh axis.

    python examples/tensor_parallel/train_tp_transformer.py
    python examples/tensor_parallel/train_tp_transformer.py --dp 1  # tp-only

The task: next-token-style regression on sequences from a fixed random
teacher transformer — the student matches it only if attention AND MLP
gradients flow correctly through the sharded layers.
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
from chainermn_tpu.parallel.tensor import (
    stack_tp_params,
    tp_attention,
    tp_mlp,
)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: Megatron-style tensor parallelism"
    )
    p.add_argument("--communicator", default="naive")
    p.add_argument("--batchsize", type=int, default=32)
    p.add_argument("--seq-len", type=int, default=16)
    p.add_argument("--d-model", type=int, default=32)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--iterations", type=int, default=200)
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--dp", type=int, default=None,
                   help="data-parallel width; model axis gets the rest "
                        "(default: 2 when the device count allows, else 1)")
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(args.communicator)
    global_except_hook._add_hook()
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    n = comm.size
    if args.dp is None:
        args.dp = 2 if n % 2 == 0 and n > 1 else 1
    if n % args.dp:
        raise SystemExit(f"--dp {args.dp} must divide the device count {n}")
    tp = n // args.dp
    if args.n_heads % tp:
        raise SystemExit(f"--n-heads {args.n_heads} must divide by tp={tp}")
    mesh = Mesh(
        np.array(comm.mesh.devices.flat).reshape(args.dp, tp),
        ("data", "model"),
    )
    if comm.rank == 0:
        print(f"tensor parallel: dp={args.dp} x tp={tp}, "
              f"{args.n_heads} heads, d_model={args.d_model}")

    D, FF = args.d_model, 4 * args.d_model

    def init_full(seed):
        ks = jax.random.split(jax.random.key(seed), 6)
        s = 1.0 / np.sqrt(D)
        return {
            "wq": jax.random.normal(ks[0], (D, D)) * s,
            "wk": jax.random.normal(ks[1], (D, D)) * s,
            "wv": jax.random.normal(ks[2], (D, D)) * s,
            "wo": jax.random.normal(ks[3], (D, D)) * s,
            "w1": jax.random.normal(ks[4], (D, FF)) * s,
            "w2": jax.random.normal(ks[5], (FF, D)) * (1.0 / np.sqrt(FF)),
        }

    def shard_full(full):
        return {
            "wq": stack_tp_params(full["wq"], tp, 1),
            "wk": stack_tp_params(full["wk"], tp, 1),
            "wv": stack_tp_params(full["wv"], tp, 1),
            "wo": stack_tp_params(full["wo"], tp, 0),
            "w1": stack_tp_params(full["w1"], tp, 1),
            "w2": stack_tp_params(full["w2"], tp, 0),
        }

    def block(p, x):
        h = x + tp_attention(
            x, p["wq"], p["wk"], p["wv"], p["wo"],
            axis_name="model", n_heads=args.n_heads, causal=True,
        )
        return h + tp_mlp(h, p["w1"], None, p["w2"], None, axis_name="model")

    params = shard_full(init_full(0))
    opt = optax.adam(args.lr)
    opt_state = opt.init(params)
    p_spec = jax.tree.map(lambda _: P("model"), params)
    s_spec = jax.tree.map(
        lambda l: P("model") if getattr(l, "ndim", 0) >= 1 else P(), opt_state
    )

    def local_step(params, opt_state, x, t):
        def loss_fn(params):
            local = jax.tree.map(lambda l: l[0], params)
            y = block(local, x)
            return jnp.mean((y - t) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = jax.lax.pmean(loss, ("data", "model"))
        # TP-sharded weight grads are exact per shard; average over data.
        grads = jax.lax.pmean(grads, "data")
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    step = jax.jit(
        shard_map(
            local_step,
            mesh=mesh,
            in_specs=(p_spec, s_spec, P("data"), P("data")),
            out_specs=(p_spec, s_spec, P()),
            check_vma=False,
        )
    )

    # Teacher: a fixed full-width block generating the targets.
    teacher = init_full(123)

    @jax.jit
    def teacher_block(x):
        from chainermn_tpu.ops.attention import dot_product_attention

        B, T = x.shape[:2]
        hd = D // args.n_heads
        q = (x @ teacher["wq"]).reshape(B, T, args.n_heads, hd)
        k = (x @ teacher["wk"]).reshape(B, T, args.n_heads, hd)
        v = (x @ teacher["wv"]).reshape(B, T, args.n_heads, hd)
        h = x + dot_product_attention(q, k, v, causal=True).reshape(B, T, D) @ teacher["wo"]
        return h + jax.nn.gelu(h @ teacher["w1"]) @ teacher["w2"]

    rng = np.random.RandomState(0)
    for it in range(1, args.iterations + 1):
        x = jnp.asarray(
            rng.randn(args.batchsize, args.seq_len, D).astype(np.float32)
        )
        t = teacher_block(x)
        params, opt_state, loss = step(params, opt_state, x, t)
        if comm.rank == 0 and it % 50 == 0:
            print(f"iter {it}/{args.iterations} loss={float(loss):.4f}")
    if comm.rank == 0:
        print(f"final: loss={float(loss):.4f}")
    return float(loss)


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
