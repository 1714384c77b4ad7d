"""Expert-parallel (MoE) training — capacity-bounded routing, end to end.

Absent from the reference (SURVEY.md section 2.2 lists expert parallelism
as the TPU-era extension); this example trains a residual MoE classifier
over an ``'expert'`` mesh axis: one expert MLP per shard, tokens routed by
a learned gate through two ``all_to_all``s
(:func:`chainermn_tpu.parallel.moe.moe_layer_local`), Switch top-1 or
GShard top-2 routing, with the standard load-balancing auxiliary loss
keeping the gate from collapsing onto one expert.

    python examples/moe/train_moe_mlp.py --iterations 200
    python examples/moe/train_moe_mlp.py --topk 2 --aux-weight 0.01

The task: 10-blob classification where each blob prefers a different
random linear map — expert specialisation measurably helps, so rising
accuracy is a real signal that routing + expert training both work.
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
from chainermn_tpu.parallel.moe import (
    load_balancing_loss,
    make_expert_params,
    moe_layer_local,
)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: expert parallelism (MoE)"
    )
    p.add_argument("--communicator", default="naive")
    p.add_argument("--batchsize", type=int, default=256)
    p.add_argument("--iterations", type=int, default=200)
    p.add_argument("--width", type=int, default=64)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--topk", type=int, default=1, choices=(1, 2),
                   help="1: Switch top-1 routing; 2: GShard top-2")
    p.add_argument("--capacity-factor", type=float, default=1.5)
    p.add_argument("--dispatch-impl", default="auto",
                   choices=("auto", "einsum", "sort"),
                   help="queue assembly: dense one-hot einsum (reference), "
                        "index sort/scatter (scalable), or auto (default: "
                        "device-aware via the chainermn_tpu.tuning "
                        "registry)")
    p.add_argument("--aux-weight", type=float, default=1e-2,
                   help="load-balancing auxiliary loss weight")
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(args.communicator)
    global_except_hook._add_hook()
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    n_experts = comm.size
    mesh = Mesh(
        np.array(comm.mesh.devices.flat).reshape(n_experts), ("expert",)
    )
    if comm.rank == 0:
        print(f"moe: {n_experts} experts, top-{args.topk} routing, "
              f"capacity x{args.capacity_factor}")

    W = args.width

    def expert_fn(params, x):
        return jax.nn.gelu(x @ params["w1"]) @ params["w2"]

    def expert_init(rng):
        k1, k2 = jax.random.split(rng)
        return {
            "w1": jax.random.normal(k1, (W, 2 * W)) / np.sqrt(W),
            "w2": jax.random.normal(k2, (2 * W, W)) / np.sqrt(2 * W),
        }

    dense = {
        "w_in": jax.random.normal(jax.random.key(0), (20, W)) * 0.3,
        "router": jax.random.normal(jax.random.key(1), (W, n_experts)) * 0.1,
        "w_out": jax.random.normal(jax.random.key(3), (W, 10)) * 0.1,
    }
    experts = make_expert_params(expert_init, jax.random.key(2), n_experts)

    # Two optimizers: dense params (and their adam moments) replicate;
    # expert params (and moments) shard over the 'expert' axis — the
    # moments mirror the param shapes, so one spec rule covers the state:
    # arrays shard, scalars (step counts) replicate.
    opt_d = optax.adam(args.lr)
    opt_e = optax.adam(args.lr)
    opt_d_state = opt_d.init(dense)
    opt_e_state = opt_e.init(experts)
    e_state_spec = jax.tree.map(
        lambda l: P("expert") if getattr(l, "ndim", 0) >= 1 else P(),
        opt_e_state,
    )

    def local_step(dense, experts, opt_d_state, opt_e_state, x, y):
        def loss_fn(dense, experts):
            h = jnp.tanh(x @ dense["w_in"])
            my_experts = jax.tree.map(lambda l: l[0], experts)
            # Aux loss must regularise the SAME router distribution the
            # layer dispatched with — i.e. the pre-residual activations.
            aux = load_balancing_loss(h @ dense["router"])
            h = h + moe_layer_local(
                h, dense["router"], expert_fn, my_experts, "expert",
                capacity_factor=args.capacity_factor, k=args.topk,
                dispatch_impl=args.dispatch_impl,
            )
            logits = h @ dense["w_out"]
            task = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            acc = (logits.argmax(-1) == y).mean()
            return task + args.aux_weight * aux, (task, acc)

        (loss, (task, acc)), (g_d, g_e) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True
        )(dense, experts)
        # Token shards differ per slot: dense grads average over the mesh;
        # expert grads are per-shard by construction (each shard owns its
        # expert, fed through the all_to_all by every shard's tokens).
        g_d = jax.lax.pmean(g_d, "expert")
        task = jax.lax.pmean(task, "expert")
        acc = jax.lax.pmean(acc, "expert")
        upd_d, opt_d_state = opt_d.update(g_d, opt_d_state, dense)
        upd_e, opt_e_state = opt_e.update(g_e, opt_e_state, experts)
        return (
            optax.apply_updates(dense, upd_d),
            optax.apply_updates(experts, upd_e),
            opt_d_state,
            opt_e_state,
            task,
            acc,
        )

    e_spec = jax.tree.map(lambda _: P("expert"), experts)
    step = jax.jit(
        shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(), e_spec, P(), e_state_spec, P("expert"),
                      P("expert")),
            out_specs=(P(), e_spec, P(), e_state_spec, P(), P()),
            check_vma=False,
        )
    )

    rng = np.random.RandomState(0)
    maps = rng.randn(10, 20, 20).astype(np.float32) * 0.5
    centers = rng.randn(10, 20).astype(np.float32) * 2
    for it in range(1, args.iterations + 1):
        y = rng.randint(0, 10, size=args.batchsize)
        base = centers[y] + 0.3 * rng.randn(args.batchsize, 20).astype(np.float32)
        x = np.einsum("bi,bij->bj", base, maps[y]) + base
        dense, experts, opt_d_state, opt_e_state, loss, acc = step(
            dense, experts, opt_d_state, opt_e_state,
            jnp.asarray(x), jnp.asarray(y),
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
