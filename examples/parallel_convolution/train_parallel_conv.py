"""Channel-parallel convolution — tensor parallelism over conv filters.

Reference: ``examples/parallel_convolution/`` (dagger) (SURVEY.md sections
2.2, 2.8): a convolution's output channels split across ranks, partial
results exchanged with collective functions — the reference's only
tensor-parallel pattern, built by hand from send/recv.

TPU-native, this is where the declarative model strictly dominates
(SURVEY.md section 2.2): shard the filter dimension of the conv weights
over a ``'model'`` mesh axis with ``NamedSharding`` and let pjit/XLA insert
the collectives. No bespoke communication code at all — compare the
reference's hand-rolled halo exchange.

    python examples/parallel_convolution/train_parallel_conv.py \
        --communicator naive --iterations 50
"""

from __future__ import annotations

import argparse
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

import chainermn_tpu
from chainermn_tpu import global_except_hook


class ConvNet(nn.Module):
    """Small CNN whose conv channels will be sharded over the mesh."""

    num_classes: int = 10
    width: int = 64

    @nn.compact
    def __call__(self, x):
        x = nn.relu(nn.Conv(self.width, (3, 3))(x))
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = nn.relu(nn.Conv(2 * self.width, (3, 3))(x))
        x = nn.avg_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(self.num_classes)(x)


def channel_sharding(params, mesh, axis="model"):
    """PartitionSpec tree: conv kernels shard their *output-channel* dim
    (last axis), biases shard their only dim — the channel-parallel layout
    of the reference example, expressed declaratively."""

    def spec_for(path, leaf):
        name = "/".join(str(p.key) for p in path if hasattr(p, "key"))
        if "Conv" in name and leaf.ndim == 4:  # HWIO kernel
            return P(None, None, None, axis)
        if "Conv" in name and leaf.ndim == 1:  # bias
            return P(axis)
        return P()  # dense head + others replicated

    return jax.tree_util.tree_map_with_path(spec_for, params)


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: channel-parallel convolution"
    )
    p.add_argument("--communicator", default="naive")
    p.add_argument("--batchsize", type=int, default=64)
    p.add_argument("--iterations", type=int, default=50)
    p.add_argument("--lr", type=float, default=0.05)
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(args.communicator)
    global_except_hook._add_hook()
    mesh = jax.sharding.Mesh(
        comm.mesh.devices.reshape(-1), ("model",)
    )
    if comm.rank == 0:
        print(f"communicator: {comm} — conv channels sharded over 'model'")

    rng = np.random.RandomState(0)
    centers = rng.randn(10, 16, 16, 3).astype(np.float32)

    def batch():
        y = rng.randint(0, 10, size=args.batchsize)
        x = centers[y] + 0.3 * rng.randn(
            args.batchsize, 16, 16, 3
        ).astype(np.float32)
        return jnp.asarray(x), jnp.asarray(y)

    model = ConvNet()
    x0, _ = batch()
    params = model.init(jax.random.key(0), x0[:1])["params"]

    # Declarative channel parallelism: place the params sharded; jit does
    # the rest (collectives inserted by XLA from sharding propagation).
    specs = channel_sharding(params, mesh)
    params = jax.tree.map(
        lambda l, s: jax.device_put(l, NamedSharding(mesh, s)), params, specs
    )
    opt = optax.sgd(args.lr, momentum=0.9)
    opt_state = jax.jit(opt.init)(params)

    @jax.jit
    def step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply({"params": p}, x)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            return loss, (logits.argmax(-1) == y).mean()

        (loss, acc), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss, acc

    acc = jnp.zeros(())
    for it in range(args.iterations):
        x, y = batch()
        params, opt_state, loss, acc = step(params, opt_state, x, y)
        if comm.rank == 0 and (it + 1) % 10 == 0:
            print(
                f"iter {it + 1}/{args.iterations} "
                f"loss={float(loss):.4f} acc={float(acc):.4f}"
            )
    # Verify the kernels really are channel-sharded:
    k1 = params["Conv_0"]["kernel"]
    if comm.rank == 0:
        print(
            f"Conv_0 kernel sharding: {k1.sharding.spec} "
            f"final acc={float(acc):.4f}"
        )
    return float(acc)


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
