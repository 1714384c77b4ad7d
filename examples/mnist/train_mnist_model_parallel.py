"""Model-parallel MNIST: the MLP split across two stages.

Reference: ``examples/mnist/train_mnist_model_parallel.py`` (dagger)
(SURVEY.md section 2.8): the 3-layer MLP is split across 2 ranks connected by
differentiable send/recv; rank 1 holds the loss.

TPU-native: the two stages are a :class:`MultiNodeChainList` executed as one
SPMD program over a ``'stage'`` mesh axis — stage transfers are ppermutes,
backward crosses the boundary automatically.

    python examples/mnist/train_mnist_model_parallel.py --iterations 100
"""

from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import PartitionSpec as P

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

import chainermn_tpu
from chainermn_tpu.links import MultiNodeChainList
from examples.mnist.train_mnist import get_mnist


def main(argv=None):
    p = argparse.ArgumentParser(description="model-parallel MNIST")
    p.add_argument("--communicator", default="naive")
    p.add_argument("--batchsize", type=int, default=128)
    p.add_argument("--iterations", type=int, default=100)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--n-units", type=int, default=256)
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(args.communicator)
    if comm.rank == 0:
        print(f"communicator: {comm} (2-stage model parallel)")

    # Single-controller SPMD: one process feeds the whole mesh. (In the
    # reference, non-data ranks held a create_empty_dataset placeholder; that
    # pattern applies here only in multi-process model parallelism.)
    train, _ = get_mnist()

    n_units = args.n_units

    def stage0_fn(params, x):
        h = jnp.maximum(x @ params["w0"] + params["b0"], 0.0)
        return jnp.maximum(h @ params["w1"] + params["b1"], 0.0)

    def stage0_init(rng, x):
        k0, k1 = jax.random.split(rng)
        s0 = 1.0 / np.sqrt(x.shape[-1])
        s1 = 1.0 / np.sqrt(n_units)
        return {
            "w0": jax.random.normal(k0, (x.shape[-1], n_units)) * s0,
            "b0": jnp.zeros(n_units),
            "w1": jax.random.normal(k1, (n_units, n_units)) * s1,
            "b1": jnp.zeros(n_units),
        }

    def stage1_fn(params, h):
        return h @ params["w2"] + params["b2"]

    def stage1_init(rng, h):
        s = 1.0 / np.sqrt(h.shape[-1])
        return {
            "w2": jax.random.normal(rng, (h.shape[-1], 10)) * s,
            "b2": jnp.zeros(10),
        }

    model = MultiNodeChainList(comm, axis_name=comm.axis_name)
    model.add_link(stage0_fn, rank=0, rank_out=1, init_fn=stage0_init)
    model.add_link(stage1_fn, rank=1, rank_in=0, init_fn=stage1_init)

    x0 = jnp.zeros((args.batchsize, 784))
    params = model.init(jax.random.key(0), x0)
    opt = optax.sgd(args.lr, momentum=0.9)
    opt_state = opt.init(params)

    mesh = comm.mesh
    ax = comm.axis_name

    def sharded_loss(params, x, y):
        """Replicated scalar loss of the multi-stage model. Differentiate
        *outside* the shard_map: the per-stage cotangents then route back
        through the stage transfers exactly once (differentiating a
        replicated loss inside each shard would multiply gradients by the
        axis size — see tests/test_links.py::test_chain_gradients...)."""

        def body(params, x, y):
            logits = model.apply(params, x)
            # logits live on stage 1's shard (zeros elsewhere); the psum is
            # both the broadcast and, under AD, the single fan-in point.
            logits = jax.lax.psum(logits, ax)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            acc = (logits.argmax(-1) == y).mean()
            return loss, acc

        return shard_map(
            body, mesh=mesh, in_specs=(P(), P(), P()), out_specs=(P(), P()),
            check_vma=False,
        )(params, x, y)

    @jax.jit
    def step(params, opt_state, x, y):
        (loss, acc), grads = jax.value_and_grad(
            sharded_loss, has_aux=True
        )(params, x, y)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss, acc

    items = list(train)
    rng = np.random.RandomState(1)
    acc = jnp.zeros(())
    for it in range(args.iterations):
        idx = rng.randint(0, len(items), size=args.batchsize)
        x = np.stack([items[i][0] for i in idx])
        y = np.stack([items[i][1] for i in idx])
        params, opt_state, loss, acc = step(params, opt_state, x, y)
        if comm.rank == 0 and (it + 1) % 25 == 0:
            print(
                f"iter {it + 1}/{args.iterations} "
                f"loss={float(loss):.4f} acc={float(acc):.4f}"
            )
    final_acc = float(acc)
    if comm.rank == 0:
        print(f"final acc={final_acc:.4f}")
    return final_acc


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
