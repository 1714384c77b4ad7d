"""Distributed detection training — the Faster-RCNN-style stress workload.

Reference: the fork's benchmark configs list "ChainerCV Faster-RCNN (stress
hierarchical communicator, odd grad shapes)" (BASELINE.json; SURVEY.md §7).
This example reproduces the *stress profile* on synthetic data:

- multi-scale images drawn from a small (H, W) bucket ladder — one jit
  compile per bucket, counted and reported (the dynamic-shape discipline);
- ragged ground-truth boxes, padded + masked per image;
- the hierarchical communicator by default (the config this workload was
  meant to stress), odd-channel gradients through the fused grad pmean.

    python examples/detection/train_detection.py --communicator hierarchical
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
from chainermn_tpu.models.detection import (
    TinyDetector,
    TwoStageDetector,
    detection_loss,
    two_stage_loss,
)

#: (H, W) bucket ladder — multiples of 32 (backbone stride x2 safety)
SHAPE_BUCKETS = ((256, 256), (256, 320), (320, 256), (320, 320))
MAX_BOXES = 8


def synthetic_batch(rng, batch, hw, with_labels=False):
    """Images + padded boxes (+ class labels) for one shape bucket."""
    H, W = hw
    images = rng.randn(batch, H, W, 3).astype(np.float32)
    n = rng.randint(1, MAX_BOXES + 1, size=batch)
    boxes = np.zeros((batch, MAX_BOXES, 4), np.float32)
    mask = np.zeros((batch, MAX_BOXES), np.float32)
    for i in range(batch):
        for j in range(n[i]):
            y0 = rng.uniform(0, H - 64)
            x0 = rng.uniform(0, W - 64)
            h = rng.uniform(32, min(160, H - y0))
            w = rng.uniform(32, min(160, W - x0))
            boxes[i, j] = (y0, x0, y0 + h, x0 + w)
            mask[i, j] = 1.0
    if with_labels:
        labels = rng.randint(0, 7, size=(batch, MAX_BOXES)).astype(np.int32)
        return images, boxes, mask, labels
    return images, boxes, mask


def main(argv=None):
    p = argparse.ArgumentParser(
        description="ChainerMN-TPU example: detection stress (Faster-RCNN-style)"
    )
    p.add_argument("--communicator", default="hierarchical")
    p.add_argument("--batchsize", type=int, default=8)
    p.add_argument("--iterations", type=int, default=24)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--two-stage", action="store_true",
                   help="Faster-RCNN-style RPN -> static top-K proposals "
                        "-> RoI-align -> per-RoI class+box head")
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(args.communicator)
    global_except_hook._add_hook()
    if comm.rank == 0:
        print(f"communicator: {comm}")

    model = TwoStageDetector() if args.two_stage else TinyDetector()
    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(args.lr), comm
    )
    axes = comm.grad_axes

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def build_step():
        def local_step(params, opt_state, batch):
            if args.two_stage:
                images, boxes, mask, labels = batch
            else:
                images, boxes, mask = batch

            def loss_fn(p):
                if args.two_stage:
                    return two_stage_loss(
                        model.apply(p, images), boxes, mask, labels
                    )
                obj, deltas = model.apply(p, images)
                return detection_loss(obj, deltas, boxes, mask)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            grads = jax.lax.pmean(grads, axes)
            loss = jax.lax.pmean(loss, axes)
            updates, opt_state = optimizer.actual_optimizer.update(
                grads, opt_state, params
            )
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return jax.jit(
            shard_map(
                local_step,
                mesh=comm.mesh,
                in_specs=(P(), P(), P(axes)),
                out_specs=(P(), P(), P()),
                check_vma=False,
            )
        )

    step = build_step()
    rng = np.random.RandomState(comm.rank * 0 + 11)  # same data all ranks
    params = None
    opt_state = None
    compiled_buckets = set()

    for it in range(args.iterations):
        hw = SHAPE_BUCKETS[it % len(SHAPE_BUCKETS)]
        batch = synthetic_batch(rng, args.batchsize, hw,
                                with_labels=args.two_stage)
        images = batch[0]
        if params is None:
            params = model.init(jax.random.key(0), jnp.asarray(images[:1]))
            params = comm.bcast_data(params)
            opt_state = optimizer.actual_optimizer.init(params)
        if hw not in compiled_buckets:
            compiled_buckets.add(hw)
            if comm.rank == 0:
                print(f"  compiling shape bucket {hw}")
        params, opt_state, loss = step(
            params, opt_state, tuple(jnp.asarray(a) for a in batch),
        )
        if comm.rank == 0 and (it + 1) % 8 == 0:
            print(f"iter {it + 1}/{args.iterations} loss={float(loss):.4f}")

    if comm.rank == 0:
        print(f"final loss={float(loss):.4f} "
              f"({len(compiled_buckets)} shape-bucket compilations)")
    return float(loss)


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
