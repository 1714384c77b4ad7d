"""Distributed MNIST training — the canonical smoke test.

Reference: ``examples/mnist/train_mnist.py`` (dagger) (SURVEY.md section 2.8):
``mpiexec -n N python train_mnist.py --communicator <name> --gpu``.

TPU-native: one process drives the whole mesh; run

    python examples/mnist/train_mnist.py --communicator naive      # CPU mesh
    python examples/mnist/train_mnist.py --communicator xla        # TPU

No torchvision/network: MNIST is synthesised deterministically when the real
ubyte files are absent (the training mechanics — scatter, psum, optimizer,
eval — are identical either way).
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
from chainermn_tpu.models import MLP
from chainermn_tpu.training import Trainer, make_train_step, make_eval_step
from chainermn_tpu.training.train_step import create_train_state


def get_mnist(n_train=8192, n_test=1024, seed=0):
    """Synthetic stand-in with MNIST shapes: 10 gaussian blobs in 784-d.
    Learnable by an MLP, so accuracy is a meaningful smoke signal."""
    rng = np.random.RandomState(seed)
    centers = rng.randn(10, 784).astype(np.float32)

    def make(n):
        y = rng.randint(0, 10, size=n)
        x = centers[y] + 0.5 * rng.randn(n, 784).astype(np.float32)
        return [(x[i], np.int32(y[i])) for i in range(n)]

    return make(n_train), make(n_test)


def main(argv=None):
    p = argparse.ArgumentParser(description="ChainerMN-TPU example: MNIST")
    p.add_argument("--communicator", default="naive")
    p.add_argument("--batchsize", type=int, default=256)
    p.add_argument("--iterations", type=int, default=200)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--double-buffering", action="store_true")
    p.add_argument("--local-sgd", type=int, default=0, metavar="H",
                   help="periodic parameter averaging every H steps "
                        "instead of the per-step gradient allreduce; "
                        "0 = off")
    p.add_argument("--outer-momentum", type=float, default=0.0,
                   help="DiLoCo outer heavy-ball momentum on the sync "
                        "deltas (try 0.6-0.9 with a reduced inner lr; "
                        "stacking it on an aggressive inner momentum "
                        "can diverge)")
    p.add_argument("--allreduce-grad-dtype", default=None)
    p.add_argument("--reduction-schedule", default=None,
                   choices=("flat", "two_level", "zero"),
                   help="gradient-reduction schedule; default: the "
                        "communicator's own strategy")
    p.add_argument("--error-feedback", action="store_true",
                   help="EF-SGD residual feedback over the int8 wire "
                        "(requires --allreduce-grad-dtype int8)")
    p.add_argument("--checkpoint", default=None, metavar="DIR",
                   help="fault-tolerant snapshots every --checkpoint-interval "
                        "iters (async native writer); resumes automatically "
                        "from the newest snapshot all ranks share")
    p.add_argument("--checkpoint-interval", type=int, default=50)
    p.add_argument("--prefetch", type=int, default=0,
                   help="device-side input double buffering: batches kept "
                        "in flight ahead of the step (0 = off)")
    p.add_argument("--checkpoint-backend", default="npz",
                   choices=("npz", "orbax"),
                   help="npz: the framework's per-rank snapshot format; "
                        "orbax: stock orbax CheckpointManager storage with "
                        "the same cross-rank resume agreement")
    args = p.parse_args(argv)

    comm = chainermn_tpu.create_communicator(
        args.communicator, allreduce_grad_dtype=args.allreduce_grad_dtype
    )
    global_except_hook._add_hook()
    if comm.rank == 0:
        print(f"communicator: {comm}")

    train, test = get_mnist()
    # No-transfer scatter: each process computes its own shard (SURVEY 3.3).
    train = chainermn_tpu.scatter_dataset(train, comm, shuffle=True, seed=42)
    test = chainermn_tpu.scatter_dataset(test, comm)

    model = MLP()
    params = model.init(jax.random.key(0), jnp.zeros((1, 784)))["params"]

    if args.local_sgd:
        bad = [f for f, on in (
            ("--double-buffering", args.double_buffering),
            ("--error-feedback", args.error_feedback),
            ("--allreduce-grad-dtype", args.allreduce_grad_dtype),
            ("--reduction-schedule", args.reduction_schedule),
        ) if on]
        if bad:
            p.error(f"--local-sgd replaces the per-step gradient wire; "
                    f"{', '.join(bad)} would be silently ignored")
        optimizer = chainermn_tpu.create_local_sgd(
            optax.sgd(args.lr, momentum=0.9), comm,
            sync_every=args.local_sgd,
            outer_momentum=args.outer_momentum,
        )
    else:
        optimizer = chainermn_tpu.create_multi_node_optimizer(
            optax.sgd(args.lr, momentum=0.9),
            comm,
            double_buffering=args.double_buffering,
            error_feedback=args.error_feedback,
            reduction_schedule=args.reduction_schedule,
        )
    state = create_train_state(params, optimizer, comm)

    def loss_fn(params, batch):
        x, y = batch
        logits = model.apply({"params": params}, x)
        loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
        acc = (logits.argmax(-1) == y).mean()
        return loss, {"accuracy": acc}

    step = make_train_step(loss_fn, optimizer, comm)

    def metric_fn(params, batch):
        x, y = batch
        logits = model.apply({"params": params}, x)
        return {
            "val_loss": optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean(),
            "val_acc": (logits.argmax(-1) == y).mean(),
        }

    eval_step = make_eval_step(metric_fn, comm)
    evaluator = chainermn_tpu.create_multi_node_evaluator(
        _evaluate(eval_step, test, args.batchsize, comm), comm
    )

    ckpt = None
    start_iteration = 0
    if args.checkpoint:
        if args.checkpoint_backend == "orbax":
            from chainermn_tpu.extensions import create_orbax_checkpointer

            ckpt = create_orbax_checkpointer(
                "mnist", comm, path=args.checkpoint
            )
        else:
            ckpt = chainermn_tpu.create_multi_node_checkpointer(
                "mnist", comm, path=args.checkpoint
            )
        state, restored_it = ckpt.maybe_load(state)
        if restored_it is not None:
            start_iteration = restored_it
            if comm.rank == 0:
                print(f"resumed from iteration {restored_it}")

    train_iter = chainermn_tpu.create_synchronized_iterator(
        train, args.batchsize, comm, seed=1
    )
    trainer = Trainer(step, state, train_iter, comm, log_interval=50,
                      prefetch=args.prefetch)

    def run_eval(tr):
        metrics = evaluator(tr.state)
        if comm.rank == 0:
            print("  eval:", {k: round(v, 4) for k, v in metrics.items()})

    trainer.extend(run_eval, interval=100)
    if ckpt is not None:
        def snapshot(tr):
            # async: serialize now, write+fsync on the C++ worker thread
            ckpt.save(tr.state, start_iteration + tr.iteration, block=False)

        trainer.extend(snapshot, interval=args.checkpoint_interval)
    state = trainer.run(max(0, args.iterations - start_iteration))
    if ckpt is not None:
        # Label with the TRUE iteration: when a restore already exceeded
        # --iterations, trainer.run did 0 steps and the weights are still
        # start_iteration's.
        ckpt.save(state, start_iteration + trainer.iteration, block=False)
        ckpt.close()  # drain async saves + release the backend

    final = evaluator(state)
    if comm.rank == 0:
        print("final:", {k: round(v, 4) for k, v in final.items()})
    return final


def _evaluate(eval_step, dataset, batchsize, comm):
    from chainermn_tpu.training.trainer import (
        default_collate,
        host_local_batch_to_global,
    )

    def fn(st):
        totals, n = {}, 0
        items = list(dataset)
        n_batches = max(0, (len(items) - batchsize) // batchsize + 1)
        if comm.host.size > 1:
            # Batch assembly below is collective: every process must run
            # the same number of iterations even if shard sizes differ ±1.
            n_batches = min(comm.allgather_obj(n_batches))
        for b in range(n_batches):
            i = b * batchsize
            batch = host_local_batch_to_global(
                default_collate(items[i : i + batchsize]), comm
            )
            m = eval_step(st.params, batch, st.model_state)
            for k, v in m.items():
                totals[k] = totals.get(k, 0.0) + float(v)
            n += 1
        return {k: v / max(n, 1) for k, v in totals.items()}

    return fn


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
