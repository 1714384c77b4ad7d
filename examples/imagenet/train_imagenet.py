"""Distributed ImageNet training — the benchmark workload.

Reference: ``examples/imagenet/train_imagenet.py`` (dagger) (SURVEY.md
section 2.8): ``mpiexec -n N python train_imagenet.py --arch resnet50
--communicator pure_nccl``. The BASELINE.json north star measures this
workload's scaling efficiency.

TPU-native: one process drives the mesh; the whole iteration (fwd, bwd,
bf16-compressed gradient psum, SGD) is one jitted SPMD program.

    python examples/imagenet/train_imagenet.py --arch resnet50 \
        --communicator xla --iterations 100 [--profile /tmp/trace]

Data: synthetic ImageNet-shaped samples by default (no network in this
environment); pass ``--train-root`` with a directory of ``.npy`` pairs to
train on real data — the training mechanics are identical.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

sys.path.insert(0, __file__.rsplit("/examples/", 1)[0])

import chainermn_tpu
from chainermn_tpu import global_except_hook
from chainermn_tpu.models import VisionTransformer, AlexNet, GoogLeNet, ResNet50
from chainermn_tpu.training import make_train_step
from chainermn_tpu.training.train_step import create_train_state

ARCHS = {
    # dropout off: a per-step rng is model-specific plumbing this throughput
    # example doesn't need
    "alex": lambda bn_ax, **kw: AlexNet(dropout_rate=0.0),
    "googlenet": lambda bn_ax, **kw: GoogLeNet(),
    "googlenetbn": lambda bn_ax, **kw: GoogLeNet(use_bn=True, bn_axis_name=bn_ax),
    "resnet50": lambda bn_ax, **kw: ResNet50(bn_axis_name=bn_ax, **kw),
    # The TPU-natural ImageNet family (round 5): pure large matmuls, no
    # MXU-starving small-channel convs, no BatchNorm cross-rank sync.
    "vit_s16": lambda bn_ax, **kw: VisionTransformer(**kw),
}


def synthetic_batch(rng, batch, size):
    x = rng.standard_normal((batch, size, size, 3), np.float32)
    y = rng.integers(0, 1000, size=(batch,)).astype(np.int32)
    return x, y


def main(argv=None):
    p = argparse.ArgumentParser(description="ChainerMN-TPU example: ImageNet")
    p.add_argument("--arch", default="resnet50", choices=sorted(ARCHS))
    p.add_argument("--communicator", default="xla")
    p.add_argument("--batchsize", type=int, default=64,
                   help="per-mesh-slot batch size")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--iterations", type=int, default=100)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--optimizer", default="sgd",
                   choices=["sgd", "lars", "lamb"],
                   help="sgd+momentum (default) or the large-batch "
                        "layer-adaptive optimizers — the regime the "
                        "reference's 15-minute/32K-batch ImageNet runs "
                        "lived in (arXiv:1711.04325)")
    p.add_argument("--double-buffering", action="store_true")
    p.add_argument("--local-sgd", type=int, default=0, metavar="H",
                   help="periodic parameter averaging every H steps "
                        "instead of the per-step gradient allreduce "
                        "(composes with --optimizer); 0 = off")
    p.add_argument("--allreduce-grad-dtype", default="bfloat16")
    p.add_argument("--error-feedback", action="store_true",
                   help="EF-SGD for the int8 quantized wire (requires "
                        "--allreduce-grad-dtype int8); shard-level on "
                        "the two_dimensional communicator")
    p.add_argument("--stem", default="standard",
                   choices=["standard", "space_to_depth"],
                   help="resnet50 input stem; space_to_depth trades the "
                        "MXU-hostile 3-channel 7x7 conv for a 48-channel "
                        "3x3 (measured +16%% img/s on v5e)")
    p.add_argument("--remat", nargs="?", const="full",
                   default=None,
                   choices=["full", "conv", "dots", "nothing"],
                   help="rematerialize blocks. resnet50: 'full' (save only "
                        "block inputs — max memory saving) or 'conv' (save "
                        "conv outputs, recompute the BN/relu chain — the "
                        "byte-cutting mode from the docs/benchmarks.md "
                        "roofline); bare --remat means 'full'. vit_s16: "
                        "'dots' (keep matmul outputs) or 'nothing' (the "
                        "LM policies)")
    p.add_argument("--profile", default=None,
                   help="directory for a jax.profiler trace of iters 10-20")
    p.add_argument("--train-root", default=None)
    p.add_argument("--native-loader", default=None, metavar="FILE.bin",
                   help="fixed-record file read by the C++ threaded "
                        "prefetch loader (chainermn_tpu.native.data_loader)")
    args = p.parse_args(argv)
    if args.local_sgd and (args.double_buffering or args.error_feedback):
        p.error("--local-sgd replaces the per-step gradient wire; "
                "--double-buffering/--error-feedback would be "
                "silently ignored")

    comm = chainermn_tpu.create_communicator(
        args.communicator,
        allreduce_grad_dtype=args.allreduce_grad_dtype or None,
    )
    global_except_hook._add_hook()
    if comm.rank == 0:
        print(f"communicator: {comm}  arch: {args.arch}")

    _REMAT_OF = {"resnet50": ("full", "conv"),
                 "vit_s16": ("dots", "nothing")}
    if args.remat and args.remat not in _REMAT_OF.get(args.arch, ()):
        p.error(
            f"--remat {args.remat} is not a policy of --arch {args.arch} "
            f"(valid for {args.arch}: {_REMAT_OF.get(args.arch, ())})")
    if args.stem != "standard" and args.arch != "resnet50":
        p.error(f"--stem is only supported for --arch resnet50 "
                f"(got {args.arch!r})")
    kw = {}
    if args.remat:
        kw["remat"] = True
        if args.remat != "full":
            kw["remat_policy"] = args.remat
    if args.arch == "resnet50":
        kw["stem"] = args.stem
    model = ARCHS[args.arch](comm.bn_axis_name, **kw)
    global_batch = args.batchsize * comm.size
    rng = np.random.default_rng(0)

    loader = None
    if args.native_loader:
        from chainermn_tpu.native.data_loader import NativeDataLoader

        hw = args.image_size
        # Each process reads only its own record-range shard (the dataset
        # scatter of SURVEY.md section 3.3 applied to files — same ±1
        # balance as scatter_dataset) and assembles the global batch from
        # it — sample-parallel across hosts.
        import os

        from chainermn_tpu.datasets.scatter_dataset import _shard_bounds

        n_proc, proc = jax.process_count(), jax.process_index()
        n_total = os.path.getsize(args.native_loader) // (hw * hw * 3 + 4)
        loader = NativeDataLoader(
            args.native_loader,
            [("image", np.uint8, (hw, hw, 3)), ("label", np.int32, ())],
            batch_size=global_batch,
            threads=4,
            prefetch=4,
            seed=proc,
            shard=_shard_bounds(n_total, n_proc, proc) if n_proc > 1 else None,
        )

    # u8 records cross host→device as u8 (4x fewer bytes) and normalise
    # on-device; the jitted cast fuses ahead of the first conv. The
    # prefetch_to_device wrapper keeps 2 batches in flight so the H2D
    # copy of batch t+1 overlaps the step running on batch t.
    _norm = jax.jit(lambda img: img.astype(jnp.float32) / 127.5 - 1.0)

    if loader is not None:
        from chainermn_tpu.training.prefetch import prefetch_to_device

        _prefetched = prefetch_to_device(
            ((b["image"], b["label"]) for b in loader), size=2
        )

        def next_batch():
            img, lab = next(_prefetched)
            return _norm(img), lab
    else:

        def next_batch():
            return synthetic_batch(rng, global_batch, args.image_size)

    x0, y0 = next_batch()

    variables = jax.jit(
        lambda k, xb: model.init(k, xb, train=True)
    )(jax.random.key(0), jnp.asarray(x0[: min(2, global_batch)]))
    batch_stats = variables.get("batch_stats", {})

    def loss_fn(params, batch, model_state):
        xb, yb = batch
        vars_in = {"params": params}
        mutable = []
        if batch_stats:
            vars_in["batch_stats"] = model_state
            mutable = ["batch_stats"]
        if mutable:
            logits, mutated = model.apply(
                vars_in, xb, train=True, mutable=mutable
            )
        else:
            logits = model.apply(vars_in, xb, train=True)
            mutated = {"batch_stats": model_state}
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, yb
        ).mean()
        acc = (logits.argmax(-1) == yb).mean()
        return loss, ({"accuracy": acc}, mutated.get("batch_stats", ()))

    inner_opt = {
        "sgd": lambda: optax.sgd(args.lr, momentum=0.9),
        "lars": lambda: optax.lars(args.lr),
        "lamb": lambda: optax.lamb(args.lr),
    }[args.optimizer]()
    if args.local_sgd:
        optimizer = chainermn_tpu.create_local_sgd(
            inner_opt, comm, sync_every=args.local_sgd,
        )
    else:
        optimizer = chainermn_tpu.create_multi_node_optimizer(
            inner_opt,
            comm,
            double_buffering=args.double_buffering,
            error_feedback=args.error_feedback,
        )
    state = create_train_state(
        variables["params"], optimizer, comm, model_state=batch_stats
    )
    step = make_train_step(loss_fn, optimizer, comm)

    t0 = time.perf_counter()
    for it in range(args.iterations):
        if args.profile and it == 10:
            jax.profiler.start_trace(args.profile)
        x, y = next_batch()
        state, metrics = step(state, (jnp.asarray(x), jnp.asarray(y)))
        if args.profile and it == 20:
            jax.block_until_ready(state.params)
            jax.profiler.stop_trace()
            if comm.rank == 0:
                print(f"profile written to {args.profile}")
        if comm.rank == 0 and (it + 1) % 10 == 0:
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            ips = global_batch * (it + 1) / dt
            print(
                f"iter {it + 1}/{args.iterations} "
                f"loss={float(metrics['loss']):.4f} "
                f"acc={float(metrics['accuracy']):.4f} ({ips:.1f} img/s)"
            )
    jax.block_until_ready(state.params)
    if comm.rank == 0:
        total = time.perf_counter() - t0
        print(
            f"done: {args.iterations} iters, "
            f"{global_batch * args.iterations / total:.1f} images/sec"
        )


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    main()
