"""Bottleneck ResNets built from the program's ``ResNet``, as
``examples/imagenet/train_imagenet.py`` and ``bench.py:_resnet_setup``
build them: bf16 compute, f32 parameters and statistics, momentum SGD,
``uint8`` input cast and normalised on the device by the loss function."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import costs  # benchmark/ is on the path of whoever loads a family

SAMPLE_KIND = "images"
SAMPLE_UNIT = "images"


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Model FLOPs of one image, forward and backward: three times the
    forward pass's, at 2 FLOPs a multiply-add."""
    macs = costs.resnet_bottleneck_forward_macs(
        config["stage_sizes"], config["num_filters"], config["image_size"],
        config["num_classes"],
    )
    return 3.0 * 2.0 * macs


def kernel_costs(config: dict, job: dict) -> dict:
    return {}  # no hand-written kernel runs in this family's step


def normalise(images):
    return images.astype(jnp.float32) / 127.5 - 1.0


class Family:
    def __init__(self, config: dict, job: dict):
        from chainermn_tpu.models.resnet import BottleneckBlock, ResNet

        self.config, self.job = config, job
        self.samples_per_row = 1
        self.check_rows = 8
        self.reference_block = None  # BatchNorm couples the whole batch
        if config["bottleneck_expansion"] != 4:
            raise ValueError("the program's BottleneckBlock expands by 4")
        if job.get("remat", "none") != "none":
            raise ValueError("this family's cells run without remat")
        train = config["training"]
        self.model = ResNet(
            stage_sizes=tuple(config["stage_sizes"]),
            block_cls=BottleneckBlock,
            num_classes=config["num_classes"],
            num_filters=config["num_filters"],
            compute_dtype=jnp.dtype(train["compute_dtype"]).type,
            bn_momentum=config["assumed"]["bn_momentum"],
            stem=config["stem"],
        )
        model = self.model

        def loss_fn(params, batch, model_state):
            images, labels = batch
            logits, mutated = model.apply(
                {"params": params, "batch_stats": model_state},
                normalise(images), train=True, mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()
            return loss, ({}, mutated["batch_stats"])

        self.loss_fn = loss_fn

    def init(self, seed: int):
        """``(params, batch_stats, check_params)`` on the device in one
        jitted call. ``params`` is the program's own initialisation and is
        what the cell trains. ``check_params`` is the tree the gradient
        comparison with the reference runs on: the same, but that the last
        BatchNorm scale of each block, which the program zero-initialises,
        is drawn from ``assumed.last_bn_scale`` (see the configuration
        file for why)."""
        c = self.config
        lo, hi = c["assumed"]["last_bn_scale"]
        dummy = jnp.zeros((2, c["image_size"], c["image_size"],
                           c["image_channels"]), jnp.float32)

        def make(key):
            k_init, k_scale = jax.random.split(key)
            v = self.model.init(k_init, dummy, train=True)
            leaves, treedef = jax.tree_util.tree_flatten_with_path(v["params"])
            keys = jax.random.split(k_scale, len(leaves))
            out = []
            for (path, leaf), k in zip(leaves, keys):
                # a zero-initialised scale is a block's last norm
                if jax.tree_util.keystr(path).endswith("['scale']"):
                    drawn = jax.random.uniform(k, leaf.shape, leaf.dtype,
                                               lo, hi)
                    leaf = jnp.where(jnp.all(leaf == 0), drawn, leaf)
                out.append(leaf)
            return (v["params"], v["batch_stats"],
                    jax.tree_util.tree_unflatten(treedef, out))

        return jax.jit(make)(jax.random.key(seed))

    def inner_optimizer(self):
        o = self.config["training"]["optimizer"]
        if o["name"] != "sgd":
            raise ValueError(f"optimizer {o['name']!r} is not built here")
        return optax.sgd(o["learning_rate"], momentum=o["momentum"])

    def pool_args(self, rows: int) -> dict:
        c = self.config
        return dict(rows=rows, image_size=c["image_size"],
                    channels=c["image_channels"],
                    num_classes=c["num_classes"])

    def rows_of(self, batch) -> int:
        return int(np.shape(batch[1])[0])

    def take_rows(self, batch, start: int, stop: int):
        return batch[0][start:stop], batch[1][start:stop]


def build(config: dict, job: dict) -> Family:
    return Family(config, job)
