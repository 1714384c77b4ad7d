"""Hybrid decoder-only language models with a stack described layer by
layer (LFM2-MoE: gated short convolutions and grouped-query attention,
leading dense layers, then experts behind a sigmoid router with a
selection bias, of which the chip may hold a share), built from the
program's ``TransformerLM`` through its model description
(``lm_from_config``): the model, its loss and its optimizer through the
program's public entry points, and the model FLOPs of a sample from the
configuration's sizes (an ``lfm2_moe`` style ``config.json``). What a
language-model family does alike (optimizer, pool, rows) is ``moe_lm``'s."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

import costs  # benchmark/ is on the path of whoever loads a family
import hybrid_costs
import moe_costs
from families import moe_lm

SAMPLE_KIND = moe_lm.SAMPLE_KIND
SAMPLE_UNIT = moe_lm.SAMPLE_UNIT
seq_len = moe_lm.seq_len
head_dim = moe_lm.head_dim


def layer_counts(config: dict) -> dict:
    kinds = config["layer_types"]
    dense = config["num_dense_layers"]
    return {"conv": kinds.count("conv"),
            "attention": kinds.count("full_attention"),
            "dense": dense, "experts": len(kinds) - dense}


def held_share(config: dict) -> float:
    """The share of the router's experts this chip holds."""
    return config["num_experts"] / config.get("experts_published",
                                              config["num_experts"])


def n_active_params(config: dict) -> float:
    n = layer_counts(config)
    return hybrid_costs.hybrid_lm_active_params(
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], head_dim(config), n["conv"],
        n["attention"], n["dense"], config["intermediate_size"],
        n["experts"],
        config.get("experts_published", config["num_experts"]),
        config["num_experts_per_tok"] * held_share(config),
        config["moe_intermediate_size"], config["vocab_size"])


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Model FLOPs of one token, forward and backward, at the cell's
    sequence length, of what *this chip* multiplies it by: 6 x the
    parameters (of the experts: ``k x held / published`` of them, one a
    layer in expectation here) + causal attention in the attention layers;
    recomputation not counted. ``mfu_pct`` is then a share of this chip's
    peak."""
    return costs.dense_lm_train_flops_per_token(
        n_active_params(config), layer_counts(config)["attention"],
        seq_len(config, job), config["hidden_size"])


def kernel_costs(config: dict, job: dict) -> dict:
    """``{kernel: (flops, bytes)}`` a chip's step needs at least:
    grouped-query causal attention in the attention layers, the gated
    short convolution between its projections in the conv layers, and the
    held experts' grouped matmuls over the rows expected to reach them
    (``tokens x k x held / published`` a layer) in the expert layers."""
    n, T = layer_counts(config), seq_len(config, job)
    tokens = job["per_chip_batch"] * T
    flash = hybrid_costs.gqa_attention_train_cost(
        job["per_chip_batch"], config["num_attention_heads"],
        config["num_key_value_heads"], T, head_dim(config))
    conv = hybrid_costs.short_conv_train_cost(
        tokens, config["hidden_size"], config["conv_L_cache"])
    live = int(tokens * config["num_experts_per_tok"] * held_share(config))
    gmm = moe_costs.gated_experts_train_cost(
        live, config["num_experts"], config["hidden_size"],
        config["moe_intermediate_size"])

    def times(k, cost):
        return k * cost[0], k * cost[1]

    return {"flash": times(n["attention"], flash),
            "short_conv": times(n["conv"], conv),
            "moe_gmm": times(n["experts"], gmm)}


def with_router_scale(params, scale: float):
    """``params`` with every router's kernel multiplied by ``scale``; the
    other leaves are the same arrays."""
    return {name: {**block, "moe_router": block["moe_router"] * scale}
            if "moe_router" in block else block
            for name, block in params.items()}


class Family(moe_lm.Family):
    def __init__(self, config: dict, job: dict):
        from chainermn_tpu.models import lm_from_config, lm_loss_moe
        from chainermn_tpu.ops.flash_attention import flash_attention

        self.config, self.job = config, job
        self.T = seq_len(config, job)
        self.samples_per_row = self.T
        #: one row a chip for the gradient comparison, and a row at a time
        #: for the reference's forward pass (nothing couples the rows)
        self.check_rows = 1
        self.reference_block = 1
        train = config["training"]
        if train["attention"] != "pallas_flash" or \
                train["head"] != "fused_chunked" or \
                train["experts"] != "dropless_grouped_matmul":
            raise ValueError("this family runs the flash kernel, the fused "
                             "head and dropless grouped-matmul experts; the "
                             "configuration asks otherwise")
        remat = job.get("remat", "none")

        def attn(q, k, v, *, causal, scale):
            # interpret=None: compiled on a TPU, interpreted on a CPU.
            return flash_attention(q, k, v, causal=causal, scale=scale)

        self.model = lm_from_config(
            config,
            compute_dtype=jnp.dtype(train["compute_dtype"]).type,
            remat=remat != "none",
            remat_policy=remat if remat != "none" else "dots",
            return_hidden=True, attention_fn=attn,
        )
        model, chunks = self.model, int(job["head_chunks"])

        def loss_fn(params, tokens, router_state=()):
            loss, metrics = lm_loss_moe(
                model, params, tokens, n_chunks=chunks,
                load_balance_coef=0.0, z_loss_coef=0.0,
                router_state=router_state or None)
            # moe_lm's guarantee in the form a share leaves it: a row
            # routed to a *held* expert that lies in no group gives the
            # step no finite loss; the rows of absent experts are the
            # share's and not counted.
            loss = jnp.where(metrics["moe/dropped"] == 0, loss, jnp.nan)
            return loss, (metrics, router_state)

        self.loss_fn = loss_fn

    def init(self, seed: int, check_router_scale: float | None = None):
        """``(params, router_state, check_params)`` on the device.
        ``params`` is the program's own initialisation and is what the
        cell trains. The router's selection bias is ``model_state``: it is
        drawn from the seed at ``assumed.expert_bias_std`` (not zero, so
        that a program that weighs by score + bias fails the comparison),
        takes no gradient and is never updated. ``check_params`` is the
        tree the comparison with the reference runs on: the same arrays,
        but that every router's kernel is multiplied by
        ``assumed.check_router_scale`` (see the configuration's file for
        why; ``tools/hybrid_controls.py`` hands in other scales to read
        what they do)."""
        from chainermn_tpu.models import ROUTER_STATE

        dummy = jnp.zeros((1, self.T), jnp.int32)
        std = self.config["assumed"]["expert_bias_std"]
        if check_router_scale is None:
            check_router_scale = self.config["assumed"]["check_router_scale"]

        def make(key):
            k_init, k_bias = jax.random.split(key)
            v = self.model.init(k_init, dummy)
            leaves, treedef = jax.tree.flatten(v.get(ROUTER_STATE, {}))
            keys = jax.random.split(k_bias, max(len(leaves), 1))
            bias = [std * jax.random.normal(k, b.shape, b.dtype)
                    for k, b in zip(keys, leaves)]
            return v["params"], jax.tree.unflatten(treedef, bias)

        params, router_state = jax.jit(make)(jax.random.key(seed))
        return params, router_state, with_router_scale(
            params, check_router_scale)

    def inner_optimizer(self):
        """AdamW under the linear warm-up a pre-training run starts with:
        the window is the run's first steps. It is no detail here, and it
        departs from the issue's constant rate. At a constant rate from
        step 0 Adam moves every weight by that rate a step, and a chip
        that trains its share alone gives the router the held experts'
        part of its gradient only (the deployment sums it over the chips
        that share the layer): within a dozen steps the rows that reach
        the held experts had drifted on one seed and not on another, and
        the step's time followed the seed (417.2 against 421.6 ms). With
        the routers' kernels left as drawn instead (their update set to
        zero, the rate constant) the states the router reads still
        train, and four seeds read 398.2 to 401.1 ms, quartiles 0.47%
        apart; under warm-up six seeds lie 0.18% apart, because the
        routing stays what the initialisation and the bias make it, about
        a quarter of the rows held, as the deployment's balanced router
        keeps it (PERF.md section 6, PR 40, has all three forms)."""
        o = self.config["training"]["optimizer"]
        if o["name"] != "adamw":
            raise ValueError(f"optimizer {o['name']!r} is not built here")
        rate = optax.linear_schedule(0.0, o["learning_rate"],
                                     o["warmup_steps"])
        return optax.adamw(rate, b1=o["b1"], b2=o["b2"],
                           weight_decay=o["weight_decay"])


def build(config: dict, job: dict) -> Family:
    return Family(config, job)
