"""Decoder-only mixture-of-experts language models built from the
program's ``TransformerLM`` through its model description
(``lm_from_config``): the model, its loss with the router's auxiliary
terms and its optimizer through the program's public entry points, and
the model FLOPs of a sample from the configuration's sizes (an OLMoE
style ``config.json``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import costs  # benchmark/ is on the path of whoever loads a family
import moe_costs

SAMPLE_KIND = "tokens"
SAMPLE_UNIT = "tokens"


def seq_len(config: dict, job: dict) -> int:
    return int(job.get("seq_len", config["max_position_embeddings"]))


def head_dim(config: dict) -> int:
    return config["hidden_size"] // config["num_attention_heads"]


def n_active_params(config: dict) -> int:
    return moe_costs.moe_lm_active_params(
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], head_dim(config),
        config["num_experts"], config["num_experts_per_tok"],
        config["intermediate_size"], config["vocab_size"],
        config["num_hidden_layers"])


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Model FLOPs of one token, forward and backward, at the cell's
    sequence length: 6 x the parameters a token is multiplied by (its 8
    experts, not the 64) + causal attention; recomputation not counted."""
    return costs.dense_lm_train_flops_per_token(
        n_active_params(config), config["num_hidden_layers"],
        seq_len(config, job), config["hidden_size"])


def kernel_costs(config: dict, job: dict) -> dict:
    """``{kernel: (flops, bytes)}`` a chip's step needs at least: causal
    attention as the LM family counts it, and the experts' grouped
    matmuls over ``tokens * experts_per_token`` rows, all layers."""
    L, T = config["num_hidden_layers"], seq_len(config, job)
    flash = costs.causal_attention_train_cost(
        job["per_chip_batch"], config["num_attention_heads"], T,
        head_dim(config), bytes_per_el=2)
    rows = job["per_chip_batch"] * T * config["num_experts_per_tok"]
    gmm = moe_costs.gated_experts_train_cost(
        rows, config["num_experts"], config["hidden_size"],
        config["intermediate_size"])
    return {"flash": (L * flash[0], L * flash[1]),
            "moe_gmm": (L * gmm[0], L * gmm[1])}


class Family:
    def __init__(self, config: dict, job: dict):
        from chainermn_tpu.models import lm_from_config, lm_loss_moe
        from chainermn_tpu.ops.flash_attention import flash_attention

        self.config, self.job = config, job
        self.T = seq_len(config, job)
        self.samples_per_row = self.T
        #: one 4096-token row a chip for the gradient comparison (in
        #: float32 the 64 experts and the 50304-wide head of one row keep
        #: 5 GB beside 5 GB of parameters and gradients); the reference's
        #: forward pass takes a chip's rows together, because the load-
        #: balancing loss is a product of means over the chip's batch
        self.check_rows = 1
        self.reference_block = None
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
        coefs = config["assumed"]

        def loss_fn(params, tokens):
            loss, metrics = lm_loss_moe(
                model, params, tokens, n_chunks=chunks,
                load_balance_coef=coefs["router_aux_loss_coef"],
                z_loss_coef=coefs["router_z_loss_coef"])
            # The guarantee the cell exists for, held exactly and on the
            # timed path: the reference's norms cannot see a few rows of
            # 131,072 go missing (reference/moe_lm.py), so a step whose
            # experts were given fewer rows than tokens x k has no finite
            # loss: checks (a) and (b) refuse it in set-up, and in the
            # window it counts as failed and check (d) refuses the run.
            loss = jnp.where(metrics["moe/dropped"] == 0, loss, jnp.nan)
            return loss, metrics

        self.loss_fn = loss_fn

    def init(self, seed: int):
        """``(params, model_state, check_params)`` on the device in one
        jitted call; ``check_params`` None: the gradient comparison runs
        on the parameters the cell trains."""
        dummy = jnp.zeros((1, self.T), jnp.int32)
        params = jax.jit(self.model.init)(jax.random.key(seed), dummy)
        return params["params"], (), None

    def inner_optimizer(self):
        o = self.config["training"]["optimizer"]
        if o["name"] != "adamw":
            raise ValueError(f"optimizer {o['name']!r} is not built here")
        return optax.adamw(o["learning_rate"], b1=o["b1"], b2=o["b2"],
                           weight_decay=o["weight_decay"])

    def pool_args(self, rows: int) -> dict:
        return dict(rows=rows, seq_len=self.T,
                    vocab_size=self.config["assumed"]["used_token_ids"],
                    eos_id=self.config["eos_token_id"])

    def rows_of(self, batch) -> int:
        return int(np.shape(batch)[0])

    def take_rows(self, batch, start: int, stop: int):
        return batch[start:stop]


def build(config: dict, job: dict) -> Family:
    return Family(config, job)
