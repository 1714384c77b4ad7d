"""Decoder-only language models built from the program's
``TransformerLM``: the model, its loss and its optimizer through the
program's public entry points, and the model FLOPs of a sample from the
configuration's sizes (a GPT-2 style ``config.json``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax

import costs  # benchmark/ is on the path of whoever loads a family

SAMPLE_KIND = "tokens"
SAMPLE_UNIT = "tokens"


def n_params(config: dict) -> int:
    """Parameters of the model as the program builds it (no qkv or
    projection bias; embedding padded to ``assumed.embedding_rows``)."""
    d, ff, L = config["n_embd"], config["n_inner"], config["n_layer"]
    rows = config["assumed"]["embedding_rows"]
    block = (2 * d) + 3 * d * d + d * d + (2 * d) + (d * ff + ff) + (ff * d + d)
    return rows * d + config["n_positions"] * d + L * block + 2 * d


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Model FLOPs of one token, forward and backward, at the cell's
    sequence length; recomputation not counted."""
    return costs.dense_lm_train_flops_per_token(
        n_params(config), config["n_layer"], seq_len(config, job),
        config["n_embd"],
    )


def seq_len(config: dict, job: dict) -> int:
    return int(job.get("seq_len", config["n_positions"]))


def kernel_costs(config: dict, job: dict) -> dict:
    """``{kernel: (flops, bytes)}`` a chip's step needs at least: causal
    attention forward and backward over all layers, bf16 tensors."""
    heads = config["n_head"]
    flops, nbytes = costs.causal_attention_train_cost(
        job["per_chip_batch"], heads, seq_len(config, job),
        config["n_embd"] // heads, bytes_per_el=2,
    )
    return {"flash": (config["n_layer"] * flops, config["n_layer"] * nbytes)}


class Family:
    def __init__(self, config: dict, job: dict):
        from chainermn_tpu.models import TransformerLM, lm_loss_fused
        from chainermn_tpu.ops.flash_attention import flash_attention

        self.config, self.job = config, job
        self.T = seq_len(config, job)
        self.samples_per_row = self.T
        #: rows a chip contributes to the gradient comparison, and rows a
        #: reference forward pass takes at a time (a float32 reference of
        #: one 1024-token sequence keeps about 5 GB of activations).
        self.check_rows = 1
        self.reference_block = 2
        train = config["training"]
        if train["attention"] != "pallas_flash" or \
                train["head"] != "fused_chunked":
            raise ValueError("this family runs the flash kernel and the "
                             "fused head; the configuration asks otherwise")
        remat = job.get("remat", "none")

        def attn(q, k, v, *, causal, scale):
            # interpret=None: compiled on a TPU, interpreted on a CPU.
            return flash_attention(q, k, v, causal=causal, scale=scale)

        self.model = TransformerLM(
            vocab_size=config["assumed"]["embedding_rows"],
            num_layers=config["n_layer"], num_heads=config["n_head"],
            d_model=config["n_embd"], d_ff=config["n_inner"],
            max_len=config["n_positions"],
            compute_dtype=jnp.dtype(train["compute_dtype"]).type,
            remat=remat != "none",
            remat_policy=remat if remat != "none" else "dots",
            return_hidden=True, attention_fn=attn,
        )
        chunks = int(job["head_chunks"])
        model = self.model

        def loss_fn(params, tokens):
            hidden = model.apply({"params": params}, tokens)
            return lm_loss_fused(hidden, params["tok_emb"]["embedding"],
                                 tokens, n_chunks=chunks)

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
        return optax.adamw(o["learning_rate"], weight_decay=o["weight_decay"])

    def pool_args(self, rows: int) -> dict:
        return dict(rows=rows, seq_len=self.T,
                    vocab_size=self.config["vocab_size"],
                    eos_id=self.config["eos_token_id"])

    def rows_of(self, batch) -> int:
        return int(np.shape(batch)[0])

    def take_rows(self, batch, start: int, stop: int):
        return batch[start:stop]


def build(config: dict, job: dict) -> Family:
    return Family(config, job)
