"""Looped decoder-only language models (Ouro's LoopLM) built from the
program's ``TransformerLM`` through its model description
(``lm_from_config``): the model, its loss over the exits and its optimizer
through the program's public entry points, and the model FLOPs of a sample
from the configuration's sizes (an Ouro style ``config.json``). What a
language-model family does alike (optimizer, pool, rows) is ``moe_lm``'s."""

from __future__ import annotations

import jax.numpy as jnp

import costs  # benchmark/ is on the path of whoever loads a family
import loop_costs
from families import moe_lm

SAMPLE_KIND = moe_lm.SAMPLE_KIND
SAMPLE_UNIT = moe_lm.SAMPLE_UNIT
seq_len = moe_lm.seq_len


def layer_params(config: dict) -> int:
    return loop_costs.dense_layer_params(
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"],
        config["intermediate_size"])


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Model FLOPs of one token, forward and backward, at the cell's
    sequence length: the stack and the head once a pass, causal attention
    a layer and pass; recomputation not counted."""
    return loop_costs.looped_lm_train_flops_per_token(
        layer_params(config), config["hidden_size"] * config["vocab_size"],
        config["num_hidden_layers"], config["total_ut_steps"],
        seq_len(config, job), config["hidden_size"])


def kernel_costs(config: dict, job: dict) -> dict:
    """``{kernel: (flops, bytes)}`` a chip's step needs at least: causal
    attention forward and backward, once a layer and pass, bf16 tensors."""
    applications = config["num_hidden_layers"] * config["total_ut_steps"]
    flops, nbytes = costs.causal_attention_train_cost(
        job["per_chip_batch"], config["num_attention_heads"],
        seq_len(config, job), config["head_dim"], bytes_per_el=2)
    return {"flash": (applications * flops, applications * nbytes)}


class Family(moe_lm.Family):
    def __init__(self, config: dict, job: dict):
        from chainermn_tpu.models import lm_from_config, lm_loss_looped
        from chainermn_tpu.ops.flash_attention import flash_attention

        self.config, self.job = config, job
        self.T = seq_len(config, job)
        self.samples_per_row = self.T
        #: one 4096-token row a chip for the gradient comparison, and a
        #: row at a time for the reference's forward pass
        self.check_rows = 1
        self.reference_block = 1
        train = config["training"]
        if train["attention"] != "pallas_flash" or \
                train["head"] != "fused_chunked":
            raise ValueError("this family runs the flash kernel and the "
                             "fused head; the configuration asks otherwise")
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
        beta = config["assumed"]["exit_entropy_beta"]

        def loss_fn(params, tokens):
            return lm_loss_looped(model, params, tokens, n_chunks=chunks,
                                  beta=beta)

        self.loss_fn = loss_fn

    def init(self, seed: int, check_gate_scale: float | None = None):
        """``(params, model_state, check_params)`` on the device.
        ``params`` is the program's own initialisation and is what the
        cell trains. ``check_params`` is the tree the comparison with the
        reference runs on: the same arrays, but that the exit gate's
        kernel is multiplied by ``assumed.check_gate_scale`` (see the
        configuration's file for why; ``tools/loop_controls.py`` hands in
        another scale to read what it does)."""
        params, model_state, _ = super().init(seed)
        if check_gate_scale is None:
            check_gate_scale = self.config["assumed"]["check_gate_scale"]
        gate = params["exit_gate"]
        return params, model_state, {**params, "exit_gate": {
            **gate, "kernel": gate["kernel"] * check_gate_scale}}


def build(config: dict, job: dict) -> Family:
    return Family(config, job)
