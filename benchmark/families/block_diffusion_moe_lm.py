"""Mixture-of-experts language models trained by block diffusion (SDAR's:
Qwen3-MoE's layers, of whose experts the chip may hold a share, one
forward over a clean and a noised copy of every sequence under a mask by
blocks, the loss on the masked rows), built from the program's
``TransformerLM`` through its model description (``lm_from_config``): the
model, its loss and its optimizer through the program's public entry
points, and the model FLOPs of a sample from the configuration's sizes
(an ``sdar_moe`` style ``config.json``). What a language-model family
does alike (pool, rows) is ``moe_lm``'s; the optimizer's warm-up is the
hybrid family's, for its reason."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import bd_costs  # benchmark/ is on the path of whoever loads a family
import moe_costs
from families import hybrid_moe_lm, moe_lm

SAMPLE_KIND = moe_lm.SAMPLE_KIND
SAMPLE_UNIT = moe_lm.SAMPLE_UNIT
seq_len = moe_lm.seq_len
held_share = hybrid_moe_lm.held_share


def _attention_sizes(config: dict, job: dict) -> tuple:
    return (config["num_attention_heads"], config["num_key_value_heads"],
            seq_len(config, job), config["head_dim"],
            config["block_length"])


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Model FLOPs of one data token, forward and backward, at the cell's
    sequence length, of what *this chip* multiplies it by
    (``bd_costs.bd_moe_lm_train_flops_per_token``: both copies through
    the stack, the head over the noised one, attention over the mask's
    support, ``k x held / published`` experts a row). ``tokens_per_s``
    counts the data's tokens, not the rows."""
    heads, kv_heads, T, head_dim, bl = _attention_sizes(config, job)
    return bd_costs.bd_moe_lm_train_flops_per_token(
        config["hidden_size"], heads, kv_heads, head_dim,
        config["num_hidden_layers"],
        config.get("experts_published", config["num_experts"]),
        config["num_experts_per_tok"] * held_share(config),
        config["moe_intermediate_size"], config["vocab_size"], T, bl)


def kernel_costs(config: dict, job: dict) -> dict:
    """``{kernel: (flops, bytes)}`` a chip's step needs at least: the
    masked attention over ``[x ; x~]`` in every layer, and the held
    experts' grouped matmuls over the rows expected to reach them (``rows
    x k x held / published``: both copies' rows in every layer but the
    last, the noised copy's there)."""
    n, B = config["num_hidden_layers"], job["per_chip_batch"]
    heads, kv_heads, T, head_dim, bl = _attention_sizes(config, job)
    flash = bd_costs.bd_attention_train_cost(B, heads, kv_heads, T,
                                             head_dim, bl)
    live = int(B * T * config["num_experts_per_tok"] * held_share(config))
    both, noised = (moe_costs.gated_experts_train_cost(
        rows, config["num_experts"], config["hidden_size"],
        config["moe_intermediate_size"]) for rows in (2 * live, live))
    return {"bd_flash": (n * flash[0], n * flash[1]),
            "moe_gmm": ((n - 1) * both[0] + noised[0],
                        (n - 1) * both[1] + noised[1])}


class Family(moe_lm.Family):
    def __init__(self, config: dict, job: dict):
        try:
            from chainermn_tpu.models import (
                diffusion_noise_key,
                lm_from_config,
                lm_loss_block_diffusion,
            )
        except ImportError as e:
            # a program from before the PR that brought the training path
            raise RuntimeError(
                "this program has no block-diffusion training path "
                f"(chainermn_tpu.models.lm_loss_block_diffusion): {e}"
            ) from e

        self.config, self.job = config, job
        self.T = seq_len(config, job)
        self.samples_per_row = self.T
        #: one row a chip for the gradient comparison, and a row at a time
        #: for the reference's forward pass (the load-balancing loss is a
        #: product of means over a row's 2L rows here and there alike)
        self.check_rows = 1
        self.reference_block = 1
        train = config["training"]
        if train["attention"] != "pallas_flash_block_mask" or \
                train["head"] != "fused_chunked" or \
                train["experts"] != "dropless_grouped_matmul":
            raise ValueError("this family runs the flash kernels under the "
                             "mask by blocks, the fused head and dropless "
                             "grouped-matmul experts; the configuration "
                             "asks otherwise")
        remat = job.get("remat", "none")
        # no attention_fn: a block-diffusion model's attention is its own
        # (ops/block_diffusion.py: compiled on a TPU, interpreted on a CPU)
        self.model = lm_from_config(
            config,
            compute_dtype=jnp.dtype(train["compute_dtype"]).type,
            remat=remat != "none",
            remat_policy=remat if remat != "none" else "dots",
            return_hidden=True,
        )
        model, chunks = self.model, int(job["head_chunks"])
        assumed = config["assumed"]

        def loss_fn(params, batch, noise_state):
            """``batch``: the step's tokens, whose noise is drawn here
            from ``noise_state``'s key; or check (a)'s and (b)'s
            ``{"tokens", "masked", "t"}``, the noise as data
            (:meth:`take_rows`)."""
            if isinstance(batch, dict):
                tokens, key = batch["tokens"], None
                noise = (batch["masked"], batch["t"])
            else:
                tokens, noise = batch, None
                key, noise_state = diffusion_noise_key(noise_state)
            loss, metrics = lm_loss_block_diffusion(
                model, params, tokens, key, noise=noise,
                mask_id=config["mask_token_id"], t_min=assumed["t_min"],
                n_chunks=chunks,
                load_balance_coef=assumed["router_aux_loss_coef"])
            # moe_lm's guarantee in the form a share leaves it
            # (hybrid_moe_lm): a row routed to a *held* expert that lies in
            # no group gives the step no finite loss
            loss = jnp.where(metrics["moe/dropped"] == 0, loss, jnp.nan)
            return loss, (metrics, noise_state)

        self.loss_fn = loss_fn

    def init(self, seed: int):
        """``(params, noise_state, None)`` on the device: the program's
        own initialisation, the state its steps draw their noise from
        (the seed and a count of draws), and no tree of its own for the
        comparison."""
        from chainermn_tpu.models import diffusion_noise_state

        dummy = jnp.zeros((1, 2 * self.T), jnp.int32)
        params = jax.jit(self.model.init)(jax.random.key(seed), dummy)
        self._noise_state = diffusion_noise_state(seed)
        return params["params"], self._noise_state, None

    inner_optimizer = hybrid_moe_lm.Family.inner_optimizer

    def take_rows(self, batch, start: int, stop: int):
        """Rows of a batch for the comparison with the reference, with
        the noise the step's first draw gives a batch of this shape: the
        reference is handed the realised ``masked`` and ``t`` as data
        (made by the program's ``block_diffusion_noise`` from the key
        :meth:`init`'s state holds, which is what the compiled step's
        first step draws), never the noising code."""
        from chainermn_tpu.models import (
            block_diffusion_noise,
            diffusion_noise_key,
        )

        key, _ = diffusion_noise_key(self._noise_state)
        masked, t = block_diffusion_noise(
            key, np.shape(batch), block_length=self.config["block_length"],
            t_min=self.config["assumed"]["t_min"])
        return {"tokens": jnp.asarray(batch[start:stop]),
                "masked": masked[start:stop], "t": t[start:stop]}


def build(config: dict, job: dict) -> Family:
    return Family(config, job)
