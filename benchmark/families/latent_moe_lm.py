"""Decoder-only language models with latent attention and a shared expert
beside the routed ones (DeepSeek-V2's layers: keys and values through one
normed latent, a rope key shared by the heads under YaRN, a leading dense
layer, then top-k of routed experts, of which the chip may hold a share,
plus shared experts every token passes), built from the program's
``TransformerLM`` through its model description (``lm_from_config``): the
model, its loss with the per-sequence balance loss and its optimizer
through the program's public entry points, and the model FLOPs of a
sample from the configuration's sizes (a ``deepseek_v2`` style
``config.json``). What a language-model family does alike (pool, rows) is
``moe_lm``'s, what a family under a share does alike ``hybrid_moe_lm``'s."""

from __future__ import annotations

import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np

import mla_costs  # benchmark/ is on the path of whoever loads a family
import moe_costs
from families import hybrid_moe_lm, moe_lm
from traffic import gen_tokens

SAMPLE_KIND = moe_lm.SAMPLE_KIND
SAMPLE_UNIT = moe_lm.SAMPLE_UNIT
seq_len = moe_lm.seq_len
#: rows of the pool a call of ``Family.expert_loads`` counts on (the
#: sorted (token, slot) rows of four sequences of 8192 take 0.8 GB a layer)
_ROWS_A_CALL = 4


def held_share(config: dict) -> float:
    """The share of the router's experts this chip holds."""
    return config["n_routed_experts"] / config.get(
        "experts_published", config["n_routed_experts"])


def _head_widths(config: dict) -> tuple:
    return (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"])


def n_active_params(config: dict) -> float:
    return mla_costs.latent_moe_lm_active_params(
        config["hidden_size"], config["num_attention_heads"],
        *_head_widths(config), config["kv_lora_rank"],
        config["num_hidden_layers"], config["first_k_dense_replace"],
        config["intermediate_size"],
        config.get("experts_published", config["n_routed_experts"]),
        config["num_experts_per_tok"] * held_share(config),
        config["moe_intermediate_size"],
        config["n_shared_experts"] * config["moe_intermediate_size"],
        config["vocab_size"])


def model_flops_per_sample(config: dict, job: dict) -> float:
    """Model FLOPs of one token, forward and backward, at the cell's
    sequence length, of what *this chip* multiplies it by: 6 x the
    parameters (of the routed experts: ``k x held / published`` of them)
    + causal attention at the published key and value widths in every
    layer; recomputation not counted. ``mfu_pct`` is then a share of this
    chip's peak."""
    return mla_costs.latent_moe_lm_train_flops_per_token(
        n_active_params(config), config["num_hidden_layers"],
        config["num_attention_heads"], seq_len(config, job),
        *_head_widths(config))


def kernel_costs(config: dict, job: dict) -> dict:
    """``{kernel: (flops, bytes)}`` a chip's step needs at least: causal
    attention at keys of 192 and values of 128 in every layer, and the
    held experts' grouped matmuls over the rows expected to reach them
    (``tokens x k x held / published`` a layer) in the expert layers."""
    L, T = config["num_hidden_layers"], seq_len(config, job)
    tokens = job["per_chip_batch"] * T
    flash = mla_costs.mla_attention_train_cost(
        job["per_chip_batch"], config["num_attention_heads"], T,
        *_head_widths(config))
    live = int(tokens * config["num_experts_per_tok"] * held_share(config))
    gmm = moe_costs.gated_experts_train_cost(
        live, config["n_routed_experts"], config["hidden_size"],
        config["moe_intermediate_size"])
    n_expert = L - config["first_k_dense_replace"]
    return {"mla_flash": (L * flash[0], L * flash[1]),
            "moe_gmm": (n_expert * gmm[0], n_expert * gmm[1])}


class Family(hybrid_moe_lm.Family):
    """``hybrid_moe_lm``'s model, rows and optimizer (AdamW under the
    linear warm-up a chip that trains its share alone needs: PERF.md
    section 6, PR 40) with this family's loss and initialisation."""

    def __init__(self, config: dict, job: dict):
        from chainermn_tpu.models import lm_loss_moe

        super().__init__(config, job)
        model, chunks = self.model, int(job["head_chunks"])
        alpha = config["assumed"]["aux_loss_alpha"]

        def loss_fn(params, tokens):
            loss, metrics = lm_loss_moe(
                model, params, tokens, n_chunks=chunks,
                load_balance_coef=0.0, z_loss_coef=0.0, seq_aux_coef=alpha)
            # moe_lm's guarantee in the form a share leaves it: a row
            # routed to a *held* expert that lies in no group gives the
            # step no finite loss
            loss = jnp.where(metrics["moe/dropped"] == 0, loss, jnp.nan)
            return loss, metrics

        self.loss_fn = loss_fn

    def init(self, seed: int):
        """``(params, (), check_params)``: ``moe_lm``'s initialisation (the
        router keeps no state) with every router's columns so ordered
        that the chip holds the experts a balanced router would give it
        (:meth:`hold_balanced_experts`), and ``check_params``, the tree
        check (a) runs on: the same arrays, but that every router's kernel
        is multiplied by ``assumed.check_router_scale`` (the
        configuration's file says why; ``tools/latent_controls.py`` reads
        what other scales do)."""
        params, state, _ = moe_lm.Family.init(self, seed)
        params = self.hold_balanced_experts(params, seed)
        return params, state, hybrid_moe_lm.with_router_scale(
            params, self.config["assumed"]["check_router_scale"])

    def expert_loads(self, params, tokens):
        """``(loads [layers, experts], seen [layers, held])`` over the
        expert layers on ``tokens [rows, T]``: the rows every published
        expert is routed by the program's own router from what the
        layer's second norm hands it, and the rows the program itself
        counted for the experts it holds."""
        from flax import linen as nn

        from chainermn_tpu.models.transformer import MOE_AUX
        from chainermn_tpu.parallel import moe

        arch = self.model.arch
        _, seen = self.model.apply(
            {"params": params}, tokens, mutable=["intermediates", MOE_AUX],
            capture_intermediates=lambda m, _: isinstance(m, nn.RMSNorm))
        loads, counted = [], []
        for i in self.model.expert_layers:
            block = f"block_{i}"
            n, = seen["intermediates"][block]["RMSNorm_1"]["__call__"]
            loads.append(moe.dropless_topk(
                n.reshape(-1, n.shape[-1]), params[block]["moe_router"],
                arch.experts_per_token, arch.renormalise_gates).group_sizes)
            counted.append(seen[MOE_AUX][block]["expert_load"][0])
        return jnp.stack(loads), jnp.stack(counted)

    def hold_balanced_experts(self, params, seed: int):
        """``params`` with the columns of each router so ordered that the
        held range is experts a balanced router would give the chip:
        together they are routed ``held / experts`` of the layer's rows,
        and each lies near ``tokens x k / experts`` (:func:`balanced`). It
        says which of the published experts this chip holds, a layer at a
        time from the first, because a layer's choice moves what the next
        one reads. The loads are counted on the first
        ``assumed.balance_batches`` batches of the run's own pool
        (``assumed.balance_sample`` is the traffic's, which the harness
        does not hand a family): the rows the warm-up and the window
        train on, because at initialisation a row's tokens route alike
        and a few rows say little of the next. Nothing else of the tree
        changes, and nothing of the grouped matmul's plan is read (the
        configuration's file has the reason under
        ``assumed.experts_held_rule``). The rows a step the choice gives
        the held experts, by layer, are printed for the run's log."""
        assumed = self.config["assumed"]
        lo, hi = self.config["experts_held_range"]
        rows = np.concatenate(gen_tokens.pool(
            seed, assumed["balance_sample"],
            **self.pool_args(self.job["per_chip_batch"]),
        )[:assumed["balance_batches"]])
        count = jax.jit(self.expert_loads)
        a_step = self.job["per_chip_batch"] / len(rows)
        given = []
        for at, i in enumerate(self.model.expert_layers):
            calls = [count(params, rows[r:r + _ROWS_A_CALL])
                     for r in range(0, len(rows), _ROWS_A_CALL)]
            loads, counted = (sum(np.asarray(x, np.int64) for x in part)
                              for part in zip(*calls))
            # a near-tied sixth expert may fall either way in two programs
            if np.abs(loads[:, lo:hi] - counted).sum() > 0.01 * counted.sum():
                raise RuntimeError(
                    "the loads counted here are not the program's own for "
                    f"the held experts: {loads[:, lo:hi]} against {counted}")
            load = loads[at]
            held = balanced(load, hi - lo)
            rest = np.setdiff1d(np.arange(load.size), held)
            order = np.concatenate([rest[:lo], held, rest[lo:]])
            block = params[f"block_{i}"]
            params = {**params, f"block_{i}": {
                **block, "moe_router": block["moe_router"][:, order]}}
            given.append(float(load[held].sum() * a_step))
        print(json.dumps({
            "held_rows_a_step_by_layer": given,
            "balanced": float(load.mean() * (hi - lo) * a_step)}), flush=True)
        return params


def balanced(load, n: int):
    """The ``n`` experts, in index order, that a balanced router would
    hand a chip of ``load``'s: of the ``3 n`` whose load lies nearest the
    mean, the ``n`` whose sum lies nearest ``n`` means (the work a step
    gives the chip; every set of ``n`` is tried, those of the nearest
    first, and the first of the best is taken)."""
    mean = load.mean()
    near = np.argsort(np.abs(load - mean), kind="stable")[:3 * n]
    sets = np.array(list(itertools.combinations(range(near.size), n)))
    off = np.abs(load[near][sets].sum(1) - n * mean)
    return np.sort(near[sets[off.argmin()]])


def build(config: dict, job: dict) -> Family:
    return Family(config, job)
