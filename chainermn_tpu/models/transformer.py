"""Transformer-base causal LM — the ``BASELINE.json`` benchmark config that
exercises large embedding gradients and the double-buffered allreduce
(``Transformer-base LM (new — large embedding grads, double-buffered
allreduce)``). Not present in the reference (2017-era); shape follows the
original Transformer-base (6 layers, d_model 512, 8 heads, d_ff 2048).

TPU-first choices: bf16 compute / f32 params; pre-LN (stable without warmup
gymnastics); pluggable attention so the same module runs single-device
(flash/blockwise kernels, :mod:`chainermn_tpu.ops`) or sequence-parallel
(ring/Ulysses locals from :mod:`chainermn_tpu.parallel` when applied inside
``shard_map`` — pass ``attention_fn=lambda q,k,v,causal,scale:
ring_attention_local(q, k, v, 'seq', causal=causal, scale=scale)``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from chainermn_tpu.observability import train_path
from chainermn_tpu.ops.attention import blockwise_attention


#: the token mixers and feed-forwards a layer of the stack may have
#: (:attr:`Architecture.layers`)
MIXERS = ("attention", "short_conv", "latent_attention")
FFNS = ("dense", "experts")


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's numbers (arXiv:2309.00071, as DeepSeek-V2's ``rope_scaling``
    spells them): rotary frequencies blended between the base's own and
    those a ``factor`` times slower, by how many turns a dimension makes
    over the ``original_max_position`` positions the model was first
    trained on; and the two ``mscale``s of the attention's temperature."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def correction_range(self, dim: int, base: float) -> tuple:
        """``(low, high)``: the dimension pairs ``i <= low`` keep the
        base's frequency, ``i >= high`` take the slowed one, those
        between a linear blend. ``corr(n)`` is the pair that makes ``n``
        turns over the original positions."""
        def corr(turns):
            return dim * math.log(self.original_max_position
                                  / (turns * 2 * math.pi)) \
                / (2 * math.log(base))

        return (max(math.floor(corr(self.beta_fast)), 0),
                min(math.ceil(corr(self.beta_slow)), dim - 1))

    def frequencies(self, dim: int, base: float):
        """The ``dim // 2`` float32 frequencies of a rotary part of
        ``dim`` values at ``base``."""
        i = jnp.arange(dim // 2, dtype=jnp.float32)
        extra = base ** (-2.0 * i / dim)
        low, high = self.correction_range(dim, base)
        ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
        return extra / self.factor * ramp + extra * (1.0 - ramp)

    def _m(self, t: float) -> float:
        if self.factor <= 1.0:
            return 1.0
        return 0.1 * t * math.log(self.factor) + 1.0

    @property
    def rotation_scale(self) -> float:
        """What cos and sin are multiplied by:
        ``m(mscale) / m(mscale_all_dim)``."""
        return self._m(self.mscale) / self._m(self.mscale_all_dim)

    @property
    def softmax_scale(self) -> float:
        """The factor on a head's ``width ** -0.5``:
        ``m(mscale_all_dim) ** 2`` (1 where ``mscale_all_dim`` is 0)."""
        return self._m(self.mscale_all_dim) ** 2 \
            if self.mscale_all_dim else 1.0


@dataclasses.dataclass(frozen=True)
class Architecture:
    """What kind of block a model is built from: everything about a
    decoder layer that is a choice between published designs and not a
    size. :class:`TransformerBlock` and :class:`TransformerLM` read it; the
    sizes (layers, width, heads, vocabulary, positions) stay their own
    fields. The defaults are the block this module always built (GPT-2's,
    with the departures ``benchmark/configs/gpt2-medium.json`` lists), so a
    model without a description is that instance and its parameter tree
    and compiled step are what they were. :meth:`from_config` reads a
    Hugging Face ``config.json``-style dict."""

    #: ``'layernorm'`` (scale and bias) or ``'rmsnorm'`` (scale only);
    #: statistics in float32 either way
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    #: ``'gelu'``: up, tanh GELU, down, both with a bias; ``'gated_silu'``:
    #: ``down(silu(gate(x)) * up(x))`` without biases
    ffn: str = "gelu"
    #: a norm of the block's kind on queries and keys before RoPE:
    #: ``'projection'`` over the whole projection, before the split into
    #: heads (OLMoE's; ``True`` reads as it), ``'head'`` over each head's
    #: values with one scale shared by the heads (LFM2's), ``False`` none
    qk_norm: Any = False
    #: ``'learned'`` absolute table or ``'rope'`` at ``rope_base``
    positions: str = "learned"
    rope_base: float = 10000.0
    #: the head shares the embedding table, or has a ``lm_head`` of its own
    tied_head: bool = True
    #: dropless top-``experts_per_token`` of ``n_experts`` routing in every
    #: block's feed-forward (0: dense), experts of ``ffn``'s kind and
    #: ``expert_width``, gates the chosen experts' softmax probabilities,
    #: renormalised over the chosen or not
    n_experts: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    renormalise_gates: bool = False
    #: a norm of the block's kind on each sub-layer's output as well, before
    #: the residual takes it: ``a = x + N(Attn(N(x)))``,
    #: ``y = a + N(FFN(N(a)))`` (params ``attn_out_norm``, ``ffn_out_norm``)
    post_norm: bool = False
    #: a ``[d, 1]`` linear gate on the normed hidden state of each pass of
    #: a looped model (param ``exit_gate``; :func:`lm_loss_looped`)
    exit_gate: bool = False
    #: the router's score of an expert: the ``'softmax'`` over the experts
    #: or each logit's ``'sigmoid'``
    router_score: str = "softmax"
    #: a per-expert bias added to the scores for the choice alone (the
    #: gates are the scores without it). It is no parameter: it lives in
    #: the :data:`ROUTER_STATE` collection (``moe_router_bias``, ``[E]``),
    #: takes no gradient, and this program never updates it
    router_bias: bool = False
    #: added to the sum the chosen gates are renormalised by
    gate_eps: float = 0.0
    #: the gates' last factor (``routed_scaling_factor``)
    routed_scaling: float = 1.0
    #: ``(lo, hi)``: the experts whose weights this program holds, a chip's
    #: share of ``n_experts`` under expert parallelism (``None``: all). The
    #: router keeps ``n_experts`` outputs and chooses among all of them,
    #: the expert leaves are ``[hi - lo, ...]``, and a layer's output is
    #: the held experts' part of the sum: what the absent ones would add
    #: is left out, and nothing stands in for the exchange. Training only
    experts_held: Optional[tuple] = None
    #: the stack layer by layer: one ``(mixer, ffn)`` pair a layer, the
    #: mixer ``'attention'`` or ``'short_conv'`` (LFM2's gated short
    #: convolution, ``conv_width`` taps), the feed-forward ``'dense'`` (at
    #: the model's ``d_ff``) or ``'experts'``. ``None``: every layer has
    #: attention and, where there are experts, experts (:meth:`layer`)
    layers: Optional[tuple] = None
    conv_width: int = 0
    #: positions a block of a block-diffusion model holds (SDAR's; 0: the
    #: model is trained left to right). Its training pass is one forward
    #: over a clean and a noised copy of every sequence, ``[x ; x~]``,
    #: ``2L`` rows at positions ``[0..L-1 ; 0..L-1]``, under
    #: :func:`~chainermn_tpu.ops.block_diffusion.block_diffusion_attention`'s
    #: mask by blocks, and its loss :func:`lm_loss_block_diffusion`.
    #: Training only
    diffusion_block: int = 0
    #: a ``'latent_attention'`` mixer's sizes (DeepSeek-V2's MLA, queries
    #: projected directly): keys and values come through one normed
    #: latent of ``latent_rank`` values a token; a head's query and key
    #: are ``qk_nope_dim`` values without position and ``qk_rope_dim``
    #: rotated ones, the rotated key one row shared by all heads; its
    #: values ``v_head_dim`` wide. Training only
    latent_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    #: scaled RoPE (:class:`Yarn`; ``None``: the base's own frequencies),
    #: read by the latent attention's rotary part
    rope_scaling: Optional[Yarn] = None
    #: width of the shared expert beside the routed ones (0: none): one
    #: gated feed-forward every token passes, added unweighted; under a
    #: share of the routed experts it is computed whole
    shared_expert_width: int = 0
    #: the router also sows the per-sequence form of the balance loss
    #: (DeepSeek-V2's ``seq_aux``; :func:`lm_loss_moe`'s ``seq_aux_coef``)
    seq_aux: bool = False

    def __post_init__(self):
        if self.qk_norm is True:
            object.__setattr__(self, "qk_norm", "projection")
        if self.qk_norm not in (False, "projection", "head"):
            raise ValueError(f"qk_norm must be False, 'projection' or "
                             f"'head', got {self.qk_norm!r}")
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm must be 'layernorm' or 'rmsnorm', got "
                             f"{self.norm!r}")
        if self.ffn not in ("gelu", "gated_silu"):
            raise ValueError(f"ffn must be 'gelu' or 'gated_silu', got "
                             f"{self.ffn!r}")
        if self.positions not in ("learned", "rope"):
            raise ValueError(f"positions must be 'learned' or 'rope', got "
                             f"{self.positions!r}")
        if self.n_experts and not (
                0 < self.experts_per_token <= self.n_experts
                and self.expert_width > 0 and self.ffn == "gated_silu"):
            raise ValueError(
                "a mixture of experts needs 0 < experts_per_token <= "
                "n_experts, an expert_width and gated SiLU experts")
        if self.router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"router_score must be 'softmax' or "
                             f"'sigmoid', got {self.router_score!r}")
        if self.diffusion_block < 0 or (self.diffusion_block and (
                self.exit_gate or self.has_short_conv)):
            raise ValueError(
                "diffusion_block is a block's length (0: none); a block-"
                "diffusion model with an exit gate or short_conv layers "
                "is not built")
        if self.experts_held is not None:
            lo, hi = self.experts_held
            if not 0 <= lo < hi <= self.n_experts:
                raise ValueError(
                    f"experts_held={self.experts_held} is no range of "
                    f"{self.n_experts} experts")
            object.__setattr__(self, "experts_held", (int(lo), int(hi)))
        if self.rope_scaling is not None and not (self.layers and all(
                m == "latent_attention" for m, _ in self.layers)):
            raise ValueError(
                "scaled RoPE is built for latent attention alone: "
                "another mixer would read the base's own frequencies")
        if (self.shared_expert_width or self.seq_aux) and not self.n_experts:
            raise ValueError("a shared expert and the per-sequence "
                             "balance loss belong to a router: n_experts")
        if self.layers is not None:
            layers = tuple(tuple(pair) for pair in self.layers)
            object.__setattr__(self, "layers", layers)
            for pair in layers:
                if len(pair) != 2 or pair[0] not in MIXERS \
                        or pair[1] not in FFNS:
                    raise ValueError(
                        f"a layer is a (mixer, ffn) pair of {MIXERS} and "
                        f"{FFNS}, got {pair!r}")
            if self.has_short_conv and self.conv_width < 1:
                raise ValueError("a short_conv layer needs conv_width >= 1")
            if self.has_latent_attention and not (
                    self.latent_rank > 0 and self.qk_nope_dim > 0
                    and self.qk_rope_dim > 0 and self.qk_rope_dim % 2 == 0
                    and 0 < self.v_head_dim
                    <= self.qk_nope_dim + self.qk_rope_dim
                    and self.positions == "rope" and not self.qk_norm):
                raise ValueError(
                    "a latent_attention layer needs latent_rank, "
                    "qk_nope_dim, an even qk_rope_dim, a v_head_dim no "
                    "wider than a key, RoPE positions and no qk_norm")
            if any(f == "experts" for _, f in layers) \
                    and not self.n_experts:
                raise ValueError("a layer of experts needs n_experts")
            if self.exit_gate or self.post_norm:
                raise ValueError(
                    "a stack described layer by layer with post-norms or "
                    "an exit gate (a looped model's) is not built")

    def layer(self, index: int) -> tuple:
        """``(mixer, ffn)`` of layer ``index``."""
        if self.layers is not None:
            return self.layers[index]
        return ("attention", "experts" if self.n_experts else "dense")

    @property
    def has_short_conv(self) -> bool:
        return any(m == "short_conv" for m, _ in self.layers or ())

    @property
    def has_latent_attention(self) -> bool:
        return any(m == "latent_attention" for m, _ in self.layers or ())

    @property
    def n_experts_held(self) -> int:
        lo, hi = self.experts_held or (0, self.n_experts)
        return hi - lo

    def router_kwargs(self) -> dict:
        """What :func:`~chainermn_tpu.parallel.moe.dropless_topk` takes
        beyond ``k`` and ``renormalise``, where it differs from a softmax
        router over experts that are all held. For that router it is
        empty, so that what wraps ``dropless_topk`` under its first
        signature still fits (``benchmark/tests/test_moe.py`` and
        ``tools/moe_controls.py`` do, to lose a row or round the router:
        a benchmark file is not edited with the program)."""
        kw = {}
        if self.router_score != "softmax":
            kw["score"] = self.router_score
        if self.gate_eps:
            kw["gate_eps"] = self.gate_eps
        if self.routed_scaling != 1.0:
            kw["scale"] = self.routed_scaling
        if self.n_experts_held != self.n_experts:
            kw["held"] = self.experts_held
        return kw

    @classmethod
    def from_config(cls, config: dict) -> "Architecture":
        kind = config.get("model_type", "gpt2")
        if kind == "gpt2":
            return cls()
        if kind == "olmoe":
            if config.get("hidden_act", "silu") != "silu" or \
                    config.get("attention_bias") or \
                    config.get("clip_qkv") is not None or \
                    config.get("rope_scaling") is not None:
                raise ValueError(
                    "an olmoe config with another activation, attention "
                    "biases, clipped qkv or scaled RoPE is not built here")
            return cls(
                norm="rmsnorm", norm_eps=float(config["rms_norm_eps"]),
                ffn="gated_silu", qk_norm="projection", positions="rope",
                rope_base=float(config["rope_theta"]),
                tied_head=bool(config["tie_word_embeddings"]),
                n_experts=int(config["num_experts"]),
                experts_per_token=int(config["num_experts_per_tok"]),
                expert_width=int(config["intermediate_size"]),
                renormalise_gates=bool(config["norm_topk_prob"]),
            )
        if kind == "ouro":
            if config.get("hidden_act", "silu") != "silu" or \
                    config.get("use_sliding_window") or \
                    config.get("rope_scaling") is not None or \
                    config.get("head_dim") not in (None, config[
                        "hidden_size"] // config["num_attention_heads"]):
                raise ValueError(
                    "an ouro config with another activation, a sliding "
                    "window, scaled RoPE or heads that do not divide the "
                    "width is not built here")
            return cls(
                norm="rmsnorm", norm_eps=float(config["rms_norm_eps"]),
                ffn="gated_silu", positions="rope",
                rope_base=float(config["rope_theta"]),
                tied_head=bool(config["tie_word_embeddings"]),
                post_norm=True, exit_gate=True,
            )
        if kind == "lfm2_moe":
            return cls._from_lfm2_moe(config)
        if kind == "sdar_moe":
            return cls._from_sdar_moe(config)
        if kind == "deepseek_v2":
            return cls._from_deepseek_v2(config)
        raise ValueError(f"no block is described for model_type {kind!r}")

    @staticmethod
    def _experts_share(config: dict, key: str = "num_experts") -> tuple:
        """``(router width, held range or None)`` of a file that may hold
        a chip's share of the experts: the experts it holds under the
        family's own ``key`` and as ``experts_held_range``, the router's
        width as ``experts_published``."""
        n_experts = int(config.get("experts_published", config[key]))
        held = config.get("experts_held_range")
        if (held is None) != (n_experts == config[key]) or (
                held is not None and held[1] - held[0] != config[key]):
            raise ValueError(
                f"a share of the experts is spelled {key} (held), "
                "experts_published (the router's width) and "
                f"experts_held_range [lo, hi) of {key} entries")
        return n_experts, tuple(held) if held is not None else None

    @classmethod
    def _from_deepseek_v2(cls, config: dict) -> "Architecture":
        """DeepSeek-V2 as its Lite model spells it: latent attention with
        queries projected directly in every layer, RoPE on a part of each
        head (under YaRN where ``rope_scaling`` says so), the first
        ``first_k_dense_replace`` layers dense and every later one top-k
        of ``n_routed_experts`` behind a softmax router beside
        ``n_shared_experts`` shared ones (one feed-forward of their summed
        width), the balance loss per sequence with ``seq_aux``."""
        scaling = config.get("rope_scaling")
        unbuilt = [why for bad, why in (
            (config.get("q_lora_rank") is not None,
             "low-rank queries (q_lora_rank)"),
            (config.get("n_group", 1) > 1
             or config.get("topk_method", "greedy") != "greedy",
             "group-limited routing (n_group > 1 or a topk_method other "
             "than greedy)"),
            (config.get("scoring_func", "softmax") != "softmax",
             "a scoring_func other than softmax"),
            (config.get("moe_layer_freq", 1) != 1,
             "moe_layer_freq other than 1"),
            (scaling is not None and scaling.get("type") != "yarn",
             "a rope_scaling type other than yarn"),
            (config.get("hidden_act", "silu") != "silu"
             or config.get("attention_bias"),
             "another activation or attention biases"),
            (config.get("num_key_value_heads")
             not in (None, config["num_attention_heads"]),
             "fewer key-value heads than heads (the latent is shared by "
             "all of them already)"),
        ) if bad]
        if unbuilt:
            raise ValueError("a deepseek_v2 config with "
                             + "; ".join(unbuilt) + " is not built here")
        n_experts, held = cls._experts_share(config, "n_routed_experts")
        dense = int(config["first_k_dense_replace"])
        yarn = None if scaling is None else Yarn(
            factor=float(scaling["factor"]),
            original_max_position=int(
                scaling["original_max_position_embeddings"]),
            beta_fast=float(scaling.get("beta_fast", 32)),
            beta_slow=float(scaling.get("beta_slow", 1)),
            mscale=float(scaling.get("mscale", 1)),
            mscale_all_dim=float(scaling.get("mscale_all_dim", 0)))
        return cls(
            norm="rmsnorm", norm_eps=float(config["rms_norm_eps"]),
            ffn="gated_silu", positions="rope",
            rope_base=float(config["rope_theta"]), rope_scaling=yarn,
            tied_head=bool(config["tie_word_embeddings"]),
            n_experts=n_experts,
            experts_per_token=int(config["num_experts_per_tok"]),
            expert_width=int(config["moe_intermediate_size"]),
            renormalise_gates=bool(config["norm_topk_prob"]),
            routed_scaling=float(config.get("routed_scaling_factor", 1)),
            experts_held=held,
            shared_expert_width=int(config.get("n_shared_experts") or 0)
            * int(config["moe_intermediate_size"]),
            seq_aux=bool(config.get("seq_aux")),
            layers=tuple(
                ("latent_attention", "dense" if i < dense else "experts")
                for i in range(int(config["num_hidden_layers"]))),
            latent_rank=int(config["kv_lora_rank"]),
            qk_nope_dim=int(config["qk_nope_head_dim"]),
            qk_rope_dim=int(config["qk_rope_head_dim"]),
            v_head_dim=int(config["v_head_dim"]),
        )

    @classmethod
    def _from_sdar_moe(cls, config: dict) -> "Architecture":
        """SDAR-MoE: Qwen3-MoE's layers (RMSNorm, grouped-query attention
        with a norm over each head's values of ``q`` and of ``k`` before
        RoPE, top-``k`` of gated-SiLU experts behind a softmax router in
        every layer, an untied head) trained by block diffusion at
        ``block_length`` (no key of the published ``config.json``: the
        released checkpoints' default is 4)."""
        if config.get("hidden_act", "silu") != "silu" or \
                config.get("attention_bias") or \
                config.get("use_sliding_window") or \
                config.get("rope_scaling") is not None or \
                config.get("mlp_only_layers") or \
                config.get("decoder_sparse_step", 1) != 1:
            raise ValueError(
                "an sdar_moe config with another activation, attention "
                "biases, a sliding window, scaled RoPE or layers without "
                "experts is not built here")
        n_experts, held = cls._experts_share(config)
        return cls(
            norm="rmsnorm", norm_eps=float(config["rms_norm_eps"]),
            ffn="gated_silu", qk_norm="head", positions="rope",
            rope_base=float(config["rope_theta"]),
            tied_head=bool(config["tie_word_embeddings"]),
            n_experts=n_experts,
            experts_per_token=int(config["num_experts_per_tok"]),
            expert_width=int(config["moe_intermediate_size"]),
            renormalise_gates=bool(config["norm_topk_prob"]),
            experts_held=held,
            diffusion_block=int(config.get("block_length", 4)),
        )

    @classmethod
    def _from_lfm2_moe(cls, config: dict) -> "Architecture":
        """LFM2-MoE: ``layer_types`` names each layer's mixer, the first
        ``num_dense_layers`` have a dense feed-forward and the rest
        experts behind a sigmoid router with a selection bias. A file that
        holds a chip's share gives the experts it holds as ``num_experts``
        and ``experts_held_range``, and the router's width as
        ``experts_published``."""
        mixers = {"conv": "short_conv", "full_attention": "attention"}
        kinds = config["layer_types"]
        unknown = sorted(set(kinds) - set(mixers))
        if unknown or config.get("conv_bias") or \
                len(kinds) != config["num_hidden_layers"] or \
                config.get("rope_scaling") is not None:
            raise ValueError(
                "an lfm2_moe config with a convolution bias, scaled RoPE, "
                "layer_types that do not count num_hidden_layers or a "
                f"layer type other than {sorted(mixers)} is not built "
                f"here (layer types not known: {unknown})")
        n_experts, held = cls._experts_share(config)
        dense = int(config["num_dense_layers"])
        renorm = bool(config["norm_topk_prob"])
        return cls(
            norm="rmsnorm", norm_eps=float(config["norm_eps"]),
            ffn="gated_silu", qk_norm="head", positions="rope",
            rope_base=float(config["rope_theta"]),
            tied_head=bool(config.get("tie_embedding", True)),
            n_experts=n_experts,
            experts_per_token=int(config["num_experts_per_tok"]),
            expert_width=int(config["moe_intermediate_size"]),
            renormalise_gates=renorm, router_score="sigmoid",
            router_bias=bool(config["use_expert_bias"]),
            gate_eps=1e-6 if renorm else 0.0,
            routed_scaling=float(config["routed_scaling_factor"]),
            experts_held=held,
            layers=tuple((mixers[kind], "dense" if i < dense else "experts")
                         for i, kind in enumerate(kinds)),
            conv_width=int(config["conv_L_cache"]),
        )


#: collection a dropless MoE block sows its router's auxiliary losses and
#: load into (``apply(..., mutable=[MOE_AUX])``; :func:`lm_loss_moe`)
MOE_AUX = "moe_aux"
#: collection of a router's selection bias (``Architecture.router_bias``):
#: ``init`` makes it zero; ``apply`` reads it beside the parameters
#: (``{"params": ..., ROUTER_STATE: ...}``), a train step carries it as
#: ``model_state``
ROUTER_STATE = "router_state"

#: ``config.json``-style descriptions of the models the examples name
#: (:func:`lm_from_config` builds them); sources in the README
MODEL_CONFIGS = {
    "gpt2-medium": {
        "model_type": "gpt2", "n_layer": 24, "n_embd": 1024, "n_head": 16,
        "n_inner": 4096, "n_positions": 1024, "vocab_size": 50257,
    },
    "olmoe-1b-7b": {
        "model_type": "olmoe", "num_hidden_layers": 16, "hidden_size": 2048,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "intermediate_size": 1024, "num_experts": 64,
        "num_experts_per_tok": 8, "norm_topk_prob": False,
        "hidden_act": "silu", "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "rope_scaling": None, "attention_bias": False, "clip_qkv": None,
        "tie_word_embeddings": False, "vocab_size": 50304,
        "max_position_embeddings": 4096,
    },
    "ouro-2.6b": {
        "model_type": "ouro", "num_hidden_layers": 48, "hidden_size": 2048,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "head_dim": 128, "intermediate_size": 5632, "hidden_act": "silu",
        "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
        "use_sliding_window": False, "sliding_window": None,
        "tie_word_embeddings": False, "vocab_size": 49152,
        "max_position_embeddings": 65536, "total_ut_steps": 4,
        "early_exit_threshold": 1,
    },
    "lfm2-8b-a1b": {
        "model_type": "lfm2_moe", "num_hidden_layers": 24,
        "num_dense_layers": 2,
        "layer_types": [
            "conv", "conv", "full_attention", "conv", "conv", "conv",
            "full_attention", "conv", "conv", "conv", "full_attention",
            "conv", "conv", "conv", "full_attention", "conv", "conv",
            "conv", "full_attention", "conv", "conv", "full_attention",
            "conv", "conv"],
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 8, "intermediate_size": 7168,
        "moe_intermediate_size": 1792, "num_experts": 32,
        "num_experts_per_tok": 4, "norm_topk_prob": True,
        "use_expert_bias": True, "routed_scaling_factor": 1,
        "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
        "rope_theta": 1000000, "vocab_size": 65536,
        "max_position_embeddings": 128000,
    },
    "sdar-30b-a3b": {
        "model_type": "sdar_moe", "num_hidden_layers": 48,
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128,
        "intermediate_size": 6144, "moe_intermediate_size": 768,
        "num_experts": 128, "num_experts_per_tok": 8,
        "norm_topk_prob": True, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "hidden_act": "silu",
        "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
        "attention_bias": False, "use_sliding_window": False,
        "sliding_window": None, "tie_word_embeddings": False,
        "vocab_size": 151936, "max_position_embeddings": 32768,
        # no key of the published config.json: the released checkpoints'
        # default block length
        "block_length": 4,
    },
    "deepseek-v2-lite": {
        "model_type": "deepseek_v2", "num_hidden_layers": 27,
        "hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 16, "q_lora_rank": None,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128,
        "intermediate_size": 10944, "moe_intermediate_size": 1408,
        "first_k_dense_replace": 1, "moe_layer_freq": 1,
        "n_routed_experts": 64, "n_shared_experts": 2,
        "num_experts_per_tok": 6, "norm_topk_prob": False,
        "scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
        "topk_group": 1, "routed_scaling_factor": 1, "seq_aux": True,
        "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": {
            "type": "yarn", "factor": 40,
            "original_max_position_embeddings": 4096, "beta_fast": 32,
            "beta_slow": 1, "mscale": 0.707, "mscale_all_dim": 0.707},
        "attention_bias": False, "tie_word_embeddings": False,
        "vocab_size": 102400, "max_position_embeddings": 163840,
    },
}


def _norm_layer(arch: Architecture, dtype, name=None):
    """A normalisation layer of the description's kind; unnamed ones take
    flax's running names (``LayerNorm_0``, ``RMSNorm_0``, ...)."""
    cls = nn.RMSNorm if arch.norm == "rmsnorm" else nn.LayerNorm
    return cls(epsilon=arch.norm_eps, dtype=dtype, param_dtype=jnp.float32,
               name=name)


def apply_rope(x, positions, base: float = 10000.0,
               scaling: Optional[Yarn] = None):
    """Rotary position embedding on ``[B, T, H, Dh]`` (half-split pairing),
    at ``base``'s own frequencies or, with ``scaling``, at YaRN's blend
    of them with cos and sin times its ``rotation_scale``.

    ``positions``: ``[T]`` GLOBAL positions — sequence-parallel shards pass
    their own offsets, so rotations agree across shards (rotation commutes
    with the ring/Ulysses resharding because it is per-position). A
    ``[B, T]`` array gives each batch row its OWN positions — the serving
    engine's slot array, where every slot sits at a different depth.
    """
    half = x.shape[-1] // 2
    if scaling is None:
        freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        m = 1.0
    else:
        freqs, m = scaling.frequencies(2 * half, base), \
            scaling.rotation_scale
    ang = positions.astype(jnp.float32)[..., None] * freqs  # [..., T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if m != 1.0:
        cos, sin = cos * m, sin * m
    if ang.ndim == 2:  # [T, half]: shared across the batch
        cos = cos[None, :, None, :].astype(x.dtype)
        sin = sin[None, :, None, :].astype(x.dtype)
    else:  # [B, T, half]: per-row slot positions
        cos = cos[:, :, None, :].astype(x.dtype)
        sin = sin[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1)


class TransformerBlock(nn.Module):
    num_heads: int
    d_ff: int
    compute_dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    #: residual dropout on the attention and FFN branch outputs (the
    #: GPT-2 placement; attention-matrix dropout is deliberately NOT
    #: offered — it would break the flash kernels' LSE bookkeeping and
    #: modern LM recipes train without it). Active when ``train=True``;
    #: callers supply the ``'dropout'`` rng.
    dropout_rate: float = 0.0
    #: kv heads for GQA/MQA (None → num_heads, i.e. standard MHA). The kv
    #: projection shrinks accordingly; the attention kernel shares kv heads
    #: across their q-head group (:mod:`chainermn_tpu.ops.flash_attention`).
    num_kv_heads: Optional[int] = None
    #: KV-cache capacity for ``decode=True`` (single-token autoregressive
    #: steps). Training/prefill paths ignore it.
    decode_max_len: int = 2048
    #: causal sliding-window width. The TRAINING path cannot apply it
    #: itself (attention is pluggable): pass an ``attention_fn`` that
    #: honours the same window (``flash_attention(..., window=W)``) — a
    #: window without one is rejected. The DECODE path applies it to the
    #: KV-cache mask directly, keeping inference consistent with the
    #: windowed training distribution.
    window: Optional[int] = None
    #: bidirectional attention when False (encoder blocks — ViT, BERT
    #: style). Decode/window paths are causal-only and reject it.
    causal: bool = True
    #: decode KV-cache layout: ``'dense'`` (``[B, decode_max_len, ...]``
    #: per slot — the classic fixed ring) or ``'paged'`` (shared block
    #: pool + per-slot block tables, :mod:`chainermn_tpu.ops.paged_kv` —
    #: the serving engine's HBM-shared layout). Paged requires the
    #: per-row decode path (``decode_positions`` + ``block_tables``).
    kv_layout: str = "dense"
    #: tokens per pool block (paged layout; tuned via the
    #: ``kv_block_size`` autotune decision).
    kv_block_size: int = 64
    #: pool capacity in blocks (paged layout; block 0 is scratch).
    kv_num_blocks: int = 0
    #: slot-decode attention impl: ``'xla'`` (scatter → dense-view
    #: gather → einsum attend — the reference path) or ``'fused'`` (the
    #: flash-decoding Pallas kernel, :mod:`chainermn_tpu.ops.
    #: paged_decode` — one HBM pass, no dense view; registry decision
    #: ``decode_attend_impl``, resolved by the serving engine). The
    #: CACHE WRITE is shared between the impls — only the attend read
    #: differs, so streams agree to fp32-accumulation tolerance.
    decode_attend_impl: str = "xla"
    #: mesh axis name for tensor-parallel decode: the block then holds
    #: LOCAL heads/kv-heads/d_ff (set ``head_dim`` explicitly) and
    #: inserts exactly one ``psum`` per column→row pair (attention
    #: output projection, FFN down projection) via
    #: :mod:`chainermn_tpu.parallel.tensor`'s adjoint ops. Row-parallel
    #: biases must be pre-divided by the axis size (the engine's param
    #: sharder does this).
    tp_axis: Optional[str] = None
    #: per-head width override; required under ``tp_axis`` where
    #: ``d_model // num_heads`` no longer holds (num_heads is local).
    head_dim: Optional[int] = None
    #: sow each NON-decode forward's post-rope K/V into a mutable
    #: ``'kv_out'`` collection (``{'k': (kh,), 'v': (vh,)}`` per block,
    #: ``compute_dtype`` — exactly what the slot-decode cache stores).
    #: The serving engine's sequence-parallel prefill (ISSUE 13) runs a
    #: train-mode forward over the prompt shards and scatters these into
    #: the paged/dense cache at true positions.
    sow_kv: bool = False
    #: mixture-of-experts FFN (ISSUE 20): with ``n_experts > 0`` the
    #: dense ``ff_up``/``ff_down`` pair is replaced by ``n_experts``
    #: independent MLPs behind a top-1 router (``moe_router`` /
    #: ``moe_w_up`` / ``moe_b_up`` / ``moe_w_down`` / ``moe_b_down``
    #: params; expert leaves stack a leading ``[n_experts, ...]`` dim).
    #: 0 (default) keeps the dense FFN — nothing changes.
    n_experts: int = 0
    #: mesh axis hosting expert shards for the serving/decode path.
    #: ``None`` evaluates every expert locally and combines with the
    #: one-hot gate (the reference form — exact, E x FLOPs, right for
    #: the sequential :func:`generate` and the engine's non-TP arms).
    #: Set (the engine sets it to ``tp_axis``) the FFN switches to the
    #: ownership-split form: each shard routes its owned slice of the
    #: replicated token rows, two ``all_to_all``s ship queues to the
    #: expert owners and back, and ONE ``psum`` re-replicates — the MoE
    #: analogue of dense ``ff_down``'s ``reduce_from_tp``, so TP stays
    #: at exactly 2 all-reduces per layer plus 2 all_to_alls per MoE
    #: layer. ``n_experts`` stays GLOBAL; the local expert count is
    #: read off the (sharder-sliced) param leaf at trace time.
    expert_axis: Optional[str] = None
    #: queue-build impl for the ownership-split path: ``'sort'`` /
    #: ``'einsum'`` / ``'auto'`` (registry decision ``moe_dispatch``,
    #: resolved at trace time — same numbers either way).
    moe_dispatch_impl: str = "auto"
    #: DECLARED leading dim of the expert param leaves (flax validates
    #: param shapes at apply): ``None`` = ``n_experts`` (full leaves —
    #: every single-device use). The serving engine's TP clone sets it
    #: to ``n_experts // tp`` so the per-shard model matches the
    #: sharder's sliced leaves; ``n_experts`` itself stays GLOBAL (the
    #: router scores every expert).
    moe_experts_local: Optional[int] = None
    #: the kind of block (:class:`Architecture`); the default instance is
    #: the GPT-2 block
    arch: Architecture = Architecture()
    #: which layer of the stack this block is: it reads its own entry of
    #: a description that goes layer by layer (``arch.layer``)
    layer_index: int = 0

    @staticmethod
    def _lora_delta(name, adapters, inp, out):
        """Add the low-rank delta ``(inp @ A) @ B`` for projection
        ``name`` (ISSUE 14: multi-tenant adapters). ``adapters`` maps a
        projection name to its ``(A, B)`` pair — either unbatched
        ``[d_in, r]`` / ``[r, d_out]`` (one adapter for every row: the
        sequential ``generate`` reference) or per-row ``[B, d_in, r]`` /
        ``[B, r, d_out]`` (the serving engine's per-slot tenant gather).
        The scale is pre-folded into ``B`` by the
        :class:`~chainermn_tpu.serving.adapters.AdapterBank`, so both
        paths consume the identical values. A zero A/B row contributes
        an exact 0 — the zero-adapter tenant stays bitwise the base
        model."""
        if not adapters or name not in adapters:
            return out
        A, B = adapters[name]
        A = A.astype(inp.dtype)
        B = B.astype(inp.dtype)
        if A.ndim == 2:  # shared adapter (reference path)
            delta = (inp @ A) @ B
        else:  # per-row gathered stacks (serving slot array)
            delta = jnp.einsum(
                "btr,bro->bto", jnp.einsum("btd,bdr->btr", inp, A), B
            )
        return out + delta.astype(out.dtype)

    def _decode_attend(self, qh, kh_new, vh_new, head_dim):
        """One-token attention against the mutable KV cache.

        The cache is a fixed-shape ``[B, max_len, kvh, dh]`` ring written
        at ``cache_index`` — fixed shapes keep the decode step a single
        compiled program (XLA semantics: no dynamic shapes), the TPU
        answer to the reference era's growing Python-side state. Masked
        positions beyond the index cost bandwidth, not correctness;
        decode is memory-bound either way.
        """
        B = qh.shape[0]
        kv_heads = kh_new.shape[2]
        ck = self.variable(
            "cache", "cached_key",
            lambda: jnp.zeros(
                (B, self.decode_max_len, kv_heads, head_dim),
                self.compute_dtype,
            ),
        )
        cv = self.variable(
            "cache", "cached_value",
            lambda: jnp.zeros(
                (B, self.decode_max_len, kv_heads, head_dim),
                self.compute_dtype,
            ),
        )
        idx = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
        )
        i = idx.value
        ck.value = jax.lax.dynamic_update_slice(
            ck.value, kh_new.astype(self.compute_dtype), (0, i, 0, 0)
        )
        cv.value = jax.lax.dynamic_update_slice(
            cv.value, vh_new.astype(self.compute_dtype), (0, i, 0, 0)
        )
        idx.value = i + 1

        group = self.num_heads // kv_heads
        # q: [B, 1, H, dh] → [B, kvh, group, dh]; cache k/v: [B, L, kvh, dh]
        q = qh[:, 0].reshape(B, kv_heads, group, head_dim)
        scores = jnp.einsum(
            "bngd,blnd->bngl", q.astype(jnp.float32),
            ck.value.astype(jnp.float32),
        ) * (head_dim ** -0.5)
        pos = jnp.arange(self.decode_max_len)
        mask = pos <= i  # [L]
        if self.window is not None:
            # Same band the windowed training attention saw: j > i - W.
            mask &= pos > i - self.window
        scores = jnp.where(mask[None, None, None, :], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum(
            "bngl,blnd->bngd", w, cv.value.astype(jnp.float32)
        )
        return o.reshape(B, 1, self.num_heads, head_dim).astype(
            self.compute_dtype
        )

    def _slot_decode_attend(self, qh, kh_new, vh_new, head_dim, positions,
                            block_tables, slots):
        """Slot-array cached attention (the serving engine's path).

        Unlike :meth:`_decode_attend`'s shared scalar write index, every
        batch row carries its OWN position (``positions[b]`` = where row
        ``b``'s first new token is written), so a fixed slot array can
        hold requests at arbitrary depths in one compiled program.
        ``T >= 1`` tokens per row are written at ``positions[b] + t`` and
        each query ``t`` attends with the causal mask ``pos <=
        positions[b] + t`` — ``T == 1`` is the steady-state decode step,
        ``T == bucket`` is prefill (pad-position writes land beyond the
        row's true length and are re-written by later decode steps
        before any mask ever admits them), and ``T == K+1`` is the
        speculative verify span (:mod:`chainermn_tpu.serving.speculate`):
        rejected-draft writes are stale by the same argument — the
        engine rewinds positions on the HOST only, so the next span
        starts at the accept point and re-writes every stale row before
        its position is ever admitted. Writes that overhang the cache
        horizon (a verify span near ``max_len``) are dropped by the
        scatter (dense rows out of bounds) or redirected to the scratch
        block (paged, :func:`~chainermn_tpu.ops.paged_kv.paged_update`);
        the engine caps ACCEPTANCE inside the horizon, so committed
        tokens always have real cache rows.

        Two cache layouts behind one arithmetic: ``'dense'`` stores
        ``[n_slots, decode_max_len, kvh, dh]`` directly (``slots`` maps
        token rows onto cache rows — prefill passes one slot id, the
        decode step passes None for the identity); ``'paged'`` scatters
        into the shared block pool and gathers the row's blocks back
        into the SAME dense view (:mod:`chainermn_tpu.ops.paged_kv`), so
        the einsums/masks — and therefore the tokens — are identical
        between the layouts.
        """
        B, T = qh.shape[:2]
        kv_heads = kh_new.shape[2]
        dt = self.compute_dtype
        if self.decode_attend_impl not in ("xla", "fused"):
            raise ValueError(
                f"decode_attend_impl must be 'xla' or 'fused', got "
                f"{self.decode_attend_impl!r}"
            )
        if self.kv_layout == "paged":
            from chainermn_tpu.ops.paged_kv import paged_lookup, paged_update

            if block_tables is None:
                raise ValueError("kv_layout='paged' needs block_tables")
            if self.kv_num_blocks < 2:
                raise ValueError(
                    "kv_layout='paged' needs kv_num_blocks >= 2 (block 0 "
                    f"is scratch), got {self.kv_num_blocks}"
                )
            nb, bs = self.kv_num_blocks, self.kv_block_size
            pk = self.variable(
                "cache", "pool_key",
                lambda: jnp.zeros((nb, bs, kv_heads, head_dim), dt),
            )
            pv = self.variable(
                "cache", "pool_value",
                lambda: jnp.zeros((nb, bs, kv_heads, head_dim), dt),
            )
            pk.value = paged_update(pk.value, block_tables, positions,
                                    kh_new.astype(dt))
            pv.value = paged_update(pv.value, block_tables, positions,
                                    vh_new.astype(dt))
            if self.decode_attend_impl == "fused":
                from chainermn_tpu.ops.paged_decode import (
                    paged_flash_decode,
                )

                # One HBM pass over the LIVE blocks — the table rides as
                # a scalar-prefetch operand, no dense view ever exists.
                # Scratch block 0 is masked in-kernel (the same released
                # -slot / beyond-horizon staleness argument as below).
                return paged_flash_decode(
                    qh.astype(dt), pk.value, pv.value, block_tables,
                    positions, window=self.window,
                    scale=head_dim ** -0.5, scratch_block=0,
                )
            keys = paged_lookup(pk.value, block_tables)
            vals = paged_lookup(pv.value, block_tables)
        else:
            ck = self.variable(
                "cache", "cached_key",
                lambda: jnp.zeros(
                    (B, self.decode_max_len, kv_heads, head_dim), dt
                ),
            )
            cv = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros(
                    (B, self.decode_max_len, kv_heads, head_dim), dt
                ),
            )
            rows = (jnp.arange(B, dtype=jnp.int32)
                    if slots is None else slots)
            cols = positions[:, None] + jnp.arange(T, dtype=positions.dtype)
            ck.value = ck.value.at[rows[:, None], cols].set(
                kh_new.astype(dt)
            )
            cv.value = cv.value.at[rows[:, None], cols].set(
                vh_new.astype(dt)
            )
            if self.decode_attend_impl == "fused":
                from chainermn_tpu.ops.paged_decode import (
                    dense_flash_decode,
                )

                # The dense ring through the SAME kernel: the cache
                # reshapes (zero-copy) into implicit blocks with an
                # identity table — the prefill view's per-slot gather
                # becomes table rows, never a materialized copy.
                return dense_flash_decode(
                    qh.astype(dt), ck.value, cv.value, positions,
                    slots=slots, window=self.window,
                    scale=head_dim ** -0.5,
                )
            if slots is None:
                keys, vals = ck.value, cv.value
            else:  # prefill view: gather just the written rows
                keys = ck.value[slots]
                vals = cv.value[slots]

        L = keys.shape[1]
        pos_l = jnp.arange(L)
        qpos = positions[:, None] + jnp.arange(T, dtype=positions.dtype)
        mask = pos_l[None, None, :] <= qpos[:, :, None]  # [B, T, L]
        if self.window is not None:
            mask &= pos_l[None, None, :] > qpos[:, :, None] - self.window
        group = self.num_heads // kv_heads
        q = qh.reshape(B, T, kv_heads, group, head_dim)
        scores = jnp.einsum(
            "btngd,blnd->btngl", q.astype(jnp.float32),
            keys.astype(jnp.float32),
        ) * (head_dim ** -0.5)
        scores = jnp.where(mask[:, :, None, None, :], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("btngl,blnd->btngd", w, vals.astype(jnp.float32))
        return o.reshape(B, T, self.num_heads, head_dim).astype(dt)

    def _moe_ffn(self, h):
        """Top-1 mixture-of-experts FFN branch (ISSUE 20).

        Routing is per token row and position-independent, so the SAME
        code serves training forwards, prefill and single-token decode —
        per-slot expert routing inside the engine's one jitted decode
        program is just this method applied to ``[B, 1, D]`` rows.

        ``expert_axis=None``: every expert evaluated, one-hot + gate
        combine — the exact reference form (row-independent, so the
        engine's co-resident slots route without coupling and streams
        stay bit-identical to the sequential :func:`generate`).

        ``expert_axis`` set: ownership-split serving form — pad the
        replicated rows to a multiple of the axis size, route the owned
        slice through :func:`~chainermn_tpu.parallel.moe.moe_layer_local`
        (no-drop capacity: serving never drops tokens), scatter the
        owned outputs into a zero buffer and re-replicate with ONE
        ``psum``. Routing uses the same ``argmax(softmax)`` as
        ``route_slots``, so both forms pick identical experts.
        """
        E = self.n_experts
        e_decl = self.moe_experts_local or E
        D = h.shape[-1]
        cd = self.compute_dtype
        kern = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,),
        )
        router = self.param(
            "moe_router", nn.initializers.normal(0.02), (D, E),
            jnp.float32,
        )
        # expert-stacked leaves: [E, ...] full, or the sharder's
        # [E/n, ...] slice under the engine's TP clone (e_decl)
        w_up = self.param("moe_w_up", kern, (e_decl, D, self.d_ff),
                          jnp.float32)
        b_up = self.param("moe_b_up", nn.initializers.zeros_init(),
                          (e_decl, self.d_ff), jnp.float32)
        w_down = self.param("moe_w_down", kern, (e_decl, self.d_ff, D),
                            jnp.float32)
        b_down = self.param("moe_b_down", nn.initializers.zeros_init(),
                            (e_decl, D), jnp.float32)

        if self.expert_axis is None:
            # The expert dim follows the LEAF: every real local
            # application carries full leaves (e_eff == n_experts,
            # exact semantics); the cache-init eval_shape applies the
            # TP-local clone outside shard_map, where only shapes flow.
            e_eff = w_up.shape[0]
            logits = h @ router[:, :e_eff]  # f32 promote: routing precision
            probs = jax.nn.softmax(logits, axis=-1)
            gate = jnp.max(probs, axis=-1)
            idx = jnp.argmax(probs, axis=-1)
            up = jnp.einsum("...d,edf->...ef", h,
                            w_up.astype(cd)) + b_up.astype(cd)
            down = jnp.einsum("...ef,efd->...ed", nn.gelu(up),
                              w_down.astype(cd)) + b_down.astype(cd)
            combine = (jax.nn.one_hot(idx, e_eff, dtype=down.dtype)
                       * gate.astype(down.dtype)[..., None])
            return jnp.einsum("...ed,...e->...d", down, combine)

        from chainermn_tpu.parallel import moe as _moe

        ax = self.expert_axis
        n = jax.lax.axis_size(ax)
        eps = w_up.shape[0]  # E_local: the sharder's slice, not E
        B, T, _ = h.shape
        rows = B * T
        own = -(-rows // n)
        hr = h.reshape(rows, D)
        if own * n != rows:
            hr = jnp.pad(hr, ((0, own * n - rows), (0, 0)))
        i = jax.lax.axis_index(ax)
        sl = jax.lax.dynamic_slice_in_dim(hr, i * own, own)
        eparams = (w_up.astype(cd), b_up.astype(cd),
                   w_down.astype(cd), b_down.astype(cd))
        if eps == 1:
            eparams = jax.tree.map(lambda l: l[0], eparams)

        def expert_mlp(p, xq):
            wu, bu, wd, bd = p
            return nn.gelu(xq @ wu + bu) @ wd + bd

        out_own = _moe.moe_layer_local(
            sl, router, expert_mlp, eparams, ax,
            capacity_factor=None, dispatch_impl=self.moe_dispatch_impl,
            experts_per_shard=eps,
        )
        full = jnp.zeros((own * n, D), out_own.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(full, out_own,
                                                   i * own, 0)
        # ONE psum re-replicates — the MoE analogue of dense ff_down's
        # reduce_from_tp (TP stays at exactly 2 all-reduces per layer)
        full = jax.lax.psum(full, ax)
        return full[:rows].reshape(B, T, D)

    def _moe_dropless(self, h, arch):
        """Dropless top-k mixture of gated-SiLU experts (the training
        path of an :class:`Architecture` with experts): route, sort the
        ``tokens * k`` rows by expert, two grouped matmuls round the SiLU
        gate, weighted sum back. No capacity, so no token is dropped and
        none is padded; the router's auxiliary losses and load are sown
        into :data:`MOE_AUX`. With a share of the experts held
        (``arch.experts_held``) the leaves and the groups are the held
        experts', and the section from the gather into expert order to
        the weighted sum back is :func:`chainermn_tpu.parallel.moe.
        experts_in_rounds`: a **round** is ``R`` consecutive rows of the
        expert-sorted order (``R`` from static shapes alone,
        :func:`~chainermn_tpu.parallel.moe.rows_bound`: twice the share a
        balanced router gives the chip), gathered, multiplied and gated by
        themselves, and a loop runs ``ceil(rows_held / R)`` of them, one
        while the held rows fit. The rows of the absent experts, which lie
        behind the last group, are zeros: behind the last round run never
        touched, inside it written by the grouped matmuls, not multiplied
        (those tiles are counted, ``tail_tiles``, and sown with ``rounds``
        beside the router's statistics). Such a layer keeps nothing of the
        section for the backward but its inputs, so trained with no remat
        policy it computes the section twice. Every expert held is one
        round of all ``tokens * k`` rows by construction and stays the
        straight-line spelling. A shared expert
        (``arch.shared_expert_width``) is one more gated feed-forward that
        every token passes outside the routing, added unweighted and,
        under a share, computed whole (:data:`train_path.MOE_SHARED`)."""
        from chainermn_tpu.observability.metrics import registry
        from chainermn_tpu.ops.grouped_matmul import tail_tiles
        from chainermn_tpu.parallel import moe as _moe

        E, F = arch.n_experts, arch.expert_width
        held = arch.n_experts_held
        B, T, D = h.shape
        kern = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,),
        )
        router = self.param("moe_router", nn.initializers.normal(0.02),
                            (D, E), jnp.float32)
        # gate and up of an expert are one matrix, gate's columns first:
        # one grouped matmul makes both
        w_gate_up = self.param("moe_w_gate_up", kern, (held, D, 2 * F),
                               jnp.float32)
        w_down = self.param("moe_w_down", kern, (held, F, D), jnp.float32)
        kw = arch.router_kwargs()
        if arch.router_bias:
            if not (self.is_initializing()
                    or self.has_variable(ROUTER_STATE, "moe_router_bias")):
                raise ValueError(
                    "this router chooses by score + bias: apply the model "
                    f"with the {ROUTER_STATE!r} collection that init made "
                    "beside the parameters")
            kw["select_bias"] = self.variable(
                ROUTER_STATE, "moe_router_bias", jnp.zeros, (E,),
                jnp.float32).value

        tokens = h.reshape(B * T, D)
        routing = _moe.dropless_topk(tokens, router, arch.experts_per_token,
                                     arch.renormalise_gates, **kw)
        for name, value in _moe.dropless_aux(
                routing, arch.router_score == "softmax").items():
            self.sow(MOE_AUX, name, value)
        if arch.seq_aux:
            with jax.named_scope(train_path.MOE_ROUTE):
                self.sow(MOE_AUX, "seq_aux", sequence_balance_loss(
                    routing.logits.reshape(B, T, E),
                    routing.experts.reshape(B, T, -1)))
        bound = _moe.rows_bound(routing.order.shape[0], held, E)
        registry().gauge(
            train_path.MOE_ROWS_BOUND,
            "expert-sorted rows one round of a dropless MoE layer's expert "
            "section takes (moe_rows_per_step where every expert is held), "
            "at the last layer traced",
        ).set(float(bound))
        if held == E:
            # one round of all the rows by construction: straight-line
            rows = _moe.dispatch(tokens, routing)
            stats = {"rounds": jnp.float32(1.0),
                     "tail_tiles": tail_tiles(
                         routing.group_sizes, rows.shape[0]
                     ).astype(jnp.float32)}
            out = _moe.gated_experts(rows, w_gate_up, w_down,
                                     routing.group_sizes)
            out = _moe.combine(out, routing)
        else:
            stats = _moe.rounds_aux(routing, bound)
            out = _moe.experts_in_rounds(tokens, w_gate_up, w_down, routing)
        for name, value in stats.items():
            self.sow(MOE_AUX, name, value)
        out = out.reshape(B, T, D)
        if arch.shared_expert_width:
            out = out + self._shared_expert(h, arch.shared_expert_width)
        return out

    def _shared_expert(self, h, width):
        """``down(silu(gate(h)) * up(h))`` at ``width``, gate and up one
        matrix (gate's columns first) as the routed experts' are."""
        from chainermn_tpu.observability.metrics import registry

        registry().gauge(
            train_path.MOE_SHARED_WIDTH,
            "width of the shared expert every token passes beside the "
            "routed ones, at the last layer traced",
        ).set(float(width))
        with jax.named_scope(train_path.MOE_SHARED):
            gate_up = nn.Dense(
                2 * width, use_bias=False, dtype=self.compute_dtype,
                param_dtype=jnp.float32, name="shared_gate_up")(h)
            act = nn.silu(gate_up[..., :width]) * gate_up[..., width:]
            return nn.Dense(
                h.shape[-1], use_bias=False, dtype=self.compute_dtype,
                param_dtype=jnp.float32, name="shared_down")(act)

    def _latent_attention(self, h, arch, rope_positions, causal, **kw):
        """DeepSeek-V2's latent attention, queries projected directly:
        ``q = h W_q`` (a head ``[q_nope ; q_pe]``), ``[c ; k_pe] = h
        W_kva``, ``[k_nope ; v] = RMSNorm(c) W_kvb`` a head, RoPE (YaRN's
        frequencies where the description scales them) on ``q_pe`` and on
        the one ``k_pe`` all heads share, which is broadcast to them and
        concatenated behind ``k_nope``; causal softmax at ``(nope +
        rope) ** -0.5`` times YaRN's ``softmax_scale`` with values of
        their own width; the output projection. Everything between the
        block's norm and the residual is under
        :data:`train_path.MLA_ATTENTION`."""
        from chainermn_tpu.observability.metrics import registry
        from chainermn_tpu.ops.flash_attention import flash_attention

        H, nope, rope = self.num_heads, arch.qk_nope_dim, arch.qk_rope_dim
        dv, rank = arch.v_head_dim, arch.latent_rank
        for name, value, what in (
                (train_path.MLA_LATENT_RANK, rank,
                 "values of the normed latent a token's keys and values "
                 "are projected from"),
                (train_path.MLA_QK_WIDTH, nope + rope,
                 "width of a head's query and key (without position + "
                 "rotated)"),
                (train_path.MLA_V_WIDTH, dv, "width of a head's values")):
            registry().gauge(
                name, what + ", at the last latent attention traced",
            ).set(float(value))
        # the blockwise reference knows one head width
        attn = self.attention_fn or flash_attention
        B, T, D = h.shape
        cd = self.compute_dtype

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=cd,
                            param_dtype=jnp.float32, name=name)

        with jax.named_scope(train_path.MLA_ATTENTION):
            q = dense(H * (nope + rope), "q_proj")(h) \
                .reshape(B, T, H, nope + rope)
            kva = dense(rank + rope, "kv_a")(h)
            c = _norm_layer(arch, cd, "kv_a_norm")(kva[..., :rank])
            kv = dense(H * (nope + dv), "kv_b")(c) \
                .reshape(B, T, H, nope + dv)
            k_pe = kva[..., None, rank:]  # [B, T, 1, rope]
            if rope_positions is not None:
                q_pe = apply_rope(q[..., nope:], rope_positions,
                                  arch.rope_base, arch.rope_scaling)
                q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
                k_pe = apply_rope(k_pe, rope_positions, arch.rope_base,
                                  arch.rope_scaling)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_pe, (B, T, H, rope))], axis=-1)
            scale = (nope + rope) ** -0.5
            if arch.rope_scaling is not None:
                scale *= arch.rope_scaling.softmax_scale
            o = attn(q, k, kv[..., nope:], causal=causal, scale=scale,
                     **kw)
            return dense(D, "proj")(o.reshape(B, T, H * dv))

    def _short_conv(self, h, arch):
        """LFM2's gated short convolution: ``(B, C, x) = split3(h W_in)``,
        ``u = B * x``, a depthwise causal convolution of ``conv_width``
        taps over ``u`` (zero before the sequence starts, no bias),
        ``(C * conv) W_out``. What lies between the two projections is
        :func:`~chainermn_tpu.ops.short_conv.gated_short_conv`, under
        :data:`train_path.SHORT_CONV` (the projections are matmuls
        outside it)."""
        from chainermn_tpu.ops.short_conv import gated_short_conv

        D, L = h.shape[-1], arch.conv_width
        bcx = nn.Dense(
            3 * D, use_bias=False, dtype=self.compute_dtype,
            param_dtype=jnp.float32, name="conv_in",
        )(h)
        # torch's Conv1d default for a depthwise kernel of L taps; tap j
        # multiplies the input L - 1 - j steps back
        bound = L ** -0.5
        taps = self.param(
            "conv_w",
            lambda key, shape, dtype: jax.random.uniform(
                key, shape, dtype, -bound, bound),
            (L, D), jnp.float32)
        y = gated_short_conv(bcx, taps)
        return nn.Dense(
            D, use_bias=False, dtype=self.compute_dtype,
            param_dtype=jnp.float32, name="conv_out",
        )(y)

    @nn.compact
    def __call__(self, x, segment_ids=None, rope_positions=None,
                 train: bool = True, decode: bool = False,
                 decode_positions=None, block_tables=None,
                 decode_slots=None, adapters=None):
        # ``train`` is positional so ``nn.remat(..., static_argnums=(4,))``
        # can mark it static. ``decode_positions`` ([B] int32 first-new
        # -token positions) selects the slot-array decode path
        # (:meth:`_slot_decode_attend`); ``block_tables`` ([B, max_blocks]
        # int32) feeds the paged layout; ``decode_slots`` ([B] int32) maps
        # token rows onto dense-cache rows (prefill of one slot out of
        # many); ``adapters`` ({'qkv'|'proj'|'ff_up'|'ff_down': (A, B)})
        # adds per-projection low-rank deltas (:meth:`_lora_delta`).
        D = x.shape[-1]
        head_dim = self.head_dim or D // self.num_heads
        kv_heads = self.num_kv_heads or self.num_heads
        attn = self.attention_fn or blockwise_attention
        arch = self.arch
        if self.tp_axis is not None:
            from chainermn_tpu.parallel.tensor import (
                copy_to_tp,
                reduce_from_tp,
            )

        mixer, ffn = arch.layer(self.layer_index)

        def attention(h):
            """Queries, keys and values, the attention of the call's mode
            and the output projection."""
            if self.tp_axis is not None:
                h = copy_to_tp(h, self.tp_axis)
            qkv = nn.Dense(
                (self.num_heads + 2 * kv_heads) * head_dim, use_bias=False,
                dtype=self.compute_dtype, param_dtype=jnp.float32, name="qkv",
            )(h)
            # Column-parallel delta (ISSUE 14): h is replicated under TP
            # (post copy_to_tp), the adapter's B is column-sharded like the
            # qkv kernel — the delta lands on the shard's own columns, no
            # new collective.
            qkv = self._lora_delta("qkv", adapters, h, qkv)
            q, k, v = jnp.split(
                qkv,
                [self.num_heads * head_dim,
                 (self.num_heads + kv_heads) * head_dim],
                axis=-1,
            )
            B, T = q.shape[:2]
            if arch.qk_norm == "projection":
                q = _norm_layer(arch, self.compute_dtype, "q_norm")(q)
                k = _norm_layer(arch, self.compute_dtype, "k_norm")(k)

            def heads(t, n):
                return t.reshape(B, T, n, head_dim)

            qh, kh = heads(q, self.num_heads), heads(k, kv_heads)
            if arch.qk_norm == "head":
                # over each head's values, one scale for all heads
                qh = _norm_layer(arch, self.compute_dtype, "q_norm")(qh)
                kh = _norm_layer(arch, self.compute_dtype, "k_norm")(kh)
            if rope_positions is not None:
                qh = apply_rope(qh, rope_positions, arch.rope_base)
                kh = apply_rope(kh, rope_positions, arch.rope_base)
            if decode:
                if not self.causal:
                    raise ValueError("decode=True requires a causal block")
                if decode_positions is not None:
                    o = self._slot_decode_attend(
                        qh, kh, heads(v, kv_heads), head_dim,
                        decode_positions, block_tables, decode_slots,
                    )
                else:
                    if T != 1:
                        raise ValueError(
                            "decode=True expects one token per step, "
                            f"got T={T}"
                        )
                    o = self._decode_attend(qh, kh, heads(v, kv_heads),
                                            head_dim)
            else:
                if self.window is not None and self.attention_fn is None:
                    raise ValueError(
                        "window needs a window-honouring attention_fn (e.g. "
                        "flash_attention(..., window=W)) — the default "
                        "blockwise reference has no window support"
                    )
                if self.window is not None and not self.causal:
                    raise ValueError("window requires a causal block")
                vh = heads(v, kv_heads)
                if self.sow_kv:
                    self.sow("kv_out", "k", kh.astype(self.compute_dtype))
                    self.sow("kv_out", "v", vh.astype(self.compute_dtype))
                kw = {} if segment_ids is None \
                    else {"segment_ids": segment_ids}
                if arch.diffusion_block:
                    # the rows are [x ; x~]: the mask is the model's own
                    # and no pluggable attention_fn knows it
                    if kw or self.window is not None or not self.causal \
                            or self.sow_kv or self.tp_axis is not None:
                        raise ValueError(
                            "a block-diffusion model's attention is its "
                            "mask by blocks over [x ; x~]: no segment "
                            "ids, window, bidirectional blocks, captured "
                            "keys or tensor parallelism yet")
                    from chainermn_tpu.ops.block_diffusion import (
                        block_diffusion_attention,
                    )
                    o = block_diffusion_attention(
                        qh, kh, vh, block_length=arch.diffusion_block,
                        scale=head_dim**-0.5)
                else:
                    o = attn(qh, kh, vh, causal=self.causal,
                             scale=head_dim**-0.5, **kw)
            o_flat = o.reshape(B, T, self.num_heads * head_dim)
            o = nn.Dense(
                D, use_bias=False,
                dtype=self.compute_dtype, param_dtype=jnp.float32, name="proj",
            )(o_flat)
            # Row-parallel delta (ISSUE 14): the adapter's A is sharded
            # along the same local-head rows as the proj kernel, so the
            # per-shard partial delta rides the existing psum below —
            # exactly the pre-adapter collective set.
            o = self._lora_delta("proj", adapters, o_flat, o)
            if self.tp_axis is not None:
                # Row-parallel output projection: the ONE psum of the
                # attention column→row pair.
                o = reduce_from_tp(o, self.tp_axis)
            return o

        h = _norm_layer(arch, self.compute_dtype)(x)
        if mixer == "short_conv":
            if decode or adapters is not None or self.sow_kv \
                    or self.tp_axis is not None or segment_ids is not None:
                raise ValueError(
                    "the gated short convolution is the training path: no "
                    "decode (it needs a state of conv_width - 1 rows a "
                    "slot), adapters, tensor parallelism, captured keys "
                    "or segment ids yet")
            o = self._short_conv(h, arch)
        elif mixer == "latent_attention":
            if decode or adapters is not None or self.sow_kv \
                    or self.tp_axis is not None or self.window is not None \
                    or self.head_dim is not None or \
                    self.num_kv_heads not in (None, self.num_heads):
                raise ValueError(
                    "latent attention is the training path: no decode "
                    "(the paged cache stores whole heads, not a latent), "
                    "adapters, tensor parallelism, captured keys, window, "
                    "head_dim override or fewer key-value heads yet")
            o = self._latent_attention(
                h, arch, rope_positions, self.causal,
                **({} if segment_ids is None
                   else {"segment_ids": segment_ids}))
        else:
            o = attention(h)

        def branch(x, out, norm_name):
            """A sub-layer's output into the residual stream."""
            if arch.post_norm:
                out = _norm_layer(arch, self.compute_dtype, norm_name)(out)
            if self.dropout_rate > 0.0:
                out = nn.Dropout(self.dropout_rate,
                                 deterministic=not train)(out)
            return x + out

        x = branch(x, o, "attn_out_norm")

        h = _norm_layer(arch, self.compute_dtype)(x)
        if ffn == "experts":
            if decode or adapters is not None or self.tp_axis is not None:
                raise ValueError(
                    "the dropless mixture of experts is the training "
                    "path: no decode, adapters or tensor parallelism yet")
            return branch(x, self._moe_dropless(h, arch), "ffn_out_norm")
        if self.n_experts > 0:
            if adapters is not None and (
                "ff_up" in adapters or "ff_down" in adapters
            ):
                raise ValueError(
                    "MoE blocks have no ff_up/ff_down projections to "
                    "hook — adapters may target qkv/proj only"
                )
            return branch(x, self._moe_ffn(h), "ffn_out_norm")
        if self.tp_axis is not None:
            h = copy_to_tp(h, self.tp_axis)
        gated = arch.ffn == "gated_silu"
        up = nn.Dense(
            self.d_ff, use_bias=not gated,
            dtype=self.compute_dtype, param_dtype=jnp.float32,
            name="ff_up",
        )(h)
        # Column-parallel (B sharded with the ff_up kernel's d_ff split).
        up = self._lora_delta("ff_up", adapters, h, up)
        if gated:
            # column-parallel like ff_up: the product stays local
            h = nn.silu(nn.Dense(
                self.d_ff, use_bias=False, dtype=self.compute_dtype,
                param_dtype=jnp.float32, name="ff_gate",
            )(h)) * up
        else:
            h = nn.gelu(up)
        down = nn.Dense(
            D, use_bias=not gated,
            dtype=self.compute_dtype, param_dtype=jnp.float32, name="ff_down",
        )(h)
        # Row-parallel (A sharded with the ff_down kernel's d_ff rows;
        # the partial delta rides the layer's second psum).
        h = self._lora_delta("ff_down", adapters, h, down)
        if self.tp_axis is not None:
            # Row-parallel FFN down projection (psum #2 of the layer).
            # ff_down's bias rides INSIDE the reduce: the sharder stores
            # bias / axis_size so the psum reassembles it exactly.
            h = reduce_from_tp(h, self.tp_axis)
        return branch(x, h, "ffn_out_norm")


#: what ``jax.checkpoint`` keeps of a block for the backward, by the name
#: ``remat_policy`` takes. ``'dots'``: the matmul-class results, XLA's
#: dots and, by the names its wrapper gives them, what the flash forward
#: kernel made (a Pallas call is no dot; an ``attention_fn`` without such
#: names saves its dots alone). ``'nothing'``: jax.checkpoint's default.
_REMAT_POLICIES = {
    "dots": jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        jax.checkpoint_policies.save_only_these_names(
            *train_path.FLASH_RESIDUALS),
    ),
    "nothing": None,
}


def _remat_block(remat_policy: str):
    """``nn.remat``-wrapped :class:`TransformerBlock` for the given save
    policy — ONE construction shared by :class:`TransformerLM` and
    :class:`chainermn_tpu.models.vit.VisionTransformer` so the
    policy-name surface cannot drift between the families."""
    if remat_policy not in _REMAT_POLICIES:
        raise ValueError(
            f"remat_policy must be 'dots' or 'nothing', got "
            f"{remat_policy!r}"
        )
    return nn.remat(
        TransformerBlock,
        policy=_REMAT_POLICIES[remat_policy],
        static_argnums=(4, 5),  # (self, x, seg, rope_pos, train, dec)
    )


def refuse_looped_decode(model, what: str):
    """Decoding a looped model needs a KV cache a pass; until there is
    one, every decode entry point refuses it (never a silent single
    pass)."""
    raise NotImplementedError(
        f"{what} of a looped model (total_ut_steps="
        f"{model.total_ut_steps}) is not built: it needs a KV cache a "
        "pass; the training path (lm_loss_looped) is")


def refuse_unbuilt_decode(model, what: str):
    """Decoding, serving and their caches are refused for a model with a
    layer kind or a share the decode path does not build (never a silent
    substitute): a gated short convolution needs a state of ``conv_width
    - 1`` rows a slot beside the KV blocks, and a chip's share of the
    experts is a part of each layer's sum that means nothing without the
    other chips' parts. No-op for every other model."""
    arch = model.arch
    if arch is None:
        return
    if arch.diffusion_block:
        raise NotImplementedError(
            f"{what} of a block-diffusion model (blocks of "
            f"{arch.diffusion_block}) is not built: a step yields a block "
            "over several denoising passes and the cache is written when "
            "a block is final; the training path "
            "(lm_loss_block_diffusion) is")
    if arch.has_latent_attention:
        raise NotImplementedError(
            f"{what} of a model with latent_attention layers is not "
            "built: its cache is a latent of "
            f"{arch.latent_rank} + {arch.qk_rope_dim} values a token "
            "that every head reads through its own up-projection, and "
            "the paged cache stores whole heads; the training path is")
    if arch.has_short_conv:
        raise NotImplementedError(
            f"{what} of a model with short_conv layers is not built: it "
            f"needs a convolution state of {arch.conv_width - 1} rows a "
            "slot beside the KV cache; the training path is")
    if arch.n_experts_held != arch.n_experts:
        raise NotImplementedError(
            f"{what} of a model that holds experts "
            f"{list(arch.experts_held)} of {arch.n_experts} is not built: "
            "a share's output is one chip's part of each layer's sum; "
            "the training path is")


def _publish_stack_kinds(arch: Architecture, num_layers: int):
    """Gauge :data:`train_path.STACK_LAYERS_BY_KIND`, set while the
    caller's program is traced."""
    from chainermn_tpu.observability.metrics import registry

    gauge = registry().gauge(
        train_path.STACK_LAYERS_BY_KIND,
        "layers of the stack with a mixer or a feed-forward of each "
        "kind, at the last model traced",
    )
    kinds = [arch.layer(i) for i in range(num_layers)]
    for label, where, name in (("attention", 0, "attention"),
                               ("short_conv", 0, "short_conv"),
                               ("latent_attention", 0, "latent_attention"),
                               ("dense_ffn", 1, "dense"),
                               ("expert_ffn", 1, "experts")):
        gauge.set(float(sum(k[where] == name for k in kinds)), kind=label)


def _publish_loop_passes(passes: int):
    """Gauge :data:`train_path.LOOP_PASSES`, set while the caller's
    program is traced."""
    from chainermn_tpu.observability.metrics import registry

    registry().gauge(
        train_path.LOOP_PASSES,
        "passes of a looped model's layer stack over one set of weights "
        "(total_ut_steps), at the last model traced",
    ).set(float(passes))


class TransformerLM(nn.Module):
    """Causal LM over integer tokens ``[B, T]`` → logits ``[B, T, vocab]``."""

    vocab_size: int = 32000
    num_layers: int = 6
    num_heads: int = 8
    d_model: int = 512
    d_ff: int = 2048
    max_len: int = 2048
    compute_dtype: Any = jnp.bfloat16
    attention_fn: Optional[Callable] = None
    #: global position offset of the local sequence shard (sequence-parallel
    #: runs pass ``axis_index * T_local`` so learned positions line up).
    pos_offset: int = 0
    #: rematerialize each block in the backward pass (keep only what
    #: ``remat_policy`` names: by default the matmul-class results);
    #: trades ~1/3 more FLOPs for activation memory, the standard TPU move
    #: for fitting larger B*T (SURVEY.md "use jax.checkpoint to trade FLOPs
    #: for memory").
    remat: bool = False
    #: remat save policy (with ``remat=True``): ``'dots'`` — keep the
    #: matmul-class results, recompute elementwise/norm chains (the
    #: default; cheapest recompute). Matmul-class: XLA's dots, and with
    #: ``flash_attention`` as ``attention_fn`` what its forward kernel
    #: made (output and log-sum-exp, by their checkpoint names), so the
    #: backward calls no attention forward again; another
    #: ``attention_fn`` saves its dots alone. ``'nothing'`` — save only
    #: block inputs, recompute everything (max memory saving, ~1/3 extra
    #: FLOPs: the knob the MFU sweep explores for HBM-bound configs).
    remat_policy: str = "dots"
    #: skip the weight-tied LM head and return the final (post-LN) hidden
    #: states; pair with :func:`lm_loss_fused`, which makes loss and
    #: gradient a chunk of tokens at a time and never materializes the
    #: ``[B, T, vocab]`` logits tensor.
    return_hidden: bool = False
    #: kv heads for GQA/MQA (None → num_heads).
    num_kv_heads: Optional[int] = None
    #: ``'learned'`` (reference-style absolute table) or ``'rope'``
    #: (rotary — no position parameters; relative by construction, the
    #: natural choice under sequence parallelism where a learned table
    #: would need per-shard rolling). The positions of a model without a
    #: description (``arch``); with one, its ``positions`` are read.
    pos_encoding: str = "learned"
    #: causal sliding-window width (see ``TransformerBlock.window``):
    #: training requires a window-honouring ``attention_fn``; the decode
    #: path masks the KV cache to the same band automatically.
    window: Optional[int] = None
    #: residual dropout rate (see ``TransformerBlock.dropout_rate``);
    #: pass ``rngs={'dropout': key}`` to ``apply`` when training with it.
    dropout_rate: float = 0.0
    #: bidirectional (BERT/MLM-style) encoder when False: every block
    #: attends both directions, the weight-tied head scores each
    #: position against the full vocabulary (pair with
    #: :func:`mlm_loss`), and autoregressive decode is rejected.
    causal: bool = True
    #: decode KV-cache layout (see ``TransformerBlock.kv_layout``):
    #: ``'dense'`` or ``'paged'`` — the serving engine clones the model
    #: with the resolved layout; :func:`generate` uses the legacy dense
    #: ring either way.
    kv_layout: str = "dense"
    #: tokens per paged-pool block (``TransformerBlock.kv_block_size``).
    kv_block_size: int = 64
    #: paged-pool capacity in blocks (``TransformerBlock.kv_num_blocks``).
    kv_num_blocks: int = 0
    #: slot-decode attend impl (``TransformerBlock.decode_attend_impl``):
    #: ``'xla'`` or ``'fused'`` — the serving engine clones the model
    #: with the registry-resolved impl (decision ``decode_attend_impl``).
    decode_attend_impl: str = "xla"
    #: decode-cache capacity override: dense slot caches allocate
    #: ``decode_cache_len`` rows instead of ``max_len`` (a serving
    #: horizon shorter than the trained context — pos_emb stays at
    #: ``max_len`` so trained params load unchanged). None → ``max_len``.
    decode_cache_len: Optional[int] = None
    #: tensor-parallel mesh axis (see ``TransformerBlock.tp_axis``);
    #: set together with LOCAL ``num_heads``/``num_kv_heads``/``d_ff``
    #: and an explicit ``head_dim`` (the serving engine's
    #: ``shard_lm_params`` builds the matching param tree).
    tp_axis: Optional[str] = None
    #: per-head width override for the blocks (required under
    #: ``tp_axis``).
    head_dim: Optional[int] = None
    #: thread ``TransformerBlock.sow_kv`` through every block (the
    #: sequence-parallel prefill's KV capture, ISSUE 13).
    sow_kv: bool = False
    #: mixture-of-experts FFN in every block (ISSUE 20; see
    #: ``TransformerBlock.n_experts``). 0 (default) = dense FFN.
    #: GLOBAL expert count — under ``expert_axis`` the serving sharder
    #: slices the stacked expert leaves, the field does not change.
    n_experts: int = 0
    #: expert-shard mesh axis for serving decode (see
    #: ``TransformerBlock.expert_axis``; the engine sets it to its TP
    #: axis — expert shards live on the TP mesh).
    expert_axis: Optional[str] = None
    #: MoE queue-build impl for the ownership-split path
    #: (``TransformerBlock.moe_dispatch_impl``).
    moe_dispatch_impl: str = "auto"
    #: declared expert-leaf leading dim for per-shard param trees
    #: (``TransformerBlock.moe_experts_local``; the engine's TP clone
    #: sets ``n_experts // tp``).
    moe_experts_local: Optional[int] = None
    #: the kind of block, of positions and of head (:class:`Architecture`;
    #: :func:`lm_from_config` builds a model from a ``config.json``).
    #: ``None`` is the GPT-2 instance with ``pos_encoding``'s positions;
    #: a description given here is read alone and ``pos_encoding`` is not.
    arch: Optional[Architecture] = None
    #: passes of a looped model (Ouro's ``total_ut_steps``): the same
    #: ``num_layers`` blocks, one set of parameters, applied this many
    #: times, each pass closed by the final norm, whose output the next
    #: pass reads. 1 is the plain stack. With more than one pass, or with
    #: the description's exit gate, ``return_hidden`` hands back ``(hidden
    #: [passes, B, T, D], gate_logits [passes, B, T] float32 or None)``
    #: for :func:`lm_loss_looped`, and the logits are the last pass's.
    #: Training only: decoding needs a cache a pass and is refused.
    total_ut_steps: int = 1

    @property
    def looped(self) -> bool:
        return self.total_ut_steps > 1 or bool(
            self.arch and self.arch.exit_gate)

    @property
    def expert_layers(self) -> tuple:
        """The layers whose feed-forward is the description's experts."""
        if self.arch is None:
            return ()
        return tuple(i for i in range(self.num_layers)
                     if self.arch.layer(i)[1] == "experts")

    @nn.compact
    def __call__(self, tokens, *, segment_ids=None, positions=None,
                 train: bool = True, decode: bool = False,
                 decode_positions=None, block_tables=None,
                 decode_slots=None, adapters=None):
        """``segment_ids`` (optional ``[B, T]``) confines attention to
        packed documents; requires a segment-capable ``attention_fn``
        (e.g. :func:`chainermn_tpu.ops.flash_attention.flash_attention`).
        ``positions`` (optional ``[T]`` int32 GLOBAL positions) overrides
        ``pos_offset + arange(T)`` — sequence-parallel shards pass
        ``axis_index * T_local + arange(T_local)``.
        ``decode=True`` runs one-token autoregressive steps (``T == 1``)
        against the mutable ``'cache'`` collection; see :func:`generate`.
        ``decode_positions`` (optional ``[B]`` int32) switches decode to
        the slot-array path — per-row write positions, ``T >= 1``
        chunked prefill, paged/dense layouts, ``decode_slots`` row
        mapping — the serving engine's contract
        (:mod:`chainermn_tpu.serving`).
        ``adapters`` (optional, ISSUE 14): per-layer low-rank deltas —
        a sequence of ``num_layers`` dicts, each mapping a hooked
        projection (``qkv``/``proj``/``ff_up``/``ff_down``) to its
        ``(A, B)`` pair (see :meth:`TransformerBlock._lora_delta` for
        the unbatched vs per-row forms); the serving engine's
        :class:`~chainermn_tpu.serving.adapters.AdapterBank` builds
        both."""
        if segment_ids is not None and self.attention_fn is None:
            raise ValueError(
                "segment_ids needs a segment-capable attention_fn — pass "
                "attention_fn=flash_attention (the default blockwise "
                "reference does not take segment masks)"
            )
        arch = self.arch
        if arch is None:
            if self.pos_encoding not in ("learned", "rope"):
                raise ValueError(
                    f"pos_encoding must be 'learned' or 'rope', got "
                    f"{self.pos_encoding!r}"
                )
            arch = Architecture(positions=self.pos_encoding)
        if arch.n_experts > 0 and self.n_experts > 0:
            raise ValueError(
                "n_experts selects the top-1 serving form, the "
                "description's experts the dropless one: give one"
            )
        passes, looped = self.total_ut_steps, self.looped
        if passes < 1:
            raise ValueError(f"total_ut_steps must be >= 1, got {passes}")
        if looped and decode:
            refuse_looped_decode(self, "decode=True")
        if decode:
            refuse_unbuilt_decode(self, "decode=True")
        if arch.layers is not None:
            if len(arch.layers) != self.num_layers:
                raise ValueError(
                    f"the description has {len(arch.layers)} layers, the "
                    f"model {self.num_layers}")
            if looped:
                raise ValueError(
                    "a looped model with a stack described layer by layer "
                    "is not built")
        _publish_stack_kinds(arch, self.num_layers)
        if looped and arch.n_experts > 0:
            raise ValueError(
                "a looped model with experts is not built: the router's "
                "auxiliary losses are read one entry a block")
        if decode and not self.causal:
            raise ValueError(
                "decode=True is autoregressive and requires causal=True"
            )
        if decode_positions is not None and not decode:
            raise ValueError("decode_positions requires decode=True")
        if adapters is not None and len(adapters) != self.num_layers:
            raise ValueError(
                f"adapters covers {len(adapters)} layers, model has "
                f"{self.num_layers}"
            )
        B, T = tokens.shape
        if decode_positions is not None and positions is None:
            # Per-row global positions for rope / the learned table:
            # row b's tokens sit at decode_positions[b] + [0, T).
            positions = (decode_positions[:, None]
                         + jnp.arange(T, dtype=jnp.int32)[None])
        emb = nn.Embed(
            self.vocab_size, self.d_model, param_dtype=jnp.float32,
            dtype=self.compute_dtype, name="tok_emb",
        )
        if arch.tied_head:
            x = emb(tokens)
        else:
            # A table that only the lookup reads takes its whole gradient
            # from the scatter-add of one row a token. Gathering the
            # float32 rows and casting them (the same values as casting
            # the table and gathering) makes that sum float32: in bf16 a
            # frequent token's hundreds of rows lose 5% of it.
            x = jnp.take(emb.embedding, tokens, axis=0).astype(
                self.compute_dtype)
        rope_positions = None
        if arch.diffusion_block and positions is None and not decode:
            # the rows are [x ; x~]: the noised copy sits at the clean
            # copy's positions
            positions = self.pos_offset + jnp.tile(
                jnp.arange(T // 2, dtype=jnp.int32), 2)
        if arch.positions == "rope":
            if positions is None:
                positions = self.pos_offset + jnp.arange(T, dtype=jnp.int32)
            rope_positions = positions
        else:
            pos_emb = self.param(
                "pos_emb",
                nn.initializers.normal(0.02),
                (self.max_len, self.d_model),
                jnp.float32,
            )
            if positions is not None:
                pos = pos_emb[positions]  # [T, D] or [B, T, D] (per-row)
            else:
                pos = jax.lax.dynamic_slice_in_dim(
                    pos_emb, self.pos_offset, T, axis=0
                )
            if pos.ndim == 2:
                pos = pos[None]
            x = x + pos.astype(self.compute_dtype)
        block_cls = (
            _remat_block(self.remat_policy) if self.remat
            else TransformerBlock
        )
        blocks = [
            block_cls(
                num_heads=self.num_heads,
                d_ff=self.d_ff,
                compute_dtype=self.compute_dtype,
                attention_fn=self.attention_fn,
                num_kv_heads=self.num_kv_heads,
                decode_max_len=self.decode_cache_len or self.max_len,
                window=self.window,
                dropout_rate=self.dropout_rate,
                causal=self.causal,
                kv_layout=self.kv_layout,
                kv_block_size=self.kv_block_size,
                kv_num_blocks=self.kv_num_blocks,
                decode_attend_impl=self.decode_attend_impl,
                tp_axis=self.tp_axis,
                head_dim=self.head_dim,
                sow_kv=self.sow_kv,
                n_experts=self.n_experts,
                expert_axis=self.expert_axis,
                moe_dispatch_impl=self.moe_dispatch_impl,
                moe_experts_local=self.moe_experts_local,
                arch=arch,
                layer_index=i,
                name=f"block_{i}",
            ) for i in range(self.num_layers)
        ]
        final_norm = _norm_layer(arch, self.compute_dtype)

        def stack(x):
            """The blocks once, then the final norm. A module called again
            reads the parameters of its first call."""
            for i, block in enumerate(blocks):
                x = block(x, segment_ids, rope_positions, train, decode,
                          decode_positions, block_tables, decode_slots,
                          adapters[i] if adapters is not None else None)
            return final_norm(x)

        if not looped:
            x = stack(x)
        else:
            _publish_loop_passes(passes)
            exits = []
            with jax.named_scope(train_path.LOOP_STACK):
                for t in range(passes):
                    with jax.named_scope(train_path.pass_scope(t)):
                        x = stack(x)
                    exits.append(x)
        if arch.tied_head:
            head = emb
        else:
            # a table of its own, laid out as the embedding's so that
            # ``lm_loss_fused`` takes either (:func:`head_table`)
            head = nn.Embed(
                self.vocab_size, self.d_model, param_dtype=jnp.float32,
                dtype=self.compute_dtype, name="lm_head",
            )
        if looped:
            hidden = jnp.stack(exits)
            gate_logits = None
            if arch.exit_gate:
                with jax.named_scope(train_path.EXIT_GATE):
                    # [d, 1] and a bias, one gate for all passes; float32
                    # at full precision: 2 d flops a token and pass
                    gate_logits = nn.Dense(
                        1, dtype=jnp.float32, param_dtype=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST,
                        name="exit_gate",
                    )(hidden.astype(jnp.float32))[..., 0]
        if self.return_hidden:
            if not arch.tied_head and self.is_initializing():
                head.attend(x[:, :1])  # creates the table
            return (hidden, gate_logits) if looped else x
        with jax.named_scope(train_path.LM_HEAD):
            logits = head.attend(x.astype(jnp.float32))
        return logits


def head_table(params, arch: Optional[Architecture] = None):
    """The ``[vocab, d_model]`` table the head multiplies by: the
    embedding's when tied, ``lm_head``'s when not."""
    tied = arch is None or arch.tied_head
    return params["tok_emb" if tied else "lm_head"]["embedding"]


def lm_from_config(config: dict, *, num_layers: Optional[int] = None,
                   **kwargs) -> "TransformerLM":
    """A :class:`TransformerLM` from a ``config.json``-style dict (GPT-2's
    keys, OLMoE's, Ouro's or LFM2-MoE's; :data:`MODEL_CONFIGS` holds one
    of each), at the published sizes but for ``num_layers`` where given
    (of a stack described layer by layer: its first ``num_layers``).
    ``kwargs`` are the model's other fields (``compute_dtype``,
    ``attention_fn``, ``remat``, ...)."""
    arch = Architecture.from_config(config)
    if num_layers is not None and arch.layers is not None:
        if num_layers > len(arch.layers):
            raise ValueError(
                f"num_layers={num_layers} exceeds the {len(arch.layers)} "
                "layers the config describes")
        arch = dataclasses.replace(arch, layers=arch.layers[:num_layers])
    if config.get("model_type", "gpt2") == "gpt2":
        sizes = dict(
            vocab_size=config["vocab_size"], num_layers=config["n_layer"],
            num_heads=config["n_head"], d_model=config["n_embd"],
            d_ff=config.get("n_inner") or 4 * config["n_embd"],
            max_len=config["n_positions"],
        )
    else:
        sizes = dict(
            vocab_size=config["vocab_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            d_model=config["hidden_size"],
            d_ff=config["intermediate_size"],
            max_len=config["max_position_embeddings"],
            total_ut_steps=config.get("total_ut_steps", 1),
        )
        if config.get("head_dim") not in (None, sizes["d_model"]
                                          // sizes["num_heads"]):
            # heads wider than width / heads (SDAR's 32 heads of 128 on a
            # width of 2048)
            sizes["head_dim"] = int(config["head_dim"])
    if num_layers is not None:
        sizes["num_layers"] = num_layers
    sizes.update(kwargs)
    return TransformerLM(arch=arch, **sizes)


def lm_loss(logits, tokens, mask=None):
    """Next-token cross-entropy: predict ``tokens[:, 1:]`` from positions
    ``[:, :-1]``; optional padding ``mask`` (same shape as tokens, 1=real)."""
    import optax

    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    losses = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    if mask is not None:
        m = mask[:, 1:].astype(losses.dtype)
        return (losses * m).sum() / jnp.maximum(m.sum(), 1)
    return losses.mean()


def mlm_loss(logits, targets, mask):
    """Masked-LM cross-entropy: predict the ORIGINAL token at each masked
    position (no shift — the encoder sees both directions). ``targets``
    are the pre-masking tokens, ``mask`` is 1 where the input was
    corrupted (the only positions scored, per the BERT recipe)."""
    import optax

    losses = optax.softmax_cross_entropy_with_integer_labels(
        logits, targets
    )
    m = mask.astype(losses.dtype)
    return (losses * m).sum() / jnp.maximum(m.sum(), 1)


def mlm_corrupt(rng, tokens, *, mask_id, vocab_size, rate=0.15):
    """BERT-style corruption under jit: select ``rate`` of positions;
    of those 80% → ``mask_id``, 10% → random REAL token, 10% →
    unchanged. Returns ``(corrupted, selected_mask)``. Random draws
    that would land on ``mask_id`` are shifted by one (mod vocab) so
    the documented 80/10/10 mix holds even for small vocabularies."""
    k1, k2, k3 = jax.random.split(rng, 3)
    sel = jax.random.uniform(k1, tokens.shape) < rate
    roll = jax.random.uniform(k2, tokens.shape)
    rand_tok = jax.random.randint(k3, tokens.shape, 0, vocab_size)
    rand_tok = jnp.where(rand_tok == mask_id,
                         (rand_tok + 1) % vocab_size, rand_tok)
    corrupted = jnp.where(sel & (roll < 0.8), mask_id, tokens)
    corrupted = jnp.where(sel & (roll >= 0.8) & (roll < 0.9), rand_tok,
                          corrupted)
    return corrupted, sel


def lm_loss_fused(hidden, emb_table, tokens, *, n_chunks=8,
                  compute_dtype=jnp.bfloat16, weights=None, shift=True):
    """Fused chunked LM-head + next-token cross-entropy.

    The naive head materializes ``[B, T, vocab]`` f32 logits (≈ 4·B·T·V
    bytes of HBM traffic both ways, plus an f32 matmul off the MXU's fast
    path). This computes the head matmul per token-chunk in ``compute_dtype``
    with f32 MXU accumulation and reduces each chunk to its scalar loss
    immediately. Under differentiation the same loop also makes the
    gradient while a chunk's logits are there (a ``jax.custom_vjp`` whose
    forward rule forms ``softmax - onehot``, scaled by the row's weight
    over the row count and cast once to ``compute_dtype``, and multiplies
    it out: three matmuls a chunk, the table's gradient summed over the
    chunks in float32), so the backward pass only scales what the forward
    kept by the loss's cotangent. The full logits tensor never exists in
    HBM, and no chunk is computed twice. Equivalent to
    ``lm_loss(emb.attend(hidden), tokens)`` up to compute-dtype rounding;
    pair with ``TransformerLM(return_hidden=True)``.

    Args:
      hidden: final post-LN hidden states ``[B, T, D]``.
      emb_table: tied embedding table ``[vocab, D]`` (f32 master copy).
      tokens: integer tokens ``[B, T]``.
      n_chunks: token-dimension split; ``B*(T-1)`` need not divide evenly —
        the tail partial chunk is padded and masked out.
      weights: optional float32 ``[B, T-1]``, one for each predicted
        position (``weights[b, t]`` for ``tokens[b, t+1]``): the result is
        ``sum(weights * cross_entropy) / (B*(T-1))`` and is differentiable
        in the weights, each row's cross-entropy being its gradient
        (:func:`lm_loss_looped`'s exit probabilities). ``None`` is all
        ones: the mean.
      shift: ``False`` takes the row at position ``t`` as the prediction
        of ``tokens[b, t]`` itself (a denoiser's: :func:`lm_loss_block_
        diffusion`): all ``B*T`` rows count, and ``weights`` is
        ``[B, T]``.
    """
    with jax.named_scope(train_path.LM_HEAD):
        return _lm_loss_fused(hidden, emb_table, tokens, n_chunks,
                              compute_dtype, weights, shift)


def _publish_head_grad_in_forward(engaged: bool):
    """Gauge :data:`train_path.LM_HEAD_GRAD_IN_FORWARD`, set while the
    caller's program is traced."""
    from chainermn_tpu.observability.metrics import registry

    registry().gauge(
        train_path.LM_HEAD_GRAD_IN_FORWARD,
        "1 where the fused head traced last made its gradient in its "
        "forward loop (it was differentiated), 0 where it made the loss "
        "alone",
    ).set(float(engaged))


def _head_chunk(hc, table, tc):
    """One chunk of the fused head: float32 logits ``[rows, vocab]`` of
    ``hc @ table.T``, each row's log-sum-exp and its cross-entropy."""
    logits = jax.lax.dot_general(
        hc, table, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
    return logits, lse, lse - gold


def _lm_loss_fused(hidden, emb_table, tokens, n_chunks, compute_dtype,
                   weights, shift=True):
    B, T, D = hidden.shape
    h = (hidden[:, :-1] if shift else hidden).reshape(-1, D)
    t = (tokens[:, 1:] if shift else tokens).reshape(-1)
    n = h.shape[0]
    chunk = -(-n // n_chunks)  # ceil
    pad = chunk * n_chunks - n
    h = jnp.pad(h, ((0, pad), (0, 0)))
    t = jnp.pad(t, (0, pad))
    valid = jnp.pad(
        jnp.ones((n,), jnp.float32) if weights is None
        else weights.astype(jnp.float32).reshape(n), (0, pad))
    h_dtype, table_dtype = hidden.dtype, emb_table.dtype

    # hs [chunks, rows, D], table [vocab, D], ts and ms [chunks, rows]
    @jax.custom_vjp
    def head(hs, table, ts, ms):
        _publish_head_grad_in_forward(False)
        w = table.astype(compute_dtype)

        def body(total, xs):
            hc, tc, mc = xs
            _, _, ce = _head_chunk(hc.astype(compute_dtype), w, tc)
            return total + jnp.sum(ce * mc), ()

        total, _ = jax.lax.scan(body, jnp.float32(0.0), (hs, ts, ms))
        return total / n

    def head_fwd(hs, table, ts, ms):
        _publish_head_grad_in_forward(True)
        w = table.astype(compute_dtype)

        def body(carry, xs):
            total, dw = carry
            hc, tc, mc = xs
            hc = hc.astype(compute_dtype)
            logits, lse, ce = _head_chunk(hc, w, tc)
            hit = jax.nn.one_hot(tc, logits.shape[-1], dtype=jnp.float32)
            # the loss's gradient in this chunk's logits at cotangent 1,
            # formed in float32 and rounded once for the two matmuls
            dl = ((jnp.exp(logits - lse[:, None]) - hit)
                  * (mc / n)[:, None]).astype(compute_dtype)
            dh = jnp.dot(dl, w, preferred_element_type=jnp.float32)
            dw = dw + jax.lax.dot_general(
                dl, hc, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            # d hidden leaves the chunk in the dtype it is handed back in
            # (bf16 states: half the bytes kept until the backward)
            return ((total + jnp.sum(ce * mc), dw),
                    (dh.astype(h_dtype), ce))

        (total, dw), (dh, ce) = jax.lax.scan(
            body, (jnp.float32(0.0), jnp.zeros(table.shape, jnp.float32)),
            (hs, ts, ms))
        return total / n, (dh, dw, ce)

    def head_bwd(residuals, g):
        dh, dw, ce = residuals
        return ((g * dh).astype(h_dtype), (g * dw).astype(table_dtype),
                None, g * ce / n)

    head.defvjp(head_fwd, head_bwd)
    return head(h.reshape(n_chunks, chunk, D), emb_table,
                t.reshape(n_chunks, chunk), valid.reshape(n_chunks, chunk))


def lm_loss_moe(model: "TransformerLM", params, tokens, *, n_chunks=8,
                load_balance_coef=0.01, z_loss_coef=0.001,
                seq_aux_coef=0.0, router_state=None):
    """Loss of a model whose description has experts (build it with
    ``return_hidden=True``): next-token cross-entropy through the fused
    head plus the router's two auxiliary losses, each the mean over the
    layers that have experts: ``load_balance_coef`` x the load-balancing
    loss and ``z_loss_coef`` x the router z-loss
    (:func:`chainermn_tpu.parallel.moe.dropless_aux`). Both are a softmax
    router's: a sigmoid router has neither, and its coefficients must be
    0. ``seq_aux_coef`` x the per-sequence balance loss
    (:func:`sequence_balance_loss`, the mean over those layers) where the
    description's ``seq_aux`` has the router sow it. ``router_state`` is
    the :data:`ROUTER_STATE` collection of a router
    with a selection bias.

    Returns ``(loss, metrics)`` as :func:`~chainermn_tpu.training.
    make_train_step` takes it: ``moe/load_balance``, ``moe/z_loss`` (a
    softmax router's), ``moe/expert_load_max_over_mean`` (the busiest held
    expert's rows over the mean, all layers together), ``moe/rows_held``
    (the rows whose expert this program holds, summed over the layers:
    all ``tokens * k`` a layer unless it holds a share),
    ``moe/seq_aux`` (with the description's ``seq_aux``),
    ``moe/tail_tiles`` (the expert section's row tiles behind the last held
    group, summed over the layers, which the grouped matmuls write as
    zeros without multiplying: 0 where every expert is held and the rows
    fill their tiles; under a share counted within the rounds run),
    ``moe/rounds`` (rounds of the expert section run, summed over the
    layers: a layer that holds a share runs its expert-sorted rows in
    rounds of a static bound, :func:`chainermn_tpu.parallel.moe.
    experts_in_rounds`, ``ceil(rows_held / bound)`` of them, so the sum is
    the number of expert layers while every layer's held rows fit the
    bound, and always where every expert is held), ``moe/dropped``
    (rows routed to a held expert that lie in no expert's group, counted
    from each layer's group sizes: 0 while the dropless path keeps its
    word) and the vector ``moe/expert_load`` (rows a held expert received,
    summed over the layers), which ``Trainer`` hands to
    ``record_moe_dispatch``."""
    variables = {"params": params}
    if router_state is not None:
        variables[ROUTER_STATE] = router_state
    hidden, sown = model.apply(variables, tokens, mutable=[MOE_AUX])
    ce = lm_loss_fused(hidden, head_table(params, model.arch), tokens,
                       n_chunks=n_chunks, compute_dtype=model.compute_dtype)
    return _with_router_aux(ce, model, sown, load_balance_coef, z_loss_coef,
                            seq_aux_coef)


def sequence_balance_loss(logits, experts):
    """DeepSeek-V2's balance loss in its per-sequence form (``seq_aux``):
    with ``p = softmax(logits [B, T, E])`` and ``experts [B, T, k]`` a
    token's choice, ``f_{b,e} = E / (k T) x`` the tokens of sequence ``b``
    that chose ``e``, ``P_{b,e}`` the mean of ``p_e`` over ``b``'s tokens,
    and the loss ``mean_b sum_e f_{b,e} P_{b,e}``; differentiable through
    ``P``, not through the choice."""
    B, T, E = logits.shape
    k = experts.shape[-1]
    chose = jax.nn.one_hot(experts, E, dtype=jnp.float32).sum(2)
    f = chose.sum(1) * (E / (k * T))
    mean_p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).mean(1)
    return (f * mean_p).sum(-1).mean()


def _with_router_aux(loss, model: "TransformerLM", sown, load_balance_coef,
                     z_loss_coef, seq_aux_coef=0.0):
    """``(loss + the router's auxiliary losses, metrics)`` from what the
    expert layers of one ``apply`` sowed into :data:`MOE_AUX`
    (:func:`lm_loss_moe` says which metrics)."""
    # one entry a block that has experts, each a 1-tuple (sow appends)
    layers = [{k: v[0] for k, v in sown[MOE_AUX][f"block_{i}"].items()}
              for i in model.expert_layers]

    def over_layers(name, reduce):
        return reduce(jnp.stack([layer[name] for layer in layers]), axis=0)

    load = over_layers("expert_load", jnp.sum)
    metrics = {
        "moe/expert_load_max_over_mean": load.max() / load.mean(),
        "moe/rows_held": over_layers("rows_held", jnp.sum),
        train_path.MOE_TAIL_TILES: over_layers("tail_tiles", jnp.sum),
        train_path.MOE_ROUNDS: over_layers("rounds", jnp.sum),
        "moe/dropped": over_layers("dropped", jnp.sum),
        "moe/expert_load": load,
    }
    for name, coef in (("load_balance", load_balance_coef),
                       ("z_loss", z_loss_coef),
                       ("seq_aux", seq_aux_coef)):
        if name in layers[0]:
            metrics["moe/" + name] = over_layers(name, jnp.mean)
            loss = loss + coef * metrics["moe/" + name]
        elif coef:
            raise ValueError(
                f"{name} is an auxiliary loss this model's router does "
                f"not sow (a softmax router's, or the description's "
                f"seq_aux; coefficient {coef})")
    return loss, metrics


def diffusion_noise_state(seed: int) -> dict:
    """The ``model_state`` a block-diffusion train step carries so that
    every step draws its own noise inside the compiled step: the seed as
    two 16-bit halves and the count of draws made, all float32, because a
    train step averages ``model_state`` over its shards (``lax.pmean``)
    and an average of equal small integers is exact in float32 alone."""
    seed = int(seed) & 0xFFFFFFFF
    return {"seed": jnp.array([seed >> 16, seed & 0xFFFF], jnp.float32),
            "draw": jnp.float32(0.0)}


def diffusion_noise_key(state: dict):
    """``(key, next state)``: the key of this step's draw, and the state
    with one more draw counted."""
    hi, lo = (state["seed"][i].astype(jnp.uint32) for i in (0, 1))
    key = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(0), (hi << 16) | lo),
        state["draw"].astype(jnp.uint32))
    return key, {**state, "draw": state["draw"] + 1.0}


def block_diffusion_noise(key, shape, *, block_length: int,
                          t_min: float = 0.05):
    """The noise of one training pass over ``shape = (B, L)`` tokens
    (BD3-LMs' linear schedule): a level ``t ~ U[t_min, 1]`` a block of
    ``block_length`` positions and a mask ``m_i ~ Bernoulli(t_b(i))`` a
    token. Returns ``(masked [B, L] bool, t [B, L // block_length]
    float32)``."""
    B, L = shape
    k_t, k_m = jax.random.split(key)
    t = jax.random.uniform(k_t, (B, L // block_length), jnp.float32,
                           t_min, 1.0)
    masked = jax.random.uniform(k_m, (B, L), jnp.float32) \
        < jnp.repeat(t, block_length, axis=1)
    return masked, t


def block_diffusion_rows(tokens, masked, t, *, mask_id: int):
    """What one training pass puts through the stack and weighs its loss
    by: ``(rows [B, 2L], positions [2L], weights [B, L])``, the rows ``[x
    ; x~]`` with ``x~_i = mask_id`` where ``masked`` else ``x_i``, the
    noised copy at the clean copy's positions, and the loss's weights
    ``m_i / t_b(i)``."""
    L = tokens.shape[1]
    noised = jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens)
    weights = masked.astype(jnp.float32) \
        / jnp.repeat(t, L // t.shape[1], axis=1)
    return (jnp.concatenate([tokens, noised], axis=1),
            jnp.tile(jnp.arange(L, dtype=jnp.int32), 2), weights)


def lm_loss_block_diffusion(model: "TransformerLM", params, tokens, key=None,
                            *, mask_id: int, t_min: float = 0.05,
                            n_chunks=8, load_balance_coef=0.001,
                            noise=None):
    """Loss of a block-diffusion model (``arch.diffusion_block``; build it
    with ``return_hidden=True``) on clean ``tokens [B, L]`` (BD3-LMs,
    arXiv:2503.09573, which SDAR adopts): with ``bl`` the block length and
    ``b(i) = i // bl``, a level ``t_b ~ U[t_min, 1]`` a block and a mask
    ``m_i ~ Bernoulli(t_b(i))`` a token (:func:`block_diffusion_noise`
    from ``key``, drawn here, inside the caller's program; or ``noise =
    (masked, t)`` as data), ``x~_i = mask_id`` where ``m_i`` else ``x_i``,
    one forward over ``[x ; x~]`` under the mask by blocks, and

        loss = 1 / (B L) sum_i m_i / t_b(i) CE(logits(noised row i), x_i)

    the noised row at position ``i`` predicting token ``i`` itself (no
    shift), through the fused head over the ``L`` noised rows alone, plus
    ``load_balance_coef`` x the router's load-balancing loss, the mean
    over the layers, each layer's over the ``2L`` rows it routes.

    Returns ``(loss, metrics)``: :func:`lm_loss_moe`'s ``moe/`` metrics,
    ``bd/masked_share`` (the mean of ``m``) and ``bd/mean_weight`` (the
    mean of ``m / t``, 1 in expectation)."""
    from chainermn_tpu.observability.metrics import registry

    bl = model.arch.diffusion_block if model.arch else 0
    B, L = tokens.shape
    if not bl or L % bl:
        raise ValueError(
            f"lm_loss_block_diffusion needs a block-diffusion model and "
            f"whole blocks: block length {bl}, {L} tokens a row")
    if (key is None) == (noise is None):
        raise ValueError("give the key the noise is drawn from, or the "
                         "noise itself (masked, t): one of the two")
    registry().gauge(
        train_path.BD_ROWS_PER_STEP,
        "rows a block-diffusion step puts through the stack (the clean "
        "and the noised copy of every token), at the last loss traced",
    ).set(float(2 * B * L))
    with jax.named_scope(train_path.BD_NOISE):
        masked, t = noise if noise is not None else block_diffusion_noise(
            key, (B, L), block_length=bl, t_min=t_min)
        rows, positions, weights = block_diffusion_rows(
            tokens, masked, t, mask_id=mask_id)
    hidden, sown = model.apply({"params": params}, rows,
                               positions=positions, mutable=[MOE_AUX])
    ce = lm_loss_fused(hidden[:, L:], head_table(params, model.arch), tokens,
                       n_chunks=n_chunks, compute_dtype=model.compute_dtype,
                       weights=weights, shift=False)
    loss, metrics = _with_router_aux(ce, model, sown, load_balance_coef, 0.0)
    metrics["bd/masked_share"] = masked.mean(dtype=jnp.float32)
    metrics["bd/mean_weight"] = weights.mean()
    return loss, metrics


def lm_loss_looped(model: "TransformerLM", params, tokens, *, n_chunks=8,
                   beta=0.1):
    """Loss of a looped model with an exit gate (build it with
    ``return_hidden=True``): the expected next-token cross-entropy over
    the exits less ``beta`` x the entropy of the exit distribution, a
    token at a time, then the mean over tokens (Ouro's first-stage
    objective, arXiv:2510.25741). With ``lambda^t = sigmoid(gate(h^t))``
    the exit distribution is ``p^t = lambda^t prod_{j<t}(1 - lambda^j)``
    for ``t < R`` and ``p^R = prod_{j<R}(1 - lambda^j)``; it and the
    entropy are built in float32 from log-sigmoids. The ``R`` exits go
    through ONE fused-head call as ``R*B*(T-1)`` rows weighted by ``p``,
    so no logits tensor exists and the gate's gradient comes out of the
    head's per-row cross-entropies.

    Returns ``(loss, metrics)`` as :func:`~chainermn_tpu.training.
    make_train_step` takes it: ``loop/exit_mass_<t>`` (the mean of
    ``p^t``, ``t`` from 1), ``loop/exit_entropy`` and
    ``loop/expected_pass`` (the mean of ``sum_t t p^t``)."""
    hidden, gate_logits = model.apply({"params": params}, tokens)
    if gate_logits is None:
        raise ValueError("lm_loss_looped needs the description's exit gate")
    R, B, T, D = hidden.shape
    with jax.named_scope(train_path.EXIT_GATE):
        g = gate_logits[:-1, :, :-1]  # the last pass takes what is left
        zero = jnp.zeros_like(gate_logits[:1, :, :-1])
        # log of what no earlier pass took, plus log of this pass's share
        left = jnp.concatenate(
            [zero, jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)])
        log_p = left + jnp.concatenate([jax.nn.log_sigmoid(g), zero])
        p = jnp.exp(log_p)  # [R, B, T-1]
        entropy = -(p * log_p).sum(0).mean()
        mass = p.mean((1, 2))
    # R * the mean over the R*B*(T-1) weighted rows: the mean over tokens
    # of the expected cross-entropy
    ce = R * lm_loss_fused(
        hidden.reshape(R * B, T, D), head_table(params, model.arch),
        jnp.tile(tokens, (R, 1)), n_chunks=n_chunks,
        compute_dtype=model.compute_dtype, weights=p.reshape(R * B, T - 1))
    metrics = {f"loop/exit_mass_{t + 1}": mass[t] for t in range(R)}
    metrics["loop/exit_entropy"] = entropy
    metrics["loop/expected_pass"] = jnp.sum(
        mass * jnp.arange(1, R + 1, dtype=mass.dtype))
    return ce - beta * entropy, metrics


def init_cache(model: TransformerLM, params, batch_size: int):
    """Allocate the fixed-shape KV cache for ``generate`` (one
    ``[B, max_len, kv_heads, head_dim]`` key+value pair per block, plus a
    scalar write index). Pure shape evaluation — no FLOPs run."""
    dummy = jnp.zeros((batch_size, 1), jnp.int32)
    variables = jax.eval_shape(
        lambda: model.apply(
            params, dummy,
            positions=jnp.zeros((1,), jnp.int32),
            train=False, decode=True, mutable=["cache"],
        )[1]
    )
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), variables)


def _decode_setup(model: TransformerLM, params, prompt, n_steps, pad_id):
    """Shared ``generate``/``beam_search`` scaffolding: validation,
    per-row true prompt lengths, and the prompt padded out to the decode
    horizon."""
    if model.return_hidden:
        raise ValueError("decoding needs logits; build the model with "
                         "return_hidden=False")
    if n_steps > model.max_len:
        raise ValueError(
            f"n_steps={n_steps} exceeds the cache capacity "
            f"max_len={model.max_len}"
        )
    B, P = prompt.shape
    # True length = index of the FIRST pad (rows without pad span all of
    # P): the right-padding convention. Tokens after a mid-row pad_id are
    # ignored — counting non-pad tokens instead would silently misalign
    # teacher forcing for such rows, which is worse than truncating.
    is_pad = prompt == pad_id
    prompt_len = jnp.where(
        jnp.any(is_pad, axis=1),
        jnp.argmax(is_pad, axis=1).astype(jnp.int32),
        jnp.int32(P),
    )
    padded = jnp.pad(prompt, ((0, 0), (0, max(0, n_steps - P))),
                     constant_values=pad_id)
    return B, P, prompt_len, padded


def _filter_logits(logits, top_k, top_p):
    """Top-k / nucleus filtering on ``[B, V]`` logits: tokens outside the
    k highest (and outside the smallest set whose probability mass
    reaches ``top_p``) are masked to -inf. Static shapes throughout —
    the nucleus cut uses a sorted cumulative sum, no dynamic slicing."""
    if top_p is None:
        if top_k is not None:
            kth = jax.lax.top_k(logits, top_k)[0][:, -1:]  # k-th largest
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
    if top_k is not None:
        # Reuse the descending sort for the k-th threshold — no second
        # vocab-sized pass — and restrict the nucleus mass to the top-k
        # survivors (HF semantics: top_p renormalizes AFTER top_k).
        kth = sorted_logits[:, top_k - 1:top_k]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
        sorted_logits = jnp.where(
            jnp.arange(sorted_logits.shape[-1])[None] < top_k,
            sorted_logits, -jnp.inf,
        )
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # Keep tokens while the mass BEFORE them is < top_p (the first
    # token is always kept).
    keep_sorted = jnp.concatenate(
        [jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < top_p], axis=-1
    )
    # Threshold = smallest kept logit per row.
    thresh = jnp.min(
        jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1,
        keepdims=True,
    )
    return jnp.where(logits < thresh, -jnp.inf, logits)


def _tempered_filtered(logits, temperature, top_k, top_p):
    """Sampling logits: temperature FIRST, then top-k/top-p (the HF
    convention — the nucleus is selected from the temperature-adjusted
    distribution, so top_p values tuned elsewhere transfer; under
    filter-then-temperature the survivor set would be temperature
    -independent)."""
    return _filter_logits(logits / temperature, top_k, top_p)


def stream_sample_keys(base_key, seeds, counters):
    """Counter-based sampling keys (docs/serving.md "Sampling"): row ``i``
    draws with ``fold_in(fold_in(base_key, seeds[i]), counters[i])``.

    The key for a sampled token is a PURE function of (base key, request
    seed, absolute stream position) — there is no consumed split chain, so
    it does not depend on which program asks: monolithic ``generate``, the
    serving engine's decode/verify/mixed grids, a chunked or
    sequence-parallel prefill, or a resumed stream on another replica all
    derive the identical key for position ``i`` of request ``seeds[i]``.
    That invariance is what extends the bit-identical-stream guarantee to
    ``temperature > 0``: any schedule that reaches position ``i`` with the
    same history sees the same logits AND the same key, hence the same
    token. ``counters[i]`` is the absolute position of the token being
    SAMPLED (the first generated token of a length-P prompt has counter
    P). Threefry is batch-invariant, so per-row keys drawn here match
    per-request individual calls exactly.
    """
    def one(seed, counter):
        return jax.random.fold_in(jax.random.fold_in(base_key, seed), counter)

    return jax.vmap(one)(jnp.asarray(seeds), jnp.asarray(counters))


def generate(model: TransformerLM, params, prompt, n_steps: int, *,
             temperature: float = 0.0, rng=None, seeds=None, pad_id: int = 0,
             top_k: Optional[int] = None, top_p: Optional[float] = None,
             adapters=None):
    """Autoregressive generation with a per-block KV cache.

    TPU-first shape discipline: ONE jitted ``lax.scan`` of single-token
    decode steps covers both prefill and sampling — step ``t`` feeds the
    prompt token while ``t < prompt_len`` (teacher forcing) and the
    previous step's sampled token afterwards, so there is exactly one
    compiled program regardless of prompt length (no per-length
    recompiles; a ragged batch of prompts just pads with ``pad_id`` and
    per-row lengths). The cache is written in the same pass the prompt is
    consumed, so no separate prefill program is needed.

    Args:
      model: a ``TransformerLM`` (``return_hidden`` must be False).
      params: the ``{'params': ...}`` variables from ``init``/training.
      prompt: ``[B, P]`` int32 prompt tokens, right-padded with ``pad_id``.
      n_steps: total sequence length to produce INCLUDING the prompt
        (``<= model.max_len``).
      temperature: 0 → greedy argmax; otherwise softmax sampling at this
        temperature (requires ``rng``).
      rng: PRNG BASE key for sampling (ignored when greedy). Keys are
        derived per token by :func:`stream_sample_keys` — position ``t``
        of row ``i`` draws with ``fold_in(fold_in(rng, seeds[i]), t)`` —
        not by a consumed split chain, so generation at a fixed
        ``(rng, seeds)`` is bit-identical to the serving engine's
        chunked / sequence-parallel / speculative schedules over the
        same requests.
      seeds: ``[B]`` int32 per-row stream seeds (default all zeros).
        The serving scheduler derives one per request
        (``crc32(request_id)``); pass the same values here to reproduce
        a served stream exactly.
      top_k: sample only among the k highest-probability tokens.
      top_p: nucleus sampling — restrict to the smallest token set whose
        probability mass reaches ``top_p``. Composes with ``top_k``
        (intersection) and is computed AFTER the temperature division
        (the HF convention, so tuned values transfer). Both require
        ``temperature > 0``.
      pad_id: padding token in ``prompt``; positions where every shorter
        row has run out of prompt switch to model continuations.
      adapters: optional per-layer low-rank deltas (ISSUE 14) — the
        unbatched ``(A, B)`` form shared by every row; the single-
        tenant reference the serving engine's per-slot gather is pinned
        against (``AdapterBank.adapter_arrays`` hands out exactly the
        values the engine gathers, scale pre-folded).

    Returns:
      ``[B, n_steps]`` int32 tokens (prompt positions pass through).
    """
    B, P, prompt_len, padded_prompt = _decode_setup(
        model, params, prompt, n_steps, pad_id
    )
    cache = init_cache(model, params, B)["cache"]
    if temperature > 0.0 and rng is None:
        raise ValueError("sampling (temperature > 0) requires rng")
    if (top_k is not None or top_p is not None) and temperature <= 0.0:
        raise ValueError("top_k/top_p filtering is for sampling — set "
                         "temperature > 0")
    if top_p is not None and not (0.0 < top_p <= 1.0):
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k is not None and not (1 <= top_k <= model.vocab_size):
        raise ValueError(
            f"top_k must be in [1, vocab_size={model.vocab_size}], "
            f"got {top_k}"
        )
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    seeds = (jnp.zeros((B,), jnp.int32) if seeds is None
             else jnp.asarray(seeds, jnp.int32))

    def step(carry, t):
        cache, prev_tok = carry
        # Teacher-force while this row still has prompt left.
        in_prompt = t < prompt_len  # [B]
        tok = jnp.where(in_prompt, padded_prompt[:, t], prev_tok)
        logits, mutated = model.apply(
            {**params, "cache": cache}, tok[:, None],
            positions=jnp.full((1,), t, jnp.int32),
            train=False, decode=True, mutable=["cache"],
            adapters=adapters,
        )
        logits = logits[:, 0]  # [B, vocab]
        if temperature > 0.0:
            # Step t samples the token for position t+1: counter t+1.
            # No key threads through the carry — each position's key is
            # derived fresh, so discarded draws (teacher-forced rows)
            # never perturb later positions.
            keys = stream_sample_keys(
                rng, seeds, jnp.full((B,), t + 1, jnp.int32))
            nxt = jax.vmap(jax.random.categorical)(
                keys, _tempered_filtered(logits, temperature, top_k, top_p),
            )
        else:
            nxt = jnp.argmax(logits, axis=-1)
        return (mutated["cache"], nxt.astype(prompt.dtype)), tok

    _, toks = jax.lax.scan(
        step, (cache, padded_prompt[:, 0]),
        jnp.arange(n_steps, dtype=jnp.int32),
    )
    # ``toks[t]`` is the token CONSUMED at position t, which is already
    # the desired output there: the prompt token while t < prompt_len,
    # and otherwise prev_tok — i.e. the model's sample from step t-1,
    # its continuation for position t.
    return jnp.moveaxis(toks, 0, 1)  # [B, n_steps]


def beam_search(model: TransformerLM, params, prompt, n_steps: int,
                beam_size: int, *, eos_id: Optional[int] = None,
                pad_id: int = 0, length_penalty: float = 0.0):
    """Beam-search decoding over the KV cache — ONE jitted ``lax.scan``.

    Same shape discipline as :func:`generate`: prompt consumption and
    beam expansion share the scan (prompt steps force every beam onto the
    prompt token with scores pinned to ``[0, -inf, ...]``, so the first
    free step expands from a single live beam), and the per-block caches
    are batched ``B·beam`` and REORDERED by backpointer gather at every
    step — no post-hoc hypothesis reconstruction pass.

    Args:
      model: ``TransformerLM`` with ``return_hidden=False``.
      params: ``{'params': ...}`` variables.
      prompt: ``[B, P]`` int32, right-padded with ``pad_id`` (ragged rows
        expand beams from their own true length).
      n_steps: total length INCLUDING the prompt (``<= model.max_len``).
      beam_size: hypotheses kept per row.
      eos_id: optional end token: finished beams are frozen (they extend
        only with ``pad_id`` at no score change).
      length_penalty: GNMT alpha — hypotheses are RANKED by
        ``score / ((5 + len) / 6)**alpha`` (len = generated tokens up to
        and including EOS): positive counters the short-hypothesis bias
        of raw summed log-probs, negative favours shorter hypotheses,
        0 ranks by raw score. The returned ``scores`` stay raw either
        way.

    Returns:
      ``(tokens, scores)``: ``[B, beam, n_steps]`` int32 hypotheses
      (best-first under the chosen ranking) and their ``[B, beam]`` raw
      summed log-probabilities.
    """
    if beam_size < 1:
        raise ValueError(f"beam_size must be >= 1, got {beam_size}")
    B, P, prompt_len, padded = _decode_setup(
        model, params, prompt, n_steps, pad_id
    )
    K = beam_size
    V = model.vocab_size

    cache = init_cache(model, params, B * K)["cache"]
    scores0 = jnp.tile(
        jnp.array([0.0] + [-jnp.inf] * (K - 1), jnp.float32), (B, 1)
    )
    seqs0 = jnp.full((B, K, n_steps), pad_id, prompt.dtype)

    def reorder(tree, parents):
        """Gather the beam dimension of ``[B·K, ...]`` cache leaves by
        the ``[B, K]`` backpointers."""
        def one(leaf):
            if leaf.ndim == 0:  # shared cache_index scalar
                return leaf
            shaped = leaf.reshape(B, K, *leaf.shape[1:])
            idx = parents.reshape(B, K, *([1] * (leaf.ndim - 1)))
            return jnp.take_along_axis(shaped, idx, axis=1).reshape(
                leaf.shape
            )
        return jax.tree.map(one, tree)

    def step(carry, t):
        cache, prev_tok, scores, seqs, finished, gen_len = carry
        # Two per-row phases, offset by one: the token CONSUMED at t is
        # prompt-forced while t < prompt_len, but the EXPANSION chosen at
        # t is consumed at t+1 — so beam search activates one step early,
        # at the LAST prompt step (t == prompt_len - 1), where the top-K
        # first tokens and their scores spread from the single live beam.
        in_prompt = (t < prompt_len)[:, None]  # [B, 1] consumption phase
        # Beam phase: the expansion chosen at t is consumed at t+1, so it
        # activates one step before the prompt ends AND must NOT commit on
        # the final step (that choice would never be consumed — scoring or
        # reordering by it would corrupt the returned hypotheses).
        expanding = (
            (t >= prompt_len - 1)[:, None] & (t < n_steps - 1)
        )  # [B, 1]
        tok = jnp.where(in_prompt, padded[:, t][:, None], prev_tok)

        logits, mutated = model.apply(
            {**params, "cache": cache}, tok.reshape(B * K, 1),
            positions=jnp.full((1,), t, jnp.int32),
            train=False, decode=True, mutable=["cache"],
        )
        logp = jax.nn.log_softmax(
            logits[:, 0].astype(jnp.float32)
        ).reshape(B, K, V)

        # Frozen (finished) beams may only extend with pad at no cost.
        if eos_id is not None:
            frozen = jnp.full((V,), -jnp.inf).at[pad_id].set(0.0)
            logp = jnp.where(finished[..., None], frozen[None, None], logp)

        total = scores[..., None] + logp  # [B, K, V]
        top_scores, flat_idx = jax.lax.top_k(total.reshape(B, K * V), K)
        parents = flat_idx // V  # [B, K]
        next_tok = (flat_idx % V).astype(prompt.dtype)

        # Pre-expansion prompt steps: identity beams, pinned scores (the
        # chosen next_tok is irrelevant — consumption stays forced).
        ident = jnp.broadcast_to(jnp.arange(K, dtype=parents.dtype), (B, K))
        parents = jnp.where(expanding, parents, ident)
        new_scores = jnp.where(expanding, top_scores, scores)

        # The identity gather of prefill steps is not free (parents is
        # traced — XLA cannot fold it): skip the whole-cache copy until
        # some row actually expands.
        cache = jax.lax.cond(
            jnp.any(expanding),
            lambda c: reorder(c, parents),
            lambda c: c,
            mutated["cache"],
        )
        seqs = jnp.take_along_axis(seqs, parents[..., None], axis=1)
        # Position t records the token CONSUMED at t by this slot's
        # PARENT lineage (gather tok by backpointer — in prompt steps the
        # token is row-uniform so the gather is a no-op).
        seqs = seqs.at[:, :, t].set(
            jnp.take_along_axis(tok, parents, axis=1)
        )
        # Generated-token count per surviving lineage (for the length
        # penalty): a committed expansion by an unfinished beam adds one.
        gen_len = jnp.take_along_axis(gen_len, parents, axis=1)
        if eos_id is not None:
            finished = jnp.take_along_axis(finished, parents, axis=1)
        gen_len = gen_len + (expanding & ~finished).astype(jnp.int32)
        if eos_id is not None:
            finished = finished | (expanding & (next_tok == eos_id))
        return ((cache, next_tok, new_scores, seqs, finished, gen_len),
                None)

    finished0 = jnp.zeros((B, K), bool)
    (cache, last, scores, seqs, finished, gen_len), _ = jax.lax.scan(
        step,
        (cache, jnp.broadcast_to(padded[:, 0][:, None], (B, K)),
         scores0, seqs0, finished0, jnp.zeros((B, K), jnp.int32)),
        jnp.arange(n_steps, dtype=jnp.int32),
    )
    if length_penalty != 0.0:
        from chainermn_tpu.models._decode_common import rank_beams

        return rank_beams(seqs, scores, gen_len, length_penalty)
    order = jnp.argsort(-scores, axis=1)
    return (jnp.take_along_axis(seqs, order[..., None], axis=1),
            jnp.take_along_axis(scores, order, axis=1))
