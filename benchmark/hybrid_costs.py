"""Operations and bytes of a hybrid stack's layers that ``costs.py`` and
``moe_costs.py`` have no function for, from their shapes (``costs.py``'s
rule: a program may change how it computes, never what these return)."""

from __future__ import annotations


def short_conv_train_cost(tokens: int, d_model: int, taps: int,
                          bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one gated short convolution between its two
    projections, forward and backward: ``u = B * x``, a depthwise causal
    convolution of ``taps`` taps, ``y = C * conv``. Forward: two gate
    products and a multiply-add a tap, ``2 + 2 taps`` flops an element;
    the backward twice that, as for a matmul. Bytes are the least HBM
    traffic of ``[tokens, d_model]`` tensors: the forward reads the input
    projection's three thirds and writes ``y`` (4); the backward reads
    ``dy`` and the three thirds again and writes their three gradients
    (7). The taps and their gradient are ``taps * d_model`` values and
    left out."""
    flops = 3.0 * (2 + 2 * taps) * tokens * d_model
    return flops, 11.0 * tokens * d_model * bytes_per_el


def gqa_attention_train_cost(batch: int, heads: int, kv_heads: int,
                             seq_len: int, head_dim: int,
                             bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of causal grouped-query attention, forward and
    backward, for one layer. FLOPs as ``costs.causal_attention_train_cost``
    counts them (every query head does its own products). Bytes are the
    least HBM traffic with ``kv_heads`` key-value heads: the forward reads
    Q, K, V and writes O; the backward reads Q, K, V, O, dO and writes dQ,
    dK, dV: six tensors of the queries' size and six of the keys'."""
    fwd = 2.0 * batch * heads * seq_len * seq_len * head_dim
    q = batch * seq_len * heads * head_dim * bytes_per_el
    kv = batch * seq_len * kv_heads * head_dim * bytes_per_el
    return 3.5 * fwd, 6.0 * (q + kv)


def hybrid_lm_active_params(d_model: int, heads: int, kv_heads: int,
                            head_dim: int, conv_layers: int,
                            attention_layers: int, dense_layers: int,
                            dense_width: int, expert_layers: int,
                            router_width: int, experts_a_token: float,
                            expert_width: int, vocab: int) -> float:
    """Parameters a token's forward pass multiplies by on this chip: a
    short-convolution mixer's two projections (``d x 3d`` and ``d x d``;
    its taps are no matmul), an attention layer's four, a dense gated
    feed-forward's three matrices, an expert layer's router and
    ``experts_a_token`` experts of three matrices (under a share: the
    experts of a token's choice that this chip holds, in expectation), and
    the tied head (the lookup is no matmul)."""
    conv = 4 * d_model * d_model
    attn = d_model * (heads + 2 * kv_heads) * head_dim \
        + heads * head_dim * d_model
    dense = 3 * d_model * dense_width
    experts = d_model * router_width \
        + experts_a_token * 3 * d_model * expert_width
    return (conv_layers * conv + attention_layers * attn
            + dense_layers * dense + expert_layers * experts
            + d_model * vocab)
