"""Operations and bytes of a latent-attention mixture-of-experts stack's
layers (DeepSeek-V2's) that ``costs.py``, ``moe_costs.py`` and
``hybrid_costs.py`` have no function for, from their published shapes
(``costs.py``'s rule: a program may change how it computes, never what
these return; values zero-padded to the keys' width, or a rope key copied
to every head, are the program's and are not counted)."""

from __future__ import annotations


def mla_attention_train_cost(batch: int, heads: int, seq_len: int,
                             qk_nope: int, qk_rope: int, v_width: int,
                             bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's causal attention between latent
    attention's projections, forward and backward, with keys and queries
    of ``qk_nope + qk_rope`` values a head and values of ``v_width``.
    Half the square under the causal mask, 2 flops a multiply-add.
    Forward: ``QK^T`` at the keys' width and ``PV`` at the values':
    ``2 (qk + v)`` a pair and head. Backward: ``costs.causal_attention_
    train_cost``'s five matmuls, each at its own width: the scores again,
    ``dQ`` and ``dK`` at the keys', ``dV`` and ``dP`` at the values':
    ``2 (3 qk + 2 v)`` (2.6 times the forward at 192 / 128, not 2.5).
    Bytes are the least HBM traffic: the forward reads Q, K, V and writes
    O; the backward reads Q, K, V, O, dO and writes dQ, dK, dV; a head's
    Q and K-without-position tensors at their widths, V, O and dO at the
    values', and the rotated key and its gradient once for all heads,
    which share it."""
    qk = qk_nope + qk_rope
    pairs = batch * heads * seq_len * seq_len / 2.0
    flops = 2.0 * pairs * ((qk + v_width) + (3 * qk + 2 * v_width))
    rows = batch * seq_len * bytes_per_el
    q = rows * heads * qk            # Q, dQ
    k = rows * (heads * qk_nope + qk_rope)  # K, dK
    v = rows * heads * v_width       # V, O, dO, dV
    return flops, 3.0 * q + 3.0 * k + 6.0 * v


def latent_moe_lm_active_params(
        d_model: int, heads: int, qk_nope: int, qk_rope: int, v_width: int,
        latent_rank: int, layers: int, dense_layers: int, dense_width: int,
        router_width: int, experts_a_token: float, expert_width: int,
        shared_width: int, vocab: int) -> float:
    """Parameters a token's forward pass multiplies by on this chip:
    latent attention's four projections in every layer (queries, the
    down-projection to the latent and the rope key, the up-projection to
    keys and values, the output; the latent's norm is no matmul), a dense
    gated feed-forward's three matrices in the leading layers, and in the
    others the router, the shared expert's three and ``experts_a_token``
    routed experts of three (under a share: the experts of a token's
    choice that this chip holds, in expectation); the untied head (the
    lookup is no matmul)."""
    attn = d_model * heads * (qk_nope + qk_rope) \
        + d_model * (latent_rank + qk_rope) \
        + latent_rank * heads * (qk_nope + v_width) \
        + heads * v_width * d_model
    dense = 3 * d_model * dense_width
    experts = d_model * router_width + 3 * d_model * shared_width \
        + experts_a_token * 3 * d_model * expert_width
    return (layers * attn + dense_layers * dense
            + (layers - dense_layers) * experts + d_model * vocab)


def latent_moe_lm_train_flops_per_token(active_params: float, layers: int,
                                        heads: int, seq_len: int,
                                        qk_nope: int, qk_rope: int,
                                        v_width: int) -> float:
    """Model FLOPs of one token's forward and backward pass: ``6 N`` for
    the matmul stack and the head, and every layer's attention
    (:func:`mla_attention_train_cost` of one sequence over its tokens);
    recomputation not counted."""
    attention, _ = mla_attention_train_cost(1, heads, seq_len, qk_nope,
                                            qk_rope, v_width)
    return 6.0 * active_params + layers * attention / seq_len
