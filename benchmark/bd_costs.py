"""Operations and bytes of a block-diffusion training pass that
``costs.py`` and ``moe_costs.py`` have no function for, from its shapes
(``costs.py``'s rule: a program may change how it computes, never what
these return). A pass is one forward and backward over a clean and a
noised copy of every sequence, ``[x ; x~]``, ``2L`` rows, under the mask
by blocks of ``bl`` positions (``b(i) = i // bl``): a clean row sees the
clean keys of its own and earlier blocks, a noised row the clean keys of
earlier blocks and the noised keys of its own."""

from __future__ import annotations


def mask_support(seq_len: int, block_length: int) -> int:
    """Allowed (query, key) pairs of one sequence's ``2L x 2L`` square:
    clean on clean ``L (L + bl) / 2``, noised on clean ``L (L - bl) / 2``,
    noised on its own block ``L bl``: ``L^2 + L bl``, a quarter of the
    square."""
    return seq_len * seq_len + seq_len * block_length


def bd_attention_train_cost(batch: int, heads: int, kv_heads: int,
                            seq_len: int, head_dim: int, block_length: int,
                            bytes_per_el: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one layer's attention over ``[x ; x~]``, forward
    and backward, over the mask's support alone. Forward: ``QK^T`` and
    ``PV``, 2 flops a multiply-add, a pair of the support each; backward
    five matmuls of that size: 3.5 x (``costs.causal_attention_train_
    cost``'s rule). Bytes are the least HBM traffic over the ``2L`` rows:
    the forward reads Q, K, V and writes O, the backward reads Q, K, V, O,
    dO and writes dQ, dK, dV: six tensors of the queries' size and six of
    the keys' (``kv_heads`` of them under grouped queries)."""
    fwd = 4.0 * batch * heads * mask_support(seq_len, block_length) \
        * head_dim
    q = batch * 2 * seq_len * heads * head_dim * bytes_per_el
    kv = batch * 2 * seq_len * kv_heads * head_dim * bytes_per_el
    return 3.5 * fwd, 6.0 * (q + kv)


def bd_moe_lm_train_flops_per_token(
        d_model: int, heads: int, kv_heads: int, head_dim: int, layers: int,
        router_width: int, experts_a_token: float, expert_width: int,
        vocab: int, seq_len: int, block_length: int) -> float:
    """Model FLOPs of one *data* token's forward and backward pass, of
    what this chip multiplies it by (6 a parameter a row, as ``costs.
    dense_lm_train_flops_per_token``): the token is two rows through the
    attention projections of every layer and through the router and
    ``experts_a_token`` experts (under a share: those of a row's choice
    that this chip holds, in expectation) of every layer but the last,
    where the clean row's feed-forward, the final norm and the head have
    no output the loss depends on and count once, for the noised row; the
    head over that one row; attention over the mask's support. The
    embedding is a lookup; recomputation is not counted."""
    attn = d_model * (heads + 2 * kv_heads) * head_dim \
        + heads * head_dim * d_model
    ffn = d_model * router_width \
        + experts_a_token * 3 * d_model * expert_width
    matmuls = 6.0 * (2 * layers * attn + (2 * layers - 1) * ffn
                     + d_model * vocab)
    scores, _ = bd_attention_train_cost(1, heads, kv_heads, seq_len,
                                        head_dim, block_length)
    return matmuls + layers * scores / seq_len
