"""Operations and bytes of a dropless mixture-of-experts layer's grouped
matmuls, from its shapes (``costs.py``'s rule: a program may change how it
computes, never what these return)."""

from __future__ import annotations


def grouped_matmul_train_cost(rows: int, experts: int, k_dim: int,
                              n_dim: int, bytes_per_el: int = 2
                              ) -> tuple[float, float]:
    """(FLOPs, bytes) of ``[rows, k_dim] x [experts, k_dim, n_dim]`` by
    group, forward and both gradients: three products of ``2 rows k n``
    each, whatever the group sizes (nothing is dropped or padded). Bytes
    are the least HBM traffic: each product reads its two operands and
    writes its result once, activations and weights at ``bytes_per_el``,
    the weights' gradient in float32."""
    flops = 3 * 2.0 * rows * k_dim * n_dim
    lhs, out = rows * k_dim * bytes_per_el, rows * n_dim * bytes_per_el
    rhs = experts * k_dim * n_dim
    nbytes = ((lhs + rhs * bytes_per_el + out)        # forward
              + (out + rhs * bytes_per_el + lhs)      # gradient of lhs
              + (lhs + out + rhs * 4))                # gradient of rhs
    return flops, float(nbytes)


def gated_experts_train_cost(rows: int, experts: int, d_model: int,
                             width: int) -> tuple[float, float]:
    """The three grouped matmuls of gated experts (gate, up, down) over
    ``rows`` (token, slot) rows: gate and up as one product of width
    ``2 * width`` (its input is read once), then down."""
    f1, b1 = grouped_matmul_train_cost(rows, experts, d_model, 2 * width)
    f2, b2 = grouped_matmul_train_cost(rows, experts, width, d_model)
    return f1 + f2, b1 + b2


def moe_lm_active_params(d_model: int, heads: int, kv_heads: int,
                         head_dim: int, experts: int, per_token: int,
                         width: int, vocab: int, layers: int) -> int:
    """Parameters a token's forward pass multiplies by: the attention
    projections, the router, ``per_token`` experts of three matrices, and
    the untied head (the embedding is a lookup and the norms are not
    matmuls)."""
    attn = d_model * (heads + 2 * kv_heads) * head_dim \
        + heads * head_dim * d_model
    layer = attn + d_model * experts + per_token * 3 * d_model * width
    return layers * layer + d_model * vocab
