"""Model FLOPs of a looped language model, from its shapes (``costs.py``'s
rule: a program may change how it computes, never what these return)."""

from __future__ import annotations


def dense_layer_params(d_model: int, heads: int, kv_heads: int,
                       head_dim: int, ffn_width: int) -> int:
    """Parameters a token's forward pass multiplies by in one block: the
    attention projections and a gated feed-forward's three matrices (the
    norms are not matmuls)."""
    return d_model * (heads + 2 * kv_heads) * head_dim \
        + heads * head_dim * d_model + 3 * d_model * ffn_width


def looped_lm_train_flops_per_token(layer_params: int, head_params: int,
                                    layers: int, passes: int, seq_len: int,
                                    d_model: int) -> float:
    """Model FLOPs of one token's forward and backward pass through
    ``layers`` blocks applied ``passes`` times on one set of weights, with
    an exit (the untied head) after every pass: ``6 N`` for each
    application of a matrix, so the stack and the head count ``passes``
    times though their parameters are held once, and ``6 T d`` for causal
    attention's two score matmuls at half a square, a layer and pass. All
    parameters are active; the gate's ``2 d`` a pass are left out; the
    embedding is a lookup; recomputation is not counted."""
    return 6.0 * passes * (layers * layer_params + head_params) \
        + 6.0 * passes * layers * seq_len * d_model
