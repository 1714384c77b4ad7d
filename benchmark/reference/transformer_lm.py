"""Plain reference of the decoder-only language model: forward pass and
next-token loss in straightforward ``jax.numpy``, float32, no kernel, no
remat, no fused head. It reads the system's own parameter tree. Callers
run it under ``jax.default_matmul_precision("highest")``; gradients are
``jax.grad`` of :func:`loss`.

Follows GPT-2 (pre-LayerNorm blocks, learned positions, tanh GELU, tied
head) with the departures the configuration file lists: no bias on the
qkv and output projections, LayerNorm epsilon 1e-6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6  # flax.linen.LayerNorm's default, which the program takes

#: How far the system (bf16 compute, f32 accumulation and parameters) may
#: stray from this reference, relative (``correct.py`` has the norms).
#: Measured on the v5e at GPT-2 medium (PERF.md, PR 22): loss 2e-5 to
#: 1.3e-4, whole gradient 0.83-0.89%, worst leaf 1.05-1.2%, the same
#: after the four-chip mean over the bf16 wire (which adds 2^-9 an
#: element). The bounds leave about three times that. Parameters or loss
#: accumulation in bf16 where the configuration says f32 move the loss past
#: 5e-3; a chip left out of a four-chip mean moves the gradient by tens of
#: percent, a sum for a mean by 300%, a skipped leaf by 100% of it.
TOLERANCES = {"loss_rtol": 2e-3, "grad_tree_rtol": 0.03,
              "grad_leaf_rtol": 0.05}


def layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def attention(x, p, n_head):
    B, T, D = x.shape
    hd = D // n_head
    qkv = x @ p["qkv"]["kernel"]
    q, k, v = (t.reshape(B, T, n_head, hd)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, D)
    return out @ p["proj"]["kernel"]


def block(x, p, n_head):
    x = x + attention(layer_norm(x, p["LayerNorm_0"]), p, n_head)
    h = layer_norm(x, p["LayerNorm_1"])
    h = gelu_tanh(h @ p["ff_up"]["kernel"] + p["ff_up"]["bias"])
    return x + h @ p["ff_down"]["kernel"] + p["ff_down"]["bias"]


def hidden(params, tokens, config):
    T = tokens.shape[1]
    x = params["tok_emb"]["embedding"][tokens] + params["pos_emb"][:T][None]
    # a plain loop over the layers: scanned over stacked layers the
    # program is a twentieth the size, but its backward pass keeps 12.4 GB
    # of temporaries for one sequence (AOT compile for the v5e, PR 22)
    for i in range(config["n_layer"]):
        x = block(x, params[f"block_{i}"], config["n_head"])
    return layer_norm(x, params["LayerNorm_0"])


def loss(params, model_state, tokens, config):
    """Mean next-token cross-entropy over every position but the last of
    each row, over all rows of the (padded) embedding table."""
    del model_state
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    h = hidden(params, tokens, config)[:, :-1]
    logits = h @ params["tok_emb"]["embedding"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    gold = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -gold.mean()
