"""Plain reference of LFM2-MoE's decoder (LFM2-8B-A1B): forward pass and
loss in straightforward ``jax.numpy``, float32, no kernel, no sort, no
fused head; a loop over the experts held. It reads the system's own
parameter tree and router state and is given tokens only: it routes for
itself, in float32. Callers run it under
``jax.default_matmul_precision("highest")``; gradients are ``jax.grad`` of
:func:`loss`.

The equations (``config.json`` of LiquidAI/LFM2-8B-A1B and Hugging Face's
``modeling_lfm2_moe.py`` as recalled, no network), no bias anywhere, ``h``
the normed input, ``d`` the hidden size:

    r = x + Op_i(RMSNorm(x))      y = r + FFN_i(RMSNorm(r))
    Op_i, layer_types[i] == "conv":
        (B, C, x~) = split3(h W_in)          W_in [d, 3d], in that order
        u = B * x~
        c_t = sum_{j<L} w_j * u_{t-(L-1)+j}  u zero before the sequence,
                                             w [L, d] depthwise, L = 3
        out = (C * c) W_out
    Op_i, "full_attention":
        q = h Wq, k = h Wk, v = h Wv         32 / 8 / 8 heads of 64
        q, k <- RMSNorm over each head's 64 values (one [64] scale each),
        then RoPE on the whole head (half-split pairing, rope_theta),
        causal softmax at 64^-1/2, a key-value head read by 4 query heads
    FFN_i, i < num_dense_layers:  W2 (silu(W1 h) * W3 h)
    FFN_i otherwise:
        s = sigmoid(h W_r)                   W_r [d, 32]
        I = the 4 largest of s + b           b [32], ties to the lower index
        g_e = s_e / (sum_{e' in I} s_e' + 1e-6), times routed_scaling_factor
        out = sum_{e in I and held} g_e W2_e (silu(W1_e h) * W3_e h)

``RMSNorm(x) = x rsqrt(mean(x^2) + eps) w``; after the last layer one
RMSNorm, then the tied head: ``logits = norm(x) E^T``. Loss: the mean
next-token cross-entropy, no auxiliary term.

**The share.** The file holds experts ``experts_held_range = [lo, hi)`` of
``experts_published``: the router keeps its published width, the choice is
over all of them and ``g`` is normalised over all 4 chosen, and the sum
runs over the chosen experts that are held. What the absent ones would
add is left out, here as in the program, and that partial result goes on
to the next layer.

Departures from the published model: ``b`` is no trained quantity here (it
comes in ``model_state``, drawn from the seed, and is held fixed: how it
is updated in training is not in ``config.json``); the 1e-6 and the place
of the norms are the published code's as recalled.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: How far the system (bf16 compute, f32 parameters, accumulation and
#: router) may stray from this reference, relative (``correct.py`` has the
#: norms). Readings on the v5e at the published widths on one 8192-token
#: row a seed, the way check (a) takes them: on the family's
#: ``check_params``, the drawn tree with every router's kernel at
#: ``assumed.check_router_scale`` 0.05 (``tools/hybrid_controls.py`` and
#: the cell's own check (a); PERF.md section 6, PR 40, has the seeds):
#:
#:                    sound,           bf16 reference,     equations,    limit
#:                    largest of 23    smallest of 16      smallest
#:   loss             1.20e-4          5.1e-5              (not held)    3e-4
#:   whole gradient   9.13%            18.07%              26.5%         13%
#:   worst leaf       28.15%           50.2%               75.5%         37%
#:
#: **Why not on the drawn tree.** There a score varies by 0.2 from token
#: to token and the bias by 0.02, the program routes from bf16 states and
#: this reference from float32 ones, and by the fourth expert layer 4.1%
#: of the (token, slot) assignments differ (1.3%, 2.0%, 3.0%, 4.1% by
#: layer; 7.8% of the held experts' rows), each a token whose whole
#: expert output changes hands under a share: the sound system reads
#: 9.0-10.4% / 24.6-27.7% and this reference computed in bf16 10.2-11.6%
#: / 27.3-30.5%, and no limit parts them. With the routers at a twentieth
#: the scores vary by 0.01, half the bias's width, and a state's rounding
#: moves the choice a twentieth as far: the sound system's reading falls
#: and the bf16 reference's rises, because bf16 scores near 0.5 lie on a
#: grid of 0.004, a fifth of the bias's width, and its choice ties
#: (scales 1 / 0.1 / 0.05 / 0.03 / 0.02 / 0.01, six seeds: sound 10.0 /
#: 8.8 / 7.4 / 6.8 / 6.0 / 5.5% at most, bf16 reference 10.2 / 18.4 / 19.2
#: / 18.5 / 15.0 / 6.7% at least). The choice still moves from token to
#: token there (16-46 thousand rows held of 131 thousand, 32.8 expected).
#:
#: The limits lie between the sound system's largest and the smallest
#: control's with a factor 1.3-1.4 on either side (the sound system over
#: its 23 seeds: 7.2% +- 0.9 and 21.3% +- 2.5). The controls: this reference
#: computed in bf16 (``loss(dtype=bfloat16)``; 18.1-28.7% / 50-84%),
#: refused by the whole gradient and by the worst leaf on every seed, in
#: the tool and through the cell's own ``run_cell``; the changed
#: equations on four seeds (top-3 for top-4 26.5-39.6% / 75-85%, the
#: softmax for the sigmoid 38-53%, gates not renormalised 43-69%, the
#: selection bias left out 57-73%), all refused by both. The loss limit is
#: the accepted cells' 3e-4: it holds check (b), which runs on the drawn
#: tree (the sound system's largest of 47 first-step readings 2.12e-4,
#: the second largest 1.15e-4), and refuses the bf16 reference on most
#: seeds only (11 of 15: a bf16 loss lies on a grid of 0.06).
#:
#: What these norms do not hold at published widths, said plainly: the
#: system on bf16 parameters, a router whose operands are rounded to bf16
#: and gates weighed by score + bias (``b`` is 0.02 wide) read as the
#: sound system does at every scale. The router's precision, the bias's
#: place and the 1e-6 are held at the tiny size in float32
#: (``tests/test_hybrid_lm.py``: all eight departures fail 1e-3 where the
#: sound system keeps 2e-5). Drops are held by the family, exactly: a row
#: routed to a held expert that lies in no group (``moe/dropped``) gives
#: the step no finite loss.
TOLERANCES = {"loss_rtol": 3e-4, "grad_tree_rtol": 0.13,
              "grad_leaf_rtol": 0.37}


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def rope(x, base):
    """Rotary embedding on ``[B, T, H, Dh]``, positions ``0..T-1``, the
    pair of dimension ``i`` being ``i + Dh/2``."""
    T, half = x.shape[1], x.shape[-1] // 2
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = (f(ang)[None, :, None].astype(x.dtype)
                for f in (jnp.cos, jnp.sin))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def by_row(fn, *batched):
    """``fn`` on one batch row at a time (rows are independent but in the
    batch mean of the loss): the score matrix and the logits of a whole
    batch at once do not fit beside the parameters."""
    return jax.lax.map(
        lambda row: fn(*(r[None] for r in row))[0], batched)


def short_conv(h, p, config):
    L = config["conv_L_cache"]
    T = h.shape[1]
    b, c, x = jnp.split(h @ p["conv_in"]["kernel"], 3, axis=-1)
    u = b * x
    # u_{t - (L-1) + j}: tap j reads L - 1 - j steps back, zeros before 0
    padded = jnp.pad(u, ((0, 0), (L - 1, 0), (0, 0)))
    conv = sum(p["conv_w"][j] * padded[:, j:j + T] for j in range(L))
    return (c * conv) @ p["conv_out"]["kernel"]


def attention(h, p, config):
    B, T, D = h.shape
    n_q, n_kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = D // n_q, config["norm_eps"]
    q, k, v = jnp.split(h @ p["qkv"]["kernel"],
                        [n_q * hd, (n_q + n_kv) * hd], axis=-1)
    q = rms_norm(q.reshape(B, T, n_q, hd), p["q_norm"], eps)
    k = rms_norm(k.reshape(B, T, n_kv, hd), p["k_norm"], eps)
    q, k = rope(q, config["rope_theta"]), rope(k, config["rope_theta"])
    v = v.reshape(B, T, n_kv, hd)
    # query head i reads key-value head i // (n_q / n_kv)
    q = q.reshape(B, T, n_kv, n_q // n_kv, hd)
    causal = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def one_kv_head(qg, kg, vg):  # [B, T, G, hd], [B, T, hd], [B, T, hd]
        scores = jnp.einsum("bqgd,bkd->bgqk", qg, kg) / jnp.sqrt(float(hd))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", probs, vg)

    # a key-value head at a time: 32 heads' scores of an 8192-token row
    # are 8.6 GB in float32
    out = jax.lax.map(lambda a: one_kv_head(*a), (
        jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(out, 0, 2).reshape(B, T, D) @ p["proj"]["kernel"]


def dense_ffn(h, p):
    return (jax.nn.silu(h @ p["ff_gate"]["kernel"])
            * (h @ p["ff_up"]["kernel"])) @ p["ff_down"]["kernel"]


def route(u, p, bias, config):
    """``[N, E]`` weights: ``g_e`` where the token chose ``e``, else 0."""
    E = config.get("experts_published", config["num_experts"])
    s = jax.nn.sigmoid(u @ p["moe_router"])
    choice = s + bias if config["use_expert_bias"] else s
    _, chosen = jax.lax.top_k(choice, config["num_experts_per_tok"])
    g = jnp.take_along_axis(s, chosen, axis=-1)
    if config["norm_topk_prob"]:
        g = g / (g.sum(-1, keepdims=True) + 1e-6)
    g = g * config["routed_scaling_factor"]
    return (jax.nn.one_hot(chosen, E, dtype=u.dtype) * g[..., None]).sum(1)


def experts(u, p, bias, config):
    """``u [N, D]`` -> the held experts' part of the layer's output."""
    F = config["moe_intermediate_size"]
    lo, hi = config.get("experts_held_range") or (0, config["num_experts"])
    weight = route(u, p, bias, config)

    @jax.checkpoint
    def one(w_gate_up, w_down, g):
        gate_up = u @ w_gate_up
        return ((jax.nn.silu(gate_up[:, :F]) * gate_up[:, F:]) @ w_down) \
            * g[:, None]

    out = jnp.zeros_like(u)
    for j, e in enumerate(range(lo, hi)):
        out = out + one(p["moe_w_gate_up"][j], p["moe_w_down"][j],
                        weight[:, e])
    return out


def hidden(params, model_state, tokens, config):
    """Final hidden states, normed."""
    eps = config["norm_eps"]
    x = params["tok_emb"]["embedding"][tokens]
    B, T, D = x.shape
    for i, kind in enumerate(config["layer_types"]):
        p = params[f"block_{i}"]
        h = rms_norm(x, p["RMSNorm_0"], eps)
        if kind == "conv":
            x = x + short_conv(h, p, config)
        else:
            x = x + by_row(lambda r: attention(r, p, config), h)
        h = rms_norm(x, p["RMSNorm_1"], eps)
        if i < config["num_dense_layers"]:
            x = x + dense_ffn(h, p)
        else:
            bias = model_state[f"block_{i}"]["moe_router_bias"] \
                if config["use_expert_bias"] else None
            x = x + experts(h.reshape(B * T, D), p, bias,
                            config).reshape(B, T, D)
    return rms_norm(x, params["RMSNorm_0"], eps)


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def logits(params, model_state, tokens, config, dtype=jnp.float32):
    params, model_state = _cast(params, dtype), _cast(model_state, dtype)
    return hidden(params, model_state, tokens, config) \
        @ params["tok_emb"]["embedding"].T


def loss(params, model_state, tokens, config, dtype=jnp.float32):
    """Mean next-token cross-entropy over every position but the last of
    each row. ``dtype`` is what everything is computed in: float32 is the
    reference; bfloat16 (parameters, router, bias, statistics and sums
    too) is the control in the precision below the configuration's, which
    the tolerances have to refuse."""
    params, model_state = _cast(params, dtype), _cast(model_state, dtype)
    h = hidden(params, model_state, tokens, config)

    def gold(h_row, tokens_row):
        out = h_row[:, :-1] @ params["tok_emb"]["embedding"].T
        logp = jax.nn.log_softmax(out, axis=-1)
        return jnp.take_along_axis(
            logp, tokens_row[:, 1:, None], axis=-1)[..., 0]

    return -by_row(gold, h, tokens).mean()
