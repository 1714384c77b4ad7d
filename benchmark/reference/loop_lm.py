"""Plain reference of the looped decoder (Ouro's LoopLM): forward pass and
loss in straightforward ``jax.numpy``, float32, a Python loop over passes
and layers, dense softmax attention, whole logits for every exit, its own
exit distribution and entropy. No kernel, no fused head, nothing imported
from the program. It reads the system's own parameter tree. Callers run it
under ``jax.default_matmul_precision("highest")``; gradients are
``jax.grad`` of :func:`loss`.

The equations (config keys are ByteDance/Ouro-2.6B's ``config.json``; what
a dagger marks is the paper's, arXiv:2510.25741, and ``modeling_ouro.py``'s
as recalled, listed under ``assumed`` in the configuration's file), no bias
but the gate's:

    a = x + N2(Wo Attn(q, k, v))       q, k, v = Wq n, Wk n, Wv n, n = N1(x)
    y = a + N4(FFN(N3(a)))  (dagger)   FFN(u) = W_down(silu(W_gate u) * W_up u)
    h^0 = Embed(tokens)
    h^t = N_f(Stack(h^(t-1)))  (dagger)   t = 1..R, R = total_ut_steps; Stack is
                                       the same L blocks and parameters in
                                       every pass; the final norm closes a pass
    logits^t = W_head h^t              lambda^t = sigmoid(w_g . h^t + b_g) (dagger)
    p^t = lambda^t prod_{j<t}(1 - lambda^j)  (t < R)
    p^R = prod_{j<R}(1 - lambda^j)
    loss = mean_tokens[ sum_t p^t CE(logits^t, next token) - beta H(p) ] (dagger)

RoPE at ``rope_theta`` over all of a head's dimensions in the half-split
pairing, causal softmax at scale ``head_dim ** -0.5``, ``N(x) = x
rsqrt(mean(x^2) + eps) w``. ``H(p) = -sum_t p^t log p^t``.

One departure from "nothing is recomputed": a block application and an
exit's cross-entropies are each a ``jax.checkpoint``. The arithmetic is
the same; without it the backward pass of a 4096-token row keeps 24 score
and probability matrices of 1.07 GB each and four exits' logits of 0.8 GB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: How far the system (bf16 compute, f32 parameters, statistics, gate and
#: exit distribution) may stray from this reference, relative
#: (``correct.py`` has the norms). Checks (a) and (c) run on the family's
#: ``check_params``: the trained parameters with the gate's kernel at a
#: tenth (``assumed.check_gate_scale``; the configuration's file says
#: why: as drawn, the share of tokens that leaves at each pass is one draw
#: a seed, and the sound system read 1.01% to 2.91% for that alone, across
#: what the precision below reads). Readings on the v5e at the published
#: widths on 4096-token rows (PERF.md section 6, PR 30, call 5, with
#: seeds; ``tools/loop_controls.py`` takes them; ``benchmark/tests/
#: test_loop.py`` holds the limits to them): the sound system and the
#: reference computed in bf16 over 30 seeds, the changed equations over 10:
#:
#:                   sound             reference in bf16   smallest changed equation          limit
#:   whole gradient  1.40% to 1.91%    2.54% to 4.28%      3.80% (gate's gradient stopped)    2.2%
#:   worst leaf      1.61% to 6.14%    5.71% to 118%       26.6% (three passes for four)      12%
#:   loss            2.7e-6 to 1.7e-4  7.8e-6 to 3.0e-3    1.04e-2 (entropy term left out)    6e-4
#:
#: **The whole gradient holds the precision**: every one of the 30 bf16
#: readings lies above every one of the 30 sound ones (on one seed the
#: bf16 reference reads 1.47 to 2.77 times the sound system), and the
#: limit is the geometric middle of 1.91% and 2.54%: 4.6 standard
#: deviations (0.12 points) above the sound system's mean of 1.67%. It
#: also refuses every changed equation on every seed read: the gate's
#: gradient stopped 3.8% to 14%, the entropy term left out 5.3% to 8.8%,
#: three passes for four 22% to 36%, the norm between passes left out 63%
#: to 89%, the norm after a sub-layer left out 76% to 94%.
#:
#: **The worst leaf and the loss hold equations, not the precision, and
#: no limit on them could**: the sound system's worst leaf has a tail of
#: one leaf, ``exit_gate/kernel`` (6.14%, 4.42%, 2.68%, 2.45%; where
#: another leaf is the worst it reads under 2.1%: the gate's gradient is
#: a sum over tokens of differences of near-equal cross-entropies), which
#: reaches past the bf16 reference's best seed (5.71%, ``tok_emb`` summed
#: in bf16); and a bf16 loss lies on a grid of 0.06 at 11, so on some
#: seed it falls beside the float32 one (7.8e-6). The leaf limit stands
#: at nearly twice the sound system's largest and under half the smallest
#: leaf reading of a changed equation (26.6%); the loss limit at 3.5 times the
#: sound system's largest (check (b), on the trained parameters: 4.2e-7
#: to 1.3e-4 over 24 runs) and a seventeenth of what the entropy term
#: left out reads.
TOLERANCES = {"loss_rtol": 6e-4, "grad_tree_rtol": 0.022,
              "grad_leaf_rtol": 0.12}


def rms_norm(x, p, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
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


def attention(x, p, config):
    B, T, D = x.shape
    n_head = config["num_attention_heads"]
    hd = D // n_head
    q, k, v = (t.reshape(B, T, n_head, hd)
               for t in jnp.split(x @ p["qkv"]["kernel"], 3, axis=-1))
    q, k = rope(q, config["rope_theta"]), rope(k, config["rope_theta"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(hd))
    scores = jnp.where(jnp.tril(jnp.ones((T, T), bool)), scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, T, D)
    return out @ p["proj"]["kernel"]


def feed_forward(u, p):
    return (jax.nn.silu(u @ p["ff_gate"]["kernel"])
            * (u @ p["ff_up"]["kernel"])) @ p["ff_down"]["kernel"]


def block(x, p, config, sublayer_norms=True):
    """One block. ``sublayer_norms=False`` is a control: the plain pre-norm
    block, N2 and N4 left out."""
    eps = config["rms_norm_eps"]

    def after(y, name):
        return rms_norm(y, p[name], eps) if sublayer_norms else y

    a = x + after(attention(rms_norm(x, p["RMSNorm_0"], eps), p, config),
                  "attn_out_norm")
    return a + after(feed_forward(rms_norm(a, p["RMSNorm_1"], eps), p),
                     "ffn_out_norm")


def exits(params, tokens, config, passes=None, norm_between_passes=True,
          sublayer_norms=True):
    """The normed hidden state of every pass, ``[R, B, T, D]``. The
    keywords are the controls: fewer passes; the final norm applied for
    the exits only, the next pass reading the stack's bare output; blocks
    without N2 and N4."""
    eps = config["rms_norm_eps"]
    x = params["tok_emb"]["embedding"][tokens]
    out = []
    for _ in range(passes or config["total_ut_steps"]):
        for i in range(config["num_hidden_layers"]):
            x = jax.checkpoint(
                lambda x, p: block(x, p, config, sublayer_norms))(
                    x, params[f"block_{i}"])
        h = rms_norm(x, params["RMSNorm_0"], eps)
        out.append(h)
        if norm_between_passes:
            x = h
    return jnp.stack(out)


def exit_distribution(gate_logits):
    """``[R, ...]`` gate logits -> ``p [R, ...]``; the last pass's gate is
    not read: it takes what the earlier passes left."""
    lam = jax.nn.sigmoid(gate_logits[:-1])
    left = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(left[:1]), left[:-1]])
    return jnp.concatenate([lam * before, left[-1:]])


def logits(params, tokens, config, dtype=jnp.float32):
    """The last pass's logits: the model's prediction when no token exits
    early (``early_exit_threshold`` 1)."""
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    return exits(params, tokens, config)[-1] \
        @ params["lm_head"]["embedding"].T


def loss(params, model_state, tokens, config, dtype=jnp.float32, beta=None,
         **controls):
    """Mean over every position but the last of each row of the expected
    next-token cross-entropy over the exits less ``beta`` x the exit
    distribution's entropy. ``dtype`` is what everything is computed in:
    float32 is the reference; bfloat16 (parameters, statistics, gate and
    sums too) is the control in the precision below the configuration's.
    ``beta`` (default: the configuration's) and ``controls``
    (:func:`exits`' keywords) change the equations, for the controls the
    tolerances have to refuse."""
    del model_state
    params = jax.tree.map(lambda a: a.astype(dtype), params)
    if beta is None:
        beta = config["assumed"]["exit_entropy_beta"]
    h = exits(params, tokens, config, **controls)
    gate = params["exit_gate"]
    p = exit_distribution(
        (h @ gate["kernel"])[..., 0] + gate["bias"])[:, :, :-1]

    @jax.checkpoint
    def cross_entropy(h_exit):
        out = h_exit[:, :-1] @ params["lm_head"]["embedding"].T
        logp = jax.nn.log_softmax(out, axis=-1)
        return -jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1)[..., 0]

    ce = jnp.stack([cross_entropy(h_exit) for h_exit in h])
    entropy = -(p * jnp.log(p)).sum(0)
    return ((p * ce).sum(0) - beta * entropy).mean()
