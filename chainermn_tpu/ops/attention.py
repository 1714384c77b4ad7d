"""Attention primitives (single-device locals).

Layout convention throughout: ``[batch, seq, heads, head_dim]`` (BTHD).
Softmax statistics are always accumulated in float32 regardless of input
dtype (bf16-safe — the same master-precision discipline as the gradient
allreduce path).

``q_offset`` / ``kv_offset`` express *global* sequence positions so the same
local kernel serves both single-device attention and the sequence-parallel
layers, where each shard sees a slice of the sequence
(:mod:`chainermn_tpu.parallel.ring_attention`).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _scale(q, scale: Optional[float]) -> float:
    return scale if scale is not None else q.shape[-1] ** -0.5


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    q_offset=0,
    kv_offset=0,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
) -> jax.Array:
    """Plain softmax attention — the correctness reference.

    Args:
      q: ``[B, Tq, H, D]``; k/v: ``[B, Tk, Hkv, D]`` where ``Hkv`` divides
        ``H`` (GQA/MQA: kv heads are repeated across their group).
      causal: mask positions where ``kv_pos > q_pos`` (global positions,
        honouring the offsets).
      segment_ids: optional ``[B, T]`` packed-segment ids (Tq == Tk);
        attention is confined to equal ids. Rows with no visible key
        return zeros.
      bias: optional additive score bias ``[B|1, H|1, Tq, Tk]``, applied
        after the qk scale and before masking.
    """
    s = _scale(q, scale)
    if k.shape[2] != q.shape[2]:
        if q.shape[2] % k.shape[2]:
            raise ValueError(
                f"q heads ({q.shape[2]}) not a multiple of kv heads "
                f"({k.shape[2]})"
            )
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * s
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    mask = None
    if causal:
        q_pos = q_offset + lax.iota(jnp.int32, q.shape[1])
        kv_pos = kv_offset + lax.iota(jnp.int32, k.shape[1])
        mask = (q_pos[:, None] >= kv_pos[None, :])[None, None]
    if segment_ids is not None:
        seg = (segment_ids[:, :, None] == segment_ids[:, None, :])[:, None]
        mask = seg if mask is None else mask & seg
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if mask is not None:
        # Fully-masked rows: softmax over all-NEG_INF is uniform garbage.
        probs = jnp.where(mask.any(-1, keepdims=True), probs, 0.0)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype)


def online_softmax_block(
    q: jax.Array,
    k_blk: jax.Array,
    v_blk: jax.Array,
    o: jax.Array,
    m: jax.Array,
    l: jax.Array,
    *,
    causal: bool = False,
    q_offset=0,
    kv_offset=0,
    scale: Optional[float] = None,
):
    """One online-softmax accumulation step over a K/V block.

    This is the flash-attention inner update — and, run over *remote* K/V
    blocks arriving by ``ppermute`` rotation, the ring-attention inner update
    (SURVEY.md section 5).

    Args:
      q: ``[B, Tq, H, D]`` (any float dtype; accumulation is f32).
      k_blk/v_blk: ``[B, Tk, H, D]`` current block.
      o: ``[B, Tq, H, D]`` f32 running (unnormalised) output.
      m: ``[B, H, Tq]`` f32 running max.
      l: ``[B, H, Tq]`` f32 running normaliser.

    Returns:
      Updated ``(o, m, l)``.
    """
    s = _scale(q, scale)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_blk, preferred_element_type=jnp.float32
    ) * s
    if causal:
        q_pos = q_offset + lax.iota(jnp.int32, q.shape[1])
        kv_pos = kv_offset + lax.iota(jnp.int32, k_blk.shape[1])
        mask = (q_pos[:, None] >= kv_pos[None, :])[None, None]
        scores = jnp.where(mask, scores, NEG_INF)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1.
    p = jnp.exp(scores - m_new[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    # corr is [B, H, Tq]; o is [B, Tq, H, D] — align layouts for the rescale.
    o_new = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v_blk, preferred_element_type=jnp.float32
    )
    return o_new, m_new, l_new


def finalize_online_softmax(o: jax.Array, l: jax.Array, dtype) -> jax.Array:
    """Normalise the accumulated output: ``o / l`` with layout fix-up.
    Fully-masked rows (l == 0) return zeros rather than NaN."""
    denom = l.transpose(0, 2, 1)[..., None]
    return jnp.where(denom > 0, o / jnp.maximum(denom, 1e-37), 0.0).astype(dtype)


def resolve_attention_impl(q_shape, dtype, *, windowed: bool = False) -> str:
    """Device-aware attention variant, through the decision registry
    (:mod:`chainermn_tpu.tuning`), keyed on ``(device_kind,
    bucket(T, H, D), dtype)``: ``flash`` (or ``windowed``, when a
    sliding window is asked for) on accelerators, ``xla`` on a CPU,
    where the kernel would run in the interpreter."""
    from chainermn_tpu import tuning

    B, T, H, D = q_shape
    name = "attention_windowed" if windowed else "attention"
    candidates = ("windowed", "xla") if windowed else ("flash", "xla")
    key = tuning.decision_key(shape=(T, H, D), dtype=dtype)
    return tuning.choice(name, candidates, key)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The variant-dispatching entry point: one spelling, device-aware
    implementation choice.

    ``impl``: ``'xla'`` (materialised :func:`dot_product_attention`),
    ``'flash'`` / ``'windowed'`` (the Pallas kernel, VMEM-blocked —
    ``'windowed'`` is the banded grid selected when ``window`` is set),
    or ``'auto'`` (default): resolved per device/shape/dtype via
    :func:`resolve_attention_impl`. Every variant computes the same
    attention (the windowed band is reproduced on the xla path as an
    additive score bias), so the choice is pure performance —
    equivalence of both sides is pinned in tests/test_tuning.py.

    ``interpret`` is forwarded to the Pallas kernel (default: interpret
    off-accelerator, the kernel's own rule).
    """
    if window is not None and not causal:
        # The Pallas kernel rejects this; validating HERE keeps the xla
        # path from silently computing different (future-visible) band
        # semantics — the dispatch must never change behaviour.
        raise ValueError("window requires causal=True")
    if impl == "auto":
        impl = resolve_attention_impl(q.shape, q.dtype,
                                      windowed=window is not None)
    if impl == "xla":
        b = bias
        if window is not None:
            # Reproduce the kernel's banded semantics exactly:
            # q_pos - kv_pos < window allowed (composes with causal).
            q_pos = lax.iota(jnp.int32, q.shape[1])
            kv_pos = lax.iota(jnp.int32, k.shape[1])
            band = jnp.where(
                (q_pos[:, None] - kv_pos[None, :]) < window, 0.0, NEG_INF
            )[None, None].astype(jnp.float32)
            b = band if b is None else b.astype(jnp.float32) + band
        return dot_product_attention(
            q, k, v, causal=causal, scale=scale,
            segment_ids=segment_ids, bias=b,
        )
    if impl in ("flash", "windowed"):
        from chainermn_tpu.ops.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=causal, scale=scale,
            segment_ids=segment_ids, bias=bias, window=window,
            interpret=interpret,
        )
    raise ValueError(
        f"unknown attention impl {impl!r} "
        "(expected auto|xla|flash|windowed)"
    )


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    block_k: int = 512,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Flash-style blockwise attention via ``lax.scan`` over K/V blocks:
    O(Tq * block_k) live memory instead of materialising ``[Tq, Tk]`` scores.
    Single-device building block; the distributed versions live in
    :mod:`chainermn_tpu.parallel`."""
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if k.shape[2] != H:
        # GQA in the reference path: materialized repeat (the flash kernel
        # shares kv blocks via its index map instead).
        rep = H // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if Tk % block_k != 0:
        block_k = Tk  # fall back to one block rather than padding
    n_blocks = Tk // block_k

    o = jnp.zeros((B, Tq, H, D), jnp.float32)
    m = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, Tq), jnp.float32)

    k_blocks = k.reshape(B, n_blocks, block_k, H, D)
    v_blocks = v.reshape(B, n_blocks, block_k, H, D)

    def body(carry, blk):
        o, m, l = carry
        k_blk, v_blk, idx = blk
        o, m, l = online_softmax_block(
            q, k_blk, v_blk, o, m, l,
            causal=causal, q_offset=0, kv_offset=idx * block_k, scale=scale,
        )
        return (o, m, l), None

    (o, m, l), _ = lax.scan(
        body,
        (o, m, l),
        (
            jnp.moveaxis(k_blocks, 1, 0),
            jnp.moveaxis(v_blocks, 1, 0),
            jnp.arange(n_blocks),
        ),
    )
    return finalize_online_softmax(o, l, q.dtype)
