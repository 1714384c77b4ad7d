"""Sequence-parallel sliding-window (local) attention — O(window) comm.

NEW capability relative to the reference (SURVEY.md section 5: no sequence
parallelism existed in the 2017-era codebase). The distributed complement
of ``flash_attention(window=W)``: a query can only reach keys within the
last ``W`` positions, which live on its OWN shard plus the TAILS of its
``m = ceil((W-1)/T_local)`` nearest predecessors. So instead of rotating
K/V around the full ring (n - 1 ``ppermute`` hops, O(T) traffic —
:mod:`chainermn_tpu.parallel.ring_attention`), each shard exchanges
exactly the ``W - 1`` needed positions (one bundled ``ppermute`` per
neighbour distance): communication is O(window) regardless of sequence
length or mesh size — a T/W-fold saving.

Mechanism (inside ``shard_map`` over the sequence axis):

1. predecessor ``s-d`` (``d = 1..m``) sends its last
   ``c_d = min(T_local, W-1-(d-1)·T_local)`` K/V positions ``d`` steps
   forward; the receiver prepends them furthest-first;
2. the banded flash kernel runs with ``q_offset = prefix_len`` — local
   query row ``i`` sits at extended-key position ``i + prefix_len``, so
   the standard causal-window band lands exactly on the right keys;
3. wrap-around slices (shard ``s`` receiving from ``s - d < 0``) must
   see nothing: a segment-id sentinel masks them (the kernel's packed
   -segment mask, reused);
4. backward: the flash backward yields gradients for the extended K/V;
   each prefix slice ``ppermute``s BACK to its owner (the transpose of
   the forward shift — the same Send/Recv duality the reference
   hand-built in ``functions/point_to_point_communication.py`` (dagger))
   and adds into the owner's last ``c_d`` positions. Wrap-around edges
   carry exact zeros (masked in forward ⇒ zero gradient), no special
   case.

Known cost accepted (round-4 ADVICE, low): the wrap sentinel rides the
segment-id path even when the caller has no packed segments, so every
block pays a small ([1, block] int32) segment DMA + compare. The
sentinel-free alternative — masking wrapped positions by GLOBAL
position — needs a traced per-shard scalar (``axis_index``-derived)
threaded into all three flash kernels via SMEM; measured against the
K/V block DMAs (hundreds of KB vs ~4 KB) the saving is marginal, and
kernel-signature changes are not made without same-session Mosaic
compile-checks on a real chip (CLAUDE.md kernel convention).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.ops.flash_attention import (
    _use_interpret,
    flash_block_bwd,
    flash_block_fwd,
)
from chainermn_tpu.parallel.collectives import shift

# Wrap-around mask sentinel: INT32_MIN cannot legitimately appear as a
# user segment id (ids are labels, and -1-style padding conventions stay
# far from the extreme), so shard 0's received tail can never match a
# query id.
_WRAP_SENTINEL = jnp.iinfo(jnp.int32).min


def _tail_slices(tail: int, L: int, n: int):
    """Static geometry of the multi-neighbour prefix: predecessor ``s-d``
    (``d = 1..m``) contributes its LAST ``c_d = min(L, tail - (d-1)L)``
    positions. ``m`` is capped at ``n - 1`` — further reach is before the
    sequence start (or a full wrap) and simply doesn't exist. Returns
    ``[(d, c_d), ...]`` ordered FURTHEST-first (prefix concat order)."""
    m = min(-(-tail // L), n - 1)
    # Every c_d >= 1 by construction: d <= ceil(tail/L) ⇒ tail-(d-1)L >= 1.
    return [(d, min(L, tail - (d - 1) * L)) for d in range(m, 0, -1)]


def _ext_and_segs(k, v, seg_q_ids, axis_name, tail):
    """Build the extended K/V (predecessors' tails prepended, furthest
    first) and the segment ids that (a) mask wrap-around slices — shard
    ``s`` receives garbage from ``s - d`` whenever ``s < d`` — and (b)
    carry any user packed-segment ids across the boundaries (all-zero
    ids when the caller has no packed segments). One bundled ``ppermute``
    per neighbour distance moves k/v/ids together."""
    L = k.shape[1]
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    k_parts, v_parts, id_parts = [], [], []
    for d, c in _tail_slices(tail, L, n):
        k_t, v_t, ids_t = shift(
            (k[:, L - c:], v[:, L - c:], seg_q_ids[:, L - c:]),
            axis_name, d,
        )
        ids_t = jnp.where(
            me >= d, ids_t, jnp.full_like(ids_t, _WRAP_SENTINEL)
        )
        k_parts.append(k_t)
        v_parts.append(v_t)
        id_parts.append(ids_t)
    k_ext = jnp.concatenate(k_parts + [k], axis=1)
    v_ext = jnp.concatenate(v_parts + [v], axis=1)
    seg_k_ids = jnp.concatenate(id_parts + [seg_q_ids], axis=1)
    return k_ext, v_ext, seg_q_ids, seg_k_ids


def _pad_ext_to_block(k_ext, v_ext, seg_k_ids, block_k):
    """Round the extended K axis up to a multiple of the effective K
    block. The extended length ``T_local + prefix`` is odd whenever the
    window is even (the common case) — without padding no power-of-two
    block divides it, ``_pick_block`` collapses to one whole-T block and
    the banded grid degenerates to O(T + W) DMA per query block (and a
    potentially VMEM-busting single K/V block). Back-padding is inert:
    pad positions exceed every query's extended position, so the causal
    mask kills them; the wrap sentinel in the segment ids is
    belt-and-braces."""
    T = k_ext.shape[1]
    b = min(block_k, T)
    pad = -T % b
    if pad:
        widths = [(0, 0)] * k_ext.ndim
        widths[1] = (0, pad)
        k_ext = jnp.pad(k_ext, widths)
        v_ext = jnp.pad(v_ext, widths)
        seg_k_ids = jnp.pad(seg_k_ids, ((0, 0), (0, pad)),
                            constant_values=_WRAP_SENTINEL)
    return k_ext, v_ext, seg_k_ids


def _local_fwd_impl(q, k, v, seg, axis_name, window, scale, block_q,
                    block_k, interpret):
    tail = window - 1
    k_ext, v_ext, seg_q_ids, seg_k_ids = _ext_and_segs(
        k, v, seg, axis_name, tail
    )
    # The realized prefix may be SHORTER than tail when the window
    # reaches past the sequence start (slices are capped at n-1
    # predecessors): q_offset is the true prefix length. Computed BEFORE
    # tile padding (the pad goes on the back; the prefix is the front).
    prefix = k_ext.shape[1] - k.shape[1]
    k_ext, v_ext, seg_k_ids = _pad_ext_to_block(
        k_ext, v_ext, seg_k_ids, block_k
    )
    out, lse = flash_block_fwd(
        q, k_ext, v_ext, causal=True, scale=scale, window=window,
        q_offset=prefix, seg_q=seg_q_ids, seg_kv=seg_k_ids,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _local_window(q, k, v, seg, axis_name, window, scale, block_q, block_k,
                  interpret):
    out, _ = _local_fwd_impl(q, k, v, seg, axis_name, window, scale,
                             block_q, block_k, interpret)
    return out


def _local_window_fwd(q, k, v, seg, axis_name, window, scale, block_q,
                      block_k, interpret):
    out, lse = _local_fwd_impl(q, k, v, seg, axis_name, window, scale,
                               block_q, block_k, interpret)
    return out, (q, k, v, seg, out, lse)


def _local_window_bwd(axis_name, window, scale, block_q, block_k, interpret,
                      res, g):
    q, k, v, seg, out, lse = res
    tail = window - 1
    L = q.shape[1]
    n = lax.axis_size(axis_name)
    # Rebuild the extended K/V (recompute beats storing an overlapping
    # copy — same remat philosophy as the flash backward itself).
    k_ext, v_ext, seg_q_ids, seg_k_ids = _ext_and_segs(
        k, v, seg, axis_name, tail
    )
    prefix = k_ext.shape[1] - L
    k_ext, v_ext, seg_k_ids = _pad_ext_to_block(
        k_ext, v_ext, seg_k_ids, block_k
    )
    dq, dk_ext, dv_ext = flash_block_bwd(
        q, k_ext, v_ext, g, lse, out, causal=True, scale=scale,
        window=window, q_offset=prefix, seg_q=seg_q_ids, seg_kv=seg_k_ids,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    # Own-shard part + each prefix slice's gradient returned to its owner
    # (the transpose of the forward shift-by-d), added into the owner's
    # last c_d positions. Wrapped slices carry exact zeros (they were
    # segment-masked in the forward), so no special case. Tile padding
    # (fully masked, zero grad) is simply dropped.
    dk = dk_ext[:, prefix:prefix + L]
    dv = dv_ext[:, prefix:prefix + L]
    off = 0
    for d, c in _tail_slices(tail, L, n):
        dk_b, dv_b = shift(
            (dk_ext[:, off:off + c], dv_ext[:, off:off + c]),
            axis_name, -d,
        )
        dk = dk.at[:, L - c:].add(dk_b)
        dv = dv.at[:, L - c:].add(dv_b)
        off += c
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None)


_local_window.defvjp(_local_window_fwd, _local_window_bwd)


def sliding_window_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    window: int,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal sliding-window attention over sequence shards — call INSIDE
    ``shard_map``. See the module docstring for the design.

    Args:
      q/k/v: local shards ``[B, T_local, H|Hkv, D]`` of a sequence
        sharded CONTIGUOUSLY over ``axis_name`` (GQA/MQA supported —
        fewer kv heads than q heads).
      window: band width ``W``; global query ``i`` sees keys
        ``(i - W, i]``. Any width: the prefix gathers from
        ``ceil((W-1)/T_local)`` predecessors (capped at the mesh — a
        window covering the whole sequence degenerates to full causal
        attention, where the plain ring is the better choice).
      segment_ids: optional local ``[B, T_local]`` packed-segment slice;
        ids travel with the tail so cross-boundary masking stays exact.
        Any int32 value except ``INT32_MIN`` is a valid id (that value is
        the internal wrap-around mask sentinel).

    Returns:
      Local output shard ``[B, T_local, H, D]`` (dtype of ``q``).
    """
    if window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    L = q.shape[1]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    if window == 1:
        # Degenerate: each query sees only itself — no communication.
        from chainermn_tpu.ops.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=True, window=1, scale=scale,
            segment_ids=segment_ids, block_q=block_q, block_k=block_k,
            interpret=interpret,
        )
    seg = (segment_ids.astype(jnp.int32) if segment_ids is not None
           else jnp.zeros((q.shape[0], L), jnp.int32))
    return _local_window(q, k, v, seg, axis_name, window, float(scale),
                         block_q, block_k, interpret)


__all__ = ["sliding_window_attention_local"]
