"""Fused paged-decode Pallas kernel: one HBM pass per decode tick.

The XLA decode hot path is three programs' worth of HBM traffic per
layer per tick — ``paged_update`` scatter → ``paged_lookup`` gather →
dense attend — and the gather materializes the ENTIRE
``[B, max_blocks * block_size]`` dense KV view regardless of how many
tokens are live (:meth:`~chainermn_tpu.models.transformer.
TransformerBlock._slot_decode_attend`). This module is the ROADMAP's
"fused paged-decode Pallas kernel" item: a flash-decoding-style kernel
over the vLLM paged layout (``vllm/core/block_manager.py`` †, the same
provenance :mod:`chainermn_tpu.ops.paged_kv` cites) that reads each
LIVE block exactly once and never materializes a dense view — the
reference's signature hide-the-phase-cost move
(``double_buffering_optimizer.py`` †) applied to the serving engine's
innermost loop.

Kernel shape, per grid cell ``(b, h, j)`` (slot × kv head × KV-block
slot):

- **Table-indexed in-kernel gather.** The block table and the per-row
  positions ride as SCALAR-PREFETCH operands
  (``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index maps
  read ``tables[b, j]`` directly: the pipeline DMAs physical block
  ``tables[b, j]``'s ``bs × D`` head slice straight from the pool.
  Block slots past the row's live horizon are redirected to one fixed
  block; consecutive revisits of an unchanged block index skip the
  copy, so dead table width costs O(1) reads, not O(max_blocks).
- **Split-K online softmax.** The ``j`` axis is the sequential
  (``arbitrary``) grid dim carrying running max / denominator and an
  fp32 accumulator in VMEM scratch — the partial-combine pass is the
  standard flash recurrence (:mod:`chainermn_tpu.ops.flash_attention`);
  the final slot rescales once and writes O(1) output bytes per row.
- **Masking.** Per-row live-length mask from ``positions`` (query row
  ``t`` of slot ``b`` admits keys at ``kpos <= positions[b] + t``),
  optional sliding-window band (the same band the XLA path applies),
  and explicit scratch-block masking: any table entry equal to
  ``scratch_block`` (id 0 in the serving pool — where beyond-horizon
  writes are redirected, :func:`~chainermn_tpu.ops.paged_kv.
  paged_update`) contributes NOTHING, so a released slot's scratch
  garbage can never leak into a live row.
- **GQA head mapping.** Grid runs over KV heads; the ``group`` query
  heads sharing kv head ``h`` ride as extra query rows in the same
  block (rows ``t * group + g``), so grouped queries share one K/V
  block read — no repeated kv heads, in-kernel or out.
- **``T >= 1`` query rows per slot.** Plain decode (``T = 1``), the
  speculative verify span (``T = K + 1``), the chunked mixed step and
  the prefill tail all ride this ONE kernel; and
  :func:`dense_flash_decode` serves the dense ring cache through the
  same program by viewing ``[B, L, kvh, dh]`` as ``L / bs`` implicit
  blocks per row with an identity table — the way
  :func:`~chainermn_tpu.ops.paged_kv.copy_block` serves plain pools
  and TP stacks with one program. TP-stacked pools (leading stack
  axis) unroll into per-shard calls; there are zero collectives inside.

CPU tests run interpret mode per convention (``interpret=None`` auto-
detects, same rule as flash attention); ALWAYS compile-check on a real
chip before trusting a change — Mosaic rejects layouts interpret mode
accepts (``tools/kernel_compile_check.py``). It rejects THIS kernel on a
TPU v5e under jax 0.9.0 (PR 21): the ``(1, bs, 1, D)`` K/V block's
second-to-last dim is one of ``Hkv`` heads — neither a multiple of 8 nor
the whole axis — so ``interpret=False`` raises at trace time until the
block takes all kv heads or the pool is laid out ``[nb, Hkv, bs, D]``
(ROADMAP S4).
Numerics: fp32 accumulation throughout, so outputs are allclose (not
bitwise) to the XLA paged path's fp32 softmax.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.ops.attention import NEG_INF
from chainermn_tpu.ops.flash_attention import _pick_block, _use_interpret

_LANES = 128

# (slot, kv head, KV-block slot): the first two produce disjoint output
# rows (any order), the LAST carries the online-softmax accumulators and
# must stay sequential. Interpret mode ignores this; the on-chip compile
# check is what keeps the declaration honest.
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
)


def _decode_body(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                 acc_ref, m_ref, l_ref, *, scale: float, bs: int,
                 group: int, T: int, num_block_slots: int,
                 window: Optional[int], scratch_block: Optional[int]):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos0 = pos_ref[b]
    # Whole-block liveness: any key position in logical block j inside
    # the union of the rows' causal bands [pos0 - W + 1, pos0 + T - 1].
    live = j * bs <= pos0 + (T - 1)
    if window is not None:
        live &= (j + 1) * bs - 1 > pos0 - window
    if scratch_block is not None:
        # Scratch entries (beyond-horizon redirects, released rows)
        # carry garbage by contract — the whole block is dead.
        live &= tables_ref[b, j] != scratch_block

    @pl.when(live)
    def _accumulate():
        q = q_ref[0, 0]          # [R, D] query rows for kv head h
        k = k_ref[0, :, 0, :]    # [bs, D] the gathered physical block
        v = v_ref[0, :, 0, :]

        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [R, bs]

        row = lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = j * bs + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = pos0 + row // group  # row t*group+g queries position pos0+t
        mask = (kpos <= qpos) & (row < T * group)  # causal + row padding
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0:1]  # [R, 1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Guard fully-masked ROWS: with every score NEG_INF,
        # exp(s - m_new) would be exp(0) = 1 per entry.
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_block_slots - 1)
    def _finalize():
        l = l_ref[:, 0:1]
        # Fully-masked rows (padding, never-admitted spans) emit exact 0.
        o_ref[0, 0] = jnp.where(
            l > 0, acc_ref[...] / jnp.maximum(l, 1e-37), 0.0
        ).astype(o_ref.dtype)


def paged_flash_decode(q, k_pool, v_pool, block_tables, positions, *,
                       window: Optional[int] = None,
                       scale: Optional[float] = None,
                       scratch_block: Optional[int] = 0,
                       interpret: Optional[bool] = None):
    """Fused attention of ``T >= 1`` fresh query rows per slot against a
    paged KV pool — one HBM pass, no dense view.

    Args:
      q: ``[B, T, Hq, D]`` query rows for the slots' NEWEST positions
        (row ``(b, t)`` sits at absolute position ``positions[b] + t``).
        The caller has already written the matching K/V into the pool
        (:func:`~chainermn_tpu.ops.paged_kv.paged_update` — write and
        attend stay two steps so the write path is IDENTICAL between
        the xla and fused impls).
      k_pool / v_pool: ``[num_blocks, bs, Hkv, D]`` shared pools, or
        ``[S, num_blocks, bs, Hkv, D]`` TP-stacked pools (then ``q`` is
        ``[S, B, T, Hq_local, D]``; tables/positions are shared across
        the stack and there are zero collectives inside).
      block_tables: ``[B, max_blocks]`` int32 — row ``b``'s logical →
        physical block map. Rides as a scalar-prefetch operand; the
        kernel gathers each live block once, in-kernel.
      positions: ``[B]`` int32 first-new-token position per row — the
        live-length mask (and the dead-block DMA cutoff) derive from it.
      window: optional causal sliding-window width (same band as the
        XLA decode mask: ``qpos - window < kpos <= qpos``).
      scale: score scale (default ``D ** -0.5``).
      scratch_block: physical block id whose table entries are fully
        masked (the serving pool's block 0); ``None`` disables the mask
        (the dense view, where every block is slot-owned).
      interpret: Pallas interpret mode; ``None`` auto-detects like
        flash attention (CPU tests interpret; Mosaic on TPU).

    Returns:
      ``[B, T, Hq, D]`` (or ``[S, B, T, Hq_local, D]``) attention
      output in ``q.dtype``; fp32 accumulation inside.
    """
    if k_pool.ndim == 5:
        # TP-stacked pools: per-shard calls unrolled over the (small,
        # static) stack axis — one program text, zero collectives.
        outs = [
            paged_flash_decode(
                q[s], k_pool[s], v_pool[s], block_tables, positions,
                window=window, scale=scale, scratch_block=scratch_block,
                interpret=interpret,
            )
            for s in range(k_pool.shape[0])
        ]
        return jnp.stack(outs)
    B, T, Hq, D = q.shape
    nb, bs, Hkv, Dk = k_pool.shape
    if Dk != D:
        raise ValueError(f"head_dim mismatch: q {D}, pool {Dk}")
    if Hq % Hkv:
        raise ValueError(
            f"q heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    if block_tables.shape[0] != B or positions.shape != (B,):
        raise ValueError(
            f"block_tables {block_tables.shape} / positions "
            f"{positions.shape} must lead with q's batch {B}"
        )
    group = Hq // Hkv
    M = block_tables.shape[1]
    scale = float(D ** -0.5 if scale is None else scale)
    if interpret is None:
        interpret = _use_interpret()

    # Query-row layout: [B, Hkv, R, D] with row t*group+g = (token t,
    # grouped head g) — GQA shares each K/V block read across its whole
    # q-head group. Rows padded to the f32 sublane tile; padded rows are
    # masked to an exact 0 and sliced off.
    R = T * group
    R_pad = max(8, -(-R // 8) * 8)
    q_rows = q.reshape(B, T, Hkv, group, D).transpose(0, 2, 1, 3, 4)
    q_rows = q_rows.reshape(B, Hkv, R, D)
    if R_pad != R:
        q_rows = jnp.pad(q_rows, ((0, 0), (0, 0), (0, R_pad - R), (0, 0)))

    tables = block_tables.astype(jnp.int32)
    pos = positions.astype(jnp.int32)

    def kv_index(b, h, j, tables_ref, pos_ref):
        # Dead slots (past the row's horizon / below its window band)
        # re-target one fixed block: consecutive unchanged block indices
        # revisit the resident copy, so the DMA bill is live blocks
        # only: one read of the live KV.
        live = j * bs <= pos_ref[b] + (T - 1)
        if window is not None:
            live &= (j + 1) * bs - 1 > pos_ref[b] - window
        dead = (jnp.int32(scratch_block)
                if scratch_block is not None else tables_ref[b, 0])
        return jnp.where(live, tables_ref[b, j], dead), 0, h, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, M),
        in_specs=[
            pl.BlockSpec((1, 1, R_pad, D),
                         lambda b, h, j, tables_ref, pos_ref: (b, h, 0, 0)),
            pl.BlockSpec((1, bs, 1, D), kv_index),
            pl.BlockSpec((1, bs, 1, D), kv_index),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, R_pad, D),
            lambda b, h, j, tables_ref, pos_ref: (b, h, 0, 0),
        ),
        scratch_shapes=[
            pltpu.VMEM((R_pad, D), jnp.float32),       # acc
            pltpu.VMEM((R_pad, _LANES), jnp.float32),  # running max
            pltpu.VMEM((R_pad, _LANES), jnp.float32),  # denominator
        ],
    )

    import functools

    out = pl.pallas_call(
        functools.partial(
            _decode_body, scale=scale, bs=bs, group=group, T=T,
            num_block_slots=M, window=window, scratch_block=scratch_block,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R_pad, D), q.dtype),
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
    )(tables, pos, q_rows, k_pool, v_pool)

    out = out[:, :, :R].reshape(B, Hkv, T, group, D)
    return out.transpose(0, 2, 1, 3, 4).reshape(B, T, Hq, D)


def dense_flash_decode(q, cache_k, cache_v, positions, slots=None, *,
                       window: Optional[int] = None,
                       scale: Optional[float] = None,
                       interpret: Optional[bool] = None):
    """The dense ring cache through the SAME kernel: ``[B, L, kvh, dh]``
    reshapes (zero-copy) into ``L / bs`` implicit blocks per row and an
    identity block table — per-slot prefill passes ``slots`` (``[B]``
    cache-row ids) and the table simply indexes those rows' blocks, so
    the prefill-tail view needs no gather either. No scratch block:
    every dense block is slot-owned, and the causal mask alone bounds
    the live span (exactly the XLA dense path's masking argument)."""
    Bc, L, Hkv, D = cache_k.shape
    bs = _pick_block(128, L)
    M = L // bs
    pool_k = cache_k.reshape(Bc * M, bs, Hkv, D)
    pool_v = cache_v.reshape(Bc * M, bs, Hkv, D)
    rows = (jnp.arange(Bc, dtype=jnp.int32) if slots is None
            else slots.astype(jnp.int32))
    tables = rows[:, None] * M + jnp.arange(M, dtype=jnp.int32)[None, :]
    return paged_flash_decode(
        q, pool_k, pool_v, tables, positions, window=window, scale=scale,
        scratch_block=None, interpret=interpret,
    )
