"""Pallas TPU flash-attention kernels: forward AND backward.

The hot local attention op: online-softmax accumulation entirely in VMEM, so
the ``[Tq, Tk]`` score matrix never touches HBM — HBM traffic drops from
O(T^2) to O(T * D), which is the difference between VPU-bound and MXU-bound
attention on TPU. This is one of the "native" components of the build: where
the reference's only custom kernels were fused CuPy cast/scale on the
allreduce path (``pure_nccl_communicator.py`` (dagger), SURVEY.md section
2.1), the TPU build's equivalent hand-written layer is Pallas (SURVEY.md
section 2.1 native-component note).

Forward emits the per-row logsumexp (LSE) alongside the output; backward is
the standard flash recurrence re-deriving probabilities from LSE — two
Pallas kernels (dq; dk+dv), no O(T^2) HBM tensor anywhere. The same block
kernels power the sequence-parallel ring attention
(:mod:`chainermn_tpu.parallel.ring_attention`), which rotates K/V blocks via
``ppermute`` and calls them per arriving block.

Layout: BTHD at the API (framework convention), BHTD inside the kernel grid;
LSE/delta rows are ``[B, H, T]``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.observability import train_path
from chainermn_tpu.ops.attention import NEG_INF

_LANES = 128

# All three kernels share the (B, H, space, reduce) grid shape: the first
# three dims produce disjoint output/scratch slices (any iteration order
# is valid — lets Mosaic parallelise/pipeline them), while the LAST dim
# carries the online-softmax / gradient accumulators and must stay
# sequential. Consumed only by the Mosaic lowering; interpret mode
# ignores it, so the bench kernel sweep's on-chip numerics gate is the
# check that this declaration is honest.
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
)


def _causal_mask(iq, ik, block_q, block_k, shape, window=None,
                 q_offset=0):
    """Causal mask, optionally banded to a sliding window: query at
    GLOBAL position ``i + q_offset`` sees keys ``j`` with
    ``i + q_offset - window < j <= i + q_offset`` (``window=None`` → full
    causal). ``q_offset`` aligns Q against a K axis that starts earlier —
    the sequence-parallel neighbour-tail layout."""
    q_pos = q_offset + iq * block_q + lax.broadcasted_iota(
        jnp.int32, shape, 0
    )
    k_pos = ik * block_k + lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    return mask


def _live(ik, iq, block_q, block_k, causal, window=None, q_offset=0):
    """Causal: blocks strictly above the diagonal contribute nothing — skip
    their matmuls entirely (≈2x for long sequences). A sliding window
    additionally kills blocks entirely BELOW the band (every pair with
    ``q_pos - k_pos >= window``). With the band-narrowed grids
    (``_band_k``/``_band_q``) this predicate only handles the clipped
    edge slots; the grid itself no longer visits far-out-of-band
    blocks."""
    if not causal:
        return True
    q0 = q_offset + iq * block_q  # min global q position in the block
    alive = ik * block_k <= q0 + block_q - 1
    if window is not None:
        # max k_pos in block = (ik+1)·bk - 1.
        alive &= q0 - ((ik + 1) * block_k - 1) < window
    return alive


def _band_k(block_q: int, block_k: int, window: int, nk: int,
            q_offset: int = 0):
    """Banded-grid geometry for a sliding window, iterating K blocks per
    fixed Q block: ``span`` k-block slots suffice to cover any query
    block's band ``[iq·bq - W + 1, iq·bq + bq - 1]``; ``lo(iq)`` is the
    (possibly negative) first candidate k block. Slots outside ``[0, nk)``
    are dead — the body predicates them off; index maps clip them to a
    valid (unused) block.

    ``span`` is EXACT: the k-block count for query block iq depends only
    on the residue ``r = iq·bq mod bk`` (achievable residues are the
    multiples of gcd(bq, bk)); taking the max over them avoids the
    lazy-bound's extra dead slot — at bq=bk=W it is the difference
    between 2 and 3 DMAs per row."""
    import math

    g = math.gcd(block_q, block_k)
    # Achievable start residues: (q_offset + iq*bq) mod bk ≡ q_offset
    # (mod g). Python // floors (also for negative numerators), which is
    # what the band-start index needs.
    span = max(
        (r + block_q - 1) // block_k - ((r - window + 1) // block_k) + 1
        for r in range(q_offset % g, block_k, g)
    )
    span = min(nk, span)

    shift = nk + (abs(q_offset) // block_k + 1)

    def lo(iq):
        # floor((q_offset + iq*bq - (W-1)) / bk): shift the numerator
        # non-negative so truncating traced-int division equals floor.
        return (
            q_offset + iq * block_q - (window - 1) + shift * block_k
        ) // block_k - shift

    return span, lo


def _band_q(block_q: int, block_k: int, window: int, nq: int,
            q_offset: int = 0):
    """Banded-grid geometry iterating Q blocks per fixed K block: the
    queries that can see k block ik lie (in LOCAL q coordinates) in
    ``[ik·bk - q_offset, ik·bk - q_offset + bk + W - 2]`` (causal lower
    edge + window upper edge). With ``q_offset > 0`` the low end can go
    negative and the high end overshoot ``nq`` — both are dead slots.
    ``span`` is exact by the same residue enumeration as
    :func:`_band_k`."""
    import math

    g = math.gcd(block_q, block_k)
    span = max(
        (r + block_k + window - 2) // block_q + 1
        for r in range((-q_offset) % g, block_q, g)
    )
    span = min(nq, span)

    shift = nq + (abs(q_offset) // block_q + 1)

    def lo(ik):
        return (ik * block_k - q_offset + shift * block_q) // block_q - shift

    return span, lo


def _clipped_slot(lo, n):
    """Slot→true-block mapper for index maps: identity when un-banded,
    else ``clip(lo(i) + j, 0, n - 1)`` (dead slots land on a valid,
    unused block — the body's liveness predicate skips them)."""
    if lo is None:
        return lambda i, j: j
    return lambda i, j: jnp.clip(lo(i) + j, 0, n - 1)


def _pick_block(requested: int, T: int) -> int:
    """Largest block <= requested that divides ``T``: halve until it fits
    (T=768 with a 512 request -> 256), else fall back to one whole-T block.
    Keeps any sequence length runnable under the large default blocks."""
    b = min(requested, T)
    while T % b and b > 8:
        b //= 2
    return b if T % b == 0 else T


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _seg_mask(sq_ref, sk_ref):
    """Segment mask from the per-block segment-id refs: attention is
    allowed only within the same packed segment.

    The q-ids ref is ``[1, block_q, 1]`` and the kv-ids ref
    ``[1, 1, block_k]`` — the host side stores ids as ``[B, T, 1]`` /
    ``[B, 1, T]`` so every Mosaic tile is (major divisible-by-8-or-full,
    minor 1-or-divisible-by-128)-legal AND arrives already column/row
    shaped: the mask is one VPU broadcast-compare, no in-kernel
    transpose. A flat ``[B, T]`` layout with ``(1, block)`` tiles is
    rejected by the Mosaic lowering (sublane dim 1 ≠ B) — caught on
    hardware by the bench kernel sweep; interpret mode accepts it."""
    sq = sq_ref[0]  # [block_q, 1]
    sk = sk_ref[0]  # [1, block_k]
    return sq == sk


def _fwd_body(q_ref, k_ref, v_ref, seg_refs, bias_ref, o_ref, lse_ref,
              acc_ref, m_ref, l_ref, *,
              scale: float, causal: bool, block_q: int, block_k: int,
              num_k_blocks: int, window=None, band_lo=None, nk_total=None,
              q_offset: int = 0):
    iq = pl.program_id(2)
    j = pl.program_id(3)
    # Banded grid: slot j covers TRUE k block band_lo(iq) + j; slots
    # falling outside [0, nk_total) are dead padding.
    ik = j if band_lo is None else band_lo(iq) + j

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = _live(ik, iq, block_q, block_k, causal, window, q_offset)
    if band_lo is not None:
        live &= (ik >= 0) & (ik < nk_total)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0, 0]  # [block_q, D]
        k = k_ref[0, 0]  # [block_k, D]
        v = v_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)

        mask = None
        if causal:
            mask = _causal_mask(iq, ik, block_q, block_k, s.shape, window,
                                q_offset)
        if seg_refs is not None:
            sm = _seg_mask(*seg_refs)
            mask = sm if mask is None else mask & sm
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0:1]  # [block_q, 1]
        l_prev = l_ref[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # Guard fully-masked ROWS: with every score NEG_INF, exp(s - m_new)
        # would be exp(0) = 1 per entry; the mask re-zeroes them.
        p = jnp.exp(s - m_new)
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)  # [block_q, 1]
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        m = m_ref[:, 0:1]
        l = l_ref[:, 0:1]
        o_ref[0, 0] = jnp.where(
            l > 0, acc_ref[...] / jnp.maximum(l, 1e-37), 0.0
        ).astype(o_ref.dtype)
        # LSE in the scaled-score domain; fully-masked rows stay NEG_INF.
        lse = jnp.where(
            l > 0, m + jnp.log(jnp.maximum(l, 1e-37)), NEG_INF
        )  # [block_q, 1]
        lse_ref[0, 0] = lse


def _group(Hq: int, Hkv: int) -> int:
    """GQA group size: q heads per kv head (MQA when Hkv == 1)."""
    if Hq % Hkv:
        raise ValueError(
            f"q heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    return Hq // Hkv


def _split_refs(refs, n_fixed, has_segments, has_bias):
    """Split a kernel's positional refs into (seg_refs, bias_ref, rest)
    after ``n_fixed`` fixed inputs — shared by all three kernels."""
    i = n_fixed
    seg_refs = None
    if has_segments:
        seg_refs = (refs[i], refs[i + 1])
        i += 2
    bias_ref = None
    if has_bias:
        bias_ref = refs[i]
        i += 1
    return seg_refs, bias_ref, refs[i:]


def _bias_spec(bias, block_q, block_k, swap=False, k_of=None, q_of=None):
    """BlockSpec for an additive bias ``[B|1, H|1, Tq, Tk]`` — size-1
    leading dims broadcast via the index map. ``swap=True`` for grids
    whose 3rd/4th program ids are (ik, iq) instead of (iq, ik).
    ``k_of(iq, j)`` / ``q_of(ik, j)`` translate a banded-grid slot to the
    true (clipped) block index."""
    bb = 0 if bias.shape[0] == 1 else None
    bh = 0 if bias.shape[1] == 1 else None

    def idx(b, h, i, j):
        if swap:
            ik = i
            iq = q_of(i, j) if q_of is not None else j
        else:
            iq = i
            ik = k_of(i, j) if k_of is not None else j
        return (bb if bb is not None else b,
                bh if bh is not None else h, iq, ik)

    return pl.BlockSpec((1, 1, block_q, block_k), idx)


def _flash_fwd_bhtd(q, k, v, seg_q=None, seg_k=None, bias=None, *, causal,
                    scale, block_q, block_k, interpret, window=None,
                    q_offset=0):
    """BHTD forward → (out [B,H,Tq,D], lse [B,H,Tq]).

    ``k``/``v`` may carry FEWER heads than ``q`` (GQA/MQA): kv head
    ``h // g`` serves q head ``h`` via the BlockSpec index map — no
    materialized ``jnp.repeat``. ``seg_q``/``seg_k`` are optional
    ``[B, T]`` int32 packed-segment ids; ``bias`` an optional additive
    ``[B|1, H|1, Tq, Tk]`` score bias (ALiBi etc.), tiled per block."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    g = _group(H, k.shape[1])
    block_q = _pick_block(block_q, Tq)
    block_k = _pick_block(block_k, Tk)
    nq, nk = Tq // block_q, Tk // block_k

    # Banded grid: with a sliding window, only `span` k-block slots per
    # query block can intersect the band — iterate those instead of all
    # nk, making DMA traffic and grid steps O(T·W) too (not just matmuls).
    band_lo = None
    grid_k = nk
    if causal and window is not None:
        span, lo = _band_k(block_q, block_k, window, nk, q_offset)
        if span < nk:
            band_lo, grid_k = lo, span

    k_block = _clipped_slot(band_lo, nk)

    params = dict(scale=scale, causal=causal,
                  block_q=block_q, block_k=block_k, num_k_blocks=grid_k,
                  window=window, band_lo=band_lo, nk_total=nk,
                  q_offset=q_offset)
    in_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, h, iq, j: (b, h // g, k_block(iq, j), 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, h, iq, j: (b, h // g, k_block(iq, j), 0)),
    ]
    has_segments = seg_q is not None
    has_bias = bias is not None
    args = (q, k, v)
    if has_segments:
        in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, h, iq, ik: (b, iq, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, h, iq, j: (b, 0, k_block(iq, j))),
        ]
        args += (seg_q[:, :, None], seg_k[:, None, :])
    if has_bias:
        in_specs.append(
            _bias_spec(bias, block_q, block_k, k_of=k_block)
        )
        args += (bias,)

    def kernel(*refs):
        seg_refs, bias_ref, rest = _split_refs(
            refs, 3, has_segments, has_bias
        )
        o_ref, lse_ref, acc, m, l = rest
        _fwd_body(refs[0], refs[1], refs[2], seg_refs, bias_ref,
                  o_ref, lse_ref, acc, m, l, **params)

    with jax.named_scope(train_path.FLASH_FWD):
        return pl.pallas_call(
            kernel,
            name=train_path.FLASH_FWD,
            grid=(B, H, nq, grid_k),
            compiler_params=_GRID_SEMANTICS,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, block_q, D),
                             lambda b, h, iq, ik: (b, h, iq, 0)),
                pl.BlockSpec((1, 1, block_q, 1),
                             lambda b, h, iq, ik: (b, h, iq, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype),
                jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),      # acc
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # m
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # l
            ],
            interpret=interpret,
        )(*args)


# ---------------------------------------------------------------------------
# Backward: dq kernel (iterate K blocks per fixed Q block)
# ---------------------------------------------------------------------------

def _bwd_dq_body(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seg_refs,
                 bias_ref, dq_ref, dq_acc, *,
                 scale: float, causal: bool, block_q: int, block_k: int,
                 num_k_blocks: int, window=None, band_lo=None,
                 nk_total=None, q_offset: int = 0):
    iq = pl.program_id(2)
    j = pl.program_id(3)
    ik = j if band_lo is None else band_lo(iq) + j

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = _live(ik, iq, block_q, block_k, causal, window, q_offset)
    if band_lo is not None:
        live &= (ik >= 0) & (ik < nk_total)

    @pl.when(live)
    def _accumulate():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]    # [block_q, 1]
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        mask = None
        if causal:
            mask = _causal_mask(iq, ik, block_q, block_k, s.shape, window,
                                q_offset)
        if seg_refs is not None:
            sm = _seg_mask(*seg_refs)
            mask = sm if mask is None else mask & sm
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        # p from the saved LSE: exp(NEG_INF - lse) underflows to exactly 0,
        # so masked/never-attended entries contribute nothing.
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Backward: dk/dv kernel (iterate Q blocks per fixed K block)
# ---------------------------------------------------------------------------

def _bwd_dkv_body(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seg_refs,
                  bias_ref, dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  num_q_blocks: int, window=None, band_lo=None,
                  nq_total=None, q_offset: int = 0):
    ik = pl.program_id(2)
    j = pl.program_id(3)
    iq = j if band_lo is None else band_lo(ik) + j

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = _live(ik, iq, block_q, block_k, causal, window, q_offset)
    if band_lo is not None:
        # With q_offset > 0 the low end can undershoot too.
        live &= (iq >= 0) & (iq < nq_total)

    if dbias_ref is not None and causal:
        # Each (iq, ik) tile is visited exactly once in this grid; dead
        # (causal-skipped) tiles must still write zeros — Pallas outputs
        # are not pre-zeroed.
        @pl.when(jnp.logical_not(live))
        def _zero_dbias():
            dbias_ref[0, 0] = jnp.zeros_like(dbias_ref[0, 0])

    @pl.when(live)
    def _accumulate():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]    # [block_q, 1]
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, 0].astype(jnp.float32)
        mask = None
        if causal:
            mask = _causal_mask(iq, ik, block_q, block_k, s.shape, window,
                                q_offset)
        if seg_refs is not None:
            sm = _seg_mask(*seg_refs)
            mask = sm if mask is None else mask & sm
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_k]
        # dv += p^T @ do
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_unscaled = p * (dp - delta)  # d loss / d s_total
        if dbias_ref is not None:
            # dbias tile == ds before the qk-scale factor (the bias adds
            # AFTER the scale multiplies q·k).
            dbias_ref[0, 0] = ds_unscaled.astype(dbias_ref.dtype)
        ds = ds_unscaled * scale  # [block_q, block_k]
        # dk += ds^T @ q
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_bhtd(q, k, v, do, lse, delta, seg_q=None, seg_k=None,
                    bias=None, want_dbias=False, *,
                    causal, scale, block_q, block_k, interpret, window=None,
                    q_offset=0):
    """BHTD backward → ``(dq, dk, dv[, dbias])``, each f32, given saved
    LSE and ``delta = rowsum(do * o)``. With GQA (kv heads Hkv < Hq),
    dk/dv come back at the KV head count: the per-q-head contributions
    are written per-head and group-summed outside the kernel.
    ``want_dbias`` materializes the full ``[B, H, Tq, Tk]`` f32 bias
    gradient (then reduced to ``bias``'s broadcast shape) — O(B·H·T²)
    regardless of the bias's own broadcast shape; see the public
    docstring's sizing caution."""
    B, H, Tq, D = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    g = _group(H, Hkv)
    block_q = _pick_block(block_q, Tq)
    block_k = _pick_block(block_k, Tk)
    nq, nk = Tq // block_q, Tk // block_k
    has_segments = seg_q is not None
    has_bias = bias is not None
    assert not (want_dbias and not has_bias)

    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, i, j: (b, h, i, 0))

    # Banded grids (see _flash_fwd_bhtd): dq iterates only the k blocks in
    # the window band; dk/dv only the q blocks that can see this k block.
    # want_dbias forces the full grid — its output tiles every (iq, ik).
    k_band_lo = None
    grid_k = nk
    q_band_lo = None
    grid_q = nq
    if causal and window is not None:
        span_k, lo_k = _band_k(block_q, block_k, window, nk, q_offset)
        if span_k < nk:
            k_band_lo, grid_k = lo_k, span_k
        if not want_dbias:
            span_q, lo_q = _band_q(block_q, block_k, window, nq, q_offset)
            if span_q < nq:
                q_band_lo, grid_q = lo_q, span_q

    k_block = _clipped_slot(k_band_lo, nk)
    q_block = _clipped_slot(q_band_lo, nq)

    dq_params = dict(scale=scale, causal=causal,
                     block_q=block_q, block_k=block_k, num_k_blocks=grid_k,
                     window=window, band_lo=k_band_lo, nk_total=nk,
                     q_offset=q_offset)
    dq_in_specs = [
        q_spec,
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, h, i, j: (b, h // g, k_block(i, j), 0)),
        pl.BlockSpec((1, 1, block_k, D),
                     lambda b, h, i, j: (b, h // g, k_block(i, j), 0)),
        q_spec,
        row_spec,
        row_spec,
    ]
    dq_args = (q, k, v, do, lse, delta)
    if has_segments:
        dq_in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, h, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, h, i, j: (b, 0, k_block(i, j))),
        ]
        dq_args += (seg_q[:, :, None], seg_k[:, None, :])
    if has_bias:
        dq_in_specs.append(_bias_spec(bias, block_q, block_k, k_of=k_block))
        dq_args += (bias,)

    def dq_kernel(*refs):
        seg_refs, bias_ref, rest = _split_refs(
            refs, 6, has_segments, has_bias
        )
        dq_ref, dq_acc = rest
        _bwd_dq_body(refs[0], refs[1], refs[2], refs[3], refs[4], refs[5],
                     seg_refs, bias_ref, dq_ref, dq_acc, **dq_params)

    with jax.named_scope(train_path.FLASH_BWD_DQ):
        dq = pl.pallas_call(
            dq_kernel,
            name=train_path.FLASH_BWD_DQ,
            grid=(B, H, nq, grid_k),
            compiler_params=_GRID_SEMANTICS,
            in_specs=dq_in_specs,
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), jnp.float32),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
            interpret=interpret,
        )(*dq_args)

    # dk/dv grid iterates Q heads; with GQA each q head writes its own
    # [B, H, Tk, D] slot (no cross-head accumulation inside the grid) and
    # the group sum happens below. Grid program ids here are (ik, iq).
    k_spec_in = pl.BlockSpec((1, 1, block_k, D),
                             lambda b, h, i, j: (b, h // g, i, 0))
    k_spec_out = pl.BlockSpec((1, 1, block_k, D),
                              lambda b, h, i, j: (b, h, i, 0))
    dkv_params = dict(scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, num_q_blocks=grid_q,
                      window=window, band_lo=q_band_lo, nq_total=nq,
                      q_offset=q_offset)
    dkv_in_specs = [
        pl.BlockSpec((1, 1, block_q, D),
                     lambda b, h, i, j: (b, h, q_block(i, j), 0)),
        k_spec_in,
        k_spec_in,
        pl.BlockSpec((1, 1, block_q, D),
                     lambda b, h, i, j: (b, h, q_block(i, j), 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda b, h, i, j: (b, h, q_block(i, j), 0)),
        pl.BlockSpec((1, 1, block_q, 1),
                     lambda b, h, i, j: (b, h, q_block(i, j), 0)),
    ]
    dkv_args = (q, k, v, do, lse, delta)
    if has_segments:
        dkv_in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda b, h, i, j: (b, q_block(i, j), 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, i)),
        ]
        dkv_args += (seg_q[:, :, None], seg_k[:, None, :])
    if has_bias:
        dkv_in_specs.append(
            _bias_spec(bias, block_q, block_k, swap=True, q_of=q_block)
        )
        dkv_args += (bias,)

    out_specs = [k_spec_out, k_spec_out]
    out_shape = [
        jax.ShapeDtypeStruct((B, H, Tk, D), jnp.float32),
        jax.ShapeDtypeStruct((B, H, Tk, D), jnp.float32),
    ]
    if want_dbias:
        out_specs.append(
            pl.BlockSpec((1, 1, block_q, block_k),
                         lambda b, h, i, j: (b, h, j, i))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((B, H, Tq, Tk), jnp.float32)
        )

    def dkv_kernel(*refs):
        seg_refs, bias_ref, rest = _split_refs(
            refs, 6, has_segments, has_bias
        )
        if want_dbias:
            dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc = rest
        else:
            dk_ref, dv_ref, dk_acc, dv_acc = rest
            dbias_ref = None
        _bwd_dkv_body(refs[0], refs[1], refs[2], refs[3], refs[4], refs[5],
                      seg_refs, bias_ref, dk_ref, dv_ref, dbias_ref,
                      dk_acc, dv_acc, **dkv_params)

    with jax.named_scope(train_path.FLASH_BWD_DKV):
        res = pl.pallas_call(
            dkv_kernel,
            name=train_path.FLASH_BWD_DKV,
            grid=(B, H, nk, grid_q),
            compiler_params=_GRID_SEMANTICS,
            in_specs=dkv_in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
            interpret=interpret,
        )(*dkv_args)
    if want_dbias:
        dk, dv, dbias = res
    else:
        dk, dv = res
        dbias = None
    if g > 1:
        dk = dk.reshape(B, Hkv, g, Tk, D).sum(axis=2)
        dv = dv.reshape(B, Hkv, g, Tk, D).sum(axis=2)
    if want_dbias:
        # Reduce to the bias's broadcast shape.
        if bias.shape[1] == 1:
            dbias = dbias.sum(axis=1, keepdims=True)
        if bias.shape[0] == 1:
            dbias = dbias.sum(axis=0, keepdims=True)
        return dq, dk, dv, dbias
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public op: BTHD custom_vjp
# ---------------------------------------------------------------------------

def interpret_on(platform: str) -> bool:
    """Whether Pallas kernels are interpreted on ``platform``: Mosaic
    compiles them on ``'tpu'``, the interpreter runs them on ``'cpu'``
    (the test meshes). Any other accelerator is an error — interpret
    mode there would train at emulator speed without saying so."""
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for TPU only; platform {platform!r} "
        "is neither 'tpu' nor the 'cpu' interpreter"
    )


def _use_interpret() -> bool:
    """:func:`interpret_on` the platform the computation will run on: an
    explicit ``jax_default_device`` override (the test harness pins CPU)
    before the backend default."""
    default = jax.config.jax_default_device
    if default is not None:
        # May be a Device object or a platform string (both accepted by JAX).
        return interpret_on(getattr(default, "platform", default))
    return interpret_on(jax.default_backend())


def _to_bhtd(x):
    return x.transpose(0, 2, 1, 3)


# One custom_vjp covers every operand combination: seg/bias are always
# passed (zero-size dummies when unused, selected by the static has_*
# flags), which avoids a per-combination class explosion.
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12, 13))
def _flash_core(q, k, v, seg, bias, has_seg, has_bias, bias_grad, causal,
                scale, block_q, block_k, interpret, window):
    # Primal == fwd minus the residuals: ONE body owns the operand
    # plumbing so primal and vjp forwards can never diverge.
    out, _res = _flash_core_fwd(
        q, k, v, seg, bias, has_seg, has_bias, bias_grad, causal, scale,
        block_q, block_k, interpret, window,
    )
    return out


def _flash_core_fwd(q, k, v, seg, bias, has_seg, has_bias, bias_grad,
                    causal, scale, block_q, block_k, interpret, window):
    out, lse = _flash_fwd_bhtd(
        _to_bhtd(q), _to_bhtd(k), _to_bhtd(v),
        seg if has_seg else None, seg if has_seg else None,
        bias if has_bias else None,  # bias is already scores-layout BHQK
        causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window,
    )
    return _to_bhtd(out), (q, k, v, seg, bias, out, lse)  # out in BHTD


def _flash_core_bwd(has_seg, has_bias, bias_grad, causal, scale, block_q,
                    block_k, interpret, window, res, g):
    q, k, v, seg, bias, out_bhtd, lse = res
    do = _to_bhtd(g)
    # delta_i = sum_d dO_i . O_i — the rowwise correction term of the flash
    # backward (re-derives softmax jacobian contributions without P).
    delta = jnp.sum(do.astype(jnp.float32) * out_bhtd.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B, H, Tq, 1] (kernel layout)
    res_bwd = _flash_bwd_bhtd(
        _to_bhtd(q), _to_bhtd(k), _to_bhtd(v), do, lse, delta,
        seg if has_seg else None, seg if has_seg else None,
        bias if has_bias else None, bias_grad,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window,
    )
    dq, dk, dv = res_bwd[:3]
    if bias_grad:
        dbias = res_bwd[3].astype(bias.dtype)  # already BHQK
    else:
        # No-grad bias (the common ALiBi/static case): a zero cotangent —
        # callers training a bias must pass bias_grad=True.
        dbias = jnp.zeros_like(bias)
    return (
        _to_bhtd(dq).astype(q.dtype),
        _to_bhtd(dk).astype(k.dtype),
        _to_bhtd(dv).astype(v.dtype),
        None,  # integer segment ids carry no gradient
        dbias,
    )


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    bias_grad: bool = False,
    window: Optional[int] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention on ``[B, T, H, D]`` inputs, Pallas forward AND
    backward (both VMEM-blocked; the score matrix never exists in HBM in
    either direction).

    ``k``/``v`` may carry fewer heads than ``q`` (GQA/MQA — q heads must be
    a multiple of kv heads; kv blocks are shared via the kernel's index map,
    never materialized per-group). ``segment_ids`` is an optional ``[B, T]``
    int array for packed sequences: attention is confined to positions with
    equal ids (composes with ``causal``).

    ``bias`` is an optional additive score bias ``[B|1, H|1, Tq, Tk]``
    (BTHD-external layout ``[B|1, Tq, H|1, Tk]`` is NOT used — pass the
    scores layout directly; size-1 batch/head dims broadcast). Applied
    after the qk scale, before masking — the ALiBi/relative-position hook.
    By default the bias gets a ZERO cotangent (static biases); pass
    ``bias_grad=True`` to materialize the true gradient. CAUTION: the
    intermediate dbias buffer is the FULL ``[B, H, Tq, Tk]`` f32 tensor
    (reduced to the bias's broadcast shape only afterwards) — for a
    broadcast bias that is B·H/broadcast-factor times the bias itself;
    size it before asking (e.g. B8·H16·T8192² f32 = 32 GiB). Flash memory
    behaviour is forfeited by request here and nowhere else.

    ``window`` is a causal sliding window (Mistral-style local attention):
    query ``i`` attends to keys ``j`` with ``i - window < j <= i``.
    Requires ``causal=True``. Composes with segment ids, GQA, and bias.
    The kernel grids are BAND-NARROWED: per query block only the k blocks
    that can intersect its window band are visited (and symmetrically for
    dk/dv), so compute, DMA traffic, and grid steps are all O(T·window)
    — true local-attention cost, not just predicated-off matmuls. One
    exception: ``bias_grad=True`` forces the dk/dv kernel back to the
    full grid (its dbias output must tile every (iq, ik)).

    On TPU the kernels compile via Mosaic; elsewhere (CPU tests) they run in
    Pallas interpreter mode unless ``interpret=False``.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    has_seg = segment_ids is not None
    has_bias = bias is not None
    if bias_grad and not has_bias:
        raise ValueError("bias_grad=True without a bias")
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (the sliding "
                             "window is defined over past positions)")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if has_bias:
        if bias.ndim != 4 or bias.shape[0] not in (1, q.shape[0]) \
                or bias.shape[1] not in (1, q.shape[2]) \
                or bias.shape[2] != q.shape[1] or bias.shape[3] != k.shape[1]:
            raise ValueError(
                f"bias must be [B|1, H|1, Tq, Tk] = "
                f"[{q.shape[0]}|1, {q.shape[2]}|1, {q.shape[1]}, "
                f"{k.shape[1]}], got {bias.shape}"
            )
    seg = (segment_ids.astype(jnp.int32) if has_seg
           else jnp.zeros((0,), jnp.int32))
    b = bias if has_bias else jnp.zeros((0,), q.dtype)
    return _flash_core(q, k, v, seg, b, has_seg, has_bias, bias_grad,
                       causal, scale, block_q, block_k, interpret, window)


# ---------------------------------------------------------------------------
# Block-level entry points for ring attention
# ---------------------------------------------------------------------------

def flash_block_fwd(q, k_blk, v_blk, *, causal, scale, block_q, block_k,
                    interpret, seg_q=None, seg_kv=None, window=None,
                    q_offset=0):
    """One ring step's forward: full flash over the resident Q shard and ONE
    arriving K/V block, returning BTHD output + ``[B, H, Tq]`` LSE. The ring
    merges successive blocks' (out, lse) partials in log space
    (:func:`chainermn_tpu.parallel.ring_attention.merge_partials`).
    ``seg_q``/``seg_kv`` are the per-shard segment-id slices (the kv ids
    travel with their block around the ring)."""
    out, lse = _flash_fwd_bhtd(
        _to_bhtd(q), _to_bhtd(k_blk), _to_bhtd(v_blk), seg_q, seg_kv,
        causal=causal, window=window, q_offset=q_offset,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return _to_bhtd(out), lse[..., 0]


def flash_block_bwd(q, k_blk, v_blk, do, lse, delta, *, causal, scale,
                    block_q, block_k, interpret, seg_q=None, seg_kv=None,
                    window=None, q_offset=0):
    """One ring step's backward: (dq, dk_blk, dv_blk) contributions for one
    K/V block, f32, BTHD (lse/delta are ``[B, H, Tq]``)."""
    dq, dk, dv = _flash_bwd_bhtd(
        _to_bhtd(q), _to_bhtd(k_blk), _to_bhtd(v_blk), _to_bhtd(do),
        lse[..., None], delta[..., None], seg_q, seg_kv,
        causal=causal, scale=scale, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return _to_bhtd(dq), _to_bhtd(dk), _to_bhtd(dv)
