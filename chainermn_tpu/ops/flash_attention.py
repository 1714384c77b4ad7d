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

Iteration geometry, the same for the three kernels (:func:`_geometry`
derives it from the lengths and the mask kind unless the caller passes
``block_q`` / ``block_k``): a grid ``(B, head blocks, space, reduce)`` of
tiles that are long along the axis a kernel walks (1024 keys for the
forward and dq, 1024 queries for dk/dv) and 512 across it. Under a causal
mask a tile is one of three kinds (:func:`_tile_class`): wholly above the
diagonal — no matmul, no vector work and, because the index maps repeat
the last live block there, no DMA; wholly below — computed with no mask
at all; crossed by the diagonal. In the forward and dq a crossed tile is walked in
sub-tiles of 512 keys inside the grid step, of which those above the
diagonal are skipped, the one it crosses builds the iota/compare/select
mask and the rest are computed without one (:func:`_pieces`); dk/dv masks
a crossed tile whole, and where nothing but the causal mask acts on the
scores (and a head's row has at most 512 bytes) its tile is 1024 x 1024.
A kernel whose walked axis is one tile long (T <= 1024) keeps no running
statistics or accumulators between steps. What that saves depends on the
lengths: at T 1024 the forward and dq visit 3 of the square's 4 sub-tiles
and mask 2, dk/dv computes its one tile; at T 2048, 10 of 16 and 4 (dk/dv
3 of 4 and 2); at T 4096, 36 of 64 and 8 (dk/dv 10 of 16 and 4); under the
former 512 x 1024 default 2 of 2, 6 of 8 and 20 of 32, every one masked.
A window, or a ``q_offset`` off the sub-tile grid, masks a crossed tile
whole in every kernel; segment ids and a bias keep their work on every
visited sub-tile, and with either, or a window, dk/dv keeps the 512 x 1024
tile. A causal mask by blocks (``causal_block``, ``causal_strict``: block
diffusion's, a query sees the keys of earlier blocks of ``bl`` positions
and, unless strict, of its own) is the same stair with steps of ``bl``:
the diagonal is the queries' :func:`_horizon`, tiles are sorted, skipped
and walked by it, and as long as ``bl`` divides the sub-tile the stair
stays inside the one sub-tile the diagonal crosses (at ``bl`` 4 and T
8192 the tiles visited are the causal mask's). With blocks of one,
inclusive, nothing of a kernel changes. The gauge ``flash_tiles`` (labels
``kernel``, ``kind``) says what the last call of the op does.

Layout: ``[B, T, H, D]`` at the API, and the kernels read and write the
layout the projections round them produce and consume (:class:`_Layout`):
``[B, T, H * D]`` rows, a head being a block of columns, in the operands'
dtype, so the reshape at the op's door transposes nothing and no XLA op
stands between the qkv matmul's slices, the kernels and the output
projection, forward or backward. Heads of a multiple of 128 lanes take
one head a grid step; heads that divide a lane tile (64: two) share one
128-lane block, each read and written through its own lanes of the block
(:func:`_head_lanes`) and walked one after the other
(:func:`_each_head`). Anything else (odd widths, GQA at heads under 128)
goes through the same kernels as ``[B * H, T, D]``, one head a "batch"
row, and for those the wrapper
transposes as it always did. The gauge ``flash_heads_per_block`` (label
``kernel``) says which: 2, 1, or 0 for the transposed form. The values'
heads may be narrower than the keys' (latent attention's 128 under keys
of 128 + 64): ``PV``, the output, ``dO``, ``dv`` and ``delta`` then run
at the values' width, ``QK^T``, ``dq`` and ``dk`` at the keys'; both
whole lane tiles keep the projections' rows, one head a step, anything
else the transposed form. At one width nothing of a kernel changes.
dq, dk, dv
leave the kernels rounded once, from the float32 scratch they are summed
in, to their operand's dtype (the ring's partial gradients to float32;
with GQA dk/dv are float32 a q head until the group sum).

The log-sum-exp crosses HBM with ``T`` on the lane axis, from the forward
to the backward kernels: ``[B, H, 1, T]`` in blocks of ``(1, heads, 1,
block_q)``, a value 4 bytes (with ``T`` second to last and a unit minor
dimension a value would occupy a 128-lane tile row, 128 times its bytes,
and XLA would squeeze and pad round every kernel). The forward turns its
``[block_q, 1]`` column into the row once a q tile, the backward kernels
turn the row back into the column their scores are corrected by; the
ring's entry points speak ``[B, H, T]``. ``delta = rowsum(dO * O)`` is
no array: both backward kernels take the forward's output beside ``dO``
and sum the rows themselves (:func:`_delta`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.observability import train_path
from chainermn_tpu.ops.attention import NEG_INF

_LANES = 128

# All three kernels share the (B, head blocks, space, reduce) grid shape:
# the first three dims produce disjoint output/scratch slices (any order
# is valid — lets Mosaic parallelise/pipeline them), while the LAST dim
# carries the online-softmax / gradient accumulators and must stay
# sequential. Consumed only by the Mosaic lowering; interpret mode
# ignores it, so the bench kernel sweep's on-chip numerics gate is the
# check that this declaration is honest.
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
)


def _blocks(causal_block: int, causal_strict: bool):
    """The kernels' static description of a causal mask by blocks:
    ``None`` for today's ``q_pos >= k_pos`` (blocks of one position,
    inclusive: nothing of a kernel changes), else ``(bl, strict)``: a
    query sees the keys of earlier blocks of ``bl`` positions and, unless
    ``strict``, of its own: ``q_pos // bl >= k_pos // bl`` (block
    diffusion's clean rows) or ``q_pos // bl > k_pos // bl`` (its noised
    rows on the clean keys)."""
    if causal_block < 1:
        raise ValueError(f"causal_block must be >= 1, got {causal_block}")
    if causal_block == 1 and not causal_strict:
        return None
    return int(causal_block), bool(causal_strict)


def _horizon(q_pos, blocks):
    """The last key position a query at ``q_pos`` sees under the causal
    mask (``blocks`` as :func:`_blocks` makes it); -1 where it sees none.
    Monotone in ``q_pos``, for Python ints, traced ints and numpy grids
    of positions that are not negative."""
    if blocks is None:
        return q_pos
    bl, strict = blocks
    start = q_pos if bl == 1 else (q_pos // bl) * bl
    return start - 1 if strict else start + (bl - 1)


def _first_query(k_pos, blocks):
    """The first query position that sees the key at ``k_pos``:
    :func:`_horizon`'s inverse."""
    if blocks is None:
        return k_pos
    bl, strict = blocks
    start = k_pos if bl == 1 else (k_pos // bl) * bl
    return start + bl if strict else start


def _causal_mask(q0, k0, shape, window=None, blocks=None):
    """Causal mask of a score tile whose first query and first key sit at
    GLOBAL positions ``q0`` and ``k0``, optionally banded to a sliding
    window: a query at ``i`` sees keys ``j`` with ``i - window < j <= i``
    (``window=None`` → full causal), or by blocks (``blocks``,
    :func:`_blocks`): a query sees the keys up to its :func:`_horizon`,
    which is made on the column of queries and compared along the keys.
    A caller folds ``q_offset`` (Q aligned against a K axis that starts
    earlier — the sequence-parallel neighbour-tail layout) into ``q0``."""
    if blocks is not None:
        bl, strict = blocks
        q_col = q0 + lax.broadcasted_iota(jnp.int32, (shape[0], 1), 0)
        if bl & (bl - 1) == 0:  # a power of two: the block's start by a mask
            start = q_col & jnp.int32(-bl)
        else:
            start = lax.div(q_col, jnp.int32(bl)) * bl
        k_pos = k0 + lax.broadcasted_iota(jnp.int32, shape, 1)
        return (start - 1 if strict else start + (bl - 1)) >= k_pos
    q_pos = q0 + lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    return mask


def _tile_class(ik, iq, block_q, block_k, causal, window=None, q_offset=0,
                blocks=None):
    """``(live, full)`` of tile ``(iq, ik)`` under the mask, for traced
    ints in a kernel and for numpy index grids alike.

    ``live`` is false for a tile that contributes nothing: wholly above
    the causal diagonal (every key after every query), or wholly below a
    sliding window's band (every pair with ``q_pos - k_pos >= window``).
    Such a tile is skipped whole; how many there are follows from the
    tiles alone (a k tile as long as the sequence is never above the
    diagonal). ``full`` is true where every pair of the tile is allowed,
    so the causal mask need not be built: every key at or before every
    query, and the farthest pair still inside the window. A live tile
    that is not full is crossed by the diagonal or a window edge and is
    the only kind that pays for iota, compare and select. Without
    ``causal`` every tile is live and full. With the band-narrowed grids
    (``_band_k``/``_band_q``) the predicate only sorts the slots the
    grid still visits. Under a mask by blocks (``blocks``,
    :func:`_blocks`) the diagonal is the queries' :func:`_horizon`: a
    tile is live where its last query sees its first key, and full where
    its first query sees its last."""
    if not causal:
        return True, True
    q0 = q_offset + iq * block_q  # first and last global q position
    q1 = q0 + block_q - 1
    k0 = ik * block_k
    k1 = k0 + block_k - 1
    live = k0 <= _horizon(q1, blocks)
    full = k1 <= _horizon(q0, blocks)
    if window is not None:
        live &= q0 - k1 < window
        full &= q1 - k0 < window
    return live, full


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


def _k_slot(band_lo, nk, block_q, block_k, causal, q_offset, blocks=None):
    """Slot→k-block mapper for the index maps of the kernels that walk K
    per Q block (forward, dq): slot ``j`` of q block ``iq`` is true block
    ``band_lo(iq) + j`` (``j`` itself un-banded), held inside ``[0, nk)``
    and, under a causal mask, at or before the last block a query of
    ``iq`` can see. A dead slot so names the block of the slot before it,
    which Pallas does not fetch again; the body's ``live`` skips it."""
    if band_lo is None and not causal:
        return lambda iq, j: j

    def k_block(iq, j):
        ik = j if band_lo is None else band_lo(iq) + j
        hi = nk - 1
        if causal:
            hi = jnp.minimum(
                hi, _horizon(q_offset + (iq + 1) * block_q - 1, blocks)
                // block_k
            )
        return jnp.maximum(jnp.minimum(ik, hi), 0)

    return k_block


def _q_slot(band_lo, nq, block_q, block_k, causal, q_offset, blocks=None):
    """:func:`_k_slot`'s mirror for the dk/dv kernel, which walks Q per K
    block: its dead slots come first (queries before the k block), so a
    slot is held at or after the first q block that sees ``ik``."""
    if band_lo is None and not causal:
        return lambda ik, j: j

    def q_block(ik, j):
        iq = j if band_lo is None else band_lo(ik) + j
        lo = 0
        if causal:
            lo = jnp.maximum(
                lo, (_first_query(ik * block_k, blocks) - q_offset)
                // block_q)
        return jnp.minimum(jnp.maximum(iq, lo), nq - 1)

    return q_block


def _pick_block(requested: int, T: int) -> int:
    """Largest block <= requested that divides ``T``: halve until it fits
    (T=768 with a 512 request -> 256), else fall back to one whole-T block.
    Keeps any sequence length runnable under the large default blocks."""
    b = min(requested, T)
    while T % b and b > 8:
        b //= 2
    return b if T % b == 0 else T


def _pick_row_block(requested: int, Tq: int) -> int:
    """:func:`_pick_block` for the q axis. The row statistics (log-sum-exp,
    ``delta``) cross HBM with ``Tq`` on the lane axis, and Mosaic takes a
    block of lanes only as a multiple of 128 or as the whole axis: where
    halving ends below that (T=576 with a 512 request -> 64) the block is
    the whole row. A ``requested`` block that divides ``Tq`` is the
    caller's and stays (the interpreter takes any)."""
    b = _pick_block(requested, Tq)
    return Tq if b % _LANES and b != requested else b


#: ``(block_q, block_k)`` where the caller names none, and for dk/dv where
#: nothing but the causal mask acts on the scores and a row of an operand
#: (``D`` elements) has at most ``_DKV_CAUSAL_ROW_BYTES``.
_TILES = (512, 1024)
_DKV_CAUSAL_TILES = (1024, 1024)
_DKV_CAUSAL_ROW_BYTES = 512


def _geometry(Tq, Tk, *, walks, causal, bare=True, row_bytes=0, window=None,
              q_offset=0, bias_heads=1, block_q=None, block_k=None,
              blocks=None):
    """``(block_q, block_k, sub)`` of a kernel, from what a call shows:
    the lengths, the mask kind (``bare``: no segment ids and no bias),
    the bytes of an operand's row (head width times item size; only
    dk/dv's tile depends on ``bare`` and ``row_bytes``) and which axis
    the kernel walks (``walks='k'``: forward and dq; ``walks='q'``:
    dk/dv).

    On the v5e what a grid step costs hardly falls with its tile
    (PERF.md, PR 24: at T 1024 sixteen steps of 256 x 256 a head take
    2.3 times as long as two of 512 x 1024 that compute the whole
    square), so the tiles stay long and, where K is walked, the triangle
    is walked *inside* a tile, in sub-tiles of ``sub`` keys: one grid
    step computes the sub-tiles the mask leaves alive and builds a mask
    for the one the diagonal crosses. ``sub`` is ``block_q``, which is
    what confines the diagonal to one sub-tile; where that cannot be (a
    window, a ``q_offset`` that is no multiple of it, a ``block_k`` it
    does not divide) the tile is its own single sub-tile and is masked
    whole, as every tile once was. dk/dv measured fastest on tiles it
    does not cut up (in the LM cells a 1024 x 512 tile walked in halves
    took 1.01 times the whole 1024 x 1024 at B 4 and 1.35 times at B 16),
    so its ``sub`` is the tile: it skips and unmasks whole tiles only.
    That tile is 1024 x 1024 under a bare causal mask and no window, for
    rows of up to 512 bytes (heads of 256 in bf16, of 128 in f32).
    Segment ids, a bias (and its gradient's tile), a window or wider
    rows add to what a tile holds in VMEM: Mosaic refuses 1024 x 1024
    with a bias gradient or with f32 heads of 256 (PERF.md, PR 24).
    There, and with no mask to skip by, the tile stays 512 x 1024. A
    step that computes several heads (``bias_heads``: those whose bias
    squares, and bias gradient's, a step holds at once; 1 without a
    bias) takes as many times fewer keys, down to a lane tile, so the
    bias tiles fill what one head's did. A caller's ``block_q`` /
    ``block_k`` are taken as given (``_pick_block`` still makes them
    divide the lengths). Under a mask by blocks (``blocks``,
    :func:`_blocks`) the stair's steps stay inside the diagonal's
    sub-tile as long as the block length divides ``sub``; where it does
    not, the tile is masked whole."""
    whole = (walks == "q" and causal and bare and window is None
             and row_bytes <= _DKV_CAUSAL_ROW_BYTES)
    derived = _DKV_CAUSAL_TILES if whole else _TILES
    block_q = _pick_row_block(block_q or derived[0], Tq)
    block_k = _pick_block(
        block_k or max(derived[1] // bias_heads, _LANES), Tk)
    nests = (walks == "k" and causal and window is None
             and block_k % block_q == 0 and q_offset % block_q == 0
             and (blocks is None or block_q % blocks[0] == 0))
    return block_q, block_k, block_q if nests else block_k


#: which axis each kernel walks, for :func:`_geometry`
_WALKS = {train_path.FLASH_FWD: "k", train_path.FLASH_BWD_DQ: "k",
          train_path.FLASH_BWD_DKV: "q"}


def _publish_tiles(kernels, lay, Tq, Tk, *, causal, window=None, q_offset=0,
                   has_bias=False, blocks=None, **geometry):
    """Set the ``flash_tiles`` gauge for each of ``kernels`` from a call's
    static arguments, in the sub-tiles the kernel skips and masks by:
    those of one head's square (``total``), those computed (``visited``)
    and those of them that build the causal mask (``masked``); and beside
    it ``flash_heads_per_block``: the heads a grid step takes from the
    projections' own layout, 0 where the wrapper transposed
    (``lay.heads``). The public entry points call it outside any jit of
    this module, so every call of theirs sets it, which for a jitted
    step is while the step is traced; the last call is what a scrape
    sees."""
    from chainermn_tpu.observability.metrics import registry

    heads = registry().gauge(
        train_path.FLASH_HEADS_PER_BLOCK,
        "heads a grid step of each flash kernel reads from the "
        "projections' own [B, T, H*D] rows in its last call; 0: the "
        "wrapper transposed to [B*H, T, D]",
    )
    gauge = registry().gauge(
        train_path.FLASH_TILES,
        "sub-tiles of one head's score square in the last call of each "
        "flash kernel: total, visited (computed) and masked (visited and "
        "crossed by the diagonal or a window edge)",
    )
    for kernel in kernels:
        heads.set(float(lay.heads), kernel=kernel)
        unit_q, _, unit_k = _geometry(
            Tq, Tk, walks=_WALKS[kernel], causal=causal, window=window,
            q_offset=q_offset, blocks=blocks,
            bias_heads=lay.step_heads if has_bias else 1, **geometry)
        nq, nk = Tq // unit_q, Tk // unit_k
        live, full = _tile_class(
            np.arange(nk)[None, :], np.arange(nq)[:, None], unit_q, unit_k,
            causal, window, q_offset, blocks,
        )
        live = np.broadcast_to(live, (nq, nk))
        masked = live & ~np.broadcast_to(full, (nq, nk))
        for kind, n in (("total", nq * nk), ("visited", live.sum()),
                        ("masked", masked.sum())):
            gauge.set(float(n), kernel=kernel, kind=kind)


def _pieces(index, sub, block_k):
    """The ``(start, stop, masked)`` stretches of a tile's keys that
    branch ``index`` computes. Branch 0 is the tile every pair of which
    is allowed: all of it, unmasked. Branch ``n >= 1`` is the tile the
    diagonal crosses in its ``n``-th sub-tile, which is masked; the
    sub-tiles before it make one unmasked stretch and those after it are
    skipped."""
    if index == 0:
        return [(0, block_k, False)]
    lo = (index - 1) * sub
    return ([(0, lo, False)] if lo else []) + [(lo, lo + sub, True)]


def _visit(live, index, branches, causal, tile):
    """Run ``tile(pieces)`` with the pieces of the branch ``index`` picks
    (:func:`_pieces`), on a live tile."""
    if not causal:
        tile(branches[0])
        return
    pl.when(live)(lambda: lax.switch(
        index, [functools.partial(tile, pieces) for pieces in branches]
    ))


# ---------------------------------------------------------------------------
# The layout the kernels read and write
# ---------------------------------------------------------------------------

def _group(Hq: int, Hkv: int) -> int:
    """GQA group size: q heads per kv head (MQA when Hkv == 1)."""
    if Hq % Hkv:
        raise ValueError(
            f"q heads ({Hq}) must be a multiple of kv heads ({Hkv})"
        )
    return Hq // Hkv


class _Layout(NamedTuple):
    """How the three kernels see the op's ``[B, T, H, D]`` operands,
    from head width, head count and group size alone.

    ``heads >= 1``: the projections' own rows, ``[B, T, H * D]`` (the
    reshape is no transposition: XLA hands a projection's slice over as
    it is, and re-tiles an array it was given four-dimensional, such as
    RoPE's result, in one pass), a head being a block of columns; a grid
    step takes ``heads`` of them in one block of ``heads * D`` lanes.
    Heads as wide as a lane tile or a multiple of it take one a step
    (``D % 128 == 0``; fewer KV heads are column ``h // g``). Narrower
    heads that divide a lane tile, in a row that is whole lane tiles,
    share one: ``128 // D`` heads a step (16 heads of 64: two), without
    GQA, whose group would have to share K/V lanes it does not own.
    ``heads == 0``: everything else (odd widths, GQA under 128 lanes);
    the wrapper transposes to ``[B * H, T, D]`` as it always did, and a
    head is a "batch" row whose block spans the array's last dimension.
    One set of kernels serves the three: they differ in the index maps
    (:meth:`spec`, :meth:`row_spec`) and in how many heads a step walks.
    """

    B: int
    H: int
    Hkv: int
    D: int
    heads: int
    #: the values' head width, which is the output's and its cotangent's
    #: (``value=True`` below); ``D`` unless the values have one of their
    #: own
    Dv: int

    @classmethod
    def of(cls, q_shape, k_shape, v_shape=None):
        """The layout of a call with ``q``, ``k`` (and ``v``, where its
        heads have a width of their own) of these BTHD shapes. Values
        narrower than the keys (latent attention's 128 under keys of
        128 + 64) keep the projections' rows where both widths are whole
        lane tiles, and go through the transposed form otherwise; two
        heads share a lane tile only at one width."""
        B, _, H, D = q_shape
        Hkv = k_shape[2]
        Dv = D if v_shape is None else v_shape[3]
        if Dv > D:
            raise ValueError(
                f"values wider than the keys ({Dv} > {D}) are not built: "
                "the kernels' tiles are chosen from the keys' row")
        if D % _LANES == 0 and Dv % _LANES == 0:
            heads = 1
        elif Dv == D and _group(H, Hkv) == 1 and _LANES % D == 0 \
                and (H * D) % _LANES == 0:
            heads = _LANES // D
        else:
            heads = 0
        return cls(B, H, Hkv, D, heads, Dv)

    @property
    def step_heads(self) -> int:
        """Heads one grid step computes."""
        return max(self.heads, 1)

    @property
    def width(self) -> int:
        """Lanes of a step's block of q, k, dq or dk."""
        return self.step_heads * self.D

    @property
    def v_width(self) -> int:
        """Lanes of a step's block of v, the output, dO or dv."""
        return self.step_heads * self.Dv

    @property
    def group(self) -> int:
        return _group(self.H, self.Hkv)

    def enter(self, x):
        """``[B, T, h, D]`` as the kernels take it."""
        B, T, h, D = x.shape
        if self.heads:
            return x.reshape(B, T, h * D)
        return x.transpose(0, 2, 1, 3).reshape(B * h, T, D)

    def leave(self, x, value=False):
        """A kernel's ``q``-, ``k``- or (``value``) ``v``-shaped array as
        ``[B, T, h, D]``."""
        if self.heads:
            D = self.Dv if value else self.D
            return x.reshape(*x.shape[:2], x.shape[2] // D, D)
        return x.reshape(self.B, x.shape[0] // self.B, *x.shape[1:]) \
            .transpose(0, 2, 1, 3)

    def spec(self, rows, row_of, shared=False, value=False):
        """BlockSpec of ``rows`` rows (block ``row_of(i, j)`` of the
        grid's last two ids) of a step's heads; ``shared``: of K or V,
        whose head ``h // g`` serves q head ``h``; ``value``: at the
        values' width (V, the output, dO, dv)."""
        g = self.group if shared else 1
        if self.heads:
            return pl.BlockSpec(
                (1, rows, self.v_width if value else self.width),
                lambda b, h, i, j: (b, row_of(i, j), h // g))
        n = self.H // g
        return pl.BlockSpec(
            (1, rows, self.Dv if value else self.D),
            lambda b, h, i, j: (b * n + h // g, row_of(i, j), 0))

    def value_shape(self, q_shape):
        """The shape of the output (and dO) beside ``q`` of ``q_shape``
        as the kernels take it."""
        return (*q_shape[:2], q_shape[2] // self.D * self.Dv)

    def row_spec(self, block_q, row_of):
        """BlockSpec of a step's rows of statistics in ``[B, H, 1, Tq]``:
        one row of ``block_q`` lanes a head."""
        return pl.BlockSpec((1, self.step_heads, 1, block_q),
                            lambda b, h, i, j: (b, h, 0, row_of(i, j)))

    def group_sum(self, x, value=False):
        """Per-q-head dk or (``value``) dv summed over each KV head's
        group."""
        g, T = self.group, x.shape[1]
        D = self.Dv if value else self.D
        if self.heads:
            return x.reshape(self.B, T, self.Hkv, g, D).sum(axis=3) \
                .reshape(self.B, T, self.Hkv * D)
        return x.reshape(self.B, self.Hkv, g, T, D).sum(axis=2) \
            .reshape(self.B * self.Hkv, T, D)


def _head_lanes(head: int, D: int):
    """The lanes of head ``head`` in a step's block ``[rows, heads * D]``
    (all of them where one head fills the block). A kernel reads and
    writes a head through this slice of its refs, so a head's matmuls
    and its rounding are those of a head alone in a block, whichever
    form the layout has; where two heads share a lane tile the second
    one's loads and stores are shifted by half a tile, which Mosaic does
    beside the vector work (measured against masking the other head's
    lanes off whole blocks, which cost the vector unit 7% of the
    kernels' time: PERF.md, PR 37)."""
    return slice(head * D, (head + 1) * D)


def _each_head(heads: int, head, looped: bool = False):
    """Run ``head(p)`` for every head of a step, ``p`` a Python int. By
    default the heads stand one after the other in the step's code, so
    one's vector work can run under the other's matmuls (the forward and
    dq, whose score temporaries are sub-tiles of 512 keys). ``looped``:
    they are a loop's iterations, each picking its own branch, and the
    step holds one head's temporaries (dk/dv: the two heads of its whole
    1024 x 1024 tile do not fit VMEM side by side)."""
    if heads == 1 or not looped:
        for p in range(heads):
            head(p)
        return
    branches = [functools.partial(head, p) for p in range(heads)]
    lax.fori_loop(0, heads,
                  lambda p, carry: (lax.switch(p, branches), carry)[1], 0)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _scores(q, k, bias_ref, seg_refs, masked, q0, k0, cols, *, scale, window,
            head=0, blocks=None):
    """One head's masked f32 scores of a piece of a tile: ``q k^T``
    scaled, plus the bias, ``NEG_INF`` where the segment ids differ and,
    on a ``masked`` piece, where the causal mask forbids. ``cols`` is the
    piece's slice of the tile's keys (for the bias and the segment ids,
    which arrive tile-sized), ``q0`` / ``k0`` the tile's first global
    positions, ``head`` the head's place among the block's (its bias is
    row ``head`` of the bias tile, or the one row all heads share).

    The segment-id refs are ``[1, block_q, 1]`` and ``[1, 1, block_k]``
    — the host side stores ids as ``[B, T, 1]`` / ``[B, 1, T]`` so every
    Mosaic tile is (major divisible-by-8-or-full, minor
    1-or-divisible-by-128)-legal AND arrives already column/row shaped:
    the mask is one VPU broadcast-compare, no in-kernel transpose. A flat
    ``[B, T]`` layout with ``(1, block)`` tiles is rejected by the Mosaic
    lowering (sublane dim 1 ≠ B) — caught on hardware by the bench kernel
    sweep; interpret mode accepts it."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    if bias_ref is not None:
        row = head if bias_ref.shape[1] > 1 else 0
        s = s + bias_ref[0, row, :, cols].astype(jnp.float32)
    if seg_refs is not None:
        sq_ref, sk_ref = seg_refs
        s = jnp.where(sq_ref[0] == sk_ref[0, :, cols], s, NEG_INF)
    if masked:
        s = jnp.where(
            _causal_mask(q0, k0 + (cols.start or 0), s.shape, window,
                         blocks),
            s, NEG_INF)
    return s


def _walk(iq, ik, block_q, block_k, sub, causal, window, q_offset,
          blocks=None):
    """``(q0, k0, live, index, branches)`` of tile ``(iq, ik)``: its first
    global positions, whether anything of it is computed, and the branch
    (a list of :func:`_pieces`) ``index`` picks: 0 where every pair is
    allowed, else the number of sub-tiles up to and with the one the
    diagonal crosses (1, the whole tile masked, where the tile is its
    own sub-tile)."""
    q0 = q_offset + iq * block_q
    k0 = ik * block_k
    n_sub = block_k // sub
    live, full = _tile_class(ik, iq, block_q, block_k, causal, window,
                             q_offset, blocks)
    index = 0
    if causal:
        crossed = jnp.clip((q0 + block_q - 1 - k0) // sub + 1, 1, n_sub)
        index = jnp.where(full, 0, crossed)
    branches = [_pieces(i, sub, block_k) for i in range(n_sub + 1)]
    return q0, k0, live, index, branches


def _fwd_body(q_ref, k_ref, v_ref, seg_refs, bias_ref, o_ref, lse_ref,
              acc_ref, m_ref, l_ref, *, heads: int, D: int, Dv: int,
              scale: float, causal: bool, block_q: int, block_k: int,
              sub: int, num_k_blocks: int, window=None, band_lo=None,
              nk_total=None, q_offset: int = 0, blocks=None):
    iq = pl.program_id(2)
    j = pl.program_id(3)
    # Banded grid: slot j covers TRUE k block band_lo(iq) + j; slots
    # falling outside [0, nk_total) are dead padding.
    ik = j if band_lo is None else band_lo(iq) + j
    # One k slot a row: nothing to merge, so no running statistics are
    # kept (the same sums, with the factor exp(-inf) = 0 on nothing).
    single = num_k_blocks == 1

    def _emit(p, acc, m, l):
        o_ref[0, :, _head_lanes(p, Dv)] = jnp.where(
            l > 0, acc / jnp.maximum(l, 1e-37), 0.0
        ).astype(o_ref.dtype)
        # LSE in the scaled-score domain; fully-masked rows stay NEG_INF.
        # The column is turned into the row it crosses HBM as: once a
        # q tile.
        lse_ref[0, p] = jnp.where(
            l > 0, m + jnp.log(jnp.maximum(l, 1e-37)), NEG_INF
        ).T

    if not single:
        @pl.when(j == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    q0, k0, live, index, branches = _walk(
        iq, ik, block_q, block_k, sub, causal, window, q_offset, blocks)
    if band_lo is not None:
        live &= (ik >= 0) & (ik < nk_total)

    def _accumulate(pieces):
        def head(p):
            lanes, v_lanes = _head_lanes(p, D), _head_lanes(p, Dv)
            q = q_ref[0, :, lanes]
            scores = [
                _scores(q, k_ref[0, a:b, lanes], bias_ref, seg_refs, masked,
                        q0, k0, slice(a, b), scale=scale, window=window,
                        head=p, blocks=blocks)
                for a, b, masked in pieces
            ]
            m_new = functools.reduce(jnp.maximum, [
                jnp.max(s, axis=1, keepdims=True) for s in scores
            ])  # [block_q, 1]
            if not single:
                m_prev = m_ref[p, :, 0:1]
                m_new = jnp.maximum(m_prev, m_new)
            # A masked score is NEG_INF and exp(NEG_INF - m) is exactly
            # 0, except in a row that has seen nothing yet: there m is
            # NEG_INF too and the difference 0. Subtract 0 in such a row.
            m_sub = jnp.where(m_new > NEG_INF, m_new, 0.0)
            l_new = acc = 0.0
            for s, (a, b, _) in zip(scores, pieces):
                pr = jnp.exp(s - m_sub)
                l_new += jnp.sum(pr, axis=1, keepdims=True)
                v = v_ref[0, a:b, v_lanes]
                acc += jax.lax.dot_general(
                    pr.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            if single:
                _emit(p, acc, m_new, l_new)
                return
            corr = jnp.exp(m_prev - m_new)  # [block_q, 1]
            acc_ref[:, v_lanes] = acc_ref[:, v_lanes] * corr + acc
            m_ref[p] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[p] = jnp.broadcast_to(l_ref[p, :, 0:1] * corr + l_new,
                                        l_ref.shape[1:])

        _each_head(heads, head)

    _visit(live, index, branches, causal, _accumulate)

    if single:
        if causal:
            @pl.when(jnp.logical_not(live))
            def _nothing_visible():
                o_ref[...] = jnp.zeros_like(o_ref)
                lse_ref[...] = jnp.full_like(lse_ref, NEG_INF)
        return

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        _each_head(heads, lambda p: _emit(
            p, acc_ref[:, _head_lanes(p, Dv)], m_ref[p, :, 0:1],
            l_ref[p, :, 0:1]))


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


def _bias_spec(bias, heads, block_q, block_k, swap=False, k_of=None,
               q_of=None):
    """BlockSpec for an additive bias ``[B|1, H|1, Tq, Tk]`` — size-1
    leading dims broadcast via the index map; a bias with a square a
    head brings those of a step's ``heads``. ``swap=True`` for grids
    whose 3rd/4th program ids are (ik, iq) instead of (iq, ik).
    ``k_of(iq, j)`` / ``q_of(ik, j)`` translate a grid slot to the true
    (clipped) block index."""
    bb = 0 if bias.shape[0] == 1 else None
    bh = 0 if bias.shape[1] == 1 else None

    def idx(b, h, i, j):
        if swap:
            ik = i
            iq = q_of(i, j)
        else:
            iq = i
            ik = k_of(i, j)
        return (bb if bb is not None else b,
                bh if bh is not None else h, iq, ik)

    return pl.BlockSpec((1, 1 if bh == 0 else heads, block_q, block_k), idx)


def _flash_fwd(lay, q, k, v, seg_q=None, seg_k=None, bias=None, *, causal,
               scale, block_q, block_k, interpret, window=None, q_offset=0,
               blocks=None):
    """Forward on operands in the layout ``lay`` → ``(out, lse)``: the
    output as ``q`` came, the log-sum-exp ``[B, H, 1, Tq]`` float32.

    ``k``/``v`` may carry FEWER heads than ``q`` (GQA/MQA): kv head
    ``h // g`` serves q head ``h`` via the BlockSpec index map — no
    materialized ``jnp.repeat``. ``seg_q``/``seg_k`` are optional ``[B, T]``
    int32 packed-segment ids; ``bias`` an optional additive
    ``[B|1, H|1, Tq, Tk]`` score bias (ALiBi etc.), tiled per block.
    ``block_q``/``block_k`` of None are derived (:func:`_geometry`)."""
    Tq, Tk = q.shape[1], k.shape[1]
    heads = lay.step_heads
    has_segments = seg_q is not None
    has_bias = bias is not None
    block_q, block_k, sub = _geometry(
        Tq, Tk, walks="k", causal=causal, window=window, q_offset=q_offset,
        bias_heads=heads if has_bias else 1,
        block_q=block_q, block_k=block_k, blocks=blocks,
    )
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

    k_block = _k_slot(band_lo, nk, block_q, block_k, causal, q_offset,
                      blocks)

    params = dict(heads=heads, D=lay.D, Dv=lay.Dv, scale=scale,
                  causal=causal, block_q=block_q, block_k=block_k, sub=sub,
                  num_k_blocks=grid_k, window=window, band_lo=band_lo,
                  nk_total=nk, q_offset=q_offset, blocks=blocks)
    q_spec = lay.spec(block_q, lambda iq, j: iq)
    o_spec = lay.spec(block_q, lambda iq, j: iq, value=True)
    k_spec = lay.spec(block_k, k_block, shared=True)
    v_spec = lay.spec(block_k, k_block, shared=True, value=True)
    in_specs = [q_spec, k_spec, v_spec]
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
            _bias_spec(bias, heads, block_q, block_k, k_of=k_block)
        )
        args += (bias,)

    def kernel(*refs):
        seg_refs, bias_ref, rest = _split_refs(
            refs, 3, has_segments, has_bias
        )
        o_ref, lse_ref, *scratch = rest
        _fwd_body(*refs[:3], seg_refs, bias_ref, o_ref, lse_ref,
                  *(scratch or (None,) * 3), **params)

    with jax.named_scope(train_path.FLASH_FWD):
        return pl.pallas_call(
            kernel,
            name=train_path.FLASH_FWD,
            grid=(lay.B, lay.H // heads, nq, grid_k),
            compiler_params=_GRID_SEMANTICS,
            in_specs=in_specs,
            out_specs=[o_spec, lay.row_spec(block_q, lambda iq, j: iq)],
            out_shape=[
                jax.ShapeDtypeStruct(lay.value_shape(q.shape), q.dtype),
                jax.ShapeDtypeStruct((lay.B, lay.H, 1, Tq), jnp.float32),
            ],
            scratch_shapes=[] if grid_k == 1 else [
                pltpu.VMEM((block_q, lay.v_width), jnp.float32),      # acc
                pltpu.VMEM((heads, block_q, _LANES), jnp.float32),    # m
                pltpu.VMEM((heads, block_q, _LANES), jnp.float32),    # l
            ],
            interpret=interpret,
        )(*args)


# ---------------------------------------------------------------------------
# Backward: dq kernel (iterate K blocks per fixed Q block)
# ---------------------------------------------------------------------------

def _column(row_ref, head=0, blocks=None):
    """Head ``head``'s ``block_q`` lanes of a ``[1, heads, 1, block_q]``
    block of row statistics as the ``[block_q, 1]`` column the scores
    are corrected by. Under a strict mask by blocks the first block's
    queries see no key, and the forward hands their log-sum-exp back as
    ``NEG_INF``: a masked score less that is 0 and its ``exp`` 1, so
    such a row's column is 0 here and the ``exp`` of its scores 0."""
    col = jnp.expand_dims(row_ref[0, head, 0], -1)
    if blocks is not None and blocks[1]:
        col = jnp.where(col > NEG_INF / 2, col, 0.0)
    return col


def _delta(do, o):
    """``rowsum(dO * O)`` of one head as a ``[block_q, 1]`` column: the
    rowwise correction of the flash backward (it re-derives the softmax
    Jacobian's contribution without P), from the head's float32 ``dO``
    and its lanes of the forward's output as that kernel wrote it. Both
    backward kernels make it where they use it. In the projections'
    layout XLA's row sum would first transpose the float32 product to
    put ``T`` on the lanes a kernel reads statistics from, and a kernel
    that wrote it as a row for the other paid more for turning the
    column than the other pays for the sum (PERF.md, PR 37)."""
    return jnp.sum(do * o.astype(jnp.float32), axis=1, keepdims=True)


def _bwd_dq_body(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, seg_refs,
                 bias_ref, dq_ref, dq_acc, *, heads: int, D: int, Dv: int,
                 scale: float, causal: bool, block_q: int, block_k: int,
                 sub: int, num_k_blocks: int, window=None, band_lo=None,
                 nk_total=None, q_offset: int = 0, blocks=None):
    iq = pl.program_id(2)
    j = pl.program_id(3)
    ik = j if band_lo is None else band_lo(iq) + j
    single = num_k_blocks == 1  # one k slot a row: nothing to add up

    if not single:
        @pl.when(j == 0)
        def _init():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    q0, k0, live, index, branches = _walk(
        iq, ik, block_q, block_k, sub, causal, window, q_offset, blocks)
    if band_lo is not None:
        live &= (ik >= 0) & (ik < nk_total)

    def _accumulate(pieces):
        def head(p):
            lanes, v_lanes = _head_lanes(p, D), _head_lanes(p, Dv)
            q = q_ref[0, :, lanes]
            do = do_ref[0, :, v_lanes].astype(jnp.float32)
            lse = _column(lse_ref, p, blocks)
            delta = _delta(do, o_ref[0, :, v_lanes])
            dq = 0.0
            for a, b, masked in pieces:
                k = k_ref[0, a:b, lanes]
                s = _scores(q, k, bias_ref, seg_refs, masked, q0, k0,
                            slice(a, b), scale=scale, window=window, head=p,
                            blocks=blocks)
                # p from the saved LSE: exp(NEG_INF - lse) underflows to
                # exactly 0, so masked/never-attended entries contribute
                # nothing.
                pr = jnp.exp(s - lse)
                dp = jax.lax.dot_general(
                    do, v_ref[0, a:b, v_lanes], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [block_q, b - a]
                ds = pr * (dp - delta) * scale
                dq += jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            if single:
                dq_ref[0, :, lanes] = dq.astype(dq_ref.dtype)
            else:
                dq_acc[:, lanes] += dq

        _each_head(heads, head)

    _visit(live, index, branches, causal, _accumulate)

    if single:
        if causal:
            @pl.when(jnp.logical_not(live))
            def _nothing_visible():
                dq_ref[...] = jnp.zeros_like(dq_ref)
        return

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# Backward: dk/dv kernel (iterate Q blocks per fixed K block)
# ---------------------------------------------------------------------------

def _bwd_dkv_body(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, seg_refs,
                  bias_ref, dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc, *,
                  heads: int, D: int, Dv: int, scale: float, causal: bool,
                  block_q: int, block_k: int, num_q_blocks: int, window=None,
                  band_lo=None, nq_total=None, q_offset: int = 0,
                  blocks=None):
    ik = pl.program_id(2)
    j = pl.program_id(3)
    iq = j if band_lo is None else band_lo(ik) + j
    single = num_q_blocks == 1  # one q slot a column: nothing to add up

    if not single:
        @pl.when(j == 0)
        def _init():
            dk_acc[...] = jnp.zeros_like(dk_acc)
            dv_acc[...] = jnp.zeros_like(dv_acc)

    q0, k0, live, index, branches = _walk(
        iq, ik, block_q, block_k, block_k, causal, window, q_offset, blocks)
    if band_lo is not None:
        # With q_offset > 0 the low end can undershoot too.
        live &= (iq >= 0) & (iq < nq_total)

    if dbias_ref is not None and causal:
        # Each (iq, ik) tile is visited exactly once in this grid; dead
        # (causal-skipped) tiles must still write zeros — Pallas outputs
        # are not pre-zeroed.
        @pl.when(jnp.logical_not(live))
        def _zero_dbias():
            dbias_ref[...] = jnp.zeros_like(dbias_ref)

    def _accumulate(pieces):
        (_, _, masked), = pieces  # the whole tile, masked or not

        def head(p):
            lanes, v_lanes = _head_lanes(p, D), _head_lanes(p, Dv)
            q = q_ref[0, :, lanes]
            k = k_ref[0, :, lanes]
            v = v_ref[0, :, v_lanes]
            do = do_ref[0, :, v_lanes].astype(jnp.float32)
            s = _scores(q, k, bias_ref, seg_refs, masked, q0, k0,
                        slice(None), scale=scale, window=window, head=p,
                        blocks=blocks)
            pr = jnp.exp(s - _column(lse_ref, p, blocks))  # [block_q, block_k]
            # dv += p^T @ do
            dv = jax.lax.dot_general(
                pr.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            # d loss / d s_total
            ds_unscaled = pr * (dp - _delta(do, o_ref[0, :, v_lanes]))
            if dbias_ref is not None:
                # dbias tile == ds before the qk-scale factor (the bias
                # adds AFTER the scale multiplies q·k).
                dbias_ref[0, p] = ds_unscaled.astype(dbias_ref.dtype)
            ds = ds_unscaled * scale  # [block_q, block_k]
            # dk += ds^T @ q
            dk = jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if single:
                dk_ref[0, :, lanes] = dk.astype(dk_ref.dtype)
                dv_ref[0, :, v_lanes] = dv.astype(dv_ref.dtype)
            else:
                dk_acc[:, lanes] += dk
                dv_acc[:, v_lanes] += dv

        _each_head(heads, head, looped=True)

    _visit(live, index, branches, causal, _accumulate)

    if single:
        if causal:
            @pl.when(jnp.logical_not(live))
            def _nothing_visible():
                dk_ref[...] = jnp.zeros_like(dk_ref)
                dv_ref[...] = jnp.zeros_like(dv_ref)
        return

    @pl.when(j == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(lay, q, k, v, out, do, lse, seg_q=None, seg_k=None,
               bias=None, want_dbias=False, *, causal, scale, block_q,
               block_k, interpret, window=None, q_offset=0, grad_dtype=None,
               blocks=None):
    """Backward on operands in the layout ``lay`` → ``(dq, dk, dv[,
    dbias])`` in that layout, given the forward's output (the ring: the
    merged one) and log-sum-exp as the forward wrote them, the latter
    ``[B, H, 1, Tq]``; the kernels make ``delta = rowsum(do * out)``
    themselves (:func:`_delta`).

    Both kernels add up in a float32 scratch and round once, in their
    last line, to ``grad_dtype`` (None: the operand's own dtype, which
    is what the op hands back; the ring sums partial gradients over its
    steps and asks for float32). With GQA (kv heads Hkv < Hq) dk/dv
    leave the kernel a q head each, float32, and are rounded after the
    group sum. ``want_dbias`` materializes the full ``[B, H, Tq, Tk]``
    f32 bias gradient (then reduced to ``bias``'s broadcast shape) —
    O(B·H·T²) regardless of the bias's own broadcast shape; see the
    public docstring's sizing caution."""
    Tq, Tk = q.shape[1], k.shape[1]
    heads = lay.step_heads
    has_segments = seg_q is not None
    has_bias = bias is not None
    assert not (want_dbias and not has_bias)
    geometry = functools.partial(
        _geometry, Tq, Tk, causal=causal, window=window, q_offset=q_offset,
        bare=not (has_segments or has_bias),
        row_bytes=lay.D * q.dtype.itemsize,
        bias_heads=heads if has_bias else 1,
        block_q=block_q, block_k=block_k, blocks=blocks,
    )
    grid_bh = (lay.B, lay.H // heads)
    seg_args = ((seg_q[:, :, None], seg_k[:, None, :]) if has_segments
                else ())
    bias_args = (bias,) if has_bias else ()

    # -- dq: K blocks per Q block -------------------------------------
    block_q, block_k, sub = geometry(walks="k")
    nq, nk = Tq // block_q, Tk // block_k
    # Banded grids (see _flash_fwd): dq iterates only the k blocks in
    # the window band; dk/dv only the q blocks that can see this k block.
    band_lo = None
    grid_k = nk
    if causal and window is not None:
        span_k, lo_k = _band_k(block_q, block_k, window, nk, q_offset)
        if span_k < nk:
            band_lo, grid_k = lo_k, span_k
    k_block = _k_slot(band_lo, nk, block_q, block_k, causal, q_offset,
                      blocks)
    dq_params = dict(heads=heads, D=lay.D, Dv=lay.Dv, scale=scale,
                     causal=causal,
                     block_q=block_q, block_k=block_k, sub=sub,
                     window=window, q_offset=q_offset, num_k_blocks=grid_k,
                     band_lo=band_lo, nk_total=nk, blocks=blocks)
    q_spec = lay.spec(block_q, lambda i, j: i)
    o_spec = lay.spec(block_q, lambda i, j: i, value=True)
    row_spec = lay.row_spec(block_q, lambda i, j: i)
    k_spec = lay.spec(block_k, k_block, shared=True)
    v_spec = lay.spec(block_k, k_block, shared=True, value=True)
    dq_in_specs = [q_spec, k_spec, v_spec, o_spec, o_spec, row_spec]
    if has_segments:
        dq_in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, h, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, h, i, j: (b, 0, k_block(i, j))),
        ]
    if has_bias:
        dq_in_specs.append(
            _bias_spec(bias, heads, block_q, block_k, k_of=k_block)
        )

    def dq_kernel(*refs):
        seg_refs, bias_ref, rest = _split_refs(
            refs, 6, has_segments, has_bias
        )
        dq_ref, *dq_acc = rest
        _bwd_dq_body(*refs[:6], seg_refs, bias_ref, dq_ref,
                     *(dq_acc or (None,)), **dq_params)

    with jax.named_scope(train_path.FLASH_BWD_DQ):
        dq = pl.pallas_call(
            dq_kernel,
            name=train_path.FLASH_BWD_DQ,
            grid=(*grid_bh, nq, grid_k),
            compiler_params=_GRID_SEMANTICS,
            in_specs=dq_in_specs,
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct(q.shape, grad_dtype or q.dtype),
            scratch_shapes=[] if grid_k == 1 else [
                pltpu.VMEM((block_q, lay.width), jnp.float32)],
            interpret=interpret,
        )(q, k, v, out, do, lse, *seg_args, *bias_args)

    # -- dk/dv: Q blocks per K block ----------------------------------
    # The grid iterates Q heads; with GQA each q head writes its own
    # slot of a q-shaped array (no cross-head accumulation inside the
    # grid) and the group sum happens below. Grid program ids here are
    # (ik, iq). want_dbias forces the full grid — its output tiles every
    # (iq, ik), written at its grid slot.
    block_q, block_k, _ = geometry(walks="q")
    nq, nk = Tq // block_q, Tk // block_k
    band_lo = None
    grid_q = nq
    if causal and window is not None and not want_dbias:
        span_q, lo_q = _band_q(block_q, block_k, window, nq, q_offset)
        if span_q < nq:
            band_lo, grid_q = lo_q, span_q
    q_block = _q_slot(band_lo, nq, block_q, block_k,
                      causal and not want_dbias, q_offset, blocks)
    dkv_params = dict(heads=heads, D=lay.D, Dv=lay.Dv, scale=scale,
                      causal=causal,
                      block_q=block_q, block_k=block_k, window=window,
                      q_offset=q_offset, num_q_blocks=grid_q,
                      band_lo=band_lo, nq_total=nq, blocks=blocks)
    q_spec_in = lay.spec(block_q, q_block)
    o_spec_in = lay.spec(block_q, q_block, value=True)
    row_spec_in = lay.row_spec(block_q, q_block)
    k_spec_in = lay.spec(block_k, lambda i, j: i, shared=True)
    v_spec_in = lay.spec(block_k, lambda i, j: i, shared=True, value=True)
    dkv_in_specs = [q_spec_in, k_spec_in, v_spec_in, o_spec_in, o_spec_in,
                    row_spec_in]
    if has_segments:
        dkv_in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda b, h, i, j: (b, q_block(i, j), 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, i)),
        ]
    if has_bias:
        dkv_in_specs.append(
            _bias_spec(bias, heads, block_q, block_k, swap=True,
                       q_of=q_block)
        )

    # a q head each: k's rows at q's width (the same array without GQA)
    kv_dtype = jnp.float32 if lay.group > 1 else grad_dtype or k.dtype
    dk_shape = (q.shape[0], Tk, q.shape[2])
    out_specs = [lay.spec(block_k, lambda i, j: i),
                 lay.spec(block_k, lambda i, j: i, value=True)]
    out_shape = [jax.ShapeDtypeStruct(dk_shape, kv_dtype),
                 jax.ShapeDtypeStruct(lay.value_shape(dk_shape), kv_dtype)]
    if want_dbias:
        out_specs.append(
            pl.BlockSpec((1, heads, block_q, block_k),
                         lambda b, h, i, j: (b, h, j, i))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((lay.B, lay.H, Tq, Tk), jnp.float32)
        )

    def dkv_kernel(*refs):
        seg_refs, bias_ref, rest = _split_refs(
            refs, 6, has_segments, has_bias
        )
        dk_ref, dv_ref, *rest = rest
        dbias_ref = rest.pop(0) if want_dbias else None
        _bwd_dkv_body(*refs[:6], seg_refs, bias_ref, dk_ref, dv_ref,
                      dbias_ref, *(rest or (None, None)), **dkv_params)

    with jax.named_scope(train_path.FLASH_BWD_DKV):
        res = pl.pallas_call(
            dkv_kernel,
            name=train_path.FLASH_BWD_DKV,
            grid=(*grid_bh, nk, grid_q),
            compiler_params=_GRID_SEMANTICS,
            in_specs=dkv_in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=[] if grid_q == 1 else [
                pltpu.VMEM((block_k, lay.width), jnp.float32),
                pltpu.VMEM((block_k, lay.v_width), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v, out, do, lse, *seg_args, *bias_args)
    if want_dbias:
        dk, dv, dbias = res
    else:
        dk, dv = res
        dbias = None
    if lay.group > 1:
        dk = lay.group_sum(dk).astype(grad_dtype or k.dtype)
        dv = lay.group_sum(dv, value=True).astype(grad_dtype or v.dtype)
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


# One custom_vjp covers every operand combination: seg/bias are always
# passed (zero-size dummies when unused, selected by the static has_*
# flags), which avoids a per-combination class explosion.
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12, 13, 14))
def _flash_core(q, k, v, seg, bias, has_seg, has_bias, bias_grad, causal,
                scale, block_q, block_k, interpret, window, blocks=None):
    # Primal == fwd minus the residuals: ONE body owns the operand
    # plumbing so primal and vjp forwards can never diverge.
    out, _res = _flash_core_fwd(
        q, k, v, seg, bias, has_seg, has_bias, bias_grad, causal, scale,
        block_q, block_k, interpret, window, blocks,
    )
    return out


def _flash_core_fwd(q, k, v, seg, bias, has_seg, has_bias, bias_grad,
                    causal, scale, block_q, block_k, interpret, window,
                    blocks=None):
    lay = _Layout.of(q.shape, k.shape, v.shape)
    out, lse = _flash_fwd(
        lay, lay.enter(q), lay.enter(k), lay.enter(v),
        seg if has_seg else None, seg if has_seg else None,
        bias if has_bias else None,  # bias is already scores-layout BHQK
        causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
        window=window, blocks=blocks,
    )
    # What the backward takes from the kernel, by name: a remat policy
    # that saves these names (models/transformer.py, 'dots') keeps them,
    # and the recomputation has no use for the kernel. Both are the
    # kernel's results as it wrote them: the output in the kernels'
    # layout, the log-sum-exp a row, [B, H, 1, Tq]; the backward kernels
    # read both as they are.
    out = checkpoint_name(out, train_path.FLASH_OUT)
    lse = checkpoint_name(lse, train_path.FLASH_LSE)
    return lay.leave(out, value=True), (q, k, v, seg, bias, out, lse)


def _flash_core_bwd(has_seg, has_bias, bias_grad, causal, scale, block_q,
                    block_k, interpret, window, blocks, res, g):
    q, k, v, seg, bias, out, lse = res
    lay = _Layout.of(q.shape, k.shape, v.shape)
    res_bwd = _flash_bwd(
        lay, lay.enter(q), lay.enter(k), lay.enter(v), out, lay.enter(g),
        lse, seg if has_seg else None, seg if has_seg else None,
        bias if has_bias else None, bias_grad,
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret, window=window, blocks=blocks,
    )
    if bias_grad:
        dbias = res_bwd[3].astype(bias.dtype)  # already BHQK
    else:
        # No-grad bias (the common ALiBi/static case): a zero cotangent —
        # callers training a bias must pass bias_grad=True.
        dbias = jnp.zeros_like(bias)
    # dq, dk, dv: rounded to their operand's dtype by the kernels
    dq, dk, dv = res_bwd[:3]
    return (
        lay.leave(dq), lay.leave(dk), lay.leave(dv, value=True),
        None,  # integer segment ids carry no gradient
        dbias,
    )


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)

# A model calls the op once a layer with the same shapes. Under a jit of
# its own those calls share one trace and one lowering of the kernels
# (tracing a kernel body and lowering it for Mosaic is what a call costs
# the host: without it 72 kernels a step, traced again by every program
# that holds the model); XLA inlines the calls, so the compiled step is
# the same program, each kernel under its call site's ``op_name``.
_flash_call = jax.jit(_flash_core, static_argnums=tuple(range(5, 15)))


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
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    causal_block: int = 1,
    causal_strict: bool = False,
) -> jax.Array:
    """Flash attention on ``[B, T, H, D]`` inputs, Pallas forward AND
    backward (both VMEM-blocked; the score matrix never exists in HBM in
    either direction).

    ``k``/``v`` may carry fewer heads than ``q`` (GQA/MQA — q heads must be
    a multiple of kv heads; kv blocks are shared via the kernel's index map,
    never materialized per-group). ``v``'s heads may be narrower than
    ``q``'s and ``k``'s (``[B, T, Hkv, Dv]``, ``Dv <= D``): the output and
    ``dv`` are ``Dv`` wide, and a default ``scale`` is still ``D ** -0.5``.
    ``segment_ids`` is an optional ``[B, T]``
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

    ``causal_block`` / ``causal_strict`` make the causal mask one by
    blocks of ``causal_block`` positions (block diffusion's): query ``i``
    sees key ``j`` where ``i // bl >= j // bl``, its own block whole, or
    with ``causal_strict`` where ``i // bl > j // bl``, earlier blocks
    alone. Both need ``causal=True`` and no ``window``. A tile the stair
    leaves dark is skipped as one above the diagonal is (``flash_tiles``
    counts by the same rule), and the mask is built on the sub-tile the
    stair crosses. A strict mask's first block of queries sees nothing:
    its output and every gradient of it are zero. The defaults (1, not
    strict) are the causal mask, and the kernels are then the same
    programs as without the two arguments.

    ``block_q`` / ``block_k`` of None (the default) are derived from the
    lengths and the mask kind (:func:`_geometry`); numbers are taken as
    given, halved until they divide the lengths.

    The kernels see ``q``, ``k``, ``v`` and the output's cotangent as
    ``[B, T, H * D]``, the layout a projection hands them in, and write
    the output and dq, dk, dv so, in the operands' dtype: where ``D`` is
    a multiple of 128, or divides 128 with ``H * D`` a multiple of 128
    and no GQA, the reshapes here are all that stands between the caller
    and the kernels. For other head widths, and GQA at heads under 128,
    this wrapper transposes to ``[B * H, T, D]`` and back
    (:class:`_Layout`; the gauge ``flash_heads_per_block`` reads 0).

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
    blocks = _blocks(causal_block, causal_strict)
    if blocks is not None and (not causal or window is not None):
        raise ValueError("causal_block / causal_strict describe a causal "
                         "mask without a window: pass causal=True and no "
                         "window")
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
    # Here and not in the kernels' builders, which `_flash_call` traces
    # once a shape: the backward's entries are what this call's gradient
    # runs, whether or not one is taken.
    _publish_tiles(_WALKS, _Layout.of(q.shape, k.shape, v.shape),
                   q.shape[1], k.shape[1], causal=causal, window=window,
                   bare=not (has_seg or has_bias), has_bias=has_bias,
                   row_bytes=q.shape[3] * q.dtype.itemsize,
                   block_q=block_q, block_k=block_k, blocks=blocks)
    return _flash_call(q, k, v, seg, b, has_seg, has_bias, bias_grad,
                       causal, scale, block_q, block_k, interpret, window,
                       blocks)


# ---------------------------------------------------------------------------
# Block-level entry points for ring attention
# ---------------------------------------------------------------------------

def _block_entry_mask(causal, window, q_offset, causal_block, causal_strict):
    """:func:`_blocks` of a block entry point's arguments."""
    blocks = _blocks(causal_block, causal_strict)
    if blocks is not None and (not causal or window is not None
                               or q_offset):
        raise ValueError("a causal mask by blocks takes causal=True, no "
                         "window and q_offset 0")
    return blocks


def flash_block_fwd(q, k_blk, v_blk, *, causal, scale, block_q, block_k,
                    interpret, seg_q=None, seg_kv=None, window=None,
                    q_offset=0, causal_block=1, causal_strict=False):
    """One ring step's forward: full flash over the resident Q shard and ONE
    arriving K/V block, returning BTHD output + ``[B, H, Tq]`` LSE. The ring
    merges successive blocks' (out, lse) partials in log space
    (:func:`chainermn_tpu.parallel.ring_attention.merge_partials`).
    ``seg_q``/``seg_kv`` are the per-shard segment-id slices (the kv ids
    travel with their block around the ring). ``causal_block`` /
    ``causal_strict``: :func:`flash_attention`'s mask by blocks (block
    diffusion merges a strict call's partial with its in-block part as
    the ring merges its steps')."""
    blocks = _block_entry_mask(causal, window, q_offset, causal_block,
                               causal_strict)
    lay = _Layout.of(q.shape, k_blk.shape, v_blk.shape)
    _publish_tiles((train_path.FLASH_FWD,), lay, q.shape[1], k_blk.shape[1],
                   causal=causal, window=window, q_offset=q_offset,
                   block_q=block_q, block_k=block_k, blocks=blocks)
    out, lse = _flash_fwd(
        lay, lay.enter(q), lay.enter(k_blk), lay.enter(v_blk), seg_q, seg_kv,
        causal=causal, window=window, q_offset=q_offset,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret,
        blocks=blocks,
    )
    return lay.leave(out, value=True), lse[:, :, 0]


def flash_block_bwd(q, k_blk, v_blk, do, lse, out, *, causal, scale,
                    block_q, block_k, interpret, seg_q=None, seg_kv=None,
                    window=None, q_offset=0, causal_block=1,
                    causal_strict=False, grad_dtype=jnp.float32):
    """One ring step's backward: (dq, dk_blk, dv_blk) contributions for one
    K/V block, BTHD, in float32 whatever the operands': the ring adds
    them up over its steps (``grad_dtype`` None: in the operands' own,
    rounded once, as the op hands them back). ``lse`` (``[B, H, Tq]``) and
    ``out`` (BTHD) are the ring's merged log-sum-exp and output."""
    blocks = _block_entry_mask(causal, window, q_offset, causal_block,
                               causal_strict)
    lay = _Layout.of(q.shape, k_blk.shape, v_blk.shape)
    _publish_tiles((train_path.FLASH_BWD_DQ, train_path.FLASH_BWD_DKV), lay,
                   q.shape[1], k_blk.shape[1], causal=causal, window=window,
                   q_offset=q_offset, bare=seg_q is None,
                   row_bytes=q.shape[3] * q.dtype.itemsize,
                   block_q=block_q, block_k=block_k, blocks=blocks)
    dq, dk, dv = _flash_bwd(
        lay, lay.enter(q), lay.enter(k_blk), lay.enter(v_blk),
        lay.enter(out), lay.enter(do), lse[:, :, None], seg_q, seg_kv,
        causal=causal, scale=scale, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, interpret=interpret,
        grad_dtype=grad_dtype, blocks=blocks,
    )
    return lay.leave(dq), lay.leave(dk), lay.leave(dv, value=True)
