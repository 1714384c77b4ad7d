"""Attention of a block-diffusion language model's training pass (BD3-LMs,
arXiv:2503.09573): one forward over a clean and a noised copy of every
sequence, ``[x ; x~]``, ``2L`` rows at positions ``[0..L-1 ; 0..L-1]``,
under a mask by blocks of ``bl`` positions, ``b(i) = i // bl``:

* a clean row ``i`` sees the clean keys with ``b(j) <= b(i)``;
* a noised row ``i`` sees the clean keys with ``b(j) < b(i)`` and the
  noised keys with ``b(j) = b(i)``;
* no clean row sees a noised key.

The mask's support is two half squares of ``L`` and a band of ``bl``, a
quarter of the ``2L x 2L`` square, so it is assembled from its three
parts and no ``[2L, 2L]`` array exists, each part a call of the flash
kernels: under an inclusive mask by blocks (clean on clean), under the
strict one (noised on clean), and the in-block part (noised on noised)
on the band's diagonal tiles alone: the noised copy is viewed as ``L /
t`` sequences of ``t`` rows, each one tile, unmasked but for segment ids
that are a row's block within its tile (:func:`in_block_fwd`). The band
is a sliver of each tile (``bl`` of ``t`` keys a row), but a tile's
products on the MXU cost a fraction of what ``bl`` lane reductions a row
cost the vector unit (PERF.md, PR 45), and every pair of a softmax row
is computed in one precision: bf16 operands as they come, float32
accumulation and statistics. The noised rows' two partials meet by their
log-sum-exps, as the ring's do
(``parallel/ring_attention.py:merge_partials``), and the backward hands
both calls the *merged* output and log-sum-exp (``flash_block_bwd``), so
a first block's row, which sees no clean key and whose own log-sum-exp
from the strict call is ``NEG_INF``, is given its in-block one.
``flash_tiles`` counts the strict and the inclusive call's tiles and
``bd_in_block_tiles`` the in-block call's; every visited tile holds a
pair the mask allows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from chainermn_tpu.observability import train_path
from chainermn_tpu.ops.flash_attention import (
    _TILES,
    _use_interpret,
    flash_attention,
    flash_block_bwd,
    flash_block_fwd,
)
from chainermn_tpu.parallel.ring_attention import merge_partials


def in_block_tile(L: int, bl: int) -> int:
    """Rows of a diagonal tile of the in-block call: the most whole blocks
    of ``bl`` positions that divide ``L`` and fit the kernels' query tile
    (``_TILES[0]``); one block where a block is longer than that. On the
    v5e what a grid step costs hardly falls with its tile (PERF.md, PR
    24), so the tile is the kernels' own and not the lane tile's 128."""
    n = L // bl
    return bl * max(d for d in range(1, n + 1)
                    if n % d == 0 and (d == 1 or d * bl <= _TILES[0]))


def _tiles(x, t: int):
    """``[B, L, ...]`` as ``L / t`` sequences of ``t`` contiguous rows,
    ``[B * L / t, t, ...]``: a reshape, no row moves."""
    return x.reshape(x.shape[0] * x.shape[1] // t, t, *x.shape[2:])


def _tile_rows(lse, t: int):
    """A log-sum-exp ``[B, H, L]`` as the tiles' ``[B * L / t, H, t]``."""
    B, H, L = lse.shape
    return lse.reshape(B, H, L // t, t).transpose(0, 2, 1, 3).reshape(
        B * L // t, H, t)


def _row_tiles(lse, B: int):
    """:func:`_tile_rows`' inverse: ``[B * n, H, t]`` -> ``[B, H, n * t]``."""
    n, (_, H, t) = lse.shape[0] // B, lse.shape
    return lse.reshape(B, n, H, t).transpose(0, 2, 1, 3).reshape(B, H, n * t)


def _same_block(x, bl: int):
    """The mask ``q_pos // bl == k_pos // bl`` of one tile as the kernels'
    segment ids, the queries' and the keys' alike: ``[x.shape[0], t]``, a
    row's block within its tile."""
    ids = jnp.arange(x.shape[1], dtype=jnp.int32) // bl
    return jnp.broadcast_to(ids, x.shape[:2])


# The model calls the two once a layer with the same shapes: under a jit of
# their own the layers share one trace and one lowering of the three kernels
# (``_flash_call``'s reason), in every program that holds the model.
_shared = functools.partial(
    jax.jit, static_argnames=("bl", "t", "scale", "interpret"))


@_shared
def in_block_fwd(q, k, v, *, bl, t, scale, interpret):
    """Every row's attention to the ``bl`` keys of its own block, through
    the flash forward kernel on the diagonal tiles of ``t`` rows alone:
    ``q [B, L, H, D]``, ``k`` / ``v`` ``[B, L, Hkv, D]`` -> ``(out [B, L,
    H, D], lse [B, H, L])``. The copy is viewed as ``L / t`` sequences of
    one tile each, unmasked but for the segment ids (:func:`_same_block`),
    so no tile off the diagonal is visited or exists."""
    qt = _tiles(q, t)
    blocks = _same_block(qt, bl)
    out, lse = flash_block_fwd(
        qt, _tiles(k, t), _tiles(v, t), causal=False, scale=scale,
        block_q=None, block_k=None, interpret=interpret, seg_q=blocks,
        seg_kv=blocks)
    return out.reshape(q.shape), _row_tiles(lse, q.shape[0])


@_shared
def in_block_bwd(q, k, v, do, lse, out, *, bl, t, scale, interpret):
    """:func:`in_block_fwd`'s gradients ``(dq, dk, dv)`` through the two
    backward kernels, in the operands' dtypes, given the rows' ``out``
    and ``lse`` over *all* the keys they see (the merged pair)."""
    qt = _tiles(q, t)
    blocks = _same_block(qt, bl)
    grads = flash_block_bwd(
        qt, _tiles(k, t), _tiles(v, t), _tiles(do, t), _tile_rows(lse, t),
        _tiles(out, t), causal=False, scale=scale, block_q=None,
        block_k=None, interpret=interpret, seg_q=blocks, seg_kv=blocks,
        grad_dtype=None)
    return tuple(g.reshape(x.shape) for g, x in zip(grads, (q, k, v)))


_STRICT = dict(causal=True, block_q=None, block_k=None, causal_strict=True)


def _in_block(q, bl, scale, interpret):
    """The in-block entries' static arguments for noised rows ``q``."""
    return dict(bl=bl, t=in_block_tile(q.shape[1], bl), scale=scale,
                interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _noised_rows(q, k_clean, v_clean, k_noised, v_noised, bl, scale,
                 interpret):
    return _noised_rows_fwd(q, k_clean, v_clean, k_noised, v_noised, bl,
                            scale, interpret)[0]


def _noised_rows_fwd(q, k_clean, v_clean, k_noised, v_noised, bl, scale,
                     interpret):
    # the in-block call first: ``flash_tiles`` holds the last call's
    # geometry, which is to be the strict call's
    out_n, lse_n = in_block_fwd(q, k_noised, v_noised,
                                **_in_block(q, bl, scale, interpret))
    out_c, lse_c = flash_block_fwd(
        q, k_clean, v_clean, scale=scale, interpret=interpret,
        causal_block=bl, **_STRICT)
    # a first block's row sees no clean key: its strict partial is
    # (0, NEG_INF) and takes weight 0; the in-block one is never empty,
    # so the merged log-sum-exp is finite
    out, lse = merge_partials(out_c.astype(jnp.float32), lse_c, out_n, lse_n)
    # by the flash kernels' names, so that a remat policy that keeps what
    # the forward kernel made keeps the merged pair and the backward runs
    # no forward kernel again
    out = checkpoint_name(out.astype(q.dtype), train_path.FLASH_OUT)
    lse = checkpoint_name(lse, train_path.FLASH_LSE)
    return out, (q, k_clean, v_clean, k_noised, v_noised, out, lse)


def _noised_rows_bwd(bl, scale, interpret, res, do):
    q, k_clean, v_clean, k_noised, v_noised, out, lse = res
    dq_n, dk_noised, dv_noised = in_block_bwd(
        q, k_noised, v_noised, do, lse, out,
        **_in_block(q, bl, scale, interpret))
    dq_c, dk_clean, dv_clean = flash_block_bwd(
        q, k_clean, v_clean, do, lse, out, scale=scale, interpret=interpret,
        causal_block=bl, grad_dtype=None, **_STRICT)
    # each call rounds its float32 accumulators to the operands' dtype
    # once; in float32 the in-block dq cost 3.4 ms a step more in the SDAR
    # cell and moved no reading of the comparison (PERF.md, PR 45)
    return dq_c + dq_n, dk_clean, dv_clean, dk_noised, dv_noised


_noised_rows.defvjp(_noised_rows_fwd, _noised_rows_bwd)


def block_diffusion_attention(q, k, v, *, block_length: int, scale=None,
                              interpret=None):
    """Attention over ``[x ; x~]``: ``q [B, 2L, H, D]``, ``k`` and ``v``
    ``[B, 2L, Hkv, D]``, the first ``L`` rows the clean copy's and the
    last ``L`` the noised one's, under the module's mask at blocks of
    ``block_length`` -> ``[B, 2L, H, D]``. Under the scope
    :data:`train_path.BD_ATTENTION`; sets the gauges
    :data:`train_path.BD_BLOCK_LENGTH` and
    :data:`train_path.BD_IN_BLOCK_TILES`."""
    from chainermn_tpu.observability.metrics import registry

    B, rows, H, D = q.shape
    L, bl = rows // 2, int(block_length)
    if rows % 2 or L % bl or bl < 1:
        raise ValueError(
            f"block diffusion attends over a clean and a noised copy of "
            f"whole blocks: {rows} rows are no two copies of a multiple "
            f"of block_length={block_length}")
    if scale is None:
        scale = D ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    registry().gauge(
        train_path.BD_BLOCK_LENGTH,
        "positions a block of the block-diffusion mask holds, at the "
        "last attention traced",
    ).set(float(bl))
    registry().gauge(
        train_path.BD_IN_BLOCK_TILES,
        "diagonal tiles of one sequence and head that the in-block call "
        "of the block-diffusion attention visits, at the last attention "
        "traced: L / t, and no tile off the diagonal",
    ).set(float(L // in_block_tile(L, bl)))
    with jax.named_scope(train_path.BD_ATTENTION):
        clean = flash_attention(
            q[:, :L], k[:, :L], v[:, :L], causal=True, scale=scale,
            interpret=interpret, causal_block=bl)
        noised = _noised_rows(q[:, L:], k[:, :L], v[:, :L], k[:, L:],
                              v[:, L:], bl, float(scale), bool(interpret))
        return jnp.concatenate([clean, noised], axis=1)
