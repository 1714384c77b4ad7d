"""Grouped matrix multiplication: the expert matmuls of a dropless
mixture-of-experts layer.

``grouped_matmul(lhs[M, K], rhs[E, K, N], group_sizes[E]) -> [M, N]``: the
rows of ``lhs`` lie in group order (all of group 0, then group 1, ...),
``group_sizes`` says how many each group holds, and row ``r`` of group
``e`` is multiplied by ``rhs[e]``. Groups are ragged and may be empty;
rows past ``sum(group_sizes)`` come out as zeros. Operands go to the MXU in
``lhs.dtype`` (bf16 in training), products accumulate in float32.

Two Pallas kernels serve the forward product and both gradients:

* ``lhs @ rhs[e]`` and ``dout @ rhs[e]^T`` (the gradient of ``lhs``) walk
  *work items*: a (row tile, group) pair for every row tile a group
  touches. A tile with one visit is one product written straight to its
  output block. A tile that straddles a group boundary is visited once a
  group, each visit masked to the group's rows and accumulated into the
  tile's output block, which stays in VMEM while the tile does not change.
  The rows behind the last group (a share's absent experts' rows, a
  receive buffer's padding) are one more group, the *tail*, whose items
  multiply nothing and fetch nothing: a tile wholly behind the groups is
  written as zeros, and a tile the tail shares with the last groups' rows
  is closed with what those accumulated. A tail tile costs its zero write.
* ``drhs[e] = lhs_e^T @ dout_e`` walks the same items with the output
  block following the *group*: every visit adds the tile's rows of that
  group, contracted over the rows.

The items are computed from ``group_sizes`` on the device (``_plan``) and
reach the kernel as scalar-prefetch operands, so block indices follow them
and no shape depends on the routing. An empty group still has one (fully
masked) item, which zeroes its ``drhs`` block. :func:`tail_tiles` counts
the tiles wholly behind the groups (``moe/tail_tiles`` in a model's
metrics).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.observability import train_path
from chainermn_tpu.ops.flash_attention import _use_interpret

#: rows of ``lhs`` a work item covers and columns of an output block in
#: the two row products; rows a work item contracts and the side of an
#: output block of ``drhs`` (chosen on the v5e at OLMoE's shapes: PERF.md,
#: PR 25)
_TILE_M = 512
_TILE_N = 512
_DRHS_TILE_M = 512
_DRHS_TILE = 1024
#: what the kernels may take of the core's 128 MiB of VMEM: the double
#: buffered blocks of the largest case need 12 MiB and the float32 products
#: beside them as much again, past the compiler's default of 16
_VMEM_LIMIT = 64 * 1024 * 1024


def _tile(size: int, want: int, align: int) -> int:
    """The largest tile of at most ``want`` that divides ``size`` and is a
    multiple of ``align``; ``size`` itself where none is (a block as large
    as the array is always allowed)."""
    t = min(want, size) // align * align
    while t >= align:
        if size % t == 0:
            return t
        t -= align
    return size


def _tile_m(m: int, want: int) -> int:
    """Rows of a row tile: ``want``, or where there are fewer rows all of
    them, rounded up to 8."""
    return want if m >= want else -(-m // 8) * 8


def _row_tiles(want: int, *arrays):
    """``(tile_m, rows, padded arrays)``: row tiles of :func:`_tile_m`, the
    arrays' rows padded with zeros to a whole number of tiles."""
    m = arrays[0].shape[0]
    tile_m = _tile_m(m, want)
    rows = -(-m // tile_m) * tile_m
    if rows != m:
        arrays = [jnp.pad(a, ((0, rows - m), (0, 0))) for a in arrays]
    return (tile_m, rows, *arrays)


def _plan(group_sizes, rows: int, tile_m: int, *, cover_tail: bool):
    """Work items for ``rows`` rows in tiles of ``tile_m``.

    Returns int32 arrays ``(group_of[I], tile_of[I], lo[G], hi[G],
    total[1])``: item ``i < total`` multiplies row tile ``tile_of[i]`` with
    group ``group_of[i]``, whose rows are ``lo[g] <= r < hi[g]``. Items are
    ordered by group and, within a group, by tile, so both indices never
    decrease; items from ``total`` on repeat the last one and do nothing.
    With ``cover_tail`` a last group of no rows (``lo == hi``), the
    *tail*, is planned over the tiles past ``sum(group_sizes)``, so that
    every tile is visited and rows outside every group read zero: its
    items are the ones with ``group_of == len(group_sizes)``, which a
    kernel answers by writing zeros (it takes no product for them).
    """
    n_tiles = rows // tile_m
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    lo, hi = starts, ends
    if cover_tail:
        # the tail: planned over [sum, rows), an empty range of rows
        starts = jnp.concatenate([starts, ends[-1:]])
        ends = jnp.concatenate([ends, jnp.full((1,), rows, jnp.int32)])
        lo = jnp.concatenate([lo, jnp.zeros((1,), jnp.int32)])
        hi = jnp.concatenate([hi, jnp.zeros((1,), jnp.int32)])
    first = jnp.minimum(starts // tile_m, n_tiles - 1)
    last = jnp.where(ends > starts, (ends - 1) // tile_m, first)
    count = last - first + 1  # an empty group keeps one masked item
    item_end = jnp.cumsum(count)
    total = item_end[-1]
    # every boundary between two groups adds at most one item to the tiles
    n_items = n_tiles + starts.shape[0] - 1
    item = jnp.minimum(jnp.arange(n_items, dtype=jnp.int32), total - 1)
    group_of = jnp.searchsorted(item_end, item, side="right").astype(jnp.int32)
    tile_of = first[group_of] + item - (item_end - count)[group_of]
    return group_of, tile_of, lo, hi, total[None]


def _tiles_read(group_of, tile_of, n_groups: int):
    """The row tile of ``lhs`` each item of a ``cover_tail`` plan has in
    VMEM: its own, and for the tail's items (which read nothing) the last
    group's last, so that the block does not change and nothing is
    fetched for them."""
    held = group_of < n_groups
    # tiles never decrease, so the groups' last is their largest
    return jnp.where(held, tile_of, jnp.max(jnp.where(held, tile_of, 0)))


def tail_tiles(group_sizes, rows: int):
    """How many row tiles of a ``[rows, K]`` ``lhs`` lie wholly behind the
    last group, int32 ``[]``: the tiles the row products write as zeros
    without a product or a read (:func:`_plan`'s tail, less the one tile
    it may share with the last groups' rows). 0 where the groups fill the
    rows."""
    tile_m = _tile_m(rows, _TILE_M)
    held = group_sizes.astype(jnp.int32).sum()
    return -(-rows // tile_m) - -(-held // tile_m)


def _row_mask(tile, tile_m, lo, hi, shape, axis):
    rows = tile * tile_m + lax.broadcasted_iota(jnp.int32, shape, axis)
    return (rows >= lo) & (rows < hi)


def _inside(tile, tile_m, lo, hi):
    """Whether every row of the tile lies in ``[lo, hi)``."""
    return (lo <= tile * tile_m) & ((tile + 1) * tile_m <= hi)


def _gmm_body(group_of, tile_of, lo, hi, total, lhs_tile_of, lhs_ref,
              rhs_ref, out_ref, acc_ref, *, tile_m, n_items, n_groups,
              transpose_rhs):
    del lhs_tile_of  # the index maps' (``_gmm``)
    i = pl.program_id(1)
    g, t = group_of[i], tile_of[i]
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
    active = i < total[0]
    tail = g == n_groups  # rows behind every group: nothing to multiply
    opens = (i == 0) | (tile_of[jnp.maximum(i - 1, 0)] != t)
    closes = (i == total[0] - 1) | \
        (tile_of[jnp.minimum(i + 1, n_items - 1)] != t)
    # the tile's only visit (an empty group's item may share a tile that
    # lies inside another group, so being inside does not say it alone)
    alone = opens & closes & _inside(t, tile_m, lo[g], hi[g])

    def product():
        return lax.dot_general(lhs_ref[...], rhs_ref[0], contract,
                               preferred_element_type=jnp.float32)

    @pl.when(active & alone)
    def _whole_tile():  # the common case: straight to the output block
        out_ref[...] = product().astype(out_ref.dtype)

    @pl.when(active & tail)
    def _tail_tile():  # one item a tile, so it always closes its tile
        @pl.when(opens)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        @pl.when(jnp.logical_not(opens))  # the last groups' rows, as summed
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    @pl.when(active & jnp.logical_not(alone | tail))
    def _shared_tile():
        prod = product()
        prod = jnp.where(
            _row_mask(t, tile_m, lo[g], hi[g], prod.shape, 0), prod, 0.0)

        @pl.when(opens)
        def _():
            acc_ref[...] = prod

        @pl.when(jnp.logical_not(opens))
        def _():
            acc_ref[...] += prod

        @pl.when(closes)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _gmm(lhs, rhs, group_sizes, *, transpose_rhs, interpret):
    """``lhs[M, K] @ rhs[e]`` by group; ``rhs`` is ``[E, K, N]``, or
    ``[E, N, K]`` with ``transpose_rhs``."""
    m, k = lhs.shape
    e = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tile_m, rows, lhs = _row_tiles(_TILE_M, lhs)
    tile_n = _tile(n, _TILE_N, 128)
    plan = _plan(group_sizes, rows, tile_m, cover_tail=True)
    n_items = plan[0].shape[0]
    # a tail item reads nothing: both operands' blocks stay where the last
    # item of a group left them, so no copy is made
    lhs_tile_of = _tiles_read(*plan[:2], e)

    def rhs_index(j, i, group_of, *_):
        g = jnp.minimum(group_of[i], e - 1)
        return (g, j, 0) if transpose_rhs else (g, 0, j)

    out = pl.pallas_call(
        functools.partial(_gmm_body, tile_m=tile_m, n_items=n_items,
                          n_groups=e, transpose_rhs=transpose_rhs),
        name=train_path.MOE_EXPERTS,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n // tile_n, n_items),
            in_specs=[
                pl.BlockSpec((tile_m, k), lambda j, i, *plan: (plan[5][i], 0)),
                pl.BlockSpec((1, tile_n, k) if transpose_rhs
                             else (1, k, tile_n), rhs_index),
            ],
            out_specs=pl.BlockSpec(
                (tile_m, tile_n), lambda j, i, g_of, t_of, *_: (t_of[i], j)),
            scratch_shapes=[pltpu.VMEM((tile_m, tile_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*plan, lhs_tile_of, lhs, rhs)
    return out[:m] if rows != m else out


def _tgmm_body(group_of, tile_of, lo, hi, total, lhs_ref, dout_ref, out_ref,
               *, tile_m):
    i = pl.program_id(2)
    g, t = group_of[i], tile_of[i]
    active = i < total[0]
    inside = _inside(t, tile_m, lo[g], hi[g])
    opens = (i == 0) | (group_of[jnp.maximum(i - 1, 0)] != g)

    def add(lhs):
        prod = lax.dot_general(lhs, dout_ref[...], (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

        @pl.when(opens)
        def _():
            out_ref[0] = prod

        @pl.when(jnp.logical_not(opens))
        def _():
            out_ref[0] += prod

    @pl.when(active & inside)
    def _whole_tile():
        add(lhs_ref[...])

    @pl.when(active & jnp.logical_not(inside))
    def _shared_tile():  # only the group's own rows are contracted
        lhs = lhs_ref[...]
        add(jnp.where(_row_mask(t, tile_m, lo[g], hi[g], lhs.shape, 0), lhs,
                      jnp.zeros_like(lhs)))


def _tgmm(lhs, dout, group_sizes, *, interpret):
    """``out[e] = lhs_e^T @ dout_e`` in float32: ``[E, K, N]`` from
    ``lhs[M, K]`` and ``dout[M, N]``."""
    m, k = lhs.shape
    n = dout.shape[1]
    e = group_sizes.shape[0]
    tile_m, rows, lhs, dout = _row_tiles(_DRHS_TILE_M, lhs, dout)
    tile_k, tile_n = _tile(k, _DRHS_TILE, 128), _tile(n, _DRHS_TILE, 128)
    plan = _plan(group_sizes, rows, tile_m, cover_tail=False)
    return pl.pallas_call(
        functools.partial(_tgmm_body, tile_m=tile_m),
        name=train_path.MOE_EXPERTS,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(k // tile_k, n // tile_n, plan[0].shape[0]),
            in_specs=[
                pl.BlockSpec((tile_m, tile_k),
                             lambda a, b, i, g_of, t_of, *_: (t_of[i], a)),
                pl.BlockSpec((tile_m, tile_n),
                             lambda a, b, i, g_of, t_of, *_: (t_of[i], b)),
            ],
            out_specs=pl.BlockSpec(
                (1, tile_k, tile_n),
                lambda a, b, i, g_of, t_of, *_: (g_of[i], a, b)),
        ),
        out_shape=jax.ShapeDtypeStruct((e, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(*plan, lhs, dout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs.astype(lhs.dtype), group_sizes,
                transpose_rhs=False, interpret=interpret)


def _grouped_matmul_fwd(lhs, rhs, group_sizes, interpret):
    # ``rhs`` is kept as it came (the float32 master where the caller
    # passes one, which costs no memory) and cast again going back
    return (_grouped_matmul(lhs, rhs, group_sizes, interpret),
            (lhs, rhs, group_sizes))


def _grouped_matmul_bwd(interpret, res, dout):
    lhs, rhs, group_sizes = res
    dout = dout.astype(lhs.dtype)
    dlhs = _gmm(dout, rhs.astype(lhs.dtype), group_sizes,
                transpose_rhs=True, interpret=interpret)
    drhs = _tgmm(lhs, dout, group_sizes, interpret=interpret)
    return dlhs, drhs.astype(rhs.dtype), None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)

# A jit of its own, as flash attention has: a model's equal calls (two a
# layer, and again in every program that holds the model) share one trace
# and one lowering of the kernels; XLA inlines them into the step.
_grouped_matmul_call = jax.jit(_grouped_matmul, static_argnums=(3,))


def grouped_matmul(lhs, rhs, group_sizes):
    """``out[r] = lhs[r] @ rhs[group of r]`` for rows in group order.

    Args:
      lhs: ``[M, K]``; its dtype is the dtype of the MXU's operands and of
        the result.
      rhs: ``[E, K, N]``, cast to ``lhs.dtype`` for the product; its
        gradient comes back in its own dtype, accumulated in float32.
      group_sizes: ``[E]`` integers, ``sum <= M``; not differentiated.
    """
    if lhs.ndim != 2 or rhs.ndim != 3 or lhs.shape[1] != rhs.shape[1] \
            or group_sizes.shape != (rhs.shape[0],):
        raise ValueError(
            f"grouped_matmul takes lhs [M, K], rhs [E, K, N] and "
            f"group_sizes [E]; got {lhs.shape}, {rhs.shape}, "
            f"{group_sizes.shape}")
    with jax.named_scope(train_path.MOE_EXPERTS):
        return _grouped_matmul_call(lhs, rhs, group_sizes, _use_interpret())
