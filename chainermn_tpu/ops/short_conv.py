"""The chain between a gated short convolution's two projections.

``gated_short_conv(bcx[B, T, 3D], taps[L, D]) -> y[B, T, D]``: with
``(b, c, x) = split3(bcx)`` along the last axis, ``u = b * x``,
``conv[t] = sum_j w[j] * u[t - (L - 1 - j)]`` (zeros before a row's first
position; ``w`` is ``taps`` rounded to ``bcx.dtype``) and ``y = c * conv``.
Rows of the batch are independent; packed documents convolve across their
boundaries.

Two Pallas kernels, one a direction, behind a ``jax.custom_vjp``:

* the forward reads ``bcx`` itself in blocks of whole rows (the thirds are
  lane-aligned slices of the block) and writes ``y``;
* the backward reads ``bcx`` and ``dy``, makes ``u`` and ``conv`` again and
  writes ``dbcx[B, T, 3D]`` as one array, the layout the input projection's
  gradient matmuls read, and the taps' gradient as float32 sums a row of the
  batch.

A block's neighbours reach a kernel as one sublane tile each: the rows
before it (``u``'s history) and, going back, the rows after it
(``du[t] = sum_j w[j] * dconv[t + (L - 1 - j)]`` looks forward), masked to
zero at the sequence's ends. Inside a kernel a block is walked in strips
of columns, which bound the float32 intermediates. Everything between the
loads and the stores is float32: ``u``, the shifted copies and ``conv`` never
reach HBM and are rounded nowhere, ``y`` and each third of ``dbcx`` once.
The residuals are ``bcx`` and the taps.

Where a shape cannot tile on a TPU (``D % 128``, ``T`` no multiple of the
dtype's sublane tile, more taps than a tile has rows) the same mathematics
runs as plain ``jax.numpy`` (:func:`plain`, which is also the tests'
oracle). The choice reads the operands' shape and dtype, nothing else.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from chainermn_tpu.observability import train_path
from chainermn_tpu.ops.flash_attention import _use_interpret
from chainermn_tpu.ops.grouped_matmul import _tile

FWD = "short_conv_fwd"
BWD = "short_conv_bwd"

#: bytes of ``bcx`` a grid step holds; the backward holds as much of
#: ``dbcx`` and a third of it of ``dy``, each twice: 28 MiB. A block's rows
#: come from it: 512 at the LFM2 cell's ``[2, 8192, 6144]`` bf16, where
#: blocks of 128 / 256 / 512 / 1024 rows read 0.85 / 0.81 / 0.77 / 0.77 ms
#: backward on the v5e and 1024 rows in strips pass :data:`_VMEM_LIMIT`
#: (PERF.md, PR 41)
_BLOCK_BYTES = 6 * 1024 * 1024
#: columns of the strips a kernel walks a block in, which bound its float32
#: intermediates: strips of 256 / 512 / 1024 read alike, 2048 do not fit;
#: HBM bounds both kernels
_STRIP_COLS = 512
_VMEM_LIMIT = 64 * 1024 * 1024
#: rows of a neighbour a strip holds in float32 (one register's): as many
#: positions back as a tap may reach
_EDGE = 8


def plain(bcx, taps):
    """The chain as ``jax.numpy`` in ``bcx.dtype``: one padded shift a
    tap."""
    T, L = bcx.shape[1], taps.shape[0]
    b, c, x = jnp.split(bcx, 3, axis=-1)
    u = b * x
    w = taps.astype(bcx.dtype)
    conv = w[L - 1] * u
    for j in range(L - 1):
        back = L - 1 - j
        conv = conv + w[j] * jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :T]
    return c * conv


def _geometry(T: int, D: int, L: int, dtype):
    """``(block rows, strip columns, halo rows)`` of both kernels for
    ``bcx[B, T, 3D]`` of ``dtype`` and ``L`` taps, or ``None`` where the
    shape does not tile: a block is whole rows of ``bcx``, as many as
    :data:`_BLOCK_BYTES` hold, a multiple of the halo, which is the
    dtype's sublane tile (16 rows of bf16, 8 of float32)."""
    item = jnp.dtype(dtype).itemsize
    if item not in (2, 4) or not jnp.issubdtype(dtype, jnp.floating):
        return None
    halo = 32 // item
    if D % 128 or T % halo or not 1 <= L <= _EDGE + 1:
        return None
    rows = _tile(T, max(_BLOCK_BYTES // (3 * D * item), halo), halo)
    return rows, _tile(D, _STRIP_COLS, 128), halo


def _shifts(ext, lead: int, rows: int, step: int, L: int):
    """``[ext[lead - step * s:][:rows] for s in range(L)]``, ``step`` +1
    (the rows before) or -1 (the rows after): a rotation along the
    sublanes and an aligned slice each (the rows that wrap lie outside the
    slice)."""
    n = ext.shape[0]
    return [(pltpu.roll(ext, step * s % n, 0) if s else ext)
            [lead:lead + rows] for s in range(L)]


def _weighed(w, shifts):
    """``sum_s w[s] * shifts[s]``."""
    out = w[0] * shifts[0]
    for tap, shift in zip(w[1:], shifts[1:]):
        out = out + tap * shift
    return out


def _strip(ref, edge_ref, col, outside, *, before: bool):
    """float32 columns ``col`` of a block with :data:`_EDGE` rows of the
    neighbouring tile ``edge_ref`` before or after them, zeros where the
    neighbour lies ``outside`` the sequence."""
    own = ref[0, :, col].astype(jnp.float32)
    edge = jnp.where(outside, 0.0, edge_ref[0, :, col].astype(jnp.float32))
    if before:
        return jnp.concatenate([edge[-_EDGE:], own], axis=0)
    return jnp.concatenate([own, edge[:_EDGE]], axis=0)


def _taps(w_ref, col, dtype):
    """The taps of columns ``col`` as the operands' dtype holds them, in
    float32, the nearest first: ``[w[L-1], w[L-2], ...]``."""
    w = w_ref[:, col].astype(dtype).astype(jnp.float32)
    L = w.shape[0]
    return [w[L - 1 - s:L - s] for s in range(L)]


def _strips(D: int, cols: int):
    """A block walked in strips of ``cols`` columns: ``(the strip's columns
    of a [.., D] array, of each third of a [.., 3D] one)``."""
    return [(slice(c, c + cols),
             [slice(k * D + c, k * D + c + cols) for k in range(3)])
            for c in range(0, D, cols)]


def _fwd_body(bcx_ref, before_ref, w_ref, y_ref, *, D, cols):
    rows = y_ref.shape[1]
    first = pl.program_id(1) == 0
    for own, (b_col, c_col, x_col) in _strips(D, cols):
        w = _taps(w_ref, own, bcx_ref.dtype)
        u = _strip(bcx_ref, before_ref, b_col, first, before=True) * \
            _strip(bcx_ref, before_ref, x_col, first, before=True)
        conv = _weighed(w, _shifts(u, _EDGE, rows, 1, len(w)))
        gate = bcx_ref[0, :, c_col].astype(jnp.float32)
        y_ref[0, :, own] = (gate * conv).astype(y_ref.dtype)


def _bwd_body(bcx_ref, before_ref, after_ref, dy_ref, dy_after_ref, w_ref,
              dbcx_ref, dw_ref, *, D, cols):
    rows, L = dy_ref.shape[1], w_ref.shape[0]
    i = pl.program_id(1)
    first, last = i == 0, i == pl.num_programs(1) - 1
    for own, (b_col, c_col, x_col) in _strips(D, cols):
        w = _taps(w_ref, own, bcx_ref.dtype)
        b = _strip(bcx_ref, before_ref, b_col, first, before=True)
        x = _strip(bcx_ref, before_ref, x_col, first, before=True)
        # ``conv`` again for the gate's gradient; the shifted ``u`` also
        # meet ``dconv`` in the taps' gradient
        back = _shifts(b * x, _EDGE, rows, 1, L)
        conv = _weighed(w, back)
        dy = _strip(dy_ref, dy_after_ref, own, last, before=False)
        dconv = dy * _strip(bcx_ref, after_ref, c_col, last, before=False)
        du = _weighed(w, _shifts(dconv, 0, rows, -1, L))
        dbcx_ref[0, :, b_col] = (du * x[_EDGE:]).astype(dbcx_ref.dtype)
        dbcx_ref[0, :, c_col] = (dy[:rows] * conv).astype(dbcx_ref.dtype)
        dbcx_ref[0, :, x_col] = (du * b[_EDGE:]).astype(dbcx_ref.dtype)
        for s in range(L):
            j = L - 1 - s
            total = jnp.sum(dconv[:rows] * back[s], axis=0, keepdims=True)
            dw_ref[0, j:j + 1, own] = jnp.where(
                first, total, dw_ref[0, j:j + 1, own] + total)


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _specs(T, rows, halo):
    """Block specs of a ``[B, T, width]`` array: the block of a grid step,
    the sublane tile before it and the one after it (clamped inside the
    array; the kernels mask what the clamp repeats)."""
    per, tiles = rows // halo, T // halo

    def block(width):
        return pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0))

    def before(width):
        return pl.BlockSpec(
            (1, halo, width),
            lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0))

    def after(width):
        return pl.BlockSpec(
            (1, halo, width),
            lambda b, i: (b, jnp.minimum((i + 1) * per, tiles - 1), 0))

    return block, before, after


def _forward(bcx, taps, geometry, interpret):
    B, T, D = bcx.shape[0], bcx.shape[1], taps.shape[1]
    rows, cols, halo = geometry
    block, before, _ = _specs(T, rows, halo)
    with jax.named_scope(train_path.SHORT_CONV):
        return pl.pallas_call(
            functools.partial(_fwd_body, D=D, cols=cols),
            name=FWD,
            grid=(B, T // rows),
            in_specs=[block(3 * D), before(3 * D),
                      pl.BlockSpec(taps.shape, lambda b, i: (0, 0))],
            out_specs=block(D),
            out_shape=jax.ShapeDtypeStruct((B, T, D), bcx.dtype),
            compiler_params=_params(("parallel", "parallel")),
            interpret=interpret,
        )(bcx, bcx, taps)


def _backward(bcx, taps, dy, geometry, interpret):
    B, T, D = bcx.shape[0], bcx.shape[1], taps.shape[1]
    rows, cols, halo = geometry
    block, before, after = _specs(T, rows, halo)
    with jax.named_scope(train_path.SHORT_CONV):
        dbcx, dw = pl.pallas_call(
            functools.partial(_bwd_body, D=D, cols=cols),
            name=BWD,
            grid=(B, T // rows),
            in_specs=[block(3 * D), before(3 * D), after(3 * D),
                      block(D), after(D),
                      pl.BlockSpec(taps.shape, lambda b, i: (0, 0))],
            # the taps' gradient of a row of the batch stays in VMEM
            # while the row's blocks go by
            out_specs=[block(3 * D),
                       pl.BlockSpec((1,) + taps.shape,
                                    lambda b, i: (b, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                       jax.ShapeDtypeStruct((B,) + taps.shape,
                                            jnp.float32)],
            compiler_params=_params(("parallel", "arbitrary")),
            interpret=interpret,
        )(bcx, bcx, bcx, dy, dy, taps)
        return dbcx, dw.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused(bcx, taps, geometry, interpret):
    return _forward(bcx, taps, geometry, interpret)


def _fused_fwd(bcx, taps, geometry, interpret):
    return _forward(bcx, taps, geometry, interpret), (bcx, taps)


def _fused_bwd(geometry, interpret, res, dy):
    bcx, taps = res
    dbcx, dtaps = _backward(bcx, taps, dy.astype(bcx.dtype), geometry,
                            interpret)
    return dbcx, dtaps.astype(taps.dtype)


_fused.defvjp(_fused_fwd, _fused_bwd)

# a jit of its own, as the grouped matmul has: a stack's equal layers share
# one trace and one lowering of the kernels
_fused_call = jax.jit(_fused, static_argnums=(2, 3))


def _publish_fused(fused: bool):
    """Gauge :data:`train_path.SHORT_CONV_FUSED`, set while the caller's
    program is traced."""
    from chainermn_tpu.observability.metrics import registry

    registry().gauge(
        train_path.SHORT_CONV_FUSED,
        "1 where the gated short convolution traced last ran its "
        "gate-and-tap chain as the two Pallas kernels, 0 where its shape "
        "does not tile and it took the plain jax.numpy spelling",
    ).set(float(fused))


def gated_short_conv(bcx, taps):
    """``c * conv(b * x)`` of ``(b, c, x) = split3(bcx)``.

    Args:
      bcx: ``[B, T, 3D]``, the input projection's result; its dtype is the
        operands' and the result's.
      taps: ``[L, D]``, rounded to ``bcx.dtype`` for the products; tap
        ``j`` multiplies ``u`` ``L - 1 - j`` positions back. Its gradient
        comes back in its own dtype, summed in float32.
    """
    if bcx.ndim != 3 or taps.ndim != 2 or bcx.shape[2] != 3 * taps.shape[1]:
        raise ValueError(
            f"gated_short_conv takes bcx [B, T, 3D] and taps [L, D]; got "
            f"{bcx.shape}, {taps.shape}")
    geometry = _geometry(bcx.shape[1], taps.shape[1], taps.shape[0],
                         bcx.dtype)
    _publish_fused(geometry is not None)
    if geometry is None:
        with jax.named_scope(train_path.SHORT_CONV):
            return plain(bcx, taps)
    return _fused_call(bcx, taps, geometry, _use_interpret())
