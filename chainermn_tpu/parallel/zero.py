"""ZeRO-style optimizer-state sharding over the data-parallel axis.

Absent from the reference (SURVEY.md section 2.2 flags it as the natural
TPU-era extension, hinted by PAPERS.md's automatic cross-replica sharding
retrieval): in plain data parallelism every shard holds the FULL optimizer
state (2x params for Adam). Here each of the ``n`` data shards owns ``1/n``
of every parameter's state:

  1. gradients are ``psum_scatter``-ed — each shard receives the *mean* of
     its own 1/n chunk (same wire bytes as the allreduce it replaces: a
     reduce-scatter is half an allreduce);
  2. the inner optimizer updates only the local chunk (1/n state, 1/n
     update FLOPs);
  3. chunk updates are ``all_gather``-ed back (the other half of the
     allreduce) and applied to the replicated parameters.

Constraint: the inner optimizer must be *elementwise* (sgd/momentum/adam/
adamw/rmsprop...) — anything computing cross-parameter statistics
(global-norm clipping) would see only chunks. Compose such transforms
outside the wrapper.

Usage (inside the shard_map'd train step, like every in-jit collective):

    opt = zero_shard_optimizer(optax.adamw(1e-3), axis_name='data')
    state = opt.init(params)          # per-shard: holds 1/n of adam state
    updates, state = opt.update(grads, state, params)
    params = optax.apply_updates(params, updates)

``axis_name`` may also be a TUPLE of mesh axes: the state then shards
over their flattened product (ravelled index, product size) — the
layout :func:`zero_shard_optimizer` shards its state in. The
data-parallel wrapper's ``reduction_schedule='zero'``
(:class:`chainermn_tpu.optimizers.MultiNodeOptimizer`) and the
:class:`~chainermn_tpu.parallel.plan.ParallelPlan`'s zero group run
:func:`zero_grad_scatter`, the inner update on the chunk and
:func:`zero_gather_updates`: the reduce-scatter, the 1/n update and
the allgather in the gradient-reduction path itself
(arXiv:2004.13336).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

# Multi-axis group helpers: ONE owner of the flattened ravelled-index
# convention (collectives) — the sharded update depends on the scatter
# chunk index and the state shard index agreeing, so no second copy of
# the axis-order rule may live here.
from chainermn_tpu.parallel.collectives import (
    _names_tuple as _names,
    axes_index as _group_index,
    axes_size as _group_size,
    two_level_shard_len as _shard_len,
)

PyTree = Any


def _chunk_rows(x: jax.Array, n: int) -> jax.Array:
    """Flatten ``x`` and pad so it splits into ``n`` equal rows [n, c].

    The row length comes from ``collectives.two_level_shard_len``, the
    ONE owner of the ceil-pad rule: gradient chunks, parameter chunks
    and the state's rows pair up only while all read the same rule."""
    flat = x.reshape(-1)
    c = _shard_len(flat.size, n)
    return jnp.pad(flat, (0, n * c - flat.size)).reshape(n, c)



def _unchunk(rows: jax.Array, shape, dtype) -> jax.Array:
    size = 1
    for s in shape:
        size *= s
    return rows.reshape(-1)[:size].reshape(shape).astype(dtype)


def zero_state_specs(
    inner: optax.GradientTransformation,
    params: PyTree,
    n: int,
    axis_name: str,
) -> PyTree:
    """PartitionSpec tree for the ZeRO-sharded state of ``inner`` — the
    shard_map ``in_specs``/``out_specs`` entry for the optimizer state.

    Chunked (array) leaves concatenate over ``axis_name``; scalar leaves
    (step counters, identical on every shard) stay replicated. Shapes come
    from ``eval_shape`` on abstract 1/n chunks, so nothing is materialised.
    """
    from jax.sharding import PartitionSpec as P

    chunks = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((-(-x.size // n),), x.dtype), params
    )
    template = jax.eval_shape(inner.init, chunks)
    return jax.tree.map(
        lambda l: P(axis_name) if getattr(l, "ndim", 0) >= 1 else P(),
        template,
    )


# ---------------------------------------------------------------------------
# ParallelPlan spec-provider surface (ISSUE 10): the plan composes ZeRO
# from these pieces instead of wrapping the optimizer at the call site —
# this module owes the compiled step exactly one reduce-scatter in and
# one all-gather out per float leaf, and publishes the stacked-state
# layout the plan's shard_map carries with an honest P(axis) spec.
# ---------------------------------------------------------------------------


def zero_plan_axis(axis_name: str = "zero") -> dict:
    """Spec-provider descriptor for :class:`~chainermn_tpu.parallel.plan.
    ParallelPlan`: the ``zero`` axis shards the OPTIMIZER STATE (params
    stay replicated over it — it is a data-parallel axis whose state is
    chunked), and owes the compiled step one reduce-scatter + one
    all-gather per parameter leaf."""
    return {
        "name": axis_name,
        "stacked": False,  # params do NOT stack a leading dim over it
        "state_stacked": True,  # opt state stacks [n, ...] over it
        "collectives": ("reduce-scatter", "all-gather"),
    }


def zero_stacked_init(inner: optax.GradientTransformation, leaves, n: int):
    """Initialise the plan's stacked ZeRO state over ``leaves`` (a list
    pytree of param leaves): every state leaf comes back stacked
    ``[n, ...]`` (scalar counters tiled), so one per-leaf ``P(axis)``
    spec shards the whole subtree — the same layout
    :class:`chainermn_tpu.optimizers.MultiNodeOptimizer`'s ``'zero'``
    schedule uses."""
    rows = [_chunk_rows(jnp.asarray(p), n) for p in leaves]
    return jax.vmap(inner.init)(rows)


def zero_grad_scatter(
    g: jax.Array, axis_name: str, *, extra_axes=(), total: int | None = None
) -> jax.Array:
    """This shard's MEAN gradient chunk: one ``psum_scatter`` over
    ``axis_name`` (half an allreduce's wire bytes) plus — when the plan
    carries more data-parallel axes — one ``psum`` of the 1/n chunk over
    ``extra_axes``. ``total`` is the full data-parallel degree the mean
    divides by (defaults to the product of the named axes). Call inside
    ``shard_map``."""
    n = lax.axis_size(axis_name)
    rows = _chunk_rows(g, n)
    part = lax.psum_scatter(rows, axis_name, scatter_dimension=0, tiled=False)
    if extra_axes:
        part = lax.psum(part, tuple(extra_axes))
    if total is None:
        total = n
        for a in extra_axes:
            total = total * lax.axis_size(a)
    return (part / total).astype(g.dtype)


def zero_param_chunk(p: jax.Array, axis_name: str) -> jax.Array:
    """This shard's 1/n chunk of a replicated parameter (the slice the
    sharded update owns). Call inside ``shard_map``."""
    n = lax.axis_size(axis_name)
    return lax.dynamic_index_in_dim(
        _chunk_rows(p, n), lax.axis_index(axis_name), keepdims=False
    )


def zero_gather_updates(u_chunk: jax.Array, like: jax.Array,
                        axis_name: str) -> jax.Array:
    """All-gather the per-shard update chunks back to ``like``'s full
    shape — the other half of the allreduce the scatter replaced. Call
    inside ``shard_map``."""
    rows = lax.all_gather(u_chunk, axis_name, axis=0, tiled=False)
    return _unchunk(rows, like.shape, like.dtype)


def zero_shard_optimizer(
    inner: optax.GradientTransformation,
    axis_name: str,
    *,
    compress_dtype=None,
) -> optax.GradientTransformation:
    """Wrap an elementwise optax transform with ZeRO-1 state sharding over
    ``axis_name``. Must be used inside that named-axis context (shard_map).

    ``compress_dtype`` casts gradients before the reduce-scatter (the
    bf16-compressed-allreduce feature, applied to the scatter instead).
    """

    names = _names(axis_name)

    def my_chunk(tree: PyTree) -> PyTree:
        idx = _group_index(names)
        n = _group_size(names)
        return jax.tree.map(
            lambda x: lax.dynamic_index_in_dim(
                _chunk_rows(x, n), idx, keepdims=False
            ),
            tree,
        )

    def init_fn(params: PyTree):
        return inner.init(my_chunk(params))

    def _scatter(rows):
        # [n_total, c] -> this shard's [c] chunk-sum: one psum_scatter
        # per axis (rows viewed [n_a, n_b, ..., c]; each stage scatters
        # its leading axis) — a flattened multi-axis reduce-scatter.
        dims = tuple(lax.axis_size(a) for a in names)
        rows = rows.reshape(dims + rows.shape[1:])
        for a in names:
            rows = lax.psum_scatter(
                rows, a, scatter_dimension=0, tiled=False
            )
        return rows

    def update_fn(grads: PyTree, state, params: Optional[PyTree] = None):
        n = _group_size(names)

        def rs(g):
            rows = _chunk_rows(g, n)
            if compress_dtype is not None and jnp.issubdtype(
                g.dtype, jnp.floating
            ):
                return (_scatter(rows.astype(compress_dtype))
                        .astype(g.dtype) / n)
            return _scatter(rows) / n

        grad_chunks = jax.tree.map(rs, grads)
        param_chunks = my_chunk(params) if params is not None else None
        update_chunks, state = inner.update(grad_chunks, state, param_chunks)

        def ag(u, g):
            rows = u
            for a in reversed(names):
                rows = lax.all_gather(rows, a, axis=0, tiled=False)
            return _unchunk(rows, g.shape, g.dtype)

        updates = jax.tree.map(ag, update_chunks, grads)
        return updates, state

    return optax.GradientTransformation(init_fn, update_fn)
