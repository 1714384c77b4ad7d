"""The named gradient-reduction schedules, written over
:mod:`chainermn_tpu.parallel.collectives`.

Three names, one bucket layout, one eager double-buffered driver:

- ``'flat'``: float leaves ride ~64 MB flat buckets (the reference's
  ``_memory_utility.pack_params`` (dagger) flat-buffer discipline,
  in-jit so XLA owns the copies), one ``pmean`` per bucket (on the int8
  wire :func:`~chainermn_tpu.parallel.collectives.int8_allreduce_mean`).
- ``'two_level'``: per bucket
  :func:`~chainermn_tpu.parallel.collectives.decomposed_allreduce`:
  ``psum_scatter`` over the last (fast/intra) mesh axis, allreduce of
  the 1/n shard over the remaining axes, ``all_gather`` back — the
  reference's ``TwoDimensionalCommunicator`` algorithm
  (``two_dimensional_communicator.py`` (dagger)) on any mesh (on a flat
  mesh the reduce-scatter/all-gather decomposition; on the int8 wire
  :func:`~chainermn_tpu.parallel.collectives.
  int8_decomposed_allreduce_mean`).
- ``'zero'``: reduce-scatter + SHARDED update + allgather: the
  optimizer update itself runs on 1/n of the parameters (1/n optimizer
  state, 1/n update FLOPs, same wire bytes as the allreduce it
  replaces; arXiv:2004.13336). Structural: it lives in
  :class:`chainermn_tpu.optimizers.MultiNodeOptimizer`, over
  :mod:`chainermn_tpu.parallel.zero`'s chunk layout.

:func:`reduce_tree` runs the first two; :func:`bucket_partition` is the
bucket layout every consumer shares. The optimizer's default
(``reduction_schedule=None``) is none of these: it reduces every large
matrix where it lies (``optimizers.allreduce_gradients``).

Double buffering (the reference's ``double_buffering_optimizer.py``
(dagger) staleness-1 semantics) rides the bucketed schedules: an
overlapped reduction tags its ``wire`` trace events with
``overlapped=True`` so ``tools/trace_report.py`` can report the
comm-hidden fraction; :class:`OverlappedBucketReducer` is the eager
per-bucket driver that MEASURES the overlap (dispatch step N's bucket
collectives without blocking, collect them after step N+1's compute).
"""

from __future__ import annotations

import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.observability import trace as _trace
from chainermn_tpu.observability import train_path

PyTree = Any

#: The schedule names ``MultiNodeOptimizer(reduction_schedule=)`` takes
#: besides ``None``.
SCHEDULES = ("flat", "two_level", "zero")

#: ~64 MB (the tuned table default of ``allreduce_bucket_mb``) — the
#: single fallback the bucket partition uses when no tuned size is
#: pinned; large enough to keep the slow level bandwidth-bound, small
#: enough to bound the transient flat copy in HBM.
DEFAULT_BUCKET_BYTES = 64 << 20


def bucket_partition(
    idxs: Sequence[int],
    sizes: Sequence[int],
    itemsize: int = 4,
    bucket_bytes: Optional[int] = None,
) -> list[list[int]]:
    """Deterministic greedy ~``bucket_bytes`` partition of the entries
    ``idxs`` (element counts in ``sizes``) — the ONE bucket layout
    shared by every schedule, the EF residual allocation, and the
    overlapped reducer, so no two consumers can disagree.

    Edge contract (ISSUE 3 satellite, unit-tested):

    - zero-size entries are SKIPPED — they would otherwise produce
      empty buckets whose concatenated payload has no max-abs for the
      int8 wire's scale (callers reduce them on the exact per-leaf
      path, where an empty array is trivially its own mean);
    - a payload smaller than one bucket yields EXACTLY one bucket (no
      degenerate empty tail);
    - a single entry larger than the bucket gets its own bucket,
      unsplit;
    - no emitted bucket is ever empty.
    """
    if bucket_bytes is None:
        bucket_bytes = DEFAULT_BUCKET_BYTES
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_bytes = 0
    for i in idxs:
        nbytes = sizes[i] * itemsize
        if nbytes == 0:
            continue
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def reduce_tree(
    grads: PyTree,
    *,
    schedule: str,
    axes,
    compress_dtype=None,
    bucket_bytes: Optional[int] = None,
    overlapped: bool = False,
    provenance: Optional[dict] = None,
    op: Optional[str] = None,
    size: Optional[int] = None,
) -> PyTree:
    """Bucketed in-jit MEAN reduction of a gradient pytree under
    ``schedule`` (``'flat'`` or ``'two_level'``). Must run inside the
    named-axis context of ``axes`` (callers probe
    ``collectives.axes_bound`` and fall back to their legacy
    identity/pmean path outside it — this function does not degrade).

    Leaves are grouped by wire dtype and packed into ~``bucket_bytes``
    flat buffers (:func:`bucket_partition`). A ``'flat'`` bucket is one
    ``pmean`` over ``axes``; a ``'two_level'`` bucket is
    :func:`~chainermn_tpu.parallel.collectives.decomposed_allreduce`
    (reduce-scatter over the last axis, allreduce of the shard over the
    rest, all-gather back). On the int8 wire the bucket packs in f32
    and crosses as ``int8_allreduce_mean`` /
    ``int8_decomposed_allreduce_mean``.

    Zero-size leaves take the exact per-leaf path (see
    :func:`bucket_partition`'s edge contract). At TRACE time (host-side
    Python, once per compilation — the lowered HLO is untouched) one
    ``pack`` event plus one ``wire`` event PER BUCKET PER STAGE are
    recorded: ``stage`` names the collective and its axes
    (``ar(<axes>)``; ``rs(<intra>)``, ``ar(<rest>)``, ``ag(<intra>)``)
    and ``nbytes`` what that stage carries (the bucket into a
    reduce-scatter and out of an all-gather, the bucket over the intra
    size through the all-reduce between them), plus ``overlapped``
    (true under the double-buffered mode, whose update consumes the
    PREVIOUS step's buckets — the dependency break that lets the
    runtime run these collectives concurrently with compute) so
    ``tools/trace_report.py`` can report the comm-hidden fraction.
    """
    from chainermn_tpu.parallel.collectives import (
        decomposed_allreduce,
        grad_wire_bytes,
        int8_allreduce_mean,
        int8_decomposed_allreduce_mean,
        publish_grad_wire,
        two_level_shard_len,
        _names_tuple,
    )

    if schedule not in ("flat", "two_level"):
        raise ValueError(
            f"reduce_tree runs 'flat' or 'two_level', got {schedule!r} "
            "('zero' is MultiNodeOptimizer's sharded update)"
        )
    names = _names_tuple(axes)
    two_level = schedule == "two_level"
    int8_wire = (compress_dtype is not None
                 and jnp.dtype(compress_dtype) == jnp.dtype(jnp.int8))
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        return grads

    def cast_dtype(g):
        if compress_dtype is not None and jnp.issubdtype(
            g.dtype, jnp.floating
        ):
            # int8 wire: buckets pack in f32; quantization happens
            # inside the wire per bucket.
            return (jnp.dtype(jnp.float32) if int8_wire
                    else jnp.dtype(compress_dtype))
        return jnp.dtype(g.dtype)

    out: list = [None] * len(leaves)
    sizes = [g.size for g in leaves]
    groups: dict = {}
    for i, g in enumerate(leaves):
        groups.setdefault(cast_dtype(g), []).append(i)

    def exact_mean(g):
        # Per-leaf exact path (zero-size leaves): pmean keeps the
        # reference-parity dtype contract.
        return lax.pmean(g, names).astype(g.dtype)

    def reduce_bucket(flat, dt):
        if int8_wire and jnp.issubdtype(dt, jnp.floating):
            fn = (int8_decomposed_allreduce_mean if two_level
                  else int8_allreduce_mean)
            return fn(flat, names)
        if two_level:
            return decomposed_allreduce(flat, names)
        return lax.pmean(flat, names)

    rec = _trace.active()
    # (element count, wire bytes an element, dtype name) per bucket
    bucket_meta: list[tuple[int, int, str]] = []
    for dt, idxs in groups.items():
        itemsize = jnp.dtype(dt).itemsize
        wire_item = (1 if int8_wire and jnp.issubdtype(dt, jnp.floating)
                     else itemsize)
        buckets = bucket_partition(idxs, sizes, itemsize, bucket_bytes)
        bucketed = {i for b in buckets for i in b}
        for i in idxs:
            if i not in bucketed:  # zero-size leaf: exact per-leaf path
                out[i] = exact_mean(leaves[i])
        for bidx in buckets:
            with jax.named_scope(train_path.bucket_scope(len(bucket_meta))):
                flat = jnp.concatenate(
                    [leaves[i].astype(dt).ravel() for i in bidx]
                )
                red = reduce_bucket(flat, dt)
                off = 0
                for i in bidx:
                    n = leaves[i].size
                    out[i] = (
                        red[off: off + n]
                        .reshape(leaves[i].shape)
                        .astype(leaves[i].dtype)
                    )
                    off += n
            bucket_meta.append((flat.size, wire_item, jnp.dtype(dt).name))

    n_buckets = len(bucket_meta)
    publish_grad_wire(leaves, compress_dtype, names, n_buckets)
    if rec is not None:
        wire_name = ("int8" if int8_wire else
                     (jnp.dtype(compress_dtype).name
                      if compress_dtype is not None else "none"))
        rec.event(
            "pack", op=(op or f"scheduled_reduce[{schedule}]"),
            nbytes=sum(grad_wire_bytes(leaves, compress_dtype).values()),
            bucket_bytes=(bucket_bytes if bucket_bytes is not None
                          else DEFAULT_BUCKET_BYTES),
            n_buckets=n_buckets,
            wire_dtype=wire_name,
            provenance=provenance,
            **({"size": size} if size is not None else {}),
        )
        intra, rest = names[-1], names[:-1]
        n_intra = lax.axis_size(intra)
        for b_i, (n_elems, wire_item, dt_name) in enumerate(bucket_meta):
            nbytes = n_elems * wire_item
            if two_level:
                stages = [(f"rs({intra})", nbytes)]
                if rest:
                    shard = two_level_shard_len(n_elems, n_intra)
                    stages.append(
                        (f"ar({'+'.join(rest)})", shard * wire_item))
                stages.append((f"ag({intra})", nbytes))
            else:
                stages = [(f"ar({'+'.join(names)})", nbytes)]
            for s_i, (stage, stage_bytes) in enumerate(stages):
                rec.event(
                    "wire", schedule=schedule, stage=stage, stage_index=s_i,
                    bucket=b_i, n_buckets=n_buckets, nbytes=stage_bytes,
                    wire_dtype=("int8" if int8_wire and "float" in dt_name
                                else dt_name),
                    overlapped=bool(overlapped),
                )
    return jax.tree.unflatten(treedef, out)


class OverlappedBucketReducer:
    """Eager double-buffered per-bucket gradient reduction — the
    MEASURED side of the overlap story (the in-jit double-buffered mode
    relies on XLA's async scheduler; this driver makes the overlap an
    explicit host-side pipeline, and its wire events carry true
    durations).

    Usage (the staleness-1 loop, reference
    ``double_buffering_optimizer.py`` (dagger) semantics)::

        red = OverlappedBucketReducer(comm)
        red.dispatch(stacked_grads_t)       # per-bucket collectives fly
        ...compute step t+1's backward...   # overlaps the wire
        mean_t = red.collect()              # blocks only on what's left

    ``dispatch`` partitions the stacked gradient tree (leaves
    ``[size, ...]``, the eager-communicator convention) into the tuned
    ~64 MB buckets and launches one jitted mean-allreduce per bucket
    WITHOUT blocking — JAX's async dispatch keeps them in flight while
    the caller computes. ``collect`` blocks on each bucket and records
    one ``wire`` trace event per bucket with ``dur_s`` (dispatch ->
    ready) and ``blocked_s`` (time actually spent waiting inside
    collect): the difference is the comm time HIDDEN behind compute,
    which ``tools/trace_report.py``'s overlap section aggregates into
    the comm-hidden fraction.
    """

    def __init__(self, comm, *, bucket_bytes: Optional[int] = None) -> None:
        self.comm = comm
        if bucket_bytes is None:
            from chainermn_tpu.parallel.collectives import tuned_bucket_bytes

            bucket_bytes = tuned_bucket_bytes(comm.device_kind, comm.size)
        self.bucket_bytes = bucket_bytes
        self._inflight: list = []
        self._layout = None

    @property
    def in_flight(self) -> bool:
        return bool(self._inflight)

    def dispatch(self, grads_stacked: PyTree) -> int:
        """Launch this step's per-bucket mean-allreduces (leaves are
        stacked ``[size, ...]`` per-rank contributions); returns the
        bucket count. A previous step's reduction must have been
        collected first."""
        if self._inflight:
            raise RuntimeError(
                "a bucketed reduction is already in flight — collect() "
                "the previous step before dispatching the next"
            )
        n = self.comm.size
        leaves, treedef = jax.tree.flatten(grads_stacked)
        for leaf in leaves:
            if leaf.shape[0] != n:
                raise ValueError(
                    f"stacked leaves must have leading dim == size ({n}), "
                    f"got {leaf.shape}"
                )
        sizes = [leaf[0].size for leaf in leaves]
        # itemsize 4: every bucket packs (and crosses the wire) in f32.
        buckets = bucket_partition(
            list(range(len(leaves))), sizes, 4, self.bucket_bytes,
        )
        self._layout = (treedef, leaves, buckets)
        mean = self.comm._jitted["mean"]
        for b_i, bidx in enumerate(buckets):
            flat = jnp.concatenate(
                [jnp.asarray(leaves[i]).astype(jnp.float32).reshape(n, -1)
                 for i in bidx],
                axis=1,
            )
            t0 = time.perf_counter()
            out = mean(flat)  # async dispatch: returns pre-wire
            self._inflight.append((b_i, bidx, out, t0, int(flat.nbytes)))
        return len(buckets)

    def collect(self) -> PyTree:
        """Block on the in-flight buckets and return the reduced mean
        tree (leaves ``[...]``, un-stacked). Records one ``wire`` event
        per bucket: ``dur_s`` is dispatch->ready, ``blocked_s`` the
        wait actually paid here — ``dur_s - blocked_s`` is comm hidden
        behind whatever the caller computed in between."""
        if not self._inflight:
            raise RuntimeError("collect() with no dispatched reduction")
        treedef, leaves, buckets = self._layout
        rec = _trace.active()
        out: list = [None] * len(leaves)
        bucketed = {i for b in buckets for i in b}
        for i, leaf in enumerate(leaves):
            if i not in bucketed:  # zero-size leaves: mean is identity
                out[i] = jnp.asarray(leaf)[0]
        for b_i, bidx, red, t0, nbytes in self._inflight:
            t_c = time.perf_counter()
            red = jax.block_until_ready(red)
            t_r = time.perf_counter()
            if rec is not None:
                dur = t_r - t0
                blocked = t_r - t_c
                rec.event(
                    "wire", schedule="overlap_eager", bucket=b_i,
                    n_buckets=len(buckets), nbytes=nbytes,
                    dur_s=round(dur, 9), blocked_s=round(blocked, 9),
                    overlapped=bool(dur - blocked > 0),
                )
            row = red[0]  # [k]: the replicated mean
            off = 0
            for i in bidx:
                k = leaves[i][0].size
                out[i] = (row[off: off + k]
                          .reshape(leaves[i].shape[1:])
                          .astype(leaves[i].dtype))
                off += k
        self._inflight = []
        self._layout = None
        return jax.tree.unflatten(treedef, out)


__all__ = [
    "DEFAULT_BUCKET_BYTES",
    "OverlappedBucketReducer",
    "SCHEDULES",
    "bucket_partition",
    "reduce_tree",
]
