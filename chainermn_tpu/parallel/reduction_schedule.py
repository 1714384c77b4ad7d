"""Interchangeable gradient-reduction schedules — the hot-path abstraction.

The one collective every data-parallel workload shares is the gradient
reduction, and the right ALGORITHM for it depends on the topology:
HiCCL (arXiv:2408.05962) shows hierarchy-aware collective composition
(intra reduce-scatter -> inter allreduce -> allgather) beating a flat
allreduce on multi-chip meshes, and Xu et al. (arXiv:2004.13336) show a
reduce-scatter + sharded weight update strictly dominating replicated
allreduce+update at data-parallel scale. This module gives the
framework ONE schedule abstraction whose entries are DERIVED INSTANCES
of the composition DSL (:mod:`chainermn_tpu.parallel.composition`,
ISSUE 12): every spelling — a menu name below, a composition signature
string, or a ``Composition`` — compiles through ``compile_schedule``
and runs through the one staged executor ``reduce_composed``, and the
autotuner's candidate set is the deriver's output for the world shape,
not a fixed menu. The three named, equivalence-tested strategies
(``tests/test_reduction_schedule.py``; derived sweep in
``tests/test_composition.py``):

- ``'flat'`` — the existing packed allreduce: float leaves ride ~64 MB
  flat buckets (the reference's ``_memory_utility.pack_params`` (dagger)
  flat-buffer discipline, in-jit so XLA owns the copies), one fused
  ``pmean`` per bucket.
- ``'two_level'`` — the pinned hierarchical pipeline per bucket:
  ``psum_scatter`` over the last (fast/intra) mesh axis, allreduce of
  the 1/n shard over the remaining axes, ``all_gather`` back — the
  reference's ``TwoDimensionalCommunicator`` algorithm
  (``two_dimensional_communicator.py`` (dagger)) generalised to any
  mesh (on a flat mesh it pins the reduce-scatter/all-gather
  decomposition).
- ``'zero'`` — reduce-scatter + SHARDED update + allgather, fusing with
  :mod:`chainermn_tpu.parallel.zero`: the optimizer update itself runs
  on 1/n of the parameters (1/n optimizer state, 1/n update FLOPs,
  same wire bytes as the allreduce it replaces). Structural — lives in
  :class:`chainermn_tpu.optimizers.MultiNodeOptimizer`, which calls the
  chunk/scatter/gather building blocks here.

Schedule choice is a decision in the registry
(:mod:`chainermn_tpu.tuning`, decision ``'reduction_schedule'``), keyed
(device_kind x world-shape x payload-MB bucket) — :func:`resolve_schedule`.

Double buffering (the reference's ``double_buffering_optimizer.py``
(dagger) staleness-1 semantics) composes with the bucketed schedules:
an overlapped reduction tags its per-bucket ``wire`` trace events with
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

#: The NAMED strategies (the head of the registry's candidate list —
#: the full choice set for a world shape is
#: :func:`chainermn_tpu.parallel.composition.schedule_candidates`,
#: which appends the derived beyond-menu composition signatures).
SCHEDULES = ("flat", "two_level", "zero")

#: Registry decision name for the ``'auto'`` schedule resolution.
DECISION = "reduction_schedule"

#: Registry decision name for the bucket-slice count a composed
#: schedule interleaves over (ISSUE 15): ∈ {1, 2, 4, 8}, table default
#: 1 — slicing multiplies per-stage collective dispatches S× (at 1/S
#: payload each). Keyed beside ``DECISION`` on world-shape x payload-MB
#: so one cell adjudicates both.
SLICES_DECISION = "comp_slices"

#: The ``comp_slices`` candidate set (registry spellings are strings).
SLICE_CANDIDATES = ("1", "2", "4", "8")

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


def resolve_comp_slices(
    device_kind: Optional[str],
    payload_bytes: int,
    world_shape: Sequence[int],
) -> int:
    """The ``comp_slices`` resolution (ISSUE 15): how many bucket
    slices a composed reduction interleaves over, through the autotune
    registry — keyed exactly like :func:`resolve_schedule` (world-shape
    x payload-MB, dtype tag ``'slices'``), table default 1."""
    from chainermn_tpu import tuning

    mb = max(1, int(payload_bytes) >> 20)
    key = tuning.decision_key(
        device_kind, shape=tuple(int(d) for d in world_shape) + (mb,),
        dtype="slices",
    )
    return int(tuning.choice(SLICES_DECISION, SLICE_CANDIDATES, key))


def resolve_schedule(
    device_kind: Optional[str],
    payload_bytes: int,
    world_shape: Sequence[int],
    *,
    candidates: Optional[Sequence[str]] = None,
    slices=None,
):
    """The ``reduction_schedule='auto'`` resolution: winner through the
    autotune registry, keyed ``device_kind x (world-shape, payload-MB)
    x 'sched'`` (each dim power-of-two bucketed by ``decision_key``, so
    nearby payloads share one decision). Returns ``(winner, record)``
    with ``record`` the registry's decision provenance (name / winner /
    source / key, plus ``composition`` — the canonical-token signature
    the winner compiles to, so provenance names the actual pipeline and
    not just a menu label) for the observability layer.

    ``candidates`` defaults to the DERIVED choice set for this world
    shape (:func:`~chainermn_tpu.parallel.composition.
    schedule_candidates`): the menu names plus every composition the
    deriver generates for a ``len(world_shape)``-level mesh, keyed by
    signature string — the autotuner searches generated schedules, not
    a fixed menu. Table default is ``'flat'``.

    ``slices='auto'`` (ISSUE 15) additionally consults the
    ``comp_slices`` decision (:func:`resolve_comp_slices`) and, when it
    resolves > 1 and the winner is sliceable (not the structural
    ``'zero'``), returns the winner's SLICED signature — the record
    then carries ``comp_slices`` and the sliced ``composition``
    spelling. An explicit integer pins the count; ``None`` (default)
    leaves the winner unsliced, the pre-ISSUE-15 behaviour."""
    from chainermn_tpu import tuning
    from chainermn_tpu.parallel.composition import (
        schedule_candidates,
        signature_for,
    )

    n_axes = max(1, len(tuple(world_shape)))
    if candidates is None:
        candidates = schedule_candidates(n_axes)
    mb = max(1, int(payload_bytes) >> 20)
    key = tuning.decision_key(
        device_kind, shape=tuple(int(d) for d in world_shape) + (mb,),
        dtype="sched",
    )
    winner = tuning.choice(DECISION, tuple(candidates), key)
    rec = next(
        (d for d in reversed(tuning.decisions_taken())
         if d.get("name") == DECISION and d.get("key") == key),
        None,
    )
    if rec is not None:
        rec = dict(rec)
        try:
            rec["composition"] = signature_for(winner, n_axes)
        except Exception:
            pass
    if slices is not None and winner != "zero":
        from chainermn_tpu.parallel.composition import (
            canonical_axis_names,
            compile_schedule,
            sliced_composition,
        )

        n_slices = (resolve_comp_slices(device_kind, payload_bytes,
                                        world_shape)
                    if slices == "auto" else int(slices))
        if n_slices > 1:
            comp = sliced_composition(
                compile_schedule(winner, canonical_axis_names(n_axes)),
                n_slices,
            )
            winner = comp.signature()
            if rec is not None:
                rec["comp_slices"] = n_slices
                rec["composition"] = winner
    return winner, rec


def reduce_tree(
    grads: PyTree,
    *,
    schedule,
    axes,
    compress_dtype=None,
    bucket_bytes: Optional[int] = None,
    overlapped: bool = False,
    provenance: Optional[dict] = None,
    op: Optional[str] = None,
    size: Optional[int] = None,
) -> PyTree:
    """Bucketed, schedule-pinned in-jit MEAN reduction of a gradient
    pytree. Must run inside the named-axis context of ``axes`` (callers
    probe ``collectives.axes_bound`` and fall back to their legacy
    identity/pmean path outside it — this function does not degrade).

    ``schedule`` is a menu name (``'flat'`` / ``'two_level'``), a
    composition signature string, or a
    :class:`~chainermn_tpu.parallel.composition.Composition` — every
    spelling is COMPILED to a validated composition
    (:func:`~chainermn_tpu.parallel.composition.compile_schedule`) and
    run through the one staged executor
    (:func:`~chainermn_tpu.parallel.composition.reduce_composed`), so
    the menu entries are derived instances, not separate code paths
    (``'flat'`` = ``ar(all)``, one fused pmean per bucket;
    ``'two_level'`` = ``rs(fast) > ar(rest) > ag(fast)``, the pinned
    hierarchical pipeline). Leaves are grouped by wire dtype and packed
    into ~``bucket_bytes`` flat buffers (:func:`bucket_partition`);
    each bucket crosses the wire as that composition's stage pipeline.
    The int8 wire is a WIRE variant, not a schedule: it has a flat and
    a two-level rendering only (the two-phase quantized scheme has no
    generic staged form), and any other composition on an int8 wire is
    refused loudly. SLICED spellings of those two renderings (ISSUE 16
    satellite, e.g. ``rs(data)[s0..3]>ag(data)``) ARE accepted: each
    bucket slice rides its own two-phase wire — same grammar, per-slice
    quantization scales (so the result matches the unsliced int8 wire
    to quantization tolerance, not bitwise; both stay within the wire's
    stated ~1/127-per-stage error of the exact mean), zigzag ``[z...]``
    cut/reassembly honored.

    Zero-size leaves take the exact per-leaf path (see
    :func:`bucket_partition`'s edge contract). At TRACE time (host-side
    Python, once per compilation — the lowered HLO is untouched) one
    ``pack`` event plus one ``wire`` event PER BUCKET PER STAGE are
    recorded: each wire event carries the bucket's ``composition``
    signature, its ``stage`` (e.g. ``rs(intra)``) and that stage's
    payload bytes, plus ``overlapped`` (true under the double-buffered
    mode, whose update consumes the PREVIOUS step's buckets — the
    dependency break that lets the runtime run these collectives
    concurrently with compute) so ``tools/trace_report.py`` can
    attribute comm time per composition stage.
    """
    from chainermn_tpu.parallel.collectives import (
        grad_wire_bytes,
        int8_allreduce_mean,
        int8_decomposed_allreduce_mean,
        publish_grad_wire,
        _names_tuple,
    )
    from chainermn_tpu.parallel.composition import (
        CompositionError,
        compact_slices,
        compile_schedule,
        effective_slices,
        reduce_composed,
        slice_bounds,
        stage_wire_layout,
        two_level_composition,
    )

    names = _names_tuple(axes)
    try:
        comp = compile_schedule(schedule, names)
    except CompositionError as e:
        raise ValueError(str(e)) from None
    if comp.has_update:
        valid = tuple(s for s in SCHEDULES if s != "zero")
        raise ValueError(
            f"reduce_tree runs the pure reduction schedules {valid} (or "
            f"any validated composition without a sharded_update stage), "
            f"got {schedule!r} — the sharded update is structural, see "
            "MultiNodeOptimizer's 'zero' schedule"
        )
    label = (schedule if isinstance(schedule, str) and "(" not in schedule
             else comp.signature())
    sig = comp.signature()
    int8_wire = (compress_dtype is not None
                 and jnp.dtype(compress_dtype) == jnp.dtype(jnp.int8))
    flat_sig = compile_schedule("flat", names).signature()
    two_level_sig = two_level_composition(names).signature()
    # The int8 gate compares the UNSLICED base pipeline: sliced
    # spellings of the two renderings ride per-slice two-phase wires
    # (ISSUE 16 satellite), anything else is refused.
    import dataclasses as _dc

    base_sig = _dc.replace(
        compact_slices(comp), slices=1, slice_layout="contiguous"
    ).signature()
    if int8_wire and base_sig not in (flat_sig, two_level_sig):
        raise ValueError(
            f"the int8 two-phase wire has flat and two-level renderings "
            f"only (sliced spellings of those included) — composition "
            f"{sig!r} cannot ride it; use the bf16/f32 wire for composed "
            "schedules"
        )
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
            # The quantized wire's rendering is chosen by the
            # composition's SHAPE: a scatter stage means the int8
            # phases ride only the non-scatter axes. Sliced spellings
            # run the two-phase wire per bucket slice (each slice
            # quantizes against its own max-abs), same cut/reassembly
            # indexing as reduce_composed's sliced path.
            fn = (int8_decomposed_allreduce_mean
                  if base_sig == two_level_sig else int8_allreduce_mean)
            s_eff = effective_slices(comp.slices, flat.size)
            if s_eff <= 1:
                return fn(flat, names)
            if comp.slice_layout == "zigzag":
                red = jnp.zeros_like(flat)
                for i in range(s_eff):
                    red = red.at[i::s_eff].set(fn(flat[i::s_eff], names))
                return red
            return jnp.concatenate([
                fn(flat[lo:hi], names)
                for lo, hi in slice_bounds(flat.size, s_eff)
            ])
        return reduce_composed(flat, comp, op="mean")

    rec = _trace.active()
    n_buckets_total = 0
    # (bucket wire bytes, dtype name, element count) per bucket
    bucket_meta: list[tuple[int, str, int]] = []
    for dt, idxs in groups.items():
        itemsize = jnp.dtype(dt).itemsize
        wire_item = (1 if int8_wire and jnp.issubdtype(dt, jnp.floating)
                     else itemsize)
        buckets = bucket_partition(idxs, sizes, itemsize, bucket_bytes)
        bucketed = {i for b in buckets for i in b}
        for i in idxs:
            if i not in bucketed:  # zero-size leaf: exact per-leaf path
                out[i] = exact_mean(leaves[i])
        n_buckets_total += len(buckets)
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
            bucket_meta.append(
                (flat.size * wire_item, jnp.dtype(dt).name, flat.size)
            )

    publish_grad_wire(leaves, compress_dtype, names, n_buckets_total)
    if rec is not None:
        wire_name = ("int8" if int8_wire else
                     (jnp.dtype(compress_dtype).name
                      if compress_dtype is not None else "none"))
        # Slice-degrade provenance (ISSUE 15 satellite, LOUD): a bucket
        # smaller than the requested slice count runs min(S, elements)
        # slices — the pack event names every degraded bucket so the
        # adopted comp_slices can be audited against what actually ran.
        slice_note = {}
        if comp.slices > 1:
            from chainermn_tpu.parallel.composition import (
                effective_slices,
            )

            degraded = {
                b_i: effective_slices(comp.slices, n_elems)
                for b_i, (_, _, n_elems) in enumerate(bucket_meta)
                if effective_slices(comp.slices, n_elems) < comp.slices
            }
            slice_note["comp_slices"] = comp.slices
            if degraded:
                slice_note["comp_slices_degraded"] = degraded
                slice_note["comp_slices_note"] = (
                    f"requested {comp.slices} slices; bucket(s) "
                    f"{sorted(degraded)} smaller than S degraded to "
                    f"min(S, elements) (zero-leaf contract)"
                )
        rec.event(
            "pack", op=(op or f"scheduled_reduce[{label}]"),
            nbytes=sum(grad_wire_bytes(leaves, compress_dtype).values()),
            bucket_bytes=(bucket_bytes if bucket_bytes is not None
                          else DEFAULT_BUCKET_BYTES),
            n_buckets=n_buckets_total,
            wire_dtype=wire_name,
            provenance=provenance,
            **slice_note,
            **({"size": size} if size is not None else {}),
        )
        axis_sizes = {a: lax.axis_size(a) for a in names}
        for b_i, (nbytes, dt_name, n_elems) in enumerate(bucket_meta):
            wire_item = max(1, nbytes // max(1, n_elems))
            for s_i, row in enumerate(
                stage_wire_layout(comp, axis_sizes, wire_item, n_elems)
            ):
                rec.event(
                    "wire", schedule=label, composition=sig,
                    stage=row["stage"], stage_index=s_i,
                    stage_op=row["op"], bucket=b_i,
                    n_buckets=n_buckets_total, nbytes=row["nbytes"],
                    wire_dtype=("int8" if int8_wire and "float" in dt_name
                                else dt_name),
                    overlapped=bool(overlapped),
                    **({"slice": row["slice"],
                        "n_slices": row["n_slices"]}
                       if "slice" in row else {}),
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

    ``slices`` (ISSUE 15): each bucket is additionally cut into
    ``min(slices, elements)`` contiguous column slices
    (:func:`~chainermn_tpu.parallel.composition.slice_bounds` — the
    zero-leaf degrade contract) and ONE collective flies per slice —
    the REAL async interleave: slice i can retire while slice i+1 is
    still on the wire, and each slice's ``wire`` event carries its
    ``slice``/``n_slices`` address beside ``dur_s``/``blocked_s``, so
    the overlap table shows per-slice hiding, not just per-bucket.
    """

    def __init__(self, comm, *, bucket_bytes: Optional[int] = None,
                 slices: int = 1) -> None:
        self.comm = comm
        if bucket_bytes is None:
            from chainermn_tpu.parallel.collectives import tuned_bucket_bytes

            bucket_bytes = tuned_bucket_bytes(comm.device_kind, comm.size)
        self.bucket_bytes = bucket_bytes
        if int(slices) < 1:
            raise ValueError(f"slices must be >= 1, got {slices}")
        self.slices = int(slices)
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
        from chainermn_tpu.parallel.composition import (
            effective_slices,
            slice_bounds,
        )

        for b_i, bidx in enumerate(buckets):
            flat = jnp.concatenate(
                [jnp.asarray(leaves[i]).astype(jnp.float32).reshape(n, -1)
                 for i in bidx],
                axis=1,
            )
            s_eff = effective_slices(self.slices, flat.shape[1])
            for s_i, (lo, hi) in enumerate(slice_bounds(flat.shape[1],
                                                        s_eff)):
                part = flat[:, lo:hi] if s_eff > 1 else flat
                t0 = time.perf_counter()
                out = mean(part)  # async dispatch: returns pre-wire
                self._inflight.append(
                    (b_i, s_i, s_eff, bidx, out, t0, int(part.nbytes)))
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
        rows: dict[int, list] = {}
        for b_i, s_i, s_eff, bidx, red, t0, nbytes in self._inflight:
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
                    **({"slice": s_i, "n_slices": s_eff}
                       if s_eff > 1 else {}),
                )
            rows.setdefault(b_i, []).append((s_i, bidx, red[0]))
        for b_i, parts in rows.items():
            parts.sort()
            bidx = parts[0][1]
            row = (jnp.concatenate([p[2] for p in parts])
                   if len(parts) > 1 else parts[0][2])  # [k]: the mean
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


class MeasuredComposedReducer:
    """Eager per-STAGE composed reduction — the measured side of the
    composed-schedule story (ISSUE 13 satellite, the PR 11 follow-up).

    The in-jit composed executor (:func:`~chainermn_tpu.parallel.
    composition.reduce_composed`) emits trace-time ``wire`` layout
    events per stage — bytes the program COMMITTED to, no durations.
    This driver runs the SAME stage list eagerly (one jitted shard_map
    program per stage over the communicator's mesh, the stacked
    ``[size, ...]`` eager-communicator convention), blocks between
    stages, and records one ``wire`` event per stage carrying
    ``dur_s`` — so ``tools/trace_report.py``'s overlap section gains a
    MEASURED per-stage duration column in the per-signature stage table
    (``summarize_overlap`` folds ``dur_s`` into ``stages[..].dur_ms``).
    The blocking is the point: a per-stage wall clock is only honest
    when the previous stage's collective has retired
    (the :class:`OverlappedBucketReducer` dur_s/blocked_s pattern,
    applied per stage instead of per bucket).

    Pure reductions only — a ``sharded_update`` stage belongs to the
    optimizer fuse point, not an eager wire driver (refused loudly).

    ``slices`` (ISSUE 15): the composition is run SLICED — the flat
    buffer cut into ``min(slices, elements)`` contiguous slices, the
    per-slice stages DISPATCHED in the skewed interleave order without
    blocking (slice i's slow stage in flight while slice i+1's fast
    stage dispatches — JAX's async dispatch realises the overlap the
    in-jit rendering only commits to), then collected in the same
    order: each per-slice stage ``wire`` event carries ``slice``/
    ``n_slices`` beside ``dur_s`` (dispatch -> ready) and ``blocked_s``
    (wait paid at collection) — the per-slice ``dur_ms``/``blocked_ms``
    columns of the overlap table. Unsliced (default) keeps the
    block-per-stage honest wall clock unchanged.

    Usage::

        red = MeasuredComposedReducer(comm, schedule="two_level")
        mean = red.reduce(stacked_grads)   # [size, ...] leaves -> mean
    """

    def __init__(self, comm, schedule="two_level", *,
                 slices: int = 1) -> None:
        from chainermn_tpu.parallel.composition import (
            CompositionError,
            compile_schedule,
            sliced_composition,
        )

        self.comm = comm
        axes = comm.grad_axes
        axes = axes if isinstance(axes, tuple) else (axes,)
        self.comp = compile_schedule(schedule, axes)
        if self.comp.has_update:
            raise CompositionError(
                f"{self.comp.signature()!r} carries a sharded_update "
                "stage — the eager measured reducer runs pure "
                "reductions (the update fuse point is "
                "MultiNodeOptimizer's 'zero' schedule)"
            )
        if int(slices) > 1:
            self.comp = sliced_composition(self.comp, int(slices))
        self._axes = axes
        self._stage_jits: dict = {}

    def _stage_fn(self, i: int, primitive, stage_axes, orig_size,
                  cur_size):
        # orig_size is in the key too: two slices can share a padded
        # shard width while un-padding to different lengths (ISSUE 15),
        # and equal-width slices share one compiled program.
        key = (i, cur_size, orig_size)
        if key in self._stage_jits:
            return self._stage_jits[key]
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from chainermn_tpu.parallel.collectives import (
            staged_allgather,
            staged_allreduce,
            staged_reduce_scatter,
        )

        def local(x):
            b = x[0]
            if primitive == "reduce_scatter":
                out = staged_reduce_scatter(b, stage_axes)
            elif primitive == "allreduce":
                out = staged_allreduce(b, stage_axes)
            else:
                out = staged_allgather(b, stage_axes, orig_size)
            return out[None]

        fn = jax.jit(shard_map(
            local, mesh=self.comm.mesh,
            in_specs=P(self._axes), out_specs=P(self._axes),
            check_vma=False,
        ))
        self._stage_jits[key] = fn
        return fn

    def reduce(self, grads_stacked: PyTree) -> PyTree:
        """Run the composition stage by stage on ONE flat f32 buffer
        (leaves ``[size, ...]`` stacked per-rank contributions,
        concatenated), blocking per stage, and return the un-stacked
        mean tree. Records one measured ``wire`` event per stage."""
        from chainermn_tpu.parallel.composition import (
            _replay_sizes,
            stage_wire_layout,
        )

        n = self.comm.size
        leaves, treedef = jax.tree.flatten(grads_stacked)
        for leaf in leaves:
            if leaf.shape[0] != n:
                raise ValueError(
                    f"stacked leaves must have leading dim == size "
                    f"({n}), got {leaf.shape}"
                )
        sizes = [leaf[0].size for leaf in leaves]
        flat = jnp.concatenate(
            [jnp.asarray(leaf).astype(jnp.float32).reshape(n, -1)
             for leaf in leaves], axis=1,
        ) if leaves else jnp.zeros((n, 0), jnp.float32)
        n_elems = flat.shape[1]
        axis_sizes = {a: int(self.comm.mesh.shape[a])
                      for a in self._axes}
        layout = stage_wire_layout(self.comp, axis_sizes, 4, n_elems)
        sig = self.comp.signature()
        rec = _trace.active()

        from chainermn_tpu.parallel.composition import effective_slices

        s_eff = effective_slices(self.comp.slices, n_elems)
        if s_eff > 1:
            mean = self._reduce_sliced(flat, s_eff, axis_sizes, layout,
                                       sig, rec) / n
        else:
            rows, _, _ = _replay_sizes(self.comp.stages, n_elems,
                                       axis_sizes)
            cur = flat
            li = 0
            for i, (st, size_in, size_out) in enumerate(rows):
                fn = self._stage_fn(i, st.primitive, st.axes, size_out,
                                    size_in)
                t0 = time.perf_counter()
                cur = jax.block_until_ready(fn(cur))
                dur = time.perf_counter() - t0
                if rec is not None and li < len(layout):
                    rec.event(
                        "wire", schedule="composed_eager",
                        composition=sig,
                        stage=st.signature(), stage_index=li,
                        stage_op=layout[li]["op"], bucket=0, n_buckets=1,
                        nbytes=layout[li]["nbytes"],
                        dur_s=round(dur, 9), overlapped=False,
                    )
                li += 1
            mean = cur[0] / n  # replicated sum row -> mean
        out = []
        off = 0
        for leaf, k in zip(leaves, sizes):
            out.append(mean[off:off + k].reshape(leaf.shape[1:])
                       .astype(leaf.dtype))
            off += k
        return jax.tree.unflatten(treedef, out)

    def _reduce_sliced(self, flat, s_eff, axis_sizes, layout, sig, rec):
        """The sliced eager run (ISSUE 15): dispatch every per-slice
        stage in the skewed interleave order WITHOUT blocking, then
        collect in the same order — ``dur_s`` is dispatch->ready,
        ``blocked_s`` the wait paid here, their gap the comm hidden
        behind the other slices' stages. Returns the replicated sum
        row (caller divides by the world size)."""
        import dataclasses as _dc

        from chainermn_tpu.parallel.composition import (
            _replay_sizes as _replay,
            expand_slices,
            slice_bounds,
        )

        bounds = slice_bounds(flat.shape[1], s_eff)
        # Honor the composition's cut: zigzag slice i is the strided
        # comb i, i+S, ... (same per-slice sizes as the contiguous
        # bounds, so the replayed stage rows are shared).
        zigzag = self.comp.slice_layout == "zigzag"
        if zigzag:
            cur_s = [flat[:, i::s_eff] for i in range(s_eff)]
        else:
            cur_s = [flat[:, lo:hi] for lo, hi in bounds]
        per_rows = [
            _replay(self.comp.stages, hi - lo, axis_sizes)[0]
            for lo, hi in bounds
        ]
        nodes = []  # (layout_index, slice, out_array, t0)
        li = 0
        for st in expand_slices(self.comp, flat.shape[1]):
            i, _ = st.slice
            base = _dc.replace(st, slice=None)
            j = self.comp.stages.index(base)
            _, size_in, size_out = per_rows[i][j]
            fn = self._stage_fn(j, st.primitive, st.axes,
                                size_out, size_in)
            t0 = time.perf_counter()
            cur_s[i] = fn(cur_s[i])  # async dispatch: no block here
            nodes.append((li, i, cur_s[i], t0))
            li += 1
        for li, i, arr, t0 in nodes:
            t_c = time.perf_counter()
            jax.block_until_ready(arr)
            t_r = time.perf_counter()
            if rec is not None and li < len(layout):
                rec.event(
                    "wire", schedule="composed_eager", composition=sig,
                    stage=layout[li]["stage"], stage_index=li,
                    stage_op=layout[li]["op"], bucket=0, n_buckets=1,
                    nbytes=layout[li]["nbytes"],
                    slice=layout[li]["slice"],
                    n_slices=layout[li]["n_slices"],
                    dur_s=round(t_r - t0, 9),
                    blocked_s=round(t_r - t_c, 9),
                    overlapped=bool((t_r - t0) - (t_r - t_c) > 0),
                )
        import jax.numpy as _jnp

        if zigzag:
            out = _jnp.zeros((flat.shape[1],), cur_s[0].dtype)
            for i, c in enumerate(cur_s):
                out = out.at[i::s_eff].set(c[0])
            return out
        return _jnp.concatenate([c[0] for c in cur_s])


__all__ = [
    "DECISION",
    "DEFAULT_BUCKET_BYTES",
    "MeasuredComposedReducer",
    "OverlappedBucketReducer",
    "SCHEDULES",
    "SLICES_DECISION",
    "SLICE_CANDIDATES",
    "bucket_partition",
    "reduce_tree",
    "resolve_comp_slices",
    "resolve_schedule",
]
