"""Multi-node optimizer wrappers.

Reference: ``chainermn/optimizers.py`` (dagger) (SURVEY.md sections 2.3, 3.2):
``create_multi_node_optimizer(opt, comm, double_buffering=False)`` wraps any
Chainer optimizer so that ``update()`` broadcasts weights on the first
iteration and allreduces gradients on every iteration;
``_DoubleBufferingOptimizer`` overlaps the allreduce with backward on a side
CUDA stream at the cost of one step of gradient staleness.

TPU-native design: the wrapped object is an :class:`optax.GradientTransformation`
meant to be used *inside the jitted train step*. ``allreduce_grad`` is a
mean over the communicator's mesh axes — XLA fuses the reference's
pack / fp16-cast / ncclAllReduce / scale / unpack pipeline
(``pure_nccl_communicator.py`` (dagger)) into its collective schedule. On a
TPU an all-reduce is a synchronous op that the core waits on wherever the
scheduler puts it, so the mean of every large leaf is written as two
``all_to_all``s (:func:`allreduce_gradients`), which XLA compiles to
asynchronous pairs and flies under the rest of the backward and the
optimizer's sweep: what double buffering bought on GPU, at staleness 0
(PERF.md, PR 39). The
``double_buffering=True`` flag is still honoured with *faithful semantics*
(updates apply the previous step's reduced gradients, staleness 1) so
convergence behaviour matches the reference feature; on TPU it additionally
lets XLA start the psum of step *t* while step *t*'s weights update with
*t-1*'s gradients.

Weight broadcast on first iteration: in the functional JAX world parameters
are created once and replicated by :meth:`CommunicatorBase.bcast_data`; call
``optimizer.broadcast(params)`` (or rely on identical PRNG keys) instead of a
hidden first-update hook.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

from chainermn_tpu.communicators.base import CommunicatorBase
from chainermn_tpu.observability import train_path

PyTree = Any


def _pmean_if_in_axis(tree: PyTree, axis_names) -> PyTree:
    """pmean over ``axis_names`` when tracing inside that named-axis context
    (shard_map/pmap); identity otherwise (pjit auto-parallel mode, where XLA
    inserts the reduction from sharding propagation, or single-device)."""
    from chainermn_tpu.parallel.collectives import axes_bound

    if not axes_bound(axis_names):
        return tree
    return lax.pmean(tree, axis_names)


def allreduce_gradients(
    grads: PyTree,
    comm: Optional[CommunicatorBase] = None,
    *,
    axis_names=None,
    compress_dtype=None,
) -> PyTree:
    """In-jit gradient averaging — the hot collective of the framework.

    With ``compress_dtype`` (e.g. ``jnp.bfloat16``) gradients are cast before
    the collective and restored after: the reference's
    ``allreduce_grad_dtype='float16'`` compressed allreduce
    (``pure_nccl_communicator.py`` (dagger), shu65's v1.3 feature) — halves
    bytes on ICI/DCN; master accumulation stays f32.

    ``compress_dtype=jnp.int8`` selects the QUANTIZED wire (beyond the
    reference): max-abs-scaled int8 over a two-phase
    all_to_all/all_gather scheme
    (:func:`chainermn_tpu.parallel.collectives.int8_allreduce_mean`) —
    ~2 bytes/element on the wire vs bf16's 4, at ~1/127-relative
    rounding noise per stage. Outside a named-axis context int8 is an
    identity (no pointless quantization round-trip).

    Over more than one device a floating leaf of two or more dimensions
    and at least ``collectives.ALL_TO_ALL_MIN_BYTES`` on the wire is
    averaged where it lies by
    :func:`~chainermn_tpu.parallel.collectives.all_to_all_mean` (the n
    pieces summed in float32, the mean rounded once to the wire dtype,
    the same bits on every member): asynchronous on a TPU where the step
    is jitted with ``collectives.async_collective_options``, as
    ``make_train_step`` does. Every other leaf takes ``pmean``.
    """
    if axis_names is None:
        if comm is None:
            raise ValueError("pass a communicator or axis_names")
        # Strategy dispatch: the communicator owns its in-jit reduction
        # algorithm (base: fused pmean; two_dimensional: explicit
        # reduce-scatter -> inter-allreduce -> all-gather).
        return comm.reduce_gradients_in_jit(grads, compress_dtype=compress_dtype)

    from chainermn_tpu.parallel import collectives

    int8_wire = (compress_dtype is not None
                 and jnp.dtype(compress_dtype) == jnp.dtype(jnp.int8))
    # one collective a leaf as the program writes it (XLA combines the
    # all-reduces; an all_to_all pair stays its leaf's own)
    leaves = jax.tree.leaves(grads)
    collectives.publish_grad_wire(leaves, compress_dtype, axis_names,
                                  len(leaves))

    n = collectives.axes_size(axis_names) \
        if collectives.axes_bound(axis_names) else 1

    def reduce_leaf(g):
        floating = jnp.issubdtype(g.dtype, jnp.floating)
        if int8_wire and floating:
            if not collectives.axes_bound(axis_names):
                return g
            return collectives.int8_allreduce_mean(g, axis_names)
        wire = jnp.dtype(compress_dtype if floating
                         and compress_dtype is not None else g.dtype)
        axis = collectives.all_to_all_split_axis(g.shape, n)
        if floating and n > 1 and axis is not None and \
                g.size * wire.itemsize >= collectives.ALL_TO_ALL_MIN_BYTES:
            return collectives.all_to_all_mean(
                g.astype(wire), axis_names, axis).astype(g.dtype)
        if compress_dtype is not None and not int8_wire and floating:
            return _pmean_if_in_axis(g.astype(compress_dtype), axis_names).astype(
                g.dtype
            )
        # pmean promotes integer leaves to float; keep the leaf dtype
        # (reference parity: allreduce_grad returned grads in-place/dtype).
        return _pmean_if_in_axis(g, axis_names).astype(g.dtype)

    return jax.tree.map(reduce_leaf, grads)


def allreduce_grads_transform(
    comm: CommunicatorBase, *, compress_dtype=None
) -> optax.GradientTransformation:
    """Standalone optax transform performing the gradient allreduce; compose
    it manually as ``optax.chain(allreduce_grads_transform(comm), inner)`` if
    you don't want the full wrapper."""

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state, params=None):
        del params
        return (
            allreduce_gradients(updates, comm, compress_dtype=compress_dtype),
            state,
        )

    return optax.GradientTransformation(init_fn, update_fn)


class _DoubleBufferState(NamedTuple):
    inner: Any
    #: gradients reduced at step t-1, applied at step t (staleness 1)
    communicated_grads: PyTree
    step: jax.Array


class _ErrorFeedbackState(NamedTuple):
    inner: Any
    #: per-rank residual of the int8 wire's quantization, added into the
    #: next step's message (EF-SGD). Flat wire: mirrors the params tree
    #: (full-param f32). Topology-aware wire: a tuple of SHARD-shaped f32
    #: buffers, one per ~64 MB bucket — the error arises only at the
    #: inter stage, on the intra-summed shard, and is stored there.
    residual: PyTree


class _ZeroShardState(NamedTuple):
    """Optimizer state of the ``'zero'`` reduction schedule: EVERY inner
    leaf is stacked ``[n_shards, ...]`` along a leading shard dim
    (scalar counters tiled), so one prefix ``PartitionSpec`` shards the
    whole subtree over the scatter axis — ZeRO-1 state sharding fused
    into the gradient-reduction schedule (reduce-scatter -> sharded
    update -> allgather, arXiv:2004.13336; the chunk layout of
    :mod:`chainermn_tpu.parallel.zero`, optimizer-wrapped)."""

    inner: Any


_EF_BUCKET_BYTES = 64 << 20


def _float_bucket_partition(float_idx, sizes, bucket_bytes=None):
    """Deterministic ~64 MB (f32) bucket partition of the float leaves
    — ONE function used by ``MultiNodeOptimizer.init`` (residual
    allocation), ``_reduce_with_feedback`` (the EF reduction), and the
    schedule layer, so no two consumers can disagree about the layout.
    Thin f32 wrapper over
    :func:`chainermn_tpu.parallel.reduction_schedule.bucket_partition`,
    which owns the edge contract: zero-size leaves are skipped (they
    ride the exact per-leaf path), a payload smaller than one bucket
    yields exactly one bucket, a single leaf larger than the bucket
    gets its own bucket unsplit, and no bucket is ever empty.
    ``bucket_bytes`` comes from the optimizer's autotuned resolution
    (decision ``allreduce_bucket_mb``, resolved ONCE per optimizer
    instance so init and update always see the same layout)."""
    from chainermn_tpu.parallel.reduction_schedule import bucket_partition

    if bucket_bytes is None:
        bucket_bytes = _EF_BUCKET_BYTES
    return bucket_partition(float_idx, sizes, 4, bucket_bytes)


class MultiNodeOptimizer:
    """optax-compatible wrapper: ``init``/``update`` plus communicator-aware
    gradient reduction. Duck-types :class:`optax.GradientTransformation`.

    Reference behaviours preserved (``optimizers.py`` (dagger)):
      - every update averages gradients across all ranks before applying;
      - ``double_buffering=True`` applies the *previous* iteration's averaged
        gradients (staleness-1) — tested for exactly that semantic;
      - attribute delegation: unknown attributes forward to the wrapped
        optimizer (the reference delegated via ``__getattr__``).

    ``reduction_schedule`` selects the gradient-reduction ALGORITHM
    (:mod:`chainermn_tpu.parallel.reduction_schedule`; see
    docs/parallelism.md "Gradient-reduction schedules"):

    - ``None`` (default): the communicator's own strategy — base:
      :func:`allreduce_gradients` (every large matrix reduced where it
      lies, the rest one fused pmean); two_dimensional: its packed
      two-level pipeline.
    - ``'flat'``: the packed flat allreduce, one ``pmean`` per ~64 MB
      bucket (the reference's ``_memory_utility.pack_params`` (dagger)
      discipline).
    - ``'two_level'``: intra reduce-scatter -> inter allreduce on the
      shard -> allgather, per ~64 MB bucket (the reference's
      ``two_dimensional_communicator.py`` (dagger) pipeline).
    - ``'zero'``: reduce-scatter + SHARDED update + allgather — the
      inner optimizer runs on 1/n of the parameters with 1/n of its
      state (arXiv:2004.13336), fused with
      :mod:`chainermn_tpu.parallel.zero`'s chunk layout. The inner
      transform must be elementwise (adam/sgd/...); carry the state
      through ``shard_map`` with :meth:`opt_state_spec`
      (``make_train_step`` does this automatically). Incompatible with
      ``double_buffering``, ``error_feedback`` and the int8 wire.

    Anything else raises a ``ValueError``.

    ``double_buffering=True`` is the OVERLAPPED mode: the update
    consumes the PREVIOUS step's banked buckets while this step's
    reduction is dispatched with no data path into the current update
    (certified structurally in tests/test_optimizer.py) — with an
    explicit schedule (or the default's bucketed overlap form) each
    bucket's trace-time ``wire`` event carries ``overlapped=True`` so
    ``tools/trace_report.py`` reports the comm-hidden fraction.
    """

    #: protocol marker for make_train_step: this wrapper performs its own
    #: cross-rank synchronisation, so the step must NOT pre-reduce grads
    #: (an isinstance special-case would silently miss sibling wrappers —
    #: it did: LocalSGDOptimizer kept the per-step wire until review).
    handles_cross_rank_sync = True

    def __init__(
        self,
        actual_optimizer: optax.GradientTransformation,
        communicator: CommunicatorBase,
        *,
        double_buffering: bool = False,
        compress_dtype=None,
        error_feedback: bool = False,
        reduction_schedule: str | None = None,
    ) -> None:
        self.actual_optimizer = actual_optimizer
        self.communicator = communicator
        self.double_buffering = double_buffering
        if isinstance(compress_dtype, str) and compress_dtype == "auto":
            # Same device-aware wire resolution the communicator's
            # allreduce_grad_dtype="auto" takes (chainermn_tpu.tuning).
            # A resolved f32 wire is None — deliberately NOT falling
            # through to the communicator's configured dtype.
            from chainermn_tpu.parallel.collectives import (
                resolve_allreduce_wire,
            )

            self.compress_dtype = resolve_allreduce_wire(
                communicator.device_kind, communicator.size
            )
        else:
            self.compress_dtype = (
                compress_dtype
                if compress_dtype is not None
                else communicator.allreduce_grad_dtype
            )
        self.error_feedback = error_feedback
        if error_feedback and not self._int8_wire():
            raise ValueError(
                "error_feedback requires the int8 quantized wire "
                "(allreduce_grad_dtype=jnp.int8) — other dtypes lose "
                "nothing systematic to feed back"
            )
        from chainermn_tpu.parallel.reduction_schedule import SCHEDULES

        if not (reduction_schedule is None
                or isinstance(reduction_schedule, str)
                and reduction_schedule in SCHEDULES):
            raise ValueError(
                "reduction_schedule must be None, 'flat', 'two_level' or "
                f"'zero'; got {reduction_schedule!r}"
            )
        if error_feedback and reduction_schedule not in (None, "flat"):
            raise ValueError(
                "error_feedback owns its reduction (the flat or the "
                "communicator's topology-aware quantized wire) — "
                f"reduction_schedule={reduction_schedule!r} cannot compose"
            )
        if reduction_schedule == "zero":
            if double_buffering:
                raise ValueError(
                    "reduction_schedule='zero' cannot compose with "
                    "double_buffering: the sharded update replaces the "
                    "grads the staleness bank would carry"
                )
            if self._int8_wire():
                raise ValueError(
                    "reduction_schedule='zero' cannot ride the int8 wire "
                    "(its reduce-scatter sums raw chunks; the two-phase "
                    "quantized scheme has no scatter form) — use bf16 "
                    "compression or the flat/two_level schedules"
                )
        self.reduction_schedule = reduction_schedule
        # One resolution per optimizer instance: init's residual
        # allocation and update's reduction must see the same bucket
        # layout. The table-default 64 MB resolves to None —
        # _float_bucket_partition then reads the module's
        # _EF_BUCKET_BYTES at call time, keeping that constant the single
        # default (and test seam); only a forced decision pins an
        # explicit size here.
        from chainermn_tpu import tuning

        mb = tuning.choice(
            "allreduce_bucket_mb", ("16", "64", "256", "none"),
            tuning.decision_key(communicator.device_kind,
                                shape=(communicator.size,), dtype="grad"),
        )
        self._bucket_bytes = (
            None if mb == "64"
            else (1 << 62) if mb == "none"
            else int(mb) << 20
        )

    def _int8_wire(self) -> bool:
        return (self.compress_dtype is not None
                and jnp.dtype(self.compress_dtype) == jnp.dtype(jnp.int8))

    # -- reduction-schedule plumbing ---------------------------------------

    def _zero_axis(self) -> str:
        """The scatter axis of the 'zero' schedule: the LAST grad axis
        (mesh convention puts the fast/intra axis last — state shards
        where the gather is cheapest)."""
        return self.communicator.grad_axes[-1]

    def _zero_n(self) -> int:
        return int(self.communicator.mesh.shape[self._zero_axis()])

    def _effective_schedule(self) -> str | None:
        """The schedule this update runs: the explicit choice, or — for
        the default ``None`` — the communicator's own strategy, EXCEPT
        under double buffering, where the overlapped mode runs the
        bucketed pipeline so each in-flight bucket is a separately
        schedulable (and separately traced) collective."""
        s = self.reduction_schedule
        if s is None and self.double_buffering:
            return ("two_level"
                    if getattr(self.communicator, "two_level_axes", None)
                    is not None else "flat")
        return s

    def _reduce_scheduled(self, grads: PyTree, schedule: str | None) -> PyTree:
        """Reduce ``grads`` under ``schedule`` (never 'zero' — that is
        structural, see ``_zero_update``). ``None`` and any
        outside-axis-context call take the legacy communicator path, so
        the degrade semantics (identity + compress-dtype roundtrip)
        stay byte-identical to the pre-schedule behaviour."""
        from chainermn_tpu.parallel.collectives import axes_bound
        from chainermn_tpu.parallel.reduction_schedule import reduce_tree

        comm = self.communicator
        if schedule is None or not axes_bound(comm.grad_axes):
            return allreduce_gradients(
                grads, comm, compress_dtype=self.compress_dtype
            )
        return reduce_tree(
            grads,
            schedule=schedule,
            axes=comm.grad_axes,
            compress_dtype=self.compress_dtype,
            bucket_bytes=self._bucket_bytes,
            overlapped=self.double_buffering,
            size=comm.size,
        )

    def opt_state_spec(self):
        """``PartitionSpec`` (prefix pytree) for carrying this
        optimizer's state through ``shard_map``: the 'zero' schedule
        shards every (stacked) state leaf over the scatter axis;
        everything else is replicated. ``make_train_step`` consumes
        this automatically; hand-rolled steps pass it as the state's
        ``in_specs``/``out_specs`` entry."""
        from jax.sharding import PartitionSpec as P

        if self.reduction_schedule == "zero":
            return _ZeroShardState(inner=P(self._zero_axis()))
        return P()

    # -- the 'zero' schedule: reduce-scatter + sharded update + allgather --

    def _zero_update(self, grads: PyTree, state, params: PyTree | None):
        """Xu et al.'s reduce-scatter sharded update (arXiv:2004.13336),
        fused with parallel/zero.py's chunk layout: each shard receives
        the MEAN of its 1/n gradient chunk (half an allreduce's wire
        bytes), updates 1/n of the optimizer state, and allgathers the
        1/n parameter updates back (the other half). Outside any
        named-axis context it degrades to a vectorised per-chunk update
        over the full stacked state — elementwise inner transforms make
        that exactly the full-parameter update, so eager/pjit callers
        see identical numerics with zero collectives."""
        from chainermn_tpu.parallel.collectives import (
            axes_bound,
            axes_size,
            publish_grad_wire,
        )
        from chainermn_tpu.parallel.zero import (
            _chunk_rows,
            _unchunk,
            zero_gather_updates,
            zero_grad_scatter,
        )

        inner = self.actual_optimizer
        comm = self.communicator
        names = comm.grad_axes
        ax = names[-1]
        n = self._zero_n()
        compress = self.compress_dtype

        if not axes_bound(names):
            grows = jax.tree.map(lambda g: _chunk_rows(g, n), grads)
            prows = (jax.tree.map(lambda p: _chunk_rows(p, n), params)
                     if params is not None else None)
            if prows is None:
                urows, inner_state = jax.vmap(
                    lambda g, s: inner.update(g, s)
                )(grows, state.inner)
            else:
                urows, inner_state = jax.vmap(inner.update)(
                    grows, state.inner, prows
                )
            updates = jax.tree.map(
                lambda u, g: _unchunk(u, g.shape, g.dtype), urows, grads
            )
            return updates, _ZeroShardState(inner=inner_state)

        lead = {int(jnp.shape(e)[0]) for e in jax.tree.leaves(state.inner)
                if jnp.ndim(e) >= 1}
        if lead and lead != {1}:
            raise ValueError(
                "the 'zero' schedule's opt_state reached update without "
                f"being sharded (leading dims {sorted(lead)}, expected 1 "
                "per shard) — carry it through shard_map with "
                "optimizer.opt_state_spec() (make_train_step does this), "
                "never closed over or replicated"
            )
        n_tot = axes_size(names)
        idx = lax.axis_index(ax)

        def mean_chunk(g):
            # reduce-scatter over the last axis, all-reduce of the shard
            # over the others, on the compressed wire where there is one
            wire = g.reshape(-1)
            if compress is not None and jnp.issubdtype(g.dtype,
                                                       jnp.floating):
                wire = wire.astype(compress)
            return zero_grad_scatter(
                wire, ax, extra_axes=names[:-1], total=n_tot
            ).astype(g.dtype)

        leaves = jax.tree.leaves(grads)
        publish_grad_wire(leaves, compress, names, len(leaves))
        with jax.named_scope(train_path.GRAD_REDUCE):
            gchunks = jax.tree.map(mean_chunk, grads)
        pchunks = (jax.tree.map(
            lambda p: lax.dynamic_index_in_dim(
                _chunk_rows(p, n), idx, keepdims=False
            ), params,
        ) if params is not None else None)
        schunk = jax.tree.map(lambda e: e[0], state.inner)
        with jax.named_scope(train_path.OPTIMIZER_UPDATE):
            uchunks, schunk = inner.update(gchunks, schunk, pchunks)
        inner_state = jax.tree.map(lambda e: e[None], schunk)

        updates = jax.tree.map(
            lambda u, g: zero_gather_updates(u, g, ax), uchunks, grads
        )
        return updates, _ZeroShardState(inner=inner_state)

    # -- optax protocol ----------------------------------------------------

    def init(self, params: PyTree):
        if self._effective_schedule() == "zero":
            # 1/n state per shard, stacked [n, ...] (scalar counters
            # tiled) so ONE prefix spec shards the whole subtree — the
            # layout _zero_update and opt_state_spec() both key on.
            # Works eagerly (create_train_state) and in-trace alike.
            from chainermn_tpu.parallel.zero import _chunk_rows

            n = self._zero_n()
            rows = jax.tree.map(
                lambda p: _chunk_rows(jnp.asarray(p), n), params
            )
            return _ZeroShardState(
                inner=jax.vmap(self.actual_optimizer.init)(rows)
            )
        state = self.actual_optimizer.init(params)
        if self.double_buffering:
            state = _DoubleBufferState(
                inner=state,
                communicated_grads=jax.tree.map(jnp.zeros_like, params),
                step=jnp.zeros((), jnp.int32),
            )
        if self.error_feedback:
            # Residual lives in float32 regardless of param dtype: with
            # bf16 params a bf16 residual would itself drop ~2/3 of the
            # quantization error being fed back each step, weakening the
            # cumulative-bias-removal guarantee EF exists for.
            axes2 = getattr(self.communicator, "two_level_axes", None)
            if axes2 is not None:
                # Topology-aware wire: the only lossy stage quantizes
                # the intra-summed SHARD per bucket, so the residual is
                # one shard-shaped f32 buffer per bucket — 1/n_intra
                # the flat-wire residual's footprint. Bucket layout is
                # static (param sizes + mesh shape), shared with the
                # update path via _float_bucket_partition.
                from chainermn_tpu.parallel.collectives import (
                    two_level_shard_len,
                )

                intra_ax, _ = axes2
                n_intra = self.communicator.mesh.shape[intra_ax]
                leaves = jax.tree.leaves(params)
                sizes = [leaf.size for leaf in leaves]
                float_idx = [
                    i for i, leaf in enumerate(leaves)
                    if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
                ]
                residual = tuple(
                    jnp.zeros(
                        (two_level_shard_len(
                            sum(sizes[i] for i in bidx), n_intra),),
                        jnp.float32,
                    )
                    for bidx in _float_bucket_partition(
                        float_idx, sizes, self._bucket_bytes)
                )
            else:
                # Flat wire: one params-sized f32 buffer.
                residual = jax.tree.map(
                    lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params
                )
            state = _ErrorFeedbackState(inner=state, residual=residual)
        return state

    def _reduce_with_feedback(self, grads: PyTree, residual: PyTree):
        """EF-SGD over the int8 wire: the NEW residual is exactly what
        quantization dropped this step — deterministic rounding bias is
        fed back instead of lost.

        Float leaves ride ~64 MB flat f32 buckets (the same packing
        discipline as the two-dimensional communicator's pipeline —
        tiny bias/scale leaves must not each pay their own collective;
        layout shared with ``init`` via ``_float_bucket_partition``);
        non-float leaves take the exact pmean, matching the non-EF
        path's reference-parity behaviour.

        Two forms, keyed on the communicator's ``two_level_axes``
        capability:

        - flat wire (any communicator): message = grads + residual at
          full param shape; residual mirrors the params tree.
        - TOPOLOGY-AWARE wire (``TwoDimensionalCommunicator``, round 5):
          the intra reduction is exact, so feedback happens at the ONLY
          lossy stage — the int8 wire on the intra-summed shard crossing
          inter/DCN. The residual is shard-shaped per bucket (1/n_intra
          the flat footprint), see
          :func:`chainermn_tpu.parallel.collectives.int8_two_level_allreduce_mean_with_feedback`.
        """
        from chainermn_tpu.parallel.collectives import (
            axes_bound,
            int8_allreduce_mean_with_feedback,
            int8_two_level_allreduce_mean_with_feedback,
            publish_grad_wire,
        )

        axes = self.communicator.grad_axes
        if not axes_bound(axes):
            publish_grad_wire(jax.tree.leaves(grads), jnp.int8, axes, 0)
            return grads, residual  # pjit/eager: identity, residual kept

        axes2 = getattr(self.communicator, "two_level_axes", None)
        leaves, treedef = jax.tree.flatten(grads)
        out: list = [None] * len(leaves)

        # Zero-size float leaves ride the exact per-leaf path with the
        # non-floats: an empty buffer has no max-abs for the int8 scale
        # (and bucket_partition skips them — see its edge contract).
        float_idx = [i for i, g in enumerate(leaves)
                     if jnp.issubdtype(g.dtype, jnp.floating) and g.size > 0]
        for i, g in enumerate(leaves):
            if i not in float_idx:
                out[i] = _pmean_if_in_axis(g, axes).astype(g.dtype)

        sizes = [g.size for g in leaves]
        buckets = _float_bucket_partition(float_idx, sizes,
                                          self._bucket_bytes)
        publish_grad_wire(leaves, jnp.int8, axes,
                          len(buckets) + len(leaves) - len(float_idx))

        if axes2 is not None:
            # Shard-level EF: residual is a tuple of per-bucket shard
            # buffers (the layout init allocated).
            intra_ax, inter_ax = axes2
            e_shards = jax.tree.leaves(residual)
            if len(e_shards) != len(buckets):
                raise ValueError(
                    f"shard-level EF residual has {len(e_shards)} "
                    f"buckets but these gradients need {len(buckets)} — "
                    "the opt_state was built for different params "
                    "(restore mismatch?); rebuild it with "
                    "optimizer.init(params) / create_train_state(...)"
                )
            new_shards = []
            for bidx, e_shard in zip(buckets, e_shards):
                m = jnp.concatenate([
                    leaves[i].astype(jnp.float32).ravel() for i in bidx
                ])
                mean, new_shard = int8_two_level_allreduce_mean_with_feedback(
                    m, e_shard, intra_ax, inter_ax
                )
                new_shards.append(new_shard)
                off = 0
                for i in bidx:
                    n = leaves[i].size
                    out[i] = (mean[off:off + n]
                              .reshape(leaves[i].shape)
                              .astype(leaves[i].dtype))
                    off += n
            return jax.tree.unflatten(treedef, out), tuple(new_shards)

        e_leaves = jax.tree.leaves(residual)
        new_e: list = list(e_leaves)
        for bidx in buckets:
            m = jnp.concatenate([
                (leaves[i].astype(jnp.float32)
                 + e_leaves[i].astype(jnp.float32)).ravel()
                for i in bidx
            ])
            mean, local_rt = int8_allreduce_mean_with_feedback(m, axes)
            err = m - local_rt
            off = 0
            for i in bidx:
                n = leaves[i].size
                out[i] = (mean[off:off + n]
                          .reshape(leaves[i].shape)
                          .astype(leaves[i].dtype))
                new_e[i] = (err[off:off + n]
                            .reshape(e_leaves[i].shape)
                            .astype(e_leaves[i].dtype))
                off += n

        return (jax.tree.unflatten(treedef, out),
                jax.tree.unflatten(treedef, new_e))

    def update(self, grads: PyTree, state, params: PyTree | None = None):
        ef_state = None
        reduced = None
        if self.error_feedback:
            ef_state, state = state, state.inner
            with jax.named_scope(train_path.GRAD_REDUCE):
                reduced, new_residual = self._reduce_with_feedback(
                    grads, ef_state.residual
                )
        else:
            schedule = self._effective_schedule()
            if schedule == "zero":
                return self._zero_update(grads, state, params)

        if not self.double_buffering:
            if reduced is None:
                with jax.named_scope(train_path.GRAD_REDUCE):
                    reduced = self._reduce_scheduled(grads, schedule)
            with jax.named_scope(train_path.OPTIMIZER_UPDATE):
                updates, inner = self.actual_optimizer.update(
                    reduced, state, params
                )
        else:
            # OVERLAPPED mode (reference staleness-1, made explicit):
            # apply last step's BANKED buckets first, then dispatch this
            # step's reduction — the update has no data path into the
            # same step's collective (certified in tests/test_optimizer
            # .py), so XLA's async scheduler (and, across a scan, step
            # t+1's backward) runs the wire concurrently with compute;
            # with donation (make_train_step's default) the bank buffer
            # is reused in place. Per-bucket wire events carry
            # overlapped=True for trace_report's comm-hidden fraction.
            with jax.named_scope(train_path.OPTIMIZER_UPDATE):
                updates, inner_inner = self.actual_optimizer.update(
                    state.communicated_grads, state.inner, params
                )
            if reduced is None:
                with jax.named_scope(train_path.GRAD_REDUCE):
                    reduced = self._reduce_scheduled(grads, schedule)
            inner = _DoubleBufferState(
                inner=inner_inner, communicated_grads=reduced,
                step=state.step + 1,
            )
        if self.error_feedback:
            return updates, _ErrorFeedbackState(
                inner=inner, residual=new_residual
            )
        return updates, inner

    # -- reference-parity conveniences ------------------------------------

    def broadcast(self, params: PyTree, root: int = 0) -> PyTree:
        """The reference's first-update ``bcast_data(model)``, made explicit."""
        return self.communicator.bcast_data(params, root)

    def __getattr__(self, item):
        # Guard against re-entry during unpickling/copy, when __dict__ is
        # not yet populated and 'actual_optimizer' itself is being looked up.
        if item.startswith("__") or "actual_optimizer" not in self.__dict__:
            raise AttributeError(item)
        return getattr(self.actual_optimizer, item)


class _LocalSGDState(NamedTuple):
    inner: Any
    #: replicated step counter driving the sync cadence
    step: jax.Array
    #: params at the last sync — the outer optimizer's reference point
    anchor: PyTree
    #: outer heavy-ball velocity (DiLoCo's outer momentum)
    outer_velocity: PyTree


class LocalSGDOptimizer:
    """Local SGD / DiLoCo-style periodic parameter averaging.

    The per-step allreduce of :class:`MultiNodeOptimizer` is the right
    default on ICI, but on a DCN-dominated topology the gradient wire is
    the bottleneck even at int8 (docs/parallelism.md's scaling model).
    This wrapper removes it entirely: each member applies ``inner``
    updates computed from its LOCAL gradients, and only every
    ``sync_every``-th step do the members communicate — one global
    parameter average, folded through an outer heavy-ball step from the
    last sync's ``anchor`` (``outer_momentum=0, outer_lr=1`` is plain
    FedAvg-style averaging; DiLoCo uses outer momentum ≈0.9).
    Communication volume drops ``sync_every``× with the usual local-SGD
    convergence trade-off.

    TPU shape: the sync is a single ``pmean`` under a ``lax.cond`` whose
    predicate (``step % sync_every == 0``) is replicated — every member
    takes the same branch, so the collective stays matched across the
    mesh. Outside any named-axis context (single device / pjit
    auto-parallel) the mean is the identity and the wrapper degrades to
    exactly ``inner``.

    Beyond the reference: ChainerMN's only communication-reduction
    levers were fp16 compression and double buffering
    (``pure_nccl_communicator.py`` †, ``optimizers.py`` †); periodic
    averaging composes with this package's int8 wire era as the third
    axis (frequency, alongside width and overlap).
    """

    #: see MultiNodeOptimizer: the sync is the periodic parameter mean;
    #: gradients must reach ``inner`` UN-reduced.
    handles_cross_rank_sync = True

    def __init__(self, inner, communicator, *, sync_every: int,
                 outer_lr: float = 1.0, outer_momentum: float = 0.0):
        if sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, got {sync_every}")
        self.inner = inner
        self.comm = communicator
        self.sync_every = sync_every
        self.outer_lr = outer_lr
        self.outer_momentum = outer_momentum

    def init(self, params: PyTree):
        return _LocalSGDState(
            inner=self.inner.init(params),
            step=jnp.zeros((), jnp.int32),
            # A COPY, not the params themselves: a donating train step
            # (make_train_step(donate=True)) would otherwise hand XLA
            # the same buffer twice (params leaf + anchor leaf) and die
            # with 'Attempt to donate the same buffer twice'.
            anchor=jax.tree.map(lambda p: jnp.array(p, copy=True), params),
            outer_velocity=jax.tree.map(jnp.zeros_like, params),
        )

    def update(self, grads: PyTree, state, params: PyTree | None = None):
        if params is None:
            raise ValueError("LocalSGDOptimizer.update requires params")
        iu, inner_state = self.inner.update(grads, state.inner, params)
        candidate = optax.apply_updates(params, iu)
        step = state.step + 1
        do_sync = (step % self.sync_every) == 0
        axes = self.comm.grad_axes

        def sync(_):
            mean_cand = _pmean_if_in_axis(candidate, axes)
            # Outer step from the anchor along the averaged local
            # progress: delta is what the flock moved since last sync.
            delta = jax.tree.map(
                lambda a, c: a - c, state.anchor, mean_cand
            )
            vel = jax.tree.map(
                lambda v, d: self.outer_momentum * v + d,
                state.outer_velocity, delta,
            )
            target = jax.tree.map(
                lambda a, v: a - self.outer_lr * v, state.anchor, vel
            )
            return target, vel, target

        def no_sync(_):
            return candidate, state.outer_velocity, state.anchor

        target, vel, anchor = lax.cond(do_sync, sync, no_sync, None)
        updates = jax.tree.map(lambda t, p: t - p, target, params)
        return updates, _LocalSGDState(
            inner=inner_state, step=step, anchor=anchor,
            outer_velocity=vel,
        )

    def __getattr__(self, item):
        # Same re-entry guard as MultiNodeOptimizer: during unpickling /
        # copy, __dict__ is empty and looking up 'inner' would recurse.
        if item.startswith("__") or "inner" not in self.__dict__:
            raise AttributeError(item)
        return getattr(self.inner, item)


def create_local_sgd(
    inner: optax.GradientTransformation,
    communicator: CommunicatorBase,
    *,
    sync_every: int,
    outer_lr: float = 1.0,
    outer_momentum: float = 0.0,
) -> LocalSGDOptimizer:
    """Factory for :class:`LocalSGDOptimizer` (periodic parameter
    averaging; see the class docstring for semantics and when it beats
    the per-step wire)."""
    return LocalSGDOptimizer(
        inner, communicator, sync_every=sync_every,
        outer_lr=outer_lr, outer_momentum=outer_momentum,
    )


def inner_transform(optimizer) -> optax.GradientTransformation:
    """The plain optax transform a :class:`~chainermn_tpu.parallel.plan.
    ParallelPlan` composes, unwrapped from a communicator-style wrapper.

    A plan owns the whole reduction (its spec providers say which
    collective each axis owes the step), so a
    :class:`MultiNodeOptimizer`'s own wire features cannot ride along:
    the plain inner transform is extracted, and wrappers whose semantics
    live in the wrapper itself (double buffering's staleness bank, the
    EF residual, local-SGD's sync cadence) are refused loudly rather
    than silently dropped. Plain optax transforms pass through.
    """
    if isinstance(optimizer, MultiNodeOptimizer):
        if optimizer.double_buffering or optimizer.error_feedback:
            raise ValueError(
                "a ParallelPlan composes its own reduction; "
                "double_buffering/error_feedback live in the wrapper's "
                "wire and cannot ride a plan-compiled step — pass the "
                "plain inner optimizer"
            )
        if optimizer.compress_dtype is not None:
            raise ValueError(
                "a ParallelPlan reduces in full precision; the wrapper's "
                f"compressed wire (allreduce_grad_dtype="
                f"{jnp.dtype(optimizer.compress_dtype).name}) would be "
                "silently dropped — pass the plain inner optimizer, or "
                "keep this call site on the communicator path"
            )
        return optimizer.actual_optimizer
    if isinstance(optimizer, LocalSGDOptimizer):
        raise ValueError(
            "LocalSGDOptimizer's sync cadence is wrapper state; a "
            "ParallelPlan cannot carry it — pass the plain inner "
            "optimizer"
        )
    return optimizer


def create_multi_node_optimizer(
    actual_optimizer: optax.GradientTransformation,
    communicator: CommunicatorBase,
    *,
    double_buffering: bool = False,
    allreduce_grad_dtype=None,
    error_feedback: bool = False,
    reduction_schedule: str | None = None,
) -> MultiNodeOptimizer:
    """Factory mirroring the reference signature
    (``create_multi_node_optimizer(opt, comm, double_buffering)``,
    ``optimizers.py`` (dagger)). ``error_feedback=True`` (with
    ``allreduce_grad_dtype=jnp.int8``) enables EF-SGD over the quantized
    wire: each rank's stage-1 quantization error is carried in the
    optimizer state and added to the next step's message, removing the
    systematic rounding bias (the cumulative applied gradient tracks the
    exact mean to one-step noise instead of drifting linearly).
    ``reduction_schedule`` picks the reduction algorithm
    (None/'flat'/'two_level'/'zero'; see
    :class:`MultiNodeOptimizer` and docs/parallelism.md)."""
    return MultiNodeOptimizer(
        actual_optimizer,
        communicator,
        double_buffering=double_buffering,
        compress_dtype=allreduce_grad_dtype,
        error_feedback=error_feedback,
        reduction_schedule=reduction_schedule,
    )


__all__ = [
    "LocalSGDOptimizer",
    "MultiNodeOptimizer",
    "allreduce_gradients",
    "allreduce_grads_transform",
    "create_local_sgd",
    "create_multi_node_optimizer",
    "inner_transform",
]
