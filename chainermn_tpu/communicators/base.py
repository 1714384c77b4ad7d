"""``CommunicatorBase`` — the heart of the framework.

Mirrors the reference's ``chainermn/communicators/communicator_base.py``
(dagger) API surface (SURVEY.md section 2.1): ``rank / size / intra_rank /
inter_rank / inter_size``, array collectives, ``*_obj`` object collectives,
and the model-level ``bcast_data`` / ``allreduce_grad`` pair — but the
execution model is TPU-native SPMD:

- The *device plane* is a ``jax.sharding.Mesh``. A "rank" of the reference
  (one MPI process per GPU) corresponds to one mesh slot. Eager array
  collectives take a **stacked** array whose leading axis enumerates per-rank
  contributions (shape ``[size, ...]``), shard it over the mesh, and run one
  jitted XLA collective — semantically identical to "every rank passes its
  local array", with the stacking making the SPMD single-controller model
  explicit. Inside a jitted train step, use the named-axis forms
  (:mod:`chainermn_tpu.parallel.collectives` or ``comm.axis_name`` with
  ``jax.lax.psum``) instead; that is the hot path.

- The *host plane* is the set of JAX processes; ``*_obj`` collectives ride
  :mod:`chainermn_tpu.communicators._host_comm` (multihost_utils / native
  backend) the way the reference's rode mpi4py.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chainermn_tpu.communicators._host_comm import HostComm
from chainermn_tpu.observability import flight as _flight
from chainermn_tpu.observability import trace as _trace
from chainermn_tpu.parallel import collectives
from chainermn_tpu.parallel.mesh import MeshTopology

PyTree = Any

#: Wildcard source for :meth:`CommunicatorBase.recv` /
#: :meth:`CommunicatorBase.recv_obj` / :meth:`CommunicatorBase.probe`
#: (reference parity: ``MPI.ANY_SOURCE``).
ANY_SOURCE = -1


def _latest_decision(name: str) -> dict | None:
    """Most recent autotune decision record for ``name`` — the tuning
    provenance a communicator attaches to the wire events of a
    configuration it resolved via ``'auto'``."""
    try:
        from chainermn_tpu import tuning

        for d in reversed(tuning.decisions_taken()):
            if d.get("name") == name:
                return d
    except Exception:
        pass
    return None


class CommunicatorBase:
    """Base communicator over a device mesh.

    Subclasses pick the mesh construction (all-devices flat, hierarchical
    (inter, intra) factorisation, CPU-only, ...) the way the reference's
    subclasses picked NCCL/MPI compositions.
    """

    #: name used by :func:`chainermn_tpu.create_communicator`
    name: str = "base"

    def __init__(
        self, mesh: Mesh, *, allreduce_grad_dtype=None, _host: HostComm | None = None
    ) -> None:
        self.mesh = mesh
        # The lazy provider keeps topology.intra_rank/intra_size truthful
        # AND mutually consistent on multi-process-per-host runtimes
        # (hostname discovery, deferred so construction stays
        # non-collective). Single-process returns None: the topology then
        # keeps its devices-per-process intra_size semantics.
        self.topology = MeshTopology(
            mesh,
            host_intra_provider=(
                lambda: self._intra if self.host.size > 1 else None
            ),
        )
        self.host = _host if _host is not None else HostComm()
        self._flat_axes = tuple(mesh.axis_names)
        self._flat_spec = P(self._flat_axes)
        #: dtype for compressed gradient allreduce
        #: (reference: ``allreduce_grad_dtype='float16'`` on
        #: ``PureNcclCommunicator`` (dagger); bf16 is the TPU-native
        #: choice). ``"auto"`` resolves the wire variant device-aware
        #: through the autotune registry (decision ``allreduce_wire``
        #: keyed on this mesh's device kind + size — table default
        #: bf16; see chainermn_tpu.tuning).
        #: autotune decision record behind an ``'auto'`` wire resolution
        #: (name/winner/source/key) — attached to this communicator's
        #: ``allreduce_grad`` wire events so every auto collective in a
        #: trace carries its dispatch provenance. None for explicit dtypes.
        self._wire_provenance: dict | None = None
        if isinstance(allreduce_grad_dtype, str) \
                and allreduce_grad_dtype == "auto":
            from chainermn_tpu.parallel.collectives import (
                resolve_allreduce_wire,
            )

            allreduce_grad_dtype = resolve_allreduce_wire(
                self.device_kind, self.topology.size
            )
            self._wire_provenance = _latest_decision("allreduce_wire")
        self.allreduce_grad_dtype = (
            jnp.dtype(allreduce_grad_dtype) if allreduce_grad_dtype else None
        )

    @functools.cached_property
    def _intra(self) -> tuple[int, int]:
        """(intra_rank, processes-on-this-host) — the reference's hostname
        exchange (``_communication_utility.init_ranks`` (dagger), which ran
        ``MPI_Comm_split_type(SHARED)``). Lazy so that *construction* stays
        a local, non-collective act (safe to do asymmetrically); the first
        ``intra_rank``/``intra_size`` access on a multi-process runtime is a
        host-plane allgather and must happen on every process."""
        if self.host.size == 1:
            return 0, 1
        import socket

        me = (socket.gethostname(), self.host.rank)
        infos = self.host.allgather_obj(me)
        same_host = sorted(r for h, r in infos if h == me[0])
        return same_host.index(self.host.rank), len(same_host)

    # ------------------------------------------------------------------
    # Topology properties (reference: communicator_base.py (dagger))
    # ------------------------------------------------------------------

    @property
    def size(self) -> int:
        """World size = number of mesh slots (reference: #MPI processes)."""
        return self.topology.size

    @functools.cached_property
    def device_kind(self) -> str:
        """``device_kind`` of this mesh's devices (``"cpu"``,
        ``"TPU v5 lite"``, ...) — the device-aware dispatch key the
        autotune registry (chainermn_tpu.tuning) resolves against.
        Cached: the mesh is immutable, and the wire-trace layer stamps
        this onto every collective event."""
        try:
            return next(iter(self.mesh.devices.flat)).device_kind
        except Exception:
            return "unknown"

    @contextlib.contextmanager
    def _mark(self, op: str, nbytes=None):
        """Flight-recorder entry marker (ISSUE 6): one lock-free slot
        store naming the collective this process is ABOUT to dispatch —
        what the hang watchdog's dump reports when peers never arrive.
        The sites call :meth:`_wire_event` INSIDE the marked region, so
        the marker covers the full dispatch including any sync wait in
        the event; the ``finally`` removes THIS entry by identity
        exactly once whether the body returns, the body raises (a
        caller that catches a bad-dtype/socket error and carries on
        healthy must not leave a phantom marker for the fire-once
        watchdog), or the event itself raises after recording (sync
        mode surfacing a deferred XLA error must not pop an ENCLOSING
        composite's marker — review finding). Always on (the cost is
        one tuple build); host-side only, so the lowered HLO is
        untouched (structural test in tests/test_metrics.py)."""
        token = _flight.collective_entered(
            op, nbytes=nbytes, axes=list(self._flat_axes), size=self.size,
        )
        try:
            yield
        finally:
            _flight.collective_exited(token)

    def _wire_event(
        self, op: str, t0: float, *, payload=None, nbytes=None,
        result=None, **extra,
    ) -> None:
        """Record one collective-wire counter event (no-op when tracing
        is off — one global read). Host-side only: never called from
        inside a jitted program, so instrumentation cannot change the
        lowered HLO (structural test in tests/test_trace.py).
        ``result`` is blocked on only in the recorder's sync mode (true
        wall durations); default durations are dispatch-to-return. The
        flight recorder's in-flight marker is NOT cleared here — the
        enclosing :meth:`_mark` owns its entry and removes it by
        identity on the way out."""
        rec = _trace.active()
        if rec is None:
            return
        if result is not None:
            _trace.sync_point(result)
        if nbytes is None and payload is not None:
            nbytes = _trace.tree_nbytes(payload)
        rec.collective(
            op, nbytes=nbytes, dur_s=time.perf_counter() - t0,
            size=self.size, device=self.device_kind, **extra,
        )

    @property
    def rank(self) -> int:
        """Host-plane rank (process index). Inside a jitted program use
        :func:`chainermn_tpu.parallel.collectives.axis_index` instead — in
        SPMD one controller drives many mesh slots."""
        return self.topology.rank

    @property
    def intra_rank(self) -> int:
        """Position of this process among the processes sharing its host
        (hostname-discovered, the reference's ``init_ranks``); 0 for a
        single process. Multihost: first access is a host-plane collective
        (see ``_intra``)."""
        return self._intra[0]

    @property
    def intra_size(self) -> int:
        """Single process: devices this process drives (the mesh slots of
        one controller). Multi-process: processes sharing this host (the
        reference's GPUs-per-node count, one process per accelerator)."""
        if self.host.size == 1:
            return self.topology.intra_size
        return self._intra[1]

    @property
    def inter_rank(self) -> int:
        return self.topology.inter_rank

    @property
    def inter_size(self) -> int:
        return self.topology.inter_size

    @property
    def axis_name(self) -> str:
        """Primary data-parallel mesh axis for gradient reduction."""
        return self.mesh.axis_names[0]

    @property
    def grad_axes(self) -> tuple[str, ...]:
        """All mesh axes gradients are averaged over. For a hierarchical
        communicator this is ``('inter', 'intra')`` — XLA performs the
        2-level reduction the reference hand-built (SURVEY.md section 2.2)."""
        return self._flat_axes

    @property
    def bn_axis_name(self):
        """Axis-name argument for flax-style ``axis_name`` parameters
        (sync-BN and friends): the single axis, or the tuple when gradients
        reduce over a factorised mesh."""
        axes = self.grad_axes
        return axes if len(axes) > 1 else axes[0]

    # ------------------------------------------------------------------
    # Eager array collectives over stacked per-rank contributions
    # ------------------------------------------------------------------

    def _shard_stacked(self, x: jax.Array) -> jax.Array:
        x = jnp.asarray(x)
        if x.shape[0] != self.size:
            raise ValueError(
                f"stacked collective input must have leading dim == size "
                f"({self.size}), got shape {x.shape}"
            )
        spec = P(self._flat_axes, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    @functools.cached_property
    def _jitted(self):
        """Jitted shard_map'd collective kernels, built once per communicator
        so jax.jit's trace cache is keyed stably."""
        mesh, axes = self.mesh, self._flat_axes

        def smap(fn, out_stacked: bool):
            def wrapper(x, *args):
                in_spec = P(axes, *([None] * (x.ndim - 1)))
                out_spec = in_spec if out_stacked else P(None, *([None] * (x.ndim - 1)))

                def body(xs, *a):
                    # xs: [1, ...] local shard; collapse the stack dim.
                    return fn(xs[0], *a)[None]

                return shard_map(
                    body, mesh=mesh, in_specs=(in_spec,) + tuple(P() for _ in args),
                    out_specs=out_spec,
                )(x, *args)

            return jax.jit(wrapper, static_argnums=())

        def _reduce(op):
            def fn(x):
                return collectives.allreduce(x, axes, op=op)
            return fn

        def _alltoall(x):
            # Local view is this rank's send row [size, ...]; piece j goes to
            # rank j, received pieces concatenate back along axis 0 — the MPI
            # alltoall exchange as ONE XLA collective over the (possibly
            # factorised) mesh axes.
            return collectives.alltoall(
                x, axes, split_axis=0, concat_axis=0, tiled=True
            )

        return {
            "sum": smap(_reduce("sum"), out_stacked=False),
            "mean": smap(_reduce("mean"), out_stacked=False),
            "max": smap(_reduce("max"), out_stacked=False),
            "min": smap(_reduce("min"), out_stacked=False),
            "alltoall": smap(_alltoall, out_stacked=True),
        }

    def allreduce(self, x: jax.Array, op: str = "sum") -> jax.Array:
        """Eager allreduce of stacked per-rank values ``x[size, ...]`` →
        reduced array ``[...]`` (replicated)."""
        t0 = time.perf_counter()
        x = self._shard_stacked(x)
        with self._mark("allreduce", nbytes=int(x.nbytes)):
            out = self._jitted[op](x)
            self._wire_event("allreduce", t0, nbytes=int(x.nbytes),
                             result=out, reduce_op=op)
        return out[0]

    def _root_process(self, root: int) -> int:
        """Host-plane rank owning mesh slot ``root`` — roots are *mesh-slot*
        ranks (the reference's MPI ranks), not process indices; on a
        multi-process runtime the two differ. For the world communicator the
        host rank IS the process index (asserted at HostComm bootstrap);
        split communicators translate through their member list."""
        pid = list(self.mesh.devices.flat)[root].process_index
        members = self.host.world_members
        return members.index(pid) if members != list(range(len(members))) else pid

    def _agree_value(self, tree: PyTree, root_host_rank: int) -> PyTree:
        """Every process of this communicator gets the root process's value
        of ``tree``.

        World communicators prefer ``multihost_utils.broadcast_one_to_all``
        (device-plane broadcast, scales to big param pytrees); subgroup
        communicators from :meth:`split` — and TCP worlds running without
        the JAX distributed runtime — ride the host plane instead, because
        ``multihost_utils`` collectives are world-global and would deadlock
        or over-synchronise a color group."""
        if self.host.size == 1:
            return tree
        is_subgroup = getattr(self.host, "_world_members", None) is not None
        if not is_subgroup and jax.process_count() > 1:
            from jax.experimental import multihost_utils

            return multihost_utils.broadcast_one_to_all(
                tree, is_source=(self.host.rank == root_host_rank)
            )
        payload = None
        if self.host.rank == root_host_rank:
            payload = jax.tree.map(lambda a: np.asarray(a), tree)
        return self.host.bcast_obj(payload, root_host_rank)

    def bcast(self, x: jax.Array, root: int = 0, *, stacked: bool = False) -> jax.Array:
        """Broadcast ``x`` to a mesh-replicated value (the common
        "replicate rank-0 data" use). With ``stacked=True``, ``x`` holds
        per-rank contributions ``[size, ...]`` and ``x[root]`` is broadcast —
        the eager-parity form the stacked-collective tests use. Explicit flag
        rather than shape sniffing: a plain batch whose leading dim happens
        to equal world size must not be silently sliced."""
        t0 = time.perf_counter()
        x = jnp.asarray(x)
        if stacked:
            if x.ndim < 1 or x.shape[0] != self.size:
                raise ValueError(
                    f"stacked bcast input must have leading dim == size "
                    f"({self.size}), got shape {x.shape}"
                )
            x = x[root]
        # Cross-process agreement: every process must end up with the
        # *root process's* value, not its own local one.
        with self._mark("bcast", nbytes=int(x.nbytes)):
            x = self._agree_value(x, self._root_process(root))
            out = jax.device_put(x, NamedSharding(self.mesh, P()))
            self._wire_event("bcast", t0, nbytes=int(out.nbytes),
                             result=out, root=root)
        return out

    def allgather(self, x: jax.Array) -> jax.Array:
        """Identity on the stacked representation (every rank gets all
        contributions), placed replicated — mirrors ``allgather`` semantics."""
        t0 = time.perf_counter()
        x = jnp.asarray(x)
        if x.shape[0] != self.size:
            raise ValueError("allgather expects stacked [size, ...] input")
        with self._mark("allgather", nbytes=int(x.nbytes)):
            out = jax.device_put(x, NamedSharding(self.mesh, P()))
            self._wire_event("allgather", t0, nbytes=int(out.nbytes),
                             result=out)
        return out

    def alltoall(self, x: jax.Array) -> jax.Array:
        """Eager all-to-all on ``x[size, size, ...]`` (rank i's row i is its
        send buffer): returns the transposed exchange, matching
        ``MPI_Alltoall`` on the stacked view. Shards the stack over the mesh
        and runs a real ``lax.all_to_all`` — the bytes move device-to-device
        over ICI, not through a host transpose."""
        t0 = time.perf_counter()
        x = jnp.asarray(x)
        if x.ndim < 2 or x.shape[0] != self.size or x.shape[1] != self.size:
            raise ValueError("alltoall expects [size, size, ...] input")
        x = self._shard_stacked(x)
        with self._mark("alltoall", nbytes=int(x.nbytes)):
            out = self._jitted["alltoall"](x)
            self._wire_event("alltoall", t0, nbytes=int(x.nbytes),
                             result=out)
        return out

    def scatter(self, x: jax.Array, root: int = 0) -> jax.Array:
        """Scatter root's ``[size, ...]`` buffer: shard i receives ``x[i]``,
        returned as the stacked sharded array. Multihost: the root process's
        buffer is broadcast first so every process shards the same data."""
        t0 = time.perf_counter()
        x = jnp.asarray(x)
        with self._mark("scatter", nbytes=int(x.nbytes)):
            x = self._agree_value(x, self._root_process(root))
            out = self._shard_stacked(x)
            self._wire_event("scatter", t0, nbytes=int(x.nbytes),
                             result=out, root=root)
        return out

    # ------------------------------------------------------------------
    # Model-level operations (the reference's hot pair)
    # ------------------------------------------------------------------

    def bcast_data(self, params: PyTree, root: int = 0) -> PyTree:
        """Replicate a parameter pytree across the mesh (and across
        processes when multihost), so all ranks start from rank-``root``'s
        weights — reference ``bcast_data(model)`` called on the first
        optimizer update (``optimizers.py`` (dagger))."""
        t0 = time.perf_counter()
        with self._mark("bcast_data"):
            params = self._agree_value(params, self._root_process(root))
            repl = NamedSharding(self.mesh, P())
            out = jax.tree.map(
                lambda x: jax.device_put(jnp.asarray(x), repl), params
            )
            self._wire_event("bcast_data", t0, payload=out, result=out,
                             root=root)
        return out

    def reduce_gradients_in_jit(
        self, grads: PyTree, *, compress_dtype=None, schedule: str | None = None
    ) -> PyTree:
        """The IN-JIT gradient reduction this communicator's strategy uses —
        called from the train step / optimizer wrapper inside the named-axis
        context. Base strategy: one fused ``pmean`` over ``grad_axes`` (XLA
        derives the topology-aware schedule). Subclasses may pin an explicit
        algorithm (:class:`TwoDimensionalCommunicator`).

        ``schedule`` overrides the strategy with one of the two bucketed
        schedules of
        :func:`chainermn_tpu.parallel.reduction_schedule.reduce_tree`
        (``'flat'`` = one ``pmean`` per packed bucket, ``'two_level'`` =
        reduce-scatter -> shard allreduce -> allgather per bucket); the
        optimizer wrapper's ``reduction_schedule=`` is the normal front
        door — this knob exists for hand-rolled steps that call the
        communicator directly. Outside the named-axis context both forms
        degrade identically."""
        from chainermn_tpu.optimizers import allreduce_gradients

        if compress_dtype is None:
            compress_dtype = self.allreduce_grad_dtype
        if schedule is not None:
            from chainermn_tpu.parallel.collectives import axes_bound
            from chainermn_tpu.parallel.reduction_schedule import (
                reduce_tree,
            )

            if axes_bound(self.grad_axes):
                return reduce_tree(
                    grads, schedule=schedule, axes=self.grad_axes,
                    compress_dtype=compress_dtype, size=self.size,
                )
        return allreduce_gradients(
            grads, axis_names=self.grad_axes, compress_dtype=compress_dtype
        )

    def allreduce_grad(self, grads: PyTree, op: str = "mean") -> PyTree:
        """Eager gradient allreduce of *stacked* per-rank grads
        (leaves shaped ``[size, ...]``) → averaged pytree ``[...]``.

        This is the eager/debugging form. The production path is in-jit:
        ``optax``-wrapped via :func:`chainermn_tpu.create_multi_node_optimizer`
        which lowers to ``lax.pmean(grads, comm.grad_axes)`` inside the train
        step — XLA fuses the reference's pack → cast → ncclAllReduce → scale →
        unpack pipeline (``pure_nccl_communicator.py`` (dagger), SURVEY.md
        section 3.2) into its collective scheduling.
        """
        dtype = self.allreduce_grad_dtype
        int8_wire = (dtype is not None
                     and jnp.dtype(dtype) == jnp.dtype(jnp.int8))

        def quantize_roundtrip(g, *, per_member: bool):
            # One quantization stage of the int8 wire (the in-jit path's
            # two stages live in _int8_core): max-abs scale, round,
            # dequantize. Stage 1 gets PER-MEMBER scales — the stacked
            # dim-0 slices here ARE the per-rank buffers, and _int8_core
            # has each member scale by its OWN amax (a global scale over
            # the stack would truncate small-magnitude ranks to zero —
            # the very failure a bare astype(int8) has). Stage 2 (the
            # reduced buffer, no rank dim) gets one global scale, like
            # the wire's requantize-the-shard. A 1-D stacked leaf means
            # scalar per-rank buffers, whose roundtrip is exact — the
            # wire's own behaviour on 1-element buffers, not a bug
            # (the in-jit path quantizes per leaf: a scalar per-rank
            # buffer dequantizes exactly there as well).
            if per_member:
                amax = jnp.max(jnp.abs(g), axis=tuple(range(1, g.ndim)),
                               keepdims=True)
            else:
                amax = jnp.max(jnp.abs(g))
            scale = jnp.maximum(amax, 1e-30) / 127.0
            return jnp.clip(jnp.round(g / scale), -127, 127) * scale

        def reduce_leaf(g):
            g = jnp.asarray(g)
            orig = g.dtype
            if int8_wire and jnp.issubdtype(orig, jnp.floating):
                # Eager approximation of the quantized wire: per-rank
                # quantize-dequantize (stage 1), exact mean, one final
                # quantize-dequantize (stage 2) — same two-rounding
                # noise model as the in-jit scheme without its chunking.
                g = quantize_roundtrip(g.astype(jnp.float32),
                                       per_member=True)
                out = self.allreduce(g, op=op)
                return quantize_roundtrip(out, per_member=False).astype(orig)
            if dtype is not None and jnp.issubdtype(orig, jnp.floating):
                g = g.astype(dtype)
            out = self.allreduce(g, op=op)
            return out.astype(orig)

        t0 = time.perf_counter()
        with self._mark("allreduce_grad"):
            out = jax.tree.map(reduce_leaf, grads)
            # The top-level wire event (the per-leaf allreduces above
            # record their own nested events): payload bytes of the whole
            # tree, the wire dtype, and — when this communicator's wire
            # came from ``allreduce_grad_dtype='auto'`` — the autotune
            # provenance.
            self._wire_event(
                "allreduce_grad", t0, payload=grads, result=out,
                wire_dtype=(jnp.dtype(dtype).name if dtype is not None
                            else "none"),
                provenance=self._wire_provenance, reduce_op=op,
            )
        return out

    # ------------------------------------------------------------------
    # Host-plane object collectives (reference: *_obj via mpi4py)
    # ------------------------------------------------------------------

    def bcast_obj(self, obj: Any, root: int = 0) -> Any:
        # Roots are mesh-slot ranks everywhere in this API; map to the owning
        # process for the host plane (same rule as the array collectives).
        return self.host.bcast_obj(obj, self._root_process(root))

    def gather_obj(self, obj: Any, root: int = 0):
        return self.host.gather_obj(obj, self._root_process(root))

    def allgather_obj(self, obj: Any) -> list[Any]:
        return self.host.allgather_obj(obj)

    def scatter_obj(self, objs: Sequence[Any] | None, root: int = 0) -> Any:
        return self.host.scatter_obj(objs, self._root_process(root))

    def allreduce_obj(self, obj: Any, op: Callable[[Any, Any], Any] | None = None) -> Any:
        return self.host.allreduce_obj(obj, op)

    def send(self, x, dest: int, tag: int = 0) -> None:
        """Eager point-to-point ndarray send (reference:
        ``MpiCommunicatorBase.send`` — an ndarray or tuple of ndarrays,
        preceded by a ``_MessageType`` header describing tuple-ness, shapes
        and dtypes, ``mpi_communicator_base.py`` (dagger)).

        Cross-process transport rides the native TCP host plane (the
        reference's non-CUDA-aware staging: device → host → wire). The
        in-jit production path for model parallelism is
        :mod:`chainermn_tpu.functions.point_to_point` (ppermute); this eager
        form exists for parity and host-driven control flows, not the hot
        loop."""
        t0 = time.perf_counter()
        # p2p counts for the flight marker too — a send into a
        # vanished peer blocks exactly like a collective.
        with self._mark("send"):
            is_tuple = isinstance(x, (tuple, list))
            parts = list(x) if is_tuple else [x]
            header = []
            payloads = []
            for p in parts:
                arr = np.asarray(p)
                header.append((arr.shape, str(arr.dtype)))
                payloads.append(arr.tobytes())
            self.send_obj(("ndarray", is_tuple, header, payloads),
                          dest, tag)
            self._wire_event("send", t0, plane="host",
                             nbytes=sum(len(b) for b in payloads),
                             dest=dest)

    def recv(self, source: int, tag: int = 0):
        """Eager point-to-point ndarray receive; returns NumPy array(s)
        matching the sender's shapes and dtypes EXACTLY (including 64-bit —
        ``jax.device_put`` would canonicalise int64→int32 under the default
        x64-off config, silently corrupting large values). Callers place on
        device with their own sharding/dtype choice."""
        t0 = time.perf_counter()
        # See send: a recv whose sender never shows is the canonical
        # p2p hang — marked like the collectives.
        with self._mark("recv"):
            kind, is_tuple, header, payloads = self.recv_obj(source, tag)
            if kind != "ndarray":
                # Recoverable contract error; the _mark context balances
                # the marker on the raise (callers may catch and carry on).
                raise RuntimeError(
                    f"recv expected an ndarray message, got {kind!r} "
                    "(interleaved send_obj/send on one channel must match "
                    "recv_obj/recv order)"
                )
            self._wire_event("recv", t0, plane="host",
                             nbytes=sum(len(b) for b in payloads),
                             source=source)
        arrays = tuple(
            # .copy(): frombuffer views the wire bytes read-only; MPI recv
            # hands back a writable buffer, so match that contract.
            np.frombuffer(buf, dtype=np.dtype(dt)).reshape(shape).copy()
            for (shape, dt), buf in zip(header, payloads)
        )
        return arrays if is_tuple else arrays[0]

    @functools.cached_property
    def _self_p2p(self) -> dict:
        """FIFO mailboxes for same-process p2p (MPI permits self send/recv;
        mesh-slot ranks sharing one process land here — including all
        single-process use). Keyed ``(slot, tag)`` where the slot is the one
        NAMED IN THE CALL (``dest`` on send, ``source`` on recv), so
        messages to different local slots never cross-deliver.

        Semantics caveat: in single-controller eager mode the caller has no
        rank identity, so MPI's "recv names the SENDER" cannot be expressed
        for co-located pairs — a ring-style ``send(x, next); recv(prev)``
        only pairs up when next/prev live on different processes. For
        cross-slot exchanges inside one process, use the in-jit
        differentiable p2p (:mod:`chainermn_tpu.functions.point_to_point`),
        which has real per-slot identity via ``axis_index``."""
        import collections

        return collections.defaultdict(collections.deque)

    def send_obj(self, obj: Any, dest: int, tag: int = 0) -> None:
        """Point-to-point host send (reference: ``send_obj`` via MPI). Rides
        the native TCP backend (:mod:`chainermn_tpu.native`); the channel is
        per-pair FIFO, so ``tag`` is carried in-band and matched on receive
        (device-plane p2p lives in :mod:`chainermn_tpu.functions`). Sends to
        mesh slots owned by THIS process are buffered locally (MPI self-send
        parity); the matching ``recv`` must name the same slot."""
        dest_proc = self._root_process(dest)
        if dest_proc == self.host.rank:
            self._self_p2p[(dest, tag)].append(obj)
            return
        self.host.send_obj((tag, obj), dest_proc)

    @functools.cached_property
    def _pending_remote(self) -> dict:
        """Messages pulled off a peer socket while waiting for a different
        tag, keyed ``(src_proc, tag)`` — the receive-side buffering that
        turns the per-pair FIFO wire into MPI-style tag matching (a
        mismatched arrival is stashed, never destroyed)."""
        import collections

        return collections.defaultdict(collections.deque)

    def recv_obj(self, source: int, tag: int = 0) -> Any:
        if source == ANY_SOURCE:
            return self.recv_any_obj(tag)[1]
        src_proc = self._root_process(source)
        if src_proc == self.host.rank:
            box = self._self_p2p.get((source, tag))
            if not box:
                raise RuntimeError(
                    f"recv_obj from local slot {source} (tag {tag}) with no "
                    "buffered self-send — same-process p2p requires a prior "
                    "send addressed to THAT slot/tag"
                )
            return box.popleft()
        pend = self._pending_remote.get((src_proc, tag))
        if pend:
            return pend.popleft()
        while True:
            got_tag, obj = self.host.recv_obj(src_proc)
            if got_tag == tag:
                return obj
            # Other-tag arrival: buffer for its own receiver (MPI matching
            # semantics; blocks here until the wanted tag arrives).
            self._pending_remote[(src_proc, got_tag)].append(obj)

    def _slot_of_process(self, proc: int) -> int:
        """Lowest-numbered mesh slot owned by host-plane rank ``proc`` —
        the source identity reported for cross-process ANY_SOURCE receives
        (a single-controller process has no finer sender identity on the
        eager plane)."""
        for slot in range(self.size):
            if self._root_process(slot) == proc:
                return slot
        raise RuntimeError(f"no mesh slot owned by process {proc}")

    def probe(self, source: int, tag: int = 0) -> bool:
        """Non-blocking pending-message check (reference parity:
        ``MPI_Iprobe`` via mpi4py on the eager transport).

        Same-process slots and already-buffered cross-process messages
        match ``(source, tag)`` exactly. A cross-process SOCKET probe is
        tag-agnostic (the wire is a per-pair FIFO; the tag is read with
        the message), so ``probe(src, tag) == True`` guarantees a message
        from ``src`` is pending but not its tag — the matching ``recv``
        buffers any other-tag arrivals rather than losing them, and
        blocks until the wanted tag arrives. ``source=ANY_SOURCE`` checks
        all peers.

        Ordering constraint (differs from full MPI matching): host-plane
        COLLECTIVES (barrier, bcast_obj, ...) share the per-pair p2p
        channels, so wildcard probes/receives must not run concurrently
        with other ranks' collectives — sequence all p2p before entering
        a collective."""
        def _pending_remote_tag():
            return any(t == tag and dq for (_, t), dq
                       in self._pending_remote.items())

        if source == ANY_SOURCE:
            if any(t == tag and dq
                   for (_, t), dq in self._self_p2p.items()):
                return True
            if _pending_remote_tag():
                return True
            return self.host.size > 1 and any(
                self.host.probe(p)
                for p in range(self.host.size) if p != self.host.rank
            )
        src_proc = self._root_process(source)
        if src_proc == self.host.rank:
            return bool(self._self_p2p.get((source, tag)))
        if self._pending_remote.get((src_proc, tag)):
            return True
        return self.host.probe(src_proc)

    def recv_any_obj(self, tag: int = 0, *,
                     poll_interval: float = 1e-3) -> tuple[int, Any]:
        """Blocking receive from ANY source (reference parity:
        ``recv(source=MPI.ANY_SOURCE)``); returns ``(source, obj)``.
        Same-process mailboxes are served first, then already-buffered
        cross-process messages, then the peer sockets round-robin
        (other-tag arrivals are buffered for their own receivers, never
        dropped). The reported source for a cross-process message is the
        sending process's lowest-numbered mesh slot."""
        import time as _time

        while True:
            for (slot, t), dq in list(self._self_p2p.items()):
                if t == tag and dq:
                    return slot, dq.popleft()
            for (proc, t), dq in list(self._pending_remote.items()):
                if t == tag and dq:
                    return self._slot_of_process(proc), dq.popleft()
            if self.host.size == 1:
                raise RuntimeError(
                    "recv_any_obj with no buffered self-send and no other "
                    "process — nothing can ever arrive"
                )
            progressed = False
            for proc in range(self.host.size):
                if proc == self.host.rank:
                    continue
                if self.host.probe(proc):
                    got_tag, obj = self.host.recv_obj(proc)
                    if got_tag == tag:
                        return self._slot_of_process(proc), obj
                    self._pending_remote[(proc, got_tag)].append(obj)
                    progressed = True
            if not progressed:
                _time.sleep(poll_interval)

    def barrier(self) -> None:
        self.host.barrier()

    # ------------------------------------------------------------------
    # Sub-communicators (reference: ``split()`` via MPI_Comm_split)
    # ------------------------------------------------------------------

    def split(self, color: int, key: int = 0) -> "CommunicatorBase":
        """Group *processes* by ``color`` into sub-communicators (reference:
        ``split()`` via ``MPI_Comm_split``). Single-process: returns self
        (there is nothing to split at host granularity; use
        :meth:`sub_communicator` to subset the mesh).

        Multihost: requires the native TCP host backend (per-pair channels
        serve independent groups; ``multihost_utils`` collectives are
        world-global and would deadlock). The returned communicator's host
        plane is the color group and its mesh covers the group processes'
        devices, so both ``*_obj`` collectives and eager array collectives
        run group-locally."""
        if self.host.size == 1:
            return self
        sub_host = self.host.split(color, key)
        members = sub_host.world_members  # world process ids, group order
        by_pid: dict[int, list] = {}
        for d in self.mesh.devices.flat:
            by_pid.setdefault(d.process_index, []).append(d)
        devices = [d for pid in members for d in by_pid.get(pid, [])]
        sub_mesh = Mesh(np.array(devices).reshape(len(devices)), (self.axis_name,))
        return _SplitCommunicator(
            sub_mesh, _host=sub_host,
            allreduce_grad_dtype=self.allreduce_grad_dtype,
        )

    def sub_communicator(self, device_indices: Sequence[int]) -> "CommunicatorBase":
        """Device-plane split: a communicator over a subset of mesh slots
        (flat indices). This is how single-controller SPMD expresses the
        reference's ``split`` in tests."""
        flat = list(self.mesh.devices.flat)
        devices = [flat[i] for i in device_indices]
        sub_mesh = Mesh(np.array(devices).reshape(len(devices)), (self.axis_name,))
        return CommunicatorBase(sub_mesh, allreduce_grad_dtype=self.allreduce_grad_dtype)

    def __repr__(self) -> str:
        return (
            f"<{type(self).__name__} name={self.name!r} size={self.size} "
            f"axes={dict(self.mesh.shape)} processes={self.host.size}>"
        )


class _SplitCommunicator(CommunicatorBase):
    """Communicator over one color group of a multihost :meth:`split`.

    ``rank``/``size`` are group-relative (MPI parity: the communicator you
    get back from ``MPI_Comm_split`` renumbers you); the host plane is the
    subgroup TCP comm and the mesh holds only group processes' devices."""

    name = "split"

    @property
    def rank(self) -> int:  # group rank, not world process index
        return self.host.rank
