"""Device-mesh construction and pod-slice topology discovery.

TPU-native replacement for the reference's rank-topology bootstrap
(``chainermn/communicators/_communication_utility.py`` (dagger):
``init_ranks`` / ``init_intra_mpi_comm`` / ``init_inter_mpi_comm`` /
``init_nccl_comm``, SURVEY.md section 2.1). There, intra/inter-node rank
discovery ran ``MPI_Comm_split_type(SHARED)`` and NCCL rings were initialised
by broadcasting a unique id over MPI. Here the JAX runtime already knows the
slice topology: ``jax.devices()`` carries coords, ``jax.process_index()``
plays the role of the MPI rank, and collective routing over ICI vs DCN is
decided by XLA from the mesh axes. ``intra``/``inter`` axes of the reference's
hierarchical communicators map onto a factorised ``(dcn, ici)`` mesh
(SURVEY.md section 5, "Distributed communication backend").
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh


def best_mesh_shape(n: int, ndims: int = 2) -> tuple[int, ...]:
    """Factor ``n`` devices into an ``ndims``-dim balanced mesh shape.

    Most balanced factorisation, larger factors first: minimises the
    largest factor, then the next-largest, and so on (lexicographic on the
    descending-sorted tuple). E.g. 8 -> (4, 2), 16 -> (4, 4), 6 -> (3, 2),
    primes -> (n, 1); 8 over 3 dims -> (2, 2, 2), 16 over 3 -> (4, 2, 2),
    24 over 4 -> (3, 2, 2, 2). A 3-axis ``data x model x zero``
    :class:`~chainermn_tpu.parallel.plan.ParallelPlan` relies on this for
    its auto-factorised mesh (the largest factor lands on the first —
    DCN-most — axis).
    """
    if ndims < 1:
        raise ValueError(f"ndims must be >= 1, got {ndims}")
    if n < 1:
        raise ValueError(f"need a positive device count, got {n}")
    if ndims == 1:
        return (n,)

    def factorisations(m: int, k: int):
        if k == 1:
            yield (m,)
            return
        for d in range(1, m + 1):
            if m % d == 0:
                for rest in factorisations(m // d, k - 1):
                    yield tuple(sorted((d,) + rest, reverse=True))

    # min() over descending-sorted tuples = smallest largest factor,
    # ties broken by the next factor — the balanced choice.
    return min(set(factorisations(n, ndims)))


def _device_array(devices: Sequence[jax.Device], shape: tuple[int, ...]) -> np.ndarray:
    """Arrange devices into ``shape``, ICI-topology-aware on a TPU.

    ``mesh_utils.create_device_mesh`` understands TPU coords and lays the mesh
    out so that neighbouring mesh indices are ICI neighbours; a shape it
    cannot lay out on the physical topology raises. CPU test meshes have no
    topology to exploit and get a plain reshape.
    """
    devices = list(devices)
    if devices[0].platform == "tpu":
        return mesh_utils.create_device_mesh(shape, devices=devices)
    return np.array(devices).reshape(shape)


def make_mesh(
    axis_names: Sequence[str] = ("data",),
    shape: Sequence[int] | None = None,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Create a :class:`jax.sharding.Mesh` over ``devices``.

    Args:
      axis_names: mesh axis names, e.g. ``('data',)`` or ``('data', 'model')``.
      shape: per-axis sizes; if ``None``, all devices go on the first axis and
        remaining axes get size 1 (or a balanced 2-d factorisation if exactly
        two axes are requested with no shape).
      devices: device list; defaults to ``jax.devices()``.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    n = len(devices)
    axis_names = tuple(axis_names)
    if shape is None:
        if len(axis_names) == 1:
            shape = (n,)
        else:
            shape = best_mesh_shape(n, 2) + (1,) * (len(axis_names) - 2)
    shape = tuple(shape)
    if math.prod(shape) != n:
        raise ValueError(
            f"mesh shape {shape} does not cover {n} devices; "
            f"pass an explicit `devices` list or fix `shape`"
        )
    return Mesh(_device_array(devices, shape), axis_names)


@dataclasses.dataclass(frozen=True)
class MeshTopology:
    """Rank-topology view of a mesh, mirroring the reference communicator's
    ``rank/size/intra_rank/inter_rank/inter_size`` surface
    (``communicator_base.py`` (dagger) properties, SURVEY.md section 2.1).

    On TPU the "node" boundary of the reference (NVLink island / MPI host)
    maps to the *process* boundary: devices local to this process are the
    intra group (ICI-attached, addressable without DCN), processes are the
    inter group. For a single-process CPU/test mesh every device is intra.
    """

    mesh: Mesh
    #: Optional provider of the hostname-discovered ``(intra_rank,
    #: processes_on_this_host)`` pair, or ``None`` from the provider when
    #: the runtime is single-process (then the device-count semantics
    #: below apply). Communicators install their lazy host-plane
    #: discovery here so the intra pair is truthful AND internally
    #: consistent (``0 <= intra_rank < intra_size``) on
    #: multi-process-per-host runtimes. CAUTION: with a provider
    #: installed, the FIRST ``intra_rank``/``intra_size`` access on a
    #: multi-process runtime is a blocking host-plane collective — read
    #: it on every process or not at all (same discipline as
    #: ``CommunicatorBase.intra_rank``, where this is documented).
    host_intra_provider: "object" = dataclasses.field(
        default=None, compare=False
    )

    def _host_intra(self):
        if self.host_intra_provider is None:
            return None
        return self.host_intra_provider()

    @property
    def size(self) -> int:
        """Total number of devices in the mesh (the reference's world size —
        one process per GPU there, one mesh slot per chip here)."""
        return self.mesh.devices.size

    @property
    def rank(self) -> int:
        """Host-plane rank: ``jax.process_index()``."""
        return jax.process_index()

    @property
    def inter_size(self) -> int:
        """Number of processes (the reference's number of nodes)."""
        return jax.process_count()

    @property
    def inter_rank(self) -> int:
        return jax.process_index()

    @property
    def intra_size(self) -> int:
        """Multi-process (provider present and reporting): processes
        sharing this host — keeps ``0 <= intra_rank < intra_size``
        coherent. Otherwise: devices managed by this process (the
        reference's GPUs per node, single-controller reading)."""
        pair = self._host_intra()
        if pair is not None:
            return pair[1]
        return jax.local_device_count()

    @property
    def intra_rank(self) -> int:
        """Index of this process among the processes sharing its host.

        When a communicator owns this topology, the value comes from its
        hostname-discovery collective (``host_intra_provider`` — the
        reference's ``init_ranks`` hostname exchange; see the provider
        field's collective-access caveat). Standalone (no provider): 0,
        the one-process-per-host JAX norm — JAX itself exposes no
        host-local process index.
        """
        pair = self._host_intra()
        if pair is not None:
            return pair[0]
        return 0

    @property
    def axis_names(self) -> tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    def axis_size(self, axis_name: str) -> int:
        return self.mesh.shape[axis_name]
