"""Concrete communicators — the TPU-native counterparts of the reference's
communicator zoo (``chainermn/communicators/*.py`` (dagger), SURVEY.md
section 2.1).

On GPU the zoo existed because the composition of transports (NCCL vs MPI,
CUDA-aware or not, intra- vs inter-node) was the user's problem. On TPU, XLA
owns transport selection: every communicator here lowers to the same XLA
collectives, and the subclasses differ only in *mesh topology* (flat vs
hierarchical factorisation) and device selection. The historical names are
kept as registry aliases so reference users find what they expect
(``create_communicator('pure_nccl')`` still works and does the right thing).
"""

from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from chainermn_tpu.communicators.base import CommunicatorBase
from chainermn_tpu.parallel.mesh import make_mesh

PyTree = Any


class XlaCommunicator(CommunicatorBase):
    """The production communicator: one flat ``('data',)`` axis over every
    device in the pod slice; gradient allreduce lowers to a single
    ``lax.psum`` over ICI (+DCN when multi-slice). Plays the role of
    ``PureNcclCommunicator`` (``pure_nccl_communicator.py`` (dagger)) — the
    communicator the reference's benchmarks name."""

    name = "xla"

    def __init__(
        self,
        *,
        mesh: Mesh | None = None,
        devices: Sequence[jax.Device] | None = None,
        axis_name: str = "data",
        allreduce_grad_dtype=None,
    ) -> None:
        if mesh is None:
            mesh = make_mesh((axis_name,), devices=devices)
        super().__init__(mesh, allreduce_grad_dtype=allreduce_grad_dtype)


class NaiveCommunicator(XlaCommunicator):
    """CPU-mesh communicator for tests/CI — the role of the reference's
    ``NaiveCommunicator`` (``naive_communicator.py`` (dagger)): works with no
    accelerator at all. Uses the host-platform XLA backend, which honours
    ``--xla_force_host_platform_device_count`` for multi-"rank" testing
    (SURVEY.md section 4)."""

    name = "naive"

    def __init__(self, **kwargs) -> None:
        if kwargs.get("mesh") is None and kwargs.get("devices") is None:
            kwargs["devices"] = jax.devices("cpu")
        super().__init__(**kwargs)


class HierarchicalCommunicator(CommunicatorBase):
    """Two-level ``('inter', 'intra')`` mesh: ``inter`` spans processes
    (DCN), ``intra`` spans each process's local devices (ICI). Gradient
    reduction over both axes reproduces — declaratively — the reference's
    intra-node-NCCL-then-inter-node-MPI pipeline
    (``hierarchical_communicator.py`` (dagger),
    ``two_dimensional_communicator.py`` (dagger)): XLA emits the
    topology-aware 2-level collective itself."""

    name = "hierarchical"

    def __init__(
        self,
        *,
        mesh: Mesh | None = None,
        devices: Sequence[jax.Device] | None = None,
        allreduce_grad_dtype=None,
    ) -> None:
        if mesh is None:
            if devices is None:
                devices = jax.devices()
            devices = list(devices)
            n_proc = jax.process_count()
            per_proc = len(devices) // max(n_proc, 1)
            if n_proc > 1 and per_proc * n_proc == len(devices):
                ordered = sorted(devices, key=lambda d: (d.process_index, d.id))
                arr = np.array(ordered).reshape(n_proc, per_proc)
            else:
                # Single process: degenerate inter axis (the same degeneracy
                # the reference's single-host MPI tests exercised —
                # ``inter_size == 1``, SURVEY.md section 4).
                arr = np.array(devices).reshape(1, len(devices))
            mesh = Mesh(arr, ("inter", "intra"))
        super().__init__(mesh, allreduce_grad_dtype=allreduce_grad_dtype)

    @property
    def axis_name(self) -> str:  # primary axis for data parallelism
        return "inter"


class TwoDimensionalCommunicator(HierarchicalCommunicator):
    """Hierarchical mesh with the EXPLICIT bandwidth-optimal reduction: the
    gradient pipeline is intra ``psum_scatter`` → inter allreduce of the
    1/n shard → intra ``all_gather``, pinned in the program rather than
    left to XLA's schedule derivation — the reference's
    ``TwoDimensionalCommunicator`` algorithm
    (``two_dimensional_communicator.py`` (dagger): intra
    ``ncclReduceScatter`` → inter MPI allreduce → intra ``ncclAllGather``).
    Numerically identical to the hierarchical pmean (tested)."""

    name = "two_dimensional"

    def __init__(self, **kwargs) -> None:
        super().__init__(**kwargs)
        if len(self.grad_axes) != 2:
            raise ValueError(
                "two_dimensional requires a 2-axis (inter, intra) mesh; "
                f"got grad_axes={self.grad_axes!r} from mesh axes "
                f"{tuple(self.mesh.axis_names)!r}"
            )

    @functools.cached_property
    def bucket_bytes(self) -> int:
        """Gradient-pack bucket size (autotuned, resolved once per
        communicator so the pipeline's layout is stable for the
        process lifetime). The resolution's provenance is kept for the
        observability layer's pack events."""
        from chainermn_tpu.communicators.base import _latest_decision
        from chainermn_tpu.parallel.collectives import tuned_bucket_bytes

        out = tuned_bucket_bytes(self.device_kind, self.size)
        self._bucket_provenance = _latest_decision("allreduce_bucket_mb")
        return out

    @property
    def two_level_axes(self):
        """``(intra_axis, inter_axis)`` names of the pinned two-level
        reduction — the capability flag the shard-level EF path keys on
        (``MultiNodeOptimizer._reduce_with_feedback``): quantization
        happens only at the inter stage here, so the EF residual is
        kept at shard shape and fed back exactly where the error
        arises."""
        inter_ax, intra_ax = self.grad_axes
        return intra_ax, inter_ax

    def reduce_gradients_in_jit(
        self, grads: PyTree, *, compress_dtype=None
    ) -> PyTree:
        """The pinned two-level pipeline, via the SHARED schedule layer
        (:func:`chainermn_tpu.parallel.reduction_schedule.reduce_tree`,
        ``schedule='two_level'``): the whole gradient tree packs into
        ~``bucket_bytes`` flat buffers per dtype group (the reference's
        ``_memory_utility.pack_params`` (dagger) discipline, in-jit so
        XLA owns the copies — per-leaf collectives would leave the slow
        inter/DCN level latency-bound on tiny bias/scale leaves), and
        each bucket crosses as intra ``psum_scatter`` -> inter allreduce
        of the shard -> intra ``all_gather``. An int8 compress dtype
        selects the quantized wire at the ONLY stage where compression
        pays — the shard crossing inter/DCN — with the intra reduction
        exact. Trace-time events record the layout: one ``pack`` with
        the bucket decision's provenance, one ``wire`` per bucket per
        stage."""
        from chainermn_tpu.parallel.collectives import axes_bound
        from chainermn_tpu.parallel.reduction_schedule import reduce_tree

        if compress_dtype is None:
            compress_dtype = self.allreduce_grad_dtype
        # Probe ONLY the axis-context question (unbound axis = auto-SPMD
        # jit / single-device eager) — a genuine error inside the
        # pipeline must propagate, not silently degrade to the fused
        # pmean fallback (numerically identical, nothing would notice).
        inter_ax, intra_ax = self.grad_axes
        if not axes_bound((intra_ax, inter_ax)):
            return super().reduce_gradients_in_jit(
                grads, compress_dtype=compress_dtype
            )
        bucket_bytes = self.bucket_bytes  # resolves provenance too
        return reduce_tree(
            grads,
            schedule="two_level",
            axes=self.grad_axes,
            compress_dtype=compress_dtype,
            bucket_bytes=bucket_bytes,
            provenance=getattr(self, "_bucket_provenance", None),
            op="two_level_allreduce",
            size=self.size,
        )


class SingleNodeCommunicator(XlaCommunicator):
    """Asserts a single process — reference ``single_node_communicator.py``
    (dagger) asserted ``inter_size == 1`` (NCCL-only, one node)."""

    name = "single_node"

    def __init__(self, **kwargs) -> None:
        if jax.process_count() != 1:
            raise ValueError(
                "SingleNodeCommunicator requires a single-process runtime "
                "(reference parity: inter_size == 1)"
            )
        super().__init__(**kwargs)
