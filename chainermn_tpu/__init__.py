"""chainermn_tpu — a TPU-native distributed training framework.

A brand-new framework with the capabilities of ChainerMN (reference:
``shu65/chainermn``), re-designed for TPU: a single jitted SPMD program over a
``jax.sharding.Mesh`` (ICI x DCN), XLA collectives instead of MPI+NCCL, and
Pallas kernels for the hot fused ops.

Public API (mirrors the reference package surface, see SURVEY.md section 2):

- :func:`create_communicator` — communicator factory
  (``chainermn/communicators/__init__.py`` (dagger) in the reference).
- :func:`create_multi_node_optimizer` — data-parallel optimizer wrapper
  (``chainermn/optimizers.py`` (dagger)).
- :func:`scatter_dataset`, :func:`create_empty_dataset` — data layer
  (``chainermn/datasets/`` (dagger)).
- :mod:`chainermn_tpu.functions` — differentiable cross-rank send/recv and
  collective functions (``chainermn/functions/`` (dagger)).
- :mod:`chainermn_tpu.links` — ``MultiNodeChainList``,
  ``MultiNodeBatchNormalization``, ``create_mnbn_model``
  (``chainermn/links/`` (dagger)).
- :mod:`chainermn_tpu.extensions` — multi-node evaluator, fault-tolerant
  checkpointer (npz + orbax backends) (``chainermn/extensions/`` (dagger)).
- :mod:`chainermn_tpu.parallel` — the TPU-era parallelism library the
  reference lacked: tensor/pipeline (GPipe + 1F1B)/sequence/expert
  parallelism, ZeRO, FSDP (see ``docs/parallelism.md``).
- :mod:`chainermn_tpu.training` — jitted train-step builder (gradient
  accumulation, device prefetch) and the Trainer loop.
- :mod:`chainermn_tpu.testing` — downstream test harness helpers (the
  ``mpiexec -n N pytest`` recipe, TPU-style).

The dagger convention follows SURVEY.md: the reference mount was empty at
survey time, so citations are to the public upstream layout.
"""

from chainermn_tpu.communicators import create_communicator
from chainermn_tpu.communicators.base import ANY_SOURCE, CommunicatorBase
from chainermn_tpu.optimizers import (
    create_local_sgd,
    create_multi_node_optimizer,
)
from chainermn_tpu.datasets import scatter_dataset, create_empty_dataset
from chainermn_tpu.iterators import (
    create_multi_node_iterator,
    create_synchronized_iterator,
)
from chainermn_tpu.extensions.evaluator import create_multi_node_evaluator
from chainermn_tpu.extensions.checkpoint import create_multi_node_checkpointer
from chainermn_tpu import global_except_hook  # noqa: F401  (import installs nothing)

__version__ = "0.5.0"

__all__ = [
    "create_communicator",
    "ANY_SOURCE",
    "CommunicatorBase",
    "create_local_sgd",
    "create_multi_node_optimizer",
    "scatter_dataset",
    "create_empty_dataset",
    "create_multi_node_iterator",
    "create_synchronized_iterator",
    "create_multi_node_evaluator",
    "create_multi_node_checkpointer",
]
