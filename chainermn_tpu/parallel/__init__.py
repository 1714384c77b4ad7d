"""Mesh topology and in-program collective primitives.

This package is the TPU-native replacement for the reference's L0-L2 layers
(native NCCL binding + ``_communication_utility.py`` (dagger) +
``_memory_utility.py`` (dagger), see SURVEY.md section 1): instead of
bootstrapping NCCL rings over MPI and packing gradients into flat device
buffers by hand, we build a ``jax.sharding.Mesh`` over the pod slice and let
XLA lower named-axis collectives onto ICI/DCN. Flat-buffer packing is
deliberately absent — XLA fuses the pack/cast/scale/unpack pipeline that the
reference implemented manually (SURVEY.md section 3.2 TPU mapping).
"""

from chainermn_tpu.parallel.mesh import (
    MeshTopology,
    make_mesh,
    best_mesh_shape,
)
from chainermn_tpu.parallel import collectives


def __getattr__(name):
    # Lazy: ring_attention/ulysses import ops (attention locals), which must
    # not load during communicator bootstrap.
    if name in ("ring_attention_local", "make_ring_attention"):
        from chainermn_tpu.parallel import ring_attention as _ra

        return getattr(_ra, name)
    if name == "sliding_window_attention_local":
        from chainermn_tpu.parallel import local_attention as _la

        return getattr(_la, name)
    if name in ("ulysses_attention_local", "make_ulysses_attention"):
        from chainermn_tpu.parallel import ulysses as _ul

        return getattr(_ul, name)
    if name in (
        "pipeline_local", "make_pipeline", "stack_stage_params",
        "stack_interleaved_stage_params", "pipeline_total_ticks",
        "pipeline_1f1b_local", "make_pipeline_1f1b",
        "pipeline_hetero_local", "make_pipeline_hetero", "pipe_plan_axis",
        "unscale_replicated_grads",
    ):
        from chainermn_tpu.parallel import pipeline as _pp

        return getattr(_pp, name)
    if name in ("zero_shard_optimizer", "zero_state_specs",
                "zero_plan_axis", "zero_stacked_init", "zero_grad_scatter",
                "zero_param_chunk", "zero_gather_updates"):
        from chainermn_tpu.parallel import zero as _z

        return getattr(_z, name)
    if name in ("ParallelPlan", "PipelinePlanSpec"):
        from chainermn_tpu.parallel import plan as _plan

        return getattr(_plan, name)
    if name in ("AxisSpec", "CANONICAL_AXES"):
        from chainermn_tpu.parallel import plan_specs as _pspec

        return getattr(_pspec, name)
    if name in ("reduce_tree", "bucket_partition",
                "OverlappedBucketReducer", "SCHEDULES"):
        from chainermn_tpu.parallel import reduction_schedule as _rs

        return getattr(_rs, name)
    if name in ("moe_layer_local", "top1_route", "topk_route",
                "load_balancing_loss", "make_expert_params",
                "moe_capacity", "routing_stats",
                "record_moe_dispatch", "resolve_expert_parallel"):
        from chainermn_tpu.parallel import moe as _m

        return getattr(_m, name)
    if name == "moe_plan_axis":
        from chainermn_tpu.parallel import plan_specs as _pspec

        return getattr(_pspec, name)
    if name in (
        "fsdp_shardings", "create_fsdp_train_state", "make_fsdp_train_step"
    ):
        from chainermn_tpu.parallel import fsdp as _f

        return getattr(_f, name)
    if name in (
        "copy_to_tp", "reduce_from_tp", "gather_from_tp", "tp_slice", "stack_tp_params",
        "column_parallel_dense", "row_parallel_dense", "tp_mlp",
        "tp_attention", "shard_qkv_columns", "tp_plan_axis",
    ):
        from chainermn_tpu.parallel import tensor as _t

        return getattr(_t, name)
    raise AttributeError(name)


__all__ = [
    "MeshTopology",
    "make_mesh",
    "best_mesh_shape",
    "collectives",
    "ring_attention_local",
    "make_ring_attention",
    "sliding_window_attention_local",
    "ulysses_attention_local",
    "make_ulysses_attention",
    "pipeline_local",
    "make_pipeline",
    "stack_interleaved_stage_params",
    "pipeline_total_ticks",
    "stack_stage_params",
    "pipeline_1f1b_local",
    "make_pipeline_1f1b",
    "pipeline_hetero_local",
    "make_pipeline_hetero",
    "zero_shard_optimizer",
    "zero_state_specs",
    "zero_plan_axis",
    "zero_stacked_init",
    "zero_grad_scatter",
    "zero_param_chunk",
    "zero_gather_updates",
    "ParallelPlan",
    "PipelinePlanSpec",
    "AxisSpec",
    "CANONICAL_AXES",
    "reduce_tree",
    "bucket_partition",
    "OverlappedBucketReducer",
    "SCHEDULES",
    "moe_layer_local",
    "top1_route",
    "topk_route",
    "load_balancing_loss",
    "make_expert_params",
    "moe_capacity",
    "routing_stats",
    "record_moe_dispatch",
    "resolve_expert_parallel",
    "moe_plan_axis",
    "fsdp_shardings",
    "create_fsdp_train_state",
    "make_fsdp_train_step",
    "copy_to_tp",
    "reduce_from_tp",
    "gather_from_tp",
    "tp_slice",
    "stack_tp_params",
    "column_parallel_dense",
    "row_parallel_dense",
    "tp_mlp",
    "tp_attention",
    "tp_plan_axis",
    "pipe_plan_axis",
]
