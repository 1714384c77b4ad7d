"""The HLO categoriser on a hand-written module in the TPU compiler's
style (names after the op's source, tuple shapes, fusions by what they
call, a while body, an asynchronous pair)."""

import hlo

TEXT = """HloModule jit_local_step, is_scheduled=true, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%fused_computation.1 (param_0: bf16[8,8], param_1: bf16[8,8]) -> bf16[8,8] {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  %param_1 = bf16[8,8]{1,0} parameter(1)
  ROOT %convolution.3 = bf16[8,8]{1,0} convolution(%param_0, %param_1), dim_labels=bf_io->bf
}

%fused_computation.2 (param_0.1: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  ROOT %multiply.1 = f32[8,8]{1,0} multiply(%param_0.1, %param_0.1)
}

%region_1.2 (a: bf16[], b: bf16[]) -> bf16[] {
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %add.9 = bf16[] add(%a, %b)
}

%body.7 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte.1 = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %dot.4 = f32[8,8]{1,0} dot(%gte.1, %gte.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %tuple.2 = (s32[], f32[8,8]{1,0}) tuple(%gte.1, %dot.4)
}

ENTRY %main.10 (Arg_0.1: f32[8,8]) -> f32[8,8] {
  %Arg_0.1 = f32[8,8]{1,0} parameter(0)
  %fusion.1 = bf16[8,8]{1,0:T(8,128)(2,1)} fusion(%Arg_0.1, %Arg_0.1), kind=kOutput, calls=%fused_computation.1
  %fusion.2 = f32[8,8]{1,0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(local_step)/shard_map/jvp(TransformerLM)/block_3/mul" stack_frame_id=7}
  %block_0.3 = (bf16[4,16,1024,64]{3,2,1,0}, f32[4,16,1024,1]{3,2,1,0}) custom-call(%fusion.1), custom_call_target="tpu_custom_call", backend_config={"x":"(y)"}
  %psum.5 = (bf16[8,8]{1,0}, bf16[8,8]{1,0}) all-reduce(%fusion.1, %fusion.1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_1.2
  %all-reduce-start.1 = bf16[8,8]{1,0} all-reduce-start(%fusion.1), replica_groups={{0,1,2,3}}, to_apply=%region_1.2
  %all-reduce-done.1 = bf16[8,8]{1,0} all-reduce-done(%all-reduce-start.1)
  %cholesky.1 = f32[8,8]{1,0} custom-call(%Arg_0.1), custom_call_target="Cholesky"
  %while.1 = (s32[], f32[8,8]{1,0}) while(%tuple.9), condition=%cond.1, body=%body.7
  ROOT %copy.1 = f32[8,8]{1,0} copy(%fusion.2)
}
"""


def test_categories():
    cats = hlo.categorize(TEXT)
    assert cats["fusion.1"] == hlo.MATMUL      # calls a convolution
    assert cats["fusion.2"] == hlo.OTHER
    assert cats["dot.4"] == hlo.MATMUL         # inside a while body
    assert cats["block_0.3"] == hlo.MOSAIC     # tuple-shaped Mosaic call
    assert cats["cholesky.1"] == hlo.OTHER     # another custom call
    assert cats["psum.5"] == hlo.COLLECTIVE    # named after its source
    assert cats["all-reduce-start.1"] == hlo.COLLECTIVE
    assert cats["all-reduce-done.1"] == hlo.COLLECTIVE
    assert cats["while.1"] == hlo.OTHER and cats["copy.1"] == hlo.OTHER


def test_op_names_say_where_an_op_came_from():
    assert hlo.op_names(TEXT) == {
        "fusion.2": "shard_map/jvp(TransformerLM)/block_3/mul"}


def test_counts_and_module_name():
    assert hlo.all_reduce_count(TEXT) == 2  # the pair counts once
    assert hlo.mosaic_call_count(TEXT) == 1
    assert hlo.module_name(TEXT) == "jit_local_step"
