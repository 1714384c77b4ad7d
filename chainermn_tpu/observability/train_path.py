"""The train path's names: every device scope, host span and counter the
program puts on a training step and its set-up, spelled once
(docs/observability.md, "Training on the device").

Device scopes are ``jax.named_scope`` names. They reach the compiled
step as ``op_name`` metadata only: the program XLA compiles is the same
with and without them. JAX wraps a scope in its own markers when it
differentiates or rematerialises the code inside
(``transpose(jvp(lm_head))``, ``checkpoint/rematted_computation``), so a
reader looks for a name anywhere in an ``op_name`` and tells forward,
backward and recomputation apart by :data:`BACKWARD_MARKER` and
:data:`REMAT_MARKER`.

Host spans go through :func:`chainermn_tpu.observability.trace.span`:
into the profiler's own trace whenever a ``jax.profiler`` session is
live, and into the JSONL when a recorder is on.

Counters live in the :mod:`~chainermn_tpu.observability.metrics`
registry and are written off the step's path: while the step is traced,
when a program compiles, or by a scrape-time hook.
"""

from __future__ import annotations

# -- device scopes ------------------------------------------------------
#: round ``value_and_grad`` of the loss: forward, backward, recomputation
LOSS_AND_GRAD = "loss_and_grad"
#: casts to and from the wire, packing, scaling and the collectives
GRAD_REDUCE = "grad_reduce"
#: the inner optimizer's sweep and ``optax.apply_updates``
OPTIMIZER_UPDATE = "optimizer_update"
#: the language-model head, fused (chunked loop) or not
LM_HEAD = "lm_head"
#: the three flash-attention kernels; also each ``pallas_call``'s ``name``
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"
#: a dropless mixture-of-experts layer, in the order a token meets them:
#: router matmul, softmax, top-k and the auxiliary losses; the sort by
#: expert, the group sizes and the gather into expert order; the grouped
#: matmuls and the SiLU gate (also each grouped-matmul kernel's ``name``);
#: the weighted sum back into token order
MOE_ROUTE = "moe_route"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_COMBINE = "moe_combine"
#: a gated short convolution's mixer between its two projections: the
#: split of the input projection, the two gates and the depthwise causal
#: convolution (the projections themselves are matmuls outside it): the
#: two kernels of ``ops/short_conv.py`` (their ``name``s hold the scope's),
#: or the plain spelling where the shape does not tile
SHORT_CONV = "short_conv"
#: a looped model: round the passes of the layer stack over one set of
#: weights (sub-scope :func:`pass_scope` round each, final norm included);
#: round the exit gate's product, the exit distribution, its entropy and
#: the weighting of the exits
LOOP_STACK = "loop_stack"
EXIT_GATE = "exit_gate"
#: a block-diffusion model's training pass: round the draw of a noise
#: level a block and a mask a token, the noised copy, the concatenation
#: ``[x ; x~]`` and its positions; round the masked attention over the
#: two copies (the flash kernels' clean-on-clean, noised-on-clean and
#: in-block calls and the merge by log-sum-exps)
BD_NOISE = "bd_noise"
BD_ATTENTION = "bd_attention"
#: a latent-attention mixer whole, between the block's norm and the
#: residual: the four projections (queries, the down-projection to the
#: latent and the rope key, the up-projection to keys and values, the
#: output), the latent's norm, RoPE, the rope key's broadcast to the heads
#: and the three flash kernels, each under its own scope inside this one
MLA_ATTENTION = "mla_attention"
#: the shared expert beside the routed ones: its two matmuls (gate|up as
#: one, down) and the SiLU gate
MOE_SHARED = "moe_shared"

#: how JAX marks the transposed (backward) and the recomputed code of a
#: scope in ``op_name``
BACKWARD_MARKER = "transpose("
REMAT_MARKER = "rematted_computation"

# -- checkpoint names -----------------------------------------------------
#: ``jax.ad_checkpoint.checkpoint_name`` tags on what the flash forward
#: kernel made and its backward takes: the attention output as the kernel
#: leaves it (the projections' own ``[B, T, H*D]`` rows; ``[B*H, T, D]``
#: where the op's wrapper transposed) and the log-sum-exp of a row's
#: scores (``[B, H, 1, T]``: ``T`` on the lane axis, 4 bytes a value).
#: A name is an identity outside ``jax.checkpoint``;
#: a remat policy that saves these names keeps the kernel's results, and
#: the recomputation no longer calls the kernel.
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"
FLASH_RESIDUALS = (FLASH_OUT, FLASH_LSE)


def pass_scope(index: int) -> str:
    """Sub-scope of :data:`LOOP_STACK` for one pass (they are unrolled)."""
    return f"pass{index}"


def bucket_scope(index: int) -> str:
    """Sub-scope of :data:`GRAD_REDUCE` for one packed bucket."""
    return f"bucket{index}"


# -- host spans ---------------------------------------------------------
FEED_NEXT = "chainermn.feed.next"
FEED_PUT = "chainermn.feed.put"
#: ``jax.profiler.StepTraceAnnotation`` name of a ``Trainer`` iteration
TRAINER_STEP = "train"
TRAINER_DATA_WAIT = "chainermn.trainer.data_wait"
TRAINER_H2D = "chainermn.trainer.h2d"
TRAINER_LOG = "chainermn.trainer.log"

# -- counters -----------------------------------------------------------
#: the compile counters, each a series by ``program``: the name JAX gives
#: the jitted function, the same through tracing, lowering and the
#: backend. A function traced inside another's trace counts under the
#: outermost; past ``utils.compile_cache.MAX_PROGRAMS`` names a process
#: files what comes later, and what JAX gave no name, under
#: :data:`OTHER_PROGRAM`
JAX_TRACE_SECONDS = "jax_trace_seconds_total"
JAX_LOWER_SECONDS = "jax_lower_seconds_total"
JAX_BACKEND_COMPILE_SECONDS = "jax_backend_compile_seconds_total"
PROGRAMS_COMPILED = "programs_compiled_total"
COMPILE_CACHE_HITS = "compile_cache_hits_total"
COMPILE_CACHE_MISSES = "compile_cache_misses_total"
COMPILE_CACHE_RETRIEVAL_SECONDS = "compile_cache_retrieval_seconds_total"
OTHER_PROGRAM = "other"
#: the ``program`` of ``training.make_train_step``'s step: the name of
#: the function it shards and jits (``shard_map`` keeps it), which is
#: also in the compiled module's name and so in the cache's key
TRAIN_STEP_PROGRAM = "local_step"
#: what the host made the process wait and what it gave it, read from
#: the kernel when the registry is: seconds its threads stood runnable
#: with no core to run on, and its CPU seconds (user and system)
PROCESS_RUNQUEUE_WAIT_SECONDS = "process_runqueue_wait_seconds_total"
PROCESS_CPU_SECONDS = "process_cpu_seconds_total"
GRAD_WIRE_BYTES = "grad_wire_bytes_per_step"
GRAD_REDUCE_BUCKETS = "grad_reduce_buckets"
FEED_BATCHES = "feed_batches_total"
FEED_BYTES = "feed_bytes_total"
FEED_NOT_READY = "feed_not_ready_total"
#: gauge, labels ``kernel`` (a flash kernel's name) and ``kind``
#: (``total`` / ``visited`` / ``masked``): the tile geometry of the last
#: call of the op (for a jitted step: the last one traced)
FLASH_TILES = "flash_tiles"
#: gauge set beside it, one series per flash kernel (label ``kernel``): the
#: heads a grid step reads from the projections' own ``[B, T, H*D]`` rows
#: (2 for heads of 64, 1 for heads of 128); 0 where the op's wrapper
#: transposed its operands to ``[B*H, T, D]``
FLASH_HEADS_PER_BLOCK = "flash_heads_per_block"
#: gauges set while a dropless MoE layer is traced (the last layer traced
#: is what a scrape sees): (token, slot) rows the layer routes in one call
#: (tokens x experts per token), and the experts it chooses among
MOE_ROWS_PER_STEP = "moe_rows_per_step"
MOE_EXPERTS_TOTAL = "moe_experts_total"
#: gauge set beside them: the experts whose weights the layer holds (all
#: of them, or the share ``Architecture.experts_held`` names)
MOE_EXPERTS_HELD = "moe_experts_held"
#: key of a MoE loss's metrics beside ``moe/rows_held``: the row tiles of
#: the expert section that lie wholly behind the last held group, which
#: the grouped matmul's row products write as zeros without multiplying
#: (``ops.grouped_matmul.tail_tiles``), summed over the expert layers
MOE_TAIL_TILES = "moe/tail_tiles"
#: key beside it: the rounds the expert sections ran, summed over the
#: expert layers. A layer that holds a share runs its expert-sorted rows
#: in rounds of ``moe_rows_bound`` rows, ``ceil(rows_held / bound)`` of
#: them (``parallel.moe.experts_in_rounds``); every expert held is one
#: round. Equal to the number of expert layers while nothing overflows the
#: bound; ``moe/tail_tiles`` counts within the rounds run
MOE_ROUNDS = "moe/rounds"
#: gauge set beside ``moe_rows_per_step`` while a dropless MoE layer is
#: traced: the expert-sorted rows one round of its expert section takes
#: (``parallel.moe.rows_bound``, from static shapes: twice the share a
#: balanced router gives the chip, rounded up to the row tile;
#: ``moe_rows_per_step`` where every expert is held)
MOE_ROWS_BOUND = "moe_rows_bound"
#: gauge set while a ``TransformerLM`` is traced, label ``kind``
#: (``attention`` / ``short_conv`` / ``dense_ffn`` / ``expert_ffn``): the
#: layers of the stack that have a mixer or a feed-forward of that kind
STACK_LAYERS_BY_KIND = "stack_layers_by_kind"
#: gauge set while a looped model is traced: passes of its layer stack
#: over one set of weights
LOOP_PASSES = "loop_passes"
#: gauges set while a block-diffusion model's loss is traced: the
#: positions a block of its mask holds (set by the attention), and the
#: rows a step puts through the stack (``B * 2L``: the clean and the
#: noised copy; the head sees half of them)
BD_BLOCK_LENGTH = "bd_block_length"
BD_ROWS_PER_STEP = "bd_rows_per_step"
#: gauge set by a block-diffusion model's attention while it is traced:
#: the diagonal tiles of one sequence and head its in-block call visits
#: (``L / t``; the call has no tile off the diagonal)
BD_IN_BLOCK_TILES = "bd_in_block_tiles"
#: gauges set while a latent-attention mixer is traced: the latent's rank,
#: a head's key width (without position + rotated) and value width (the
#: kernels take a value width of their own)
MLA_LATENT_RANK = "mla_latent_rank"
MLA_QK_WIDTH = "mla_qk_width"
MLA_V_WIDTH = "mla_v_width"
#: gauge set while a shared expert is traced: its width
MOE_SHARED_WIDTH = "moe_shared_width"
#: gauge set while the fused LM head is traced: 1 where the trace made the
#: head's gradient inside its forward loop (it was differentiated), 0
#: where it made the loss alone (evaluation)
LM_HEAD_GRAD_IN_FORWARD = "lm_head_grad_in_forward"
#: gauge set while a gated short convolution is traced: 1 where its
#: gate-and-tap chain went through the two Pallas kernels, 0 where its
#: shape does not tile and it took the plain ``jax.numpy`` spelling
SHORT_CONV_FUSED = "short_conv_fused"
