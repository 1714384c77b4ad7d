"""Continuous-batching serving engine: ONE jitted steady-state decode
step over a fixed slot array (ISSUE 4 tentpole).

The reference had no inference story beyond a per-sentence Python
``translate`` loop (``examples/seq2seq/seq2seq.py`` (dagger); SURVEY.md:
"no scheduler layer, no serving layer"), and this repo's own
:func:`chainermn_tpu.models.transformer.generate` still serves one
prompt batch at a time — the chip idles between requests and every
ragged batch re-pads into a fresh scan. This engine applies PR 3's
discipline (hide cost behind a FIXED compiled program, account
honestly) to serving:

- **Slot array.** ``num_slots`` requests decode in one fused program.
  Join/leave mutate HOST-side metadata only (positions, free list,
  block tables); the compiled step never changes — the suite pins
  exactly one compilation across occupancy churn.
- **Prefill/decode split.** Prompts run through a separate bucketed
  prefill program (``datasets/bucketing.py`` ladder), writing the whole
  prompt's KV in one pass; compile count is bounded by
  ``len(prefill_buckets)``, not prompt-length spread.
- **Paged KV cache.** ``decode_impl='paged'`` stores KV in a shared
  block pool with per-slot tables (:mod:`chainermn_tpu.ops.paged_kv`,
  :mod:`chainermn_tpu.serving.kv_blocks`): HBM scales with resident
  tokens, and the cache is DONATED through the decode jit so occupancy
  changes never reallocate. ``'dense'`` keeps the classic
  ``[slots, max_len]`` ring; ``'auto'`` resolves through the tuning
  registry (decisions ``decode_impl`` / ``kv_block_size``).
- **Tensor-parallel decode.** Pass a ``mesh`` with a ``'model'`` axis:
  weights are head/width-sharded through
  :mod:`chainermn_tpu.parallel.tensor`'s adjoint pairs — exactly one
  psum per column→row pair (2 per layer), zero collectives in the
  paged-cache bookkeeping (both pinned structurally in the suite).

Token-stream guarantee: engine output for a request equals the
:func:`generate` stream for the same prompt, regardless of what other
requests share the slot array (per-row attention never mixes rows; the
equivalence test drives staggered joins/leaves). At temperature 0 that
is greedy determinism; at temperature > 0 it holds because sampling
keys are COUNTER-BASED (:func:`~chainermn_tpu.models.transformer.
stream_sample_keys`): token ``i`` of a request with seed ``s`` draws
with ``fold_in(fold_in(base_key, s), i)`` — no consumed split chain, so
the draw is invariant to which program (monolithic, chunked,
seq-parallel, speculative) or which replica emitted it
(docs/serving.md "Sampling").
"""

from __future__ import annotations

import time
from typing import Any, Optional, Sequence

import numpy as np

from chainermn_tpu.datasets.bucketing import DEFAULT_BUCKETS, bucket_length
from chainermn_tpu.serving.kv_blocks import (
    BlockAllocator,
    PrefixCache,
    default_num_blocks,
    init_serving_cache,
)

#: tuning-registry candidates for the serving decisions.
DECODE_IMPLS = ("dense", "paged")
KV_BLOCK_SIZES = ("16", "32", "64", "128")
#: slot-decode attention impl (ISSUE 19): 'xla' = scatter → dense-view
#: gather → einsum attend; 'fused' = the flash-decoding Pallas kernel
#: (:mod:`chainermn_tpu.ops.paged_decode`) — one HBM pass over the live
#: blocks, table-indexed in-kernel gather, no dense view. Table default
#: 'xla'.
DECODE_ATTEND_IMPLS = ("xla", "fused")
#: speculation lengths the ``spec_tokens`` decision chooses among
#: (ISSUE 5): 0 = plain one-token decode; K > 0 = draft-and-verify with
#: K drafted tokens per slot per tick.
SPEC_TOKENS = ("0", "2", "4", "8")
#: cross-request prefix sharing over the paged pool (ISSUE 7): the
#: radix-trie block cache + copy-on-write; paged-only (dense rows are
#: slot-private by layout).
PREFIX_CACHE = ("off", "on")
#: minimum matched FULL blocks before a trie hit is adopted — below it
#: the join prefills from scratch (a one-block hit saves little prefill
#: but still pays table/refcount churn and pins blocks in the cache).
MIN_SHARED_BLOCKS = ("1", "2", "4")
#: chunked-prefill widths the ``prefill_chunk`` decision chooses among
#: (ISSUE 11): 0 = monolithic bucketed prefill (``prefill_join``); C > 0
#: = admitted prompts write C tokens of KV per tick INSIDE the mixed
#: step while the remaining active slots decode — the long-prompt
#: TPOT-freeze fix.
PREFILL_CHUNKS = ("0", "16", "32", "64", "128")
#: sequence-parallel long-prompt prefill over the replica's ``model``
#: partition (ISSUE 13): 'off' = the TP (or single-device) monolithic
#: prefill; 'on' = a cache-miss prompt's forward is SHARDED over the
#: mesh's 'model' axis — each shard runs its token slice through the
#: ring/Ulysses attention (decision ``seq_attn_impl``, shared with the
#: ParallelPlan's seq axis), the sown per-layer K/V is resharded
#: heads<->sequence by one all_to_all into exactly the TP cache layout,
#: and the assembled block chain is handed to the existing paged/dense
#: decode path. Streams stay bit-identical to sequential ``generate``.
PREFILL_SEQ_PARALLEL = ("off", "on")
#: multi-tenant adapter application (ISSUE 14): 'gather' = the one
#: compiled program gathers each slot's A/B rows from the bank's
#: stacks and adds the rank-r delta in-forward (mixed-tenant traffic;
#: tenant churn is host metadata only); 'merged' = the tenant's delta
#: is folded into the base weights at construction (zero per-step
#: delta cost — single-tenant-dominant traffic; other tenants refused
#: loudly). Table default 'gather'. ONE definition, in
#: adapters.py — the ctor's validation and the tuning candidates must
#: never disagree.
from chainermn_tpu.serving.adapters import ADAPTER_IMPLS  # noqa: E402


def resolve_adapter_impl(d_model: int, num_heads: int, max_len: int) -> str:
    """Resolve ``adapter_impl`` ('gather' | 'merged') via the registry
    (decision ``adapter_impl``, same key as the other serving
    decisions)."""
    from chainermn_tpu import tuning

    return tuning.choice(
        "adapter_impl", ADAPTER_IMPLS,
        serving_decision_key(d_model, num_heads, max_len),
    )


def _gather_adapter_rows(stacks, rows):
    """Per-slot adapter gather (ISSUE 14): index every layer's stacked
    ``[capacity, ...]`` A/B pair by the ``[B]`` tenant-row vector —
    the ONE in-program step that turns host tenant metadata into the
    forward's per-row deltas. Runs inside the jitted programs; a row
    of 0 gathers the null adapter (exact zeros)."""
    return [
        {tgt: (A[rows], B[rows]) for tgt, (A, B) in layer.items()}
        for layer in stacks
    ]


def serving_decision_key(d_model: int, num_heads: int, max_len: int,
                         device_kind: Optional[str] = None) -> str:
    """The ONE key the serving decisions resolve under —
    device_kind x model-shape bucket x max-seq bucket."""
    from chainermn_tpu import tuning

    return tuning.decision_key(
        device_kind, shape=(d_model, num_heads, max_len), dtype="decode"
    )


def resolve_decode_impl(d_model: int, num_heads: int, max_len: int) -> str:
    """Resolve ``decode_impl`` ('dense' | 'paged') via the registry."""
    from chainermn_tpu import tuning

    return tuning.choice(
        "decode_impl", DECODE_IMPLS,
        serving_decision_key(d_model, num_heads, max_len),
    )


def resolve_kv_block_size(d_model: int, num_heads: int, max_len: int) -> int:
    """Resolve the paged-pool block size via the registry."""
    from chainermn_tpu import tuning

    return int(tuning.choice(
        "kv_block_size", KV_BLOCK_SIZES,
        serving_decision_key(d_model, num_heads, max_len),
    ))


def resolve_decode_attend_impl(d_model: int, num_heads: int,
                               max_len: int) -> str:
    """Resolve ``decode_attend_impl`` ('xla' | 'fused') via the registry
    (same key as the other serving decisions; table default 'xla')."""
    from chainermn_tpu import tuning

    return tuning.choice(
        "decode_attend_impl", DECODE_ATTEND_IMPLS,
        serving_decision_key(d_model, num_heads, max_len),
    )


def resolve_spec_tokens(d_model: int, num_heads: int, max_len: int) -> int:
    """Resolve the speculation length K via the registry (decision
    ``spec_tokens``, same key as the other serving decisions)."""
    from chainermn_tpu import tuning

    return int(tuning.choice(
        "spec_tokens", SPEC_TOKENS,
        serving_decision_key(d_model, num_heads, max_len),
    ))


def resolve_prefix_cache(d_model: int, num_heads: int, max_len: int) -> str:
    """Resolve ``prefix_cache`` ('off' | 'on') via the registry."""
    from chainermn_tpu import tuning

    return tuning.choice(
        "prefix_cache", PREFIX_CACHE,
        serving_decision_key(d_model, num_heads, max_len),
    )


def resolve_min_shared_blocks(d_model: int, num_heads: int,
                              max_len: int) -> int:
    """Resolve the trie-hit adoption threshold via the registry."""
    from chainermn_tpu import tuning

    return int(tuning.choice(
        "min_shared_blocks", MIN_SHARED_BLOCKS,
        serving_decision_key(d_model, num_heads, max_len),
    ))


def resolve_prefill_chunk(d_model: int, num_heads: int,
                          max_len: int) -> int:
    """Resolve the chunked-prefill width via the registry (decision
    ``prefill_chunk``, same key as the other serving decisions — table
    default 0)."""
    from chainermn_tpu import tuning

    return int(tuning.choice(
        "prefill_chunk", PREFILL_CHUNKS,
        serving_decision_key(d_model, num_heads, max_len),
    ))


def resolve_prefill_seq_parallel(d_model: int, num_heads: int,
                                 max_len: int) -> str:
    """Resolve ``prefill_seq_parallel`` ('off' | 'on') via the registry
    (decision ``prefill_seq_parallel``, same key as the other serving
    decisions; table default 'off')."""
    from chainermn_tpu import tuning

    return tuning.choice(
        "prefill_seq_parallel", PREFILL_SEQ_PARALLEL,
        serving_decision_key(d_model, num_heads, max_len),
    )


def shard_lm_params(model, variables, n: int):
    """Stack a :class:`~chainermn_tpu.models.transformer.TransformerLM`
    param tree into ``[n, ...]`` per-shard leaves for tensor-parallel
    decode over a ``'model'`` axis.

    Sharding map (Megatron column/row placement, matching the
    ``tp_axis`` psum hooks in the block): ``qkv`` kernels head-sharded
    (:func:`~chainermn_tpu.parallel.tensor.shard_qkv_columns`), ``proj``
    and ``ff_down`` kernels row-sharded, ``ff_up`` column-sharded;
    ``ff_down`` bias divided by ``n`` so the row-parallel psum
    reassembles it exactly (bit-exact for power-of-two ``n``);
    MoE expert leaves (``moe_w_up``/``moe_b_up``/``moe_w_down``/
    ``moe_b_down``, ISSUE 20) sliced along their leading ``n_experts``
    dim (shard ``i`` owns experts ``[i*E/n, (i+1)*E/n)`` — the
    residency unit the cluster router filters on) with ``moe_router``
    replicated (every shard routes its owned token rows against the
    full expert table); everything else (embeddings, norms) replicated
    by tiling. Feed through ``shard_map`` with ``P('model')`` on every
    leaf's leading axis.
    """
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.parallel.tensor import (
        shard_qkv_columns,
        stack_tp_params,
    )

    n_heads = model.num_heads
    kv_heads = model.num_kv_heads or model.num_heads
    head_dim = model.d_model // model.num_heads

    def shard_leaf(path, leaf):
        names = [str(getattr(p, "key", p)) for p in path]
        if "qkv" in names and names[-1] == "kernel":
            return shard_qkv_columns(leaf, n_heads, kv_heads, head_dim, n)
        if "proj" in names and names[-1] == "kernel":
            return stack_tp_params(leaf, n, 0)
        if "ff_up" in names:  # kernel [D, dff] dim 1; bias [dff] dim 0
            return stack_tp_params(leaf, n, leaf.ndim - 1)
        if "ff_down" in names and names[-1] == "kernel":
            return stack_tp_params(leaf, n, 0)
        if "ff_down" in names and names[-1] == "bias":
            return jnp.stack([leaf / n] * n)
        if any(nm.startswith("moe_") and nm != "moe_router"
               for nm in names):
            if leaf.shape[0] % n:
                raise ValueError(
                    f"n_experts={leaf.shape[0]} must divide the "
                    f"model-axis size {n} (leaf {'/'.join(names)})"
                )
            return stack_tp_params(leaf, n, 0)  # expert-dim slice
        return jnp.stack([leaf] * n)

    return jax.tree_util.tree_map_with_path(shard_leaf, variables)


def unshard_lm_params(model, stacked):
    """Inverse of :func:`shard_lm_params`: reassemble the FULL param
    tree from the ``[n, ...]``-stacked shard form. Pure ``jnp`` — the
    sequence-parallel prefill program calls it INSIDE ``shard_map``
    after an in-program all-gather of the resident TP stacks, so the
    full weights exist only transiently per prefill (no 2x-params
    replica lives in HBM). ``ff_down``'s bias was stored divided by
    ``n``, so its reassembly is the shard SUM (bit-exact for
    power-of-two ``n``, same note as the shard direction). Roundtrip
    ``unshard(shard(p)) == p`` is pinned in tests/test_serving.py."""
    import jax
    import jax.numpy as jnp

    n_heads = model.num_heads
    kv_heads = model.num_kv_heads or model.num_heads
    head_dim = model.d_model // model.num_heads

    def cols(t):
        # [n, d, c] stacked column shards -> [d, n*c] in shard order
        return t.transpose(1, 0, 2).reshape(t.shape[1], -1)

    def un(path, leaf):
        names = [str(getattr(p, "key", p)) for p in path]
        n = leaf.shape[0]
        if "qkv" in names and names[-1] == "kernel":
            hl = n_heads // n * head_dim
            kl = kv_heads // n * head_dim
            q = leaf[:, :, :hl]
            k = leaf[:, :, hl:hl + kl]
            v = leaf[:, :, hl + kl:]
            return jnp.concatenate([cols(q), cols(k), cols(v)], axis=-1)
        if "proj" in names and names[-1] == "kernel":
            return leaf.reshape(-1, leaf.shape[-1])
        if "ff_up" in names:
            if names[-1] == "kernel":
                return cols(leaf)
            return leaf.reshape(-1)  # bias: [n, dff/n] -> [dff]
        if "ff_down" in names and names[-1] == "kernel":
            return leaf.reshape(-1, leaf.shape[-1])
        if "ff_down" in names and names[-1] == "bias":
            return leaf.sum(axis=0)  # stored as bias / n per shard
        if any(nm.startswith("moe_") and nm != "moe_router"
               for nm in names):
            # [n, E/n, ...] expert slices -> [E, ...] in shard order
            return leaf.reshape(-1, *leaf.shape[2:])
        return leaf[0]  # replicated tiles

    return jax.tree_util.tree_map_with_path(un, stacked)


class ServingEngine:
    """Fixed-slot continuous-batching decode over a ``TransformerLM``.

    Args:
      model: the trained model (``causal=True``, ``return_hidden=False``).
      params: its ``{'params': ...}`` variables.
      num_slots: concurrent requests in the compiled step.
      max_len: serving horizon (prompt + generated) per request;
        defaults to ``model.max_len``. Dense caches and paged tables are
        sized to it.
      decode_impl: ``'dense'`` | ``'paged'`` | ``'auto'`` (tuning
        registry, decision ``decode_impl``).
      kv_block_size: paged block size in tokens, or ``'auto'``
        (decision ``kv_block_size``).
      num_blocks: paged-pool capacity in blocks (incl. scratch block 0);
        default is the no-oversubscription worst case
        (:func:`~chainermn_tpu.serving.kv_blocks.default_num_blocks`) —
        pass less to oversubscribe (admission defers on exhaustion).
      temperature/top_k/top_p: sampling configuration shared with
        :func:`generate` (same ``_tempered_filtered`` path; temperature
        0 = greedy). Sampling keys are COUNTER-BASED
        (:func:`~chainermn_tpu.models.transformer.stream_sample_keys`):
        the token at absolute position ``i`` of a request with seed
        ``s`` draws with ``fold_in(fold_in(base_key, s), i)`` — a pure
        function of (base key, request seed, position), so sampled
        streams keep the same bit-identical-stream guarantee as greedy
        ones across chunked/seq-parallel prefill, speculative decode,
        preemption/resume and cross-replica migration.
      base_seed: integer seed for the sampling base key
        (``PRNGKey(base_seed)``, default 0) — the EXPLICIT spelling of
        the engine-level randomness source; two engines with the same
        ``base_seed`` and per-request seeds produce identical sampled
        streams.
      rng: optional explicit PRNG base key; overrides ``base_seed``
        (passing both is rejected). Use when the base key comes from an
        existing key-management scheme rather than an integer seed.
      pad_id: prompt right-padding token for the bucketed prefill.
      mesh: optional ``Mesh`` with a ``'model'`` axis → tensor-parallel
        decode (weights sharded via :func:`shard_lm_params`).
      spec_tokens: speculative draft length K per tick (ISSUE 5):
        ``0`` = plain one-token decode; ``K > 0`` = each tick drafts up
        to K tokens per slot and ONE jitted verify forward scores
        ``[slots, K+1]`` positions, committing the longest greedy-
        matching prefix plus the model's own next token (1..K+1 tokens
        per tick, bit-identical to the plain stream). ``'auto'``
        resolves through the registry (decision ``spec_tokens``).
        Under ``temperature > 0`` the verify grid samples every
        position with its counter key and acceptance is the standard
        rejection-sampling rule specialised to the deterministic
        drafters (:func:`~chainermn_tpu.serving.speculate.
        rejection_accept_length`) — the committed stream is
        distribution-exact AND bit-identical to sequential sampling at
        a fixed seed.
      drafter: proposal source for ``spec_tokens > 0`` — any object with
        ``propose(history, k)`` (:mod:`chainermn_tpu.serving.speculate`;
        default :class:`~chainermn_tpu.serving.speculate.NgramDrafter`).
      prefix_cache: cross-request prefix sharing (ISSUE 7): ``'on'``
        keeps a block-granular radix trie over completed prefills so a
        joining request adopts the already-filled blocks of its longest
        matching full-block prefix and prefills only the unshared tail
        (the TTFT lever under duplicate-prefix load). ``'auto'``
        resolves through the registry (decision ``prefix_cache``);
        paged-only — under ``decode_impl='dense'`` it is forced off.
        Host metadata + one block-copy program only: the decode/verify
        programs are untouched, and shared streams are bit-identical to
        unshared ones (pinned in tests/test_prefix_cache.py).
      min_shared_blocks: minimum matched FULL blocks before a trie hit
        is adopted (decision ``min_shared_blocks`` under ``'auto'``).
      prefill_chunk: chunked-prefill width in tokens per tick (ISSUE
        11): ``0`` = monolithic bucketed prefill (``prefill_join`` runs
        the whole prompt in one forward, freezing every active slot's
        decode for its duration — the long-prompt p99 killer); ``C >
        0`` = admission reserves the slot without a forward
        (``chunked_join``) and each :meth:`mixed_step` tick writes up
        to C prompt tokens of KV at their true positions for the
        filling slots WHILE the remaining active slots decode (or, with
        ``spec_tokens > 0``, draft-and-verify) — ONE jitted program of
        fixed width ``max(C, spec_tokens + 1)`` whose jit cache stays
        at 1 across every chunk/decode occupancy mix. Chunked streams
        are bit-identical to monolithic ones at ANY temperature (every
        emitted token is the model's own argmax — or counter-keyed
        sample — at its true position). ``'auto'`` resolves through the
        registry (decision
        ``prefill_chunk``, table default 0).
      prefill_seq_parallel: sequence-parallel long-prompt prefill over
        the mesh's ``model`` partition (ISSUE 13): ``'on'`` shards a
        cache-MISS prompt's forward over the TP devices — each shard
        runs its token slice with ring/Ulysses attention (decision
        ``seq_attn_impl``; Ulysses force-falls back to ring when heads
        are indivisible), the sown per-layer K/V is resharded by one
        ``all_to_all`` per layer into exactly the TP cache layout and
        scattered at true positions, and the last true position's
        logits are psum-selected for the first token — the assembled
        block chain then feeds the existing paged/dense decode path.
        Streams stay bit-identical to sequential ``generate``; composes
        with the prefix cache (a trie HIT takes the monolithic tail
        prefill — its context lives in adopted blocks the sharded
        forward cannot see; the MISS, which is where long-prompt TTFT
        lives, goes wide). The psum-selected last-position logits feed
        the same counter-keyed sample as the monolithic path, so
        sampled streams stay bit-identical too. Requires a ``mesh``, no
        ``window``, and ``prefill_chunk == 0`` (chunked admission takes
        precedence) — explicit ``'on'`` violating these is rejected; an
        ``'auto'`` resolution is forced off with provenance. ``'auto'``
        resolves via the registry (table default ``off``).
      adapter_bank: multi-tenant low-rank delta store (ISSUE 14,
        :class:`~chainermn_tpu.serving.adapters.AdapterBank`): each
        slot carries a host-side tenant row, every serving program
        gathers that slot's A/B rows from the bank's stacks and adds
        the rank-r delta inside the forward — tenant join/leave/
        registration churn mutates host metadata only (the jit caches
        stay pinned at 1), and under TP the stacks are sharded along
        the existing column/row split so the compiled step keeps
        exactly the pre-adapter 2 all-reduces/layer. A tenant's stream
        is bit-identical to sequential ``generate`` with that tenant's
        adapter (``bank.adapter_arrays``); a zero-adapter tenant is
        bitwise the base model. Blocks ``prefill_seq_parallel`` (no
        delta path in the sharded prompt forward yet — forced off with
        provenance).
      adapter_impl: ``'gather'`` | ``'merged'`` | ``'auto'`` (registry
        decision ``adapter_impl``, table ``gather``) — requires
        ``adapter_bank``. ``'merged'`` folds ``merged_tenant``'s delta
        into the base weights at construction
        (``bank.merge_adapter_params``) and serves ONLY that tenant
        (others refused loudly): zero per-step delta cost for
        single-tenant-dominant traffic, bit-identical to ``generate``
        over the offline-merged weights.
      merged_tenant: the tenant ``adapter_impl='merged'`` folds
        (required for explicit ``'merged'``; an ``'auto'`` resolution
        of ``merged`` without it falls back to ``gather`` with
        provenance).
    """

    def __init__(self, model, params, *, num_slots: int,
                 max_len: Optional[int] = None,
                 decode_impl: str = "auto",
                 decode_attend_impl: str = "auto",
                 kv_block_size="auto",
                 num_blocks: Optional[int] = None,
                 prefill_buckets: Sequence[int] = DEFAULT_BUCKETS,
                 temperature: float = 0.0,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 base_seed: int = 0,
                 rng=None, pad_id: int = 0, mesh=None,
                 spec_tokens="auto", drafter=None,
                 prefix_cache="auto", min_shared_blocks="auto",
                 prefill_chunk="auto",
                 prefill_seq_parallel="auto",
                 adapter_bank=None, adapter_impl="auto",
                 merged_tenant=None) -> None:
        import jax

        from chainermn_tpu.models.transformer import TransformerLM

        if not isinstance(model, TransformerLM):
            raise TypeError(f"ServingEngine serves TransformerLM, got "
                            f"{type(model).__name__}")
        if model.looped:
            from chainermn_tpu.models.transformer import refuse_looped_decode

            refuse_looped_decode(model, "serving")
        from chainermn_tpu.models.transformer import refuse_unbuilt_decode

        refuse_unbuilt_decode(model, "serving")
        if model.return_hidden or not model.causal:
            raise ValueError("serving needs a causal LM with logits "
                             "(return_hidden=False, causal=True)")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        max_len = int(max_len or model.max_len)
        if max_len > model.max_len:
            raise ValueError(
                f"max_len={max_len} exceeds the model context "
                f"{model.max_len}"
            )
        if rng is not None and base_seed:
            raise ValueError(
                "pass base_seed= (an integer) OR rng= (an explicit base "
                "key), not both — they name the same randomness source"
            )
        if (top_k is not None or top_p is not None) and temperature <= 0.0:
            raise ValueError("top_k/top_p filtering is for sampling — set "
                             "temperature > 0")
        if top_p is not None and not (0.0 < top_p <= 1.0):
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if top_k is not None and not (1 <= top_k <= model.vocab_size):
            raise ValueError(
                f"top_k must be in [1, vocab_size={model.vocab_size}], "
                f"got {top_k}"
            )

        self.num_slots = int(num_slots)
        self.max_len = max_len
        self.pad_id = int(pad_id)
        self.temperature = float(temperature)
        self.top_k, self.top_p = top_k, top_p
        # Counter-based sampling state: ONE base key (explicit — no
        # silent PRNGKey(0) fallback hidden behind temperature > 0) and
        # a per-slot request-seed row. Token i of the request in slot s
        # draws with fold_in(fold_in(_base_key, _seeds[s]), i); there is
        # no consumed split chain, so no key threads through steps.
        self.base_seed = int(base_seed)
        self._base_key = (rng if rng is not None
                          else jax.random.PRNGKey(self.base_seed))
        self._seeds = np.zeros((self.num_slots,), dtype=np.int32)
        self._seeds_ver = 0  # bumped on every _seeds mutation
        self._seeds_dev = None  # cached device copy (H2D discipline)
        self._seeds_dev_ver = -1
        self._buckets = tuple(
            b for b in sorted(set(prefill_buckets)) if b <= max_len
        ) or (max_len,)
        if self._buckets[-1] < max_len:
            # the ladder must be able to carry a full-horizon prompt
            self._buckets = self._buckets + (max_len,)
        self.decisions: list[dict] = []

        # ---- decode_impl / kv_block_size resolution (with provenance)
        from chainermn_tpu import tuning

        key = serving_decision_key(model.d_model, model.num_heads, max_len)
        if decode_impl == "auto":
            decode_impl = resolve_decode_impl(
                model.d_model, model.num_heads, max_len
            )
            self._adopt_decision("decode_impl", key)
        elif decode_impl in DECODE_IMPLS:
            self.decisions.append({"name": "decode_impl", "key": key,
                                   "winner": decode_impl,
                                   "source": "explicit"})
        else:
            raise ValueError(
                f"decode_impl must be one of {DECODE_IMPLS + ('auto',)}, "
                f"got {decode_impl!r}"
            )
        self.decode_impl = decode_impl

        if decode_impl == "paged":
            if kv_block_size == "auto":
                kv_block_size = resolve_kv_block_size(
                    model.d_model, model.num_heads, max_len
                )
                self._adopt_decision("kv_block_size", key)
            else:
                kv_block_size = int(kv_block_size)
                self.decisions.append({"name": "kv_block_size", "key": key,
                                       "winner": str(kv_block_size),
                                       "source": "explicit"})
            num_blocks = num_blocks or default_num_blocks(
                num_slots, kv_block_size, max_len
            )
            self._alloc: Optional[BlockAllocator] = BlockAllocator(
                num_blocks, kv_block_size, num_slots, max_len
            )
        else:
            kv_block_size = int(kv_block_size) if kv_block_size != "auto" \
                else 64
            self._alloc = None

        # ---- decode attend impl (ISSUE 19): the fused paged-decode
        # Pallas kernel vs the XLA scatter → gather → attend. ONE field
        # on the decode model clone, so the decode / verify / mixed /
        # prefill-tail programs all switch together (their jit caches
        # stay pinned — the impl is a static model field, not a traced
        # arg).
        if (decode_attend_impl != "auto"
                and decode_attend_impl not in DECODE_ATTEND_IMPLS):
            raise ValueError(
                f"decode_attend_impl must be one of "
                f"{DECODE_ATTEND_IMPLS + ('auto',)}, got "
                f"{decode_attend_impl!r}"
            )
        if decode_attend_impl == "auto":
            decode_attend_impl = resolve_decode_attend_impl(
                model.d_model, model.num_heads, max_len
            )
            self._adopt_decision("decode_attend_impl", key)
        else:
            self.decisions.append({"name": "decode_attend_impl",
                                   "key": key,
                                   "winner": decode_attend_impl,
                                   "source": "explicit"})
        self.decode_attend_impl = decode_attend_impl

        # ---- MoE dispatch impl (ISSUE 20): the ownership-split decode
        # path builds its expert queues by sort-scatter or dense one-hot
        # einsum — registry decision, resolved ONCE here so the decode /
        # verify / mixed / prefill programs all trace the same impl (a
        # static model field, exactly like decode_attend_impl; jit
        # caches stay pinned).
        self.n_experts = int(model.n_experts)
        self.moe_dispatch_impl: Optional[str] = None
        if self.n_experts > 0:
            from chainermn_tpu.parallel.moe import resolve_dispatch_impl

            tp = (int(mesh.shape["model"])
                  if mesh is not None and "model" in mesh.axis_names
                  else 1)
            own_rows = -(-num_slots // tp)
            moe_key = tuning.decision_key(
                shape=(own_rows, self.n_experts, model.d_model),
                dtype=model.compute_dtype,
            )
            self.moe_dispatch_impl = resolve_dispatch_impl(
                own_rows, self.n_experts, model.d_model,
                model.compute_dtype, model.moe_dispatch_impl,
            )
            if model.moe_dispatch_impl == "auto":
                self._adopt_decision("moe_dispatch", moe_key)
            else:
                self.decisions.append({
                    "name": "moe_dispatch", "key": moe_key,
                    "winner": self.moe_dispatch_impl,
                    "source": "explicit",
                })

        # ---- prefix sharing (ISSUE 7): trie + COW over the paged pool.
        # Dense rows are slot-private by layout — nothing to share, so
        # the decision is forced off there without consulting the
        # registry (an 'on' record for a dense shape would be a
        # lie about what ran). Validate BEFORE the dense force: a typo
        # must raise identically whichever decode impl it rides with.
        if prefix_cache != "auto" and prefix_cache not in PREFIX_CACHE:
            raise ValueError(
                f"prefix_cache must be one of {PREFIX_CACHE + ('auto',)}, "
                f"got {prefix_cache!r}"
            )
        if self._alloc is None:
            prefix_cache = "off"
            self.decisions.append({"name": "prefix_cache", "key": key,
                                   "winner": "off",
                                   "source": "forced:dense"})
        elif prefix_cache == "auto":
            prefix_cache = resolve_prefix_cache(
                model.d_model, model.num_heads, max_len
            )
            self._adopt_decision("prefix_cache", key)
        else:
            self.decisions.append({"name": "prefix_cache", "key": key,
                                   "winner": prefix_cache,
                                   "source": "explicit"})
        self.prefix_cache_enabled = prefix_cache == "on"
        if self.prefix_cache_enabled:
            if min_shared_blocks == "auto":
                min_shared_blocks = resolve_min_shared_blocks(
                    model.d_model, model.num_heads, max_len
                )
                self._adopt_decision("min_shared_blocks", key)
            else:
                min_shared_blocks = int(min_shared_blocks)
                self.decisions.append({"name": "min_shared_blocks",
                                       "key": key,
                                       "winner": str(min_shared_blocks),
                                       "source": "explicit"})
            if min_shared_blocks < 1:
                raise ValueError(
                    f"min_shared_blocks must be >= 1, got "
                    f"{min_shared_blocks}"
                )
            self._prefix: Optional[PrefixCache] = PrefixCache(self._alloc)
            self._min_shared_blocks = int(min_shared_blocks)
        else:
            self._prefix = None
            self._min_shared_blocks = 0
        #: lifetime prefix-cache accounting (the scheduler's hit-rate
        #: gauge and dryrun/bench lines read it).
        self.prefix_stats = {
            "lookups": 0, "hits": 0, "hit_tokens": 0, "prompt_tokens": 0,
            "prefill_tokens": 0, "cow_blocks": 0,
        }
        #: per-join event payload for the scheduler's ``prefix_cache``
        #: trace event — set by every paged+cache-on prefill_join, None
        #: otherwise.
        self.last_prefix_info: Optional[dict] = None

        # ---- speculation length (ISSUE 5): K drafted tokens per tick,
        # verified in one forward. Resolved like the other serving
        # decisions. At temperature 0 acceptance compares drafts
        # against the model's argmax; at temperature > 0 the verify
        # grid is counter-key SAMPLED and the same comparison is the
        # rejection-sampling acceptance rule (speculate.
        # rejection_accept_length) — both modes serve.
        if spec_tokens == "auto":
            spec_tokens = resolve_spec_tokens(
                model.d_model, model.num_heads, max_len
            )
            self._adopt_decision("spec_tokens", key)
        else:
            spec_tokens = int(spec_tokens)
            self.decisions.append({"name": "spec_tokens", "key": key,
                                   "winner": str(spec_tokens),
                                   "source": "explicit"})
        if spec_tokens < 0 or spec_tokens >= max_len:
            raise ValueError(
                f"spec_tokens must be in [0, max_len={max_len}), got "
                f"{spec_tokens}"
            )
        self.spec_tokens = spec_tokens
        if drafter is not None and not callable(
            getattr(drafter, "propose", None)
        ):
            raise TypeError(
                "drafter must have a propose(history, k) method "
                "(see chainermn_tpu.serving.speculate)"
            )
        if drafter is None and spec_tokens > 0:
            from chainermn_tpu.serving.speculate import NgramDrafter

            drafter = NgramDrafter()
        self._drafter = drafter

        # ---- chunked prefill (ISSUE 11): C prompt tokens of KV written
        # per tick inside the mixed step, interleaved with decode.
        if prefill_chunk == "auto":
            prefill_chunk = resolve_prefill_chunk(
                model.d_model, model.num_heads, max_len
            )
            self._adopt_decision("prefill_chunk", key)
        else:
            prefill_chunk = int(prefill_chunk)
            self.decisions.append({"name": "prefill_chunk", "key": key,
                                   "winner": str(prefill_chunk),
                                   "source": "explicit"})
        if prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0, got {prefill_chunk}"
            )
        self.prefill_chunk = int(prefill_chunk)
        #: width of the mixed step's token grid — the chunk columns and
        #: the verify span share ONE program, so chunk and draft rows
        #: coexist in the same tick at the wider of the two.
        self._mixed_T = (max(self.prefill_chunk, self.spec_tokens + 1)
                         if self.prefill_chunk > 0 else 0)
        #: slots admitted by chunked_join whose prompt KV is still being
        #: written (insertion order = admission order, the fill-row FIFO
        #: mixed_step advances). NOT active: decode masks exclude them.
        self._pending_fill: dict[int, dict] = {}

        # ---- multi-tenant adapters (ISSUE 14): resolve the impl and,
        # under 'merged', fold the tenant's delta into the base weights
        # BEFORE the clone/shard below — the rest of the ctor then
        # builds an ordinary engine over the folded tree.
        if adapter_impl != "auto" and adapter_impl not in ADAPTER_IMPLS:
            raise ValueError(
                f"adapter_impl must be one of "
                f"{ADAPTER_IMPLS + ('auto',)}, got {adapter_impl!r}"
            )
        self.adapter_bank = adapter_bank
        self.merged_tenant = merged_tenant
        if adapter_bank is None:
            if adapter_impl != "auto":
                raise ValueError(
                    f"adapter_impl={adapter_impl!r} needs an "
                    "adapter_bank"
                )
            if merged_tenant is not None:
                raise ValueError("merged_tenant needs an adapter_bank")
            self.adapter_impl: Optional[str] = None
        else:
            if adapter_bank.num_layers != model.num_layers:
                raise ValueError(
                    f"adapter_bank stacks {adapter_bank.num_layers} "
                    f"layers, model has {model.num_layers}"
                )
            if adapter_impl == "auto":
                adapter_impl = resolve_adapter_impl(
                    model.d_model, model.num_heads, max_len
                )
                self._adopt_decision("adapter_impl", key)
                if adapter_impl == "merged" and merged_tenant is None:
                    # The cache says merging wins this shape, but this
                    # engine was built without a tenant to fold — serve
                    # the gather path with honest provenance rather
                    # than guess whose weights to merge.
                    adapter_impl = "gather"
                    self.decisions.append({
                        "name": "adapter_impl", "key": key,
                        "winner": "gather",
                        "source": "forced:no-merged-tenant",
                    })
            else:
                if adapter_impl == "merged" and merged_tenant is None:
                    raise ValueError(
                        "adapter_impl='merged' needs merged_tenant= — "
                        "the fold must know whose delta to bake in"
                    )
                if adapter_impl == "gather" and merged_tenant is not None:
                    # Loud like every other invalid combination: an
                    # explicit gather engine never folds, so a
                    # merged_tenant here is a typoed/confused intent
                    # the caller must resolve, not a silent no-op.
                    raise ValueError(
                        "merged_tenant= is only meaningful with "
                        "adapter_impl='merged' (or 'auto'); an "
                        "explicit 'gather' engine serves every "
                        "registered tenant and folds nothing"
                    )
                self.decisions.append({"name": "adapter_impl",
                                       "key": key,
                                       "winner": adapter_impl,
                                       "source": "explicit"})
            self.adapter_impl = adapter_impl
            if adapter_impl == "merged":
                params = adapter_bank.merge_adapter_params(
                    params, merged_tenant)
        #: whether the compiled programs carry the per-slot gather+delta
        #: (the 'gather' impl); merged/bank-less engines run the plain
        #: programs.
        self._use_adapters = (adapter_bank is not None
                              and self.adapter_impl == "gather")
        if self._use_adapters:
            # Trie invalidation on weight churn (review finding): a
            # tenant's cached KV is only valid under the stacks that
            # produced it — drop the namespace whenever the bank's
            # content for that tenant changes (register overwrite,
            # zero-adapter downgrade, evict), whichever engine or
            # caller mutated the bank.
            adapter_bank.add_listener(self._on_adapter_change)
        #: per-slot tenant identity (host metadata: the prefix-trie
        #: namespace, the bank pin, the export payload field).
        self._tenant_ids: list[Optional[str]] = [None] * num_slots
        #: per-slot bank row the programs gather (0 = null adapter).
        self._tenant_rows = np.zeros(num_slots, np.int32)
        self._tenant_rows_ver = 0
        self._tenant_rows_dev = None
        self._tenant_rows_dev_ver = -1
        self._adapter_dev = None
        self._adapter_ver = -1

        # ---- decode-path model (and its TP shard form)
        self._mesh = mesh
        clone_kw: dict[str, Any] = dict(
            kv_layout=decode_impl,
            kv_block_size=int(kv_block_size),
            kv_num_blocks=(self._alloc.num_blocks if self._alloc else 0),
            decode_cache_len=max_len,
            decode_attend_impl=decode_attend_impl,
        )
        if mesh is None:
            self._decode_model = model.clone(**clone_kw)
            self._vars = {"params": params["params"]}
        else:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    f"serving mesh needs a 'model' axis, got "
                    f"{mesh.axis_names}"
                )
            n = int(mesh.shape["model"])
            kvh = model.num_kv_heads or model.num_heads
            moe = self.n_experts > 0
            if model.num_heads % n or kvh % n or (
                    not moe and model.d_ff % n):
                raise ValueError(
                    f"heads={model.num_heads}/kv={kvh}/d_ff={model.d_ff} "
                    f"must divide the model-axis size {n}"
                )
            if moe and self.n_experts % n:
                raise ValueError(
                    f"n_experts={self.n_experts} must divide the "
                    f"model-axis size {n} — expert shards live on the "
                    f"TP mesh"
                )
            self._tp_n = n
            # MoE keeps the FULL d_ff (experts shard by expert index,
            # not by hidden width) and n_experts stays GLOBAL — the
            # sharder slices the stacked expert leaves, the block reads
            # the local count off the leaf at trace time.
            self._decode_model = model.clone(
                num_heads=model.num_heads // n,
                num_kv_heads=kvh // n,
                d_ff=model.d_ff if moe else model.d_ff // n,
                head_dim=model.d_model // model.num_heads,
                tp_axis="model",
                expert_axis="model" if moe else None,
                moe_dispatch_impl=(self.moe_dispatch_impl or "auto"),
                moe_experts_local=(self.n_experts // n if moe else None),
                **clone_kw,
            )
            self._vars = shard_lm_params(
                model, {"params": params["params"]}, n
            )

        # ---- cache + host slot metadata. Shape evaluation runs outside
        # shard_map where no mesh axis is bound, so strip the psum hooks
        # (tp_axis) — cache shapes depend only on the (local) head/width
        # fields, which the clone keeps.
        cache = init_serving_cache(
            self._decode_model.clone(tp_axis=None, expert_axis=None),
            self._local_vars_for_init(), num_slots,
        )
        if mesh is not None:
            import jax.numpy as jnp
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            # Placed with the mesh sharding the step programs RETURN
            # (out_specs P('model')): the first program to touch the
            # cache must see the canonical sharding, or its jit entry
            # compiles against the default placement and the second
            # call recompiles — monolithic engines never noticed
            # (prefill always ran first and canonicalised it), but a
            # chunked engine's FIRST forward is the mixed step itself.
            sh = NamedSharding(mesh, P("model"))
            cache = jax.tree.map(
                lambda c: jax.device_put(
                    jnp.broadcast_to(c[None], (self._tp_n,) + c.shape),
                    sh,
                ),
                cache,
            )
        self._cache = cache
        self._positions = np.zeros(num_slots, np.int64)
        self._last_tok = np.zeros(num_slots, np.int64)
        self._active = np.zeros(num_slots, bool)
        self._free = list(range(num_slots - 1, -1, -1))
        #: per-slot committed token history (prompt + generated incl.
        #: the pending last token) — what the drafter proposes from.
        self._history: list[list[int]] = [[] for _ in range(num_slots)]
        #: the resolution key every serving decision rode (the cluster
        #: router resolves its disaggregation decision under the same
        #: key — one key per model shape, ISSUE 8).
        self.decision_key = key
        self._tables_dev = None  # device copy of the block tables...
        self._tables_ver = -1    # ...valid while allocator.version holds
        # Cross-replica KV handoff programs (ISSUE 8): built lazily on
        # the first export/import — most engines never transfer.
        self._kv_extract_jit = None
        self._kv_inject_jit = None

        # ---- sequence-parallel prefill (ISSUE 13): shard a cache-miss
        # prompt's forward over the mesh's 'model' partition.
        if (prefill_seq_parallel != "auto"
                and prefill_seq_parallel not in PREFILL_SEQ_PARALLEL):
            raise ValueError(
                f"prefill_seq_parallel must be one of "
                f"{PREFILL_SEQ_PARALLEL + ('auto',)}, got "
                f"{prefill_seq_parallel!r}"
            )
        explicit_sp = prefill_seq_parallel != "auto"
        if prefill_seq_parallel == "auto":
            prefill_seq_parallel = resolve_prefill_seq_parallel(
                model.d_model, model.num_heads, max_len
            )
            self._adopt_decision("prefill_seq_parallel", key)
        else:
            self.decisions.append({"name": "prefill_seq_parallel",
                                   "key": key,
                                   "winner": prefill_seq_parallel,
                                   "source": "explicit"})
        if prefill_seq_parallel == "on":
            blocked = None
            if mesh is None:
                blocked = ("forced:no-mesh",
                           "needs a mesh with a 'model' axis to shard "
                           "the prompt over")
            elif model.window is not None:
                blocked = ("forced:window",
                           "the sharded forward's ring/Ulysses "
                           "attention does not honour a sliding window")
            elif self.prefill_chunk > 0:
                blocked = ("forced:chunked",
                           "chunked admission (prefill_chunk > 0) "
                           "already bounds long-prompt interference and "
                           "takes precedence")
            elif adapter_bank is not None:
                blocked = ("forced:adapters",
                           "the sequence-parallel prompt forward has "
                           "no adapter-delta path — multi-tenant "
                           "engines take the monolithic prefill")
            if blocked is not None:
                if explicit_sp:
                    raise ValueError(
                        f"prefill_seq_parallel='on' {blocked[1]} — "
                        f"({blocked[0]})"
                    )
                prefill_seq_parallel = "off"
                self.decisions.append({"name": "prefill_seq_parallel",
                                       "key": key, "winner": "off",
                                       "source": blocked[0]})
        self.prefill_seq_parallel = prefill_seq_parallel == "on"
        #: whether the LAST prefill_join ran the sequence-parallel
        #: program (the scheduler's prefill-event field).
        self.last_prefill_seq_parallel = False
        self._base_model = model
        self._seq_base_model = None
        self._seq_attn_impl = None
        self._seq_prefill_jits: dict[int, Any] = {}
        if self.prefill_seq_parallel:
            from chainermn_tpu import tuning
            from chainermn_tpu.ops.flash_attention import interpret_on
            from chainermn_tpu.parallel.plan_specs import SEQ_ATTN_IMPLS
            from chainermn_tpu.parallel.ring_attention import (
                seq_ring_attention_local,
            )
            from chainermn_tpu.parallel.ulysses import (
                ulysses_attention_local,
            )

            n = self._tp_n
            kvh = model.num_kv_heads or model.num_heads
            skey = tuning.decision_key(
                shape=(n, model.num_heads, max_len), dtype="seqattn"
            )
            impl = tuning.choice("seq_attn_impl", SEQ_ATTN_IMPLS, skey)
            self._adopt_decision("seq_attn_impl", skey)
            if impl == "ulysses" and (model.num_heads % n or kvh % n):
                impl = "ring"
                self.decisions.append({
                    "name": "seq_attn_impl", "key": skey,
                    "winner": "ring",
                    "source": "forced:heads-indivisible",
                })
            self._seq_attn_impl = impl
            interp = interpret_on(mesh.devices.flat[0].platform)
            if impl == "ring":
                def _seq_attn(q, k, v, *, causal, scale, **kw):
                    return seq_ring_attention_local(
                        q, k, v, "model", causal=causal, scale=scale,
                        interpret=interp,
                    )
            else:
                def _seq_attn(q, k, v, *, causal, scale, **kw):
                    return ulysses_attention_local(
                        q, k, v, "model", causal=causal, scale=scale,
                        impl="flash", interpret=interp,
                    )
            self._seq_base_model = model.clone(
                attention_fn=_seq_attn, sow_kv=True
            )

        self._decode_step_jit = self._build_decode_step()
        self._verify_step_jit = (
            self._build_verify_step() if self.spec_tokens > 0 else None
        )
        self._mixed_step_jit = (
            self._build_mixed_step() if self.prefill_chunk > 0 else None
        )
        self._cow_copy_jit = (
            self._build_cow_copy() if self._prefix is not None else None
        )
        self._prefill_jits: dict[int, Any] = {}

    # ------------------------------------------------------------------
    # construction helpers

    def _adopt_decision(self, name: str, key: str) -> None:
        """Copy the registry's resolution record (winner + provenance)
        into ``self.decisions`` — what dryrun/bench print per engine."""
        from chainermn_tpu import tuning

        recs = [d for d in tuning.decisions_taken()
                if d["name"] == name and d["key"] == key]
        if recs:
            self.decisions.append(dict(recs[-1]))

    def _local_vars_for_init(self):
        """Per-shard variables for cache shape evaluation (TP stacks
        carry a leading mesh axis the local model must not see)."""
        if self._mesh is None:
            return self._vars
        import jax

        return jax.tree.map(lambda a: a[0], self._vars)

    def _dummy_tables(self):
        """Dense decode still passes a (tiny, ignored) tables arg so the
        step signature — and therefore the compiled program — is one
        shape for both impls."""
        if self._alloc is not None:
            return self._alloc.tables
        return np.zeros((self.num_slots, 1), np.int32)

    def _tables_device(self):
        """The block tables as a CACHED device array, re-uploaded only
        when the allocator actually mutated a row — the steady-state
        decode loop then pays no H2D transfer after its D2H token sync
        on ticks where no table changed."""
        import jax.numpy as jnp

        version = self._alloc.version if self._alloc is not None else 0
        if self._tables_dev is None or self._tables_ver != version:
            self._tables_dev = jnp.asarray(self._dummy_tables())
            self._tables_ver = version
        return self._tables_dev

    def _adapter_device(self):
        """The bank's stacks as CACHED device arrays (TP-sharded under a
        mesh), re-uploaded only when a registration actually changed a
        row (``bank.version`` — the block-table discipline: the decode
        loop must not pay an H2D per tick for tenant data that did not
        change)."""
        import jax

        bank = self.adapter_bank
        if self._adapter_dev is None or self._adapter_ver != bank.version:
            import jax.numpy as jnp

            stacks = bank.stacks()
            if self._mesh is None:
                dev = [
                    {t: (jnp.asarray(A), jnp.asarray(B))
                     for t, (A, B) in layer.items()}
                    for layer in stacks
                ]
            else:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P

                from chainermn_tpu.serving.adapters import (
                    shard_adapter_stacks,
                )

                sh = NamedSharding(self._mesh, P("model"))
                dev = jax.tree.map(
                    lambda a: jax.device_put(a, sh),
                    shard_adapter_stacks(
                        self._base_model, stacks, self._tp_n),
                )
            self._adapter_dev = dev
            self._adapter_ver = bank.version
        return self._adapter_dev

    def _tenant_rows_device(self):
        """The per-slot tenant-row vector as a cached device array —
        re-uploaded only when a join/leave changed a row (same H2D
        discipline as the block tables)."""
        import jax.numpy as jnp

        if (self._tenant_rows_dev is None
                or self._tenant_rows_dev_ver != self._tenant_rows_ver):
            self._tenant_rows_dev = jnp.asarray(self._tenant_rows)
            self._tenant_rows_dev_ver = self._tenant_rows_ver
        return self._tenant_rows_dev

    def _step_args(self, *mid, tail=(), tenant_rows=None):
        """ONE argument-splice rule for every jitted program call
        (prefill/decode/verify/mixed): ``(cache, vars, *mid, *tail)``,
        with the adapter stacks inserted after ``vars`` and the
        per-slot tenant rows between ``mid`` and ``tail`` when the
        bank is active (review finding: four hand-expanded if/else
        copies of the argument list were one reorder away from
        silently misfeeding a compiled program). ``tenant_rows``
        defaults to the cached whole-array upload; prefill passes its
        single-slot slice."""
        if not self._use_adapters:
            return (self._cache, self._vars, *mid, *tail)
        rows = (self._tenant_rows_device() if tenant_rows is None
                else tenant_rows)
        return (self._cache, self._vars, self._adapter_device(),
                *mid, rows, *tail)

    def _tp_jit(self, inner, n_plain_args: int, n_model_args: int = 0):
        """The ONE jit(+shard_map) wrapper all the serving programs
        (decode / verify / mixed / prefill) share: donate the cache,
        and under TP unstack the ``[n, ...]`` cache/param stacks around
        the local program so the psum hooks see per-shard leaves.

        ``inner(cache, variables, *model_args, *rest) -> (cache, out)``;
        ``n_model_args`` counts extra model-axis-sharded pytrees right
        after ``variables`` (ISSUE 14: the adapter stacks ride here so
        each shard gathers its own column/row slice), ``n_plain_args``
        counts the trailing ``rest`` (replicated under TP)."""
        import jax

        if self._mesh is None:
            return jax.jit(inner, donate_argnums=(0,))

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def local(cache_st, vars_st, *rest):
            cache = jax.tree.map(lambda a: a[0], cache_st)
            variables = jax.tree.map(lambda a: a[0], vars_st)
            sharded = [jax.tree.map(lambda a: a[0], t)
                       for t in rest[:n_model_args]]
            cache2, out = inner(cache, variables, *sharded,
                                *rest[n_model_args:])
            return jax.tree.map(lambda a: a[None], cache2), out

        return jax.jit(
            shard_map(
                local, mesh=self._mesh,
                in_specs=(P("model"), P("model"))
                + (P("model"),) * n_model_args
                + (P(),) * n_plain_args,
                out_specs=(P("model"), P()),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )

    def _pool_exhausted_error(self) -> RuntimeError:
        # blocks_in_use counts slot-referenced blocks only; cached
        # (trie-held, refcount 0) blocks make the arithmetic add up —
        # without them "20/32 in use" on a full pool reads like a lie.
        cached = self._alloc.blocks_cached()
        return RuntimeError(
            "paged KV pool exhausted mid-stream: "
            f"{self._alloc.blocks_in_use}/"
            f"{self._alloc.num_blocks - 1} blocks in use"
            + (f" (+{cached} trie-cached)" if cached else "")
            + " — size num_blocks for the resident-token worst case "
            "or admit fewer concurrent requests"
        )

    def _seeds_device(self):
        """The per-slot request-seed vector as a cached device array —
        re-uploaded only when an admission/release changed a seed (same
        H2D discipline as the block tables and tenant rows: the decode
        loop must not pay an H2D right after its D2H token sync)."""
        import jax.numpy as jnp

        if self._seeds_dev is None or self._seeds_dev_ver != self._seeds_ver:
            self._seeds_dev = jnp.asarray(self._seeds)
            self._seeds_dev_ver = self._seeds_ver
        return self._seeds_dev

    def _set_slot_seed(self, slot: int, seed) -> None:
        """Commit a slot's request seed (admission / KV import / release
        hygiene), bumping the H2D version only on an actual change."""
        seed = np.int32(0 if seed is None else int(seed))
        if self._seeds[slot] != seed:
            self._seeds[slot] = seed
            self._seeds_ver += 1

    def _sample(self, logits, seeds, counters):
        """Shared sampling tail of every serving program: greedy argmax
        at temperature 0 (``seeds``/``counters`` are then dead arguments
        XLA drops — the compiled grids stay bitwise the pre-sampling
        programs); otherwise ONE counter-keyed categorical per row — row
        ``i`` draws with ``fold_in(fold_in(base_key, seeds[i]),
        counters[i])`` (:func:`~chainermn_tpu.models.transformer.
        stream_sample_keys`), so the token depends only on (request
        seed, absolute position, logits) — never on which program or
        tick asked, which is the whole bit-identical-stream argument."""
        import jax
        import jax.numpy as jnp

        from chainermn_tpu.models.transformer import (
            _tempered_filtered,
            stream_sample_keys,
        )

        if self.temperature > 0.0:
            keys = stream_sample_keys(self._base_key, seeds, counters)
            return jax.vmap(jax.random.categorical)(
                keys,
                _tempered_filtered(logits, self.temperature, self.top_k,
                                   self.top_p),
            ).astype(jnp.int32)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _build_decode_step(self):
        model = self._decode_model

        if self._use_adapters:
            def inner(cache, variables, ad, tokens, positions, tables,
                      rows, seeds):
                logits, mutated = model.apply(
                    {**variables, "cache": cache}, tokens[:, None],
                    train=False, decode=True, decode_positions=positions,
                    block_tables=tables, mutable=["cache"],
                    adapters=_gather_adapter_rows(ad, rows),
                )
                # Slot s holds `positions[s]` tokens; this step samples
                # the token at that absolute position + 1 → counter.
                return mutated["cache"], self._sample(
                    logits[:, 0], seeds, positions + 1)

            return self._tp_jit(inner, 5, n_model_args=1)

        def inner(cache, variables, tokens, positions, tables, seeds):
            logits, mutated = model.apply(
                {**variables, "cache": cache}, tokens[:, None],
                train=False, decode=True, decode_positions=positions,
                block_tables=tables, mutable=["cache"],
            )
            return mutated["cache"], self._sample(
                logits[:, 0], seeds, positions + 1)

        return self._tp_jit(inner, 4)

    def _build_verify_step(self):
        """The speculative verify program: ONE forward scores
        ``[slots, K+1]`` positions — the pending last token plus K
        drafts per row, written/attended at per-row position spans
        (``_slot_decode_attend`` with ``T = K+1``) — and returns the
        model's OWN token at every position: greedy argmax at
        temperature 0, the counter-keyed sample otherwise (cell
        ``(s, j)`` uses counter ``positions[s] + j + 1``, the absolute
        index of the token that cell emits — exactly the key sequential
        decode would use there, which is what makes sampled acceptance
        the rejection-sampling rule, see :func:`~chainermn_tpu.serving.
        speculate.rejection_accept_length`). Acceptance, rollback,
        and padding are HOST decisions (:meth:`verify_step`): the
        compiled program is one fixed shape across request churn and
        any acceptance outcome, and under TP it carries exactly the
        same 2 all-reduces per layer as the one-token step (the
        amortization the suite pins by HLO count)."""
        import jax.numpy as jnp

        model = self._decode_model

        def grid_sample(logits, positions, seeds):
            if self.temperature <= 0.0:  # bitwise the pre-sampling grid
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            S, T = logits.shape[:2]
            counters = positions[:, None] + jnp.arange(
                1, T + 1, dtype=positions.dtype)[None, :]
            return self._sample(
                logits.reshape(S * T, -1),
                jnp.repeat(seeds, T), counters.reshape(S * T),
            ).reshape(S, T)

        if self._use_adapters:
            def inner(cache, variables, ad, tokens, positions, tables,
                      rows, seeds):
                logits, mutated = model.apply(
                    {**variables, "cache": cache}, tokens,
                    train=False, decode=True, decode_positions=positions,
                    block_tables=tables, mutable=["cache"],
                    adapters=_gather_adapter_rows(ad, rows),
                )
                return mutated["cache"], grid_sample(
                    logits, positions, seeds)  # [slots, K+1]

            return self._tp_jit(inner, 5, n_model_args=1)

        def inner(cache, variables, tokens, positions, tables, seeds):
            logits, mutated = model.apply(
                {**variables, "cache": cache}, tokens,  # [slots, K+1]
                train=False, decode=True, decode_positions=positions,
                block_tables=tables, mutable=["cache"],
            )
            return mutated["cache"], grid_sample(
                logits, positions, seeds)  # [slots, K+1]

        return self._tp_jit(inner, 4)

    def _build_mixed_step(self):
        """The chunked-prefill MIXED step (ISSUE 11 tentpole): ONE
        forward over a fixed ``[slots, T]`` grid, ``T = max(chunk,
        K+1)``, through the same per-row position spans as the verify
        step (``_slot_decode_attend``) — fill rows write up to
        ``chunk`` REAL prompt tokens at their true positions, decode
        rows carry ``[last_tok, drafts..., pad]``, inactive/stalled
        rows carry pads whose writes land in scratch or in blocks the
        next real write re-covers before any causal mask admits them
        (the speculative-rollback staleness argument, reused). Which
        rows chunk vs decode is HOST metadata, so the jit cache stays
        at one entry across every chunk/decode occupancy mix — and
        under TP the program carries exactly the same 2 all-reduces
        per layer as the one-token step (pinned by HLO count).
        Sampling runs per grid position with the cell's COUNTER key
        (cell ``(s, j)`` emits the token at absolute index
        ``positions[s] + j + 1`` and uses exactly that counter — the
        final chunk's boundary cell lands on counter ``P_len``, the
        same key the monolithic prefill uses): at temperature 0 that
        is the verify step's greedy-argmax grid, which is what
        acceptance and the chunk boundary token both read."""
        import jax.numpy as jnp

        model = self._decode_model

        def grid_sample(logits, positions, seeds):
            if self.temperature <= 0.0:  # bitwise the pre-sampling grid
                return jnp.argmax(logits, axis=-1).astype(jnp.int32)
            S, T = logits.shape[:2]
            counters = positions[:, None] + jnp.arange(
                1, T + 1, dtype=positions.dtype)[None, :]
            return self._sample(
                logits.reshape(S * T, -1),
                jnp.repeat(seeds, T), counters.reshape(S * T),
            ).reshape(S, T)

        if self._use_adapters:
            def inner(cache, variables, ad, tokens, positions, tables,
                      rows, seeds):
                logits, mutated = model.apply(
                    {**variables, "cache": cache}, tokens,  # [slots, T]
                    train=False, decode=True, decode_positions=positions,
                    block_tables=tables, mutable=["cache"],
                    adapters=_gather_adapter_rows(ad, rows),
                )
                return mutated["cache"], grid_sample(
                    logits, positions, seeds)  # [slots, T]

            return self._tp_jit(inner, 5, n_model_args=1)

        def inner(cache, variables, tokens, positions, tables, seeds):
            logits, mutated = model.apply(
                {**variables, "cache": cache}, tokens,  # [slots, T]
                train=False, decode=True, decode_positions=positions,
                block_tables=tables, mutable=["cache"],
            )
            return mutated["cache"], grid_sample(
                logits, positions, seeds)  # [slots, T]

        return self._tp_jit(inner, 4)

    def _build_cow_copy(self):
        """The copy-on-write block copy: ONE jitted program copying one
        physical block (src -> dst) in every layer's K and V pool
        (:func:`chainermn_tpu.ops.paged_kv.copy_block`). Routed through
        the same ``_tp_jit`` wrapper as the serving programs so the
        cache stays donated and, under TP, each shard copies its own
        slice — zero collectives, one compile for any block pair (the
        jit-cache pin extends over COW churn)."""
        import jax

        from chainermn_tpu.ops.paged_kv import copy_block

        def inner(cache, variables, src, dst):
            del variables
            cache2 = jax.tree.map(
                lambda pool: copy_block(pool, src, dst), cache
            )
            return cache2, src

        return self._tp_jit(inner, 2)

    def _cow_protect(self, slot: int, start: int, n_positions: int,
                     strict: bool = True) -> Optional[int]:
        """Copy-on-write guard for a device write span ``[start, start +
        n_positions)`` of ``slot``: any covered block that another slot
        references — or the prefix trie caches — is copied to a fresh
        block and the WRITER's table repointed before the write program
        runs (host rewrite for this slot only; readers and the trie's
        pristine copy untouched). Partial tail blocks are never shared,
        so in practice this fires on the boundary block of a full-prefix
        hit and is a no-op everywhere else. Returns blocks copied; on
        genuine pool exhaustion raises when ``strict`` (the decode/
        verify paths, where the slot already holds tokens) and returns
        None when not (the join path defers the admission instead —
        the copy needs ONE block beyond what ``ensure`` reserved)."""
        if self._prefix is None or n_positions <= 0:
            return 0
        import jax.numpy as jnp

        alloc = self._alloc
        bs = alloc.block_size
        # Read the live table row, no defensive copy: this guard runs
        # per active slot per decode/verify tick and is a no-op outside
        # the join boundary (partial tails are never shared).
        owned = alloc._owned[slot]
        first = start // bs
        last = min(-(-(start + n_positions) // bs), len(owned))
        copied = 0
        for j in range(first, last):
            blk = owned[j]
            if not alloc.shared_for_write(blk):
                continue
            fresh = alloc.alloc_block()
            if fresh is None:
                if strict:
                    raise self._pool_exhausted_error()
                return None
            self._cache, _ = self._cow_copy_jit(
                self._cache, self._vars,
                jnp.int32(blk), jnp.int32(fresh),
            )
            alloc.cow_replace(slot, j, fresh)
            copied += 1
        if copied:
            self.prefix_stats["cow_blocks"] += copied
        return copied

    def _prefill_fn(self, bucket: int):
        """The (cached) prefill program for one bucket length. ``start``
        is a traced per-call scalar — position of the bucket's FIRST
        token — so the same compiled program serves a from-scratch
        prefill (start 0) and a prefix-cache tail prefill that begins
        at the first unshared position (ISSUE 7): compile count stays
        bounded by the bucket ladder either way."""
        if bucket in self._prefill_jits:
            return self._prefill_jits[bucket]
        import jax.numpy as jnp

        model = self._decode_model

        if self._use_adapters:
            def inner(cache, variables, ad, tokens, true_len, start,
                      slot, table_row, rows, seed):
                logits, mutated = model.apply(
                    {**variables, "cache": cache}, tokens,
                    train=False, decode=True,
                    decode_positions=start,
                    block_tables=table_row, decode_slots=slot,
                    mutable=["cache"],
                    adapters=_gather_adapter_rows(ad, rows),
                )
                last = jnp.take(logits[0], true_len - 1, axis=0)  # [V]
                # The first generated token sits at absolute position
                # start + true_len → its sampling counter (start is 0
                # for a from-scratch prefill, the resume depth for a
                # trie-tail or re-prefill — which is exactly why a
                # resumed stream redraws the SAME token here).
                return mutated["cache"], self._sample(
                    last[None], seed, start + true_len)[0]

            fn = self._tp_jit(inner, 7, n_model_args=1)
        else:
            def inner(cache, variables, tokens, true_len, start, slot,
                      table_row, seed):
                logits, mutated = model.apply(
                    {**variables, "cache": cache}, tokens,
                    train=False, decode=True,
                    decode_positions=start,
                    block_tables=table_row, decode_slots=slot,
                    mutable=["cache"],
                )
                last = jnp.take(logits[0], true_len - 1, axis=0)  # [V]
                return mutated["cache"], self._sample(
                    last[None], seed, start + true_len)[0]

            fn = self._tp_jit(inner, 6)
        self._prefill_jits[bucket] = fn
        return fn

    def _seq_prefill_fn(self, t_pad: int):
        """The (cached) sequence-parallel prefill program for one padded
        length ``t_pad`` (a bucket rounded up to the shard count — the
        compile count stays bounded by the bucket ladder).

        ONE ``shard_map`` over the mesh's ``model`` axis: tokens arrive
        sequence-sharded ``[1, t_pad/n]`` per shard; the resident TP
        param stacks are all-gathered and reassembled IN-PROGRAM
        (:func:`unshard_lm_params` — full weights exist only transiently,
        no 2x-params replica in HBM); each shard runs its slice through
        the base model with global rope/learned positions and
        ``sow_kv=True``; per layer, one ``all_to_all`` reshards the sown
        K/V heads<->sequence into exactly the TP cache layout (all
        positions x local kv heads) and scatters it at true positions
        (``paged_update`` redirects pad overhang to scratch; dense
        scatters drop out-of-bounds rows — the monolithic path's own
        staleness contract); the last TRUE position's logits are
        psum-selected across shards and fed to the same sampling tail
        as the monolithic prefill — greedy argmax at temperature 0, the
        counter-keyed sample (counter ``true_len``, every shard derives
        the identical replicated key) otherwise — for the first token.
        The cache is donated, so the chain hands off to decode without
        a copy."""
        if t_pad in self._seq_prefill_jits:
            return self._seq_prefill_jits[t_pad]
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from chainermn_tpu.ops.paged_kv import paged_update

        base = self._seq_base_model
        base_model = self._base_model
        paged = self._alloc is not None

        def local(cache_st, vars_st, tokens, true_len, slot, table_row,
                  seed):
            cache = jax.tree.map(lambda a: a[0], cache_st)
            stacked = jax.tree.map(
                lambda a: jax.lax.all_gather(
                    a[0], "model", axis=0, tiled=False
                ),
                vars_st,
            )
            full = unshard_lm_params(base_model, stacked)
            Tl = tokens.shape[1]
            my = jax.lax.axis_index("model")
            pos = my * Tl + jnp.arange(Tl, dtype=jnp.int32)
            logits, mut = base.apply(
                full, tokens, positions=pos, train=False,
                mutable=["kv_out"],
            )
            # first generated token = the monolithic prefill's sampling
            # tail over the psum-assembled last-TRUE-position logits:
            # argmax at temperature 0, else the counter-keyed sample at
            # counter true_len (seed/true_len/psum row are replicated,
            # so every shard derives the identical key and token).
            j = true_len - 1
            row = jnp.where(
                (j // Tl) == my,
                logits[0, j % Tl].astype(jnp.float32), 0.0,
            )
            full_row = jax.lax.psum(row, "model")
            tok = self._sample(
                full_row[None], seed,
                jnp.reshape(true_len, (1,)).astype(jnp.int32),
            )[0].astype(jnp.int32)
            new_cache = dict(cache)
            for blk, kv in mut["kv_out"].items():
                entry = dict(cache[blk])
                for src, dst in (("k", "key"), ("v", "value")):
                    sh = jax.lax.all_to_all(
                        kv[src][0], "model", split_axis=2,
                        concat_axis=1, tiled=True,
                    )  # [1, t_pad, kvh/n, dh] — the TP cache layout
                    if paged:
                        pool = entry[f"pool_{dst}"]
                        entry[f"pool_{dst}"] = paged_update(
                            pool, table_row,
                            jnp.zeros((1,), jnp.int32),
                            sh.astype(pool.dtype),
                        )
                    else:
                        cols = jnp.arange(t_pad, dtype=jnp.int32)
                        entry[f"cached_{dst}"] = (
                            entry[f"cached_{dst}"]
                            .at[slot[:, None], cols[None, :]]
                            .set(sh.astype(entry[f"cached_{dst}"].dtype))
                        )
                new_cache[blk] = entry
            return jax.tree.map(lambda a: a[None], new_cache), tok

        fn = jax.jit(
            shard_map(
                local, mesh=self._mesh,
                in_specs=(P("model"), P("model"), P(None, "model"),
                          P(), P(), P(), P()),
                out_specs=(P("model"), P()),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )
        self._seq_prefill_jits[t_pad] = fn
        return fn

    # ------------------------------------------------------------------
    # serving surface

    @property
    def n_active(self) -> int:
        return int(self._active.sum())

    @property
    def free_slot_count(self) -> int:
        return len(self._free)

    @property
    def n_filling(self) -> int:
        """Slots admitted by ``chunked_join`` still writing prompt KV."""
        return len(self._pending_fill)

    def occupancy(self) -> float:
        return self.n_active / self.num_slots

    def pool_utilization(self) -> Optional[float]:
        return self._alloc.utilization() if self._alloc else None

    def _publish_pool_gauges(self) -> None:
        """Direct KV-pool gauges (ISSUE 6): the block allocator is
        state with no trace events — refresh free/leased on every
        mutation point (join / leave / per-step growth). One global
        read when the metrics plane is off."""
        from chainermn_tpu.observability import metrics

        reg = metrics.active_registry()
        if reg is None:
            return
        if self._alloc is None:
            self._publish_adapter_gauges(reg)
            return
        reg.gauge("kv_blocks_free",
                  "allocatable KV pool blocks currently free").set(
            self._alloc.free_blocks)
        reg.gauge("kv_blocks_leased",
                  "KV pool blocks owned by slots").set(
            self._alloc.blocks_in_use)
        if self._prefix is not None:
            reg.gauge("kv_blocks_shared",
                      "KV pool blocks referenced by more than one "
                      "slot's table (prefix sharing)").set(
                self._alloc.blocks_shared())
            reg.gauge("kv_blocks_cached",
                      "trie-cached KV blocks no slot references (an "
                      "upper bound on reclaimable — a live descendant "
                      "pins its cached ancestors)").set(
                self._alloc.blocks_cached())
        self._publish_adapter_gauges(reg)

    def _publish_adapter_gauges(self, reg) -> None:
        """Adapter-bank gauges (ISSUE 14): residency + per-tenant slot
        occupancy, tenant-labeled (the live-SLO surface;
        ``tools/metrics_dump.py --label tenant=<id>`` filters on
        exactly this label). No-op without a bank."""
        if self.adapter_bank is None:
            return
        reg.gauge("adapter_bank_residents",
                  "tenants with a registered adapter row").set(
            len(self.adapter_bank.residents()))
        reg.gauge("adapter_bank_free_rows",
                  "unclaimed adapter rows in the bank").set(
            self.adapter_bank.free_rows)
        counts: dict = {}
        for t in self._tenant_ids:
            if t is not None:
                counts[t] = counts.get(t, 0) + 1
        for t in self.adapter_bank.residents():
            reg.gauge("serving_tenant_active_slots",
                      "slots currently serving a tenant").set(
                counts.get(t, 0), tenant=str(t))

    def prefix_trie_blocks(self) -> Optional[int]:
        """Blocks held by the prefix trie (None when sharing is off) —
        the scheduler's trie-size gauge."""
        return self._prefix.n_nodes if self._prefix is not None else None

    def prefix_evictions(self) -> int:
        """Lifetime trie evictions (0 when sharing is off)."""
        return self._prefix.evictions if self._prefix is not None else 0

    def decode_compile_count(self) -> Optional[int]:
        """Compilations of the steady-state step (the no-recompile pin:
        must stay 1 across any join/leave churn)."""
        size = getattr(self._decode_step_jit, "_cache_size", None)
        return int(size()) if size else None

    def verify_compile_count(self) -> Optional[int]:
        """Compilations of the speculative verify step (same pin as the
        plain step: must stay 1 across churn AND acceptance variation).
        None when speculation is off or the runtime hides the cache."""
        if self._verify_step_jit is None:
            return None
        size = getattr(self._verify_step_jit, "_cache_size", None)
        return int(size()) if size else None

    def mixed_compile_count(self) -> Optional[int]:
        """Compilations of the chunked-prefill mixed step (the ISSUE 11
        pin: must stay 1 across every chunk/decode occupancy mix).
        None when chunking is off or the runtime hides the cache."""
        if self._mixed_step_jit is None:
            return None
        size = getattr(self._mixed_step_jit, "_cache_size", None)
        return int(size()) if size else None

    def prefill_compile_count(self) -> Optional[int]:
        sizes = [getattr(f, "_cache_size", None)
                 for f in self._prefill_jits.values()]
        if any(s is None for s in sizes):
            return None
        return int(sum(s() for s in sizes))

    def prefill_join(self, prompt, tenant_id: Optional[str] = None,
                     seed: Optional[int] = None):
        """Admit one request: claim a slot, run bucketed prefill, return
        ``(slot, first_token, bucket)`` — or None when no slot (or,
        paged, not enough pool blocks) is available right now (the
        scheduler retries later; host state is untouched on refusal).

        ``tenant_id`` (ISSUE 14) selects the slot's adapter row (the
        bank must hold the tenant — unknown tenants raise loudly rather
        than silently serve the base model) and namespaces the
        prefix-trie consultation: one tenant's cached blocks can never
        adopt into another's stream.

        ``seed`` is the request's sampling-stream seed (counter-based
        keys: token ``i`` draws with ``fold_in(fold_in(base_key, seed),
        i)``); ``None`` means stream 0. The scheduler derives one per
        request (``crc32(request_id)``) and re-passes the SAME value on
        resume/migration, which is what keeps a moved sampled stream
        ONE stream. Ignored at temperature 0.

        With the prefix cache on (ISSUE 7) the join first consults the
        trie: the longest matching FULL-block chain is adopted into the
        slot's table (refcounts, no copy) and the prefill runs only the
        unshared tail at its true start position — bucketed by the TAIL
        length, so a full-hit request's prefill shrinks to one token.
        The bucket of the RUN prefill is returned (the scheduler's
        event field measures exactly the work done). A full-block-exact
        hit re-feeds the last prompt token (logits need a forward), and
        the write at that boundary position triggers the copy-on-write
        path (:meth:`_cow_protect`) — the one place a shared block is
        ever written toward.
        """
        import jax.numpy as jnp

        res = self._admit_common(prompt, tenant_id, seed)
        if res is None:
            return None
        slot, prompt, P_len, tail_start, tail_len, _matched, _cow = res
        bucket = bucket_length(tail_len, self._buckets)
        self.last_prefill_seq_parallel = False

        # Sequence-parallel path (ISSUE 13): a cache-MISS prompt
        # (tail_start == 0 — on a trie hit the tail's context lives in
        # adopted blocks the sharded forward cannot see, so the
        # monolithic tail prefill runs; it is also already short) whose
        # shard-rounded bucket fits the horizon goes wide over the
        # 'model' partition.
        if self.prefill_seq_parallel and tail_start == 0:
            t_pad = -(-bucket // self._tp_n) * self._tp_n
            if t_pad <= self.max_len:
                return self._seq_prefill_run(
                    slot, prompt, P_len, tail_len, t_pad, bucket
                )

        padded = np.full((1, bucket), self.pad_id, np.int32)
        padded[0, :tail_len] = prompt[tail_start:]
        fn = self._prefill_fn(bucket)
        self._cache, tok = fn(*self._step_args(
            jnp.asarray(padded),
            jnp.int32(tail_len),
            jnp.full((1,), tail_start, jnp.int32),
            jnp.asarray([slot], jnp.int32),
            jnp.asarray(self._dummy_tables()[slot:slot + 1]),
            tail=(jnp.asarray(self._seeds[slot:slot + 1]),),
            tenant_rows=jnp.asarray(self._tenant_rows[slot:slot + 1]),
        ))
        tok = int(tok)
        self._positions[slot] = P_len
        self._last_tok[slot] = tok
        self._active[slot] = True
        self._history[slot] = [int(t) for t in prompt] + [tok]
        self._publish_full_blocks(slot, prompt, P_len)
        self._publish_pool_gauges()
        return slot, tok, bucket

    def _seq_prefill_run(self, slot, prompt, P_len, tail_len, t_pad,
                         bucket):
        """The sequence-parallel half of :meth:`prefill_join`: run the
        sharded forward (:meth:`_seq_prefill_fn`), then commit the SAME
        host metadata the monolithic join commits — the stream is
        indistinguishable downstream (that is the guarantee)."""
        import jax.numpy as jnp

        fn = self._seq_prefill_fn(t_pad)
        padded = np.full((1, t_pad), self.pad_id, np.int32)
        padded[0, :tail_len] = prompt
        self._cache, tok = fn(
            self._cache, self._vars, jnp.asarray(padded),
            jnp.int32(tail_len), jnp.asarray([slot], jnp.int32),
            jnp.asarray(self._dummy_tables()[slot:slot + 1]),
            jnp.asarray(self._seeds[slot:slot + 1]),
        )
        tok = int(tok)
        self._positions[slot] = P_len
        self._last_tok[slot] = tok
        self._active[slot] = True
        self._history[slot] = [int(t) for t in prompt] + [tok]
        self.last_prefill_seq_parallel = True
        self._publish_full_blocks(slot, prompt, P_len)
        self._publish_pool_gauges()
        return slot, tok, bucket

    def seq_prefill_compile_count(self) -> Optional[int]:
        """Compilations of the sequence-parallel prefill programs —
        bounded by the shard-rounded bucket ladder, like the monolithic
        prefill's. None when the path is off or the runtime hides the
        cache."""
        if not self._seq_prefill_jits:
            return None if not self.prefill_seq_parallel else 0
        sizes = [getattr(f, "_cache_size", None)
                 for f in self._seq_prefill_jits.values()]
        if any(s is None for s in sizes):
            return None
        return int(sum(s() for s in sizes))

    def _publish_full_blocks(self, slot: int, tokens,
                             n_positions: int) -> None:
        """Insert ``slot``'s FULL blocks covering the WRITTEN positions
        ``[0, n_positions)`` into the prefix trie — the ONE publish
        rule every path shares (prefill/fill completion, import_kv
        adoption, preemption): an adopted prefix walks existing nodes,
        only fresh full blocks add nodes, and the partial tail block is
        never inserted (the next write targets it). Inserts under the
        SLOT's tenant namespace (ISSUE 14): publication is as tenant-
        scoped as adoption, so cross-tenant block sharing is
        structurally impossible. No-op with sharing off."""
        if self._prefix is None:
            return
        bs = self._alloc.block_size
        full = int(n_positions) // bs
        if full:
            self._prefix.insert(
                [int(t) for t in tokens[:full * bs]],
                self._alloc.owned_blocks(slot)[:full],
                namespace=self._tenant_ids[slot],
            )

    def _admit_common(self, prompt, tenant_id: Optional[str] = None,
                      seed: Optional[int] = None):
        """Shared admission front half of :meth:`prefill_join` and
        :meth:`chunked_join`: validate the prompt (and, ISSUE 14, the
        tenant — its adapter row must be resident BEFORE any state
        mutates), consult the prefix trie under the TENANT's namespace,
        reserve the slot's pool blocks for the whole prompt plus
        the first decode write, COW-protect the unshared tail's
        boundary, commit the slot (tenant row + bank pin + sampling
        seed included) and account the admission. Returns
        ``(slot, prompt, P_len, tail_start, tail_len, matched, cow)``
        with the slot POPPED from the free list, or None to defer (host
        state untouched — the scheduler retries). ``last_prefix_info``
        is (re)set here, so both join flavours feed the same
        ``prefix_cache`` event."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        P_len = int(prompt.shape[0])
        if P_len < 1:
            raise ValueError("empty prompt")
        if P_len >= self.max_len:
            raise ValueError(
                f"prompt of {P_len} tokens leaves no room to generate "
                f"within max_len={self.max_len}"
            )
        row = 0
        if self.adapter_bank is not None:
            if self.adapter_impl == "merged":
                if tenant_id != self.merged_tenant:
                    raise ValueError(
                        f"this engine serves the merged tenant "
                        f"{self.merged_tenant!r} only — got "
                        f"{tenant_id!r} (route other tenants to a "
                        "gather-mode engine)"
                    )
            else:
                row = self.adapter_bank.row_of(tenant_id)
        if not self._free:
            return None
        slot = self._free[-1]  # peek; commit only after alloc succeeds
        self.last_prefix_info = None
        matched: list[int] = []
        if self._prefix is not None:
            matched = self._prefix.lookup(prompt, namespace=tenant_id)
            if len(matched) < self._min_shared_blocks:
                matched = []
        hit_tokens = len(matched) * (self._alloc.block_size
                                     if self._alloc else 0)
        # The tail must carry at least the LAST prompt token — its
        # logits sample the first generated token — so a hit covering
        # the whole prompt re-feeds one token into the boundary block.
        tail_start = min(hit_tokens, P_len - 1)
        tail_len = P_len - tail_start
        if self._alloc is not None:
            # Reserve only the REAL tokens plus the first decode write
            # (position P_len) — NOT the padded bucket: pad writes
            # beyond the reservation land in the scratch block by the
            # layout contract, and decode grows blocks incrementally,
            # so reserving bucket-width here would silently defeat the
            # oversubscription the pool exists for (review finding:
            # a prompt that falls back to the max_len bucket would
            # demand the whole horizon up front). Adoption precedes the
            # tail ensure (table order = position order); a refused
            # ensure rolls the adoption back via release — all-or-
            # nothing, as before.
            # A free slot's table row is all-scratch, so a rolled-back
            # deferral restores the EXACT prior table — restore the
            # version too, or every scheduler retry would invalidate
            # the engine's cached device tables and pay a full H2D
            # re-upload right after the decode loop's D2H (the
            # degradation trap the version key exists to avoid).
            v0 = self._alloc.version
            self._alloc.adopt(slot, matched)
            if not self._alloc.ensure(slot, P_len + 1):
                self._alloc.release(slot)
                self._alloc.version = v0
                return None
            # The boundary-block COW needs ONE block beyond ensure's
            # reservation; under genuine exhaustion defer the admission
            # (release rolls the adoption AND any copy back) — never an
            # error a cache-off engine wouldn't have raised.
            cow = self._cow_protect(slot, tail_start, tail_len,
                                    strict=False)
            if cow is None:
                self._alloc.release(slot)
                self._alloc.version = v0
                return None
        else:
            cow = 0
        self._free.pop()
        # Sampling-seed commit: the slot's counter-based key stream —
        # host metadata + one versioned H2D, like the tenant row below.
        self._set_slot_seed(slot, seed)
        # Tenant commit (ISSUE 14): the slot's adapter row + bank pin +
        # trie namespace — host metadata only, like everything above.
        self._tenant_ids[slot] = tenant_id
        if self._use_adapters:
            self.adapter_bank.pin(tenant_id)
            if self._tenant_rows[slot] != row:
                self._tenant_rows[slot] = row
                self._tenant_rows_ver += 1

        # Lifetime accounting covers ADMITTED requests only — a deferred
        # admission is retried by the scheduler, and counting each retry
        # would dilute the hit-rate gauge with duplicates.
        if self._prefix is not None:
            self.prefix_stats["lookups"] += 1
            self.prefix_stats["prompt_tokens"] += P_len
            self.prefix_stats["prefill_tokens"] += tail_len
        if matched:
            self.prefix_stats["hits"] += 1
            self.prefix_stats["hit_tokens"] += hit_tokens
        if self._prefix is not None:
            self.last_prefix_info = {
                "prompt_tokens": P_len,
                "hit_blocks": len(matched),
                "hit_tokens": hit_tokens,
                "prefill_tokens": tail_len,
                "cow_blocks": cow,
            }
        return slot, prompt, P_len, tail_start, tail_len, matched, cow

    def chunked_join(self, prompt, tenant_id: Optional[str] = None,
                     seed: Optional[int] = None):
        """Admit one request for CHUNKED prefill (``prefill_chunk > 0``,
        ISSUE 11): claim the slot and reserve its blocks EXACTLY like
        :meth:`prefill_join` — trie adoption, whole-prompt ensure,
        boundary-block COW — but run no forward here. The prompt's
        unshared tail is written ``prefill_chunk`` tokens per
        :meth:`mixed_step` tick while the remaining slots decode; the
        final chunk samples the first generated token and activates the
        slot. Returns the slot, or None to defer (host state untouched
        — the scheduler retries; same deferral contract as the
        monolithic join)."""
        if self.prefill_chunk <= 0:
            raise RuntimeError(
                "chunked_join needs prefill_chunk > 0 — use prefill_join"
            )
        res = self._admit_common(prompt, tenant_id, seed)
        if res is None:
            return None
        slot, prompt, P_len, tail_start, tail_len, _matched, _cow = res
        self._pending_fill[slot] = {
            "prompt": prompt, "pos": tail_start, "P_len": P_len,
            "chunks": 0,
        }
        self._publish_pool_gauges()
        return slot

    def decode_step(self):
        """One fused decode step over ALL slots. Returns ``(tokens,
        dur_s)`` — ``tokens[s]`` is slot ``s``'s next token (garbage for
        inactive slots; callers consult their own active set). Host
        metadata for active slots advances by one position."""
        import jax.numpy as jnp

        active = np.flatnonzero(self._active)
        for s in active:
            p = int(self._positions[s])
            if p + 1 > self.max_len:
                raise RuntimeError(
                    f"slot {int(s)} ran past the serving horizon "
                    f"max_len={self.max_len}; bound max_new_tokens"
                )
            if self._alloc is not None and not self._alloc.ensure(
                int(s), p + 1
            ):
                raise self._pool_exhausted_error()
            # COW guard (ISSUE 7): the write at position p must not land
            # in a block another slot or the trie still reads.
            self._cow_protect(int(s), p, 1)
        t0 = time.perf_counter()
        self._cache, toks = self._decode_step_jit(*self._step_args(
            jnp.asarray(self._last_tok, jnp.int32),
            jnp.asarray(self._positions, jnp.int32),
            self._tables_device(),
            tail=(self._seeds_device(),),
        ))
        toks = np.asarray(toks)  # device sync: honest per-step latency
        dur = time.perf_counter() - t0
        self._publish_pool_gauges()
        self._last_tok[active] = toks[active]
        self._positions[active] += 1
        for s in active:
            self._history[int(s)].append(int(toks[s]))
        return toks, dur

    def verify_step(self):
        """One speculative tick over ALL slots: draft up to K tokens per
        active slot from its own history, score every draft in ONE
        jitted verify forward, and commit the longest greedy-matching
        prefix plus the model's own next token.

        Returns ``(committed, dur_s, stats)``: ``committed[slot]`` is
        the list of 1..K+1 tokens slot ``slot`` advanced by this tick
        (every one of them a token the verify forward itself produced —
        argmax at temperature 0, the counter-keyed sample otherwise —
        so the stream is bit-identical to the plain path in BOTH modes;
        sampled acceptance is the rejection-sampling rule,
        :func:`~chainermn_tpu.serving.speculate.
        rejection_accept_length`); ``stats`` carries
        ``drafted``/``accepted`` token counts, the per-slot
        ``accept_lens`` and the sampling ``mode`` — the scheduler's
        ``speculate`` trace event.

        Rollback is HOST metadata only: rejected drafts leave their
        (stale) cache writes in place — positions are explicit, so the
        next tick's span ``[new_pos, new_pos+K]`` re-writes every stale
        row before any causal mask can admit it, and the jit cache stays
        pinned at one entry across churn and acceptance variation.
        Near the horizon (or when an oversubscribed paged pool cannot
        cover the whole span) acceptance is CAPPED, which costs
        throughput, never correctness.
        """
        import jax.numpy as jnp

        if self.spec_tokens <= 0:
            raise RuntimeError("verify_step needs spec_tokens > 0 — use "
                               "decode_step for the plain path")
        K = self.spec_tokens
        active = [int(s) for s in np.flatnonzero(self._active)]
        # Speculative block reservations are per-tick LEASES, not
        # commitments (review regression): an extension to p+K+1 holds
        # blocks for draft positions that may never be committed, and
        # letting those reservations accumulate across ticks — or
        # letting an earlier slot's optional extension grab the pool's
        # last blocks — would starve another slot of the plain-decode
        # minimum it needs just to make progress, turning a pool that
        # spec_tokens=0 serves fine into a crash. Three ordered passes
        # pin the degrade contract (caps cost throughput, never an
        # error plain decode would not raise):
        #   1. trim every active slot back to its committed frontier
        #      (p+1), returning earlier ticks' unused extensions;
        #   2. guarantee every slot the plain minimum — only genuine
        #      exhaustion (plain decode would also fail) raises;
        #   3. extend to the K-span where the remainder allows; a
        #      refused extension degrades that slot's room — drafted
        #      writes beyond the covered span land in the scratch block
        #      (unallocated table entries) and the acceptance cap keeps
        #      every COMMITTED token inside real blocks.
        if self._alloc is not None:
            for s in active:
                self._alloc.trim(s, int(self._positions[s]) + 1)
        for s in active:
            p = int(self._positions[s])
            if p + 1 > self.max_len:
                raise RuntimeError(
                    f"slot {s} ran past the serving horizon "
                    f"max_len={self.max_len}; bound max_new_tokens"
                )
            if self._alloc is not None and not self._alloc.ensure(
                s, p + 1
            ):
                raise self._pool_exhausted_error()
        room: dict[int, int] = {}
        for s in active:
            p = int(self._positions[s])
            covered = min(p + K + 1, self.max_len)
            if (self._alloc is not None and covered > p + 1
                    and not self._alloc.ensure(s, covered)):
                covered = p + 1
            room[s] = min(K, covered - p - 1, self.max_len - 1 - p)
            # COW guard (ISSUE 7): the whole verify span [p, p+room+1)
            # must write private blocks BEFORE the forward — a rejected
            # draft's stale write must never corrupt a shared ancestor
            # block (rollback stays host-metadata-only and composes).
            self._cow_protect(s, p, room[s] + 1)

        from chainermn_tpu.serving.speculate import (
            accept_length,
            rejection_accept_length,
        )

        accept = (rejection_accept_length if self.temperature > 0.0
                  else accept_length)
        drafts = np.zeros((self.num_slots, K), np.int64)
        prop_len: dict[int, int] = {}
        n_drafted = 0
        for s in active:
            # ask only for what could be accepted (room): near the
            # horizon a full-K proposal would be wasted drafter work
            # (K jitted forwards for a ModelDrafter) and would deflate
            # the accept-rate evidence the tuning cache stores.
            prop = list(
                self._drafter.propose(self._history[s], room[s])
            )[:room[s]]
            prop_len[s] = len(prop)
            n_drafted += len(prop)
            drafts[s, :len(prop)] = prop
        tokens = np.concatenate([self._last_tok[:, None], drafts], axis=1)

        t0 = time.perf_counter()
        self._cache, grid = self._verify_step_jit(*self._step_args(
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(self._positions, jnp.int32),
            self._tables_device(),
            tail=(self._seeds_device(),),
        ))
        grid = np.asarray(grid)  # device sync: honest tick latency
        dur = time.perf_counter() - t0

        committed: dict[int, list[int]] = {}
        accept_lens: list[int] = []
        n_accepted = 0
        for s in active:
            # acceptance never extends past the drafter's TRUE proposal
            # (a zero-padded verify column that happens to match the
            # model's own token would be a correct token, but crediting
            # it as "accepted speculation" would corrupt the tuning
            # signal).
            a = accept(drafts[s], grid[s],
                       min(room[s], prop_len[s]))
            toks = [int(t) for t in grid[s, :a + 1]]
            committed[s] = toks
            accept_lens.append(a)
            n_accepted += a
            self._history[s].extend(toks)
            self._last_tok[s] = toks[-1]
            self._positions[s] += a + 1
        stats = {"drafted": n_drafted, "accepted": n_accepted,
                 "accept_lens": accept_lens,
                 "mode": "sampled" if self.temperature > 0.0
                 else "greedy"}
        self._publish_pool_gauges()
        return committed, dur, stats

    def mixed_step(self, max_fill_rows: Optional[int] = None):
        """One fused chunk+decode tick over ALL slots (ISSUE 11
        tentpole). Fill rows (:meth:`chunked_join` admissions, FIFO)
        write their next ``prefill_chunk`` prompt tokens of KV at their
        true positions; active rows decode one token — or, with
        ``spec_tokens > 0``, draft-and-verify their span — in the SAME
        jitted forward (:meth:`_build_mixed_step`), so a long prompt's
        prefill no longer freezes every in-flight stream for a whole
        monolithic forward: per-tick interference is bounded by the
        chunk width.

        ``max_fill_rows`` caps how many fill rows advance this tick
        (the SLO scheduler's interference bound — host selection only,
        the compiled program never changes); stalled fill rows ride the
        grid as pad rows whose garbage writes land in their own
        reserved blocks and are re-written by the real chunk before
        any causal mask admits them (the speculative-rollback staleness
        argument).

        Returns ``(committed, fills, dur_s, spec_stats)``:
        ``committed[slot]`` = the decode tokens slot advanced by
        (1..K+1, every one a verify-grid token — argmax at temperature
        0, the counter-keyed sample otherwise — bit-identical to the
        plain stream in both modes); ``fills`` = one record per ADVANCED
        fill row
        (``slot``/``chunk`` index/``tokens`` written/``done`` and, on
        the final chunk, ``first_tok`` — the request's first generated
        token, sampled at the last prompt position exactly as the
        monolithic prefill would); ``spec_stats`` = the ``speculate``
        accounting (None when ``spec_tokens == 0``)."""
        import jax.numpy as jnp

        if self._mixed_step_jit is None:
            raise RuntimeError("mixed_step needs prefill_chunk > 0 — "
                               "use decode_step/verify_step")
        T, K, C = self._mixed_T, self.spec_tokens, self.prefill_chunk
        active = [int(s) for s in np.flatnonzero(self._active)]
        # Decode-side block discipline: verify_step's per-tick lease
        # rules verbatim at K > 0; the plain ensure at K == 0. (Fill
        # rows reserved their whole span at admission.)
        if self._alloc is not None and K > 0:
            for s in active:
                self._alloc.trim(s, int(self._positions[s]) + 1)
        for s in active:
            p = int(self._positions[s])
            if p + 1 > self.max_len:
                raise RuntimeError(
                    f"slot {s} ran past the serving horizon "
                    f"max_len={self.max_len}; bound max_new_tokens"
                )
            if self._alloc is not None and not self._alloc.ensure(
                s, p + 1
            ):
                raise self._pool_exhausted_error()
        room: dict[int, int] = {}
        for s in active:
            p = int(self._positions[s])
            if K > 0:
                covered = min(p + K + 1, self.max_len)
                if (self._alloc is not None and covered > p + 1
                        and not self._alloc.ensure(s, covered)):
                    covered = p + 1
                room[s] = min(K, covered - p - 1, self.max_len - 1 - p)
            else:
                room[s] = 0
            self._cow_protect(s, p, room[s] + 1)

        fill_slots = list(self._pending_fill)
        if max_fill_rows is not None:
            fill_slots = fill_slots[:max(0, int(max_fill_rows))]

        tokens = np.full((self.num_slots, T), self.pad_id, np.int64)
        positions = np.zeros(self.num_slots, np.int64)
        drafts = np.zeros((self.num_slots, max(K, 1)), np.int64)
        prop_len: dict[int, int] = {}
        n_drafted = 0
        for s in active:
            positions[s] = self._positions[s]
            tokens[s, 0] = self._last_tok[s]
            if K > 0:
                prop = list(
                    self._drafter.propose(self._history[s], room[s])
                )[:room[s]]
                prop_len[s] = len(prop)
                n_drafted += len(prop)
                for j, t in enumerate(prop):
                    drafts[s, j] = t
                    tokens[s, 1 + j] = t
        chunk_len: dict[int, int] = {}
        for s, st in self._pending_fill.items():
            # Stalled rows keep position = frontier with all-pad tokens:
            # their garbage lands in blocks the real chunk re-writes.
            positions[s] = st["pos"]
            if s in fill_slots:
                n = min(C, st["P_len"] - st["pos"])
                tokens[s, :n] = st["prompt"][st["pos"]:st["pos"] + n]
                chunk_len[s] = n

        t0 = time.perf_counter()
        self._cache, toks = self._mixed_step_jit(*self._step_args(
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32),
            self._tables_device(),
            tail=(self._seeds_device(),),
        ))
        toks = np.asarray(toks)  # device sync: honest tick latency
        dur = time.perf_counter() - t0

        from chainermn_tpu.serving.speculate import (
            accept_length,
            rejection_accept_length,
        )

        accept = (rejection_accept_length if self.temperature > 0.0
                  else accept_length)
        committed: dict[int, list[int]] = {}
        accept_lens: list[int] = []
        n_accepted = 0
        for s in active:
            a = accept(
                drafts[s], toks[s], min(room[s], prop_len[s])
            ) if K > 0 else 0
            take = [int(t) for t in toks[s, :a + 1]]
            committed[s] = take
            if K > 0:
                accept_lens.append(a)
                n_accepted += a
            self._history[s].extend(take)
            self._last_tok[s] = take[-1]
            self._positions[s] += a + 1

        fills: list[dict] = []
        for s in fill_slots:
            st = self._pending_fill[s]
            n = chunk_len[s]
            st["pos"] += n
            st["chunks"] += 1
            done = st["pos"] >= st["P_len"]
            rec = {"slot": s, "chunk": st["chunks"] - 1, "tokens": n,
                   "done": done, "first_tok": None}
            if done:
                # The final chunk's last REAL column sits at position
                # P_len - 1: its grid token is the first generated
                # token, exactly what the monolithic prefill samples.
                first = int(toks[s, n - 1])
                prompt, P_len = st["prompt"], st["P_len"]
                del self._pending_fill[s]
                self._positions[s] = P_len
                self._last_tok[s] = first
                self._active[s] = True
                self._history[s] = [int(t) for t in prompt] + [first]
                self._publish_full_blocks(s, prompt, P_len)
                rec["first_tok"] = first
            fills.append(rec)
        stats = ({"drafted": n_drafted, "accepted": n_accepted,
                  "accept_lens": accept_lens,
                  "mode": "sampled" if self.temperature > 0.0
                  else "greedy"} if K > 0 else None)
        self._publish_pool_gauges()
        return committed, fills, dur, stats

    def preempt(self, slot: int) -> None:
        """Release ``slot`` mid-stream (the SLO scheduler's preemption
        hook, ISSUE 11), first publishing its WRITTEN full blocks into
        the prefix trie (when sharing is on) so a resumed request
        re-adopts its OWN KV through the ordinary trie-hit path and
        re-prefills only the partial tail block — resume costs one
        short prefill, not the whole history. Covers active slots AND
        in-progress chunked fills (their written chunks are cached
        too). Without the prefix cache the resume re-prefills the full
        history — slower, still bit-identical (greedy streams are
        deterministic, and sampled streams re-derive the same counter
        keys: the resumed prefill's first sample uses counter = the
        re-prefilled length, exactly the uninterrupted stream's counter
        at that position — provided the resume re-passes the request's
        ``seed``)."""
        pend = self._pending_fill.pop(slot, None)
        if pend is not None:
            self._publish_full_blocks(slot, pend["prompt"],
                                      int(pend["pos"]))
            self._release_slot(slot)
            return
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self._publish_full_blocks(slot, self._history[slot],
                                  int(self._positions[slot]))
        self.leave(slot)

    # ------------------------------------------------------------------
    # cross-replica KV handoff (ISSUE 8): the engine-side hooks behind
    # chainermn_tpu.serving.cluster.kv_transfer — a prefill replica
    # EXPORTS a slot's finished KV as host numpy blocks, a decode
    # replica IMPORTS them into freshly-allocated blocks of its OWN
    # pool and adopts the slot metadata, so decode starts without
    # re-prefilling. Pure block slicing on the device plane (zero
    # collectives, structurally pinned); everything else is host state.

    def prefix_match_depth(self, prompt,
                           tenant_id: Optional[str] = None) -> int:
        """FULL blocks of ``prompt`` this engine's prefix trie holds
        UNDER ``tenant_id``'s namespace (ISSUE 14) — the router's
        cache-aware placement signal (read-only probe, no LRU touch).
        0 when prefix sharing is off."""
        if self._prefix is None:
            return 0
        return self._prefix.match_depth(
            [int(t) for t in np.asarray(prompt).reshape(-1)],
            namespace=tenant_id,
        )

    def expert_signature(self) -> Optional[tuple]:
        """MoE residency signature (ISSUE 20): ``None`` for a dense
        engine, ``(n_experts, experts_per_shard)`` when this engine's
        mesh hosts the model's expert shards. The router compares
        signatures the way it compares ``kv_signature`` — a dense
        replica cannot serve MoE traffic (it has no expert weights at
        all), so residency is a hard placement filter, not a score."""
        if self.n_experts <= 0:
            return None
        n = (int(self._mesh.shape["model"])
             if self._mesh is not None else 1)
        return (self.n_experts, self.n_experts // n)

    # ------------------------------------------------------------------
    # multi-tenant adapter surface (ISSUE 14)

    def adapter_resident(self, tenant_id: Optional[str]) -> bool:
        """Whether this engine can serve ``tenant_id`` RIGHT NOW — the
        router's adapter-residency placement signal. Bank-less engines
        serve every tenant (base model + namespace isolation only);
        merged engines serve exactly their folded tenant."""
        if self.adapter_bank is None:
            return True
        if self.adapter_impl == "merged":
            return tenant_id == self.merged_tenant
        return self.adapter_bank.resident(tenant_id)

    def _on_adapter_change(self, tenant_id: str) -> None:
        """Bank change hook (ISSUE 14 review finding): cached KV under
        ``tenant_id``'s trie namespace was computed with the PREVIOUS
        weights — a join after a re-registration must re-prefill under
        the current stacks, never adopt stale-adapter blocks (the
        bit-equivalence anchor would silently break)."""
        prefix = getattr(self, "_prefix", None)
        if prefix is not None:
            prefix.drop_namespace(tenant_id)

    def register_adapter(self, tenant_id: str, adapter=None) -> int:
        """Register a tenant on the bank (``adapter=None`` = a zero-
        adapter tenant riding the null row) and refresh the gauges.
        Returns the bank row. The NEXT step's cached upload picks the
        new stacks up (``bank.version``); the compiled programs never
        change — registration churn is host metadata + one H2D."""
        if self.adapter_bank is None:
            raise RuntimeError("this engine has no adapter_bank")
        if self.adapter_impl == "merged":
            raise RuntimeError(
                "a merged engine's weights are folded at construction "
                "— register tenants on a gather-mode engine"
            )
        row = self.adapter_bank.register(tenant_id, adapter)
        self._publish_pool_gauges()
        return row

    def evict_adapter(self, tenant_id: str) -> None:
        """Evict a tenant's row (refused while any slot serves it —
        the bank's refcount contract) and refresh the gauges."""
        if self.adapter_bank is None:
            raise RuntimeError("this engine has no adapter_bank")
        self.adapter_bank.evict(tenant_id)
        self._publish_pool_gauges()

    def tenant_of_slot(self, slot: int) -> Optional[str]:
        """The tenant occupying ``slot`` (None = base/unoccupied)."""
        return self._tenant_ids[slot]

    def kv_blocks_free(self) -> Optional[int]:
        """Free paged-pool blocks (None under dense) — the same number
        the ``kv_blocks_free`` gauge publishes; the router reads it
        before placing work."""
        return self._alloc.free_blocks if self._alloc is not None else None

    def kv_signature(self) -> tuple:
        """Layout fingerprint two engines must share for KV blocks to
        be portable between their pools: decode impl, paged block
        size, and every cache leaf's shape-minus-the-block-axis plus
        dtype (the block axis is ``ndim - 4`` — the pool's block count
        for paged, the slot axis for dense — and MAY differ between
        replicas; a TP stack's leading shard axis is part of the shape,
        so differing TP degrees refuse loudly)."""
        import jax

        leaves = jax.tree.leaves(self._cache)
        axis_sig = tuple(
            (leaf.shape[:leaf.ndim - 4] + leaf.shape[leaf.ndim - 3:],
             str(leaf.dtype))
            for leaf in leaves
        )
        return (self.decode_impl,
                self._alloc.block_size if self._alloc else None,
                self.max_len, axis_sig)

    def _kv_io(self):
        """The two (lazily built) handoff programs: ``extract(cache,
        blk)`` gathers one block across every pool leaf, ``inject
        (cache, blk, payload)`` scatters one serialized block back.
        No axis primitive anywhere, so ZERO collectives (the
        structural test compiles both and counts) — under TP they
        still ride a ``shard_map`` so the cache keeps its mesh
        sharding through the donation: a plain jit would return
        default-sharded leaves and the next decode step would
        RECOMPILE (caught live by dryrun phase J's compile-count pin);
        each shard simply slices its own block piece. The inject
        donates the cache: adoption never reallocates."""
        if self._kv_extract_jit is None:
            import jax

            from chainermn_tpu.ops.paged_kv import extract_block, \
                inject_block

            if self._mesh is None:
                self._kv_extract_jit = jax.jit(
                    lambda cache, blk: jax.tree.map(
                        lambda pool: extract_block(pool, blk), cache))
                self._kv_inject_jit = jax.jit(
                    lambda cache, blk, payload: jax.tree.map(
                        lambda pool, p: inject_block(pool, blk, p),
                        cache, payload),
                    donate_argnums=(0,),
                )
            else:
                from jax import shard_map
                from jax.sharding import PartitionSpec as P

                mesh = self._mesh

                def ex_local(cache, blk):
                    cache = jax.tree.map(lambda a: a[0], cache)
                    out = jax.tree.map(
                        lambda pool: extract_block(pool, blk), cache)
                    return jax.tree.map(lambda a: a[None], out)

                def in_local(cache, blk, payload):
                    cache = jax.tree.map(lambda a: a[0], cache)
                    payload = jax.tree.map(lambda a: a[0], payload)
                    out = jax.tree.map(
                        lambda pool, p: inject_block(pool, blk, p),
                        cache, payload)
                    return jax.tree.map(lambda a: a[None], out)

                self._kv_extract_jit = jax.jit(shard_map(
                    ex_local, mesh=mesh, in_specs=(P("model"), P()),
                    out_specs=P("model"), check_vma=False,
                ))
                self._kv_inject_jit = jax.jit(
                    shard_map(
                        in_local, mesh=mesh,
                        in_specs=(P("model"), P(), P("model")),
                        out_specs=P("model"), check_vma=False,
                    ),
                    donate_argnums=(0,),
                )
        return self._kv_extract_jit, self._kv_inject_jit

    def export_kv(self, slot: int) -> dict:
        """Serialize ``slot``'s written KV + stream metadata for
        adoption by another engine (:meth:`import_kv`). Paged engines
        ship only the blocks covering the written positions ``[0,
        position)``; dense engines ship the slot's whole ring row (one
        "block" — the honest cost of disaggregating a dense layout,
        and the reason the paged impl is the cluster default). The
        export only READS (the slot stays live — callers that hand the
        stream off ``leave()`` afterwards); trailing in-block garbage
        travels as-is and stays masked by positions on both sides."""
        import jax

        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        extract, _ = self._kv_io()
        import jax.numpy as jnp

        pos = int(self._positions[slot])
        if self._alloc is not None:
            bs = self._alloc.block_size
            phys = self._alloc.owned_blocks(slot)[:-(-pos // bs)]
        else:
            phys = [slot]
        # Dispatch every block's extract asynchronously, then ONE
        # device_get for the whole payload: a per-block np.asarray
        # would be a blocking D2H round trip per leaf per block.
        device_blocks = [
            jax.tree.leaves(extract(self._cache, jnp.int32(b)))
            for b in phys
        ]
        blocks = jax.device_get(device_blocks)
        return {
            "schema": 1,
            "signature": self.kv_signature(),
            "tokens": list(self._history[slot]),
            "position": pos,
            "last_tok": int(self._last_tok[slot]),
            "tenant": self._tenant_ids[slot],
            # The request's sampling seed rides the payload (read with
            # .get — schema stays 1, older payloads mean stream 0): the
            # importer re-derives the SAME counter keys, so a moved
            # sampled stream stays ONE stream bit-identically.
            "seed": int(self._seeds[slot]),
            "blocks": blocks,
            "nbytes": sum(a.nbytes for blk in blocks for a in blk),
        }

    def import_kv(self, payload: dict):
        """Adopt an :meth:`export_kv` payload: claim a slot, allocate
        covering blocks from THIS pool (fresh ids — the source's block
        numbering never leaks across allocators; refcounts start at 1
        here, so a release on either side can never corrupt the
        other), inject the serialized blocks, and restore the stream
        metadata so the next ``decode_step`` continues the stream
        bit-identically. Returns ``(slot, last_tok)``, or None when no
        slot / not enough pool right now (state untouched — the router
        retries, the deferred-admission contract). A layout mismatch
        raises: silently adopting foreign-shaped KV would corrupt
        streams, not degrade them. With prefix sharing on, the
        adopted FULL blocks are inserted into this engine's trie —
        followers of the same prefix hit locally without their own
        transfer."""
        import jax

        if payload.get("schema") != 1:
            raise ValueError(
                f"unknown kv payload schema {payload.get('schema')!r}")
        if tuple(payload["signature"]) != self.kv_signature():
            raise ValueError(
                "kv payload layout mismatch: source "
                f"{payload['signature']} vs target {self.kv_signature()} "
                "— replicas must share decode_impl/kv_block_size/"
                "max_len/model shape/TP degree"
            )
        pos = int(payload["position"])
        if pos + 1 > self.max_len:
            raise ValueError(
                f"payload position {pos} leaves no room within "
                f"max_len={self.max_len}"
            )
        # Tenant validation BEFORE any state mutates (ISSUE 14): an
        # adopted stream keeps decoding under its tenant's delta, so
        # the adapter must be resident HERE too.
        tenant = payload.get("tenant")
        row = 0
        if self.adapter_bank is not None:
            if self.adapter_impl == "merged":
                if tenant != self.merged_tenant:
                    raise ValueError(
                        f"merged engine serves {self.merged_tenant!r} "
                        f"only — payload carries tenant {tenant!r}"
                    )
            else:
                try:
                    row = self.adapter_bank.row_of(tenant)
                except KeyError as e:
                    raise ValueError(
                        f"kv payload tenant {tenant!r} has no resident "
                        "adapter on the importing engine — register it "
                        "before streaming"
                    ) from e
        if not self._free:
            return None
        slot = self._free[-1]  # peek; commit only after alloc succeeds
        if self._alloc is not None:
            if not self._alloc.ensure(slot, pos + 1):
                return None  # all-or-nothing: nothing was adopted yet
            bs = self._alloc.block_size
            targets = self._alloc.owned_blocks(slot)[:-(-pos // bs)]
        else:
            targets = [slot]
        if len(targets) != len(payload["blocks"]):
            # structurally impossible when signatures match — guard
            # against a truncated payload before touching the cache
            if self._alloc is not None:
                self._alloc.release(slot)
            raise ValueError(
                f"payload carries {len(payload['blocks'])} blocks, "
                f"target needs {len(targets)}"
            )
        import jax.numpy as jnp

        _, inject = self._kv_io()
        treedef = jax.tree.structure(self._cache)
        try:
            for tgt, leaves in zip(targets, payload["blocks"]):
                block_tree = jax.tree.unflatten(
                    treedef, [jnp.asarray(a) for a in leaves]
                )
                self._cache = inject(self._cache, jnp.int32(tgt),
                                     block_tree)
        except Exception:
            # Failed mid-injection (device OOM and kin): the slot was
            # never committed — return its reserved blocks so the
            # allocator stays consistent (written garbage is
            # unreachable once the table points back at scratch).
            if self._alloc is not None:
                self._alloc.release(slot)
            raise
        self._free.pop()
        self._positions[slot] = pos
        self._last_tok[slot] = int(payload["last_tok"])
        self._active[slot] = True
        self._history[slot] = [int(t) for t in payload["tokens"]]
        self._set_slot_seed(slot, payload.get("seed"))
        self._tenant_ids[slot] = tenant
        if self._use_adapters:
            self.adapter_bank.pin(tenant)
            if self._tenant_rows[slot] != row:
                self._tenant_rows[slot] = row
                self._tenant_rows_ver += 1
        # KV exists for tokens[:pos]; cache the FULL blocks (the shared
        # publish rule — partial tails never inserted).
        self._publish_full_blocks(slot, self._history[slot], pos)
        self._publish_pool_gauges()
        return slot, int(payload["last_tok"])

    def leave(self, slot: int) -> None:
        """Release a slot (host metadata + paged blocks only — the
        compiled program and the device cache are untouched; stale
        writes land in the slot's own rows or the scratch block)."""
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self._release_slot(slot)

    def _release_slot(self, slot: int) -> None:
        """The ONE slot-release body :meth:`leave` and the mid-fill
        branch of :meth:`preempt` share (free list, history, paged
        blocks, tenant row/pin, gauges) — release-side accounting added
        here reaches both paths."""
        self._active[slot] = False
        self._free.append(int(slot))
        self._history[int(slot)] = []
        if self._alloc is not None:
            self._alloc.release(int(slot))
        # Tenant release (ISSUE 14): unpin the bank row and point the
        # slot back at the null adapter — a reused slot must never
        # gather a departed tenant's delta.
        if self._tenant_ids[slot] is not None:
            if self._use_adapters:
                self.adapter_bank.unpin(self._tenant_ids[slot])
            self._tenant_ids[slot] = None
        if self._tenant_rows[slot] != 0:
            self._tenant_rows[slot] = 0
            self._tenant_rows_ver += 1
        # Seed hygiene: a reused slot must never sample on a departed
        # request's stream (admission always rewrites, but garbage rows
        # also feed the grid programs for inactive slots).
        self._set_slot_seed(slot, 0)
        self._publish_pool_gauges()
