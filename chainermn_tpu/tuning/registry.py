"""The decision registry: ``choice(name, candidates, key)``.

Resolution order (each step records its provenance):

1. ``CHAINERMN_TPU_AUTOTUNE_FORCE`` override (``name=winner,...``);
2. the persistent cache (measured on this machine, or seeded offline
   from on-chip bench artifacts — :mod:`chainermn_tpu.tuning.cache`);
3. one-shot measurement, when the call site supplies per-candidate
   measurement callables, tracing is not active, and the mode allows it
   (:mod:`chainermn_tpu.tuning.measure`); the winner is persisted;
4. the deterministic per-device-class table below.

Every resolution is appended to a process-local decision log so
``bench.py`` / ``dryrun_multichip`` can report exactly which path each
site took (dispatch provenance in every capture artifact).
"""

from __future__ import annotations

import os
from typing import Callable, Mapping, Optional, Sequence

from chainermn_tpu.tuning import cache as _cache
from chainermn_tpu.tuning import measure as _measure

#: Deterministic fallbacks, keyed ``decision -> device class -> winner``
#: (``*`` = any). Each winner cites the measurement it rests on
#: (the CPU-proxy rows of 2026-08-07 and the one-chip v5e capture of
#: 2026-08-01, both older than the code since PR 1 — PERF.md), so the
#: table is the documented crossover, not an opinion:
#:
#: - ``moe_dispatch``: sort won BOTH measured points — 167.8x on the CPU
#:   proxy (T2048xE8xD64) and 1.63x on TPU v5e at the production shape
#:   (T16384xE16xD512, where the dense path is einsum-competitive); the
#:   dense [T,E,C] einsum only ties at tiny shapes, so ``sort``
#:   everywhere and let a cache entry flip shapes where a sweep shows
#:   otherwise.
#: - ``attention``: flash is 3.0x fwd+bwd on the chip but 0.56x under
#:   CPU interpret mode — the inversion that motivated this package.
#: - ``allreduce_wire``: bf16 is the measured default (halved bytes,
#:   zero rounding risk); int8's two rounding stages pay only where DCN
#:   bandwidth is scarce, which a cache entry (seeded from a multi-slice
#:   curve) must demonstrate before it is chosen.
#: - ``allreduce_bucket_mb``: ~64 MB keeps the inter level
#:   bandwidth-bound while bounding the transient flat-copy in HBM
#:   (docs/benchmarks.md curve); ``none`` = single fused buffer.
#: - ``double_buffering``: measured 0.752x on the CPU proxy and 0.85x on
#:   a single chip (no collective to overlap) — ``off`` until a
#:   multi-slice capture shows the overlap paying.
#: - ``reduction_schedule``: ``flat`` everywhere until measured — XLA
#:   already derives a topology-aware schedule from the fused pmean,
#:   so the pinned ``two_level``/``zero`` pipelines must EARN their
#:   extra program structure with a bench ``overlap``-phase win
#:   (seeded from BENCH_DETAILS.json ``overlap_schedule_ms`` rows; see
#:   chainermn_tpu.parallel.reduction_schedule). The choice set is the
#:   DERIVED composition list for the world shape (ISSUE 12:
#:   composition.schedule_candidates — menu names + signature-keyed
#:   derived pipelines, swept by bench's ``composed`` phase and seeded
#:   from its ``composed_schedule_ms`` rows, spread-gated as always);
#:   the ``flat`` table default stays the no-evidence answer.
#: - ``decode_impl`` (serving steady-state step): ``paged`` everywhere
#:   — the idle-box CPU-proxy point measured paged 0.95 ms vs dense
#:   1.38 ms/step (D64xH4xL64, gap outside the 17.5% spread), and on
#:   chip paging additionally buys the HBM-capacity win that motivates
#:   the layout; later proxy runs on a loaded box were SPREAD-DOMINATED
#:   (impls within ~8%, noise ~16%) and correctly refused adoption, so
#:   the table — not a coin-flip cache entry — decides until a decisive
#:   per-shape capture (bench ``serving`` rows) seeds one.
#: - ``kv_block_size``: ``64`` — big enough that table/gather overhead
#:   amortises, small enough that a short request strands < 64 stale
#:   rows per slot; the proxy's 16-vs-64 sweep was SPREAD-DOMINATED
#:   (29% noise), so the table default stands until a decisive
#:   ``serving_kv_block_ms`` capture seeds a winner.
#: - ``spec_tokens`` (speculative decode length K): ``0`` (off) — the
#:   payoff is acceptance-dependent (draft hit rate is a property of
#:   the WORKLOAD, not the device), and a K that drafts junk pays K
#:   wasted verify columns plus draft overhead per tick, so speculation
#:   must EARN adoption through a bench ``serving`` capture
#:   (``serving_spec_ms`` rows + acceptance rate) before 'auto' turns
#:   it on for a shape. Since ISSUE 18 the knob covers SAMPLED traffic
#:   too (counter-based keys + rejection acceptance, docs/serving.md
#:   "Sampling"), so sampled captures (``serving_sampled`` rows,
#:   per-mode acceptance) feed the same decision.
#: - ``prefix_cache`` (cross-request KV prefix sharing): ``on`` — the
#:   miss path costs host metadata only (one trie walk + refcounts per
#:   join; the decode/verify programs are untouched and shared streams
#:   are bit-identical, both pinned in tests/test_prefix_cache.py),
#:   while a hit removes the shared prefix from prefill entirely —
#:   bench's ``serving_prefix`` phase measured the CPU-proxy TTFT win
#:   under duplicate-prefix load and unlike ``spec_tokens`` there is no
#:   workload that pays a device-plane penalty for a junk hit (COW
#:   copies one block, only ever on a full-prefix boundary). A cache
#:   entry can still turn it off where a sweep shows the host walk
#:   mattering.
#: - ``min_shared_blocks``: ``1`` — adopt every full-block hit; raise
#:   via a sweep only where table/refcount churn on tiny hits shows up
#:   (``serving_prefix_msb_ttft_ms`` rows).
DEFAULT_TABLE: dict = {
    "moe_dispatch": {"cpu": "sort", "tpu": "sort", "*": "sort"},
    # Expert-axis MoE (ISSUE 20): spread the experts over an 'expert'
    # mesh axis (2 all_to_alls/layer, 1/n experts resident per shard)
    # vs replicated-local (every shard hosts every expert, zero
    # collectives). 'off' everywhere — on one host the a2a pair is pure
    # overhead, and the HBM-per-expert capacity win that motivates
    # spreading only prices honestly on a real multi-chip mesh, so the
    # axis must EARN adoption through bench's ``moe`` phase rows
    # (``moe_step_ms``, spread-gated; the spec_tokens precedent).
    "expert_parallel": {"*": "off"},
    "attention": {"cpu": "xla", "tpu": "flash", "*": "flash"},
    "attention_windowed": {"cpu": "xla", "tpu": "windowed", "*": "windowed"},
    "allreduce_wire": {"*": "bf16"},
    "allreduce_bucket_mb": {"*": "64"},
    "double_buffering": {"*": "off"},
    "reduction_schedule": {"*": "flat"},
    # Bucket-sliced composed reduction (ISSUE 15): how many slices a
    # composed schedule's stages interleave over (slice i's slow inter-
    # level stage behind slice i+1's fast rs/ag). ``1`` everywhere —
    # slicing multiplies per-stage collective DISPATCHES S× at 1/S
    # payload (total wire bytes unchanged), so the latency/overlap
    # trade must EARN adoption through bench's ``composed`` sliced arms
    # (``composed_sliced_ms`` rows, spread-gated; the
    # spec_tokens/prefill_chunk precedent).
    "comp_slices": {"*": "1"},
    "decode_impl": {"*": "paged"},
    "kv_block_size": {"*": "64"},
    # Fused paged-decode Pallas kernel (ISSUE 19): 'xla' = scatter →
    # dense-view gather → einsum attend; 'fused' = one flash-decoding
    # HBM pass with the block table as a scalar-prefetch operand
    # (ops/paged_decode.py). 'xla' everywhere — the kernel must EARN
    # adoption through bench's ``serving_decode_kernel`` step-time rows
    # (spread-gated; the spec_tokens precedent), and interpret-mode CPU
    # emulation is slower than the XLA path by construction, so only a
    # live-chip capture can honestly flip this. byte_audit's decode
    # workload prices the HBM-bytes case the proxy can't.
    "decode_attend_impl": {"*": "xla"},
    "spec_tokens": {"*": "0"},
    "prefix_cache": {"*": "on"},
    "min_shared_blocks": {"*": "1"},
    # Cluster disaggregation (ISSUE 8): colocated until a bench capture
    # shows the prefill/decode split wins TTFT on this shape — the
    # transfer hop must EARN its place, like speculation.
    "cluster_disagg": {"*": "colocated"},
    # Chunked prefill (ISSUE 11): tokens of prompt prefilled per decode
    # tick inside the mixed step; 0 = monolithic prefill. Default 0 —
    # chunking trades peak prefill throughput for decode-tick latency
    # (every tick pays the chunk-width forward), so it must earn
    # adoption through the bench's bursty goodput-under-SLO rows
    # (spread-gated, the spec_tokens/cluster_disagg precedent). Applies
    # to sampled traffic too since ISSUE 18: counter-based keys make the
    # chunked schedule bit-identical to monolithic at temperature > 0
    # (docs/serving.md "Sampling"), so one decision covers both modes.
    "prefill_chunk": {"*": "0"},
    # Sequence-axis attention (ISSUE 13): ring (n-1 neighbour ppermutes
    # per layer, O(T_local) resident K/V, no divisibility constraint)
    # vs Ulysses (two all_to_alls in + one out per layer; cheaper when
    # heads >= seq size AND the full sequence fits a shard's HBM —
    # which is exactly when you need less sequence parallelism). Ring
    # everywhere until a bench ``seq_parallel`` capture shows Ulysses
    # winning a shape; heads-indivisible shapes force ring regardless.
    "seq_attn_impl": {"*": "ring"},
    # Cost-model schedule search (ISSUE 16): how the composed-schedule
    # sweep covers its candidate grid. 'topk' ranks the candidates with
    # the fitted alpha-beta model and MEASURES only the top-k (skipped
    # arms logged with their predicted costs — no silent coverage
    # loss); 'exhaustive' measures every arm. Topk everywhere — the
    # model is audited on every adoption (predicted-vs-measured error
    # recorded as cache evidence) and an uncalibrated or disagreeing
    # model FORCES exhaustive with loud provenance, so the cheap path
    # can never silently rank on a default-initialized model.
    "sched_search": {"*": "topk"},
    # Multi-tenant adapter application (ISSUE 14): 'gather' = the one
    # compiled program gathers each slot's A/B rows and adds the rank-r
    # delta in-forward — mixed-tenant traffic pays O(r(d_in+d_out)) per
    # projection and tenant churn stays host metadata; 'merged' folds
    # one tenant's delta into the base weights (zero per-step cost but
    # ONE tenant per engine). Gather everywhere until the bench's
    # ``serving_tenants`` rows show merging winning a single-tenant-
    # dominant shape (spread-gated, the spec_tokens precedent).
    "adapter_impl": {"*": "gather"},
    # Sequence-parallel long-prompt prefill over the replica's 'model'
    # partition (ISSUE 13): 'off' until the bench's long-prompt TTFT
    # rows (``seq_parallel_ttft_ms``) show the sharded forward beating
    # the TP prefill on this shape — the in-program param all-gather
    # and per-layer ring hops must EARN their place, the
    # spec_tokens/cluster_disagg precedent. No longer greedy-only
    # (ISSUE 18): every shard derives the same counter-based key from
    # the psum'd logits row, so the sampled sharded prefill emits the
    # token the monolithic path would (docs/serving.md "Sampling").
    "prefill_seq_parallel": {"*": "off"},
}

_MODE_ENV = "CHAINERMN_TPU_AUTOTUNE"
_FORCE_ENV = "CHAINERMN_TPU_AUTOTUNE_FORCE"

#: process-local decision log: (name, key) -> record, insertion-ordered
_DECISIONS: dict = {}


def _mode() -> str:
    mode = os.environ.get(_MODE_ENV, "auto").lower()
    return mode if mode in ("auto", "measure", "table", "off") else "auto"


def _forced() -> dict:
    out = {}
    for part in os.environ.get(_FORCE_ENV, "").split(","):
        if "=" in part:
            name, _, winner = part.partition("=")
            out[name.strip()] = winner.strip()
    return out


def current_device_kind() -> str:
    """``device_kind`` of the default backend's first device (``"cpu"``,
    ``"TPU v5 lite"``, ...); ``"unknown"`` when no backend is up. Call
    sites resolving at trace time always have a live backend."""
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:
        return "unknown"


def device_class(device_kind: str) -> str:
    """Coarse class for table lookup: ``cpu`` / ``tpu`` / ``*``."""
    kind = (device_kind or "").lower()
    if "cpu" in kind:
        return "cpu"
    if "tpu" in kind or kind.startswith("v"):
        return "tpu"
    return "*"


def shape_bucket(shape: Sequence[int]) -> str:
    """Bucket each dim up to the next power of two, joined with ``x`` —
    nearby shapes share one decision (and one measurement) instead of
    fragmenting the cache per exact shape."""

    def bucket(d: int) -> int:
        d = int(d)
        if d < 1:
            raise ValueError(f"shape dims must be >= 1, got {d}")
        b = 1
        while b < d:
            b <<= 1
        return b

    return "x".join(str(bucket(d)) for d in shape)


def decision_key(
    device_kind: Optional[str] = None,
    shape: Optional[Sequence[int]] = None,
    dtype=None,
) -> str:
    """``"<device_kind>|<shape-bucket>|<dtype>"`` — the cache key a call
    site's decision is stored under. ``device_kind`` defaults to the
    live backend's; ``dtype`` accepts anything ``jnp.dtype`` does (or a
    plain string tag for non-dtype keys)."""
    kind = device_kind if device_kind is not None else current_device_kind()
    shape_s = shape_bucket(shape) if shape else "-"
    if dtype is None:
        dtype_s = "-"
    elif isinstance(dtype, str):
        dtype_s = dtype
    else:
        import numpy as np

        dtype_s = np.dtype(dtype).name
    return f"{kind}|{shape_s}|{dtype_s}"


def _record(name: str, key: str, winner: str, source: str,
            evidence: Optional[dict] = None) -> None:
    _DECISIONS[(name, key)] = {
        "name": name, "key": key, "winner": winner, "source": source,
        **({"evidence": evidence} if evidence else {}),
    }
    # Every resolution also lands in the structured trace (when one is
    # active) as a ``dispatch`` event — the tuning-cache provenance the
    # observability layer attaches to 'auto' decisions.
    try:
        from chainermn_tpu.observability import trace as _trace

        rec = _trace.active()
        if rec is not None:
            rec.event("dispatch", **_DECISIONS[(name, key)])
    except Exception:
        pass


def decisions_taken() -> list:
    """The decisions this process resolved, in first-resolution order —
    what bench.py / dryrun_multichip fold into their artifacts."""
    return list(_DECISIONS.values())


def decisions_summary(max_len: int = 200) -> str:
    """Compact ``name=winner(source)`` summary for size-capped artifact
    lines (bench's compact JSON line has a 2000-char budget)."""
    parts = [
        f"{d['name']}={d['winner']}({d['source'].split(':')[0]})"
        for d in _DECISIONS.values()
    ]
    out = " ".join(parts)
    return out[:max_len]


def reset_decisions() -> None:
    """Clear the process-local decision log (test isolation)."""
    _DECISIONS.clear()


def _trace_clean() -> bool:
    """Whether we are OUTSIDE any jax trace — measurement runs real
    device work and must never fire mid-trace (inside shard_map/jit the
    table/cache answer is used instead). jax 0.9.0 keeps the predicate
    in ``jax._src.core`` only; an upgrade that moves it fails here
    loudly instead of silently disabling measurement."""
    from jax._src import core as jax_core

    return bool(jax_core.trace_state_clean())


def _table_winner(name: str, key: str, candidates, table) -> str:
    tab = table if table is not None else DEFAULT_TABLE.get(name, {})
    cls = device_class(key.split("|", 1)[0])
    winner = tab.get(cls) or tab.get("*")
    if winner in candidates:
        return winner
    return candidates[0]


def choice(
    name: str,
    candidates: Sequence[str],
    key: str,
    *,
    measure: Optional[Mapping[str, Callable[[], float]]] = None,
    table: Optional[dict] = None,
    cache_path: Optional[str] = None,
) -> str:
    """Resolve decision ``name`` among ``candidates`` for ``key``.

    ``measure`` (optional): per-candidate zero-arg callables returning a
    cost in ms (lower wins) — supplied only by call sites that can
    afford a one-shot measurement (bench, tests, offline sweeps); plain
    library call sites omit it and get cache/table resolution, which is
    pure Python and safe inside a trace.
    """
    if not candidates:
        raise ValueError(f"decision {name!r}: no candidates")
    forced = _forced().get(name)
    if forced is not None:
        if forced not in candidates:
            raise ValueError(
                f"{_FORCE_ENV} forces {name}={forced!r}, not one of "
                f"{tuple(candidates)}"
            )
        _record(name, key, forced, "forced")
        return forced

    mode = _mode()
    if mode != "off":
        entry = _cache.lookup_entry(name, key, cache_path)
        if entry and entry.get("winner") in candidates:
            _record(name, key, entry["winner"],
                    f"cache:{entry.get('source', '?')}",
                    {k: entry[k] for k in ("candidates_ms", "spread_pct")
                     if k in entry})
            return entry["winner"]

    if (measure and mode in ("auto", "measure") and _trace_clean()):
        fns = {c: measure[c] for c in candidates if c in measure}
        if fns:
            winner, evidence = _measure.measure_candidates(fns)
            if winner is not None:
                _cache.store_entry(
                    name, key, {"winner": winner, "source": "measured",
                                **evidence}, cache_path,
                )
                _record(name, key, winner, "measured", evidence)
                return winner
            # spread-dominated: deterministic fallback, evidence kept
            winner = _table_winner(name, key, candidates, table)
            _record(name, key, winner, "table:spread-dominated", evidence)
            return winner

    winner = _table_winner(name, key, candidates, table)
    _record(name, key, winner, "table")
    return winner


def record_measurement(
    name: str,
    key: str,
    medians_ms: Mapping[str, float],
    *,
    spreads: Optional[Mapping[str, float]] = None,
    higher_is_better: bool = False,
    source: str = "measured:bench",
    cache_path: Optional[str] = None,
    extra_evidence: Optional[Mapping[str, object]] = None,
) -> Optional[str]:
    """Adopt an ALREADY-measured comparison into the cache (bench.py's
    phases measure the candidates anyway — this turns those rows into
    dispatch decisions without re-running them). Returns the winner, or
    None when spread-dominated (nothing stored).

    ``spreads=None`` means the caller has NO repeat-derived noise
    estimate (the on-chip bench runs one sample of many chained
    iterations instead of n>=3 samples): a conservative 10% noise floor
    is applied, so a single-sample comparison is adopted only when the
    winner's margin is decisive — never a coin flip recorded as
    spread_pct 0.

    ``extra_evidence`` (ISSUE 16): caller-supplied keys merged into the
    stored entry beside the medians — the cost-model schedule search
    records its predicted-vs-measured error here on every top-k
    adoption, so the model is audited in the cache, never trusted
    blind. Reserved entry keys (winner/source/medians/spread) win over
    a colliding extra key."""
    floored = spreads is None
    if floored:
        spreads = {k: 10.0 for k in medians_ms}
    winner = _measure.decide(medians_ms, spreads,
                             higher_is_better=higher_is_better)
    if winner is None:
        return None
    unit = "candidates_score" if higher_is_better else "candidates_ms"
    entry = {
        **(dict(extra_evidence) if extra_evidence else {}),
        "winner": winner, "source": source,
        unit: {k: round(float(v), 4) for k, v in medians_ms.items()},
        "spread_pct": max(spreads.values(), default=0.0),
    }
    if floored:
        entry["noise_floor_pct"] = 10.0  # single-sample caller
    _cache.store_entry(name, key, entry, cache_path)
    return winner
