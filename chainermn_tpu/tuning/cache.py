"""Persistent autotune cache + offline seeding.

Deliberately jax-free: the cache is plain JSON so the bench parent
process (which never imports jax — bench.py's robustness contract) and
the ``python -m chainermn_tpu.tuning`` CLI can read/seed it cheaply.

File format (``.autotune_cache.json``)::

    {"version": 1,
     "decisions": {
       "moe_dispatch|TPU v5 lite|16384x16x512|bfloat16": {
         "winner": "sort",
         "source": "seeded:BENCH_DETAILS.json",
         "candidates_ms": {"einsum": 11.362, "sort": 6.981},
         "spread_pct": 0.0,
         "measured_at": "2026-08-01T08:46:00Z"}}}

Keys are ``name|decision_key`` (see :func:`registry.decision_key`).
Every entry carries its evidence (``candidates_ms`` or a free-form
``evidence``) and provenance (``source`` + ``measured_at``) — a cache
the next session can audit, not just obey.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time

CACHE_ENV = "CHAINERMN_TPU_AUTOTUNE_CACHE"
VERSION = 1

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_LOCK = threading.Lock()


def default_cache_path() -> str:
    """Cache file path: ``CHAINERMN_TPU_AUTOTUNE_CACHE`` or
    ``<repo>/.autotune_cache.json``."""
    return os.environ.get(CACHE_ENV) or os.path.join(
        _REPO_ROOT, ".autotune_cache.json"
    )


#: path -> (mtime_ns, size, parsed doc) — choice() resolves on every
#: auto-dispatched library call, so repeated full read+parse of the
#: JSON would be per-call I/O; one stat per lookup keeps cross-process
#: freshness (a bench child rewriting the file bumps the mtime).
_LOAD_MEMO: dict = {}


def load_cache(path: str | None = None) -> dict:
    """Load the cache document (mtime-memoized); a missing or corrupt
    file is an empty cache, never an error (the cache is an
    accelerator, not a dependency)."""
    path = path or default_cache_path()
    try:
        st = os.stat(path)
        stamp = (st.st_mtime_ns, st.st_size)
    except OSError:
        _LOAD_MEMO.pop(path, None)
        return {"version": VERSION, "decisions": {}}
    memo = _LOAD_MEMO.get(path)
    if memo is not None and memo[0] == stamp:
        return memo[1]
    try:
        with open(path) as f:
            doc = json.load(f)
        if not (isinstance(doc, dict)
                and isinstance(doc.get("decisions"), dict)):
            doc = {"version": VERSION, "decisions": {}}
    except (OSError, json.JSONDecodeError):
        doc = {"version": VERSION, "decisions": {}}
    _LOAD_MEMO[path] = (stamp, doc)
    return doc


def lookup_entry(name: str, key: str, path: str | None = None):
    """The cached entry for ``name|key``, or None."""
    entry = load_cache(path)["decisions"].get(f"{name}|{key}")
    return entry if isinstance(entry, dict) else None


def store_entry(
    name: str, key: str, entry: dict, path: str | None = None
) -> bool:
    """Read-modify-write one decision entry. Best-effort: an unwritable
    location (read-only checkout, scrubbed env) loses the persistence,
    never the decision. Returns whether the write landed."""
    path = path or default_cache_path()
    with _LOCK:
        doc = load_cache(path)
        # copy before mutating: load_cache memoizes the parsed doc and
        # hands the same object to concurrent readers
        doc = {**doc, "decisions": dict(doc["decisions"])}
        doc["version"] = VERSION
        entry = dict(entry)
        entry.setdefault(
            "measured_at", time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        )
        doc["decisions"][f"{name}|{key}"] = entry
        try:
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
            return True
        except OSError:
            return False


# ---------------------------------------------------------------------------
# Offline seeding from bench artifacts
# ---------------------------------------------------------------------------

_MOE_SHAPE = re.compile(r"T(\d+)xE(\d+)xD(\d+)")
_ATTN_SHAPE = re.compile(r"B(\d+)xT(\d+)xH(\d+)xD(\d+)_(\w+?)_")
_SERVING_SHAPE = re.compile(r"D(\d+)xH(\d+)xL(\d+)")
_SEQATTN_SHAPE = re.compile(r"S(\d+)xH(\d+)xT(\d+)")


def _bucketed_key(device_kind: str, dims, dtype_name: str) -> str:
    # The ONE key builder (registry.decision_key), imported lazily to
    # break the module cycle (registry imports this module at top).
    # With an explicit device_kind and a string dtype the registry path
    # is jax-free, so seeding stays usable without a backend.
    from chainermn_tpu.tuning.registry import decision_key

    return decision_key(device_kind, shape=[int(d) for d in dims],
                        dtype=dtype_name)


def _seed_one_result(result: dict, source: str, out: list,
                     path: str | None) -> None:
    kind = result.get("device_kind")
    if not kind:
        return
    stamp = result.get("measured_at")

    def put(name, key, winner, evidence):
        entry = {"winner": winner, "source": source, **evidence}
        if stamp:
            entry["measured_at"] = stamp
        if store_entry(name, key, entry, path):
            out.append(f"{name}|{key} -> {winner}")

    # MoE dispatch: einsum vs sort medians at the measured shape.
    m = _MOE_SHAPE.search(result.get("moe_dispatch_shape", ""))
    e_ms = result.get("moe_dispatch_einsum_ms")
    s_ms = result.get("moe_dispatch_sort_ms")
    if m and e_ms and s_ms:
        key = _bucketed_key(kind, m.groups(), "bfloat16")
        put("moe_dispatch", key,
            "sort" if s_ms <= e_ms else "einsum",
            {"candidates_ms": {"einsum": e_ms, "sort": s_ms},
             "spread_pct": result.get("moe_dispatch_spread_pct", 0.0)})

    # Expert axis (ISSUE 20): the bench ``moe`` phase's expert-plan vs
    # replicated-experts step pair, spread-gated like the LIVE adoption
    # path (record_measurement) — and under the SAME key derivation
    # (shape=(T, E, D), dtype float32), so offline seed and in-run
    # adoption land on one cache entry.
    m = _MOE_SHAPE.search(result.get("moe_plan_shape", ""))
    on_ms = result.get("moe_step_ms")
    off_ms = result.get("moe_off_step_ms")
    if m and on_ms and off_ms:
        from chainermn_tpu.tuning.measure import decide

        # absent spread = single-sample on-chip row: the 10% noise
        # floor record_measurement would apply
        spread = float(result.get("moe_spread_pct", 10.0))
        pair = {"on": float(on_ms), "off": float(off_ms)}
        winner = decide(pair, {k: spread for k in pair})
        if winner is not None:
            key = _bucketed_key(kind, m.groups(), "float32")
            put("expert_parallel", key, winner,
                {"candidates_ms": pair, "spread_pct": spread})

    # Attention variant: fwd+bwd medians (the training-relevant row).
    m = _ATTN_SHAPE.search(result.get("attn_shape", ""))
    f_ms = result.get("flash_fwdbwd_ms")
    x_ms = result.get("xla_fwdbwd_ms")
    if m and f_ms and x_ms:
        _, t, h, d, dt = m.groups()
        # normalise to numpy dtype names — the spelling runtime keys use
        dt = {"bf16": "bfloat16", "f32": "float32",
              "f16": "float16"}.get(dt, dt)
        key = _bucketed_key(kind, (t, h, d), dt)
        put("attention", key,
            "flash" if f_ms <= x_ms else "xla",
            {"candidates_ms": {"flash": f_ms, "xla": x_ms},
             "spread_pct": result.get("attn_proxy_spread_pct", 0.0)})

    # Allreduce wire: best busbw mode among the curve's rows. Only on a
    # REAL multi-member axis — at n=1 there is no wire, and the dtype
    # "comparison" would just adopt loopback memory-bandwidth noise.
    curve = result.get("allreduce_curve")
    n = result.get("n_devices", 1)
    if isinstance(curve, list) and n > 1:
        best: dict[str, float] = {}
        for row in curve:
            if not isinstance(row, dict) or "busbw_gbps" not in row:
                continue
            wire = ("int8" if row.get("mode") == "int8"
                    else {"bfloat16": "bf16", "float32": "f32"}.get(
                        row.get("dtype")))
            if wire:
                best[wire] = max(best.get(wire, 0.0), row["busbw_gbps"])
        if best:
            key = _bucketed_key(kind, (n,), "grad")
            put("allreduce_wire", key,
                max(best, key=best.get),
                {"busbw_gbps": best})
    if isinstance(curve, list):
        # Bucket size: the ~64 MB packing discipline is adopted unless
        # the curve shows the fused single buffer decisively faster.
        # Only rows big enough to actually CARRY >= 64 MiB buckets count
        # — the CPU proxy's shrunken-bucket rows measure per-collective
        # latency at micro sizes, not the packing discipline.
        by_mode = {
            row.get("mode"): row["busbw_gbps"]
            for row in curve
            if isinstance(row, dict) and "busbw_gbps" in row
            and row.get("dtype") == "bfloat16"
            and row.get("mib", 0) >= 64
        }
        if "fused" in by_mode and "bucketed" in by_mode:
            key = _bucketed_key(kind, (n,), "grad")
            put("allreduce_bucket_mb", key,
                "64" if by_mode["bucketed"] >= 0.9 * by_mode["fused"]
                else "none",
                {"busbw_gbps": by_mode})

    # Reduction schedule: the overlap phase's per-schedule step-time
    # medians (ISSUE 3 — bench's ``overlap`` rows become the 'auto'
    # schedule's evidence). The key must
    # reproduce resolve_schedule's exactly: world-shape + payload-MB
    # bucket, dtype tag 'sched' — bench records both alongside the rows.
    sched_ms = result.get("overlap_schedule_ms")
    if isinstance(sched_ms, dict) and len(sched_ms) >= 2 and all(
        isinstance(v, (int, float)) for v in sched_ms.values()
    ):
        # Spread-gated like the LIVE adoption path (measure.decide): a
        # schedule "winner" inside the run's own noise band must not be
        # pinned into the cache — the in-run record_measurement refused
        # it, and the offline seeder must not resurrect it.
        from chainermn_tpu.tuning.measure import decide

        spread = float(result.get("overlap_schedule_spread_pct", 0.0))
        winner = decide(sched_ms, {k: spread for k in sched_ms})
        if winner is not None:
            world = result.get("overlap_world_shape") or [
                result.get("n_devices", 1)
            ]
            payload_mb = result.get("overlap_payload_mb", 1)
            key = _bucketed_key(
                kind, tuple(world) + (payload_mb,), "sched"
            )
            put("reduction_schedule", key, winner,
                {"candidates_ms": {k: round(float(v), 4)
                                   for k, v in sched_ms.items()},
                 "spread_pct": spread})

    # Composed schedules (ISSUE 12): bench's ``composed`` phase sweeps
    # the DERIVED composition list on the multi-level factoring of the
    # mesh (rows keyed by composition signature string) — same decision
    # name, its own world-shape key (e.g. (2,2,2) vs the flat (8,)), so
    # the flat-mesh 'overlap' entry and the 3-level one coexist. Spread-
    # gated through measure.decide like every adoption.
    comp_ms = result.get("composed_schedule_ms")
    if isinstance(comp_ms, dict) and len(comp_ms) >= 2 and all(
        isinstance(v, (int, float)) for v in comp_ms.values()
    ):
        from chainermn_tpu.parallel.composition import (
            normalize_schedule_name,
        )
        from chainermn_tpu.tuning.measure import decide

        n_axes = len(result.get("composed_world_shape") or (1, 1, 1))
        # The registry's candidate spelling: menu-instance signatures
        # (the derived flat/two_level) adopt by MENU NAME — a signature
        # winner the candidate list excludes would be silently
        # discarded at choice() time and the table default would win.
        comp_ms = {normalize_schedule_name(k, n_axes): v
                   for k, v in comp_ms.items()}
        spread = float(result.get("composed_spread_pct", 0.0))
        winner = decide(comp_ms, {k: spread for k in comp_ms})
        if winner is not None:
            world = result.get("composed_world_shape") or [
                result.get("n_devices", 1)
            ]
            payload_mb = result.get("composed_payload_mb", 1)
            key = _bucketed_key(
                kind, tuple(world) + (payload_mb,), "sched"
            )
            put("reduction_schedule", key, winner,
                {"candidates_ms": {k: round(float(v), 4)
                                   for k, v in comp_ms.items()},
                 "spread_pct": spread})

    # Bucket-slice count (ISSUE 15): bench's ``composed`` sliced arms
    # time the hierarchical pipeline at comp_slices ∈ {1,2,4,8} — rows
    # keyed by slice count, adopted under the SAME world-shape x
    # payload-MB key resolve_comp_slices reads (dtype tag 'slices').
    # Spread-gated through measure.decide exactly like the live
    # record_measurement adoption, so offline seed and in-run adoption
    # agree on identical rows (the PR 14 adapter_impl lesson).
    sl_ms = result.get("composed_sliced_ms")
    if isinstance(sl_ms, dict) and len(sl_ms) >= 2 and all(
        isinstance(v, (int, float)) for v in sl_ms.values()
    ):
        from chainermn_tpu.tuning.measure import decide

        if "composed_sliced_spread_pct" in result:
            spread = float(result["composed_sliced_spread_pct"])
        else:
            spread = 10.0  # on-accel single sample: the noise floor
        winner = decide(sl_ms, {k: spread for k in sl_ms})
        if winner is not None:
            world = result.get("composed_world_shape") or [
                result.get("n_devices", 1)
            ]
            payload_mb = result.get("composed_payload_mb", 1)
            key = _bucketed_key(
                kind, tuple(world) + (payload_mb,), "slices"
            )
            put("comp_slices", key, str(winner),
                {"candidates_ms": {k: round(float(v), 4)
                                   for k, v in sl_ms.items()},
                 "spread_pct": spread})

    # Cost-model schedule search (ISSUE 16): the composed phase now
    # ranks arms with the fitted α–β model and measures only top-k; the
    # predicted-vs-measured max error over the arms it DID time is the
    # model audit. Seed the sched_search decision from that audit:
    # error inside the measurement spread keeps the ranked top-k path,
    # disagreement past the gate seeds 'exhaustive' so the next run
    # restores full coverage — loud provenance either way, and the
    # predicted rows ride along as evidence (never trusted blind).
    cm_err = result.get("cost_model_err_pct")
    if isinstance(cm_err, (int, float)) and result.get(
            "sched_search_selected"):
        spread = float(result.get("composed_spread_pct", 0.0)) or 10.0
        world = result.get("composed_world_shape") or [
            result.get("n_devices", 1)
        ]
        payload_mb = result.get("composed_payload_mb", 1)
        key = _bucketed_key(
            kind, tuple(world) + (payload_mb,), "search"
        )
        winner = "topk" if float(cm_err) <= spread else "exhaustive"
        evidence: dict = {
            "cost_model_err_pct": round(float(cm_err), 3),
            "spread_pct": spread,
            "selected": str(result["sched_search_selected"]),
        }
        pred = result.get("sched_search_predicted_ms")
        if isinstance(pred, dict):
            evidence["predicted_ms"] = {
                k: round(float(v), 4) for k, v in pred.items()
                if isinstance(v, (int, float))
            }
        skipped = result.get("sched_search_skipped")
        if isinstance(skipped, (list, tuple)):
            evidence["skipped"] = [str(s) for s in skipped]
        put("sched_search", key, winner, evidence)

    # Sequence-axis attention impl (ISSUE 13): bench's ``seq_parallel``
    # phase times the ONE plan-compiled step per candidate (ring's n-1
    # ppermutes/layer vs Ulysses' all_to_all reshard), keyed
    # shards x heads x LOCAL-T — the same key
    # ParallelPlan.seq_attention resolves under. Spread-gated like
    # every adoption.
    m_sa = _SEQATTN_SHAPE.search(result.get("seq_parallel_attn_shape", ""))
    sa_ms = result.get("seq_parallel_attn_ms")
    if m_sa and isinstance(sa_ms, dict) and len(sa_ms) >= 2 and all(
        isinstance(v, (int, float)) for v in sa_ms.values()
    ):
        from chainermn_tpu.tuning.measure import decide

        if "seq_parallel_attn_spread_pct" in result:
            spread = float(result["seq_parallel_attn_spread_pct"])
        else:
            spread = 10.0  # on-accel single sample: the noise floor
        winner = decide(sa_ms, {k: spread for k in sa_ms})
        if winner is not None:
            key = _bucketed_key(kind, m_sa.groups(), "seqattn")
            put("seq_attn_impl", key, winner,
                {"candidates_ms": {k: round(float(v), 4)
                                   for k, v in sa_ms.items()},
                 "spread_pct": spread})

    # Serving decode decisions (ISSUE 4/5/7): bench's ``serving`` and
    # ``serving_prefix`` phases record per-candidate medians keyed by
    # the engine's own decision key material (``serving_model_shape``
    # D..xH..xL..) — decode impl, paged block size, the speculative
    # length K (``serving_spec_ms``: ms per GENERATED token per K, so
    # the acceptance rate is priced in), the prefix cache on/off
    # (``serving_prefix_ttft_ms``: median TTFT under duplicate-prefix
    # load — the metric sharing exists to move) and its adoption
    # threshold (``serving_prefix_msb_ttft_ms``). All adoptions are
    # spread-gated through measure.decide, same as the overlap schedule
    # rows above.
    m = _SERVING_SHAPE.search(result.get("serving_model_shape", ""))
    # The prefix rows carry their OWN shape key: the two phases share a
    # model today, but last-writer-wins on one merged key would silently
    # re-key the other phase's decisions if either shape ever diverges.
    m_px = (_SERVING_SHAPE.search(
        result.get("serving_prefix_model_shape", "")) or m)
    m_cl = (_SERVING_SHAPE.search(
        result.get("serving_cluster_model_shape", "")) or m)
    m_bu = (_SERVING_SHAPE.search(
        result.get("serving_burst_model_shape", "")) or m)
    m_sp = (_SERVING_SHAPE.search(
        result.get("seq_parallel_model_shape", "")) or m)
    m_te = (_SERVING_SHAPE.search(
        result.get("serving_tenants_model_shape", "")) or m)
    m_dk = (_SERVING_SHAPE.search(
        result.get("serving_decode_kernel_model_shape", "")) or m)
    if m or m_px or m_cl or m_bu or m_sp or m_te or m_dk:
        from chainermn_tpu.tuning.measure import decide

        for row_key, spread_key, name in (
            ("serving_decode_impl_ms", "serving_decode_spread_pct",
             "decode_impl"),
            ("serving_kv_block_ms", "serving_kv_block_spread_pct",
             "kv_block_size"),
            ("serving_spec_ms", "serving_spec_spread_pct",
             "spec_tokens"),
            ("serving_prefix_ttft_ms", "serving_prefix_spread_pct",
             "prefix_cache"),
            ("serving_prefix_msb_ttft_ms",
             "serving_prefix_msb_spread_pct", "min_shared_blocks"),
            ("serving_cluster_disagg_ttft_ms",
             "serving_cluster_disagg_spread_pct", "cluster_disagg"),
            ("serving_burst_chunk_ms",
             "serving_burst_spread_pct", "prefill_chunk"),
            ("seq_parallel_ttft_ms",
             "seq_parallel_spread_pct", "prefill_seq_parallel"),
            ("serving_tenants_adapter_ms",
             "serving_tenants_adapter_spread_pct", "adapter_impl"),
            ("serving_decode_kernel_ms",
             "serving_decode_kernel_spread_pct", "decode_attend_impl"),
        ):
            rows = result.get(row_key)
            if not (isinstance(rows, dict) and len(rows) >= 2 and all(
                isinstance(v, (int, float)) for v in rows.values()
            )):
                continue
            # A PRESENT spread key is a real multi-sample estimate and
            # is used verbatim (0.0 = genuinely tied medians adopts,
            # matching the in-run path); an ABSENT key marks an
            # on-accel single-sample row, which takes the same 10%
            # noise floor the live adoption applies (spreads=None in
            # registry.record_measurement) — neither path can pin a
            # margin the other would have refused.
            if spread_key in result:
                spread = float(result[spread_key])
            else:
                spread = 10.0
            winner = decide(rows, {k: spread for k in rows})
            if winner is not None:
                if name in ("prefix_cache", "min_shared_blocks"):
                    m_row = m_px
                elif name == "cluster_disagg":
                    m_row = m_cl
                elif name == "prefill_chunk":
                    m_row = m_bu
                elif name == "prefill_seq_parallel":
                    m_row = m_sp
                elif name == "adapter_impl":
                    m_row = m_te
                elif name == "decode_attend_impl":
                    m_row = m_dk
                else:
                    m_row = m
                if m_row is None:
                    continue
                key = _bucketed_key(kind, m_row.groups(), "decode")
                evidence = {"candidates_ms": {k: round(float(v), 4)
                                              for k, v in rows.items()},
                            "spread_pct": spread}
                if name == "spec_tokens":
                    # acceptance rate rides as evidence: a cache entry
                    # the next session can audit for WHY K won (high
                    # accept rate) or lost (drafts were junk).
                    rates = result.get("serving_spec_accept_rates")
                    if isinstance(rates, dict):
                        evidence["accept_rates"] = rates
                if name == "prefix_cache":
                    # the hit rate behind the TTFT comparison: 'on'
                    # winning at 0% hits would be noise, not sharing.
                    hr = result.get("serving_prefix_hit_rate")
                    if hr is not None:
                        evidence["hit_rate"] = hr
                if name == "cluster_disagg":
                    # the handoff's measured wire cost + the replica
                    # scaling behind it — a 'disaggregated' entry the
                    # next session can audit.
                    for ev_key, row in (
                        ("transfers", "serving_cluster_transfers"),
                        ("transfer_bytes",
                         "serving_cluster_transfer_bytes"),
                        ("scaling", "serving_cluster_scaling"),
                    ):
                        v = result.get(row)
                        if v is not None:
                            evidence[ev_key] = v
                if name == "prefill_chunk":
                    # the bursty goodput-under-SLO and p99 TTFT behind
                    # the ms ranking — WHY chunking won (or lost) on
                    # this shape, auditable next session.
                    for ev_key, row in (
                        ("goodput", "serving_burst_goodput"),
                        ("ttft_p99_ms", "serving_burst_ttft_p99_ms"),
                    ):
                        v = result.get(row)
                        if v is not None:
                            evidence[ev_key] = v
                if name == "prefill_seq_parallel":
                    # the per-shard-count TTFT curve behind the off/on
                    # ranking (ISSUE 13) — auditable evidence for the
                    # wide-prefill adoption.
                    v = result.get("seq_parallel_ttft_shards_ms")
                    if v is not None:
                        evidence["ttft_shards_ms"] = v
                if name == "decode_attend_impl":
                    # the kernel-vs-gather speedup behind the ranking
                    # (ISSUE 19) — on a CPU proxy the fused arm timed
                    # the interpret-mode EMULATOR, so an 'xla' entry
                    # here is expected and only an on-chip row should
                    # ever seed 'fused'.
                    v = result.get("serving_decode_kernel_fused_speedup")
                    if v is not None:
                        evidence["fused_speedup"] = v
                if name == "adapter_impl":
                    # the multi-tenant goodput + fairness behind the
                    # gather/merged ranking (ISSUE 14) — a 'merged'
                    # entry the next session can audit for WHY the
                    # fold won (single-tenant-dominant traffic).
                    for ev_key, row in (
                        ("goodput", "serving_tenants_goodput"),
                        ("fairness", "serving_tenants_fairness"),
                    ):
                        v = result.get(row)
                        if v is not None:
                            evidence[ev_key] = v
                put(name, key, winner, evidence)

    # Double buffering: the measured on/off step-time ratio.
    speedup = result.get("double_buffer_speedup")
    if speedup:
        n = result.get("n_devices", 1)
        key = _bucketed_key(kind, (n,), "step")
        put("double_buffering", key,
            "on" if speedup > 1.02 else "off",
            {"double_buffer_speedup": speedup,
             "spread_pct": result.get("double_buffer_spread_pct", 0.0)})


def seed_from_bench_details(
    details_path: str | None = None, cache_path: str | None = None
) -> list[str]:
    """Seed the cache from a bench artifact (``BENCH_DETAILS.json`` by
    default), under the ``device_kind`` that run measured on: a chip
    run's winners are adopted for the chip without re-measuring, a CPU
    proxy's entries describe the CPU. Returns the list of seeded
    ``name|key -> winner`` strings."""
    details_path = details_path or os.path.join(
        _REPO_ROOT, "BENCH_DETAILS.json"
    )
    with open(details_path) as f:
        result = json.load(f)
    seeded: list[str] = []
    _seed_one_result(result, f"seeded:{os.path.basename(details_path)}",
                     seeded, cache_path)
    return seeded
