#!/usr/bin/env python
"""Summarize a chainermn_tpu observability trace (JSONL) into per-op
byte/time tables (ISSUE 2: the consumer side of the wire counters).

Usage::

    python tools/trace_report.py TRACE.jsonl [MORE.jsonl ...]
        [--json] [--chrome OUT.json] [--journeys] [--top K]

Multiple JSONL files concatenate before summarizing — the per-rank
trace files of one cluster run merge into one report.

Sections:

- **collectives** — per (op, plane): count, total payload bytes, total
  and mean duration, achieved GB/s where both are known, the wire
  dtypes seen, and how many events carry 'auto' dispatch provenance.
  ``allreduce_grad`` events SUBSUME their per-leaf ``allreduce``
  children (nested spans — don't sum the two rows).
- **steps** — per-phase mean/max milliseconds over the Trainer's
  step-timeline events (data_wait / h2d / compute / logging /
  extensions).
- **dispatch** — every autotune decision the traced processes resolved
  (name=winner(source), keyed).
- **overlap** — comm/compute overlap (ISSUE 3): the step's overlap
  configuration (``overlap_config`` events — double-buffering
  staleness, reduction schedule, donation), the ``wire`` layout the
  compiled schedules committed to (buckets and the bytes their stages
  carry, per schedule), and
  — where measured wire events exist (the eager
  ``OverlappedBucketReducer``; dur = dispatch->ready, blocked = wait
  actually paid at collect) — per-step comm time vs comm time hidden
  behind compute and the ``hidden_fraction`` between them. Omitted
  when the trace carries no overlap events.
- **serving** — continuous-batching accounting (ISSUE 4) from the
  scheduler's ``serving`` events: requests/tokens served, tokens/s over
  device-busy time, nearest-rank p50/p99 per-token latency (one decode
  step = one token for every active request; under speculation, the
  tick latency for 1..K+1 tokens), TTFT (submit → first token) p50/p99,
  mean slot occupancy, and queue-wait/prefill means. When ``speculate``
  events exist (ISSUE 5), adds drafted/accepted token counts, the
  acceptance rate, and an accept-length histogram. When
  ``prefix_cache`` events exist (ISSUE 7), adds the prefix-sharing
  rollup: admission lookups/hits, prompt vs prefilled vs cache-served
  token totals (the measured prefill-work reduction) and COW copies.
  ISSUE 14: prefill/finish events roll up PER TENANT (requests,
  tokens, TTFT/TPOT p50/p99, SLO attainment) with a Jain fairness
  index over the token totals; events without a ``tenant`` tag fall
  back to one ``'default'`` tenant so pre-tenant traces keep parsing.
  Omitted when the trace has no serving events.
- **journeys** (``--journeys``; ISSUE 17) — per-request CAUSAL
  timelines merged across ranks by journey/span ids (hop order, never
  clock order), epoch stamps aligned by the traced ``clock_sync``
  offsets and displayed WITH their uncertainty, the top-K slowest
  requests by TTFT, and per-journey TTFT critical-path decomposition
  (queue wait / prefill / handoff / preemption gap — the components
  sum back to the measured ``ttft_s`` within rounding + clock
  uncertainty, or the report says so loudly).
- **moe** (ISSUE 20) — expert-dispatch rollup from ``moe_dispatch``
  events: aggregate per-expert load histogram with ``load_fractions``
  (a skewed row is the router-collapse signal), dropped/padded token
  totals and the dispatch capacity, plus the layers observed. Omitted
  when the trace carries no MoE events.
- **stragglers** — flagged divergence reports, if any.
- **roofline** — where a device kind with a published HBM peak appears
  (``benchmark/peaks.py``), collective GB/s is floored against it: an
  eager-plane number near the HBM peak is copy-bound, far below it is
  latency/dispatch-bound.

``--json`` prints the machine-readable summary (the contract tested in
tests/test_capture_tools.py); default output is a human table.
``--chrome`` additionally writes a Chrome-trace/Perfetto file.

Durations caveat: device-plane events record dispatch-to-return unless
the trace was captured with ``CHAINERMN_TPU_TRACE_SYNC=1`` (the meta
event's ``sync`` field says which); host-plane (obj) events are true
blocking durations either way. See docs/observability.md.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)


def _trace_mod():
    """The observability trace module, loaded by FILE PATH: one owner of
    the JSONL parser and the Chrome exporter (no drift), without paying
    for ``import chainermn_tpu`` (which pulls jax) in a report tool."""
    import importlib.util

    path = os.path.join(_HERE, "chainermn_tpu", "observability", "trace.py")
    spec = importlib.util.spec_from_file_location("_obs_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _journey_mod():
    """The journey merge module, loaded the same file-path way (pure
    stdlib by contract — see its module docstring)."""
    import importlib.util

    path = os.path.join(
        _HERE, "chainermn_tpu", "observability", "journey.py")
    spec = importlib.util.spec_from_file_location("_obs_journey", path)
    mod = importlib.util.module_from_spec(spec)
    # Register BEFORE exec: @dataclass resolves its defining module
    # through sys.modules (3.10's KW_ONLY probe dies on None).
    sys.modules["_obs_journey"] = mod
    spec.loader.exec_module(mod)
    return mod


def _read_events(paths) -> list[dict]:
    if isinstance(paths, str):
        paths = [paths]
    tm = _trace_mod()
    events: list[dict] = []
    for p in paths:
        events.extend(tm.read_jsonl(p))
    return events


@functools.lru_cache(maxsize=None)
def _hbm_peak(device_kind: str):
    """Per-kind HBM peak in bytes/s from ``benchmark/peaks.py`` (the one
    place device peaks live), loaded by file path; None for a kind that
    has no published row there (a CPU, say)."""
    import importlib.util

    path = os.path.join(_HERE, "benchmark", "peaks.py")
    spec = importlib.util.spec_from_file_location("_bench_peaks", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        return mod.lookup(device_kind)["hbm_bytes_per_s"]
    except KeyError:
        return None


def summarize(events: list[dict]) -> dict:
    """The machine-readable summary: stable keys, deterministic ordering
    (tests/test_capture_tools.py pins this contract)."""
    coll: dict = {}
    steps: list[dict] = []
    dispatch: list[dict] = []
    stragglers: list[dict] = []
    packs: list[dict] = []
    moes: list[dict] = []
    schemas: set[int] = set()
    meta: dict = {}

    for ev in events:
        if "schema" in ev:
            schemas.add(ev["schema"])
        kind = ev.get("kind")
        if kind == "meta":
            # first meta wins for top-level fields; sync=True anywhere
            # means at least part of the trace has true durations
            for k in ("started_at", "sync", "source", "mode"):
                if k in ev and k not in meta:
                    meta[k] = ev[k]
            # dropped_events ACCUMULATES (one close() meta per recorder;
            # a multi-process trace file carries several) — a summary
            # over a lossy trace must say so loudly, not silently
            # under-count (ISSUE 6 satellite; previously ignored).
            if ev.get("dropped_events"):
                meta["dropped_events"] = (
                    meta.get("dropped_events", 0)
                    + int(ev["dropped_events"])
                )
            continue
        if kind == "collective":
            key = (ev.get("op", "?"), ev.get("plane", "?"))
            row = coll.setdefault(key, {
                "n": 0, "nbytes": 0, "dur_s": 0.0, "n_with_bytes": 0,
                "n_with_dur": 0, "wire_dtypes": set(), "n_auto": 0,
                "devices": set(),
            })
            row["n"] += 1
            if ev.get("nbytes") is not None:
                row["nbytes"] += int(ev["nbytes"])
                row["n_with_bytes"] += 1
            if ev.get("dur_s") is not None:
                row["dur_s"] += float(ev["dur_s"])
                row["n_with_dur"] += 1
            if ev.get("wire_dtype"):
                row["wire_dtypes"].add(str(ev["wire_dtype"]))
            if ev.get("provenance"):
                row["n_auto"] += 1
            if ev.get("device"):
                row["devices"].add(str(ev["device"]))
        elif kind == "step":
            steps.append(ev)
        elif kind == "dispatch":
            dispatch.append(ev)
        elif kind == "straggler":
            stragglers.append(ev)
        elif kind == "pack":
            packs.append(ev)
        elif kind == "moe_dispatch":
            moes.append(ev)

    ops = []
    for (op, plane) in sorted(coll):
        row = coll[(op, plane)]
        entry = {
            "op": op,
            "plane": plane,
            "n": row["n"],
            "total_bytes": row["nbytes"],
            "total_s": round(row["dur_s"], 6),
            "mean_ms": (round(row["dur_s"] / row["n_with_dur"] * 1e3, 4)
                        if row["n_with_dur"] else None),
            "wire_dtypes": sorted(row["wire_dtypes"]),
            "auto_events": row["n_auto"],
        }
        if row["nbytes"] and row["dur_s"] > 0 and row["n_with_bytes"]:
            # 6 decimals: host-plane obj collectives run at KB/ms scales
            # where 3 would round every row to 0.0
            entry["gbps"] = round(row["nbytes"] / row["dur_s"] / 1e9, 6)
        entry["_devices"] = sorted(row["devices"])  # stripped before emit
        ops.append(entry)

    phase_stats: dict = {}
    for ev in steps:
        for k, v in (ev.get("phases") or {}).items():
            s = phase_stats.setdefault(k, {"sum": 0.0, "max": 0.0, "n": 0})
            s["sum"] += float(v)
            s["max"] = max(s["max"], float(v))
            s["n"] += 1
    phases = {
        k: {"mean_ms": round(s["sum"] / s["n"] * 1e3, 4),
            "max_ms": round(s["max"] * 1e3, 4), "n": s["n"]}
        for k, s in sorted(phase_stats.items()) if s["n"]
    }

    disp = [
        {"name": d.get("name"), "key": d.get("key"),
         "winner": d.get("winner"), "source": d.get("source")}
        for d in dispatch
    ]

    out = {
        "schema_versions": sorted(schemas),
        "meta": meta,
        "n_events": len(events),
        "collectives": ops,
        "steps": {"n": len(steps), "phases": phases},
        "dispatch": disp,
        "packs": [
            {k: p.get(k) for k in
             ("op", "nbytes", "bucket_bytes", "n_buckets", "wire_dtype")}
            for p in packs
        ],
        "stragglers": [
            {"flagged_ranks": s.get("flagged_ranks"),
             "phases": s.get("phases")}
            for s in stragglers
        ],
    }

    # Roofline floors where the device kind names a known HBM peak:
    # device-plane ops only, floored against the kinds THEY actually ran
    # on (a multi-backend trace — e.g. a chip run and its CPU children
    # in one file — must not cross-product ops against foreign devices, and
    # a host-plane pickle transfer has no HBM roofline at all).
    floors = []
    for entry in ops:
        if entry["plane"] != "device" or not entry.get("gbps"):
            continue
        for kind in entry["_devices"]:
            peak = _hbm_peak(kind)
            if not peak:
                continue
            floors.append({
                "device": kind, "op": entry["op"],
                "achieved_gbps": entry["gbps"],
                "hbm_peak_gbps": round(peak / 1e9, 1),
                "fraction_of_peak": round(entry["gbps"] * 1e9 / peak, 4),
            })
    for entry in ops:
        entry.pop("_devices")
    if floors:
        out["roofline"] = floors

    # MoE dispatch rollup (ISSUE 20): aggregate the per-layer expert
    # load histogram and the drop/pad token flow across every
    # ``moe_dispatch`` event — a skewed ``load_fractions`` row is the
    # router-collapse signal the aux loss is supposed to prevent.
    if moes:
        load: list[float] = []
        dropped = padded = 0.0
        for ev in moes:
            dropped += float(ev.get("dropped") or 0)
            padded += float(ev.get("padded") or 0)
            for i, v in enumerate(ev.get("expert_load") or ()):
                while len(load) <= i:
                    load.append(0.0)
                load[i] += float(v)
        total = sum(load)
        out["moe"] = {
            "n_events": len(moes),
            "dropped_tokens": round(dropped, 3),
            "padded_slots": round(padded, 3),
            "capacity": max((float(ev.get("capacity") or 0)
                             for ev in moes), default=0.0),
            "expert_load": [round(v, 3) for v in load],
            "load_fractions": [round(v / total, 4) if total else 0.0
                               for v in load],
            "layers": sorted({int(ev["layer"]) for ev in moes
                              if ev.get("layer") is not None}),
        }

    # Overlap section (one owner of the rollup: the trace module's
    # summarize_overlap — bench's overlap phase reads the same shape).
    overlap = _trace_mod().summarize_overlap(events)
    if overlap is not None:
        out["overlap"] = overlap
    # Serving section (ISSUE 4: same one-owner discipline —
    # summarize_serving feeds this report AND bench's serving phase).
    serving = _trace_mod().summarize_serving(events)
    if serving is not None:
        out["serving"] = serving
    return out


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n} B"
        n /= 1024
    return f"{n}"


def render_text(s: dict) -> str:
    lines = []
    dropped = s["meta"].get("dropped_events")
    if dropped:
        lines.append(
            f"*** WARNING: the recorder DROPPED {dropped} event(s) "
            f"(in-memory buffer overflow) — every count below "
            f"undercounts; raise MAX_BUFFERED_EVENTS or shorten the "
            f"capture ***"
        )
    lines.append(
        f"trace: {s['n_events']} events, schema {s['schema_versions']}, "
        f"sync={s['meta'].get('sync', False)}"
    )
    if s["collectives"]:
        lines.append("")
        lines.append(f"{'op':<18} {'plane':<7} {'n':>6} {'bytes':>12} "
                     f"{'total s':>9} {'mean ms':>9} {'GB/s':>7} "
                     f"{'auto':>5}  wire")
        for e in s["collectives"]:
            lines.append(
                f"{e['op']:<18} {e['plane']:<7} {e['n']:>6} "
                f"{_fmt_bytes(e['total_bytes']):>12} "
                f"{e['total_s']:>9.4f} "
                f"{(e['mean_ms'] if e['mean_ms'] is not None else 0):>9.3f} "
                f"{(str(e.get('gbps', '-'))):>7} "
                f"{e['auto_events']:>5}  {','.join(e['wire_dtypes']) or '-'}"
            )
        lines.append("(allreduce_grad rows subsume their nested "
                     "per-leaf allreduce rows; don't sum)")
    if s["steps"]["n"]:
        lines.append("")
        lines.append(f"steps: {s['steps']['n']}")
        for k, v in s["steps"]["phases"].items():
            lines.append(f"  {k:<12} mean {v['mean_ms']:>9.3f} ms   "
                         f"max {v['max_ms']:>9.3f} ms")
    if s["dispatch"]:
        lines.append("")
        lines.append("dispatch decisions:")
        for d in s["dispatch"]:
            lines.append(f"  {d['name']}={d['winner']} ({d['source']}) "
                         f"key={d['key']}")
    if s["packs"]:
        lines.append("")
        lines.append("gradient packs (per compilation):")
        for p in s["packs"]:
            lines.append(
                f"  {p['op']}: {p['n_buckets']} bucket(s) x "
                f"<= {_fmt_bytes(p['bucket_bytes'] or 0)}, wire "
                f"{p['wire_dtype']}, {_fmt_bytes(p['nbytes'] or 0)} total"
            )
    if s.get("overlap"):
        ov = s["overlap"]
        lines.append("")
        lines.append("comm/compute overlap:")
        for cfg in ov.get("config", []):
            lines.append(
                f"  mode: double_buffering={cfg.get('double_buffering')} "
                f"staleness={cfg.get('staleness')} "
                f"schedule={cfg.get('schedule') or 'communicator-default'} "
                f"donate={cfg.get('donate')}"
            )
        for name, row in ov.get("schedules", {}).items():
            lines.append(
                f"  {name}: {row['buckets']} bucket(s), "
                f"{_fmt_bytes(row['nbytes'])} wire, "
                f"{row['overlapped']} overlapped"
            )
        m = ov.get("measured")
        if m:
            lines.append(
                f"  measured: comm {m['comm_ms_total']:.3f} ms total, "
                f"{m['comm_ms_hidden']:.3f} ms hidden behind compute "
                f"({m['hidden_fraction'] * 100:.1f}% hidden, "
                f"{m['n']} bucket events)"
            )
    if s.get("serving"):
        sv = s["serving"]
        lines.append("")
        lines.append("serving (continuous batching):")
        lines.append(
            f"  {sv['requests']} request(s), {sv['generated_tokens']} "
            f"token(s) over {sv['prefills']} prefill(s) + "
            f"{sv['decode_steps']} decode step(s)"
        )
        if sv.get("tokens_per_sec") is not None:
            lines.append(f"  tokens/s: {sv['tokens_per_sec']}")
        if sv.get("token_ms_p50") is not None:
            lines.append(
                f"  per-token latency: p50 {sv['token_ms_p50']:.3f} ms, "
                f"p99 {sv['token_ms_p99']:.3f} ms"
            )
        if sv.get("ttft_ms_p50") is not None:
            lines.append(
                f"  TTFT: p50 {sv['ttft_ms_p50']:.3f} ms, "
                f"p99 {sv['ttft_ms_p99']:.3f} ms"
            )
        if sv.get("tpot_ms_p50") is not None:
            lines.append(
                f"  TPOT: p50 {sv['tpot_ms_p50']:.3f} ms, "
                f"p99 {sv['tpot_ms_p99']:.3f} ms per request"
            )
        if sv.get("slo_attainment") is not None:
            lines.append(
                f"  SLO attainment: {sv['slo_attainment'] * 100:.1f}% "
                f"of {sv['slo_requests']} target-bearing request(s)"
            )
        if sv.get("preemptions"):
            lines.append(f"  preemptions: {sv['preemptions']}")
        ck = sv.get("chunked_prefill")
        if ck:
            lines.append(
                f"  chunked prefill: {ck['chunk_tokens']} prompt "
                f"token(s) over {ck['chunks']} mixed-step chunk(s)"
            )
        if sv.get("occupancy_mean") is not None:
            lines.append(
                f"  slot occupancy: {sv['occupancy_mean'] * 100:.1f}% mean"
            )
        sp = sv.get("speculation")
        if sp:
            rate = sp.get("accept_rate")
            lines.append(
                f"  speculation: {sp['drafted']} drafted, "
                f"{sp['accepted']} accepted"
                + (f" ({rate * 100:.1f}% acceptance)"
                   if rate is not None else "")
                + f" over {sp['ticks']} tick(s)"
            )
            hist = " ".join(
                f"{k}:{v}" for k, v in sorted(
                    sp.get("accept_len_hist", {}).items(),
                    key=lambda kv: int(kv[0]),
                )
            )
            if hist:
                lines.append(f"  accept-length histogram: {hist}")
        px = sv.get("prefix_cache")
        if px:
            lines.append(
                f"  prefix cache: {px['hits']}/{px['lookups']} admissions "
                f"hit ({px['hit_rate'] * 100:.1f}%), "
                f"{px['prefilled_tokens']}/{px['prompt_tokens']} prompt "
                f"tokens prefilled ({px['hit_tokens']} served from "
                f"cache), {px['cow_blocks']} COW block cop"
                f"{'y' if px['cow_blocks'] == 1 else 'ies'}"
            )
        tn = sv.get("tenants")
        if tn:
            # ISSUE 14: the per-tenant rollup (requests/tokens/latency
            # percentiles/SLO) + the Jain fairness index over token
            # totals; pre-tenant traces print one 'default' row.
            lines.append(
                f"  tenants: {len(tn)} (Jain fairness "
                f"{sv['tenant_fairness_jain']:.4f})"
            )
            for t, row in tn.items():
                parts = [f"{row['requests']} req",
                         f"{row['generated_tokens']} tok"]
                if row.get("ttft_ms_p50") is not None:
                    parts.append(
                        f"TTFT p50/p99 {row['ttft_ms_p50']:.3f}/"
                        f"{row['ttft_ms_p99']:.3f} ms")
                if row.get("tpot_ms_p50") is not None:
                    parts.append(
                        f"TPOT p50/p99 {row['tpot_ms_p50']:.3f}/"
                        f"{row['tpot_ms_p99']:.3f} ms")
                if row.get("slo_requests"):
                    parts.append(
                        f"SLO {row['slo_attainment'] * 100:.1f}% of "
                        f"{row['slo_requests']}")
                lines.append(f"    {t}: " + ", ".join(parts))
        # queue_wait and prefill are separate events: a truncated trace
        # may carry one without the other — guard each independently.
        if sv.get("queue_wait_ms_mean") is not None:
            lines.append(
                f"  queue wait: {sv['queue_wait_ms_mean']:.3f} ms mean"
            )
        if sv.get("prefill_ms_mean") is not None:
            lines.append(
                f"  prefill: {sv['prefill_ms_mean']:.3f} ms mean"
            )
    if s.get("moe"):
        mo = s["moe"]
        lines.append("")
        lines.append(
            f"moe dispatch: {mo['n_events']} events, capacity "
            f"{mo['capacity']:g}, dropped {mo['dropped_tokens']:g} "
            f"tokens, padded {mo['padded_slots']:g} slots"
        )
        if mo.get("layers"):
            lines.append(f"  layers: {mo['layers']}")
        if mo.get("expert_load"):
            frac = " ".join(
                f"e{i}={f * 100:.1f}%"
                for i, f in enumerate(mo["load_fractions"])
            )
            lines.append(f"  expert load: {frac}")
    if s["stragglers"]:
        lines.append("")
        lines.append(f"STRAGGLER reports: {len(s['stragglers'])}")
        for r in s["stragglers"]:
            lines.append(f"  flagged ranks {r['flagged_ranks']}: "
                         f"{json.dumps(r['phases'])}")
    if s.get("roofline"):
        lines.append("")
        lines.append("roofline (eager-plane achieved vs HBM peak):")
        for f in s["roofline"]:
            lines.append(
                f"  {f['op']} on {f['device']}: {f['achieved_gbps']} GB/s "
                f"= {f['fraction_of_peak'] * 100:.1f}% of "
                f"{f['hbm_peak_gbps']} GB/s"
            )
    return "\n".join(lines)


def render_journeys(j: dict) -> str:
    """Human rendering of the :func:`journey.merge_journeys` section."""
    lines = []
    clock = j["clock"]
    lines.append(
        f"journeys: {j['n_journeys']} merged, {j['n_complete']} "
        f"complete, {j['n_orphan_spans']} orphan span(s)"
    )
    if clock["offsets"]:
        for rank, off in sorted(clock["offsets"].items()):
            lines.append(
                f"  clock: rank {rank} offset "
                f"{off['offset_s'] * 1e3:+.3f} ms to rank "
                f"{off['peer']} (± {off['uncertainty_s'] * 1e3:.3f} ms)"
            )
    else:
        lines.append(
            "  clock: no clock_sync events — cross-rank stamps are "
            "raw epochs (uncertainty unbounded)"
        )
    for row in j["slowest"]:
        d = row["decomposition"]
        head = (f"  {row['journey']}: {row['n_spans']} span(s) over "
                f"rank(s) {row['ranks']}")
        if not row["complete"]:
            head += "  [INCOMPLETE: no finish]"
        if not row["contiguous"]:
            head += "  [HOP GAPS]"
        if row["orphan_spans"]:
            head += f"  [ORPHANS: {row['orphan_spans']}]"
        lines.append(head)
        if d is not None:
            parts = [
                f"queue {d['queue_wait_s'] * 1e3:.3f}",
                f"prefill {d['prefill_s'] * 1e3:.3f}",
                f"handoff {d['handoff_s'] * 1e3:.3f}",
            ]
            if d["preempts_before_first_token"]:
                parts.append(
                    f"preempt-gap {d['preempt_gap_s'] * 1e3:.3f} "
                    f"({d['preempts_before_first_token']} preempt(s))")
            decomp = (f"    TTFT {d['ttft_s'] * 1e3:.3f} ms = "
                      + " + ".join(parts)
                      + f"  (residual {d['residual_s'] * 1e3:+.4f} ms)")
            lines.append(decomp)
            if d.get("total_s") is not None:
                lines.append(
                    f"    total {d['total_s'] * 1e3:.3f} ms "
                    f"(decode {d['decode_s'] * 1e3:.3f} ms)")
        for sp in row["spans"]:
            what = sp["phase"] or sp["kind"]
            dur = (f"  dur {sp['dur_s'] * 1e3:.3f} ms"
                   if sp.get("dur_s") is not None else "")
            lines.append(
                f"    hop {sp['hop']:<2} rank {sp['rank']} "
                f"{what:<14} t_adj {sp['t_adj']}{dur}"
            )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize a chainermn_tpu observability JSONL trace"
    )
    ap.add_argument("trace", nargs="+",
                    help="JSONL trace file(s) — per-rank files of one "
                         "run concatenate before summarizing")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable summary")
    ap.add_argument("--chrome", metavar="OUT",
                    help="also write a Chrome-trace/Perfetto JSON file")
    ap.add_argument("--journeys", action="store_true",
                    help="merge per-request causal journeys across "
                         "ranks (ISSUE 17) and report the slowest")
    ap.add_argument("--top", type=int, default=5,
                    help="journeys to show in the slowest table "
                         "(default 5)")
    args = ap.parse_args(argv)

    events = _read_events(args.trace)
    summary = summarize(events)
    if args.journeys:
        summary["journeys"] = _journey_mod().merge_journeys(
            events, top=args.top)
    # Loud on stderr too, so --json pipelines (and humans paging the
    # table) cannot miss a lossy trace.
    if summary["meta"].get("dropped_events"):
        print(
            f"WARNING: trace dropped "
            f"{summary['meta']['dropped_events']} event(s) — summary "
            f"undercounts",
            file=sys.stderr,
        )
    if args.chrome:
        with open(args.chrome, "w") as f:
            json.dump(_trace_mod().chrome_trace(events), f)
        if not args.json:
            print(f"chrome trace: {args.chrome}", file=sys.stderr)
    try:
        if args.json:
            print(json.dumps(summary, sort_keys=True))
        else:
            text = render_text(summary)
            if args.journeys:
                text += "\n\n" + render_journeys(summary["journeys"])
            print(text)
    except BrokenPipeError:
        # piped into head/less that closed early — not an error
        try:
            sys.stdout.close()
        except OSError:
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
