"""Contract tests for the offline observability tools
(``tools/trace_report.py`` and ``tools/metrics_dump.py``): golden
traces in, pinned report fields out.
"""

import os
import subprocess

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _golden_trace_lines():
    """A small fixed trace: meta + auto/explicit collectives + steps +
    dispatch + straggler + one torn line (crashed-writer tail)."""
    import json as _json

    evs = [
        {"schema": 1, "kind": "meta", "t": 1.0, "pid": 1, "rank": 0,
         "started_at": "2026-08-03T00:00:00Z", "sync": False,
         "source": "bench"},
        {"schema": 1, "kind": "collective", "t": 1.1, "pid": 1, "rank": 0,
         "op": "allreduce_grad", "plane": "device", "nbytes": 1000,
         "dur_s": 0.002, "wire_dtype": "bfloat16", "size": 8,
         "device": "cpu",
         "provenance": {"name": "allreduce_wire", "winner": "bf16",
                        "source": "table", "key": "cpu|8|grad"}},
        {"schema": 1, "kind": "collective", "t": 1.2, "pid": 1, "rank": 0,
         "op": "allreduce_grad", "plane": "device", "nbytes": 1000,
         "dur_s": 0.002, "wire_dtype": "bfloat16", "size": 8,
         "device": "cpu"},
        {"schema": 1, "kind": "collective", "t": 1.3, "pid": 1, "rank": 0,
         "op": "bcast_obj", "plane": "host", "nbytes": 64,
         "dur_s": 0.0005, "size": 2},
        {"schema": 1, "kind": "step", "t": 1.4, "pid": 1, "rank": 0,
         "iteration": 1,
         "phases": {"data_wait": 0.001, "compute": 0.01,
                    "logging": 0.0}},
        {"schema": 1, "kind": "step", "t": 1.5, "pid": 1, "rank": 0,
         "iteration": 2,
         "phases": {"data_wait": 0.003, "compute": 0.02,
                    "logging": 0.001}},
        {"schema": 1, "kind": "dispatch", "t": 1.6, "pid": 1, "rank": 0,
         "name": "allreduce_wire", "key": "cpu|8|grad", "winner": "bf16",
         "source": "table"},
        {"schema": 1, "kind": "straggler", "t": 1.7, "pid": 1, "rank": 0,
         "flagged_ranks": [3],
         "phases": {"compute": {"median_s": 0.01, "worst_rank": 3,
                                "worst_rel_dev": 0.8, "flagged": [3]}}},
        # Overlap configuration + wire events. Trace-time layout events
        # as reduce_tree records them, one a bucket a stage, no dur: a
        # flat bucket of the double-buffered mode, and a two_level
        # bucket on a 2x4 mesh (the scatter and the gather carry the
        # bucket, the shard's all-reduce a quarter of it). Then two
        # MEASURED eager-reducer events (dur = dispatch->ready, blocked
        # = wait paid at collect; the 4 ms gap on bucket 0 is comm
        # hidden by compute).
        {"schema": 1, "kind": "overlap_config", "t": 1.8, "pid": 1,
         "rank": 0, "double_buffering": True, "staleness": 1,
         "schedule": "two_level", "donate": True},
        {"schema": 1, "kind": "wire", "t": 1.9, "pid": 1, "rank": 0,
         "schedule": "flat", "stage": "ar(inter+intra)", "stage_index": 0,
         "bucket": 0, "n_buckets": 1,
         "nbytes": 1000, "wire_dtype": "bfloat16", "overlapped": True},
        {"schema": 1, "kind": "wire", "t": 2.0, "pid": 1, "rank": 0,
         "schedule": "overlap_eager", "bucket": 0, "n_buckets": 2,
         "nbytes": 4096, "dur_s": 0.005, "blocked_s": 0.001,
         "overlapped": True},
        {"schema": 1, "kind": "wire", "t": 2.1, "pid": 1, "rank": 0,
         "schedule": "overlap_eager", "bucket": 1, "n_buckets": 2,
         "nbytes": 4096, "dur_s": 0.003, "blocked_s": 0.003,
         "overlapped": False},
        {"schema": 1, "kind": "wire", "t": 2.12, "pid": 1, "rank": 0,
         "schedule": "two_level", "stage": "rs(intra)", "stage_index": 0,
         "bucket": 0, "n_buckets": 1, "nbytes": 2048,
         "wire_dtype": "bfloat16", "overlapped": False},
        {"schema": 1, "kind": "wire", "t": 2.13, "pid": 1, "rank": 0,
         "schedule": "two_level", "stage": "ar(inter)", "stage_index": 1,
         "bucket": 0, "n_buckets": 1, "nbytes": 512,
         "wire_dtype": "bfloat16", "overlapped": False},
        {"schema": 1, "kind": "wire", "t": 2.14, "pid": 1, "rank": 0,
         "schedule": "two_level", "stage": "ag(intra)", "stage_index": 2,
         "bucket": 0, "n_buckets": 1, "nbytes": 2048,
         "wire_dtype": "bfloat16", "overlapped": False},
        # ISSUE 4: one request through the serving scheduler — queue
        # wait, bucketed prefill (its sampled token counts as generated;
        # ttft_s = submit -> first token, ISSUE 5), three decode steps
        # at varying occupancy, finish.
        {"schema": 1, "kind": "serving", "t": 2.2, "pid": 1, "rank": 0,
         "phase": "queue_wait", "request": "r0", "dur_s": 0.002},
        {"schema": 1, "kind": "serving", "t": 2.3, "pid": 1, "rank": 0,
         "phase": "prefill", "request": "r0", "slot": 0, "prompt_len": 5,
         "dur_s": 0.01, "ttft_s": 0.012},
        {"schema": 1, "kind": "serving", "t": 2.4, "pid": 1, "rank": 0,
         "phase": "decode_step", "n_active": 1, "n_slots": 4, "tokens": 1,
         "dur_s": 0.004},
        {"schema": 1, "kind": "serving", "t": 2.5, "pid": 1, "rank": 0,
         "phase": "decode_step", "n_active": 2, "n_slots": 4, "tokens": 2,
         "dur_s": 0.006},
        {"schema": 1, "kind": "serving", "t": 2.6, "pid": 1, "rank": 0,
         "phase": "decode_step", "n_active": 1, "n_slots": 4, "tokens": 1,
         "dur_s": 0.002},
        {"schema": 1, "kind": "serving", "t": 2.7, "pid": 1, "rank": 0,
         "phase": "finish", "request": "r0", "generated": 4,
         "dur_s": 0.03},
        # ISSUE 5: two speculative ticks — per-tick drafted/accepted
        # counts and per-slot accept lengths (8 drafted, 2 accepted ->
        # 25% acceptance; histogram counts PER-SLOT accept lengths).
        {"schema": 1, "kind": "speculate", "t": 2.8, "pid": 1, "rank": 0,
         "drafted": 4, "accepted": 2, "accept_lens": [2], "dur_s": 0.004},
        {"schema": 1, "kind": "speculate", "t": 2.9, "pid": 1, "rank": 0,
         "drafted": 4, "accepted": 0, "accept_lens": [0, 0],
         "dur_s": 0.006},
        # ISSUE 7: two prefix-cache admissions — a miss that prefilled
        # the whole 5-token prompt, then a full-prefix hit that adopted
        # 2 blocks (16 tokens), prefilled only the 1-token tail and
        # copied the boundary block (COW).
        {"schema": 1, "kind": "prefix_cache", "t": 3.0, "pid": 1,
         "rank": 0, "request": "r0", "slot": 0, "prompt_tokens": 5,
         "hit_blocks": 0, "hit_tokens": 0, "prefill_tokens": 5,
         "cow_blocks": 0},
        {"schema": 1, "kind": "prefix_cache", "t": 3.1, "pid": 1,
         "rank": 0, "request": "r1", "slot": 1, "prompt_tokens": 16,
         "hit_blocks": 2, "hit_tokens": 16, "prefill_tokens": 1,
         "cow_blocks": 1},
        # ISSUE 11: chunked prefill + SLO scheduling — one preemption,
        # two mixed-step chunk rows (12 prompt tokens written through
        # the mixed step), and a target-bearing finish whose TPOT
        # verdict failed (explicit tpot_ms preferred over the derived
        # fallback; r0's finish above derives 6.0 ms from dur - ttft).
        {"schema": 1, "kind": "serving", "t": 3.2, "pid": 1, "rank": 0,
         "phase": "preempt", "request": "r1", "generated": 2,
         "dur_s": 0.02},
        {"schema": 1, "kind": "prefill_chunk", "t": 3.3, "pid": 1,
         "rank": 0, "request": "r2", "slot": 2, "chunk": 0,
         "tokens": 8, "dur_s": 0.004},
        {"schema": 1, "kind": "prefill_chunk", "t": 3.35, "pid": 1,
         "rank": 0, "request": "r2", "slot": 2, "chunk": 1,
         "tokens": 4, "dur_s": 0.004},
        # ISSUE 14: r2 carries a tenant tag — the per-tenant rollup
        # buckets it under 'acme' while the pre-tenant r0 events fall
        # back to the 'default' tenant (old traces keep parsing).
        {"schema": 1, "kind": "serving", "t": 3.4, "pid": 1, "rank": 0,
         "phase": "finish", "request": "r2", "generated": 5,
         "dur_s": 0.05, "tpot_ms": 8.0, "slo_ttft_ok": True,
         "slo_tpot_ok": False, "tenant": "acme"},
        # ISSUE 20: two MoE dispatch observations (layers 0/1) — the
        # per-expert load histograms sum across events in the 'moe'
        # section ([10, 6] -> 62.5%/37.5% load fractions), with the
        # dropped/padded token flow and the static capacity beside.
        {"schema": 1, "kind": "moe_dispatch", "t": 3.5, "pid": 1,
         "rank": 0, "layer": 0, "expert_load": [6.0, 2.0],
         "n_experts": 2, "dropped": 1.0, "padded": 0.0,
         "capacity": 4.0},
        {"schema": 1, "kind": "moe_dispatch", "t": 3.6, "pid": 1,
         "rank": 0, "layer": 1, "expert_load": [4.0, 4.0],
         "n_experts": 2, "dropped": 0.0, "padded": 0.0,
         "capacity": 4.0},
    ]
    return [_json.dumps(e) for e in evs] + ['{"torn']


def test_trace_report_contract(tmp_path):
    """Golden JSONL in -> stable summary out (ISSUE 2 satellite): the
    machine-readable contract downstream consumers (capture logs,
    future dashboards) parse. Full-dict equality so a field rename or
    rounding change is a DELIBERATE contract bump, not drift."""
    import json as _json
    import sys

    trace_file = tmp_path / "trace.jsonl"
    trace_file.write_text("\n".join(_golden_trace_lines()) + "\n")
    chrome_file = tmp_path / "chrome.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
         str(trace_file), "--json", "--chrome", str(chrome_file)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    summary = _json.loads(proc.stdout)
    assert summary == {
        "schema_versions": [1],
        "meta": {"started_at": "2026-08-03T00:00:00Z", "sync": False,
                 "source": "bench"},
        "n_events": 31,  # torn tail line skipped, not fatal
        "collectives": [
            {"op": "allreduce_grad", "plane": "device", "n": 2,
             "total_bytes": 2000, "total_s": 0.004, "mean_ms": 2.0,
             "wire_dtypes": ["bfloat16"], "auto_events": 1,
             "gbps": 0.0005},  # 2000 B / 4 ms
            {"op": "bcast_obj", "plane": "host", "n": 1,
             "total_bytes": 64, "total_s": 0.0005, "mean_ms": 0.5,
             "wire_dtypes": [], "auto_events": 0, "gbps": 0.000128},
        ],
        "steps": {"n": 2, "phases": {
            "compute": {"mean_ms": 15.0, "max_ms": 20.0, "n": 2},
            "data_wait": {"mean_ms": 2.0, "max_ms": 3.0, "n": 2},
            "logging": {"mean_ms": 0.5, "max_ms": 1.0, "n": 2},
        }},
        "dispatch": [{"name": "allreduce_wire", "key": "cpu|8|grad",
                      "winner": "bf16", "source": "table"}],
        "packs": [],
        "stragglers": [{"flagged_ranks": [3], "phases": {
            "compute": {"median_s": 0.01, "worst_rank": 3,
                        "worst_rel_dev": 0.8, "flagged": [3]}}}],
        # ISSUE 3: per-step comm vs comm-overlapped-with-compute, from
        # the per-bucket wire events. 8 ms of measured bucket comm, 4 ms
        # of it waited on -> half the wire rode behind compute.
        "overlap": {
            "config": [{"double_buffering": True, "staleness": 1,
                        "schedule": "two_level", "donate": True}],
            # a bucket is counted at its first stage; nbytes is what
            # its stages carry together (2048 + 512 + 2048)
            "schedules": {
                "flat": {"buckets": 1, "nbytes": 1000, "overlapped": 1},
                "two_level": {"buckets": 1, "nbytes": 4608,
                              "overlapped": 0},
            },
            "measured": {"n": 2, "comm_ms_total": 8.0,
                         "comm_ms_blocked": 4.0, "comm_ms_hidden": 4.0,
                         "hidden_fraction": 0.5},
        },
        # ISSUE 4/5: the serving rollup — tokens/s over device-busy time
        # (1 prefill token + 4 step tokens over 10 + 12 ms), nearest-rank
        # p50/p99 over the three step durations, TTFT from the prefill's
        # ttft_s, mean occupancy (0.25 + 0.5 + 0.25)/3, and the
        # speculation totals from the two speculate events.
        "serving": {
            "requests": 2,
            "prefills": 1,
            "generated_tokens": 5,
            "decode_steps": 3,
            "queue_wait_ms_mean": 2.0,
            "prefill_ms_mean": 10.0,
            "token_ms_p50": 4.0,
            "token_ms_p99": 6.0,
            "ttft_ms_p50": 12.0,
            "ttft_ms_p99": 12.0,
            # ISSUE 11: per-request TPOT — r0 derives (30 - 12) ms / 3
            # intervals = 6.0; r2 carries an explicit tpot_ms = 8.0.
            "tpot_ms_p50": 6.0,
            "tpot_ms_p99": 8.0,
            "occupancy_mean": 0.3333,
            "tokens_per_sec": 227.27,
            # ISSUE 11: one target-bearing finish, TPOT verdict failed;
            # one preemption; 12 prompt tokens over 2 mixed-step chunks.
            "slo_requests": 1,
            "slo_attainment": 0.0,
            "preemptions": 1,
            "chunked_prefill": {"chunks": 2, "chunk_tokens": 12},
            "speculation": {
                "ticks": 2,
                "drafted": 8,
                "accepted": 2,
                "accept_rate": 0.25,
                "accept_len_hist": {"0": 2, "2": 1},
            },
            # ISSUE 7: the prefix-sharing rollup — 1 of 2 admissions
            # hit; 6 of 21 prompt tokens were actually prefilled (16
            # rode the cache), one boundary-block COW copy.
            "prefix_cache": {
                "lookups": 2,
                "hits": 1,
                "hit_rate": 0.5,
                "prompt_tokens": 21,
                "hit_tokens": 16,
                "prefilled_tokens": 6,
                "hit_token_rate": 0.7619,
                "cow_blocks": 1,
            },
            # ISSUE 14: the per-tenant rollup — r2's tenant-tagged
            # finish lands under 'acme', the pre-tenant r0 events fall
            # back to 'default'; Jain over the [5, 4] token totals =
            # 81/82.
            "tenants": {
                "acme": {"requests": 1, "generated_tokens": 5,
                         "ttft_ms_p50": None, "ttft_ms_p99": None,
                         "tpot_ms_p50": 8.0, "tpot_ms_p99": 8.0,
                         "slo_requests": 1, "slo_attainment": 0.0},
                "default": {"requests": 1, "generated_tokens": 4,
                            "ttft_ms_p50": 12.0, "ttft_ms_p99": 12.0,
                            "tpot_ms_p50": 6.0, "tpot_ms_p99": 6.0},
            },
            "tenant_fairness_jain": 0.9878,
        },
        # ISSUE 20: the MoE dispatch rollup — summed expert-load
        # histogram with load fractions (the router-collapse signal),
        # total dropped/padded token flow, capacity, layers seen.
        "moe": {
            "n_events": 2,
            "dropped_tokens": 1.0,
            "padded_slots": 0.0,
            "capacity": 4.0,
            "expert_load": [10.0, 6.0],
            "load_fractions": [0.625, 0.375],
            "layers": [0, 1],
        },
    }, summary
    # chrome export emitted alongside
    chrome = _json.loads(chrome_file.read_text())
    assert len(chrome["traceEvents"]) == 30  # meta excluded
    # and the human rendering mentions the essentials
    proc2 = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
         str(trace_file)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc2.returncode == 0
    for token in ("allreduce_grad", "STRAGGLER", "allreduce_wire=bf16",
                  "comm/compute overlap", "50.0% hidden",
                  "flat: 1 bucket(s), 1000 B wire, 1 overlapped",
                  "two_level: 1 bucket(s), 4.5 KiB wire, 0 overlapped",
                  "serving (continuous batching)", "tokens/s: 227.27",
                  "p50 4.000 ms, p99 6.000 ms", "33.3% mean",
                  "TTFT: p50 12.000 ms, p99 12.000 ms",
                  "TPOT: p50 6.000 ms, p99 8.000 ms per request",
                  "SLO attainment: 0.0% of 1 target-bearing request(s)",
                  "preemptions: 1",
                  "chunked prefill: 12 prompt token(s) over 2 "
                  "mixed-step chunk(s)",
                  "speculation: 8 drafted, 2 accepted (25.0% acceptance)",
                  "accept-length histogram: 0:2 2:1",
                  "prefix cache: 1/2 admissions hit (50.0%), "
                  "6/21 prompt tokens prefilled (16 served from cache), "
                  "1 COW block copy",
                  "tenants: 2 (Jain fairness 0.9878)",
                  "acme: 1 req, 5 tok, TPOT p50/p99 8.000/8.000 ms, "
                  "SLO 0.0% of 1",
                  "default: 1 req, 4 tok, TTFT p50/p99 12.000/12.000 "
                  "ms, TPOT p50/p99 6.000/6.000 ms",
                  # ISSUE 20: the MoE rollup rendering
                  "moe dispatch: 2 events, capacity 4, dropped 1 "
                  "tokens, padded 0 slots",
                  "layers: [0, 1]",
                  "expert load: e0=62.5% e1=37.5%"):
        assert token in proc2.stdout, (token, proc2.stdout)


def test_overlap_summary_has_schedules_and_no_compositions():
    """The overlap rollup groups layout events by schedule name and by
    nothing else, a bucket counted once however many stages it has; a
    trace of an older build, whose events also carry ``composition`` and
    ``slice``, is grouped the same way."""
    import json as _json

    from chainermn_tpu.observability.trace import summarize_overlap

    events = []
    for line in _golden_trace_lines():
        try:
            events.append(_json.loads(line))
        except ValueError:
            pass  # the fixture's torn tail
    ov = summarize_overlap(events)
    assert set(ov) == {"config", "schedules", "measured"}
    assert set(ov["schedules"]) == {"flat", "two_level"}
    older = [dict(e, composition="rs(a1)[s0..1]>ar(a0)>ag(a1)", slice=0,
                  n_slices=2) if e.get("kind") == "wire" else e
             for e in events]
    assert summarize_overlap(older) == ov


def _golden_journey_lines():
    """A two-rank disaggregated journey as two per-rank JSONL files:
    rank 0 routes (hop 0), rank 1 syncs its clock, adopts the KV
    payload and decodes (hops 1-4). Durations are exact binary
    fractions so the pinned decomposition has ZERO float drift."""
    import json as _json

    jid = "r0@b.0"
    rank0 = [
        {"schema": 1, "kind": "meta", "t": 1.0, "pid": 11, "rank": 0,
         "started_at": "2026-08-07T00:00:00Z", "sync": False,
         "source": "cluster"},
        {"schema": 1, "kind": "route", "t": 10.0, "t_mono": 100.0,
         "pid": 11, "rank": 0, "request": "r0", "replica": 1,
         "journey": jid, "span": f"{jid}/0"},
    ]
    rank1 = [
        {"schema": 1, "kind": "clock_sync", "t": 9.5, "t_mono": 200.0,
         "pid": 22, "rank": 1, "peer": 0, "offset_s": -0.5,
         "uncertainty_s": 0.001, "min_rtt_s": 0.002, "n": 8},
        {"schema": 1, "kind": "kv_transfer", "t": 10.5, "t_mono": 200.5,
         "pid": 22, "rank": 1, "request": "r0", "dur_s": 0.25,
         "journey": jid, "span": f"{jid}/1", "parent": f"{jid}/0"},
        {"schema": 1, "kind": "serving", "phase": "queue_wait",
         "t": 10.75, "t_mono": 200.75, "pid": 22, "rank": 1,
         "request": "r0", "dur_s": 0.25, "journey": jid,
         "span": f"{jid}/2", "parent": f"{jid}/1"},
        {"schema": 1, "kind": "serving", "phase": "prefill", "t": 11.0,
         "t_mono": 201.0, "pid": 22, "rank": 1, "request": "r0",
         "slot": 0, "bucket": None, "prompt_len": 4, "dur_s": 0.5,
         "ttft_s": 0.75, "journey": jid, "span": f"{jid}/3",
         "parent": f"{jid}/2"},
        {"schema": 1, "kind": "serving", "phase": "finish", "t": 11.25,
         "t_mono": 201.25, "pid": 22, "rank": 1, "request": "r0",
         "generated": 3, "dur_s": 1.0, "journey": jid,
         "span": f"{jid}/4", "parent": f"{jid}/3"},
    ]
    return ([_json.dumps(e) for e in rank0],
            [_json.dumps(e) for e in rank1])


def test_journey_report_contract(tmp_path):
    """ISSUE 17 golden: multi-file JSONL in -> stable ``--journeys``
    section out (full-dict equality — the causal-merge contract), flow
    events in the Chrome export for the cross-rank hop, and the human
    rendering's essentials."""
    import json as _json
    import sys

    jid = "r0@b.0"
    lines0, lines1 = _golden_journey_lines()
    f0, f1 = tmp_path / "rank0.jsonl", tmp_path / "rank1.jsonl"
    f0.write_text("\n".join(lines0) + "\n")
    f1.write_text("\n".join(lines1) + "\n")
    chrome_file = tmp_path / "chrome.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
         str(f0), str(f1), "--json", "--journeys",
         "--chrome", str(chrome_file)],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    summary = _json.loads(proc.stdout)
    assert summary["n_events"] == 7  # both files concatenated
    assert summary["journeys"] == {
        "n_journeys": 1,
        "n_complete": 1,
        "n_orphan_spans": 0,
        # rank 1 is 500 ms BEHIND rank 0's epoch, known to ±1 ms
        "clock": {
            "offsets": {"1": {"offset_s": -0.5, "uncertainty_s": 0.001,
                              "peer": 0}},
            "max_uncertainty_s": 0.001,
        },
        "slowest": [{
            "journey": jid,
            "request": "r0",
            "n_spans": 5,
            "ranks": [0, 1],
            "pids": [11, 22],
            "complete": True,
            "contiguous": True,
            "orphan_spans": [],
            # 0.25 queue + 0.25 net prefill (0.5 raw minus the 0.25
            # handoff it contains) + 0.25 handoff = the 0.75 TTFT
            "decomposition": {
                "ttft_s": 0.75,
                "queue_wait_s": 0.25,
                "prefill_s": 0.25,
                "handoff_s": 0.25,
                "preempt_gap_s": 0.0,
                "residual_s": 0.0,
                "preempts_before_first_token": 0,
                "total_s": 1.0,
                "decode_s": 0.25,
            },
            # hop order (clock-free); t_adj = t + the traced offset
            "spans": [
                {"hop": 0, "span": f"{jid}/0", "parent": None,
                 "kind": "route", "phase": None, "rank": 0, "pid": 11,
                 "t": 10.0, "t_adj": 10.0, "t_mono": 100.0,
                 "dur_s": None},
                {"hop": 1, "span": f"{jid}/1", "parent": f"{jid}/0",
                 "kind": "kv_transfer", "phase": None, "rank": 1,
                 "pid": 22, "t": 10.5, "t_adj": 10.0, "t_mono": 200.5,
                 "dur_s": 0.25},
                {"hop": 2, "span": f"{jid}/2", "parent": f"{jid}/1",
                 "kind": "serving", "phase": "queue_wait", "rank": 1,
                 "pid": 22, "t": 10.75, "t_adj": 10.25,
                 "t_mono": 200.75, "dur_s": 0.25},
                {"hop": 3, "span": f"{jid}/3", "parent": f"{jid}/2",
                 "kind": "serving", "phase": "prefill", "rank": 1,
                 "pid": 22, "t": 11.0, "t_adj": 10.5, "t_mono": 201.0,
                 "dur_s": 0.5},
                {"hop": 4, "span": f"{jid}/4", "parent": f"{jid}/3",
                 "kind": "serving", "phase": "finish", "rank": 1,
                 "pid": 22, "t": 11.25, "t_adj": 10.75,
                 "t_mono": 201.25, "dur_s": 1.0},
            ],
        }],
    }, summary["journeys"]
    # Chrome export: 6 non-meta base events + ONE s/f flow pair for the
    # single cross-rank hop (route on rank 0 -> kv_transfer on rank 1);
    # the rank-1-internal hops draw no arrows.
    chrome = _json.loads(chrome_file.read_text())
    flows = [e for e in chrome["traceEvents"] if e["ph"] in ("s", "f")]
    assert len(chrome["traceEvents"]) == 8
    assert [e["ph"] for e in flows] == ["s", "f"]
    assert flows[0]["id"] == flows[1]["id"]
    assert flows[0]["pid"] == 0 and flows[1]["pid"] == 1
    assert flows[1]["bp"] == "e"
    assert flows[0]["name"] == jid and flows[0]["cat"] == "journey"
    # t_mono stays a clock, not an arg, on every slice
    assert all("t_mono" not in e.get("args", {})
               for e in chrome["traceEvents"])
    # human rendering: the decomposition line and the clock error bar
    proc2 = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
         str(f0), str(f1), "--journeys"],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc2.returncode == 0
    for token in ("journeys: 1 merged, 1 complete, 0 orphan span(s)",
                  "clock: rank 1 offset -500.000 ms to rank 0 "
                  "(± 1.000 ms)",
                  "TTFT 750.000 ms = queue 250.000 + prefill 250.000 "
                  "+ handoff 250.000  (residual +0.0000 ms)",
                  "total 1000.000 ms (decode 250.000 ms)",
                  "hop 1  rank 1 kv_transfer    t_adj 10.0  "
                  "dur 250.000 ms"):
        assert token in proc2.stdout, (token, proc2.stdout)


def test_trace_report_roofline_scoped_to_device_plane(tmp_path):
    """Roofline floors apply only to device-plane ops, against the
    device kinds they actually ran on — a host-plane pickle transfer
    has no HBM roofline, and a mixed cpu+TPU trace (a chip run and its
    CPU children in one file) must not cross-product."""
    import json as _json
    import sys

    evs = [
        {"schema": 1, "kind": "collective", "t": 1.0, "pid": 1,
         "rank": 0, "op": "allreduce", "plane": "device",
         "nbytes": 1 << 30, "dur_s": 0.01, "size": 8,
         "device": "TPU v5 lite"},
        {"schema": 1, "kind": "collective", "t": 1.1, "pid": 1,
         "rank": 0, "op": "bcast", "plane": "device",
         "nbytes": 1 << 20, "dur_s": 0.001, "size": 8, "device": "cpu"},
        {"schema": 1, "kind": "collective", "t": 1.2, "pid": 1,
         "rank": 0, "op": "bcast_obj", "plane": "host", "nbytes": 4096,
         "dur_s": 0.001, "size": 2},
    ]
    trace_file = tmp_path / "trace.jsonl"
    trace_file.write_text("\n".join(_json.dumps(e) for e in evs) + "\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
         str(trace_file), "--json"],
        capture_output=True, text=True, cwd=_REPO,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    summary = _json.loads(proc.stdout)
    floors = summary.get("roofline", [])
    # only the TPU-device op gets a floor, only under ITS device kind
    assert [(f["op"], f["device"]) for f in floors] == [
        ("allreduce", "TPU v5 lite")
    ], floors
    assert floors[0]["hbm_peak_gbps"] == 819.0  # benchmark/peaks.py
    # no internal bookkeeping leaks into the contract
    assert all("_devices" not in c for c in summary["collectives"])


def test_trace_report_reads_its_peaks_from_the_benchmarks_table():
    """The report's HBM floor is ``benchmark/peaks.py``'s row for the
    device kind, and a kind with no published row there has no floor."""
    import importlib.util

    def load(name, *parts):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(_REPO, *parts))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    report = load("_tr_peaks", "tools", "trace_report.py")
    peaks = load("_bench_peaks_t", "benchmark", "peaks.py")
    for kind in ("TPU v5 lite", "TPU v5e"):
        assert report._hbm_peak(kind) == \
            peaks.lookup(kind)["hbm_bytes_per_s"]
    assert report._hbm_peak("cpu") is None
    assert report._hbm_peak("TPU v99") is None


def _metrics_dump_mod():
    import importlib.util

    path = os.path.join(_REPO, "tools", "metrics_dump.py")
    spec = importlib.util.spec_from_file_location("_md_capture", path)
    md = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(md)
    return md


_TENANT_PROM = """\
# HELP serving_tenant_tokens_total generated tokens per tenant
# TYPE serving_tenant_tokens_total counter
serving_tenant_tokens_total{tenant="acme"} 5
serving_tenant_tokens_total{tenant="globex"} 3
# HELP serving_queue_depth requests waiting
# TYPE serving_queue_depth gauge
serving_queue_depth 2
"""


def test_metrics_dump_label_filters_offline_table(tmp_path, capsys):
    """ISSUE 14 satellite: ``--label tenant=<id>`` narrows the parsed
    table to one tenant's series — offline (saved scrape) path."""
    prom = tmp_path / "t.prom"
    prom.write_text(_TENANT_PROM)
    md = _metrics_dump_mod()
    assert md.main([str(prom), "--label", "tenant=acme"]) == 0
    out = capsys.readouterr().out
    assert "tenant=acme" in out and "5" in out
    assert "globex" not in out
    assert "serving_queue_depth" not in out  # unlabeled series dropped


def test_metrics_dump_label_no_match_is_loud(tmp_path, capsys):
    """A typoed tenant id must exit 1 with a stderr note, never an
    empty table that reads as 'tenant idle'."""
    prom = tmp_path / "t.prom"
    prom.write_text(_TENANT_PROM)
    md = _metrics_dump_mod()
    assert md.main([str(prom), "--label", "tenant=nope"]) == 1
    err = capsys.readouterr().err
    assert "no series carry" in err and "nope" in err


def test_metrics_dump_label_validation_and_down_endpoint(capsys):
    """Bad --label syntax and --raw/--health combinations are refused;
    a down endpoint under --label keeps the fetch path's exit-1
    contract (the label filter never masks unreachability)."""
    md = _metrics_dump_mod()
    assert md.main(["--label", "tenant", "--port", "1"]) == 1
    assert "key=value" in capsys.readouterr().err
    assert md.main(["--label", "tenant=a", "--raw", "--port", "1"]) == 1
    assert "--raw" in capsys.readouterr().err
    # unreachable endpoint (port 1 is never listening): exit 1 with the
    # unreachable note, not the no-match note
    assert md.main(["--label", "tenant=a", "--port", "1",
                    "--timeout", "0.2"]) == 1
    err = capsys.readouterr().err
    assert "unreachable" in err
