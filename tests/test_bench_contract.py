"""Driver-contract pins for bench.py: the FINAL stdout line must stay a
single compact JSON object that fits (with margin) inside the driver's
2000-char tail-capture window, whatever rows and notes the run
accumulated (a fat line truncated mid-JSON is unparseable)."""

import contextlib
import io
import json

import bench


def test_compact_line_fits_tail_window(tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "_DETAILS_PATH",
                        str(tmp_path / "details.json"))
    # Worst-case: every compact key present, fat note/error strings, a
    # long failed-phase list.
    result = {k: 123456.789 for k in bench._COMPACT_KEYS}
    result.update(
        metric="resnet50_images_per_sec",
        unit="images/sec",
        device_kind="TPU v5 lite",
        bench_note="x" * 500,
        error="y" * 500,
        failed_phases=[f"serving_phase_{i}_error" for i in range(30)],
        # Fat non-compact rows must NOT leak into the line at all.
        allreduce_curve=[{"mib": 512, "busbw_gbps": 1.0}] * 8,
        kernel_sweep=[{"kernel": "causal_fwd", "ok": True}] * 8,
    )
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench._emit_final(result)
    line = buf.getvalue().strip().splitlines()[-1]
    assert len(line) < 1900, len(line)
    parsed = json.loads(line)  # a single well-formed object
    assert parsed["metric"] == "resnet50_images_per_sec"
    assert "allreduce_curve" not in parsed
    assert "kernel_sweep" not in parsed
    assert parsed["details"] == "BENCH_DETAILS.json"
    # the full details file holds everything
    full = json.load(open(tmp_path / "details.json"))
    assert "allreduce_curve" in full and "kernel_sweep" in full


def test_failed_phase_makes_exit_code_nonzero(tmp_path, monkeypatch):
    """``python bench.py`` runs in-process and its exit code says whether
    every phase held: any ``*_error`` row is a failure (the XLA
    comparator's expected OOM at T=32768 is recorded as ``xla_32k_oom``,
    a result)."""
    monkeypatch.setattr(bench, "_DETAILS_PATH",
                        str(tmp_path / "details.json"))
    monkeypatch.setattr(bench, "_TRACE_PATH", str(tmp_path / "trace.jsonl"))
    rows = {"metric": "m", "value": 1.0,
            "xla_32k_oom": "OOM (34.4 gb): expected"}

    def run(extra):
        monkeypatch.setattr(bench, "_run_bench",
                            lambda mode: dict(rows, **extra))
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = bench.main()
        return rc, json.loads(buf.getvalue().strip().splitlines()[-1])

    rc, line = run({})
    assert rc == 0 and "failed_phases" not in line
    rc, line = run({"attn_error": "MosaicError: boom"})
    assert rc == 1 and line["failed_phases"] == ["attn_error"]
    rc, line = run({"xla_32k_error": "ValueError: not an OOM"})
    assert rc == 1 and line["failed_phases"] == ["xla_32k_error"]


def test_kernel_sweep_crashed_checker_counts_as_numeric_error():
    """ADVICE r5: a row whose numerics checker RAISED must not read as
    0 numeric failures."""
    rows = [
        {"kernel": "a", "ok": True, "numerics_ok": True},
        {"kernel": "b", "ok": True, "numerics_ok": False},
        {"kernel": "c", "ok": True,
         "numerics_error": "ValueError: boom"},
        {"kernel": "d", "ok": False, "error": "Mosaic"},
    ]
    counts = bench._kernel_sweep_counts(rows)
    assert counts["kernel_sweep_failures"] == 1
    assert counts["kernel_sweep_numeric_failures"] == 1
    assert counts["kernel_sweep_numeric_errors"] == 1
    assert "kernel_sweep_numeric_errors" in bench._COMPACT_KEYS


def test_serving_rows_contract_and_seeding(tmp_path):
    """ISSUE 4 satellite: the ``serving`` phase's headline rows ride the
    compact line (tokens/s + spread gate), and ``tuning seed`` learns
    ``decode_impl``/``kv_block_size`` from the detail rows — spread-gated
    exactly like the in-run adoption, so a noise-band "winner" is never
    resurrected offline."""
    assert "serving_tokens_per_sec" in bench._COMPACT_KEYS
    assert "serving_spread_pct" in bench._COMPACT_KEYS

    from chainermn_tpu.tuning.cache import seed_from_bench_details

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-03T00:00:00Z",
        "serving_model_shape": "D512xH8xL512",
        "serving_decode_impl_ms": {"dense": 4.0, "paged": 2.0},
        "serving_decode_spread_pct": 5.0,
        # 2.9 vs 2.95 inside an 8% spread: indistinguishable from noise
        "serving_kv_block_ms": {"16": 3.0, "32": 2.9, "64": 2.95},
        "serving_kv_block_spread_pct": 8.0,
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    # the engine's own key material (serving_decision_key) reproduced
    assert "decode_impl|TPU v5 lite|512x8x512|decode -> paged" in seeded
    assert "kv_block_size" not in seeded  # spread-dominated: refused

    # a decisive sweep seeds the block size too
    doc["serving_kv_block_ms"] = {"16": 4.0, "64": 2.0}
    doc["serving_kv_block_spread_pct"] = 5.0
    details.write_text(json.dumps(doc))
    seeded2 = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert "kv_block_size|TPU v5 lite|512x8x512|decode -> 64" in seeded2

    # ABSENT spread key = on-accel single-sample row: the 10% noise
    # floor applies (the live adoption's spreads=None convention) — a
    # 5% margin is refused, a decisive one still seeds.
    doc.pop("serving_decode_spread_pct")
    doc["serving_decode_impl_ms"] = {"dense": 4.0, "paged": 3.9}
    details.write_text(json.dumps(doc))
    assert "decode_impl" not in "\n".join(
        seed_from_bench_details(str(details), str(cache)))
    # ...while a PRESENT 0.0 spread is a real three-tied-medians
    # estimate and adopts verbatim, matching the in-run path.
    doc["serving_decode_spread_pct"] = 0.0
    details.write_text(json.dumps(doc))
    assert "decode_impl|TPU v5 lite|512x8x512|decode -> paged" in "\n".join(
        seed_from_bench_details(str(details), str(cache)))


def test_spec_tokens_rows_contract_and_seeding(tmp_path):
    """ISSUE 5 satellite: the speculative rows ride the compact line
    (selected K, spec-vs-plain speedup, acceptance rate) and ``tuning
    seed`` learns ``spec_tokens`` from ``serving_spec_ms`` (ms per
    GENERATED token: acceptance is priced in) under the same spread
    gate and key material as the other serving decisions — with the
    per-K acceptance rates carried as auditable evidence."""
    for k in ("serving_spec_selected", "serving_spec_speedup",
              "serving_spec_accept_rate"):
        assert k in bench._COMPACT_KEYS, k

    from chainermn_tpu.tuning.cache import (
        load_cache,
        seed_from_bench_details,
    )

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-03T00:00:00Z",
        "serving_model_shape": "D512xH8xL512",
        "serving_spec_ms": {"0": 2.0, "2": 1.4, "4": 1.0, "8": 1.1},
        "serving_spec_spread_pct": 6.0,
        "serving_spec_accept_rates": {"2": 0.8, "4": 0.7, "8": 0.4},
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert "spec_tokens|TPU v5 lite|512x8x512|decode -> 4" in seeded
    entry = load_cache(str(cache))["decisions"][
        "spec_tokens|TPU v5 lite|512x8x512|decode"]
    assert entry["accept_rates"] == {"2": 0.8, "4": 0.7, "8": 0.4}
    assert entry["candidates_ms"]["4"] == 1.0

    # spread-dominated spec rows are refused (noise-band "winner")
    doc["serving_spec_ms"] = {"0": 1.0, "2": 0.98, "4": 0.99, "8": 1.01}
    doc["serving_spec_spread_pct"] = 12.0
    details.write_text(json.dumps(doc))
    cache2 = tmp_path / "cache2.json"
    assert "spec_tokens" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))

    # ABSENT spread = on-accel single sample: the 10% floor applies
    doc.pop("serving_spec_spread_pct")
    doc["serving_spec_ms"] = {"0": 1.0, "4": 0.95}
    details.write_text(json.dumps(doc))
    assert "spec_tokens" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))
    doc["serving_spec_ms"] = {"0": 2.0, "4": 0.9}
    details.write_text(json.dumps(doc))
    assert "spec_tokens|TPU v5 lite|512x8x512|decode -> 4" in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))


def test_serving_prefix_rows_contract_and_seeding(tmp_path):
    """ISSUE 7 satellite: the ``serving_prefix`` phase's headline rows
    ride the compact line (TTFT speedup + hit rate + spread gate), and
    ``tuning seed`` learns ``prefix_cache``/``min_shared_blocks`` from
    the TTFT rows under the same spread gate and key material as the
    other serving decisions — with the measured hit rate carried as
    auditable evidence for WHY 'on' won."""
    for k in ("serving_prefix_ttft_speedup", "serving_prefix_hit_rate",
              "serving_prefix_spread_pct"):
        assert k in bench._COMPACT_KEYS, k

    from chainermn_tpu.tuning.cache import (
        load_cache,
        seed_from_bench_details,
    )

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-03T00:00:00Z",
        "serving_model_shape": "D512xH8xL512",
        "serving_prefix_ttft_ms": {"off": 20.0, "on": 6.0},
        "serving_prefix_spread_pct": 8.0,
        "serving_prefix_hit_rate": 0.89,
        "serving_prefix_msb_ttft_ms": {"1": 6.0, "2": 6.8, "4": 9.0},
        "serving_prefix_msb_spread_pct": 7.0,
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert "prefix_cache|TPU v5 lite|512x8x512|decode -> on" in seeded
    assert "min_shared_blocks|TPU v5 lite|512x8x512|decode -> 1" in seeded
    entry = load_cache(str(cache))["decisions"][
        "prefix_cache|TPU v5 lite|512x8x512|decode"]
    assert entry["hit_rate"] == 0.89
    assert entry["candidates_ms"]["on"] == 6.0

    # spread-dominated rows are refused (noise-band "winner")
    doc["serving_prefix_ttft_ms"] = {"off": 6.1, "on": 6.0}
    doc["serving_prefix_spread_pct"] = 12.0
    details.write_text(json.dumps(doc))
    cache2 = tmp_path / "cache2.json"
    assert "prefix_cache" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))

    # ABSENT spread = on-accel single sample: the 10% floor applies
    doc.pop("serving_prefix_spread_pct")
    details.write_text(json.dumps(doc))
    assert "prefix_cache" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))
    doc["serving_prefix_ttft_ms"] = {"off": 20.0, "on": 6.0}
    details.write_text(json.dumps(doc))
    assert "prefix_cache|TPU v5 lite|512x8x512|decode -> on" in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))


def test_serving_burst_rows_contract_and_seeding(tmp_path):
    """ISSUE 11 satellite: the ``serving_burst`` phase's headline rows
    ride the compact line (per-arm goodput-under-SLO + p99 TTFT +
    spread gate + the adopted decision), and ``tuning seed`` learns
    ``prefill_chunk`` from the ms-per-SLO-good-token rows — spread-
    gated under the phase's OWN shape key, with the measured goodput
    and p99 TTFT carried as evidence."""
    for k in ("serving_burst_goodput", "serving_burst_ttft_p99_ms",
              "serving_burst_spread_pct", "serving_burst_selected"):
        assert k in bench._COMPACT_KEYS, k

    from chainermn_tpu.tuning.cache import (
        load_cache,
        seed_from_bench_details,
    )

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-03T00:00:00Z",
        "serving_burst_model_shape": "D512xH8xL512",
        "serving_burst_chunk_ms": {"0": 2.4, "64": 1.2},
        "serving_burst_spread_pct": 6.0,
        "serving_burst_goodput": {"monolithic": 410.0, "chunked": 830.0,
                                  "chunked_slo": 870.0},
        "serving_burst_ttft_p99_ms": {"monolithic": 90.0,
                                      "chunked": 22.0,
                                      "chunked_slo": 18.0},
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert "prefill_chunk|TPU v5 lite|512x8x512|decode -> 64" in seeded
    entry = load_cache(str(cache))["decisions"][
        "prefill_chunk|TPU v5 lite|512x8x512|decode"]
    assert entry["candidates_ms"]["64"] == 1.2
    assert entry["goodput"]["chunked"] == 830.0
    assert entry["ttft_p99_ms"]["monolithic"] == 90.0

    # spread-dominated rows are refused (noise-band "winner") — the
    # table default 0 stands, the honest-refusal precedent
    doc["serving_burst_chunk_ms"] = {"0": 1.25, "64": 1.2}
    doc["serving_burst_spread_pct"] = 15.0
    details.write_text(json.dumps(doc))
    cache2 = tmp_path / "cache2.json"
    assert "prefill_chunk" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))

    # ABSENT spread = on-accel single sample: the 10% floor applies
    doc.pop("serving_burst_spread_pct")
    details.write_text(json.dumps(doc))
    assert "prefill_chunk" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))


def test_seq_parallel_rows_contract_and_seeding(tmp_path):
    """ISSUE 13 satellite: the ``seq_parallel`` phase's headline rows
    ride the compact line (selected prefill mode + off/on TTFT + spread
    gate), the phase is wired into the supplementary chain, and
    ``tuning seed`` learns BOTH new decisions — ``seq_attn_impl`` from
    the ring-vs-ulysses step medians (keyed shards x heads x local-T,
    the plan resolver's own key) and ``prefill_seq_parallel`` from the
    long-prompt TTFT rows (the serving decision key) — spread-gated
    exactly like the in-run adoption, with the per-shard TTFT curve
    carried as evidence."""
    for k in ("seq_parallel_selected", "seq_parallel_ttft_ms",
              "seq_parallel_spread_pct"):
        assert k in bench._COMPACT_KEYS, k
    assert callable(bench._bench_seq_parallel)
    import inspect

    src = inspect.getsource(bench._run_bench)
    assert 'supp("seq_parallel", "seq_parallel_error"' in src

    from chainermn_tpu.tuning.cache import (
        load_cache,
        seed_from_bench_details,
    )

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-04T00:00:00Z",
        "seq_parallel_attn_shape": "S4xH8xT512",
        "seq_parallel_attn_ms": {"ring": 2.0, "ulysses": 3.1},
        "seq_parallel_attn_spread_pct": 5.0,
        "seq_parallel_model_shape": "D512xH8xL2048",
        "seq_parallel_ttft_ms": {"off": 40.0, "on": 14.0},
        "seq_parallel_spread_pct": 6.0,
        "seq_parallel_ttft_shards_ms": {"1": 40.0, "2": 22.0, "4": 14.0},
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert "seq_attn_impl|TPU v5 lite|4x8x512|seqattn -> ring" in seeded
    assert ("prefill_seq_parallel|TPU v5 lite|512x8x2048|decode -> on"
            in seeded)
    entry = load_cache(str(cache))["decisions"][
        "prefill_seq_parallel|TPU v5 lite|512x8x2048|decode"]
    assert entry["ttft_shards_ms"] == {"1": 40.0, "2": 22.0, "4": 14.0}
    assert entry["candidates_ms"]["on"] == 14.0

    # spread-dominated rows are refused (noise-band "winner") — the
    # table defaults (ring / off) stand, the honest-refusal precedent
    doc["seq_parallel_ttft_ms"] = {"off": 14.2, "on": 14.0}
    doc["seq_parallel_spread_pct"] = 12.0
    doc["seq_parallel_attn_ms"] = {"ring": 2.0, "ulysses": 2.05}
    doc["seq_parallel_attn_spread_pct"] = 11.0
    details.write_text(json.dumps(doc))
    cache2 = tmp_path / "cache2.json"
    seeded2 = "\n".join(seed_from_bench_details(str(details),
                                                str(cache2)))
    assert "prefill_seq_parallel" not in seeded2
    assert "seq_attn_impl" not in seeded2

    # ABSENT spread = on-accel single sample: the 10% floor applies
    doc.pop("seq_parallel_spread_pct")
    doc.pop("seq_parallel_attn_spread_pct")
    doc["seq_parallel_ttft_ms"] = {"off": 15.0, "on": 14.0}
    details.write_text(json.dumps(doc))
    assert "prefill_seq_parallel" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))
    doc["seq_parallel_ttft_ms"] = {"off": 40.0, "on": 14.0}
    details.write_text(json.dumps(doc))
    assert ("prefill_seq_parallel|TPU v5 lite|512x8x2048|decode -> on"
            in "\n".join(seed_from_bench_details(str(details),
                                                 str(cache2))))


def test_serving_tenants_rows_contract_and_seeding(tmp_path):
    """ISSUE 14 satellite: the ``serving_tenants`` phase's headline
    rows ride the compact line (goodput + Jain fairness + spread gate
    + the adopted ``adapter_impl``), the phase is wired into the
    supplementary chain, and ``tuning seed`` learns ``adapter_impl``
    from the gather/merged ms-per-token rows — spread-gated under the
    phase's OWN shape key, with the measured goodput and fairness
    carried as evidence."""
    for k in ("serving_tenants_goodput", "serving_tenants_fairness",
              "serving_tenants_spread_pct", "serving_tenants_selected"):
        assert k in bench._COMPACT_KEYS, k
    assert callable(bench._bench_serving_tenants)
    import inspect

    src = inspect.getsource(bench._run_bench)
    assert 'supp("serving_tenants", "serving_tenants_error"' in src

    from chainermn_tpu.tuning.cache import (
        load_cache,
        seed_from_bench_details,
    )

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-04T00:00:00Z",
        "serving_tenants_model_shape": "D512xH8xL512",
        "serving_tenants_adapter_ms": {"gather": 0.9, "merged": 0.5},
        "serving_tenants_adapter_spread_pct": 5.0,
        "serving_tenants_spread_pct": 40.0,
        "serving_tenants_goodput": 4100.0,
        "serving_tenants_fairness": 0.98,
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert "adapter_impl|TPU v5 lite|512x8x512|decode -> merged" in seeded
    entry = load_cache(str(cache))["decisions"][
        "adapter_impl|TPU v5 lite|512x8x512|decode"]
    assert entry["candidates_ms"]["merged"] == 0.5
    assert entry["goodput"] == 4100.0
    assert entry["fairness"] == 0.98

    # spread-dominated rows are refused (noise-band "winner") — the
    # table default gather stands, the honest-refusal precedent
    doc["serving_tenants_adapter_ms"] = {"gather": 0.52, "merged": 0.5}
    doc["serving_tenants_adapter_spread_pct"] = 15.0
    details.write_text(json.dumps(doc))
    cache2 = tmp_path / "cache2.json"
    assert "adapter_impl" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))

    # ABSENT spread = on-accel single sample: the 10% floor applies
    doc.pop("serving_tenants_adapter_spread_pct")
    details.write_text(json.dumps(doc))
    assert "adapter_impl" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))
    doc["serving_tenants_adapter_ms"] = {"gather": 0.9, "merged": 0.5}
    details.write_text(json.dumps(doc))
    assert ("adapter_impl|TPU v5 lite|512x8x512|decode -> merged"
            in "\n".join(seed_from_bench_details(str(details),
                                                 str(cache2))))


def test_transformer_knob_env_validation(monkeypatch):
    """The accel transformer knobs reject malformed env values with a
    message naming the variable (a bare ZeroDivisionError from
    CHAINERMN_BENCH_TF_HEADS=0 once leaked through review)."""
    import pytest

    class _Comm:  # knob validation happens before any communicator use
        size = 1

    cases = {
        "CHAINERMN_BENCH_TF_HEADS": ["0", "-8", "7"],
        "CHAINERMN_BENCH_TF_DB": ["yes", "1"],
        "CHAINERMN_BENCH_TF_REMAT": ["conv", "all"],
    }
    for var, bads in cases.items():
        for bad in bads:
            monkeypatch.setenv(var, bad)
            with pytest.raises(ValueError, match=var.rsplit("_", 1)[-1]):
                bench._transformer_setup(_Comm(), on_accel=True)
            monkeypatch.delenv(var)


def test_serving_cluster_rows_contract_and_seeding(tmp_path):
    """ISSUE 8 satellite: the ``serving_cluster`` phase's headline rows
    ride the compact line (goodput at the top replica count, the
    replica-scaling ratio, the disagg-vs-colocated TTFT speedup,
    spread gate), and ``tuning seed`` learns ``cluster_disagg`` from
    the TTFT rows — spread-gated under the phase's OWN shape key, with
    the measured transfer accounting carried as evidence."""
    for k in ("serving_cluster_goodput_tokens_per_sec",
              "serving_cluster_scaling", "serving_cluster_disagg_speedup",
              "serving_cluster_spread_pct"):
        assert k in bench._COMPACT_KEYS, k

    from chainermn_tpu.tuning.cache import (
        load_cache,
        seed_from_bench_details,
    )

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-03T00:00:00Z",
        "serving_cluster_model_shape": "D512xH8xL512",
        "serving_cluster_disagg_ttft_ms": {"colocated": 20.0,
                                           "disaggregated": 8.0},
        "serving_cluster_disagg_spread_pct": 6.0,
        "serving_cluster_transfers": 24,
        "serving_cluster_transfer_bytes": 98304,
        "serving_cluster_scaling": 3.1,
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert ("cluster_disagg|TPU v5 lite|512x8x512|decode -> "
            "disaggregated") in seeded
    entry = load_cache(str(cache))["decisions"][
        "cluster_disagg|TPU v5 lite|512x8x512|decode"]
    assert entry["transfer_bytes"] == 98304
    assert entry["scaling"] == 3.1
    assert entry["candidates_ms"]["disaggregated"] == 8.0

    # spread-dominated rows are refused (noise-band "winner")
    doc["serving_cluster_disagg_ttft_ms"] = {"colocated": 8.1,
                                             "disaggregated": 8.0}
    doc["serving_cluster_disagg_spread_pct"] = 12.0
    details.write_text(json.dumps(doc))
    cache2 = tmp_path / "cache2.json"
    assert "cluster_disagg" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))

    # ABSENT spread = on-accel single sample: the 10% floor applies
    doc.pop("serving_cluster_disagg_spread_pct")
    details.write_text(json.dumps(doc))
    assert "cluster_disagg" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))
    doc["serving_cluster_disagg_ttft_ms"] = {"colocated": 20.0,
                                             "disaggregated": 8.0}
    details.write_text(json.dumps(doc))
    assert ("cluster_disagg|TPU v5 lite|512x8x512|decode -> "
            "disaggregated") in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))


def test_compact_overflow_sheds_newest_keys_with_marker(tmp_path,
                                                        monkeypatch):
    """The tail-window guard: a saturated line sheds NEWEST-declared
    compact keys first, marks how many went, and never touches the
    identity/provenance core — the driver sees valid JSON, the details
    file keeps everything."""
    monkeypatch.setattr(bench, "_DETAILS_PATH",
                        str(tmp_path / "details.json"))
    result = {k: 123456.789 for k in bench._COMPACT_KEYS}
    result.update(metric="resnet50_images_per_sec", unit="images/sec",
                  device_kind="TPU v5 lite", bench_note="x" * 500,
                  error="y" * 500)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench._emit_final(result)
    parsed = json.loads(buf.getvalue().strip().splitlines()[-1])
    assert parsed.get("compact_keys_shed", 0) >= 1
    # newest-declared keys go first; the core survives
    assert "serving_cluster_spread_pct" not in parsed
    for k in ("metric", "value", "unit", "device_kind", "details"):
        assert k in parsed, k
    # an unsaturated line sheds nothing and carries no marker
    small = {"metric": "m", "value": 1.0,
             "serving_cluster_spread_pct": 2.0}
    buf2 = io.StringIO()
    with contextlib.redirect_stdout(buf2):
        bench._emit_final(small)
    parsed2 = json.loads(buf2.getvalue().strip().splitlines()[-1])
    assert "compact_keys_shed" not in parsed2
    assert parsed2["serving_cluster_spread_pct"] == 2.0


def test_composed_rows_contract_and_seeding(tmp_path, monkeypatch):
    """ISSUE 12 satellite: the ``composed`` phase's headline rows ride
    the compact line (best-vs-two_level ratio + spread gate + selected
    pipeline), the phase is wired into the supplementary chain, and
    ``tuning seed`` learns the 3-level ``reduction_schedule`` decision
    from the signature-keyed ``composed_schedule_ms`` rows — spread-
    gated exactly like the in-run adoption, under its own world-shape
    key so the flat-mesh ``overlap`` entry is untouched."""
    for k in ("composed_best_vs_two_level", "composed_spread_pct",
              "composed_selected"):
        assert k in bench._COMPACT_KEYS, k
    assert callable(bench._bench_composed)
    import inspect

    src = inspect.getsource(bench._run_bench)
    assert 'supp("composed", "composed_error"' in src

    from chainermn_tpu.tuning.cache import seed_from_bench_details

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    ladder = "rs(a2)>rs(a1)>ar(a0)>ag(a1)>ag(a2)"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-04T00:00:00Z",
        "composed_schedule_ms": {
            "ar(a0+a1+a2)": 4.0,
            "rs(a2)>ar(a0+a1)>ag(a2)": 3.5,
            ladder: 2.0,
        },
        "composed_spread_pct": 5.0,
        "composed_world_shape": [2, 2, 2],
        "composed_payload_mb": 3,
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    # keyed by the 3-level world shape + payload bucket, winner = the
    # ladder SIGNATURE (a pipeline the old menu could not express)
    assert (f"reduction_schedule|TPU v5 lite|2x2x2x4|sched -> {ladder}"
            in seeded)

    # ...and the seeded entry is exactly what resolve_schedule's
    # derived candidate set resolves for that world shape (conftest
    # pins the registry to 'off' for hermeticity — 'table' still
    # consults the cache, like every non-off mode).
    from chainermn_tpu.parallel.reduction_schedule import resolve_schedule

    monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE", "table")
    monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_CACHE", str(cache))
    winner, rec = resolve_schedule("TPU v5 lite", 3 << 20, (2, 2, 2))
    assert winner == ladder
    assert rec["source"].startswith("cache")
    assert rec["composition"] == ladder

    # a winner that IS a menu instance adopts by MENU NAME — stored
    # under its signature the candidate list would never match it and
    # choice() would silently fall back to the table default (review
    # finding, pinned here): two_level's derived signature wins ->
    # entry winner 'two_level', and resolve_schedule returns it.
    cache3 = tmp_path / "cache3.json"
    doc["composed_schedule_ms"] = {
        "ar(a0+a1+a2)": 4.0,
        "rs(a2)>ar(a0+a1)>ag(a2)": 2.0,
        ladder: 3.5,
    }
    doc["composed_spread_pct"] = 5.0
    details.write_text(json.dumps(doc))
    seeded3 = "\n".join(seed_from_bench_details(str(details), str(cache3)))
    assert ("reduction_schedule|TPU v5 lite|2x2x2x4|sched -> two_level"
            in seeded3)
    monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_CACHE", str(cache3))
    winner3, rec3 = resolve_schedule("TPU v5 lite", 3 << 20, (2, 2, 2))
    assert winner3 == "two_level"
    assert rec3["composition"] == "rs(a2)>ar(a0+a1)>ag(a2)"

    # a spread-dominated sweep refuses to pin a winner
    doc["composed_schedule_ms"] = {ladder: 2.0, "ar(a0+a1+a2)": 2.05}
    doc["composed_spread_pct"] = 10.0
    details.write_text(json.dumps(doc))
    assert "reduction_schedule" not in "\n".join(
        seed_from_bench_details(str(details), str(cache.with_suffix(".2")))
    )


def test_plan_rows_contract():
    """ISSUE 10 satellite: the ``plan`` bench phase's headline rows ride
    the compact line (hand-wired vs plan-compiled ratio + spread gate),
    and the phase is wired into the supplementary chain so a plan
    regression reaches the driver artifact."""
    for k in ("plan_vs_handwired", "plan_spread_pct"):
        assert k in bench._COMPACT_KEYS, k
    assert callable(bench._bench_plan)
    import inspect

    src = inspect.getsource(bench._run_bench)
    assert 'supp("plan", "plan_error"' in src


def test_composed_sliced_rows_contract_and_seeding(tmp_path, monkeypatch):
    """ISSUE 15 satellite: the ``composed`` phase's sliced-arm rows
    ride the compact line (per-S medians + spread gate + selected
    count), and ``tuning seed`` learns the ``comp_slices`` decision
    from the same rows — spread-gated exactly like the in-run
    ``record_measurement`` adoption, under the world-shape x
    payload-MB key ``resolve_comp_slices`` reads (offline seed and
    live adoption must agree on identical rows — the PR 14
    adapter_impl lesson)."""
    for k in ("composed_sliced_ms", "composed_slices_selected",
              "composed_sliced_spread_pct"):
        assert k in bench._COMPACT_KEYS, k

    from chainermn_tpu.tuning.cache import seed_from_bench_details

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-04T00:00:00Z",
        "composed_sliced_ms": {"1": 4.0, "2": 3.2, "4": 2.0, "8": 2.8},
        "composed_sliced_spread_pct": 5.0,
        "composed_world_shape": [2, 2, 2],
        "composed_payload_mb": 3,
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert "comp_slices|TPU v5 lite|2x2x2x4|slices -> 4" in seeded

    # the seeded entry is exactly what resolve_comp_slices resolves —
    # and what the 'auto' schedule resolution slices its winner by.
    from chainermn_tpu.parallel.reduction_schedule import (
        resolve_comp_slices,
        resolve_schedule,
    )

    monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE", "table")
    monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_CACHE", str(cache))
    assert resolve_comp_slices("TPU v5 lite", 3 << 20, (2, 2, 2)) == 4
    winner, rec = resolve_schedule("TPU v5 lite", 3 << 20, (2, 2, 2),
                                   slices="auto")
    assert winner == "ar(a0+a1+a2)[s0..3]"
    assert rec["comp_slices"] == 4

    # live adoption over the SAME rows agrees with the offline seed
    from chainermn_tpu import tuning

    live_cache = tmp_path / "live.json"
    key = tuning.decision_key(
        "TPU v5 lite", shape=(2, 2, 2, 3), dtype="slices")
    live = tuning.record_measurement(
        "comp_slices", key,
        {k: float(v) for k, v in doc["composed_sliced_ms"].items()},
        spreads={k: 5.0 for k in doc["composed_sliced_ms"]},
        cache_path=str(live_cache),
    )
    assert live == "4"

    # a spread-dominated sweep refuses to pin a winner (table default
    # 1 stands — the honest CPU-proxy outcome)
    doc["composed_sliced_ms"] = {"1": 2.0, "2": 1.98, "4": 2.02,
                                 "8": 2.05}
    doc["composed_sliced_spread_pct"] = 10.0
    details.write_text(json.dumps(doc))
    assert "comp_slices" not in "\n".join(
        seed_from_bench_details(str(details),
                                str(cache.with_suffix(".2")))
    )
    monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_CACHE",
                       str(cache.with_suffix(".2")))
    assert resolve_comp_slices("TPU v5 lite", 3 << 20, (2, 2, 2)) == 1

def test_sched_search_rows_contract_and_seeding(tmp_path):
    """ISSUE 16 satellite: the cost-model schedule search's headline
    rows ride the compact line (``sched_search_selected`` +
    ``cost_model_err_pct``), the composed phase really ranks with
    ``rank_compositions`` and logs the skipped arms with their
    predicted prices (no silent coverage loss), and ``tuning seed``
    learns the ``sched_search`` decision from the model audit —
    error inside the spread keeps top-k, disagreement past the gate
    seeds 'exhaustive' so the next run restores full coverage."""
    for k in ("sched_search_selected", "cost_model_err_pct"):
        assert k in bench._COMPACT_KEYS, k
    import inspect

    src = inspect.getsource(bench._bench_composed)
    # the search contract, pinned structurally: model loaded from the
    # PRIOR capture, ranked top-k measured (k default 3), skipped arms
    # + predicted costs logged, model error recorded as adoption
    # evidence, disagreement falls back to exhaustive loudly.
    for marker in ("load_from_bench_details", "rank_compositions",
                   "k=3", "sched_search_skipped",
                   "sched_search_predicted_ms", "extra_evidence",
                   "exhaustive:model_err"):
        assert marker in src, marker

    from chainermn_tpu.tuning.cache import seed_from_bench_details
    from chainermn_tpu.tuning.cache import lookup_entry

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-05T00:00:00Z",
        "composed_world_shape": [2, 2, 2],
        "composed_payload_mb": 3,
        "composed_spread_pct": 8.0,
        "sched_search_selected": "topk",
        "cost_model_err_pct": 4.5,
        "sched_search_predicted_ms": {"ar(a0+a1+a2)": 3.1},
        "sched_search_skipped": ["rs(a2)>ar(a0+a1)>ag(a2)"],
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert "sched_search|TPU v5 lite|2x2x2x4|search -> topk" in seeded
    entry = lookup_entry(
        "sched_search", "TPU v5 lite|2x2x2x4|search", path=str(cache))
    assert entry["cost_model_err_pct"] == 4.5
    assert entry["spread_pct"] == 8.0
    assert entry["skipped"] == ["rs(a2)>ar(a0+a1)>ag(a2)"]
    assert entry["predicted_ms"] == {"ar(a0+a1+a2)": 3.1}

    # model error past the spread gate seeds the exhaustive fallback
    doc["cost_model_err_pct"] = 40.0
    details.write_text(json.dumps(doc))
    seeded2 = "\n".join(seed_from_bench_details(
        str(details), str(cache.with_suffix(".2"))))
    assert ("sched_search|TPU v5 lite|2x2x2x4|search -> exhaustive"
            in seeded2)

    # no audit keys -> no sched_search entry (never seeded blind)
    doc.pop("cost_model_err_pct")
    details.write_text(json.dumps(doc))
    assert "sched_search" not in "\n".join(seed_from_bench_details(
        str(details), str(cache.with_suffix(".3"))))


def test_serving_sampled_rows_contract():
    """ISSUE 18 satellite: the ``serving_sampled`` phase's headline
    rows ride the compact line (per-arm tokens/s + spread + sampled
    spec speedup/acceptance + the spread-gated verdict), the phase is
    wired into the supplementary chain, and its verdict is recorded as
    cache evidence under the NON-decision ``sampled_serving`` name —
    never under spec_tokens/prefill_chunk: the greedy ``serving``/
    ``serving_burst`` phases own those adoption rows, and counter-
    based sampling makes one decision cover both modes
    (docs/serving.md "Sampling")."""
    for k in ("serving_sampled_tokens_per_sec",
              "serving_sampled_spread_pct",
              "serving_sampled_spec_speedup",
              "serving_sampled_spec_accept_rate",
              "serving_sampled_selected"):
        assert k in bench._COMPACT_KEYS, k
    assert callable(bench._bench_serving_sampled)
    import inspect

    src = inspect.getsource(bench._run_bench)
    assert 'supp("serving_sampled", "serving_sampled_error"' in src
    # evidence rides its own cache name; the phase never re-records
    # the greedy phases' knob decisions
    phase_src = inspect.getsource(bench._bench_serving_sampled)
    assert '"sampled_serving"' in phase_src
    for knob in ('"spec_tokens"', '"prefill_chunk"'):
        assert knob not in phase_src

    # the decide rule: decisive sampled win -> stored with evidence;
    # spread-dominated -> None and 'plain' stands (honest refusal)
    from chainermn_tpu import tuning

    winner = tuning.record_measurement(
        "sampled_serving", "unit-test|sampled",
        {"plain": 100.0, "spec": 150.0, "chunked": 90.0},
        spreads={"plain": 5.0, "spec": 5.0, "chunked": 5.0},
        higher_is_better=True,
        extra_evidence={"spec_accept_rate": 0.6},
    )
    assert winner == "spec"
    assert tuning.record_measurement(
        "sampled_serving", "unit-test|sampled",
        {"plain": 100.0, "spec": 104.0},
        spreads={"plain": 12.0, "spec": 12.0},
        higher_is_better=True,
    ) is None


def test_decode_kernel_rows_contract_and_seeding(tmp_path):
    """ISSUE 19 satellite: the fused-kernel adoption rows ride the
    compact line (per-impl ms, spread gate, fused speedup, selected)
    and ``tuning seed`` learns ``decode_attend_impl`` from
    ``serving_decode_kernel_ms`` under the same spread gate — keyed by
    the phase's OWN model shape, with the kernel-vs-gather speedup as
    auditable evidence. The table default is 'xla' (the kernel must
    EARN adoption on a live chip; the CPU proxy times interpret-mode
    emulation, so its honest verdict is refusal-or-xla)."""
    for k in ("serving_decode_kernel_ms",
              "serving_decode_kernel_spread_pct",
              "serving_decode_kernel_fused_speedup",
              "serving_decode_kernel_selected"):
        assert k in bench._COMPACT_KEYS, k
    assert callable(bench._bench_serving_decode_kernel)
    import inspect

    src = inspect.getsource(bench._run_bench)
    assert ('supp("serving_decode_kernel", '
            '"serving_decode_kernel_error"') in src

    # the registry's shipped default: the kernel has NOT been adopted
    from chainermn_tpu.tuning.registry import DEFAULT_TABLE

    assert DEFAULT_TABLE["decode_attend_impl"] == {"*": "xla"}

    from chainermn_tpu.tuning.cache import (
        load_cache,
        seed_from_bench_details,
    )

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-06T00:00:00Z",
        # the phase's own shape key, diverging from the main serving
        # shape on purpose: last-writer-wins on a merged key would
        # re-key the other phase's decisions
        "serving_model_shape": "D256xH4xL256",
        "serving_decode_kernel_model_shape": "D512xH8xL512",
        "serving_decode_kernel_ms": {"xla": 3.0, "fused": 1.2},
        "serving_decode_kernel_spread_pct": 6.0,
        "serving_decode_kernel_fused_speedup": 2.5,
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert ("decode_attend_impl|TPU v5 lite|512x8x512|decode -> fused"
            in seeded)
    entry = load_cache(str(cache))["decisions"][
        "decode_attend_impl|TPU v5 lite|512x8x512|decode"]
    assert entry["fused_speedup"] == 2.5
    assert entry["candidates_ms"]["fused"] == 1.2

    # spread-dominated rows are refused: the 'xla' default stands
    doc["serving_decode_kernel_ms"] = {"xla": 1.0, "fused": 0.97}
    doc["serving_decode_kernel_spread_pct"] = 9.0
    details.write_text(json.dumps(doc))
    cache2 = tmp_path / "cache2.json"
    assert "decode_attend_impl" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))

    # ABSENT spread = on-accel single sample: the 10% floor applies
    doc.pop("serving_decode_kernel_spread_pct")
    doc["serving_decode_kernel_ms"] = {"xla": 1.0, "fused": 0.95}
    details.write_text(json.dumps(doc))
    assert "decode_attend_impl" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))
    doc["serving_decode_kernel_ms"] = {"xla": 2.0, "fused": 0.9}
    details.write_text(json.dumps(doc))
    assert ("decode_attend_impl|TPU v5 lite|512x8x512|decode -> fused"
            in "\n".join(seed_from_bench_details(str(details),
                                                 str(cache2))))


def test_moe_rows_contract_and_seeding(tmp_path):
    """ISSUE 20 satellite: the ``moe`` phase's headline rows ride the
    compact line (expert-plan step median + selected ``expert_parallel``
    + spread gate + drop accounting), the phase is wired into the
    supplementary chain, and ``tuning seed`` learns ``expert_parallel``
    from the on/off step pair under the SAME key the live adoption uses
    (shape=(T, E, D), float32) — spread-gated exactly like the in-run
    ``record_measurement``."""
    for k in ("moe_step_ms", "moe_selected", "moe_spread_pct",
              "moe_drop_rate"):
        assert k in bench._COMPACT_KEYS, k
    assert callable(bench._bench_moe_plan)
    import inspect

    src = inspect.getsource(bench._run_bench)
    assert 'supp("moe", "moe_error"' in src

    from chainermn_tpu.tuning.cache import (
        load_cache,
        seed_from_bench_details,
    )

    details = tmp_path / "details.json"
    cache = tmp_path / "cache.json"
    doc = {
        "device_kind": "TPU v5 lite", "n_devices": 8,
        "measured_at": "2026-08-07T00:00:00Z",
        "moe_plan_shape": "T16384xE8xD512",
        "moe_step_ms": 3.1, "moe_off_step_ms": 6.0,
        "moe_spread_pct": 4.0, "moe_drop_rate": 0.13,
    }
    details.write_text(json.dumps(doc))
    seeded = "\n".join(seed_from_bench_details(str(details), str(cache)))
    assert "expert_parallel|TPU v5 lite|16384x8x512|float32 -> on" in \
        seeded
    entry = load_cache(str(cache))["decisions"][
        "expert_parallel|TPU v5 lite|16384x8x512|float32"]
    assert entry["candidates_ms"] == {"on": 3.1, "off": 6.0}

    # parity with the live adoption key: decision_key over the same
    # shape lands on the seeded entry
    from chainermn_tpu import tuning

    key = tuning.decision_key("TPU v5 lite", shape=(16384, 8, 512),
                              dtype="float32")
    assert key == "TPU v5 lite|16384x8x512|float32"

    # spread-dominated pair is refused — the table default (off) stands
    doc["moe_step_ms"] = 5.9
    doc["moe_spread_pct"] = 12.0
    details.write_text(json.dumps(doc))
    cache2 = tmp_path / "cache2.json"
    assert "expert_parallel" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))

    # ABSENT spread = on-accel single sample: the 10% floor applies
    doc.pop("moe_spread_pct")
    details.write_text(json.dumps(doc))
    assert "expert_parallel" not in "\n".join(
        seed_from_bench_details(str(details), str(cache2)))
    doc["moe_step_ms"] = 3.1
    details.write_text(json.dumps(doc))
    assert "expert_parallel|TPU v5 lite|16384x8x512|float32 -> on" in \
        "\n".join(seed_from_bench_details(str(details), str(cache2)))
