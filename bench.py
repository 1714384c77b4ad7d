"""Benchmark driver: ResNet-50 data-parallel training throughput.

``python bench.py`` runs in THIS process on the device JAX finds, and
refuses a CPU: a measurement path that finds no chip fails, it does not
fall back. It prints a cumulative JSON line after every phase, writes the
full result to ``BENCH_DETAILS.json`` (an ignored output) and ends with
ONE compact JSON line:
``{"metric": ..., "value": N, "unit": "images/sec", "vs_baseline": N, ...}``
with supplementary fields: ``mfu`` (model-FLOPs utilisation against the
chip's bf16 peak), ``allreduce_gbps`` (the reference's second tracked
metric, BASELINE.json / SURVEY.md section 6: achieved bytes/s of a jitted
gradient-buffer allreduce), ``device_kind``, ``n_devices``, and
``failed_phases`` when a phase recorded an error. The exit code is
non-zero when any phase failed.

``python bench.py --run cpu`` is the CPU proxy at toy shapes (counts and
correctness evidence, not speed); ``--run native-loop`` is the child mode
of the CPU input-pipeline row.

The primary benchmark is the reference's headline workload (ResNet-50
ImageNet, ``examples/imagenet`` (dagger), SURVEY.md section 6): one fully
jitted SPMD train step — forward, backward, bf16-compressed gradient
allreduce over the mesh, SGD update — on synthetic 224x224 data, i.e. the
same measurement the reference's images/sec numbers report (data pipeline
excluded).

Baseline: ``BASELINE.json`` has ``"published": {}`` (the reference repo's
own numbers were unreadable — empty mount), so ``vs_baseline`` compares
per-device throughput against the best documented ChainerMN-era
per-accelerator figure: the 15-minute ImageNet run (Akiba, Suzuki & Fukuda,
arXiv:1711.04325 — 90 epochs, 1024 P100s) ~= 125 images/sec/P100.
UNVERIFIED external figure and different hardware — ``mfu`` is the
hardware-honest number; see BASELINE.md.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))

BASELINE_IMG_PER_SEC_PER_DEVICE = 125.0

# Peak bf16 FLOPs/s per chip by device_kind substring (public figures).
_PEAK_BF16_FLOPS = {
    "v2": 46e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v5": 459e12,  # after the lite variants; substring order matters
    "v6 lite": 918e12,
    "v6e": 918e12,
}

# HBM bytes/s per chip, SAME keys and ordering rule as the flops table
# (public spec sheets). Kept adjacent so a new device kind is added to
# both in one place — tools/byte_audit.py derives its roofline floors
# from these via _peak_lookup.
_PEAK_HBM_BYTES = {
    "v2": 700e9,
    "v3": 900e9,
    "v4": 1228e9,
    "v5 lite": 819e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v5": 2765e9,
    "v6 lite": 1640e9,
    "v6e": 1640e9,
}


def _last_json_line(text) -> dict | None:
    """Parse the last JSON object line from child stdout (bytes or str)."""
    if isinstance(text, bytes):
        text = text.decode(errors="replace")
    for line in reversed((text or "").splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _run_child(mode: str, timeout: float, env=None):
    """Run ``bench.py --run <mode>``; return its parsed JSON line or an
    error string."""
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(_HERE, "bench.py"), "--run", mode],
            env=env, cwd=_HERE, capture_output=True, text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        return None, f"{mode} bench timed out after {timeout:.0f}s"
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout or "")[-800:]
        return None, f"{mode} bench rc={proc.returncode}: {tail}"
    result = _last_json_line(proc.stdout)
    if result is not None:
        return result, None
    return None, f"{mode} bench emitted no JSON line"


# Observability trace (ISSUE 2): the bench appends structured wire/phase
# events here; tools/trace_report.py summarizes it.
_TRACE_PATH = os.environ.get(
    "CHAINERMN_TPU_TRACE", os.path.join(_HERE, "BENCH_TRACE.jsonl")
)


def _truncate_trace() -> None:
    """Start each run with a fresh trace (the run's children append to
    the same file). Creates the directory like the Recorder does: a
    missing parent dir must not silently skip the truncation while the
    run goes on appending to a stale file."""
    try:
        parent = os.path.dirname(os.path.abspath(_TRACE_PATH))
        os.makedirs(parent, exist_ok=True)
        open(_TRACE_PATH, "w").close()
    except OSError:
        pass


_DETAILS_PATH = os.path.join(_HERE, "BENCH_DETAILS.json")

# The driver captures only a bounded tail of stdout and parses the last
# JSON line from it. Keys on this whitelist are the headline numbers;
# everything else goes to BENCH_DETAILS.json.
_COMPACT_KEYS = (
    "metric", "value", "unit", "vs_baseline", "source", "step_time_ms",
    "device_kind", "n_devices", "mfu", "transformer_tokens_per_sec",
    "transformer_mfu", "flash_fwdbwd_speedup", "allreduce_gbps",
    "resnet50_s2d_images_per_sec", "moe_dispatch_sort_speedup",
    "moe_step_ms", "moe_selected", "moe_spread_pct", "moe_drop_rate",
    "native_input_images_per_sec", "double_buffer_speedup",
    "flash_32k_fwd_ms", "flash_32k_window2k_fwd_ms",
    "kernel_sweep_failures", "kernel_sweep_numeric_failures",
    "kernel_sweep_numeric_errors", "proxy_spread_pct", "autotune",
    "hidden_comm_fraction", "reduction_schedule_selected",
    "overlap_spread_pct", "composed_best_vs_two_level",
    "composed_spread_pct", "composed_selected",
    "composed_sliced_ms", "composed_slices_selected",
    "composed_sliced_spread_pct",
    "sched_search_selected", "cost_model_err_pct",
    "serving_tokens_per_sec", "serving_spread_pct",
    "serving_spec_selected", "serving_spec_speedup",
    "serving_spec_accept_rate", "serving_prefix_ttft_speedup",
    "serving_prefix_hit_rate", "serving_prefix_spread_pct",
    "serving_cluster_goodput_tokens_per_sec", "serving_cluster_scaling",
    "serving_cluster_disagg_speedup", "serving_cluster_spread_pct",
    "plan_vs_handwired", "plan_spread_pct",
    "serving_burst_goodput", "serving_burst_ttft_p99_ms",
    "serving_burst_spread_pct", "serving_burst_selected",
    "serving_sampled_tokens_per_sec", "serving_sampled_spread_pct",
    "serving_sampled_spec_speedup", "serving_sampled_spec_accept_rate",
    "serving_sampled_selected",
    "serving_decode_kernel_ms", "serving_decode_kernel_spread_pct",
    "serving_decode_kernel_fused_speedup",
    "serving_decode_kernel_selected",
    "seq_parallel_selected", "seq_parallel_ttft_ms",
    "seq_parallel_spread_pct",
    "serving_tenants_goodput", "serving_tenants_fairness",
    "serving_tenants_spread_pct", "serving_tenants_selected",
)


def _emit_final(result: dict) -> None:
    """Write the full result to BENCH_DETAILS.json and print a COMPACT
    final JSON line guaranteed to fit (with margin) inside the driver's
    2000-char stdout tail window."""
    wrote_details = False
    try:
        full = dict(result)
        full["emitted_at"] = time.strftime(
            "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
        )
        with open(_DETAILS_PATH, "w") as f:
            json.dump(full, f, indent=1)
            f.write("\n")
        wrote_details = True
    except OSError:
        pass
    compact = {k: result[k] for k in _COMPACT_KEYS if k in result}
    if "bench_note" in result:
        compact["bench_note"] = str(result["bench_note"])[:160]
    if "error" in result:
        compact["error"] = str(result["error"])[:240]
    if result.get("failed_phases"):
        compact["failed_phases"] = result["failed_phases"][:12]
    if wrote_details:
        compact["details"] = "BENCH_DETAILS.json"
    else:
        compact["details_write_failed"] = True
    # Hard driver contract: the final line must parse inside the
    # 2000-char stdout tail window. The key list grows a few entries
    # per PR and a saturated run (every phase landed every row) can
    # overflow — shed the NEWEST keys first (reverse declaration
    # order; the details file always has everything) rather than let
    # the tail truncate mid-JSON, and say how many were shed. The
    # identity core is never shed.
    keep = ("metric", "value", "unit", "source", "device_kind",
            "n_devices", "error", "failed_phases", "details",
            "details_write_failed")
    line = json.dumps(compact)
    shed = 0
    for k in reversed(_COMPACT_KEYS):
        if len(line) < 1840:
            break
        if k in compact and k not in keep:
            del compact[k]
            shed += 1
            compact["compact_keys_shed"] = shed
            line = json.dumps(compact)
    print(line, flush=True)


def _failed_phases(out: dict) -> list:
    """The keys under which a phase recorded a failure."""
    return sorted(k for k in out if k == "error" or k.endswith("_error"))


def main() -> int:
    """``python bench.py``: measure in this process on the device JAX
    finds (``_run_bench`` refuses a CPU), emit the compact line, and
    return non-zero when any phase recorded an error."""
    _truncate_trace()
    out = _run_bench("accel")
    out["source"] = "live"
    out["failed_phases"] = _failed_phases(out)
    _emit_final(out)
    return 1 if out["failed_phases"] else 0


# ---------------------------------------------------------------------------
# The measurements (jax is imported only below this line).
# ---------------------------------------------------------------------------


def _repeat_median(sample, repeats: int):
    """Median-of-n measurement discipline: the single-sample CPU-proxy
    rows drifted run-to-run (flash interpret 0.75x->0.63x, s2d
    36.9->31.4) with no way to tell a real regression from noise. ``sample`` is a zero-arg measurement returning a float;
    returns ``(median, spread_pct)`` with spread = 100*(max-min)/median.
    ``repeats=1`` degenerates to the single sample (spread 0) — used on
    the chip, where the budget goes to more steps per sample instead."""
    vals = sorted(sample() for _ in range(max(1, repeats)))
    n = len(vals)
    med = (vals[n // 2] if n % 2
           else 0.5 * (vals[n // 2 - 1] + vals[n // 2]))
    spread = 100.0 * (vals[-1] - vals[0]) / med if med else 0.0
    return med, round(spread, 1)


def _peak_lookup(device_kind: str, table: dict):
    """Order-sensitive substring match over a per-kind peak table (the
    single matcher for _PEAK_BF16_FLOPS and _PEAK_HBM_BYTES). The CPU
    proxy has no peak (``None``: its rows carry no utilisation); an
    accelerator kind the table does not know is an error, not a silently
    missing MFU."""
    kind = device_kind.lower()
    for sub, peak in table.items():
        if sub in kind:
            return peak
    if kind == "cpu":
        return None
    raise KeyError(
        f"no peak for device kind {device_kind!r}: add it to "
        "_PEAK_BF16_FLOPS and _PEAK_HBM_BYTES in bench.py"
    )


def _peak_flops(device_kind: str):
    return _peak_lookup(device_kind, _PEAK_BF16_FLOPS)


def _fetch_scalar(x) -> float:
    """End a timed region by materialising a scalar on the host: the
    transfer cannot complete before the device work that produces it.
    On a directly attached chip ``jax.block_until_ready`` gives the same
    time (chip_smoke.py prints both; PERF.md records the comparison)."""
    import jax
    import numpy as np

    return float(np.asarray(jax.device_get(x)).ravel()[0])


def _bench_attention(on_accel: bool):
    """Flash-attention Pallas kernel vs XLA's fused attention on the same
    chip (VERDICT round-1 item 6: 'microbench kernel-vs-XLA attention on the
    real chip and record the win'). Iterations are dependency-chained
    through a scan so the device cannot overlap or elide them."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.attention import dot_product_attention
    from chainermn_tpu.ops.flash_attention import flash_attention

    if on_accel:
        B, T, H, D, iters = 4, 4096, 8, 128, 10
    else:
        B, T, H, D, iters = 1, 256, 2, 64, 2
    rng = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, H, D), jnp.bfloat16)

    spreads = []

    def chained(fn, n):
        """The dependency-chained scan harness — ONE builder for every
        attention row (T=4096 and T=32768), so the timing method cannot
        silently diverge between them (a single-dispatch timing adds the
        host's per-dispatch latency to a kernel that takes milliseconds)."""
        @jax.jit
        def many(q, k, v):
            def body(qc, _):
                out = fn(qc, k, v)
                return (qc + 0.0001 * out).astype(qc.dtype), ()
            qc, _ = jax.lax.scan(body, q, None, length=n)
            return jnp.sum(qc.astype(jnp.float32))
        return many

    def timed(fn):
        many = chained(fn, iters)
        _fetch_scalar(many(q, k, v))  # compile + warm

        def sample():
            t0 = time.perf_counter()
            _fetch_scalar(many(q, k, v))
            return (time.perf_counter() - t0) / iters * 1000

        # n=5: the interpret-mode flash rows measured 60%+ spread at
        # n=3 — the row driving two rounds of phantom "drift".
        med, spread = _repeat_median(sample, 1 if on_accel else 5)
        spreads.append(spread)
        return med

    def grad_of(attn):
        # Full backward (dq AND dk/dv kernels — grad wrt q alone would let
        # JAX dead-code-eliminate the dkv kernel); sum into q's shape so the
        # chained-scan timing harness can thread it.
        def fn(q, k, v):
            dq, dk, dv = jax.grad(
                lambda qq, kk, vv: jnp.sum(attn(qq, kk, vv).astype(jnp.float32)),
                argnums=(0, 1, 2),
            )(q, k, v)
            return dq + dk + dv
        return fn

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa: E731
    xla = lambda q, k, v: dot_product_attention(q, k, v, causal=True)  # noqa: E731
    f_fwd, x_fwd = timed(flash), timed(xla)
    f_bwd, x_bwd = timed(grad_of(flash)), timed(grad_of(xla))
    out = {
        "attn_shape": f"B{B}xT{T}xH{H}xD{D}_bf16_causal",
        "flash_fwd_ms": round(f_fwd, 3),
        "xla_fwd_ms": round(x_fwd, 3),
        "flash_fwdbwd_ms": round(f_bwd, 3),
        "xla_fwdbwd_ms": round(x_bwd, 3),
        "flash_fwd_speedup": round(x_fwd / f_fwd, 2),
        "flash_fwdbwd_speedup": round(x_bwd / f_bwd, 2),
    }
    if not on_accel:
        # Worst per-measurement spread of the 4 medians-of-3 above: the
        # driver line can now tell proxy jitter from a real regression.
        out["attn_proxy_spread_pct"] = max(spreads)

    # Adopt the fwd+bwd rows (the training-relevant comparison) as this
    # (device, shape-bucket)'s attention-variant decision — the measured
    # flash-vs-xla inversion (3.0x on chip, 0.56x CPU interpret) is
    # exactly what ops.attention's 'auto' dispatch needs persisted.
    try:
        from chainermn_tpu import tuning

        key = tuning.decision_key(shape=(T, H, D), dtype=jnp.bfloat16)
        # spreads=None on accel: single-sample rows take the registry's
        # 10% noise floor (see _bench_moe_dispatch).
        tuning.record_measurement(
            "attention", key, {"flash": f_bwd, "xla": x_bwd},
            spreads=(None if on_accel
                     else {"flash": spreads[2], "xla": spreads[3]}),
        )
        out["attention_selected"] = tuning.choice(
            "attention", ("flash", "xla"), key
        )
    except Exception as e:
        out["attention_autotune_error"] = f"{type(e).__name__}: {e}"[:120]

    if on_accel:
        # Long-context single-chip point: the VMEM-blocked kernel keeps
        # working where materialised attention stops compiling (measured
        # T=32768: flash 90 ms; XLA attention fails to compile).
        LT = 32768

        ql = jax.random.normal(kq, (1, LT, 8, 128), jnp.bfloat16)

        def timed_long(attn, n=4):
            """Long-context timing via the SAME ``chained`` harness as
            the T=4096 rows: a single-dispatch timing measures kernel +
            dispatch latency, which can swamp the banded-grid win the
            windowed row exists to show (the k-block span math says ~8x
            of the work vanishes at window 2048)."""
            many = chained(attn, n)
            _fetch_scalar(many(ql, ql, ql))  # compile + warm
            t0 = time.perf_counter()
            _fetch_scalar(many(ql, ql, ql))
            return round((time.perf_counter() - t0) / n * 1000, 1)

        def classify(e, note: str = "") -> str:
            """Name the real cause, not just the exception class (round-4
            VERDICT item 8). ``note`` carries the per-path explanation —
            only the XLA comparator materialises the O(T^2) scores."""
            import re

            msg = str(e)
            low = msg.lower()
            if ("resource_exhausted" in low or "out of memory" in low
                    or "oom" in low or "exceeds the limit" in low
                    or ("allocat" in low and "fail" in low)):
                m = re.search(
                    r"[\d.]+\s*(?:[gmk]i?b|bytes)", low
                )
                size = f" ({m.group(0)})" if m else ""
                return f"OOM{size}{note}"
            return f"{type(e).__name__}: {msg}"[:200]

        xla_oom_note = (": expected — the materialised O(T^2) score "
                        "tensor alone is 8 heads * 32768^2 * 4 B = "
                        "34.4 GB vs 16 GB HBM")
        try:
            out["flash_32k_fwd_ms"] = timed_long(
                lambda q, k, v: flash_attention(q, k, v, causal=True)
            )
        except Exception as e:
            out["flash_32k_error"] = classify(e)
        try:
            # Same iters as the flash row: on 16 GB parts this OOMs in
            # compile, but on a larger-HBM chip the row must not fall
            # back to the retired single-dispatch method.
            out["xla_32k_fwd_ms"] = timed_long(
                lambda q, k, v: dot_product_attention(q, k, v, causal=True)
            )
        except Exception as e:
            # keep *_ms keys type-stable (floats). The comparator running
            # out of memory is this row's expected RESULT on a 16 GB part,
            # not a failed phase; anything else is an error.
            cause = classify(e, xla_oom_note)
            out["xla_32k_oom" if cause.startswith("OOM")
                else "xla_32k_error"] = cause

        # Sliding window at long context: the band-narrowed grid should
        # approach full-causal-time * (window/T) — the row that certifies
        # the O(T*W) claim on silicon (r3; docs/api.md ops section).
        try:
            win = 2048
            out["flash_32k_window2k_fwd_ms"] = timed_long(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=True, window=win
                ),
                n=8,  # ~8x less work than full-causal; amortise more
            )
        except Exception as e:
            out["flash_32k_window_error"] = f"{type(e).__name__}"[:80]
    return out


def _resnet_setup(comm, on_accel: bool, *, stem: str = "standard",
                  force_remat: str | None = None):
    """Shared ResNet bench setup (headline and s2d variants): model, global
    batch (multihost-converted), jitted step, initial state. One place owns
    the workload definition so the variants cannot drift."""
    import jax
    import jax.numpy as jnp
    import optax

    from chainermn_tpu import create_multi_node_optimizer
    from chainermn_tpu.models import ResNet18, ResNet50
    from chainermn_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    knobs = {}
    if on_accel:
        # Perf knobs adoptable from the sweep's winner without a code
        # edit (examples/imagenet/sweep_mfu.py -> docs/benchmarks.md
        # roofline): remat mode and per-device batch. ALWAYS recorded in
        # the returned knobs (defaults included) so the carried-result
        # machinery compares like with like.
        remat_mode = (force_remat if force_remat is not None else
                      os.environ.get("CHAINERMN_BENCH_RESNET_REMAT", "none"))
        if remat_mode not in ("none", "conv", "full"):
            raise ValueError(
                "CHAINERMN_BENCH_RESNET_REMAT must be none|conv|full, "
                f"got {remat_mode!r}"
            )
        model = ResNet50(
            num_classes=1000, stem=stem,
            remat=remat_mode != "none",
            remat_policy="conv" if remat_mode == "conv" else None,
        )
        per_device_batch = int(
            os.environ.get("CHAINERMN_BENCH_RESNET_BATCH", "128")
        )
        hw = 224
        metric = "resnet50_images_per_sec"
        donate = (os.environ.get(
            "CHAINERMN_BENCH_RESNET_DONATE", "false").lower()
            in ("1", "true", "yes"))
        knobs = {"resnet_remat": remat_mode,
                 "resnet_batch": per_device_batch,
                 "resnet_donate": donate}
    else:
        model = ResNet18(num_classes=100, compute_dtype=jnp.float32,
                         stem=stem)
        per_device_batch, hw = 8, 32
        metric = "resnet18_cpu_proxy_images_per_sec"

    batch = per_device_batch * comm.size
    rng = jax.random.PRNGKey(0)
    # bf16 images: halves the input-pipeline HBM bytes of a bandwidth-bound
    # step (measured +6% img/s on v5e); the model casts to its compute dtype
    # at entry either way.
    x = jax.random.normal(rng, (batch, hw, hw, 3), jnp.bfloat16)
    y = jax.random.randint(rng, (batch,), 0, 10)
    if jax.process_count() > 1:
        # Each process holds the full batch locally; assemble the global
        # sharded arrays the jitted step's in_specs expect.
        from jax.experimental import multihost_utils
        from jax.sharding import PartitionSpec as P

        x, y = multihost_utils.host_local_array_to_global_array(
            (x, y), comm.mesh, P()
        )

    variables = jax.jit(lambda k, xb: model.init(k, xb, train=True))(
        jax.random.PRNGKey(42), x[:2]
    )

    def loss_fn(params, batch_, model_state):
        xb, yb = batch_
        logits, mutated = model.apply(
            {"params": params, "batch_stats": model_state},
            xb,
            train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, yb
        ).mean()
        return loss, ({}, mutated["batch_stats"])

    optimizer = create_multi_node_optimizer(
        optax.sgd(0.1, momentum=0.9), comm,
        allreduce_grad_dtype=jnp.bfloat16,
    )
    state = create_train_state(
        variables["params"], optimizer, comm,
        model_state=variables["batch_stats"],
    )
    step = make_train_step(loss_fn, optimizer, comm,
                           donate=bool(knobs.get("resnet_donate", False)))
    return step, state, (x, y), batch, metric, knobs


def _bench_s2d_resnet(comm, on_accel: bool):
    """ResNet-50 with the space-to-depth stem (supplementary): the 3-channel
    7x7 conv wastes the 128-lane MXU; rearranging 4x4 pixel blocks into 48
    channels is the classic TPU fix (measured +16% img/s on v5e). Reported
    separately because the stem is not weight-compatible with the standard
    ResNet-50 the headline metric measures."""
    steps = 13 if on_accel else 2
    step, state, batch_arrays, batch, _, _ = _resnet_setup(
        comm, on_accel, stem="space_to_depth"
    )
    for _ in range(3):
        state, m = step(state, batch_arrays)
    _fetch_scalar(m["loss"])

    def sample():
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, batch_arrays)
        _fetch_scalar(m["loss"])
        return (time.perf_counter() - t0) / steps

    dt, spread = _repeat_median(sample, 1 if on_accel else 3)
    out = {
        "resnet50_s2d_images_per_sec": round(batch / dt, 2),
        "resnet50_s2d_step_ms": round(dt * 1e3, 2),
    }
    if not on_accel:
        out["resnet50_s2d_spread_pct"] = spread
    return out


def _bench_moe_dispatch(on_accel: bool):
    """MoE dispatch-cost crossover (VERDICT r2 item 8): dense one-hot
    einsum (O(T·E·C·d)) vs index sort/scatter dispatch (O(T·d)) at LM
    scale — queue assembly + weighted combine, single device (the
    all_to_all between them is identical either way)."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.parallel.moe import dispatch_einsum, dispatch_sort

    if on_accel:
        T, E, D, iters = 16384, 16, 512, 10
    else:
        T, E, D, iters = 2048, 8, 64, 3
    capacity = int(T / E * 1.25)
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (T, D), jnp.bfloat16)
    logits = jax.random.normal(jax.random.fold_in(rng, 1), (T, E),
                               jnp.float32)

    spreads = []

    def timed(fn):
        @jax.jit
        def run(x, logits):
            def body(c, _):
                queues, combine_fn = fn(c, logits, capacity, 2)
                out = combine_fn(queues)  # identity "expert": pure dispatch
                return (c + 0.001 * out).astype(c.dtype), ()

            c, _ = jax.lax.scan(body, x, None, length=iters)
            return jnp.sum(c.astype(jnp.float32))

        _fetch_scalar(run(x, logits))  # compile + warm

        def sample():
            t0 = time.perf_counter()
            _fetch_scalar(run(x, logits))
            return (time.perf_counter() - t0) / iters * 1000

        med, spread = _repeat_median(sample, 1 if on_accel else 3)
        spreads.append(spread)
        return med

    einsum_ms = timed(dispatch_einsum)
    sort_ms = timed(dispatch_sort)
    out = {
        "moe_dispatch_shape": f"T{T}xE{E}xD{D}_cap{capacity}_top2",
        "moe_dispatch_einsum_ms": round(einsum_ms, 3),
        "moe_dispatch_sort_ms": round(sort_ms, 3),
        "moe_dispatch_sort_speedup": round(einsum_ms / sort_ms, 2),
    }
    if not on_accel:
        out["moe_dispatch_spread_pct"] = max(spreads)
    # Adopt the rows this phase ALREADY measured as the dispatch
    # decision for this (device, shape-bucket): future runs route
    # moe_layer_local's 'auto' through the persisted winner instead of
    # re-measuring (chainermn_tpu.tuning).
    try:
        from chainermn_tpu import tuning

        key = tuning.decision_key(shape=(T, E, D), dtype=jnp.bfloat16)
        # On-accel rows are single samples (many chained iterations):
        # pass spreads=None so adoption applies the registry's 10%
        # single-sample noise floor instead of a fake spread of 0.
        tuning.record_measurement(
            "moe_dispatch", key,
            {"einsum": einsum_ms, "sort": sort_ms},
            spreads=(None if on_accel
                     else {"einsum": spreads[0], "sort": spreads[1]}),
        )
        out["moe_dispatch_selected"] = tuning.choice(
            "moe_dispatch", ("sort", "einsum"), key
        )
    except Exception as e:
        out["moe_dispatch_autotune_error"] = f"{type(e).__name__}: {e}"[:120]
    return out


def _bench_moe_plan(comm, on_accel: bool):
    """ISSUE 20: the expert axis, priced (CPU-proxy convention:
    median-of-n>=3 + spread — a delta inside ``moe_spread_pct`` is
    noise; on-accel rows are single samples under the registry's 10%
    floor).

    One MoE MLP train-step workload, identical routing semantics both
    ways:

    - ``on``: an ``expert x data`` ``ParallelPlan`` — expert leaves
      sharded over the expert axis, tokens dispatched through the two
      all_to_alls (``plan.moe_layer``, dispatch impl via the tuned
      ``moe_dispatch`` decision);
    - ``off``: a pure data plan with every expert replicated — the
      same top-1 sort dispatch run shard-locally, no expert wire.

    The pair is adopted (spread-gated) as this shape's
    ``expert_parallel`` decision, and the drop accounting rides out as
    ``moe_drop_rate`` (dropped tokens / routed tokens at capacity
    factor 1.25)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.parallel.moe import (
        dispatch_sort,
        load_balancing_loss,
        make_expert_params,
        moe_capacity,
        record_moe_dispatch,
    )
    from chainermn_tpu.parallel.plan import ParallelPlan

    n = comm.size
    e_axis = 4 if n >= 8 else (2 if n >= 2 else 1)
    data_axis = max(1, n // e_axis)
    eps = 2  # experts per shard: the a2a ships eps queues per peer
    E = e_axis * eps
    D = 256 if on_accel else 64
    F = 2 * D
    tokens = (64 if on_accel else 16) * n
    steps = 16 if on_accel else 4

    rng = jax.random.PRNGKey(0)

    def _expert_init(r):
        k1, k2 = jax.random.split(r)
        return {"w1": jax.random.normal(k1, (D, F), jnp.float32) * 0.05,
                "w2": jax.random.normal(k2, (F, D), jnp.float32) * 0.05}

    def expert_fn(p, xq):
        return jnp.tanh(xq @ p["w1"]) @ p["w2"]

    # global expert e lives on shard e // eps: stack [e_axis, eps, ...]
    # so the expert-spec'd leading dim matches the axis size and each
    # shard's squeezed leaf is the [eps, ...] stack moe_layer_local
    # vmaps over
    experts = jax.tree.map(
        lambda l: l.reshape(e_axis, eps, *l.shape[1:]),
        make_expert_params(_expert_init, rng, E),
    )
    params = {
        "experts": experts,
        "router": jax.random.normal(jax.random.fold_in(rng, 1),
                                    (D, E), jnp.float32) / 4.0,
    }
    x = jax.random.normal(jax.random.fold_in(rng, 2), (tokens, D),
                          jnp.float32)
    y = jax.random.normal(jax.random.fold_in(rng, 3), (tokens, D),
                          jnp.float32)
    inner = optax.sgd(1e-2)
    devices = list(comm.mesh.devices.flat)
    spreads = []

    def time_plan(plan, loss_fn, specs):
        state = plan.create_train_state(params, inner, param_specs=specs)
        step = plan.compile_train_step(loss_fn, inner, params,
                                       param_specs=specs)
        state, m = step(state, (x, y))
        state, m = step(state, (x, y))
        _fetch_scalar(m["loss"])

        def sample():
            nonlocal state, m
            t0 = time.perf_counter()
            for _ in range(steps):
                state, m = step(state, (x, y))
            _fetch_scalar(m["loss"])
            return (time.perf_counter() - t0) / steps * 1000

        med, spread = _repeat_median(sample, 1 if on_accel else 3)
        spreads.append(spread)
        return med, m

    # ---- on: expert (x data) plan, tokens through the two all_to_alls
    axes = ({"expert": e_axis, "data": data_axis}
            if data_axis > 1 else {"expert": e_axis})
    plan_on = ParallelPlan(axes, devices=devices)
    moe_fn, rec = plan_on.moe_layer(
        tokens_local=tokens // data_axis, d_model=D,
        experts_per_shard=eps, capacity_factor=1.25,
    )
    specs = {"experts": P("expert"), "router": P()}

    def loss_on(p, batch_):
        xb, yb = batch_
        out, aux = moe_fn(xb, p["router"], expert_fn, p["experts"])
        loss = (jnp.mean((xb + out - yb) ** 2)
                + 0.01 * aux["load_balance"])
        return loss, ({"dropped": aux["dropped"],
                       "padded": aux["padded"],
                       "capacity": aux["capacity"],
                       "expert_load": aux["expert_load"]}, ())

    on_ms, on_metrics = time_plan(plan_on, loss_on, specs)
    drop_rate = float(on_metrics["dropped"]) / tokens
    # Host-side mirror of the last step's routing stats (ISSUE 20
    # observability row: the moe_dispatch event -> tap gauges).
    record_moe_dispatch(on_metrics)

    # ---- off: pure data plan, every expert replicated, local dispatch
    plan_off = ParallelPlan({"data": max(1, n)}, devices=devices)
    off_specs = {"experts": P(), "router": P()}

    def loss_off(p, batch_):
        xb, yb = batch_
        logits = xb @ p["router"]
        cap = moe_capacity(xb.shape[0], E, 1, 1.25)
        queues, combine_fn = dispatch_sort(xb, logits, cap, 1)
        flat = jax.tree.map(lambda l: l.reshape(E, *l.shape[2:]),
                            p["experts"])
        out = combine_fn(jax.vmap(expert_fn)(flat, queues))
        loss = (jnp.mean((xb + out - yb) ** 2)
                + 0.01 * load_balancing_loss(logits, axis_name="data"))
        return loss, ({}, ())

    off_ms, _ = time_plan(plan_off, loss_off, off_specs)

    out = {
        "moe_plan_shape": f"T{tokens}xE{E}xD{D}",
        "moe_plan_mesh": plan_on.describe()["mesh"],
        "moe_plan_dispatch": rec["winner"],
        "moe_step_ms": round(on_ms, 3),
        "moe_off_step_ms": round(off_ms, 3),
        "moe_drop_rate": round(drop_rate, 4),
    }
    if not on_accel:
        out["moe_spread_pct"] = max(spreads)
    # Adopt the pair as this shape's expert_parallel decision (the
    # registry default is 'off': the axis must EARN its all_to_alls).
    try:
        from chainermn_tpu import tuning

        key = tuning.decision_key(shape=(tokens, E, D),
                                  dtype=jnp.float32)
        tuning.record_measurement(
            "expert_parallel", key,
            {"on": on_ms, "off": off_ms},
            spreads=(None if on_accel
                     else {"on": spreads[0], "off": spreads[1]}),
        )
        out["moe_selected"] = tuning.choice(
            "expert_parallel", ("on", "off"), key
        )
    except Exception as e:
        out["moe_autotune_error"] = f"{type(e).__name__}: {e}"[:120]
    return out


def _bench_serving(comm, on_accel: bool):
    """ISSUE 4: the continuous-batching serving phase.

    Three measurements on one LM (CPU-proxy convention: median-of-n>=3
    + spread; on-accel rows are single samples of many chained steps and
    adopt under the registry's 10% noise floor):

    1. steady-state decode step per ``decode_impl`` (dense slot ring vs
       paged block pool) — adopted as this shape's ``decode_impl``
       decision;
    2. the paged step across ``kv_block_size`` candidates — adopted as
       ``kv_block_size``;
    3. a full scheduler stream (staggered requests through
       ``prefill_priority`` admission, ``decode_impl='auto'`` so the
       freshly recorded decision is exercised with provenance):
       tokens/s + nearest-rank p50/p99 per-token latency + mean slot
       occupancy from ``Scheduler.summary()``;
    4. speculative spec-vs-plain (ISSUE 5): the same stream at every
       ``spec_tokens`` candidate K (n-gram drafting over each request's
       own history, greedy) — per-K tokens/s medians, ms-per-GENERATED-
       token rows (``serving_spec_ms``: acceptance rate priced in) and
       per-K acceptance rates, adopted as this shape's ``spec_tokens``
       decision via ``record_measurement`` (spread-gated: a noise-band
       "winner" is honestly refused and the table default stands).

    ``serving_model_shape`` (DxHxL) is the key material
    ``tuning seed`` uses to rebuild ``serving_decision_key`` offline.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import (
        DECODE_IMPLS,
        SPEC_TOKENS,
        Request,
        Scheduler,
        ServingEngine,
        serving_decision_key,
    )

    if on_accel:
        layers, d_model, heads, d_ff = 4, 512, 8, 2048
        vocab, max_len, slots = 32000, 512, 16
        block_sizes = (16, 32, 64, 128)
        decode_steps, stream_requests, gen = 32, 24, 32
        dtype = jnp.bfloat16
    else:
        layers, d_model, heads, d_ff = 2, 64, 4, 128
        vocab, max_len, slots = 256, 64, 4
        block_sizes = (16, 64)
        decode_steps, stream_requests, gen = 6, 6, 4
        dtype = jnp.float32
    model = TransformerLM(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        d_model=d_model, d_ff=d_ff, max_len=max_len, compute_dtype=dtype,
    )
    params = jax.jit(
        functools.partial(model.init, train=False)
    )(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    out = {
        "serving_model_shape": f"D{d_model}xH{heads}xL{max_len}",
        "serving_slots": slots,
    }

    def step_median(impl, bs):
        # spec_tokens pinned to 0: these are the PLAIN decode rows — on
        # a box whose cache carries an adopted spec_tokens>0 an 'auto'
        # here would silently turn the baseline speculative.
        eng = ServingEngine(
            model, params, num_slots=slots, max_len=max_len,
            decode_impl=impl, kv_block_size=bs, prefill_buckets=(8, 16),
            spec_tokens=0,
        )
        for i in range(slots):  # full occupancy: the steady-state shape
            eng.prefill_join([1 + i % (vocab - 1)] * 4)

        def sample():
            t0 = time.perf_counter()
            for _ in range(decode_steps):
                eng.decode_step()
            return (time.perf_counter() - t0) / decode_steps * 1000

        sample()  # compile + warm
        return _repeat_median(sample, 1 if on_accel else 3)

    impl_ms, impl_spreads = {}, {}
    block_ms, block_spreads = {}, {}
    impl_ms["dense"], impl_spreads["dense"] = step_median("dense", 64)
    for bs in block_sizes:
        block_ms[str(bs)], block_spreads[str(bs)] = step_median("paged", bs)
    # the impl comparison uses paged at the table-default block size
    # (numeric min as the fallback — a string sort would rank '128'
    # before '16')
    paged_ref = "64" if "64" in block_ms else min(block_ms, key=int)
    impl_ms["paged"] = block_ms[paged_ref]
    impl_spreads["paged"] = block_spreads[paged_ref]
    out["serving_decode_impl_ms"] = {k: round(v, 4)
                                     for k, v in impl_ms.items()}
    out["serving_kv_block_ms"] = {k: round(v, 4)
                                  for k, v in block_ms.items()}
    if not on_accel:
        # Spread keys are emitted ONLY for real multi-sample runs: an
        # on-accel row is a single sample of many chained steps, and an
        # absent key is what tells the offline seeder to apply the same
        # 10% noise floor the live adoption uses (spreads=None below) —
        # a recorded 0.0 would read as "three tied medians" and pin a
        # coin flip.
        out["serving_decode_spread_pct"] = max(impl_spreads.values())
        out["serving_kv_block_spread_pct"] = max(block_spreads.values())

    try:
        from chainermn_tpu import tuning

        key = serving_decision_key(d_model, heads, max_len)
        tuning.record_measurement(
            "decode_impl", key, impl_ms,
            spreads=None if on_accel else impl_spreads,
        )
        tuning.record_measurement(
            "kv_block_size", key, block_ms,
            spreads=None if on_accel else block_spreads,
        )
        out["serving_decode_impl_selected"] = tuning.choice(
            "decode_impl", DECODE_IMPLS, key
        )
    except Exception as e:
        out["serving_autotune_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- full scheduler stream at 'auto' decode/block (provenance
    # exercised) but PLAIN decode (spec_tokens=0): this is the headline
    # baseline the spec sweep below compares against; one engine reused
    # so repeats measure serving, not recompiles.
    eng = ServingEngine(
        model, params, num_slots=slots, max_len=max_len,
        decode_impl="auto", kv_block_size="auto", prefill_buckets=(8, 16),
        spec_tokens=0,
    )

    def run_stream(engine):
        sched = Scheduler(engine, policy="prefill_priority")
        rs = np.random.RandomState(0)
        for _ in range(stream_requests):
            p_len = int(rs.randint(3, 13))
            sched.submit(Request(
                prompt=rs.randint(1, vocab, size=p_len).tolist(),
                max_new_tokens=gen,
            ))
        sched.run()
        return sched.summary()

    def stream_medians(engine):
        """Median summary + tokens/s spread over repeats (one engine:
        repeats measure serving, not recompiles)."""
        run_stream(engine)  # compile + warm every bucket
        summaries = [run_stream(engine)
                     for _ in range(1 if on_accel else 3)]
        summaries.sort(key=lambda s: s["tokens_per_sec"])
        med = summaries[len(summaries) // 2]
        tps = [s["tokens_per_sec"] for s in summaries]
        spread = None
        if len(summaries) > 1 and med["tokens_per_sec"]:
            spread = round(
                100.0 * (tps[-1] - tps[0]) / med["tokens_per_sec"], 1
            )
        return med, spread

    med, spread = stream_medians(eng)
    out["serving_tokens_per_sec"] = med["tokens_per_sec"]
    if spread is not None:
        out["serving_spread_pct"] = spread
    out["serving_token_ms_p50"] = med["token_ms_p50"]
    out["serving_token_ms_p99"] = med["token_ms_p99"]
    out["serving_ttft_ms_p50"] = med.get("ttft_ms_p50")
    out["serving_occupancy_mean"] = med["occupancy_mean"]
    out["serving_requests"] = med["requests"]

    # --- speculative spec-vs-plain (ISSUE 5): identical stream at every
    # spec_tokens candidate, greedy n-gram drafting. ms per GENERATED
    # token (1000 / tokens-per-sec) is the adoption row — acceptance
    # rate is priced into it, and `tuning seed` rebuilds the decision
    # from exactly these keys offline.
    try:
        spec_ms, spec_tps, spec_spreads, spec_rates = {}, {}, {}, {}
        for k_str in SPEC_TOKENS:
            k = int(k_str)
            if k == 0:
                # the headline baseline above IS the K=0 row (identical
                # engine args and request stream, and the registry was
                # last mutated before it was built, so 'auto' resolved
                # the same) — reuse its medians instead of paying
                # another warm-up plus repeat streams.
                med_k, spread_k = med, spread
            else:
                eng_k = ServingEngine(
                    model, params, num_slots=slots, max_len=max_len,
                    decode_impl="auto", kv_block_size="auto",
                    prefill_buckets=(8, 16), spec_tokens=k,
                )
                med_k, spread_k = stream_medians(eng_k)
                del eng_k
            tps_k = med_k["tokens_per_sec"]
            spec_tps[k_str] = tps_k
            spec_ms[k_str] = round(1000.0 / tps_k, 4) if tps_k else None
            spec_spreads[k_str] = spread_k if spread_k is not None else 0.0
            sp = med_k.get("speculation") or {}
            if sp.get("accept_rate") is not None:
                spec_rates[k_str] = sp["accept_rate"]
        out["serving_spec_tokens_per_sec"] = spec_tps
        if all(v is not None for v in spec_ms.values()):
            out["serving_spec_ms"] = spec_ms
        if not on_accel:
            # same convention as the decode rows above: spread keys only
            # for real multi-sample runs; absent = 10% seeding floor.
            out["serving_spec_spread_pct"] = max(spec_spreads.values())
        if spec_rates:
            out["serving_spec_accept_rates"] = spec_rates
        sel = None
        if "serving_spec_ms" in out:
            from chainermn_tpu import tuning

            key = serving_decision_key(d_model, heads, max_len)
            tuning.record_measurement(
                "spec_tokens", key, spec_ms,
                spreads=None if on_accel else spec_spreads,
            )
            sel = tuning.choice("spec_tokens", SPEC_TOKENS, key)
            out["serving_spec_selected"] = sel
            if spec_tps.get("0"):
                best = sel if spec_tps.get(sel) else "0"
                out["serving_spec_speedup"] = round(
                    spec_tps[best] / spec_tps["0"], 3
                )
            if sel in spec_rates:
                out["serving_spec_accept_rate"] = spec_rates[sel]
    except Exception as e:  # never lose the phase's plain rows
        out["serving_spec_error"] = f"{type(e).__name__}: {e}"[:160]
    if not on_accel:
        out["serving_note"] = (
            "CPU-proxy honest floor: tiny LM on the loopback mesh — the "
            "medians rank decode impls/block sizes for THIS backend; "
            "absolute tokens/s is not chip throughput"
        )
    return out


def _bench_serving_prefix(comm, on_accel: bool):
    """ISSUE 7: prefix-sharing KV cache under high duplicate-prefix
    load — N requests over one long system prompt with short unique
    tails, hit depths cycling full/1/2 shared blocks so the
    ``min_shared_blocks`` thresholds produce genuinely different
    streams. The workload the cache exists for: TTFT should collapse
    to the unshared tail's prefill.

    Rows (CPU-proxy convention: median-of-n>=3 + spread; on-accel rows
    are single samples and the offline seeder applies the 10% floor):

    1. the same request stream with ``prefix_cache`` off vs on —
       median TTFT p50 per config (``serving_prefix_ttft_ms``), plus
       tokens/s; adopted as this shape's ``prefix_cache`` decision;
    2. the cache-on stream across ``min_shared_blocks`` candidates
       (``serving_prefix_msb_ttft_ms``) — adopted as
       ``min_shared_blocks``;
    3. the MEASURED prefill-work reduction from the ``prefix_cache``
       trace events (``serving_prefix_prefilled_tokens`` vs
       ``serving_prefix_prompt_tokens``) and the hit rate — the
       acceptance criterion's number, not prose.

    Streams are bit-identical on vs off (the suite pins it); only the
    latency may move, so the comparison is honest by construction.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import (
        MIN_SHARED_BLOCKS,
        PREFIX_CACHE,
        Request,
        Scheduler,
        ServingEngine,
        serving_decision_key,
    )

    if on_accel:
        layers, d_model, heads, d_ff = 4, 512, 8, 2048
        vocab, max_len, slots = 32000, 512, 16
        block_size, shared_len, tail_len = 32, 256, 8
        n_requests, gen = 24, 16
        dtype = jnp.bfloat16
    else:
        layers, d_model, heads, d_ff = 2, 64, 4, 128
        vocab, max_len, slots = 256, 64, 4
        block_size, shared_len, tail_len = 8, 32, 4
        n_requests, gen = 6, 4
        dtype = jnp.float32
    model = TransformerLM(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        d_model=d_model, d_ff=d_ff, max_len=max_len, compute_dtype=dtype,
    )
    params = jax.jit(
        functools.partial(model.init, train=False)
    )(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    rs = np.random.RandomState(7)
    shared = rs.randint(1, vocab, size=shared_len).tolist()
    # Hit DEPTHS must span the min_shared_blocks candidates (1/2/4) or
    # the msb sweep measures three identical streams and seeds noise:
    # cycle full / 1-block / 2-block shared prefixes across requests.
    full_blocks = shared_len // block_size
    depth_cycle = (full_blocks, 1, 2)
    prompts = [
        shared[:depth_cycle[i % len(depth_cycle)] * block_size]
        + rs.randint(1, vocab, size=tail_len).tolist()
        for i in range(n_requests)
    ]

    # Own shape key (the seeder reads it for the two prefix decisions):
    # never the shared "serving_model_shape" — both phases use the same
    # model today, but a merged-doc overwrite would silently re-key the
    # serving phase's decisions if either shape diverged.
    out = {
        "serving_prefix_model_shape": f"D{d_model}xH{heads}xL{max_len}",
        "serving_prefix_shared_tokens": shared_len,
        "serving_prefix_requests": n_requests,
    }

    def run_stream(engine):
        sched = Scheduler(engine, policy="prefill_priority")
        for prompt in prompts:
            sched.submit(Request(prompt=prompt, max_new_tokens=gen))
        sched.run()
        return sched.summary()

    def stream_medians(engine):
        """(median summary by TTFT p50, spread) over repeats — one
        engine reused so repeats measure the steady-state cache-hot
        path (the trie persists across runs), not recompiles."""
        run_stream(engine)  # compile + warm (and, cache on, trie fill)
        summaries = [run_stream(engine)
                     for _ in range(1 if on_accel else 3)]
        summaries.sort(key=lambda s: s["ttft_ms_p50"])
        med = summaries[len(summaries) // 2]
        vals = [s["ttft_ms_p50"] for s in summaries]
        spread = None
        if len(summaries) > 1 and med["ttft_ms_p50"]:
            spread = round(
                100.0 * (vals[-1] - vals[0]) / med["ttft_ms_p50"], 1
            )
        return med, spread

    def engine_for(prefix_cache, msb="1"):
        return ServingEngine(
            model, params, num_slots=slots, max_len=max_len,
            decode_impl="paged", kv_block_size=block_size,
            prefill_buckets=(8, 16), spec_tokens=0,
            prefix_cache=prefix_cache, min_shared_blocks=msb,
        )

    # --- prefix_cache off vs on at the table-default threshold
    ttft_ms, ttft_spreads, tps = {}, {}, {}
    on_summary = None
    for cfg in PREFIX_CACHE:
        med, spread = stream_medians(engine_for(cfg))
        ttft_ms[cfg] = round(med["ttft_ms_p50"], 4)
        ttft_spreads[cfg] = spread if spread is not None else 0.0
        tps[cfg] = med["tokens_per_sec"]
        if cfg == "on":
            on_summary = med
    out["serving_prefix_ttft_ms"] = ttft_ms
    out["serving_prefix_tokens_per_sec"] = tps
    if not on_accel:
        # spread keys only for real multi-sample runs (absent = the
        # seeder's 10% single-sample floor) — the serving-phase
        # convention.
        out["serving_prefix_spread_pct"] = max(ttft_spreads.values())
    if ttft_ms.get("on"):
        out["serving_prefix_ttft_speedup"] = round(
            ttft_ms["off"] / ttft_ms["on"], 3
        )

    # --- the measured prefill-work reduction (trace-event rollup, not
    # prose): with every prompt = shared prefix + unique tail, a hot
    # cache prefills only the tails.
    px = (on_summary or {}).get("prefix_cache") or {}
    if px:
        out["serving_prefix_prompt_tokens"] = px.get("prompt_tokens")
        out["serving_prefix_prefilled_tokens"] = px.get("prefilled_tokens")
        out["serving_prefix_hit_rate"] = px.get("hit_token_rate")

    # --- min_shared_blocks sweep (cache on). msb='1' IS the 'on' arm
    # just measured (engine_for's default) — reuse that row instead of
    # re-benching an identical config.
    try:
        msb_ms = {"1": ttft_ms["on"]}
        msb_spreads = {"1": ttft_spreads["on"]}
        for msb in MIN_SHARED_BLOCKS:
            if msb == "1":
                continue
            med, spread = stream_medians(engine_for("on", msb))
            msb_ms[msb] = round(med["ttft_ms_p50"], 4)
            msb_spreads[msb] = spread if spread is not None else 0.0
        out["serving_prefix_msb_ttft_ms"] = msb_ms
        if not on_accel:
            out["serving_prefix_msb_spread_pct"] = max(
                msb_spreads.values())
    except Exception as e:  # never lose the on/off rows
        out["serving_prefix_msb_error"] = f"{type(e).__name__}: {e}"[:160]

    # --- adoption (spread-gated like every serving decision)
    try:
        from chainermn_tpu import tuning

        key = serving_decision_key(d_model, heads, max_len)
        tuning.record_measurement(
            "prefix_cache", key, ttft_ms,
            spreads=None if on_accel else ttft_spreads,
        )
        if "serving_prefix_msb_ttft_ms" in out:
            tuning.record_measurement(
                "min_shared_blocks", key, out["serving_prefix_msb_ttft_ms"],
                spreads=None if on_accel else msb_spreads,
            )
        out["serving_prefix_selected"] = tuning.choice(
            "prefix_cache", PREFIX_CACHE, key
        )
    except Exception as e:
        out["serving_prefix_autotune_error"] = (
            f"{type(e).__name__}: {e}"[:120])
    if not on_accel:
        out["serving_prefix_note"] = (
            "CPU-proxy honest floor: tiny LM, loopback — the on/off "
            "TTFT ranking holds for THIS backend; absolute ms is not "
            "chip latency"
        )
    return out


def _bench_serving_cluster(comm, on_accel: bool):
    """ISSUE 8: the cluster serving plane — goodput and TTFT at 1 vs 2
    vs 4 replicas over a ``replica × model`` device partition, and
    disaggregated vs colocated prefill/decode at 2 replicas (the
    handoff's TTFT cost/benefit, measured not asserted).

    Rows (CPU-proxy convention: median-of-n>=3 + spread; on-accel rows
    are single samples and the offline seeder applies the 10% floor):

    1. ``serving_cluster_goodput`` / ``serving_cluster_ttft_ms`` per
       replica count — open-loop request burst through the router,
       goodput = generated tokens / router wall;
    2. ``serving_cluster_disagg_ttft_ms`` — the SAME 2-replica set
       driven colocated vs disaggregated; adopted as this shape's
       ``cluster_disagg`` decision (spread-gated — the transfer hop
       must earn adoption, the spec_tokens precedent);
    3. transfer accounting from the router (bytes/handoffs) so the
       disaggregated row carries its measured wire cost.

    Streams are bit-identical across every arm (the suite pins it);
    only latency/goodput may move, so the comparison is honest by
    construction. Engines are reused across repeats (steady-state
    warm caches); each repeat gets a fresh Router.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import Request
    from chainermn_tpu.serving.cluster import Router, make_replicas
    from chainermn_tpu.serving.engine import serving_decision_key

    if on_accel:
        layers, d_model, heads, d_ff = 4, 512, 8, 2048
        vocab, max_len, slots = 32000, 512, 8
        block_size, shared_len = 32, 128
        n_requests, gen = 24, 16
        dtype = jnp.bfloat16
    else:
        layers, d_model, heads, d_ff = 2, 64, 4, 128
        vocab, max_len, slots = 256, 64, 2
        block_size, shared_len = 8, 16
        n_requests, gen = 8, 4
        dtype = jnp.float32
    model = TransformerLM(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        d_model=d_model, d_ff=d_ff, max_len=max_len, compute_dtype=dtype,
    )
    params = jax.jit(
        functools.partial(model.init, train=False)
    )(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    devices = jax.devices()
    counts = [1, 2, 4]
    # tp=2 per replica when the device pool covers the largest
    # replica x model partition (8 devices); else unmeshed replicas
    # (same-process async dispatch only — the honest floor, noted on
    # the row).
    tp = 2 if len(devices) >= max(counts) * 2 else 1

    rs = np.random.RandomState(11)
    shared = rs.randint(1, vocab, size=shared_len).tolist()
    prompts = [
        (shared if i % 2 else shared[:shared_len // 2])
        + rs.randint(1, vocab, size=4).tolist()
        for i in range(n_requests)
    ]

    def burst(router):
        for i, p in enumerate(prompts):
            router.submit(Request(prompt=p, max_new_tokens=gen,
                                  session_id=f"s{i % 4}"))
        router.run(max_seconds=120)
        return router.summary()

    def medians(mk_router):
        burst(mk_router())  # compile + warm (trie fill on repeat 0)
        sums = [burst(mk_router()) for _ in range(1 if on_accel else 3)]
        sums.sort(key=lambda s: s.get("ttft_ms_p50") or 0.0)
        med = sums[len(sums) // 2]
        vals = [s.get("ttft_ms_p50") or 0.0 for s in sums]
        spread = None
        if len(sums) > 1 and med.get("ttft_ms_p50"):
            spread = round(
                100.0 * (vals[-1] - vals[0]) / med["ttft_ms_p50"], 1)
        return med, spread

    engine_kw = dict(
        num_slots=slots, max_len=max_len, decode_impl="paged",
        kv_block_size=block_size, prefill_buckets=(8, 16),
        spec_tokens=0, prefix_cache="on",
    )
    out = {
        "serving_cluster_model_shape": f"D{d_model}xH{heads}xL{max_len}",
        "serving_cluster_requests": n_requests,
        "serving_cluster_tp": tp,
        "serving_cluster_counts": counts,
    }

    goodput, ttft_ms, spreads = {}, {}, {}
    two_replica_set = None
    for n in counts:
        reps = make_replicas(model, params, n, tp=tp, **engine_kw)
        if n == 2:
            two_replica_set = reps
        med, spread = medians(lambda r=reps: Router(
            r, mode="colocated", policy="prefix_aware"))
        goodput[str(n)] = med.get("goodput_tokens_per_sec")
        ttft_ms[str(n)] = round(med.get("ttft_ms_p50") or 0.0, 4)
        spreads[str(n)] = spread if spread is not None else 0.0
    out["serving_cluster_goodput"] = goodput
    out["serving_cluster_ttft_ms"] = ttft_ms
    top = str(max(counts))
    out["serving_cluster_goodput_tokens_per_sec"] = goodput.get(top)
    if goodput.get("1") and goodput.get(top):
        out["serving_cluster_scaling"] = round(
            goodput[top] / goodput["1"], 3)
    if not on_accel:
        out["serving_cluster_spread_pct"] = max(spreads.values())

    # --- disaggregated vs colocated on the SAME 2-replica set
    if two_replica_set is not None:
        try:
            disagg_ms = {"colocated": ttft_ms["2"]}
            disagg_spreads = {"colocated": spreads["2"]}
            med, spread = medians(lambda: Router(
                two_replica_set, mode="disaggregated",
                prefill_replicas=[two_replica_set[0].replica_id]))
            disagg_ms["disaggregated"] = round(
                med.get("ttft_ms_p50") or 0.0, 4)
            disagg_spreads["disaggregated"] = (
                spread if spread is not None else 0.0)
            out["serving_cluster_disagg_ttft_ms"] = disagg_ms
            out["serving_cluster_transfers"] = med["kv_transfer"][
                "transfers"]
            out["serving_cluster_transfer_bytes"] = med["kv_transfer"][
                "bytes"]
            if not on_accel:
                out["serving_cluster_disagg_spread_pct"] = max(
                    disagg_spreads.values())
            if disagg_ms["disaggregated"]:
                out["serving_cluster_disagg_speedup"] = round(
                    disagg_ms["colocated"] / disagg_ms["disaggregated"],
                    3)
            # --- adoption (spread-gated like every serving decision)
            from chainermn_tpu import tuning

            key = serving_decision_key(d_model, heads, max_len)
            tuning.record_measurement(
                "cluster_disagg", key, disagg_ms,
                spreads=None if on_accel else disagg_spreads,
            )
            out["serving_cluster_disagg_selected"] = tuning.choice(
                "cluster_disagg",
                ("colocated", "disaggregated"), key,
            )
        except Exception as e:  # never lose the scaling rows
            out["serving_cluster_disagg_error"] = (
                f"{type(e).__name__}: {e}"[:160])
    if not on_accel:
        out["serving_cluster_note"] = (
            "CPU-proxy honest floor: tiny LM over the virtual-device "
            "mesh — replica scaling and the disagg TTFT ranking hold "
            "for THIS backend; absolute ms is not chip latency"
            + ("" if tp == 2 else
               "; tp=1 (shared device): replicas overlap via async "
               "dispatch only")
        )
    return out


def _bench_serving_burst(comm, on_accel: bool):
    """ISSUE 11: goodput under SLO for bursty OPEN-LOOP traffic —
    monolithic prefill vs chunked prefill vs chunked + SLO policy.

    Seeded Poisson arrivals (open loop: requests are stamped with their
    SCHEDULED arrival time, so a tick that runs long honestly inflates
    queue_wait/TTFT instead of silently slowing the offered load) over
    mixed prompt lengths — short conversational tails plus long
    prompts whose MONOLITHIC prefill freezes every active slot's
    decode for a full forward, the p99 killer chunking exists to fix.

    Every arm serves the identical request set with identical
    per-request TTFT/TPOT targets (calibrated from a monolithic
    warm-up run's medians, so "inside SLO" means "within ~2x/1.5x of
    this box's typical latency" for all three arms alike); goodput =
    generated tokens of requests that finished INSIDE their targets /
    wall. Rows (CPU-proxy convention: median-of-n>=3 + spread; on-accel
    single samples take the seeder's 10% floor):

    1. ``serving_burst_goodput`` / ``serving_burst_ttft_p99_ms`` per
       arm (monolithic / chunked / chunked_slo);
    2. ``serving_burst_chunk_ms`` — ms per SLO-good token at chunk 0
       vs the chunked arm (same admission policy; the SLO arm is a
       scheduler choice, not an engine decision) — adopted as this
       shape's ``prefill_chunk`` decision via ``record_measurement``
       (spread-gated: a noise-band winner is honestly refused and the
       table default 0 stands — the PR 4/5/7/8 precedent).
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import (
        PREFILL_CHUNKS,
        Request,
        Scheduler,
        ServingEngine,
        serving_decision_key,
    )

    if on_accel:
        layers, d_model, heads, d_ff = 4, 512, 8, 2048
        vocab, max_len, slots = 32000, 512, 8
        block_size, chunk = 32, 64
        n_requests, gen = 24, 24
        long_len, short_len = 256, 8
        mean_gap_s = 0.01
        dtype = jnp.bfloat16
    else:
        layers, d_model, heads, d_ff = 2, 64, 4, 128
        vocab, max_len, slots = 256, 64, 4
        block_size, chunk = 8, 16
        n_requests, gen = 10, 6
        long_len, short_len = 40, 4
        mean_gap_s = 0.002
        dtype = jnp.float32
    model = TransformerLM(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        d_model=d_model, d_ff=d_ff, max_len=max_len, compute_dtype=dtype,
    )
    params = jax.jit(
        functools.partial(model.init, train=False)
    )(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    # Seeded workload: every third request is a LONG prompt (the
    # interference source), the rest short; seeded Poisson inter-arrival
    # gaps. One schedule shared by every arm and repeat.
    rs = np.random.RandomState(17)
    reqs_spec = []
    for i in range(n_requests):
        p_len = long_len if i % 3 == 2 else short_len
        reqs_spec.append(
            (rs.randint(1, vocab, size=p_len).tolist(), gen))
    arrivals = np.cumsum(rs.exponential(scale=mean_gap_s,
                                        size=n_requests)).tolist()

    def drive(engine, policy, targets):
        sched = Scheduler(engine, policy=policy)
        sched.start_window()
        t0 = time.perf_counter()
        i = 0
        rounds = 0
        while i < len(reqs_spec) or not sched.drained:
            now = time.perf_counter() - t0
            while i < len(reqs_spec) and arrivals[i] <= now:
                p, g = reqs_spec[i]
                req = Request(
                    prompt=p, max_new_tokens=g,
                    ttft_target_ms=targets[0] if targets else None,
                    tpot_target_ms=targets[1] if targets else None,
                )
                # open-loop stamp: the SCHEDULED arrival, not "when the
                # loop got around to submitting it"
                req._arrival = t0 + arrivals[i]
                sched.submit(req)
                i += 1
            if not sched.drained:
                sched.tick()
            elif i < len(reqs_spec):
                time.sleep(max(
                    0.0, arrivals[i] - (time.perf_counter() - t0)))
            rounds += 1
            if rounds > 500_000:
                raise RuntimeError("serving_burst runaway loop")
        sched.close_window()
        return sched

    def measure(engine, policy, targets):
        sched = drive(engine, policy, targets)
        s = sched.summary()
        wall = s.get("wall_s") or 1e-9
        good = 0
        for ev in sched.event_window:
            if ev.get("kind") != "serving" or ev.get("phase") != "finish":
                continue
            verdicts = [ev.get(k) for k in ("slo_ttft_ok", "slo_tpot_ok")
                        if ev.get(k) is not None]
            if not verdicts or all(verdicts):
                good += int(ev.get("generated") or 0)
        return {
            "goodput": round(good / wall, 2),
            "ttft_p99_ms": s.get("ttft_ms_p99"),
            "tpot_p99_ms": s.get("tpot_ms_p99"),
            "slo_attainment": s.get("slo_attainment"),
            "preemptions": s.get("preemptions", 0),
        }

    def medians(engine, policy, targets):
        measure(engine, policy, targets)  # compile + warm
        rows = [measure(engine, policy, targets)
                for _ in range(1 if on_accel else 3)]
        rows.sort(key=lambda r: r["goodput"])
        med = rows[len(rows) // 2]
        vals = [r["goodput"] for r in rows]
        spread = None
        if len(rows) > 1 and med["goodput"]:
            spread = round(
                100.0 * (vals[-1] - vals[0]) / med["goodput"], 1)
        return med, spread

    engine_kw = dict(
        num_slots=slots, max_len=max_len, decode_impl="paged",
        kv_block_size=block_size, prefill_buckets=(8, 16),
        spec_tokens=0, prefix_cache="off",
    )
    mono = ServingEngine(model, params, prefill_chunk=0, **engine_kw)
    chunked = ServingEngine(model, params, prefill_chunk=chunk,
                            **engine_kw)

    # Calibrate the shared SLO targets from a WARM monolithic run
    # (first run compiles — calibrating on it would hand every arm a
    # compile-inflated, trivially satisfiable TTFT budget): 2x typical
    # TTFT, 1.5x typical TPOT — identical for every arm.
    drive(mono, "prefill_priority", None)
    cal = drive(mono, "prefill_priority", None).summary()
    ttft_target = 2.0 * (cal.get("ttft_ms_p50") or 10.0)
    tpot_target = 1.5 * (cal.get("tpot_ms_p50")
                         or cal.get("token_ms_p50") or 5.0)
    targets = (ttft_target, tpot_target)

    out = {
        "serving_burst_model_shape": f"D{d_model}xH{heads}xL{max_len}",
        "serving_burst_requests": n_requests,
        "serving_burst_chunk": chunk,
        "serving_burst_ttft_target_ms": round(ttft_target, 4),
        "serving_burst_tpot_target_ms": round(tpot_target, 4),
    }
    arms = (
        ("monolithic", mono, "prefill_priority"),
        ("chunked", chunked, "prefill_priority"),
        ("chunked_slo", chunked, "slo"),
    )
    goodput, ttft99, spreads, extra = {}, {}, {}, {}
    for name, eng, policy in arms:
        med, spread = medians(eng, policy, targets)
        goodput[name] = med["goodput"]
        ttft99[name] = med["ttft_p99_ms"]
        spreads[name] = spread if spread is not None else 0.0
        extra[name] = {"slo_attainment": med["slo_attainment"],
                       "preemptions": med["preemptions"],
                       "tpot_p99_ms": med["tpot_p99_ms"]}
    out["serving_burst_goodput"] = goodput
    out["serving_burst_ttft_p99_ms"] = ttft99
    out["serving_burst_arm_details"] = extra
    if not on_accel:
        # spread keys only for real multi-sample runs; absent = the
        # seeder applies the 10% on-accel noise floor (the serving
        # phases' shared convention)
        out["serving_burst_spread_pct"] = max(spreads.values())

    # --- prefill_chunk adoption: ms per SLO-good token, chunk 0 vs C
    # under the SAME admission policy (the engine decision, isolated
    # from the scheduler-policy choice).
    try:
        from chainermn_tpu import tuning

        if goodput.get("monolithic") and goodput.get("chunked"):
            chunk_ms = {
                "0": round(1000.0 / goodput["monolithic"], 4),
                str(chunk): round(1000.0 / goodput["chunked"], 4),
            }
            chunk_spreads = dict.fromkeys(
                chunk_ms, max(spreads["monolithic"], spreads["chunked"]))
            out["serving_burst_chunk_ms"] = chunk_ms
            key = serving_decision_key(d_model, heads, max_len)
            tuning.record_measurement(
                "prefill_chunk", key, chunk_ms,
                spreads=None if on_accel else chunk_spreads,
            )
            out["serving_burst_selected"] = tuning.choice(
                "prefill_chunk", PREFILL_CHUNKS, key)
            out["serving_burst_chunked_speedup"] = round(
                goodput["chunked"] / goodput["monolithic"], 3)
    except Exception as e:
        out["serving_burst_autotune_error"] = (
            f"{type(e).__name__}: {e}"[:160])
    if not on_accel:
        out["serving_burst_note"] = (
            "CPU-proxy honest floor: tiny LM, ms-scale open-loop gaps "
            "— the goodput ranking holds for THIS backend; absolute "
            "tokens/s is not chip throughput"
        )
    return out


def _bench_serving_sampled(comm, on_accel: bool):
    """ISSUE 18: sampled-traffic serving — the perf stack at
    temperature > 0.

    Before counter-based sampling every sampled request was pinned to
    the slow path (the ctor REJECTED spec_tokens>0 / prefill_chunk>0 /
    seq-parallel prefill at temperature>0); this phase measures what
    lifting the gate bought. One seeded request stream at temperature
    0.7 (per-request seeds fixed, so every arm serves a reproducible
    workload) through three arms sharing decode_impl/block size:

    1. ``plain`` — single-token decode, the pre-ISSUE-18 ceiling;
    2. ``spec`` — speculative decode (n-gram drafting, rejection-rule
       acceptance — docs/serving.md "Sampling");
    3. ``chunked`` — chunked prefill through the mixed step.

    Rows (CPU-proxy convention: median-of-n>=3 + spread):
    ``serving_sampled_tokens_per_sec`` per arm, the sampled spec
    acceptance rate, and a spread-gated ``serving_sampled_selected``
    verdict — 'plain' when no arm clears the noise band (honest
    refusal, the spec_tokens precedent). The verdict is recorded as
    cache EVIDENCE under its own ``sampled_serving`` name (acceptance
    rate + speedup beside the per-arm rows) — it drives NO dispatch
    decision: the greedy ``serving``/``serving_burst`` phases own the
    spec_tokens/prefill_chunk adoption rows, and ISSUE 18's whole
    point is that one decision now covers both modes.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import Request, Scheduler, ServingEngine

    if on_accel:
        layers, d_model, heads, d_ff = 4, 512, 8, 2048
        vocab, max_len, slots = 32000, 512, 8
        block_size, chunk, spec_k = 32, 64, 3
        n_requests, gen = 16, 24
        dtype = jnp.bfloat16
    else:
        layers, d_model, heads, d_ff = 2, 64, 4, 128
        vocab, max_len, slots = 256, 64, 4
        block_size, chunk, spec_k = 8, 16, 2
        n_requests, gen = 8, 5
        dtype = jnp.float32
    model = TransformerLM(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        d_model=d_model, d_ff=d_ff, max_len=max_len, compute_dtype=dtype,
    )
    params = jax.jit(
        functools.partial(model.init, train=False)
    )(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    # One seeded workload with FIXED per-request seeds: every arm (and
    # every repeat) samples the identical token streams — counter-based
    # derivation makes throughput comparable across schedules because
    # the work really is the same tokens.
    rs = np.random.RandomState(23)
    reqs_spec = []
    for i in range(n_requests):
        p_len = int(rs.randint(3, 13))
        reqs_spec.append((rs.randint(1, vocab, size=p_len).tolist(),
                          gen, 1000 + i))

    def run_stream(engine):
        sched = Scheduler(engine, policy="prefill_priority")
        for p, g, sd in reqs_spec:
            sched.submit(Request(prompt=p, max_new_tokens=g, seed=sd))
        sched.run()
        return sched.summary()

    def stream_medians(engine):
        run_stream(engine)  # compile + warm every bucket
        summaries = [run_stream(engine)
                     for _ in range(1 if on_accel else 3)]
        summaries.sort(key=lambda s: s["tokens_per_sec"])
        med = summaries[len(summaries) // 2]
        tps = [s["tokens_per_sec"] for s in summaries]
        spread = None
        if len(summaries) > 1 and med["tokens_per_sec"]:
            spread = round(
                100.0 * (tps[-1] - tps[0]) / med["tokens_per_sec"], 1)
        return med, spread

    engine_kw = dict(
        num_slots=slots, max_len=max_len, decode_impl="paged",
        kv_block_size=block_size, prefill_buckets=(8, 16),
        prefix_cache="off", temperature=0.7, base_seed=42,
    )
    arms = (
        ("plain", dict(spec_tokens=0, prefill_chunk=0)),
        ("spec", dict(spec_tokens=spec_k, prefill_chunk=0)),
        ("chunked", dict(spec_tokens=0, prefill_chunk=chunk)),
    )
    out = {
        "serving_sampled_model_shape": f"D{d_model}xH{heads}xL{max_len}",
        "serving_sampled_requests": n_requests,
        "serving_sampled_temperature": 0.7,
    }
    tps, spreads = {}, {}
    accept_rate = None
    for name, kw in arms:
        eng = ServingEngine(model, params, **engine_kw, **kw)
        med, spread = stream_medians(eng)
        tps[name] = med["tokens_per_sec"]
        spreads[name] = spread if spread is not None else 0.0
        if name == "spec":
            sp = med.get("speculation") or {}
            accept_rate = sp.get("accept_rate")
        del eng
    out["serving_sampled_tokens_per_sec"] = tps
    if not on_accel:
        # spread keys only for real multi-sample runs (the serving
        # phases' shared convention; absent = on-accel 10% floor)
        out["serving_sampled_spread_pct"] = max(spreads.values())
    if accept_rate is not None:
        out["serving_sampled_spec_accept_rate"] = accept_rate
    if tps.get("plain"):
        out["serving_sampled_spec_speedup"] = round(
            (tps.get("spec") or 0.0) / tps["plain"], 3)
        # Spread-gated verdict through the registry's own decide rule,
        # recorded as cache evidence under a NON-decision name (no
        # resolve site reads 'sampled_serving' — the greedy phases own
        # the knob adoptions). None = spread-dominated: 'plain' stands,
        # the honest refusal every adoption row uses, and nothing is
        # stored.
        try:
            from chainermn_tpu import tuning
            from chainermn_tpu.serving import serving_decision_key

            key = serving_decision_key(d_model, heads, max_len)
            evidence = {"tokens_per_sec": tps}
            if accept_rate is not None:
                evidence["spec_accept_rate"] = accept_rate
            winner = tuning.record_measurement(
                "sampled_serving", key, tps,
                spreads=None if on_accel else spreads,
                higher_is_better=True,
                extra_evidence=evidence,
            )
            out["serving_sampled_selected"] = winner or "plain"
        except Exception as e:
            out["serving_sampled_autotune_error"] = (
                f"{type(e).__name__}: {e}"[:160])
    if not on_accel:
        out["serving_sampled_note"] = (
            "CPU-proxy honest floor: tiny LM, sampled streams — the "
            "arm ranking holds for THIS backend; absolute tokens/s is "
            "not chip throughput"
        )
    return out


def _bench_serving_decode_kernel(comm, on_accel: bool):
    """ISSUE 19: the fused paged-decode kernel vs the XLA dense-view
    attend — the adoption row for ``decode_attend_impl``.

    One paged engine shape, two arms differing ONLY in the attend read
    (``decode_attend_impl`` is a static model field; the write path is
    byte-identical): prefill every slot to HALF the horizon — the
    regime where the kernel's live-only block reads beat the gather's
    full-table-width habit (tools/byte_audit.py decode prices the HBM
    story) — then time steady-state decode ticks.

    Rows (CPU-proxy convention: median-of-n>=3 + spread):
    ``serving_decode_kernel_ms`` per arm, spread-gated adoption of
    ``decode_attend_impl`` via ``record_measurement``. On CPU the fused
    arm runs the kernel's interpret-mode EMULATION — slower than XLA by
    construction, so the expected CPU verdict is an HONEST REFUSAL (or
    an xla win): the table default stands and only a chip run (this
    phase plus tools/kernel_compile_check.py) can flip the decision.
    Mosaic rejects the kernel's KV block today (ROADMAP S4), so on a TPU
    the fused arm raises and the phase records its error.
    """
    import functools
    import time

    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import (
        DECODE_ATTEND_IMPLS,
        ServingEngine,
        serving_decision_key,
    )

    if on_accel:
        layers, d_model, heads, d_ff = 4, 512, 8, 2048
        vocab, max_len, slots = 32000, 512, 16
        block_size, prompt_len, decode_steps = 32, 256, 32
        dtype = jnp.bfloat16
    else:
        layers, d_model, heads, d_ff = 2, 64, 4, 128
        vocab, max_len, slots = 256, 64, 4
        block_size, prompt_len, decode_steps = 8, 32, 6
        dtype = jnp.float32
    model = TransformerLM(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        d_model=d_model, d_ff=d_ff, max_len=max_len, compute_dtype=dtype,
    )
    params = jax.jit(
        functools.partial(model.init, train=False)
    )(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    out = {
        "serving_decode_kernel_model_shape":
            f"D{d_model}xH{heads}xL{max_len}",
        "serving_decode_kernel_prompt_len": prompt_len,
    }

    def step_median(attend_impl):
        eng = ServingEngine(
            model, params, num_slots=slots, max_len=max_len,
            decode_impl="paged", decode_attend_impl=attend_impl,
            kv_block_size=block_size, prefill_buckets=(prompt_len,),
            spec_tokens=0,
        )
        for i in range(slots):  # half-horizon histories, full occupancy
            eng.prefill_join([1 + (i + j) % (vocab - 1)
                              for j in range(prompt_len)])

        def sample():
            t0 = time.perf_counter()
            for _ in range(decode_steps):
                eng.decode_step()
            return (time.perf_counter() - t0) / decode_steps * 1000

        sample()  # compile + warm
        return _repeat_median(sample, 1 if on_accel else 3)

    ms, spreads = {}, {}
    ms["xla"], spreads["xla"] = step_median("xla")
    ms["fused"], spreads["fused"] = step_median("fused")
    out["serving_decode_kernel_ms"] = {k: round(v, 4)
                                       for k, v in ms.items()}
    if not on_accel:
        # Absent spread key = on-accel single sample; the offline
        # seeder then applies the registry's 10% noise floor.
        out["serving_decode_kernel_spread_pct"] = max(spreads.values())
    out["serving_decode_kernel_fused_speedup"] = round(
        ms["xla"] / ms["fused"], 3) if ms["fused"] else None
    try:
        from chainermn_tpu import tuning

        key = serving_decision_key(d_model, heads, max_len)
        winner = tuning.record_measurement(
            "decode_attend_impl", key, ms,
            spreads=None if on_accel else spreads,
            extra_evidence={"prompt_len": prompt_len,
                            "decode_steps": decode_steps},
        )
        out["serving_decode_kernel_selected"] = tuning.choice(
            "decode_attend_impl", DECODE_ATTEND_IMPLS, key)
    except Exception as e:
        out["serving_decode_kernel_autotune_error"] = (
            f"{type(e).__name__}: {e}"[:120])
    if not on_accel:
        out["serving_decode_kernel_note"] = (
            "CPU proxy runs the kernel in interpret mode (an emulator): "
            "the fused arm losing here says nothing about the chip — "
            "adoption waits for a live capture"
        )
    return out


def _bench_serving_tenants(comm, on_accel: bool):
    """ISSUE 14: mixed-tenant adapter serving — N tenants' low-rank
    deltas over one base model, Zipf-skewed offered load, shared
    per-tenant system prompts (the namespaced prefix cache's food),
    deficit-round-robin fair-share admission.

    The run is SATURATED and wall-bounded (``max_seconds``) so the
    fairness property is actually exercised: the queue holds a
    Zipf-skewed backlog, and equal-weight DRR admission should serve
    tenants near-evenly regardless — Jain's index over the per-tenant
    served-token totals is the measured verdict, not prose. Rows
    (CPU-proxy convention: median-of-n>=3 + spread):

    1. ``serving_tenants_goodput`` — generated tokens / wall for the
       mixed-tenant gather engine;
    2. ``serving_tenants_fairness`` — Jain over per-tenant served
       tokens (1.0 = perfectly even service under the skewed backlog);
    3. ``serving_tenants_ttft_p99_ms`` — per-tenant p99 TTFT from the
       rollup (details file);
    4. ``serving_tenants_adapter_ms`` — ms per generated token serving
       the DOMINANT tenant's stream set via the gather bank vs a
       merged (weights-folded) engine — adopted as this shape's
       ``adapter_impl`` decision via ``record_measurement``
       (spread-gated: a noise-band winner is honestly refused and the
       table default ``gather`` stands, the PR 4/5/7/8/10 precedent).
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.observability.stats import jain_index
    from chainermn_tpu.serving import (
        ADAPTER_IMPLS,
        AdapterBank,
        Request,
        Scheduler,
        ServingEngine,
        random_adapter,
        serving_decision_key,
    )

    if on_accel:
        layers, d_model, heads, d_ff = 4, 512, 8, 2048
        vocab, max_len, slots = 32000, 512, 8
        block_size, sys_len, tail_len = 32, 64, 8
        n_tenants, n_requests, gen = 4, 48, 24
        max_seconds = 20.0
        dtype = jnp.bfloat16
    else:
        layers, d_model, heads, d_ff = 2, 64, 4, 128
        vocab, max_len, slots = 256, 64, 4
        block_size, sys_len, tail_len = 8, 16, 4
        # Offered load deliberately exceeds what the wall bound can
        # serve (every tenant's backlog outlives the window on an idle
        # box): the queue stays backlogged for ALL tenants, so the
        # fairness index measures the ADMISSION policy — an FCFS run
        # would reproduce the offered Zipf skew (~0.77), fair-share
        # should push toward 1.0.
        n_tenants, n_requests, gen = 3, 120, 16
        max_seconds = 0.2
        dtype = jnp.float32
    model = TransformerLM(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        d_model=d_model, d_ff=d_ff, max_len=max_len, compute_dtype=dtype,
    )
    params = jax.jit(
        functools.partial(model.init, train=False)
    )(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))

    tenants = [f"tenant{i}" for i in range(n_tenants)]
    bank = AdapterBank(model, capacity=n_tenants + 1, rank=2)
    for i, t in enumerate(tenants):
        bank.register(t, random_adapter(model, 2, seed=100 + i,
                                        scale=0.5))
    weights = {t: 1.0 for t in tenants}

    # Zipf-skewed offered load over a shared per-tenant system prompt
    # plus a unique tail — one seeded schedule for every repeat/arm.
    rs = np.random.RandomState(23)
    sys_prompts = {t: rs.randint(1, vocab, size=sys_len).tolist()
                   for t in tenants}
    zipf_w = np.array([1.0 / (i + 1) ** 1.2 for i in range(n_tenants)])
    zipf_w /= zipf_w.sum()
    order = rs.choice(n_tenants, size=n_requests, p=zipf_w)
    reqs_spec = [
        (tenants[int(i)],
         sys_prompts[tenants[int(i)]]
         + rs.randint(1, vocab, size=tail_len).tolist())
        for i in order
    ]

    engine = ServingEngine(
        model, params, num_slots=slots, max_len=max_len,
        decode_impl="paged", kv_block_size=block_size,
        prefill_buckets=(8, 16, 32), spec_tokens=0, prefix_cache="on",
        min_shared_blocks=1, prefill_chunk=0,
        prefill_seq_parallel="off", adapter_bank=bank,
        adapter_impl="gather",
    )

    def run_mixed(bound, fair: bool = True):
        sched = Scheduler(engine, policy="prefill_priority",
                          tenant_weights=dict(weights) if fair
                          else None)
        for t, p in reqs_spec:
            sched.submit(Request(prompt=p, max_new_tokens=gen,
                                 tenant_id=t))
        sched.run(max_seconds=bound)
        s = sched.summary()
        # The wall bound leaves work in flight by design (saturation);
        # release the engine's slots so the next repeat starts from a
        # clean array instead of raising on a full engine.
        for slot in range(engine.num_slots):
            if engine._active[slot]:
                engine.leave(slot)
        wall = s.get("wall_s") or 1e-9
        per_tenant = {
            t: row["generated_tokens"]
            for t, row in (s.get("tenants") or {}).items()
        }
        fairness = jain_index([
            per_tenant.get(t, 0) / weights[t] for t in tenants
        ])
        return {
            "goodput": round((s.get("generated_tokens") or 0) / wall, 2),
            "fairness": round(fairness, 4) if fairness is not None
            else None,
            "ttft_p99": {t: (s.get("tenants") or {}).get(
                t, {}).get("ttft_ms_p99") for t in tenants},
        }

    run_mixed(max_seconds)  # compile + trie warm
    rows = [run_mixed(max_seconds) for _ in range(1 if on_accel else 3)]
    rows.sort(key=lambda r: r["goodput"])
    med = rows[len(rows) // 2]
    vals = [r["goodput"] for r in rows]
    spread = None
    if len(rows) > 1 and med["goodput"]:
        spread = round(100.0 * (vals[-1] - vals[0]) / med["goodput"], 1)

    # The FCFS contrast row: same backlog, fair share off — the
    # fairness delta is the admission policy's measured contribution.
    fifo = run_mixed(max_seconds, fair=False)

    out = {
        "serving_tenants_model_shape": f"D{d_model}xH{heads}xL{max_len}",
        "serving_tenants_n": n_tenants,
        "serving_tenants_requests": n_requests,
        "serving_tenants_goodput": med["goodput"],
        "serving_tenants_fairness": med["fairness"],
        "serving_tenants_fairness_fifo": fifo["fairness"],
        "serving_tenants_ttft_p99_ms": med["ttft_p99"],
    }
    if not on_accel and spread is not None:
        out["serving_tenants_spread_pct"] = spread

    # --- adapter_impl adoption: ms per generated token serving the
    # DOMINANT tenant's streams — the per-slot gather vs the folded
    # weights (the single-tenant-dominant question the decision asks).
    try:
        from chainermn_tpu import tuning

        dom = tenants[0]
        dom_reqs = [p for t, p in reqs_spec if t == dom][:slots + 2]

        def run_dominant(eng):
            sched = Scheduler(eng, policy="prefill_priority")
            for p in dom_reqs:
                sched.submit(Request(prompt=p, max_new_tokens=gen,
                                     tenant_id=dom))
            sched.run()
            s = sched.summary()
            toks = s.get("generated_tokens") or 1
            return (s.get("wall_s") or 1e-9) / toks * 1e3

        merged_eng = ServingEngine(
            model, params, num_slots=slots, max_len=max_len,
            decode_impl="paged", kv_block_size=block_size,
            prefill_buckets=(8, 16, 32), spec_tokens=0,
            prefix_cache="on", min_shared_blocks=1, prefill_chunk=0,
            prefill_seq_parallel="off", adapter_bank=bank,
            adapter_impl="merged", merged_tenant=dom,
        )
        arm_ms = {"gather": [], "merged": []}
        run_dominant(engine)
        run_dominant(merged_eng)  # compile both before timing
        for _ in range(1 if on_accel else 3):
            arm_ms["gather"].append(run_dominant(engine))
            arm_ms["merged"].append(run_dominant(merged_eng))
        med_ms = {}
        arm_spreads = {}
        for name, samples in arm_ms.items():
            samples.sort()
            m = samples[len(samples) // 2]
            med_ms[name] = round(m, 4)
            arm_spreads[name] = (
                round(100.0 * (samples[-1] - samples[0]) / m, 1)
                if len(samples) > 1 and m else 0.0)
        out["serving_tenants_adapter_ms"] = med_ms
        # The gather/merged arms' OWN spread, not the mixed-run goodput
        # spread (review finding: the offline seed gated adapter_impl
        # on serving_tenants_spread_pct, a different measurement — the
        # live adoption below and a re-seed from this row could
        # disagree on identical data).
        if not on_accel:
            out["serving_tenants_adapter_spread_pct"] = max(
                arm_spreads.values())
        key = serving_decision_key(d_model, heads, max_len)
        tuning.record_measurement(
            "adapter_impl", key, med_ms,
            spreads=None if on_accel else {
                k: max(arm_spreads.values()) for k in med_ms},
        )
        out["serving_tenants_selected"] = tuning.choice(
            "adapter_impl", ADAPTER_IMPLS, key)
        out["serving_tenants_merged_speedup"] = round(
            med_ms["gather"] / med_ms["merged"], 3)
    except Exception as e:
        out["serving_tenants_autotune_error"] = (
            f"{type(e).__name__}: {e}"[:160])
    if not on_accel:
        out["serving_tenants_note"] = (
            "CPU-proxy honest floor: tiny LM + rank-2 adapters — the "
            "fairness index and the gather/merged ranking hold for "
            "THIS backend; absolute tokens/s is not chip throughput"
        )
    return out


def _bench_native_input(comm, on_accel: bool):
    """Real-input-pipeline throughput: the jitted ResNet step fed by the
    C++ threaded prefetch loader (``native/data_loader.py`` — the
    reference's MultiprocessIterator role,
    ``examples/imagenet/train_imagenet.py`` (dagger)) plus
    ``prefetch_to_device`` double buffering, vs device-resident synthetic
    arrays.

    The end-to-end loop (:func:`_native_loop`) performs no device→host
    transfer between its warm-up and the one sync that ends the timed
    region, so the loader, the H2D copies and the steps pipeline freely.
    On an accelerator it runs in THIS process — the process that holds
    the chip is the only one that can use it. The CPU proxy runs it in
    two fresh ``--run native-loop`` children at two step counts and
    differences the timings (set-up and warm-up backlog cancel)."""
    import os
    import tempfile

    import numpy as np

    from chainermn_tpu.native.data_loader import (
        NativeDataLoader,
        write_fixed_records,
    )

    # steps_small must exceed the total buffering depth (loader prefetch=4
    # + prefetch_to_device=2 = 6): a shorter timed region can be served
    # entirely from buffers filled during the untimed warmup/compile,
    # which would bias the difference toward pure loader time.
    steps_small, steps_big = (8, 24) if on_accel else (8, 16)
    step, state, (x_syn, y_syn), batch, _, _ = _resnet_setup(comm, on_accel)
    hw = x_syn.shape[1]

    # A few batches of records; the loader loops epochs, which is fine for
    # a throughput measurement (shuffle order changes per epoch).
    n_records = batch * 4
    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(n_records, hw, hw, 3), dtype=np.uint8)
    labels = rng.integers(0, 10, size=(n_records,)).astype(np.int32)
    fd, path = tempfile.mkstemp(suffix=".bin", prefix="bench_records_")
    os.close(fd)
    write_fixed_records(path, images, labels)
    out = {}
    try:
        # Host-side loader throughput alone (no JAX involvement): the
        # number that isolates the C++ reader+shuffle+batch assembly.
        # Timed from COLD construction so every consumed batch was
        # produced inside the timed window — no assumption about queue
        # fill state (a warm-up batch would make up to `prefetch` timed
        # batches free only in the producer-bound regime, biasing the
        # rate by an amount that depends on which side is faster).
        # Thread spin-up is inside the window; reps amortise it.
        reps = 24 if on_accel else 12
        t0 = time.perf_counter()
        loader = NativeDataLoader(
            path,
            [("image", np.uint8, (hw, hw, 3)), ("label", np.int32, ())],
            batch_size=batch, threads=4, prefetch=4,
        )
        try:
            for _ in range(reps):
                next(loader)
            dt_host = (time.perf_counter() - t0) / reps
        finally:
            loader.close()
        out["native_loader_host_images_per_sec"] = round(batch / dt_host, 2)

        # Synthetic comparison (device-resident inputs — no H2D in the
        # loop). Before the end-to-end rows: it does not depend on them
        # and must survive their failure.
        syn_steps = 12 if on_accel else 3
        state, m = step(state, (x_syn, y_syn))
        _fetch_scalar(m["loss"])
        t0 = time.perf_counter()
        for _ in range(syn_steps):
            state, m = step(state, (x_syn, y_syn))
        _fetch_scalar(m["loss"])
        dt_syn = (time.perf_counter() - t0) / syn_steps
        out["synthetic_images_per_sec"] = round(batch / dt_syn, 2)

        if on_accel:
            out["native_input_method"] = (
                f"in-process ({steps_big} steps), prefetch_to_device(2), "
                "no mid-loop D2H"
            )
            dt_loader = _native_loop(
                step, state, x_syn.dtype, path, hw, batch, steps_big
            ) / steps_big
        else:
            out["native_input_method"] = (
                f"fresh-process differenced ({steps_big}-{steps_small} "
                "steps), prefetch_to_device(2), no mid-loop D2H"
            )

            def child(steps: int) -> float:
                env = dict(os.environ)
                env.update(
                    CMN_NATIVE_STEPS=str(steps),
                    CMN_NATIVE_RECORDS=path,
                    CMN_NATIVE_HW=str(hw),
                    CMN_NATIVE_BATCH=str(batch),
                )
                r, err = _run_child("native-loop", 180, env=env)
                if r is None or "wall_s" not in r:
                    raise RuntimeError(
                        err or "native-loop child: no wall_s")
                return float(r["wall_s"])

            t_small = child(steps_small)
            t_big = child(steps_big)
            dt_loader = (t_big - t_small) / (steps_big - steps_small)
            if dt_loader <= 0:
                raise RuntimeError(
                    f"non-positive differenced step time ({t_big:.2f}s @ "
                    f"{steps_big} vs {t_small:.2f}s @ {steps_small})"
                )

        out.update({
            "native_input_images_per_sec": round(batch / dt_loader, 2),
            "input_pipeline_overhead_pct": round(
                (dt_loader / dt_syn - 1) * 100, 1
            ),
        })
        return out
    finally:
        try:
            os.remove(path)
        except OSError:
            pass


def _native_loop(step, state, dtype, path: str, hw: int, batch: int,
                 steps: int) -> float:
    """``steps`` end-to-end steps (C++ loader → device prefetch → jitted
    ResNet step) with NO device→host transfer between the warm-up and
    the final sync; returns the wall seconds of the timed region."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.native.data_loader import NativeDataLoader
    from chainermn_tpu.training.prefetch import prefetch_to_device

    loader = NativeDataLoader(
        path,
        [("image", np.uint8, (hw, hw, 3)), ("label", np.int32, ())],
        batch_size=batch, threads=4, prefetch=4,
    )
    # u8 over H2D (4x fewer bytes than f32); normalisation on-device.
    norm = jax.jit(
        lambda img: img.astype(dtype) / jnp.asarray(127.5, dtype) - 1.0
    )

    def batches():
        for b in loader:
            yield b["image"], b["label"]

    try:
        it = prefetch_to_device(batches(), size=2)

        def fetch():
            img, lab = next(it)
            return norm(img), lab

        # Warmup: compiles (synchronously, on host) and seeds the device
        # pipeline; no sync here, so the timed region starts with the
        # loader, the copies and the steps already overlapping.
        for _ in range(2):
            state, m = step(state, fetch())

        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step(state, fetch())
        _fetch_scalar(m["loss"])  # the one sync, ends the region
        return time.perf_counter() - t0
    finally:
        loader.close()


def _run_native_loop() -> None:
    """Child mode for the CPU proxy's ``_bench_native_input``: run
    :func:`_native_loop` in a fresh process and print its wall time."""
    steps = int(os.environ["CMN_NATIVE_STEPS"])
    path = os.environ["CMN_NATIVE_RECORDS"]
    hw = int(os.environ["CMN_NATIVE_HW"])
    batch = int(os.environ["CMN_NATIVE_BATCH"])

    from chainermn_tpu import create_communicator

    comm = create_communicator("xla")
    step, state, (x_syn, _), _, _, _ = _resnet_setup(comm, on_accel=False)
    wall = _native_loop(step, state, x_syn.dtype, path, hw, batch, steps)
    print(json.dumps({"wall_s": wall, "steps": steps, "batch": batch}),
          flush=True)


def _transformer_setup(comm, on_accel: bool, steps: int | None = None,
                       interpret: bool | None = None,
                       abstract_params: bool = False):
    """Shared transformer workload definition (bench + byte audit): one
    place owns the model config, knobs, loss, and jitted step so the
    roofline audit (``tools/byte_audit.py``) cannot drift from what the
    bench times — the same rule `_resnet_setup` enforces for the ResNet
    variants. Returns ``(fn, args, B, T, steps, model, cfg,
    knob_fields, n_chunks)`` with ``fn`` the un-lowered jitted step and
    ``args = (params, opt_state, tokens)``. ``interpret`` overrides the
    flash-kernel interpret mode (default: interpret off accelerator) —
    the audit compiles the LM-SCALE config on CPU and needs both.
    ``abstract_params=True`` builds zero params from ``eval_shape`` (no
    forward executed) — for AOT-compile-only consumers like the byte
    audit, where a real interpret-mode init at LM scale would dominate
    wall time producing values nobody reads."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu import create_multi_node_optimizer
    from chainermn_tpu.models import TransformerLM, lm_loss_fused
    from chainermn_tpu.ops.flash_attention import flash_attention

    knob_fields = {}
    use_db = True  # CPU-proxy config keeps the baseline-faithful default
    if on_accel:
        # LM-scale config (VERDICT r2 item 3): 8L / d1024 / 16H / ff4096,
        # T=2048 — ~134M params incl. the 32k tied embedding. Perf knobs
        # adoptable from the sweep's winner without a code edit
        # (examples/transformer/sweep_mfu.py); MFU here uses MODEL flops
        # (6P/token), so remat granularity never inflates it. Non-default
        # knob values are recorded in the artifact.
        remat_mode = os.environ.get("CHAINERMN_BENCH_TF_REMAT", "dots")
        if remat_mode not in ("none", "dots", "nothing"):
            raise ValueError(
                "CHAINERMN_BENCH_TF_REMAT must be none|dots|nothing, "
                f"got {remat_mode!r}"
            )
        B = int(os.environ.get("CHAINERMN_BENCH_TF_BATCH", "16"))
        n_chunks = int(os.environ.get("CHAINERMN_BENCH_TF_CHUNKS", "16"))
        # Head GEOMETRY at fixed d_model: H16xD64 (the classic -base
        # split) vs H8xD128. Identical params and model FLOPs — the qkv
        # projections are d_model x d_model either way — but D=64 head
        # tiles fill only half the 128-wide MXU contraction / VMEM lane
        # dim, so D=128 is the hardware-shaped split. Sweepable so the
        # capture measures rather than asserts the difference.
        n_heads = int(os.environ.get("CHAINERMN_BENCH_TF_HEADS", "16"))
        if n_heads < 1 or 1024 % n_heads:
            raise ValueError(
                f"CHAINERMN_BENCH_TF_HEADS must divide 1024, got {n_heads}"
            )
        # Double buffering is part of the BASELINE workload identity
        # ("Transformer-base LM, double-buffered allreduce"), hence the
        # default — but on ONE chip there is no collective to overlap
        # and the bank carry is pure cost (micro row: 0.85x), so the
        # sweep measures both and the knob records which ran.
        db_env = os.environ.get("CHAINERMN_BENCH_TF_DB", "true").lower()
        if db_env not in ("true", "false"):
            raise ValueError(
                f"CHAINERMN_BENCH_TF_DB must be true|false, got {db_env!r}"
            )
        use_db = db_env == "true"
        T = 2048
        if steps is None:
            steps = 10
        model = TransformerLM(
            num_layers=8, d_model=1024, num_heads=n_heads, d_ff=4096,
            max_len=2048, remat=remat_mode != "none",
            remat_policy="dots" if remat_mode != "nothing" else "nothing",
            return_hidden=True,
        )
        cfg = "8L-d1024-ff4096-v32k"
        # ALWAYS recorded (defaults included) so the carried-result
        # machinery compares like with like — same rule as the ResNet
        # knobs.
        knob_fields = {"tf_remat": remat_mode, "tf_batch": B,
                       "tf_chunks": n_chunks, "tf_heads": n_heads,
                       "tf_db": use_db}
    else:
        B, T = 2, 128
        if steps is None:
            steps = 2
        model = TransformerLM(vocab_size=512, num_layers=2, d_model=64,
                              d_ff=128, max_len=256, return_hidden=True)
        n_chunks = 2
        cfg = "tiny-cpu-proxy"
    if interpret is None:
        interpret = not on_accel

    def attn(q, k, v, *, causal, scale):
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               interpret=interpret)

    model = model.clone(attention_fn=attn)
    B *= comm.size
    tokens = jax.random.randint(
        jax.random.PRNGKey(0), (B, T), 0, model.vocab_size
    )
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        tokens = multihost_utils.host_local_array_to_global_array(
            tokens, comm.mesh, P()
        )
    if abstract_params:
        params = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(
                lambda k, t: model.init(k, t, train=True),
                jax.random.PRNGKey(1), tokens[:2],
            ),
        )
    else:
        params = jax.jit(
            lambda k, t: model.init(k, t, train=True)
        )(jax.random.PRNGKey(1), tokens[:2])
    opt = create_multi_node_optimizer(
        optax.adam(1e-4), comm, double_buffering=use_db,
        allreduce_grad_dtype=jnp.bfloat16,
    )
    axes = comm.grad_axes

    def loss_fn(p, tok):
        hidden = model.apply(p, tok, train=True)
        emb = p["params"]["tok_emb"]["embedding"]
        return lm_loss_fused(hidden, emb, tok, n_chunks=n_chunks)

    def local(params, opt_state, tok):
        def one(carry, _):
            params, opt_state = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, tok)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            one, (params, opt_state), None, length=steps
        )
        return losses[-1]

    fn = jax.jit(
        shard_map(local, mesh=comm.mesh,
                  in_specs=(P(), P(), P(axes)),
                  out_specs=P(), check_vma=False)
    )
    opt_state = opt.init(params)
    return (fn, (params, opt_state, tokens), B, T, steps, model, cfg,
            knob_fields, n_chunks)


def _bench_transformer(comm, on_accel: bool):
    """Transformer LM tokens/sec + MFU — the remaining BASELINE.json config
    ("Transformer-base LM — large embedding grads, double-buffered
    allreduce"): full train step (fwd + bwd + bf16 grad pmean + adam) with
    the flash-attention kernel, double buffering, per-block remat
    (dots-saveable policy) and the fused chunked LM head
    (``lm_loss_fused`` — the [B,T,vocab] logits tensor never hits HBM).
    MFU uses MODEL flops (6P/token + attention), not cost analysis —
    see the note at the bottom of this function."""
    import jax

    (fn, (params, opt_state, tokens), B, T, steps, model, cfg,
     knob_fields, n_chunks) = _transformer_setup(comm, on_accel)

    fn = fn.lower(params, opt_state, tokens).compile()

    _fetch_scalar(fn(params, opt_state, tokens))  # compile + warm

    def sample():
        t0 = time.perf_counter()
        _fetch_scalar(fn(params, opt_state, tokens))
        return (time.perf_counter() - t0) / steps

    dt, tf_spread = _repeat_median(sample, 1 if on_accel else 3)

    # MFU uses MODEL flops (the PaLM-appendix convention): 6P per token for
    # the matmul stack + 6·L·T·d for causal attention fwd+bwd. Remat
    # recomputation deliberately NOT counted — that's the price paid, not
    # useful work. (XLA's cost analysis, which does count it, is reported
    # separately as hardware utilisation.)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    model_flops_per_token = (
        6 * n_params
        + 6 * model.num_layers * T * model.d_model
    )
    model_step_flops = model_flops_per_token * B * T / comm.size  # per device

    out = {
        "transformer_tokens_per_sec": round(B * T / dt, 1),
        "transformer_step_ms": round(dt * 1e3, 2),
        "transformer_params_m": round(n_params / 1e6, 1),
        "transformer_config": (
            f"{cfg} B{B}xT{T} flash"
            + ("+double-buffer" if knob_fields.get("tf_db", True) else "")
            + (f"+remat[{model.remat_policy}]" if model.remat else "")
            + "+fused-head"
        ),
        **knob_fields,
    }
    if not on_accel:
        out["transformer_proxy_spread_pct"] = tf_spread
    peak = _peak_flops(jax.devices()[0].device_kind)
    if peak:
        out["transformer_mfu"] = round(model_step_flops / dt / peak, 4)
        out["transformer_model_tflops_per_step"] = round(
            model_step_flops / 1e12, 3
        )
        # NOTE: XLA's cost_analysis() does not multiply flops by the
        # scan/while trip count, so a per-step "hardware utilisation"
        # derived from it under the 10-step scan is meaningless (r3
        # measured 0.024 against a model-flops MFU of 0.35). The ResNet
        # rows are unaffected (no scan around the timed step there).
    return out


def _bench_double_buffering(comm, on_accel: bool):
    """Measured (not asserted) double-buffering overlap: step time of a
    communication-heavy MLP with ``double_buffering`` off vs on (VERDICT
    round-1 weak item 6 — the overlap claim needs a number behind it).

    On a single chip the grad psum is a no-op, so the honest expectation is
    a ratio ~1.0; the metric carries ``n_devices`` context and becomes
    meaningful on a real multi-chip mesh, where overlap hides the allreduce
    behind the next step's backward (staleness-1, the reference's
    ``_DoubleBufferingOptimizer``, SURVEY.md §2.3)."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu import create_multi_node_optimizer

    width = 4096 if on_accel else 256
    layers = 4
    batch = 8 * comm.size
    steps = 20 if on_accel else 3
    rng = jax.random.PRNGKey(0)
    params = [
        jax.random.normal(jax.random.fold_in(rng, i),
                          (width, width), jnp.float32) * 0.02
        for i in range(layers)
    ]
    x = jax.random.normal(rng, (batch, width), jnp.bfloat16)
    axes = comm.grad_axes

    def time_variant(double_buffering: bool) -> float:
        opt = create_multi_node_optimizer(
            optax.sgd(1e-3), comm, double_buffering=double_buffering,
            allreduce_grad_dtype=jnp.bfloat16,
        )

        def local(params, opt_state, xb):
            def one_step(carry, _):
                params, opt_state = carry

                def loss_fn(ps):
                    h = xb
                    for w in ps:
                        h = jnp.tanh(h @ w.astype(jnp.bfloat16))
                    return jnp.sum(h.astype(jnp.float32) ** 2)

                grads = jax.grad(loss_fn)(params)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), ()

            (params, opt_state), _ = jax.lax.scan(
                one_step, (params, opt_state), None, length=steps
            )
            return params

        fn = jax.jit(
            shard_map(local, mesh=comm.mesh,
                      in_specs=(P(), P(), P(axes)),
                      out_specs=P(), check_vma=False)
        )
        opt_state = opt.init(params)
        fn = fn.lower(params, opt_state, x).compile()
        a = fn.cost_analysis()
        a = a[0] if isinstance(a, (list, tuple)) else a
        flops = float((a or {}).get("flops", 0.0)) or None
        _fetch_scalar(fn(params, opt_state, x)[0][:1, :1])  # warm

        def sample():
            t0 = time.perf_counter()
            _fetch_scalar(fn(params, opt_state, x)[0][:1, :1])
            return (time.perf_counter() - t0) / steps * 1000

        # The RATIO row is the one that drifted round-to-round (1.034x
        # r3 -> 0.876x r4 on the CPU proxy): median-of-3 on both
        # variants, chip included — each sample is one scan-fused call.
        med, spread = _repeat_median(sample, 3)
        return med, flops, spread

    plain, flops_p, spread_p = time_variant(False)
    buffered, flops_b, spread_b = time_variant(True)
    out = {
        "double_buffer_step_ms": round(buffered, 3),
        "plain_step_ms": round(plain, 3),
        "double_buffer_speedup": round(plain / buffered, 3),
        "double_buffer_spread_pct": max(spread_p, spread_b),
        "double_buffer_note": (
            (
                "single-chip: NO collective to overlap (psum is a no-op), "
                "so a ratio < 1.0 is the EXPECTED cost of carrying the "
                "grad-sized bank through the scan, and a >1.0 reading is a "
                "critical-path effect (the stale update decouples from the "
                "current backward), NOT collective overlap — flops_ratio "
                "1.0 certifies no work was eliminated. Enable double "
                "buffering only when a real inter-chip allreduce sits on "
                "the critical path (multi-host DCN); see "
                "docs/benchmarks.md and the structural independence test "
                "in tests/test_optimizer.py"
            )
            if comm.size == 1 else ""
        ),
    }
    if flops_p and flops_b:
        # 1.0 == both programs do the same work; the speedup is schedule,
        # not dead-code elimination.
        out["double_buffer_flops_ratio"] = round(flops_p / flops_b, 4)
    # Adopt the on/off step times as this backend's double_buffering
    # advisory record (the optimizer wrapper warns from it when the
    # flag is enabled where it measures as a loss).
    try:
        from chainermn_tpu import tuning

        key = tuning.decision_key(shape=(comm.size,), dtype="step")
        tuning.record_measurement(
            "double_buffering", key, {"on": buffered, "off": plain},
            spreads={"on": spread_b, "off": spread_p},
        )
        out["double_buffering_selected"] = tuning.choice(
            "double_buffering", ("on", "off"), key
        )
    except Exception as e:
        out["double_buffer_autotune_error"] = (
            f"{type(e).__name__}: {e}"[:120]
        )
    return out


def _bench_overlap(comm, on_accel: bool):
    """ISSUE 3: the reduction-SCHEDULE comparison and the overlap
    hidden-comm fraction, measured (CPU-proxy convention: median-of-n>=3
    + spread — a delta inside the spread is noise).

    Three measurements over one comm-heavy MLP workload (the
    double-buffer bench's shape family):

    1. step time per reduction schedule (flat / two_level / zero, all
       equivalence-tested) — adopted into the tuning cache as this
       topology's ``reduction_schedule`` decision, so the optimizer's
       ``'auto'`` resolves from evidence (provenance reported);
    2. overlap off vs on at the chosen schedule plus a no-collective
       compute-only baseline: ``hidden_comm_fraction`` =
       (plain - overlapped) / (plain - compute_only), clamped to [0,1]
       — the share of the wire the staleness-1 mode hid behind compute;
    3. the eager per-bucket driver
       (:class:`chainermn_tpu.parallel.reduction_schedule.OverlappedBucketReducer`):
       dispatch -> interleaved compute -> collect, with per-bucket wire
       events (dur vs blocked) — the measured fraction lands in the
       trace and is summarized here from the same events
       ``tools/trace_report.py``'s overlap section reads."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu import create_multi_node_optimizer
    from chainermn_tpu.observability import trace as obs_trace
    from chainermn_tpu.parallel.reduction_schedule import (
        DECISION as _SCHED_DECISION,
        OverlappedBucketReducer,
        SCHEDULES,
    )

    width = 2048 if on_accel else 192
    layers = 3
    batch = 8 * comm.size
    steps = 16 if on_accel else 3
    rng = jax.random.PRNGKey(0)
    params = [
        jax.random.normal(jax.random.fold_in(rng, i),
                          (width, width), jnp.float32) * 0.02
        for i in range(layers)
    ]
    x = jax.random.normal(rng, (batch, width), jnp.bfloat16)
    axes = comm.grad_axes
    payload_bytes = sum(p.size * 4 for p in params)

    def time_loop(opt, opt_spec, out_spec):
        def local(params, opt_state, xb):
            def one(carry, _):
                params, opt_state = carry

                def loss_fn(ps):
                    h = xb
                    for w in ps:
                        h = jnp.tanh(h @ w.astype(jnp.bfloat16))
                    return jnp.sum(h.astype(jnp.float32) ** 2)

                grads = jax.grad(loss_fn)(params)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), ()

            (params, opt_state), _ = jax.lax.scan(
                one, (params, opt_state), None, length=steps
            )
            return params

        fn = jax.jit(
            shard_map(local, mesh=comm.mesh,
                      in_specs=(P(), opt_spec, P(axes)),
                      out_specs=out_spec, check_vma=False)
        )
        opt_state = opt.init(params)
        _fetch_scalar(fn(params, opt_state, x)[0][:1, :1])  # compile+warm

        def sample():
            t0 = time.perf_counter()
            _fetch_scalar(fn(params, opt_state, x)[0][:1, :1])
            return (time.perf_counter() - t0) / steps * 1000

        return _repeat_median(sample, 3)

    # --- 1. schedule comparison, adopted as the dispatch decision
    sched_ms: dict = {}
    spreads: dict = {}
    for sched in SCHEDULES:
        opt = create_multi_node_optimizer(
            optax.sgd(1e-3), comm, allreduce_grad_dtype=jnp.bfloat16,
            reduction_schedule=sched,
        )
        med, spread = time_loop(opt, opt.opt_state_spec(), P())
        sched_ms[sched] = round(med, 3)
        spreads[sched] = spread
    out = {
        "overlap_schedule_ms": sched_ms,
        "overlap_schedule_spread_pct": max(spreads.values()),
        # Key material for offline seeding (tuning.cache must rebuild
        # the exact decision key the 'auto' resolution will ask for).
        "overlap_world_shape": [int(v) for v in comm.mesh.shape.values()],
        "overlap_payload_mb": max(1, payload_bytes >> 20),
    }
    selected = "flat"
    try:
        from chainermn_tpu import tuning

        key = tuning.decision_key(
            shape=tuple(int(v) for v in comm.mesh.shape.values())
            + (max(1, payload_bytes >> 20),),
            dtype="sched",
        )
        tuning.record_measurement(
            _SCHED_DECISION, key, sched_ms, spreads=spreads
        )
        selected = tuning.choice(_SCHED_DECISION, SCHEDULES, key)
        out["reduction_schedule_selected"] = selected
        rec = [d for d in tuning.decisions_taken()
               if d["name"] == _SCHED_DECISION and d["key"] == key]
        if rec:
            out["reduction_schedule_source"] = rec[-1]["source"]
    except Exception as e:
        out["overlap_autotune_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- 2. hidden-comm fraction: compute-only vs plain vs overlapped.
    # Compute-only runs the inner optimizer on UN-reduced grads (per-
    # shard params returned sharded — identical FLOPs, zero collective).
    compute_ms, sp_c = time_loop(optax.sgd(1e-3), P(), P(axes))
    plain_opt = create_multi_node_optimizer(
        optax.sgd(1e-3), comm, allreduce_grad_dtype=jnp.bfloat16,
        reduction_schedule=selected,
    )
    # opt_state_spec(), not P(): a 'zero' winner carries sharded state.
    plain_ms, sp_p = time_loop(plain_opt, plain_opt.opt_state_spec(), P())
    db_ms, sp_d = time_loop(
        create_multi_node_optimizer(
            optax.sgd(1e-3), comm, allreduce_grad_dtype=jnp.bfloat16,
            reduction_schedule=(None if selected == "zero" else selected),
            double_buffering=True,
        ), P(), P(),
    )
    out.update({
        "overlap_compute_ms": round(compute_ms, 3),
        "overlap_plain_ms": round(plain_ms, 3),
        "overlap_db_ms": round(db_ms, 3),
        "overlap_spread_pct": max(sp_c, sp_p, sp_d, max(spreads.values())),
    })
    comm_ms = plain_ms - compute_ms
    if comm_ms > 0.01 * plain_ms:
        out["hidden_comm_fraction"] = round(
            min(1.0, max(0.0, (plain_ms - db_ms) / comm_ms)), 3
        )
    else:
        # No resolvable wire cost at this scale (single chip / loopback
        # noise floor): there is nothing to hide, report 0 honestly.
        out["hidden_comm_fraction"] = 0.0
        out["overlap_note"] = (
            "comm time below the measurement floor "
            f"({comm_ms:.3f} ms of {plain_ms:.3f} ms step) — no wire to "
            "hide on this topology; fraction reported as 0"
        )

    # --- 3. eager per-bucket overlap: real dispatch/collect timestamps
    # feeding the SAME wire-event contract trace_report's overlap
    # section summarizes.
    try:
        per_rank = (1 << 20) if on_accel else (1 << 14)
        gtree = {
            f"g{i}": jnp.full((comm.size, per_rank), float(i + 1),
                              jnp.float32)
            for i in range(3)
        }
        red = OverlappedBucketReducer(
            comm, bucket_bytes=per_rank * 4 * 2,  # ~2 leaves per bucket
        )
        busy = jax.jit(lambda a: jnp.tanh(a @ a.transpose()).sum())
        # Warm round: compiles the bucket collectives and the busy work —
        # its wire events carry compile time, so the measured round's
        # events are summarized separately below.
        red.dispatch(gtree)
        _fetch_scalar(busy(x.astype(jnp.float32)))
        red.collect()
        rec_ = obs_trace.active()
        n_before = len(rec_.events) if rec_ is not None else 0
        n_buckets = red.dispatch(gtree)
        overlap_work = busy(x.astype(jnp.float32))  # rides behind the wire
        mean = red.collect()
        _fetch_scalar(overlap_work)
        ok = all(
            abs(_fetch_scalar(mean[f"g{i}"][:1]) - (i + 1)) < 1e-5
            for i in range(3)
        )
        out["overlap_eager_buckets"] = n_buckets
        out["overlap_eager_mean_ok"] = bool(ok)
        if rec_ is not None:
            ov = obs_trace.summarize_overlap(rec_.events[n_before:])
            if ov and "measured" in ov:
                out["overlap_wire_hidden_fraction"] = (
                    ov["measured"]["hidden_fraction"]
                )
                out["overlap_wire_comm_ms"] = ov["measured"]["comm_ms_total"]
    except Exception as e:
        out["overlap_eager_error"] = f"{type(e).__name__}: {e}"[:160]
    return out


def _bench_composed(comm, on_accel: bool):
    """ISSUE 12: the derived-composition sweep — the mesh re-factored
    THREE-LEVEL (8 devices -> 2x2x2, the north-star multi-slice
    rehearsal a flat or 2-axis bench cannot stand in for) and every
    composition the deriver generates for it timed through the standard
    optimizer path (CPU-proxy convention: median-of-n>=3 + spread — a
    delta inside ``composed_spread_pct`` is noise).

    Rows are keyed by COMPOSITION SIGNATURE (the registry's spelling):
    the menu's ``flat``/``two_level`` appear as their derived instances
    (``ar(a0+a1+a2)`` / ``rs(a2)>ar(a0+a1)>ag(a2)``), so the
    best-vs-``two_level`` ratio on the compact line prices exactly what
    the composition layer buys beyond the old menu. The medians are
    adopted into the tuning cache as this 3-level world shape's
    ``reduction_schedule`` decision (spread-gated, carried-blob aware —
    ``tuning seed`` learns the same rows offline)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from chainermn_tpu import create_multi_node_optimizer
    from chainermn_tpu.communicators.xla_communicator import XlaCommunicator
    from chainermn_tpu.parallel.composition import (
        canonical_axis_names,
        derive_compositions,
        normalize_schedule_name,
        schedule_candidates,
        two_level_composition,
    )
    from chainermn_tpu.parallel.mesh import best_mesh_shape
    from chainermn_tpu.parallel.reduction_schedule import (
        DECISION as _SCHED_DECISION,
    )

    devices = list(comm.mesh.devices.flat)
    shape = best_mesh_shape(len(devices), 3)
    names = canonical_axis_names(3)
    comm3 = XlaCommunicator(
        mesh=Mesh(np.array(devices).reshape(shape), names)
    )
    axes = comm3.grad_axes

    width = 1536 if on_accel else 128
    layers = 2
    batch = 8 * comm3.size
    steps = 16 if on_accel else 2
    rng = jax.random.PRNGKey(0)
    params = [
        jax.random.normal(jax.random.fold_in(rng, i),
                          (width, width), jnp.float32) * 0.02
        for i in range(layers)
    ]
    x = jax.random.normal(rng, (batch, width), jnp.bfloat16)
    payload_bytes = sum(p.size * 4 for p in params)

    def time_loop(opt):
        def local(params, opt_state, xb):
            def one(carry, _):
                params, opt_state = carry

                def loss_fn(ps):
                    h = xb
                    for w in ps:
                        h = jnp.tanh(h @ w.astype(jnp.bfloat16))
                    return jnp.sum(h.astype(jnp.float32) ** 2)

                grads = jax.grad(loss_fn)(params)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
                return (params, opt_state), ()

            (params, opt_state), _ = jax.lax.scan(
                one, (params, opt_state), None, length=steps
            )
            return params

        fn = jax.jit(
            shard_map(local, mesh=comm3.mesh,
                      in_specs=(P(), opt.opt_state_spec(), P(axes)),
                      out_specs=P(), check_vma=False)
        )
        opt_state = opt.init(params)
        _fetch_scalar(fn(params, opt_state, x)[0][:1, :1])  # compile+warm

        def sample():
            t0 = time.perf_counter()
            _fetch_scalar(fn(params, opt_state, x)[0][:1, :1])
            return (time.perf_counter() - t0) / steps * 1000

        return _repeat_median(sample, 3)

    # --- cost-model schedule search (ISSUE 16): rank the derived grid
    # with the α-β model fitted from the PRIOR capture's rows and
    # measure only the top-k (+ the two_level ratio baseline) instead
    # of every arm. Degrades loudly: no prior rows for this mesh shape
    # -> forced:uncalibrated exhaustive sweep; model error past the
    # spread gate after measuring -> the skipped arms are measured
    # after all (exhaustive fallback, provenance says why). Skipped
    # arms are always logged WITH their predicted prices — no silent
    # coverage loss.
    from chainermn_tpu.parallel import cost_model as _cm

    payload_mb = max(1, payload_bytes >> 20)
    cands = [c.signature() for c in derive_compositions(names)]
    two_level_sig = two_level_composition(names).signature()
    model = _cm.load_from_bench_details(
        _DETAILS_PATH, world_shape=shape)
    search_mode = "topk"
    search_source = None
    try:
        from chainermn_tpu import tuning as _tuning_q

        key_q = _tuning_q.decision_key(
            shape=tuple(int(d) for d in shape) + (payload_mb,),
            dtype="search",
        )
        search_mode = _tuning_q.choice(
            "sched_search", ("topk", "exhaustive"), key_q)
        rec_q = [d for d in _tuning_q.decisions_taken()
                 if d["name"] == "sched_search" and d["key"] == key_q]
        if rec_q:
            search_source = rec_q[-1]["source"]
    except Exception:
        pass
    rank = _cm.rank_compositions(
        model, cands, payload_bytes, k=3, mode=search_mode)

    sched_ms: dict = {}
    spreads: dict = {}

    def _measure_arm(sig):
        opt = create_multi_node_optimizer(
            optax.sgd(1e-3), comm3, allreduce_grad_dtype=jnp.bfloat16,
            reduction_schedule=sig,
        )
        med, spread = time_loop(opt)
        sched_ms[sig] = round(med, 3)
        spreads[sig] = spread

    for sig in rank.measured:
        _measure_arm(sig)
    if two_level_sig not in sched_ms:
        _measure_arm(two_level_sig)  # the ratio baseline, always timed
    err_pct = _cm.model_error_pct(rank.predicted_ms, sched_ms)
    provenance = rank.provenance
    if (rank.mode == "topk" and err_pct is not None
            and err_pct > max(spreads.values())):
        # the model disagreed with the wall clock past the noise gate:
        # its ranking cannot be trusted to have skipped only losers —
        # measure everything, say why.
        provenance = (f"exhaustive:model_err {err_pct}% > spread "
                      f"{round(max(spreads.values()), 3)}%")
        for sig in rank.skipped:
            if sig not in sched_ms:
                _measure_arm(sig)
        err_pct = _cm.model_error_pct(rank.predicted_ms, sched_ms)
    searched_mode = ("topk" if len(sched_ms) < len(cands)
                     else "exhaustive")
    skipped = [s for s in rank.order if s not in sched_ms]
    best_sig = min(sched_ms, key=sched_ms.get)
    out = {
        "composed_schedule_ms": sched_ms,
        "composed_spread_pct": max(spreads.values()),
        "composed_world_shape": [int(d) for d in shape],
        "composed_payload_mb": payload_mb,
        "composed_best": best_sig,
        # what composing beyond the menu buys: the best derived
        # pipeline's speedup over the menu's two_level on this
        # 3-level factoring (>1 = a composition the menu could not
        # express wins; judge it against composed_spread_pct).
        "composed_best_vs_two_level": round(
            sched_ms[two_level_sig] / max(sched_ms[best_sig], 1e-9), 3
        ),
        "sched_search_selected": searched_mode,
        "sched_search_provenance": provenance,
        "sched_search_skipped": skipped,
    }
    if search_source:
        out["sched_search_source"] = search_source
    if rank.predicted_ms:
        out["sched_search_predicted_ms"] = rank.predicted_ms
    if err_pct is not None:
        out["cost_model_err_pct"] = err_pct
    if model is not None:
        out["cost_model_fit"] = {
            "source": model.source,
            "fit_err_pct": model.fit_err_pct,
            "n_rows": len(model.fit_rows),
        }
    import dataclasses as _dc_mod

    _cm.emit_sched_search_event(
        _dc_mod.replace(rank, mode=searched_mode, provenance=provenance),
        sched_ms, spread_pct=max(spreads.values()))
    try:
        from chainermn_tpu import tuning

        key = tuning.decision_key(
            shape=tuple(int(d) for d in shape)
            + (max(1, payload_bytes >> 20),),
            dtype="sched",
        )
        # Adopt under the registry's candidate SPELLING: the flat /
        # two_level derived instances go in by menu name (a signature
        # winner the candidate list excludes would be silently
        # discarded at choice() time), novel pipelines by signature.
        adopt_ms = {normalize_schedule_name(s, 3): v
                    for s, v in sched_ms.items()}
        adopt_spreads = {normalize_schedule_name(s, 3): v
                         for s, v in spreads.items()}
        # every top-k adoption carries the model audit as evidence —
        # the winner row records how far the predictions that chose
        # the measured set sat from the wall clock (ISSUE 16).
        audit = {"sched_search": provenance}
        if err_pct is not None:
            audit["cost_model_err_pct"] = err_pct
        tuning.record_measurement(
            _SCHED_DECISION, key, adopt_ms, spreads=adopt_spreads,
            extra_evidence=audit,
        )
        selected = tuning.choice(
            _SCHED_DECISION, schedule_candidates(3), key
        )
        out["composed_selected"] = selected
        rec = [d for d in tuning.decisions_taken()
               if d["name"] == _SCHED_DECISION and d["key"] == key]
        if rec:
            out["composed_schedule_source"] = rec[-1]["source"]
    except Exception as e:
        out["composed_autotune_error"] = f"{type(e).__name__}: {e}"[:120]

    # --- sliced arms (ISSUE 15): the hierarchical two_level instance
    # re-timed at comp_slices ∈ {1,2,4,8} — slice i's slow ar(a0+a1)
    # rides concurrently with slice i+1's fast rs/ag(a2), S× the
    # per-stage collectives at 1/S payload. Same CPU-proxy convention
    # (n>=3 medians + spread) and the same spread-gated adoption into
    # the ``comp_slices`` decision ``tuning seed`` learns offline from
    # these exact rows — offline and live must agree (the PR 14
    # adapter_impl lesson). The arm's key spelling is the slice count.
    try:
        from chainermn_tpu.parallel.composition import sliced_composition
        from chainermn_tpu.parallel.reduction_schedule import (
            SLICES_DECISION as _SLICES_DECISION,
            SLICE_CANDIDATES as _SLICE_CANDIDATES,
        )

        base_comp = two_level_composition(names)
        sliced_ms: dict = {}
        sliced_spreads: dict = {}
        sliced_pred: dict = {}
        for s in _SLICE_CANDIDATES:
            sig_s = (base_comp.signature() if s == "1" else
                     sliced_composition(base_comp, int(s)).signature())
            opt = create_multi_node_optimizer(
                optax.sgd(1e-3), comm3,
                allreduce_grad_dtype=jnp.bfloat16,
                reduction_schedule=sig_s,
            )
            med, spread = time_loop(opt)
            sliced_ms[s] = round(med, 3)
            sliced_spreads[s] = spread
            if model is not None:
                # the model prices sliced variants too (critical-path
                # ticks) — logged beside the measurement as its audit
                sliced_pred[s] = round(
                    model.predict(sig_s, payload_bytes), 3)
        out["composed_sliced_ms"] = sliced_ms
        out["composed_sliced_spread_pct"] = round(
            max(sliced_spreads.values()), 3)
        if sliced_pred:
            out["composed_sliced_predicted_ms"] = sliced_pred
        from chainermn_tpu import tuning

        key_s = tuning.decision_key(
            shape=tuple(int(d) for d in shape)
            + (max(1, payload_bytes >> 20),),
            dtype="slices",
        )
        tuning.record_measurement(
            _SLICES_DECISION, key_s, sliced_ms, spreads=sliced_spreads
        )
        out["composed_slices_selected"] = int(tuning.choice(
            _SLICES_DECISION, _SLICE_CANDIDATES, key_s
        ))
        rec_s = [d for d in tuning.decisions_taken()
                 if d["name"] == _SLICES_DECISION and d["key"] == key_s]
        if rec_s:
            out["composed_slices_source"] = rec_s[-1]["source"]
    except Exception as e:
        out["composed_sliced_error"] = f"{type(e).__name__}: {e}"[:120]
    return out


def _bench_plan(comm, on_accel: bool):
    """ISSUE 10: hand-wired vs plan-compiled train step (CPU-proxy
    convention: median-of-n>=3 + spread — a delta inside the spread is
    noise).

    One comm-heavy MLP workload, identical semantics both ways — ZeRO
    data parallelism over every device (reduce-scatter -> 1/n sharded
    update -> all-gather, adamw inner):

    - hand-wired: ``make_train_step`` + ``MultiNodeOptimizer(
      reduction_schedule='zero')`` over the communicator (the
      call-site-wrapper composition this repo shipped in PR 3);
    - plan: ``ParallelPlan({'zero': n})`` compiling the same step
      global-view through the spec providers, donation on.

    The ratio is the refactor's price tag (expected ~1.0x: same
    collectives, pinned structurally in tests/test_plan.py); both rows
    land in the compact line as ``plan_vs_handwired`` + spread."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import PartitionSpec as P

    from chainermn_tpu.optimizers import create_multi_node_optimizer
    from chainermn_tpu.parallel.plan import ParallelPlan
    from chainermn_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    width = 1024 if on_accel else 128
    layers = 3
    n = comm.size
    batch = 8 * n
    steps = 16 if on_accel else 4
    rng = jax.random.PRNGKey(0)
    params = {
        f"w{i}": jax.random.normal(jax.random.fold_in(rng, i),
                                   (width, width), jnp.float32) * 0.02
        for i in range(layers)
    }
    x = jax.random.normal(rng, (batch, width), jnp.float32)
    y = jnp.zeros((batch,), jnp.int32)

    def loss_fn(p, batch_):
        xb, yb = batch_
        h = xb
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return optax.softmax_cross_entropy_with_integer_labels(
            h[:, :16], yb
        ).mean()

    inner = optax.adamw(1e-3)

    def time_steps(step, state):
        # Two warm calls: the hand-wired path's eager-built state has
        # uncommitted shardings, so its SECOND call (committed outputs)
        # compiles a fresh signature — the plan path stays at one
        # compile because create_train_state places the state sharded.
        state, m = step(state, (x, y))
        state, m = step(state, (x, y))
        _fetch_scalar(m["loss"])

        def sample():
            nonlocal state
            t0 = time.perf_counter()
            for _ in range(steps):
                state, m = step(state, (x, y))
            _fetch_scalar(m["loss"])
            return (time.perf_counter() - t0) / steps * 1000

        med, spread = _repeat_median(sample, 1 if on_accel else 3)
        return med, spread, state

    opt = create_multi_node_optimizer(inner, comm,
                                      reduction_schedule="zero")
    # Copy: the donating hand-wired step would otherwise delete the
    # shared template params the plan state is built from below.
    hand_state = create_train_state(
        jax.tree.map(lambda p: jnp.array(p, copy=True), params), opt, comm
    )
    hand_step = make_train_step(loss_fn, opt, comm)
    hand_ms, hand_spread, _ = time_steps(hand_step, hand_state)

    devices = list(comm.mesh.devices.flat)
    plan = ParallelPlan({"zero": n}, devices=devices)
    plan_state = plan.create_train_state(params, inner)
    plan_step = plan.compile_train_step(loss_fn, inner, params)
    plan_ms, plan_spread, _ = time_steps(plan_step, plan_state)

    out = {
        "plan_step_ms": round(plan_ms, 3),
        "plan_handwired_ms": round(hand_ms, 3),
        "plan_vs_handwired": round(hand_ms / plan_ms, 3),
        "plan_spread_pct": max(hand_spread, plan_spread),
        "plan_mesh": plan.describe()["mesh"],
        "plan_compiles": plan_step.cache_size()
        if hasattr(plan_step, "cache_size") else None,
    }
    return out


def _bench_seq_parallel(comm, on_accel: bool):
    """ISSUE 13: the sequence axis, priced twice (CPU-proxy convention:
    median-of-n>=3 + spread — a delta inside ``seq_parallel_spread_pct``
    is noise; on-accel rows are single samples and the offline seeder
    applies the 10% floor):

    1. TRAINING — one ``data x seq`` plan-compiled Transformer step per
       ``seq_attn_impl`` candidate (ring's n-1 ppermutes/layer vs
       Ulysses' all_to_all reshard), adopted as this
       shards x heads x T shape's ``seq_attn_impl`` decision;
    2. SERVING — long-prompt TTFT through a TP engine at 1/2/4 model
       shards, monolithic vs sequence-parallel prefill at the top shard
       count, adopted (spread-gated) as this model shape's
       ``prefill_seq_parallel`` decision — the number that decides
       whether the wide-prefill/narrow-decode split finally earns
       ``cluster_disagg`` its hop.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.parallel.plan import ParallelPlan
    from chainermn_tpu.parallel.plan_specs import SEQ_ATTN_IMPLS
    from chainermn_tpu.serving import ServingEngine, serving_decision_key

    devices = list(comm.mesh.devices.flat)
    n_seq = min(4, len(devices) // 2) or 1
    if on_accel:
        layers, d_model, heads, d_ff = 4, 512, 8, 2048
        vocab, T, batch = 32000, 2048, 2 * (len(devices) // n_seq or 1)
        dtype = jnp.bfloat16
        steps = 8
    else:
        layers, d_model, heads, d_ff = 2, 64, 4, 128
        vocab, T, batch = 256, 64, 4
        dtype = jnp.float32
        steps = 2
    t_local = T // n_seq

    # --- 1. training: ring vs ulysses through the ONE plan step
    plan = ParallelPlan(
        {"data": len(devices) // n_seq, "seq": n_seq}, devices=devices
    )
    tok = jax.random.randint(jax.random.PRNGKey(0), (batch, T), 0, vocab)
    import optax

    inner = optax.sgd(1e-3)
    attn_ms: dict = {}
    attn_spreads: dict = {}
    lm_kw = dict(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        d_model=d_model, d_ff=d_ff, max_len=T, compute_dtype=dtype,
        pos_encoding="rope", return_hidden=True,
    )
    # init through the attention-free twin: the ring/ulysses locals
    # need the mesh axis context the init trace does not have
    params = {"params": jax.jit(
        functools.partial(TransformerLM(**lm_kw).init, train=False)
    )(jax.random.PRNGKey(1), tok[:1, :8])["params"]}
    for impl in SEQ_ATTN_IMPLS:
        if impl == "ulysses" and heads % n_seq:
            continue  # forced-ring shape: nothing to compare
        attn_fn, _rec = plan.seq_attention(
            heads=heads, t_local=t_local, impl=impl
        )
        model = TransformerLM(**lm_kw, attention_fn=attn_fn)

        def loss_fn(p, batch_):
            pos = ParallelPlan.seq_local_positions(batch_.shape[1])
            h = model.apply({"params": p["params"]}, batch_,
                            positions=pos, train=False)
            return jnp.mean(h.astype(jnp.float32) ** 2)

        state = plan.create_train_state(params, inner)
        step = plan.compile_train_step(loss_fn, inner, params)
        state, m = step(state, tok)  # compile + warm
        _fetch_scalar(m["loss"])

        def sample():
            nonlocal state
            t0 = time.perf_counter()
            for _ in range(steps):
                state, m = step(state, tok)
            _fetch_scalar(m["loss"])
            return (time.perf_counter() - t0) / steps * 1000

        med, spread = _repeat_median(sample, 1 if on_accel else 3)
        attn_ms[impl] = round(med, 3)
        attn_spreads[impl] = spread
    out = {
        "seq_parallel_attn_ms": attn_ms,
        # T here is the LOCAL shard length — the seq_attn_impl decision
        # key's T-bucket (the plan's seq_attention and the offline
        # seeder must rebuild the same key).
        "seq_parallel_attn_shape": f"S{n_seq}xH{heads}xT{t_local}",
        "seq_parallel_shards": n_seq,
    }
    if not on_accel and attn_spreads:
        out["seq_parallel_attn_spread_pct"] = max(attn_spreads.values())

    try:
        from chainermn_tpu import tuning

        if len(attn_ms) > 1:
            akey = tuning.decision_key(
                shape=(n_seq, heads, t_local), dtype="seqattn"
            )
            tuning.record_measurement(
                "seq_attn_impl", akey, attn_ms,
                spreads=None if on_accel else attn_spreads,
            )
            out["seq_parallel_attn_selected"] = tuning.choice(
                "seq_attn_impl", SEQ_ATTN_IMPLS, akey
            )
    except Exception as e:
        out["seq_parallel_attn_autotune_error"] = (
            f"{type(e).__name__}: {e}"[:120])

    # --- 2. serving: long-prompt TTFT, monolithic vs seq-parallel
    if on_accel:
        s_layers, s_dm, s_heads, s_dff = 4, 512, 8, 2048
        s_vocab, s_maxlen, prompt_len, gen = 32000, 2048, 1500, 4
        s_dtype = jnp.bfloat16
    else:
        s_layers, s_dm, s_heads, s_dff = 2, 64, 4, 128
        s_vocab, s_maxlen, prompt_len, gen = 256, 64, 40, 2
        s_dtype = jnp.float32
    s_model = TransformerLM(
        vocab_size=s_vocab, num_layers=s_layers, num_heads=s_heads,
        d_model=s_dm, d_ff=s_dff, max_len=s_maxlen,
        compute_dtype=s_dtype,
    )
    s_params = jax.jit(
        functools.partial(s_model.init, train=False)
    )(jax.random.PRNGKey(2), jnp.zeros((1, 8), jnp.int32))
    rs = np.random.RandomState(11)
    prompt = rs.randint(1, s_vocab, size=prompt_len).tolist()

    def ttft_median(shards, seq_parallel):
        mesh = Mesh(np.array(devices[:shards]), ("model",))
        engine = ServingEngine(
            s_model, s_params, num_slots=2, max_len=s_maxlen,
            decode_impl="paged", kv_block_size="auto",
            prefill_buckets=(s_maxlen,), mesh=mesh,
            prefill_seq_parallel="on" if seq_parallel else "off",
        )

        def once():
            t0 = time.perf_counter()
            res = engine.prefill_join(prompt)
            jax.block_until_ready(jax.tree.leaves(engine._cache)[0])
            dt = (time.perf_counter() - t0) * 1000
            assert res is not None
            engine.leave(res[0])
            return dt

        once()  # compile + warm
        return _repeat_median(once, 1 if on_accel else 3)

    ttft_by_shards: dict = {}
    ttft_spreads: dict = {}
    top = None
    for shards in (1, 2, 4):
        if shards > len(devices) or s_heads % shards:
            continue
        kvh = s_heads  # MHA here; GQA shapes gate on kv heads too
        if shards > 1 and kvh % shards:
            continue
        med, spread = ttft_median(shards, seq_parallel=shards > 1)
        ttft_by_shards[str(shards)] = round(med, 4)
        ttft_spreads[str(shards)] = spread
        top = shards
    out["seq_parallel_ttft_shards_ms"] = ttft_by_shards
    out["seq_parallel_model_shape"] = f"D{s_dm}xH{s_heads}xL{s_maxlen}"
    if top and top > 1:
        # the decision's candidates, measured at the TOP shard count:
        # 'off' = the TP monolithic prefill on the SAME mesh (isolates
        # the sharded forward from the TP speedup itself)
        med_off, spread_off = ttft_median(top, seq_parallel=False)
        ttft_ms = {"off": round(med_off, 4),
                   "on": ttft_by_shards[str(top)]}
        sp = {"off": spread_off, "on": ttft_spreads[str(top)]}
        out["seq_parallel_ttft_ms"] = ttft_ms
        if not on_accel:
            out["seq_parallel_spread_pct"] = max(sp.values())
        if ttft_ms["on"]:
            out["seq_parallel_ttft_speedup"] = round(
                ttft_ms["off"] / ttft_ms["on"], 3
            )
        try:
            from chainermn_tpu import tuning

            key = serving_decision_key(s_dm, s_heads, s_maxlen)
            tuning.record_measurement(
                "prefill_seq_parallel", key, ttft_ms,
                spreads=None if on_accel else sp,
            )
            out["seq_parallel_selected"] = tuning.choice(
                "prefill_seq_parallel", ("off", "on"), key
            )
        except Exception as e:
            out["seq_parallel_autotune_error"] = (
                f"{type(e).__name__}: {e}"[:120])
    if not on_accel:
        out["seq_parallel_note"] = (
            "CPU-proxy honest floor: tiny LM, loopback ppermutes — the "
            "ring-vs-ulysses and off-vs-on rankings hold for THIS "
            "backend; absolute ms is not chip latency"
        )
    return out


def _bench_allreduce(comm, n_elems: int = 100_000_000):
    """The reference's ``allreduce_grad`` GB/s microbenchmark (BASELINE.json
    tracked metric): achieved bytes/s of a jitted psum over a flat bf16
    gradient-sized buffer — the fused equivalent of
    ``pure_nccl_communicator.py`` (dagger)'s pack -> ncclAllReduce path.

    Matches ``allreduce_grad`` semantics: every device holds the FULL
    ``n_elems`` gradient buffer. The buffer is made device-distinct (axis
    index added) inside the program so XLA cannot simplify the all-reduce
    of a replicated value into a local multiply."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = comm.mesh
    axes = comm.grad_axes
    axes_tuple = axes if isinstance(axes, tuple) else (axes,)
    n = comm.size
    dtype = jnp.bfloat16
    buf = jnp.ones((n_elems,), dtype)

    # Enough rounds to amortise the end-of-run scalar fetch out of the
    # per-iteration figure.
    iters = 50

    def local(x):
        # Iterations chained INSIDE one program: per-dispatch host latency
        # must not pollute a bandwidth measurement. Each round's input depends on the previous psum, so
        # the collectives execute serially on-device.
        salt = sum(jax.lax.axis_index(a) for a in axes_tuple)

        def body(b, _):
            red = jax.lax.psum(b + salt.astype(b.dtype), axes)
            return (red * 0.5).astype(b.dtype), ()

        out, _ = jax.lax.scan(body, x, None, length=iters)
        return out

    fn = jax.jit(
        shard_map(local, mesh=mesh, in_specs=P(), out_specs=P(),
                  check_vma=False)
    )
    _fetch_scalar(fn(buf)[:1])  # compile + warm
    t0 = time.perf_counter()
    _fetch_scalar(fn(buf)[:1])  # true sync: host transfer, not block_until_ready
    dt = (time.perf_counter() - t0) / iters
    nbytes = n_elems * buf.dtype.itemsize
    # Algorithm bandwidth (bytes through the reduction per second). With
    # n devices a ring moves 2(n-1)/n * nbytes per device; report both.
    algbw = nbytes / dt
    busbw = algbw * (2 * (n - 1) / n) if n > 1 else algbw
    return {
        "allreduce_gbps": round(algbw / 1e9, 2),
        "allreduce_busbw_gbps": round(busbw / 1e9, 2),
        "allreduce_elems": n_elems,
        "allreduce_dtype": "bfloat16",
    }


def _bench_allreduce_curve(comm, on_accel: bool):
    """busbw-vs-message-size curve (round-4 VERDICT item 6, the BASELINE
    ``allreduce_grad GB/s`` metric's missing depth): jitted psum at
    1 MiB -> 512 MiB, bf16 and f32, fused single-buffer vs ~64 MiB
    bucketed (the TwoDimensionalCommunicator's packing discipline).
    Single-chip rows measure loopback reduction throughput; the shape of
    the curve (latency-bound small messages -> bandwidth-bound plateau)
    is the evidence the scaling model's bucket-size choice rests on."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = comm.mesh
    axes = comm.grad_axes
    axes_tuple = axes if isinstance(axes, tuple) else (axes,)
    n = comm.size
    bucket_elems_bf16 = 32 << 20  # 64 MiB of bf16

    if not on_accel:
        # Tiny sizes keep the CPU proxy fast; shrink the bucket too so
        # the bucketed row is a REAL multi-psum program, not a relabelled
        # copy of the fused one.
        bucket_elems_bf16 = 1 << 16

    cases = ([
        (1 << 19, jnp.bfloat16, "fused", 100),   # 1 MiB
        (1 << 23, jnp.bfloat16, "fused", 50),    # 16 MiB
        (1 << 26, jnp.bfloat16, "fused", 20),    # 128 MiB
        (1 << 28, jnp.bfloat16, "fused", 8),     # 512 MiB
        (1 << 28, jnp.bfloat16, "bucketed", 8),
        (1 << 26, jnp.float32, "fused", 20),     # 256 MiB f32
    ] if on_accel else [
        (1 << 16, jnp.bfloat16, "fused", 10),
        (1 << 18, jnp.bfloat16, "fused", 5),
        (1 << 18, jnp.bfloat16, "bucketed", 5),
    ])
    if n > 1:
        # The quantized wire only exists on a real multi-member axis
        # (size-1 short-circuits to the exact value by design).
        cases.append(
            (1 << 26, jnp.float32, "int8", 20) if on_accel
            else (1 << 18, jnp.float32, "int8", 5)
        )

    rows = []
    for n_elems, dtype, mode_, iters in cases:
        buf = jnp.ones((n_elems,), dtype)
        n_buckets = (max(1, n_elems // bucket_elems_bf16)
                     if mode_ == "bucketed" else 1)

        def local(x, n_buckets=n_buckets, mode=mode_):
            salt = sum(jax.lax.axis_index(a) for a in axes_tuple)

            def body(b, _):
                if mode == "int8":
                    from chainermn_tpu.parallel.collectives import (
                        int8_allreduce_mean,
                    )

                    red = int8_allreduce_mean(
                        b + salt.astype(b.dtype), axes_tuple
                    )
                elif n_buckets == 1:
                    red = jax.lax.psum(b + salt.astype(b.dtype), axes)
                else:
                    parts = jnp.split(b + salt.astype(b.dtype), n_buckets)
                    red = jnp.concatenate(
                        [jax.lax.psum(p, axes) for p in parts]
                    )
                return (red * 0.5).astype(b.dtype), ()

            out, _ = jax.lax.scan(body, x, None, length=iters)
            return out

        fn = jax.jit(shard_map(local, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=False))
        try:
            _fetch_scalar(fn(buf)[:1])  # compile + warm
            t0 = time.perf_counter()
            _fetch_scalar(fn(buf)[:1])
            dt = (time.perf_counter() - t0) / iters
        except Exception as e:
            rows.append({
                "mib": round(n_elems * jnp.dtype(dtype).itemsize / 2**20,
                             3),
                "dtype": jnp.dtype(dtype).name, "mode": mode_,
                "error": f"{type(e).__name__}: {e}"[:160],
            })
            continue
        nbytes = n_elems * jnp.dtype(dtype).itemsize
        algbw = nbytes / dt  # logical (pre-compression) bytes reduced/s
        # Bus bandwidth from the bytes that PHYSICALLY cross the wire:
        # ring allreduce moves 2(n-1)/n * itemsize per element; the int8
        # scheme moves ~2(n-1)/n * 1 byte regardless of logical dtype
        # (all_to_all int8 chunks + int8 all-gather; scales negligible).
        wire_itemsize = 1 if mode_ == "int8" else jnp.dtype(dtype).itemsize
        wire_bytes = n_elems * wire_itemsize
        busbw = (wire_bytes / dt) * (2 * (n - 1) / n) if n > 1 \
            else wire_bytes / dt
        rows.append({
            "mib": round(nbytes / 2**20, 3),
            "dtype": jnp.dtype(dtype).name,
            "mode": mode_,
            "n_buckets": n_buckets,
            "ms": round(dt * 1e3, 3),
            "algbw_gbps": round(algbw / 1e9, 2),
            "busbw_gbps": round(busbw / 1e9, 2),
        })
    out = {"allreduce_curve": rows}
    # Adopt the curve as this topology's wire decision: best busbw per
    # wire variant (bf16 fused vs the int8 two-phase wire), higher
    # wins. The bucket-size decision keeps its ~64 MB table default
    # unless the bucketed row is decisively slower than fused.
    try:
        from chainermn_tpu import tuning

        best = {}
        for row in rows:
            if "busbw_gbps" not in row:
                continue
            wire = ("int8" if row.get("mode") == "int8"
                    else {"bfloat16": "bf16", "float32": "f32"}.get(
                        row.get("dtype")))
            if wire:
                best[wire] = max(best.get(wire, 0.0), row["busbw_gbps"])
        # n > 1 only: at one device there IS no wire, and a dtype
        # "comparison" would adopt loopback-bandwidth noise.
        if len(best) > 1 and comm.size > 1:
            key = tuning.decision_key(shape=(comm.size,), dtype="grad")
            tuning.record_measurement(
                "allreduce_wire", key, best, higher_is_better=True,
            )
            out["allreduce_wire_selected"] = tuning.choice(
                "allreduce_wire", ("f32", "bf16", "int8"), key
            )
    except Exception as e:
        out["allreduce_wire_autotune_error"] = (
            f"{type(e).__name__}: {e}"[:120]
        )
    return out


def _bench_kernel_sweep(on_accel: bool):
    """On-chip Pallas kernel compile/perf sweep (round-4 VERDICT item 7):
    every flash-attention variant class — causal, banded sliding window
    (even AND odd widths: the even case regressed once), GQA, packed
    segments, unequal q/k lengths (the SP extended-K shape), fwd and
    fwd+bwd — jitted, run, and timed on the REAL chip, so a Mosaic
    layout rejection shows up in the driver artifact instead of waiting
    for someone to hand-drive the chip (CPU interpret mode accepts
    layouts Mosaic rejects — CLAUDE.md kernel convention)."""
    if not on_accel:
        return {"kernel_sweep": "skipped on CPU (interpret mode cannot "
                                "catch Mosaic layout rejections)"}
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.attention import dot_product_attention
    from chainermn_tpu.ops.flash_attention import flash_attention

    B, T, H, D = 2, 2048, 8, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.bfloat16)
    kv2 = jax.random.normal(ks[1], (B, T, 2, D), jnp.bfloat16)
    seg = (jnp.arange(T)[None, :] // 512).astype(jnp.int32).repeat(B, 0)
    k_long = jax.random.normal(ks[2], (B, 3072, H, D), jnp.bfloat16)

    # Sliding-window reference: the materialised comparator has no window
    # arg, but an additive band bias reproduces it exactly.
    def band_bias(W):
        qpos = jnp.arange(T)[:, None]
        kpos = jnp.arange(T)[None, :]
        return jnp.where(
            qpos - kpos < W, 0.0, -1e9
        )[None, None, :, :].astype(jnp.float32)

    # Numerics references for the fwd variants: compile/run alone cannot
    # catch a SILENTLY wrong Mosaic schedule (e.g. a misdeclared parallel
    # grid dim) — compare each flash output against the materialised
    # reference on the chip itself. bf16 accumulate-order differences sit
    # well under the 0.05 gate; a scheduling bug blows past it.
    numerics = {
        "causal_fwd": (
            lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=True, interpret=False),
            lambda q_, k_, v_: dot_product_attention(
                q_, k_, v_, causal=True),
        ),
        "window_odd_fwd": (
            lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=True, window=1023, interpret=False),
            lambda q_, k_, v_: dot_product_attention(
                q_, k_, v_, causal=True, bias=band_bias(1023)),
        ),
        "segments_fwd": (
            lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=True, segment_ids=seg, interpret=False),
            lambda q_, k_, v_: dot_product_attention(
                q_, k_, v_, causal=True, segment_ids=seg),
        ),
        "gqa4_fwdbwd": (
            lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=True, interpret=False),
            lambda q_, k_, v_: dot_product_attention(
                q_, k_, v_, causal=True),
        ),
        "cross_len_fwd": (
            lambda q_, k_, v_: flash_attention(
                q_, k_, v_, causal=False, interpret=False),
            lambda q_, k_, v_: dot_product_attention(q_, k_, v_),
        ),
    }

    def fwd(fn):
        def f(*a):
            return jnp.sum(fn(*a).astype(jnp.float32))
        return f

    def fwdbwd(fn):
        def f(*a):
            return jnp.sum(fn(*a).astype(jnp.float32))
        # argnums=(0,1,2), NOT 0: grad wrt q alone needs only the dq
        # kernel — the dkv kernel would be dead-code-eliminated and
        # never face Mosaic (the gap that let the dkv segment specs go
        # unchecked until r5).
        return jax.grad(f, argnums=(0, 1, 2))

    variants = [
        ("causal_fwd", fwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False)), (q, q, q)),
        ("causal_fwdbwd", fwdbwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False)), (q, q, q)),
        ("window_even_fwdbwd", fwdbwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=1024, interpret=False)),
         (q, q, q)),
        ("window_odd_fwd", fwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=1023, interpret=False)),
         (q, q, q)),
        ("gqa4_fwdbwd", fwdbwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False)), (q, kv2, kv2)),
        ("segments_fwd", fwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, segment_ids=seg, interpret=False)),
         (q, q, q)),
        ("segments_fwdbwd", fwdbwd(lambda q, k, v: flash_attention(
            q, k, v, causal=True, segment_ids=seg, interpret=False)),
         (q, q, q)),
        ("cross_len_fwd", fwd(lambda q, k, v: flash_attention(
            q, k, v, causal=False, interpret=False)), (q, k_long, k_long)),
    ]

    # The sliding-window SP entry (round-4 grid-collapse fix changed this
    # geometry): flash_block_fwd with an ODD extended-K length (even
    # window), q_offset=prefix, wrap-sentinel kv ids, tile-padded by the
    # SAME helper the SP path uses — the exact shape Mosaic must accept.
    from chainermn_tpu.parallel.local_attention import (
        _WRAP_SENTINEL,
        _pad_ext_to_block,
    )
    from chainermn_tpu.ops.flash_attention import flash_block_fwd

    W = 1024
    tail = W - 1
    k_pre, v_pre = q[:, -tail:], q[:, -tail:]
    k_ext = jnp.concatenate([k_pre, q], axis=1)  # odd length T + W - 1
    v_ext = jnp.concatenate([v_pre, q], axis=1)
    seg_q = jnp.zeros((B, T), jnp.int32)
    seg_k = jnp.concatenate(
        [jnp.full((B, tail), _WRAP_SENTINEL, jnp.int32), seg_q], axis=1
    )
    k_ext, v_ext, seg_k = _pad_ext_to_block(k_ext, v_ext, seg_k, 1024)

    def sp_ext(qq, kk, vv):
        out, _ = flash_block_fwd(
            qq, kk, vv, causal=True, scale=D**-0.5, window=W,
            q_offset=tail, seg_q=seg_q, seg_kv=seg_k,
            block_q=512, block_k=1024, interpret=False,
        )
        return jnp.sum(out.astype(jnp.float32))

    variants.append(("sp_window_ext_fwd", sp_ext, (q, k_ext, v_ext)))

    from chainermn_tpu.ops.flash_attention import flash_block_bwd

    def sp_ext_bwd(qq, kk, vv):
        # The SP ring's backward entry with the same extended-K banded
        # geometry: lse/delta derived from the fwd, do = ones. Compiles
        # the dq AND dkv kernels with wrap-sentinel segment ids.
        out, lse = flash_block_fwd(
            qq, kk, vv, causal=True, scale=D**-0.5, window=W,
            q_offset=tail, seg_q=seg_q, seg_kv=seg_k,
            block_q=512, block_k=1024, interpret=False,
        )
        do = jnp.ones_like(out)
        delta = jnp.sum(
            (do * out).astype(jnp.float32), axis=-1
        ).transpose(0, 2, 1)
        dq, dk, dv = flash_block_bwd(
            qq, kk, vv, do, lse, delta, causal=True, scale=D**-0.5,
            window=W, q_offset=tail, seg_q=seg_q, seg_kv=seg_k,
            block_q=512, block_k=1024, interpret=False,
        )
        return (jnp.sum(dq.astype(jnp.float32))
                + jnp.sum(dk.astype(jnp.float32))
                + jnp.sum(dv.astype(jnp.float32)))

    variants.append(("sp_window_ext_bwd", sp_ext_bwd, (q, k_ext, v_ext)))

    rows = []
    for name, fn, args in variants:
        row = {"kernel": name}
        try:
            jf = jax.jit(fn)
            out = jf(*args)
            _fetch_scalar(jax.tree.leaves(out)[0].ravel()[:1])
            t0 = time.perf_counter()
            for _ in range(3):
                out = jf(*args)
            _fetch_scalar(jax.tree.leaves(out)[0].ravel()[:1])
            row["ms"] = round((time.perf_counter() - t0) / 3 * 1e3, 2)
            row["ok"] = True
            if name in numerics:
                try:
                    flash_t, ref_t = numerics[name]
                    fa = jax.jit(
                        lambda *a, _f=flash_t: _f(*a).astype(jnp.float32)
                    )(*args)
                    rf = jax.jit(
                        lambda *a, _r=ref_t: _r(*a).astype(jnp.float32)
                    )(*args)
                    err = jnp.max(jnp.abs(fa - rf))
                    den = jnp.max(jnp.abs(rf)) + 1e-6
                    row["rel_err"] = round(_fetch_scalar(err / den), 5)
                    row["numerics_ok"] = row["rel_err"] < 0.05
                except Exception as e:
                    row["numerics_error"] = f"{type(e).__name__}: {e}"[:120]
        except Exception as e:
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {e}"[:160]
        rows.append(row)
    return {"kernel_sweep": rows, **_kernel_sweep_counts(rows)}


def _kernel_sweep_counts(rows) -> dict:
    """Compact-line counts for the sweep rows. A CRASHED numerics
    checker is not 0 numeric failures: rows whose checker raised
    (``numerics_error`` set, so ``numerics_ok`` is absent and the
    failure count can't see them) get their own
    ``kernel_sweep_numeric_errors`` key, so the numerics gate cannot be
    satisfied by the checker erroring out (ADVICE r5)."""
    return {
        "kernel_sweep_failures": sum(1 for r in rows if not r["ok"]),
        "kernel_sweep_numeric_failures": sum(
            1 for r in rows if not r.get("numerics_ok", True)
        ),
        "kernel_sweep_numeric_errors": sum(
            1 for r in rows if "numerics_error" in r
        ),
    }


def _run_bench(mode: str) -> dict:
    """Run every phase in this process and return the result rows.
    ``mode='accel'`` refuses a CPU; ``mode='cpu'`` is the toy-shape
    proxy."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu import create_communicator
    from chainermn_tpu.observability import trace as obs_trace

    trace_path = _TRACE_PATH
    try:
        obs_trace.enable(trace_path, meta={"source": "bench", "mode": mode})
    except OSError:
        trace_path = None
    # Live metrics plane (ISSUE 6): the recorder tap aggregates every
    # wire/step/serving event this run emits into the registry; the
    # snapshot lands in BENCH_DETAILS.json at the end, so each bench
    # artifact carries the rolled-up counter/histogram view beside the
    # raw trace.
    try:
        from chainermn_tpu.observability import metrics as obs_metrics

        obs_metrics.install_tap()
    except Exception:
        obs_metrics = None

    devices = jax.devices()
    on_accel = devices[0].platform != "cpu"
    if mode == "accel" and not on_accel:
        raise RuntimeError(
            "bench.py measures on an accelerator and JAX found only "
            f"platform {devices[0].platform!r} "
            f"({devices[0].device_kind}); the toy-shape CPU proxy is "
            "`python bench.py --run cpu`"
        )
    if mode == "cpu":
        # The proxy runs its toy shapes whatever device is present.
        on_accel = False
    comm = create_communicator("xla")

    # One tiny eager 'auto'-wire gradient allreduce through a separate
    # communicator: every emitted trace then carries a REAL collective
    # event whose wire dtype was resolved by the autotune registry, with
    # the decision's provenance attached (ISSUE 2 acceptance). The
    # headline workloads keep their explicit bf16 wire — this demo never
    # touches their configuration.
    auto_demo_err = None
    try:
        auto_comm = create_communicator("xla", allreduce_grad_dtype="auto")
        auto_comm.allreduce_grad(
            {"g": jnp.ones((auto_comm.size, 4), jnp.float32)}
        )
        del auto_comm
    except Exception as e:
        # Record (never raise): the demo exists so the trace carries an
        # auto-provenance event — losing it silently would let a broken
        # provenance path masquerade as "no auto sites ran".
        auto_demo_err = f"{type(e).__name__}: {e}"[:160]

    steps, warmup = (20, 3) if on_accel else (5, 1)
    step, state, (x, y), batch, metric, knob_fields = _resnet_setup(
        comm, on_accel
    )

    # AOT-compile once; reuse the executable for the timing loops and pull
    # XLA's own FLOP count (of the per-device partitioned module) for MFU.
    step = step.lower(state, (x, y)).compile()
    analysis = step.cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0]
    step_flops = float((analysis or {}).get("flops", 0.0)) or None

    # MFU keeps the MODEL-flops convention: under remat, cost_analysis
    # of the compiled step counts recompute as work, so pull the flops
    # from a remat-free compile of the same workload instead (one extra
    # AOT compile, only on the non-default path — same convention as
    # examples/imagenet/sweep_mfu.py). The probe's duplicate state is
    # deleted before the timed region so it cannot occupy HBM during
    # the measurement it calibrates.
    if knob_fields.get("resnet_remat", "none") != "none":
        try:
            step0, state0, batch0, _, _, _ = _resnet_setup(
                comm, on_accel, force_remat="none"
            )
            compiled0 = step0.lower(state0, batch0).compile()
            a0 = compiled0.cost_analysis()
            a0 = a0[0] if isinstance(a0, (list, tuple)) else a0
            model_flops = float(a0.get("flops", 0.0)) or None
            del step0, state0, batch0, compiled0
            if model_flops:
                step_flops = model_flops
                knob_fields["mfu_note"] = (
                    "model flops from the remat-free program; recompute "
                    "counted as price, not useful work"
                )
        except Exception as e:
            knob_fields["mfu_note"] = (
                f"remat-free flops compile failed ({type(e).__name__}); "
                "mfu uses compiled-step flops INCLUDING recompute"
            )

    for _ in range(warmup):
        state, metrics = step(state, (x, y))
    _fetch_scalar(metrics["loss"])

    # Steps chain through `state`; the loss fetch at the end forces the
    # device to have executed every step (true sync — see _fetch_scalar).
    def sample():
        nonlocal state, metrics
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, (x, y))
        _fetch_scalar(metrics["loss"])
        return time.perf_counter() - t0

    dt, headline_spread = _repeat_median(sample, 1 if on_accel else 3)

    images_per_sec = batch * steps / dt
    per_device = images_per_sec / comm.size
    vs_baseline = per_device / BASELINE_IMG_PER_SEC_PER_DEVICE

    out = {
        "metric": metric,
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(vs_baseline, 3),
        "step_time_ms": round(dt / steps * 1e3, 2),
        "device_kind": devices[0].device_kind,
        "n_devices": comm.size,
        "baseline_note": (
            "vs_baseline compares per-device img/s to the unverified "
            "125 img/s/P100 ChainerMN-era figure (different hardware); "
            "mfu is the hardware-honest metric"
        ),
        **knob_fields,
    }
    if auto_demo_err:
        out["trace_auto_demo_error"] = auto_demo_err
    if not on_accel:
        out["proxy_spread_pct"] = headline_spread
    peak = _peak_flops(devices[0].device_kind)
    if step_flops and peak:
        # cost_analysis() describes the per-device SPMD-partitioned module,
        # so compare against a single chip's peak.
        achieved = step_flops / (dt / steps)
        out["mfu"] = round(achieved / peak, 4)
        out["per_device_step_tflops"] = round(step_flops / 1e12, 3)

    # Emit the primary number NOW — if the supplementary benchmark below
    # stalls past the parent's budget, this line is what gets salvaged.
    print(json.dumps(out), flush=True)

    def supp(name: str, err_key: str, fn) -> None:
        """One supplementary phase: exception-isolated (a failed phase
        does not lose the others' rows; its ``err_key`` makes the run
        exit non-zero — ``_failed_phases``), cumulative line after each,
        and a span in the observability trace so the per-phase wall time
        is in the artifact, not just the log ordering. The span sits
        INSIDE the try so a failed phase records ok=False — catching
        inside the span would stamp every failure ok=True."""
        try:
            with obs_trace.span(f"bench:{name}"):
                out.update(fn())
        except Exception as e:
            out[err_key] = f"{type(e).__name__}: {e}"[:200]
        print(json.dumps(out), flush=True)

    supp("allreduce", "allreduce_error",
         lambda: _bench_allreduce(
             comm, 100_000_000 if on_accel else 10_000_000))
    supp("allreduce_curve", "allreduce_curve_error",
         lambda: _bench_allreduce_curve(comm, on_accel))
    supp("attention", "attn_error", lambda: _bench_attention(on_accel))
    # Early on purpose (round-4 VERDICT item 7): a Mosaic layout
    # rejection must reach the artifact even if the budget cuts the
    # expensive transformer/native phases below.
    supp("kernel_sweep", "kernel_sweep_error",
         lambda: _bench_kernel_sweep(on_accel))
    supp("double_buffer", "double_buffer_error",
         lambda: _bench_double_buffering(comm, on_accel))
    supp("overlap", "overlap_error",
         lambda: _bench_overlap(comm, on_accel))
    supp("composed", "composed_error",
         lambda: _bench_composed(comm, on_accel))
    supp("plan", "plan_error",
         lambda: _bench_plan(comm, on_accel))
    supp("seq_parallel", "seq_parallel_error",
         lambda: _bench_seq_parallel(comm, on_accel))
    supp("transformer", "transformer_error",
         lambda: _bench_transformer(comm, on_accel))
    supp("s2d_resnet", "s2d_error", lambda: _bench_s2d_resnet(comm, on_accel))
    supp("moe_dispatch", "moe_dispatch_error",
         lambda: _bench_moe_dispatch(on_accel))
    supp("moe", "moe_error",
         lambda: _bench_moe_plan(comm, on_accel))
    supp("serving", "serving_error",
         lambda: _bench_serving(comm, on_accel))
    supp("serving_prefix", "serving_prefix_error",
         lambda: _bench_serving_prefix(comm, on_accel))
    supp("serving_cluster", "serving_cluster_error",
         lambda: _bench_serving_cluster(comm, on_accel))
    supp("serving_burst", "serving_burst_error",
         lambda: _bench_serving_burst(comm, on_accel))
    supp("serving_sampled", "serving_sampled_error",
         lambda: _bench_serving_sampled(comm, on_accel))
    supp("serving_decode_kernel", "serving_decode_kernel_error",
         lambda: _bench_serving_decode_kernel(comm, on_accel))
    supp("serving_tenants", "serving_tenants_error",
         lambda: _bench_serving_tenants(comm, on_accel))
    supp("native_input", "native_input_error",
         lambda: _bench_native_input(comm, on_accel))

    # Dispatch provenance: every decision the autotune registry
    # resolved during this run (full trail in the artifact, a compact
    # name=winner(source) summary on the driver line) — each capture
    # shows which path every tuned site took and why.
    try:
        from chainermn_tpu import tuning

        out["autotune_decisions"] = tuning.decisions_taken()
        out["autotune"] = tuning.decisions_summary(max_len=160)
    except Exception as e:
        out["autotune_error"] = f"{type(e).__name__}: {e}"[:120]
    if trace_path is not None:
        out["trace"] = trace_path
        rec = obs_trace.active()
        if rec is not None:
            rec.flush()
    # Metrics snapshot (ISSUE 6): counters/gauges + streaming histogram
    # quantiles over the whole run — full blob to BENCH_DETAILS.json
    # only (the compact stdout line keeps its whitelist).
    if obs_metrics is not None:
        try:
            reg = obs_metrics.active_registry()
            if reg is not None:
                out["metrics_snapshot"] = reg.snapshot()
        except Exception as e:
            out["metrics_snapshot_error"] = f"{type(e).__name__}: {e}"[:120]
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    from chainermn_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    if len(sys.argv) >= 3 and sys.argv[1] == "--run":
        if sys.argv[2] == "native-loop":
            _run_native_loop()
        else:
            sys.exit(1 if _failed_phases(_run_bench(sys.argv[2])) else 0)
    else:
        sys.exit(main())
