#!/usr/bin/env python
"""Roofline byte audit for the bench workloads (round-5 VERDICT ask #3).

The ResNet roofline (docs/benchmarks.md) was grounded in two numbers per
config: XLA ``cost_analysis`` FLOPs and the compiled module's byte
traffic — this tool produces the same pair for the TRANSFORMER bench
step (and, for cross-checking, the ResNet one), so the MFU targets are
mechanistic instead of aspirational.

Usage::

    python tools/byte_audit.py transformer [--remat dots|nothing|none]
        [--batch 16] [--chunks 16]
    python tools/byte_audit.py resnet [--remat none|conv|full] [--batch 128]
    python tools/byte_audit.py decode [--live-frac 0.5]
    python tools/byte_audit.py moe

Prints one JSON object: per-step FLOPs, XLA "bytes accessed" (post-fusion
HBM traffic estimate of the partitioned module), peak/temp memory from
``memory_analysis``, and the derived compute/bandwidth floors for the
device (or the v5e reference numbers when compiling on CPU — the compile
is backend-honest for FLOPs; bytes-accessed on CPU reflects CPU fusion
and is labelled as such).

The bench's own workload definitions are reused (``bench._resnet_setup``
and the same transformer construction as ``bench._bench_transformer``)
so the audit cannot drift from what the bench times.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _HERE)


def _note(msg: str) -> None:
    """Progress trail on stderr, flushed: the audit's slowest phase is
    an AOT ``.compile()`` of the full train step, and a run killed at
    its time limit should say which phase it was in."""
    print(f"[audit] {msg}", file=sys.stderr, flush=True)

V5E_PEAK_FLOPS = 197e12  # bf16
V5E_HBM_GBPS = 819e9


def _device_peaks() -> tuple[float, float, str]:
    """(flops, hbm_bytes_per_s, label) for the actual device, from
    bench's adjacent per-kind tables (one matcher, one place to add a
    kind; a kind the tables do not know raises there). A CPU compile
    gets the v5e reference numbers, labelled as such."""
    import jax

    import bench

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return V5E_PEAK_FLOPS, V5E_HBM_GBPS, "v5e (reference; CPU compile)"
    kind = dev.device_kind
    return (bench._peak_lookup(kind, bench._PEAK_BF16_FLOPS),
            bench._peak_lookup(kind, bench._PEAK_HBM_BYTES), kind)


def _analyses(compiled) -> dict:
    out: dict = {}
    try:
        a = compiled.cost_analysis()
        a = a[0] if isinstance(a, (list, tuple)) else a
        out["flops"] = float(a.get("flops", 0.0))
        out["bytes_accessed"] = float(a.get("bytes accessed", 0.0))
    except Exception as e:
        out["cost_analysis_error"] = f"{type(e).__name__}: {e}"[:160]
    try:
        m = compiled.memory_analysis()
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(m, k, None)
            if v is not None:
                out[k] = int(v)
    except Exception as e:
        out["memory_analysis_error"] = f"{type(e).__name__}: {e}"[:160]
    return out


def _floors(rec: dict, steps_in_program: int) -> None:
    """Derive per-step floors against the ACTUAL device's peaks (per
    bench's kind table); the v5e reference numbers, labelled as such,
    when the kind is unknown or the compile ran on CPU."""
    import jax

    peak_flops, peak_hbm, label = _device_peaks()
    on_tpu = jax.devices()[0].platform == "tpu"
    rec["device_kind"] = jax.devices()[0].device_kind
    rec["floors_vs"] = label
    flops = rec.get("flops")
    nbytes = rec.get("bytes_accessed")
    if flops:
        rec["flops_per_step"] = flops / steps_in_program
        rec["compute_floor_ms"] = round(
            flops / steps_in_program / peak_flops * 1e3, 1)
    if nbytes:
        rec["bytes_per_step"] = nbytes / steps_in_program
        rec["bandwidth_floor_ms"] = round(
            nbytes / steps_in_program / peak_hbm * 1e3, 1)
        if not on_tpu:
            rec["bytes_note"] = (
                "bytes accessed from the CPU-compiled module: CPU fusion "
                "differs from TPU; treat as an upper-ish bound and "
                "re-audit on chip"
            )


def _seq_ring_bytes(model, B: int, T: int, n: int) -> dict:
    """The seq-axis ring's wire-byte accounting for this workload at
    ``n`` sequence shards (ISSUE 13): per hop the unrolled plan ring
    (``seq_ring_attention_local``) moves the stacked (K, V) pair of one
    shard's slice — ``2 * B * T/n * kv_heads * head_dim`` elements — as
    ONE collective-permute; a forward pass is ``n-1`` hops per layer,
    the backward ``(n-1) + n`` (kv ring + the travelling dk/dv
    accumulator). These bytes cross the ICI neighbour links, NOT HBM,
    so they are reported as roofline INPUTS (floor them against the
    device's ICI bandwidth when sizing a mesh), not folded into the
    HBM floors above."""
    import numpy as np

    kv_heads = model.num_kv_heads or model.num_heads
    head_dim = model.d_model // model.num_heads
    try:
        itemsize = np.dtype(model.compute_dtype).itemsize
    except TypeError:
        itemsize = 2  # bfloat16: not a numpy dtype, 2 wire bytes
    per_hop = 2 * B * (T // n) * kv_heads * head_dim * itemsize
    layers = model.num_layers
    return {
        "shards": n,
        "per_hop_kv_bytes": per_hop,
        "hops_per_layer_fwd": n - 1,
        "hops_per_layer_bwd": 2 * n - 1,
        "ring_bytes_per_step": per_hop * (3 * n - 2) * layers,
        "plane": "ici (neighbour exchange; not an HBM floor)",
    }


def audit_transformer(remat: str, batch: int, chunks: int) -> dict:
    """AOT-compile the LM-scale bench transformer step — the VERY
    workload ``bench._bench_transformer`` times, via the shared
    ``bench._transformer_setup`` (knobs flow through the same
    CHAINERMN_BENCH_TF_* env surface the bench and capture script use),
    with one scan step in the program so per-step numbers need no
    trip-count division."""
    import jax

    import bench

    from chainermn_tpu import create_communicator

    os.environ["CHAINERMN_BENCH_TF_REMAT"] = remat
    os.environ["CHAINERMN_BENCH_TF_BATCH"] = str(batch)
    os.environ["CHAINERMN_BENCH_TF_CHUNKS"] = str(chunks)
    comm = create_communicator("xla")
    on_tpu = jax.devices()[0].platform == "tpu"
    _note(f"transformer: tracing step (backend={jax.devices()[0].platform})")
    (fn, (params, opt_state, tokens), B, T, _steps, model, cfg, _kf,
     _nc) = bench._transformer_setup(
        comm, on_accel=True, steps=1, interpret=not on_tpu,
        abstract_params=True)
    lowered = fn.lower(params, opt_state, tokens)
    _note("transformer: lowered; compiling")
    compiled = lowered.compile()
    _note("transformer: compiled; running analyses")
    rec = {"workload": "transformer",
           "config": f"{cfg} B{B}xT{T} remat={remat} chunks={chunks}",
           "cost_analysis_note": (
               "the bench step body sits inside lax.scan (and the fused "
               "LM head scans over chunks); XLA cost_analysis does not "
               "multiply through scan regions (see bench.py's MFU note), "
               "so flops/bytes_accessed under-count — "
               "model_flops_per_step is the grounded compute number"
           )}
    rec.update(_analyses(compiled))
    _floors(rec, steps_in_program=1)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    rec["params_m"] = round(n_params / 1e6, 1)
    # The bench's MODEL-flops convention (6P/token + causal attention),
    # for MFU-target math independent of remat recompute.
    peak_flops, _, _ = _device_peaks()
    # Per DEVICE (cost_analysis also describes the per-device
    # partitioned module) — same division as the bench's MFU.
    model_flops = (
        6 * n_params + 6 * model.num_layers * T * model.d_model
    ) * B * T / comm.size
    rec["model_flops_per_step"] = model_flops
    rec["model_compute_floor_ms"] = round(
        model_flops / peak_flops * 1e3, 1)
    # ISSUE 13: the seq-axis ring's per-hop K/V wire bytes for THIS
    # workload — the ICI-side roofline input for long-context sharding.
    n_seq = int(os.environ.get("CHAINERMN_AUDIT_SEQ_SHARDS", "4"))
    if n_seq > 1 and T % n_seq == 0:
        rec["seq_ring"] = _seq_ring_bytes(model, B, T, n_seq)
    return rec


def _moe_a2a_bytes(*, tokens_local: int, d_model: int, n_shards: int,
                   eps: int, k: int, capacity_factor, itemsize: int,
                   n_layers: int) -> dict:
    """The expert axis's all_to_all wire accounting (ISSUE 20) — pure
    shape arithmetic, no compile, backend-independent.

    Each shard assembles queues ``[E_global, capacity, d_model]`` for
    its local tokens and ships the off-shard ``(n-1)/n`` fraction per
    ``all_to_all``; dispatch + combine = exactly 2 per MoE layer on the
    forward (pinned structurally in tests/test_moe.py), 3 on
    forward+backward (XLA merges one backward transpose into a forward
    a2a). ``capacity`` is the drop/pad knob: padded slots cross the
    wire as zeros — the ``pad_fraction`` row prices what a tighter
    capacity factor would save. These bytes cross ICI, not HBM, so they
    are roofline INPUTS (floor them against the device's a2a
    bandwidth), not folded into the HBM floors."""
    from chainermn_tpu.parallel.moe import moe_capacity

    e_global = n_shards * eps
    capacity = moe_capacity(tokens_local, e_global, k, capacity_factor)
    queue_bytes = e_global * capacity * d_model * itemsize
    wire = queue_bytes * (n_shards - 1) // max(1, n_shards)
    slots = e_global * capacity
    pad_fraction = max(0, slots - tokens_local * k) / max(1, slots)
    return {
        "shards": n_shards,
        "experts": e_global,
        "experts_per_shard": eps,
        "capacity": capacity,
        "queue_bytes_per_shard": queue_bytes,
        "wire_bytes_per_a2a": wire,
        "a2a_per_layer_fwd": 2,
        "a2a_per_layer_fwd_bwd": 3,
        "dispatch_combine_wire_bytes_fwd": 2 * wire * n_layers,
        "dispatch_combine_wire_bytes_fwd_bwd": 3 * wire * n_layers,
        "pad_fraction": round(pad_fraction, 4),
        "plane": "ici (all_to_all; not an HBM floor)",
    }


def audit_moe() -> dict:
    """ISSUE 20: roofline the expert axis's dispatch/combine wire.

    Structural side only — the a2a byte model needs no compile (the
    arithmetic mirrors ``moe_layer_local``'s queue shapes exactly), so
    the same rows are honest on CPU and on chip. Audited at the bench
    ``moe`` phase's CPU-proxy shape AND at its accel shape (the
    on-chip roofline target), with a serving-decode row for the
    ownership-split TP MoE tick (per-slot rows, no-drop capacity)."""
    import jax

    rec = {"workload": "moe", "plane": "ici"}
    # bench._bench_moe_plan's shape convention: CPU proxy vs accel.
    rec["train_proxy"] = dict(
        config="T128xE8xD64 f32 expert4xdata2 (bench CPU-proxy shape)",
        **_moe_a2a_bytes(tokens_local=64, d_model=64, n_shards=4,
                         eps=2, k=1, capacity_factor=1.25,
                         itemsize=4, n_layers=1))
    rec["train_accel"] = dict(
        config="T512xE8xD256 f32 expert4xdata2 (bench accel shape, "
               "8-chip mesh)",
        **_moe_a2a_bytes(tokens_local=256, d_model=256, n_shards=4,
                         eps=2, k=1, capacity_factor=1.25,
                         itemsize=4, n_layers=1))
    # Serving decode tick (engine ownership split over the TP mesh):
    # own_rows slots per shard, no-drop capacity, bf16 activations at
    # the accel serving shape (bench._bench_serving's convention).
    rec["serving_decode_accel"] = dict(
        config="slots=16 tp=4 E8 D512 bf16 no-drop (serving accel "
               "shape)",
        **_moe_a2a_bytes(tokens_local=4, d_model=512, n_shards=4,
                         eps=2, k=1, capacity_factor=None,
                         itemsize=2, n_layers=4))
    rec["device_kind"] = jax.devices()[0].device_kind
    rec["itemsize_note"] = (
        "train rows price float32 queues (the bench moe phase's "
        "dtype); serving row prices the engine's bf16 compute dtype"
    )
    return rec


def audit_resnet(remat: str, batch: int) -> dict:
    import bench

    from chainermn_tpu import create_communicator

    os.environ["CHAINERMN_BENCH_RESNET_BATCH"] = str(batch)
    comm = create_communicator("xla")
    import jax

    # Always audit the ACCEL workload (ResNet-50 at the bench batch):
    # the audit exists to ground the on-chip MFU target, and the FLOPs
    # side is backend-honest even when the compile runs on CPU (the
    # resnet step has no Pallas kernels, so a CPU compile is legal).
    step, state, (x, y), b, _, _ = bench._resnet_setup(
        comm, True, force_remat=remat)
    rec = {"workload": "resnet50", "config": f"b{b} remat={remat}"}
    try:
        _note(f"resnet: lowering (backend={jax.devices()[0].platform})")
        lowered = step.lower(state, (x, y))
        _note("resnet: lowered; compiling")
        compiled = lowered.compile()
        _note("resnet: compiled; running analyses")
        rec.update(_analyses(compiled))
        _floors(rec, steps_in_program=1)
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    return rec


def _decode_attend_models(*, slots: int, max_len: int, bs: int,
                          heads: int, kv_heads: int, head_dim: int,
                          itemsize: int, live_frac: float) -> dict:
    """Structural per-tick HBM byte models for the three paged-decode
    attend stories (ISSUE 19) — pure shape arithmetic, no compile, so
    the accounting is backend-independent:

    - ``floor``: ONE live-KV read (every live (token, kv-head) element
      of K and V touched exactly once) + the q read and o write. No
      attend that looks at the whole live history can read less.
    - ``fused``: the kernel's actual traffic — live blocks once per
      kv-head slice (grid ``(B, Hkv, M)``, block ``(1, bs, 1, D)``),
      PLUS one redirect block per (slot, head) (dead grid cells aim
      their DMA at a fixed block; Pallas skips refetching an unchanged
      index, so the dead tail costs O(1) reads, not O(M)), PLUS the
      sublane-padded q/o rows (``R_pad >= 8``).
    - ``xla_gather``: the dense-view story — ``pool[tables]`` reads the
      FULL table width regardless of liveness, materializes the view
      (write + attend read-back), and the masked fp32 scores make an
      HBM round-trip. Horizon-priced by construction: its bytes do not
      shrink when the history is short.

    ``live_frac`` sets the live history length (fraction of
    ``max_len``) for the floor/fused side; ``*_full`` rows price the
    full-horizon case where even the fused kernel must read every
    block. Ratios land in docs/benchmarks.md next to the measured
    serving_decode_kernel rows."""
    group = heads // kv_heads
    r_pad = max(8, -(-group // 8) * 8)  # T=1 decode tick rows
    q_bytes = slots * kv_heads * r_pad * head_dim * itemsize
    o_bytes = q_bytes
    qo_floor = 2 * slots * heads * head_dim * itemsize  # unpadded
    m_total = -(-max_len // bs)
    block_bytes = bs * head_dim * itemsize  # one kv-head's slice

    def kv(nblocks):  # K and V, every kv head, nblocks per slot
        return 2 * slots * kv_heads * nblocks * block_bytes

    def story(nblocks):
        floor = kv(nblocks) + qo_floor
        fused = kv(min(nblocks + 1, m_total)) + q_bytes + o_bytes
        xla = (3 * kv(m_total)                      # gather+write+read
               + 2 * slots * heads * m_total * bs * 4   # fp32 scores
               + qo_floor)
        return {
            "floor_bytes": floor, "fused_bytes": fused,
            "xla_gather_bytes": xla,
            "fused_vs_floor_x": round(fused / floor, 2),
            "xla_vs_fused_x": round(xla / fused, 1),
        }

    live = max(1, min(m_total, round(m_total * live_frac)))
    rec = {"live_blocks": live, "total_blocks": m_total,
           "live_frac": live_frac}
    rec.update(story(live))
    rec.update({k + "_full": v for k, v in story(m_total).items()})
    return rec


def audit_decode(live_frac: float) -> dict:
    """ISSUE 19: roofline the paged DECODE tick, xla vs fused.

    Measured side: AOT-compile the serving engine's real decode-step
    program (``_decode_step_jit`` — the very program the bench's
    serving phases time) per ``decode_attend_impl`` at the bench's
    backend shape and run the usual analyses/floors. On CPU the fused
    program compiles the kernel's interpret-mode EMULATION, whose
    bytes describe the emulator, not the kernel — labelled, and the
    reason the structural section exists.

    Structural side: :func:`_decode_attend_models` at the audited
    shape AND at the accel serving shape (the on-chip roofline
    target; arithmetic needs no compile)."""
    import functools

    import jax
    import jax.numpy as jnp

    from chainermn_tpu.models.transformer import TransformerLM
    from chainermn_tpu.serving import ServingEngine

    on_tpu = jax.devices()[0].platform == "tpu"
    # The serving bench's shape convention (bench._bench_serving):
    # accel vs CPU-proxy.
    if on_tpu:
        layers, d_model, heads, d_ff = 4, 512, 8, 2048
        vocab, max_len, slots, bs = 32000, 512, 16, 32
        dtype = jnp.bfloat16
    else:
        layers, d_model, heads, d_ff = 2, 64, 4, 128
        vocab, max_len, slots, bs = 256, 64, 4, 8
        dtype = jnp.float32
    model = TransformerLM(
        vocab_size=vocab, num_layers=layers, num_heads=heads,
        d_model=d_model, d_ff=d_ff, max_len=max_len, compute_dtype=dtype,
    )
    _note(f"decode: init params (backend={jax.devices()[0].platform})")
    params = jax.jit(
        functools.partial(model.init, train=False)
    )(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    itemsize = jnp.dtype(dtype).itemsize
    head_dim = d_model // heads
    rec = {
        "workload": "paged_decode",
        "config": (f"D{d_model}xH{heads}xL{max_len} slots={slots} "
                   f"bs={bs} layers={layers}"),
        "impls": {},
    }
    for impl in ("xla", "fused"):
        _note(f"decode: compiling decode step (attend={impl})")
        sub: dict = {}
        try:
            eng = ServingEngine(
                model, params, num_slots=slots, max_len=max_len,
                decode_impl="paged", decode_attend_impl=impl,
                kv_block_size=bs, prefill_buckets=(8,), spec_tokens=0,
            )
            args = (
                eng._cache, eng._vars,
                jnp.zeros((slots,), jnp.int32),
                jnp.zeros((slots,), jnp.int32),
                jnp.asarray(eng._dummy_tables()),
                jnp.asarray(eng._seeds),
            )
            compiled = eng._decode_step_jit.lower(*args).compile()
            sub.update(_analyses(compiled))
            _floors(sub, steps_in_program=1)
            if impl == "fused" and not on_tpu:
                sub["bytes_note"] = (
                    "CPU compile runs the kernel's interpret-mode "
                    "emulation: these bytes describe the emulator, not "
                    "the kernel — the structural section below is the "
                    "honest fused number off-chip; re-audit on chip"
                )
        except Exception as e:
            sub["error"] = f"{type(e).__name__}: {e}"[:200]
        rec["impls"][impl] = sub
    _note("decode: structural attend models")
    rec["attend_model"] = _decode_attend_models(
        slots=slots, max_len=max_len, bs=bs, heads=heads,
        kv_heads=heads, head_dim=head_dim, itemsize=itemsize,
        live_frac=live_frac)
    if not on_tpu:
        # The on-chip roofline target, priced by the same arithmetic.
        rec["attend_model_accel_shape"] = dict(
            config="D512xH8xL512 slots=16 bs=32 bf16",
            **_decode_attend_models(
                slots=16, max_len=512, bs=32, heads=8, kv_heads=8,
                head_dim=64, itemsize=2, live_frac=live_frac))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload",
                    choices=["transformer", "resnet", "decode", "moe"])
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--chunks", type=int, default=16)
    ap.add_argument(
        "--seq-shards", type=int, default=4,
        help="seq-axis shard count for the transformer audit's "
             "seq_ring wire-byte rows (ISSUE 13); the ring's per-hop "
             "K/V bytes are ICI-plane roofline inputs")
    ap.add_argument(
        "--live-frac", type=float, default=0.5,
        help="live-history fraction of max_len for the decode audit's "
             "floor/fused attend models (ISSUE 19); the xla dense-view "
             "gather is horizon-priced regardless")
    ap.add_argument(
        "--target", choices=["auto", "cpu"], default="auto",
        help="cpu: pin the CPU backend before first device use "
             "(conftest's recipe) — FLOPs are backend-honest either way "
             "and the process leaves the chip alone; "
             "bytes-accessed is then labelled CPU-fusion")
    args = ap.parse_args()
    if args.target == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    os.environ["CHAINERMN_AUDIT_SEQ_SHARDS"] = str(args.seq_shards)
    if args.workload == "transformer":
        rec = audit_transformer(
            args.remat, args.batch or 16, args.chunks)
    elif args.workload == "decode":
        rec = audit_decode(args.live_frac)
    elif args.workload == "moe":
        rec = audit_moe()
    else:
        rec = audit_resnet(
            args.remat if args.remat != "dots" else "none",
            args.batch or 128)
    print(json.dumps(rec, indent=1))


if __name__ == "__main__":
    main()
