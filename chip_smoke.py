"""Chip smoke: a few LM train steps on the TPU through the library's
front door — the quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process, every chip it sees (the same file is the one-chip and the
four-chip run). It trains the LM the repo runs at full width — 8 layers,
d_model 1024, 16 heads, d_ff 4096, vocab 32000, T 2048, per-chip batch 16,
bf16 compute, per-block remat ``dots``, the fused LM head, the Pallas flash
kernel as ``attention_fn``, random weights from a seed — through
``create_communicator`` → ``create_multi_node_optimizer`` →
``create_train_state`` → ``make_train_step``, and checks what comes out:
finite falling losses, a Mosaic-compiled kernel, and on several chips a
batch shard and live bytes on every device and an all-reduce in the
compiled step. Times are printed as information, not as a metric.

Nothing is caught: any exception or failed check is a non-zero exit. It
exits non-zero, naming what it found, unless JAX's first device is a TPU.
The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import importlib.metadata
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

#: custom-call target of a Mosaic-compiled Pallas kernel in lowered text;
#: an interpreted kernel lowers to plain HLO and leaves none.
_MOSAIC_CALL = "tpu_custom_call"


def check(ok: bool, what: str) -> None:
    """A check that survives ``python -O`` (``assert`` does not)."""
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def train_smoke(
    *,
    num_layers: int = 8,
    d_model: int = 1024,
    num_heads: int = 16,
    d_ff: int = 4096,
    vocab_size: int = 32000,
    seq_len: int = 2048,
    per_chip_batch: int = 16,
    head_chunks: int = 16,
    warm_steps: int = 4,
) -> dict:
    """Train the LM for ``2 + 2 * warm_steps`` steps on every device the
    process sees and return what was observed. Checks what holds on any
    backend (finite falling loss, a shard of the batch on every device, an
    all-reduce when there is more than one); what only a chip can show is
    returned for :func:`main` to check."""
    import chainermn_tpu
    from chainermn_tpu import tuning
    from chainermn_tpu.models import TransformerLM, lm_loss_fused
    from chainermn_tpu.ops.flash_attention import (
        _use_interpret,
        flash_attention,
    )
    from chainermn_tpu.training import make_train_step
    from chainermn_tpu.training.train_step import create_train_state

    comm = chainermn_tpu.create_communicator(
        "xla", allreduce_grad_dtype="bfloat16"
    )
    devices = list(comm.mesh.devices.flat)

    def attn(q, k, v, *, causal, scale):
        # interpret=None: the kernel decides from the backend — compiled
        # on a TPU, interpreted on the CPU test mesh.
        return flash_attention(q, k, v, causal=causal, scale=scale)

    model = TransformerLM(
        vocab_size=vocab_size, num_layers=num_layers, num_heads=num_heads,
        d_model=d_model, d_ff=d_ff, max_len=seq_len,
        compute_dtype=jnp.bfloat16, remat=True, remat_policy="dots",
        return_hidden=True, attention_fn=attn,
    )
    # One fixed batch from a seed: the loss on it must fall.
    tokens = np.random.default_rng(0).integers(
        0, vocab_size, size=(per_chip_batch * comm.size, seq_len),
        dtype=np.int32,
    )
    batch = jax.device_put(
        tokens, NamedSharding(comm.mesh, P(comm.grad_axes))
    )
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.asarray(tokens[:1])
    )["params"]

    def loss_fn(params, tokens):
        hidden = model.apply({"params": params}, tokens)
        return lm_loss_fused(hidden, params["tok_emb"]["embedding"],
                             tokens, n_chunks=head_chunks)

    optimizer = chainermn_tpu.create_multi_node_optimizer(
        optax.adam(3e-4), comm
    )
    state = create_train_state(params, optimizer, comm)
    step = make_train_step(loss_fn, optimizer, comm)

    # Cold: trace, compile (or load from the persistent cache) and run.
    t0 = time.perf_counter()
    lowered = step.lower(state, batch)
    compiled = lowered.compile()
    state, metrics = compiled(state, batch)
    losses = [float(metrics["loss"])]
    cold_s = time.perf_counter() - t0

    state, metrics = compiled(state, batch)  # settle before timing
    losses.append(float(metrics["loss"]))

    # The same N warm steps timed two ways: to block_until_ready, and to
    # a scalar fetched to the host. On a directly attached chip they
    # should agree (ROADMAP S0).
    pending = []
    t0 = time.perf_counter()
    for _ in range(warm_steps):
        state, metrics = compiled(state, batch)
        pending.append(metrics["loss"])
    jax.block_until_ready(state)
    warm_block_ms = (time.perf_counter() - t0) / warm_steps * 1e3
    t0 = time.perf_counter()
    for _ in range(warm_steps):
        state, metrics = compiled(state, batch)
        pending.append(metrics["loss"])
    float(metrics["loss"])  # the host fetch ends this region
    warm_fetch_ms = (time.perf_counter() - t0) / warm_steps * 1e3
    losses += [float(x) for x in pending]

    check(all(np.isfinite(losses)), f"every loss is finite: {losses}")
    check(losses[-1] < losses[0],
          f"the loss fell: {losses[0]} -> {losses[-1]}")

    shard_devices = {s.device for s in batch.addressable_shards}
    check(shard_devices == set(devices),
          "every device holds a shard of the batch")
    check(all(s.data.shape == (per_chip_batch, seq_len)
              for s in batch.addressable_shards),
          "each batch shard is one chip's batch")
    all_reduces = compiled.as_text().count("all-reduce")
    if len(devices) > 1:
        check(all_reduces > 0, "the compiled step contains an all-reduce")

    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    return {
        "model": (f"{num_layers}L d{d_model} h{num_heads} ff{d_ff} "
                  f"v{vocab_size} T{seq_len}, {n_params / 1e6:.1f}M params, "
                  f"per-chip batch {per_chip_batch}, "
                  f"global batch {tokens.shape[0]}"),
        "losses": losses,
        "cold_s": cold_s,
        "warm_block_ms": warm_block_ms,
        "warm_fetch_ms": warm_fetch_ms,
        "kernel_interpreted": _use_interpret(),
        "mosaic_calls": lowered.as_text().count(_MOSAIC_CALL),
        "all_reduces": all_reduces,
        "memory_stats": [d.memory_stats() for d in devices],
        "decisions": tuning.decisions_taken(),
    }


def main() -> int:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found platform: {dev.platform} "
            f"({dev.device_kind} x{len(devices)})",
            file=sys.stderr,
        )
        return 1

    from chainermn_tpu.utils.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    print(f"platform: {dev.platform}")
    print(f"device_kind: {dev.device_kind}")
    print(f"device_count: {len(devices)}")
    print("versions: " + " ".join(
        f"{p}={importlib.metadata.version(p)}"
        for p in ("jax", "jaxlib", "libtpu")
    ))
    print(f"compile_cache: {cache_dir}")

    r = train_smoke()

    print(f"model: {r['model']}")
    print("losses: " + " ".join(f"{x:.4f}" for x in r["losses"]))
    print(f"cold (trace + compile-or-cache-load + first step): "
          f"{r['cold_s']:.1f} s")
    print(f"warm step, to block_until_ready: {r['warm_block_ms']:.1f} ms")
    print(f"warm step, to host scalar fetch: {r['warm_fetch_ms']:.1f} ms")
    print(f"flash kernel: interpreted={r['kernel_interpreted']}, "
          f"{r['mosaic_calls']} Mosaic custom calls in the lowered step")
    print(f"all-reduce ops in the compiled step: {r['all_reduces']}")
    for d, stats in zip(devices, r["memory_stats"]):
        print(f"device {d.id}: bytes_in_use={stats['bytes_in_use']} "
              f"peak_bytes_in_use={stats['peak_bytes_in_use']}")
    print("decisions: " + json.dumps(r["decisions"]))

    check(not r["kernel_interpreted"],
          "the flash kernel is compiled, not interpreted")
    check(r["mosaic_calls"] > 0,
          "the lowered step contains a Mosaic custom call")
    check(all(s["bytes_in_use"] > 0 for s in r["memory_stats"]),
          "every device holds live bytes")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
