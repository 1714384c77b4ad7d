#!/usr/bin/env python
"""Mosaic AOT compile check for EVERY Pallas kernel in the tree.

The repo's standing trap (CLAUDE.md): interpret mode accepts layouts
Mosaic rejects — CPU-green kernels can still be chip-dead. This tool
AOT-lowers each kernel entry point with ``interpret=False`` at
representative on-chip shapes and ``.compile()``s it, so a layout
rejection becomes a named row instead of a surprise mid-run. No kernel is
RUN — compile only, a few seconds each (the progress trail on stderr
names the case being compiled).

Checked kernels:

- flash attention forward (causal, GQA, window variant)
- flash attention backward (dq + dkv kernels, via jax.grad), also at
  ``chip_smoke.py``'s LM shape
- flash attention forward with packed ``segment_ids``, and the
  sequence-parallel window-extension forward (``flash_block_fwd`` with
  an extended, tile-padded K axis, ``q_offset`` and wrap-sentinel
  segment ids) — the two variants Mosaic rejected on 2026-08-01, at the
  shapes it rejected them
- fused paged decode (ISSUE 19): plain tick T=1, verify span T>1,
  window, and the dense-cache wrapper — the ``(1, bs, 1, D)`` KV block
  (second-to-last dim 1 over the kv-head axis) is exactly the kind of
  layout Mosaic might refuse (ROADMAP S4).

Usage (through the chip tool; needs the chip)::

    python tools/kernel_compile_check.py
    python tools/kernel_compile_check.py --json chiprun_out/kernels.json

On CPU every case fails fast with the honest explanation (Mosaic
lowering needs a TPU backend). Exit code: number of failed cases
(0 = all compiled).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _HERE)


def _note(msg: str) -> None:
    print(f"[kernel-check] {msg}", file=sys.stderr, flush=True)


def _cases():
    """(name, thunk) per kernel entry point; each thunk returns a
    lowered-and-compiled executable (discarded — compile IS the test)."""
    import functools

    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.flash_attention import (
        flash_attention,
        flash_block_fwd,
    )
    from chainermn_tpu.ops.paged_decode import (
        dense_flash_decode,
        paged_flash_decode,
    )
    from chainermn_tpu.parallel.local_attention import (
        _WRAP_SENTINEL,
        _pad_ext_to_block,
    )

    dt = jnp.bfloat16
    # Flash at the bench transformer's LM block shape.
    B, T, Hq, Hkv, D = 2, 2048, 8, 4, 64
    q = jax.ShapeDtypeStruct((B, T, Hq, D), dt)
    kv = jax.ShapeDtypeStruct((B, T, Hkv, D), dt)

    def flash(**kw):
        return jax.jit(functools.partial(
            flash_attention, causal=True, interpret=False,
            block_q=512, block_k=1024, **kw))

    def flash_bwd():
        def loss(q_, k_, v_):
            return flash_attention(
                q_, k_, v_, causal=True, interpret=False,
                block_q=512, block_k=1024).astype(jnp.float32).sum()

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    # Flash forward + backward at chip_smoke.py's LM shape (per-chip
    # batch 16, 16 heads of 64, the kernel's default blocks).
    q_lm = jax.ShapeDtypeStruct((16, 2048, 16, 64), dt)

    def flash_lm_fwdbwd(q_, k_, v_):
        return flash_attention(
            q_, k_, v_, causal=True, interpret=False
        ).astype(jnp.float32).sum()

    # The two variants Mosaic rejected, at bench's kernel-sweep shape.
    Bs, Ts, Hs, Ds = 2, 2048, 8, 128
    qs = jax.ShapeDtypeStruct((Bs, Ts, Hs, Ds), dt)
    seg = jax.ShapeDtypeStruct((Bs, Ts), jnp.int32)

    def segments_fwd(q_, k_, v_, seg_):
        return flash_attention(q_, k_, v_, causal=True, segment_ids=seg_,
                               interpret=False)

    W = 1024  # even window: the extended K length Ts + W - 1 is odd
    tail = W - 1

    def sp_window_ext_fwd(q_, seg_q):
        # The SP local-attention entry (parallel/local_attention.py):
        # the predecessor's tail prepended to K/V, wrap-sentinel ids on
        # it, tile-padded by the SAME helper the SP path uses.
        k_ext = jnp.concatenate([q_[:, -tail:], q_], axis=1)
        seg_k = jnp.concatenate(
            [jnp.full((Bs, tail), _WRAP_SENTINEL, jnp.int32), seg_q],
            axis=1)
        k_ext, v_ext, seg_k = _pad_ext_to_block(k_ext, k_ext, seg_k, 1024)
        out, _ = flash_block_fwd(
            q_, k_ext, v_ext, causal=True, scale=Ds ** -0.5, window=W,
            q_offset=tail, seg_q=seg_q, seg_kv=seg_k,
            block_q=512, block_k=1024, interpret=False,
        )
        return out

    # Paged decode at the accel serving shape (bench._bench_serving):
    # slots=16, max_len=512, bs=32 — pool of 257 blocks (scratch + all).
    S, L, bs = 16, 512, 32
    M = L // bs
    pool = jax.ShapeDtypeStruct((S * M + 1, bs, Hkv, D), dt)
    tables = jax.ShapeDtypeStruct((S, M), jnp.int32)
    pos = jax.ShapeDtypeStruct((S,), jnp.int32)

    def paged(T_rows, **kw):
        qd = jax.ShapeDtypeStruct((S, T_rows, Hq, D), dt)
        return (jax.jit(functools.partial(
            paged_flash_decode, interpret=False, **kw)),
            (qd, pool, pool, tables, pos))

    dense_cache = jax.ShapeDtypeStruct((S, L, Hkv, D), dt)
    qd1 = jax.ShapeDtypeStruct((S, 1, Hq, D), dt)

    return [
        ("flash_fwd", lambda: flash().lower(q, kv, kv).compile()),
        ("flash_fwd_window",
         lambda: flash(window=1024).lower(q, kv, kv).compile()),
        ("flash_bwd", lambda: flash_bwd().lower(q, kv, kv).compile()),
        ("flash_lm_fwdbwd",
         lambda: jax.jit(jax.grad(flash_lm_fwdbwd, argnums=(0, 1, 2)))
         .lower(q_lm, q_lm, q_lm).compile()),
        ("segments_fwd",
         lambda: jax.jit(segments_fwd).lower(qs, qs, qs, seg).compile()),
        ("sp_window_ext_fwd",
         lambda: jax.jit(sp_window_ext_fwd).lower(qs, seg).compile()),
        ("paged_decode_t1",
         lambda: (lambda f, a: f.lower(*a).compile())(*paged(1))),
        ("paged_decode_verify_t4",
         lambda: (lambda f, a: f.lower(*a).compile())(*paged(4))),
        ("paged_decode_window",
         lambda: (lambda f, a: f.lower(*a).compile())(
             *paged(1, window=128))),
        ("dense_decode",
         lambda: jax.jit(functools.partial(
             dense_flash_decode, interpret=False)).lower(
             qd1, dense_cache, dense_cache, pos).compile()),
    ]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the result rows to this path")
    args = ap.parse_args()

    import jax

    backend = jax.devices()[0].platform
    rows = []
    for name, thunk in _cases():
        _note(f"compiling {name} (backend={backend})")
        t0 = time.perf_counter()
        row = {"kernel": name}
        try:
            thunk()
            row["ok"] = True
        except Exception as e:
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {e}"[:600]
        row["compile_s"] = round(time.perf_counter() - t0, 2)
        rows.append(row)
    failures = sum(1 for r in rows if not r["ok"])
    out = {
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "n_cases": len(rows),
        "failures": failures,
        "results": rows,
    }
    if backend != "tpu":
        out["note"] = (
            "non-TPU backend: Mosaic never ran, failures here say "
            "nothing about the chip — run it through the chip tool"
        )
    doc = json.dumps(out, indent=1)
    print(doc)
    if args.json:
        with open(args.json, "w") as f:
            f.write(doc + "\n")
    return failures


if __name__ == "__main__":
    sys.exit(main())
