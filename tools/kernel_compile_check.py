#!/usr/bin/env python
"""Mosaic AOT compile check for EVERY Pallas kernel in the tree.

The repo's standing trap (CLAUDE.md): interpret mode accepts layouts
Mosaic rejects — CPU-green kernels can still be chip-dead. This tool
AOT-lowers each kernel entry point with ``interpret=False`` at
representative on-chip shapes and ``.compile()``s it, so a layout
rejection becomes a named row instead of a surprise mid-run (the
progress trail on stderr names the case being compiled). The flash
cases, which take the geometry the kernels derive for themselves, are
then RUN on seeded inputs and held to XLA's attention (``rel_err``, the
worst leaf's relative L2 error, under ``_TOL``); the paged-decode cases
are compiled only. On the chip it ends with ``timings``: flash forward +
backward, ms a call, at four shapes.

Checked kernels:

- flash attention forward (causal, GQA, window variant)
- flash attention backward (dq + dkv kernels, via jax.grad), also at
  ``chip_smoke.py``'s LM shape and at the benchmark's LM cells' shape
  (B 4, T 1024, 16 heads of 64), and with a window, with packed
  ``segment_ids`` and with a trained bias (``bias_grad=True``), each of
  which changes the tiles dk/dv takes
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

#: largest ``rel_err`` against XLA's attention a flash case may show:
#: three times the worst measured with bf16 operands (0.0029, PERF.md).
_TOL = 1e-2


def _note(msg: str) -> None:
    print(f"[kernel-check] {msg}", file=sys.stderr, flush=True)


def _cases():
    """(name, fn, arg specs, reference) per kernel entry point. ``fn`` is
    lowered for the specs and compiled; where there is a reference (the
    flash cases: XLA's attention on the same arguments) the executable is
    also run and compared."""
    import functools

    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.attention import NEG_INF, dot_product_attention
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
    flash = functools.partial(flash_attention, causal=True, interpret=False)

    def per_example(dense):
        """``dense`` one batch row at a time: the reference's score
        matrix of a whole batch does not fit beside the kernels'."""
        def ref(*args):
            return jax.lax.map(
                lambda row: jax.tree.map(
                    lambda x: x[0], dense(*(x[None] for x in row))),
                args)
        return ref

    def grads(attn):
        return jax.grad(
            lambda q_, k_, v_: attn(q_, k_, v_).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))

    def grads_with_bias(attn):
        return jax.grad(
            lambda q_, k_, v_, b_: attn(q_, k_, v_, b_)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2, 3))

    def band_bias(Tq, Tk, window, q_offset=0):
        i = q_offset + jnp.arange(Tq)[:, None]
        j = jnp.arange(Tk)[None, :]
        return jnp.where(i - j < window, 0.0, NEG_INF)[None, None]

    # Flash at the bench transformer's LM block shape.
    B, T, Hq, Hkv, D = 2, 2048, 8, 4, 64
    q = jax.ShapeDtypeStruct((B, T, Hq, D), dt)
    kv = jax.ShapeDtypeStruct((B, T, Hkv, D), dt)
    # a trained bias: a float32 parameter, one square a head
    bias = jax.ShapeDtypeStruct((1, Hq, T, T), jnp.float32)
    xla = per_example(functools.partial(dot_product_attention, causal=True))
    xla_window = per_example(lambda q_, k_, v_: dot_product_attention(
        q_, k_, v_, causal=True, bias=band_bias(T, T, 1024)))
    xla_segments = per_example(lambda q_, k_, v_, s_: dot_product_attention(
        q_, k_, v_, causal=True, segment_ids=s_))
    # chip_smoke.py's LM shape (per-chip batch 16, 16 heads of 64) and the
    # benchmark's LM cells' (gpt2-medium at 4 sequences a chip).
    q_lm = jax.ShapeDtypeStruct((16, 2048, 16, 64), dt)
    q_cell = jax.ShapeDtypeStruct((4, 1024, 16, 64), dt)

    # The two variants Mosaic rejected, at bench's kernel-sweep shape.
    Bs, Ts, Hs, Ds = 2, 2048, 8, 128
    qs = jax.ShapeDtypeStruct((Bs, Ts, Hs, Ds), dt)
    seg = jax.ShapeDtypeStruct((Bs, Ts), jnp.int32)

    W = 1024  # even window: the extended K length Ts + W - 1 is odd
    tail = W - 1

    def segments(q_, k_, v_, s_):
        return flash(q_, k_, v_, segment_ids=s_)

    def sp_ext(q_, seg_q):
        # The SP local-attention entry (parallel/local_attention.py):
        # the predecessor's tail prepended to K/V, wrap-sentinel ids on
        # it, tile-padded by the SAME helper the SP path uses.
        k_ext = jnp.concatenate([q_[:, -tail:], q_], axis=1)
        seg_k = jnp.concatenate(
            [jnp.full((q_.shape[0], tail), _WRAP_SENTINEL, jnp.int32),
             seg_q], axis=1)
        return _pad_ext_to_block(k_ext, k_ext, seg_k, 1024)

    def sp_window_ext_fwd(q_, seg_q):
        k_ext, v_ext, seg_k = sp_ext(q_, seg_q)
        out, _ = flash_block_fwd(
            q_, k_ext, v_ext, causal=True, scale=Ds ** -0.5, window=W,
            q_offset=tail, seg_q=seg_q, seg_kv=seg_k,
            block_q=None, block_k=None, interpret=False,
        )
        return out

    def sp_window_ext_ref(q_, seg_q):
        k_ext, v_ext, seg_k = sp_ext(q_, seg_q)
        same = (seg_q[:, :, None] == seg_k[:, None, :])[:, None]
        bias = band_bias(Ts, k_ext.shape[1], W, tail) \
            + jnp.where(same, 0.0, NEG_INF)
        return dot_product_attention(q_, k_ext, v_ext, causal=True,
                                     q_offset=tail, bias=bias)

    # Paged decode at the accel serving shape (bench._bench_serving):
    # slots=16, max_len=512, bs=32 — pool of 257 blocks (scratch + all).
    S, L, bs = 16, 512, 32
    M = L // bs
    pool = jax.ShapeDtypeStruct((S * M + 1, bs, Hkv, D), dt)
    tables = jax.ShapeDtypeStruct((S, M), jnp.int32)
    pos = jax.ShapeDtypeStruct((S,), jnp.int32)

    def paged(name, T_rows, **kw):
        qd = jax.ShapeDtypeStruct((S, T_rows, Hq, D), dt)
        return (name,
                functools.partial(paged_flash_decode, interpret=False, **kw),
                (qd, pool, pool, tables, pos), None)

    dense_cache = jax.ShapeDtypeStruct((S, L, Hkv, D), dt)
    qd1 = jax.ShapeDtypeStruct((S, 1, Hq, D), dt)

    return [
        ("flash_fwd", flash, (q, kv, kv), xla),
        ("flash_fwd_window", functools.partial(flash, window=1024),
         (q, kv, kv), xla_window),
        ("flash_bwd", grads(flash), (q, kv, kv), grads(xla)),
        ("flash_lm_fwdbwd", grads(flash), (q_lm,) * 3, grads(xla)),
        ("flash_cell_fwdbwd", grads(flash), (q_cell,) * 3, grads(xla)),
        ("flash_bwd_window", grads(functools.partial(flash, window=1024)),
         (q, kv, kv), grads(xla_window)),
        ("flash_bwd_bias_grad",
         grads_with_bias(lambda q_, k_, v_, b_: flash(
             q_, k_, v_, bias=b_, bias_grad=True)),
         (q, kv, kv, bias),
         grads_with_bias(lambda q_, k_, v_, b_: dot_product_attention(
             q_, k_, v_, causal=True, bias=b_))),
        ("segments_fwd", segments, (qs, qs, qs, seg), xla_segments),
        ("segments_bwd",
         lambda q_, k_, v_, s_: grads(
             lambda *a: segments(*a, s_))(q_, k_, v_),
         (qs, qs, qs, seg),
         lambda q_, k_, v_, s_: grads(
             lambda *a: xla_segments(*a, s_))(q_, k_, v_)),
        ("sp_window_ext_fwd", sp_window_ext_fwd, (qs, seg),
         per_example(sp_window_ext_ref)),
        paged("paged_decode_t1", 1),
        paged("paged_decode_verify_t4", 4),
        paged("paged_decode_window", 1, window=128),
        ("dense_decode",
         functools.partial(dense_flash_decode, interpret=False),
         (qd1, dense_cache, dense_cache, pos), None),
    ]


def _seeded(specs):
    """Arguments for ``specs``: normal floats; an int32 ``[B, T]`` is
    packed-segment ids, a new document every ~400 positions."""
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(24), len(specs))
    return [
        jnp.cumsum(jax.random.bernoulli(key, 1 / 400, s.shape), axis=1,
                   dtype=jnp.int32)
        if s.dtype == jnp.int32
        else jax.random.normal(key, s.shape, s.dtype)
        for key, s in zip(keys, specs)
    ]


def _rel_err(got, want) -> float:
    """Worst leaf's ``|got - want| / |want|`` in float32."""
    import jax
    import jax.numpy as jnp

    def one(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))

    return max(jax.tree.leaves(jax.tree.map(one, got, want)))


def _timings():
    """Forward + backward of causal flash attention, ms a call, at the
    shape ``docs/benchmarks.md`` carries (B4 x T4096 x H8 x D128), at
    the LM cells' (B4 and B16 x T1024 x H16 x D64) and at
    ``chip_smoke.py``'s (B16 x T2048 x H16 x D64). Iterations are chained
    through a scan inside one program, as ``bench.py`` times them, so the
    host's dispatch stays out of the figure."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.flash_attention import flash_attention

    def step(qc, k, v):
        dq, dk, dv = jax.grad(
            lambda a, b, c: flash_attention(a, b, c, causal=True)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(qc, k, v)
        return (qc + 0.0001 * (dq + dk + dv)).astype(qc.dtype)

    rows = []
    for (B, T, H, D), iters in (((4, 4096, 8, 128), 10),
                                ((4, 1024, 16, 64), 40),
                                ((16, 1024, 16, 64), 10),
                                ((16, 2048, 16, 64), 5)):
        many = jax.jit(lambda q, k, v: jax.lax.scan(
            lambda qc, _: (step(qc, k, v), ()), q, None, length=iters,
        )[0].astype(jnp.float32).sum())
        q, k, v = _seeded([jax.ShapeDtypeStruct((B, T, H, D),
                                                jnp.bfloat16)] * 3)
        float(many(q, k, v))  # compile + warm
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            float(many(q, k, v))
            samples.append((time.perf_counter() - t0) / iters * 1e3)
        rows.append({"shape": f"B{B}xT{T}xH{H}xD{D}_bf16_causal",
                     "flash_fwdbwd_ms": [round(x, 4) for x in samples]})
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the result rows to this path")
    args = ap.parse_args()

    import jax

    backend = jax.devices()[0].platform
    rows = []
    for name, fn, specs, ref in _cases():
        _note(f"compiling {name} (backend={backend})")
        t0 = time.perf_counter()
        row = {"kernel": name}
        try:
            compiled = jax.jit(fn).lower(*specs).compile()
            row["compile_s"] = round(time.perf_counter() - t0, 2)
            row["ok"] = True
            if ref is not None:
                _note(f"running {name} against XLA's attention")
                inputs = _seeded(specs)
                row["rel_err"] = _rel_err(compiled(*inputs),
                                          jax.jit(ref)(*inputs))
                row["ok"] = row["rel_err"] <= _TOL
        except Exception as e:
            row["ok"] = False
            row["error"] = f"{type(e).__name__}: {e}"[:600]
        row.setdefault("compile_s", round(time.perf_counter() - t0, 2))
        rows.append(row)
    failures = sum(1 for r in rows if not r["ok"])
    out = {
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "n_cases": len(rows),
        "failures": failures,
        "results": rows,
    }
    if backend == "tpu":
        _note("timing flash forward+backward")
        out["timings"] = _timings()
    else:
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
