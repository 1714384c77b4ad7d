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
- flash attention forward + backward at the OLMoE cell's shape (B 4,
  T 4096, 16 heads of 128)
- flash attention forward + backward at latent attention's widths (keys
  of 192, values of 128, 16 heads: the transposed form at two widths)
- flash attention forward + backward at T 200 and T 576: the kernels
  pass the log-sum-exp as ``[B, H, 1, T]`` rows, whose block Mosaic
  takes as a multiple of 128 lanes or as the whole row, and these
  lengths have no such divisor (``_pick_row_block``)
- flash attention forward + backward in the projections' own layout
  (``[B, T, H * D]``, no transposition round the kernels): two heads of
  64 a 128-lane block at the GPT-2 cells' shapes (B 16 and B 4, T 1024,
  16 heads) and one head of 128 a block of columns at OLMoE's and Ouro's
  (B 4 and B 1, T 4096, 16 heads), each also with packed ``segment_ids``
  and with a window of 64; the GQA and odd-width cases above take the
  transposed form, ``[B * H, T, D]``, through the same kernels
- the gradient of two remat'ed blocks (``remat_policy='dots'``) at the
  memory-full GPT-2 cell's shape (B 16, T 1024, 16 heads of 64): the
  policy keeps what the flash forward made, so the compiled gradient
  holds three Mosaic calls a layer and not four (``mosaic_calls``)
- the grouped matmul of the dropless mixture of experts
  (``ops/grouped_matmul.py``): forward and both gradients on uneven groups
  with empty ones, run against a masked loop over the groups (``rel_err``
  under ``_TOL``), and compiled at the OLMoE cell's shapes (131,072 rows,
  64 experts, 2048 x 2048 and 1024 x 2048); ``timings`` carries its
  forward and forward + backward there
- the gated short convolution's gate-and-tap chain
  (``ops/short_conv.py``): forward and both gradients at the LFM2 cell's
  shape (``[2, 8192, 6144]`` bf16, 3 taps) and at a small float32 one of
  4 taps, run against the plain ``jax.numpy`` spelling in float32
  (``rel_err`` under ``_TOL``); ``timings`` carries the kernels' and the
  plain spelling's forward and forward + backward at the cell's shape
- block diffusion's in-block call (``ops/block_diffusion.py``): the flash
  kernels without a causal mask under segment ids that are a row's block
  within its tile, at the SDAR cell's shape (8192 noised rows as 16
  sequences of one 512-row tile, 32 / 4 heads of 128, blocks of 4),
  forward (output and log-sum-exp) and the three gradients, run against
  the band computed block by block in float32; ``timings`` carries the
  call's forward + backward there
- fused paged decode (ISSUE 19): plain tick T=1, verify span T>1,
  window, and the dense-cache wrapper — the ``(1, bs, 1, D)`` KV block
  (second-to-last dim 1 over the kv-head axis) is exactly the kind of
  layout Mosaic might refuse (ROADMAP S4).

Usage (through the chip tool; needs the chip)::

    python tools/kernel_compile_check.py
    python tools/kernel_compile_check.py --json chiprun_out/kernels.json
    python tools/kernel_compile_check.py --only bd_in_block   # no timings

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
#: The grouped-matmul cases are held to the same: bf16 operands and a bf16
#: result against the same products in float32 (0.0025-0.0026 measured).
_TOL = 1e-2

#: Mosaic custom calls the compiled program of a case has to hold: two
#: remat'ed layers of forward, dq and dk/dv, the forward not run again
_MOSAIC_CALLS = {"remat_dots_block_grads": 2 * 3}


def _group_sizes(noise, rows: int):
    """Uneven group sizes summing to ``rows`` from a float vector, every
    fifth group empty: what a skewed router hands the grouped matmul."""
    import jax
    import jax.numpy as jnp

    n = noise.shape[0]
    share = jax.nn.softmax(2.0 * noise.astype(jnp.float32))
    share = jnp.where(jnp.arange(n) % 5 == 3, 0.0, share)
    sizes = jnp.floor(share / share.sum() * rows).astype(jnp.int32)
    return sizes.at[0].add(rows - sizes.sum())


def _short_conv_plain32(bcx, taps):
    """The short convolution's plain spelling in float32 on what the
    operands' dtype holds, rounded once. The taps are rounded by
    ``reduce_precision``: XLA takes a cast there and back again for excess
    precision it may keep."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.short_conv import plain

    held = jnp.finfo(bcx.dtype)
    return plain(bcx.astype(jnp.float32),
                 jax.lax.reduce_precision(taps, held.nexp, held.nmant)
                 ).astype(bcx.dtype)


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
    from chainermn_tpu.ops.grouped_matmul import grouped_matmul
    from chainermn_tpu.ops.paged_decode import (
        dense_flash_decode,
        paged_flash_decode,
    )
    from chainermn_tpu.ops.short_conv import gated_short_conv
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

    def segments_grads(attn):
        return lambda q_, k_, v_, s_: grads(
            lambda *a: attn(*a, s_))(q_, k_, v_)

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
    # the OLMoE cell's: 4 sequences of 4096, 16 heads of 128
    q_olmoe = jax.ShapeDtypeStruct((4, 4096, 16, 128), dt)
    # lengths no 128-lane block divides: the row statistics' block is the
    # whole row, at 200 as the q block always was, at 576 where halving
    # 512 would end at 64
    q_200 = jax.ShapeDtypeStruct((4, 200, 8, 64), dt)
    q_576 = jax.ShapeDtypeStruct((4, 576, 8, 64), dt)
    # the projections' layout at the other cells' batches: the memory-full
    # GPT-2 cell's (two heads of 64 a block) and Ouro's (one head of 128)
    q_cell16 = jax.ShapeDtypeStruct((16, 1024, 16, 64), dt)
    q_ouro = jax.ShapeDtypeStruct((1, 4096, 16, 128), dt)
    # latent attention's widths (DeepSeek-V2-Lite: 16 heads, keys of 128 +
    # 64, values of 128): 192 lanes are no whole tiles, so the transposed
    # form, each kernel at two widths
    qk_mla = jax.ShapeDtypeStruct((1, 2048, 16, 192), dt)
    v_mla = jax.ShapeDtypeStruct((1, 2048, 16, 128), dt)
    seg_cell = jax.ShapeDtypeStruct((4, 1024), jnp.int32)
    seg_olmoe = jax.ShapeDtypeStruct((4, 4096), jnp.int32)

    def xla_window64(T):
        return per_example(lambda q_, k_, v_: dot_product_attention(
            q_, k_, v_, causal=True, bias=band_bias(T, T, 64)))

    # The grouped matmul: group sizes are made from a float vector inside
    # the case, uneven and with empty groups; the reference multiplies
    # every row by every group's matrix and keeps the group's own rows.
    def gmm(lhs, rhs, noise):
        return grouped_matmul(lhs, rhs, _group_sizes(noise, lhs.shape[0]))

    def gmm_loop(lhs, rhs, noise):
        sizes = _group_sizes(noise, lhs.shape[0])
        ends = jnp.cumsum(sizes)
        rows = jnp.arange(lhs.shape[0])[:, None]
        lhs32 = lhs.astype(jnp.float32)

        def one(acc, group):
            w, lo, hi = group
            mine = (rows >= lo) & (rows < hi)
            prod = jnp.dot(lhs32, w.astype(jnp.float32),
                           precision=jax.lax.Precision.HIGHEST)
            return acc + jnp.where(mine, prod, 0.0), ()

        out, _ = jax.lax.scan(
            one, jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32),
            (rhs, ends - sizes, ends))
        return out.astype(lhs.dtype)

    def gmm_grads(fn):
        return jax.grad(
            lambda a, b, n_: fn(a, b, n_).astype(jnp.float32).sum(),
            argnums=(0, 1))

    def gmm_specs(rows, experts, k_dim, n_dim):
        return (jax.ShapeDtypeStruct((rows, k_dim), dt),
                jax.ShapeDtypeStruct((experts, k_dim, n_dim), jnp.float32),
                jax.ShapeDtypeStruct((experts,), jnp.float32))

    gmm_small = gmm_specs(8192, 16, 1024, 512)

    # The gated short convolution: the reference is the plain spelling in
    # float32 on what the operands' dtype holds; the gradients' cotangent
    # varies along all three axes.
    def conv_grads(fn):
        def weighed(bcx, taps):
            y = fn(bcx, taps).astype(jnp.float32)
            ramp = sum(jnp.cos(jax.lax.broadcasted_iota(
                jnp.float32, y.shape, axis) * (0.37 + axis))
                for axis in range(3))
            return (y * ramp).sum()
        return jax.grad(weighed, argnums=(0, 1))

    def conv_specs(B, T, D, L, dtype):
        return (jax.ShapeDtypeStruct((B, T, 3 * D), dtype),
                jax.ShapeDtypeStruct((L, D), jnp.float32))

    conv_cell = conv_specs(2, 8192, 2048, 3, dt)
    conv_small = conv_specs(2, 64, 128, 4, jnp.float32)

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

    # Block diffusion's in-block call at the SDAR cell's shape. The
    # reference computes the band a block at a time in float32: a dense
    # [32, 8192, 8192] score array does not fit.
    from chainermn_tpu.ops import block_diffusion as bd

    in_block, sdar = _sdar_in_block()

    def in_block_ref(q_, k_, v_):
        bl, (B_, L_, H_, D_) = in_block["bl"], q_.shape

        def blocks(x):
            x = jnp.repeat(x, H_ // x.shape[2], axis=2)
            return x.reshape(B_, L_ // bl, bl, H_, D_).astype(jnp.float32)

        s = jnp.einsum("bnqhd,bnkhd->bnhqk", blocks(q_), blocks(k_),
                       precision="highest") * in_block["scale"]
        out = jnp.einsum("bnhqk,bnkhd->bnqhd", jax.nn.softmax(s, -1),
                         blocks(v_), precision="highest")
        lse = jax.nn.logsumexp(s, -1).transpose(0, 2, 1, 3)
        return out.reshape(q_.shape), lse.reshape(B_, H_, L_)

    def in_block_grads(q_, k_, v_):
        out, lse = bd.in_block_fwd(q_, k_, v_, **in_block)
        return bd.in_block_bwd(q_, k_, v_, jnp.ones_like(out), lse, out,
                               **in_block)

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

    # Two remat'ed GPT-2 medium blocks at the memory-full cell's batch.
    from chainermn_tpu.models import TransformerLM

    remat_lm = TransformerLM(
        vocab_size=512, num_layers=2, num_heads=16, d_model=1024, d_ff=4096,
        max_len=1024, compute_dtype=dt, remat=True, remat_policy="dots",
        return_hidden=True,
        attention_fn=lambda q_, k_, v_, *, causal, scale: flash_attention(
            q_, k_, v_, causal=causal, scale=scale, interpret=False),
    )
    remat_tokens = jax.ShapeDtypeStruct((16, 1024), jnp.int32)
    remat_params = jax.eval_shape(
        lambda: remat_lm.init(jax.random.PRNGKey(0),
                              jnp.zeros((1, 1024), jnp.int32))["params"])

    def remat_block_grads(params, tokens):
        return jax.grad(lambda p: remat_lm.apply(
            {"params": p}, tokens).astype(jnp.float32).sum())(params)

    return [
        ("flash_fwd", flash, (q, kv, kv), xla),
        ("flash_fwd_window", functools.partial(flash, window=1024),
         (q, kv, kv), xla_window),
        ("flash_bwd", grads(flash), (q, kv, kv), grads(xla)),
        ("flash_lm_fwdbwd", grads(flash), (q_lm,) * 3, grads(xla)),
        ("flash_cell_fwdbwd", grads(flash), (q_cell,) * 3, grads(xla)),
        ("flash_olmoe_fwdbwd", grads(flash), (q_olmoe,) * 3, grads(xla)),
        ("flash_pairs_b16_fwdbwd", grads(flash), (q_cell16,) * 3,
         grads(xla)),
        ("flash_columns_b1_fwdbwd", grads(flash), (q_ouro,) * 3, grads(xla)),
        ("flash_mla_192_128_fwdbwd", grads(flash), (qk_mla, qk_mla, v_mla),
         grads(xla)),
        ("flash_whole_row_t200_fwdbwd", grads(flash), (q_200,) * 3,
         grads(xla)),
        ("flash_whole_row_t576_fwdbwd", grads(flash), (q_576,) * 3,
         grads(xla)),
        ("grouped_matmul_fwd", gmm, gmm_small, gmm_loop),
        ("grouped_matmul_grads", gmm_grads(gmm), gmm_small,
         gmm_grads(gmm_loop)),
        ("grouped_matmul_olmoe_gate_up", gmm_grads(gmm),
         gmm_specs(131072, 64, 2048, 2048), None),
        ("grouped_matmul_olmoe_down", gmm_grads(gmm),
         gmm_specs(131072, 64, 1024, 2048), None),
        ("short_conv_fwd", gated_short_conv, conv_cell, _short_conv_plain32),
        ("short_conv_grads", conv_grads(gated_short_conv), conv_cell,
         conv_grads(_short_conv_plain32)),
        ("short_conv_small_f32_fwd", gated_short_conv, conv_small,
         _short_conv_plain32),
        ("short_conv_small_f32_grads", conv_grads(gated_short_conv),
         conv_small, conv_grads(_short_conv_plain32)),
        ("flash_bwd_window", grads(functools.partial(flash, window=1024)),
         (q, kv, kv), grads(xla_window)),
        ("flash_bwd_bias_grad",
         grads_with_bias(lambda q_, k_, v_, b_: flash(
             q_, k_, v_, bias=b_, bias_grad=True)),
         (q, kv, kv, bias),
         grads_with_bias(lambda q_, k_, v_, b_: dot_product_attention(
             q_, k_, v_, causal=True, bias=b_))),
        # two heads a step hold two bias squares and two of its gradient:
        # the tile takes half the keys (``_geometry``'s ``bias_heads``)
        ("flash_pairs_bias_grad",
         grads_with_bias(lambda q_, k_, v_, b_: flash(
             q_, k_, v_, bias=b_, bias_grad=True)),
         (q, q, q, bias),
         grads_with_bias(lambda q_, k_, v_, b_: dot_product_attention(
             q_, k_, v_, causal=True, bias=b_))),
        ("segments_fwd", segments, (qs, qs, qs, seg), xla_segments),
        ("segments_bwd", segments_grads(segments), (qs, qs, qs, seg),
         segments_grads(xla_segments)),
        ("flash_pairs_segments_fwdbwd", segments_grads(segments),
         (q_cell,) * 3 + (seg_cell,), segments_grads(xla_segments)),
        ("flash_columns_segments_fwdbwd", segments_grads(segments),
         (q_olmoe,) * 3 + (seg_olmoe,), segments_grads(xla_segments)),
        ("flash_pairs_window64_fwdbwd",
         grads(functools.partial(flash, window=64)), (q_cell,) * 3,
         grads(xla_window64(1024))),
        ("flash_columns_window64_fwdbwd",
         grads(functools.partial(flash, window=64)), (q_olmoe,) * 3,
         grads(xla_window64(4096))),
        ("sp_window_ext_fwd", sp_window_ext_fwd, (qs, seg),
         per_example(sp_window_ext_ref)),
        ("bd_in_block_fwd",
         lambda q_, k_, v_: bd.in_block_fwd(q_, k_, v_, **in_block),
         sdar, in_block_ref),
        ("bd_in_block_grads", in_block_grads, sdar,
         grads(lambda *a: in_block_ref(*a)[0])),
        paged("paged_decode_t1", 1),
        paged("paged_decode_verify_t4", 4),
        paged("paged_decode_window", 1, window=128),
        ("dense_decode",
         functools.partial(dense_flash_decode, interpret=False),
         (qd1, dense_cache, dense_cache, pos), None),
        ("remat_dots_block_grads", remat_block_grads,
         (remat_params, remat_tokens), None),
    ]


def _sdar_in_block():
    """``(static arguments, (q, k, v) specs)`` of block diffusion's
    in-block call at the SDAR cell's shape: 8192 noised rows, 32 / 4
    heads of 128, bf16, blocks of 4."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.block_diffusion import in_block_tile

    kw = dict(bl=4, t=in_block_tile(8192, 4), scale=128 ** -0.5,
              interpret=False)
    return kw, tuple(jax.ShapeDtypeStruct((1, 8192, h, 128), jnp.bfloat16)
                     for h in (32, 4, 4))


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
    shape ``PERF.md`` carries (B4 x T4096 x H8 x D128), at
    the LM cells' (B4 and B16 x T1024 x H16 x D64) and at
    ``chip_smoke.py``'s (B16 x T2048 x H16 x D64). Iterations are chained
    through a scan inside one program, so the host's dispatch stays out
    of the figure."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.flash_attention import flash_attention

    def step(qc, k, v):
        dq, dk, dv = jax.grad(
            lambda a, b, c: flash_attention(a, b, c, causal=True)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(qc, k, v)
        dv = jnp.pad(dv, [(0, 0)] * 3 + [(0, dq.shape[-1] - dv.shape[-1])])
        return (qc + 0.0001 * (dq + dk + dv)).astype(qc.dtype)

    rows = []
    # the last: the DeepSeek-V2-Lite cell's (keys of 192, values of 128)
    for (B, T, H, D), iters, Dv in (((4, 4096, 8, 128), 10, 128),
                                    ((4, 1024, 16, 64), 40, 64),
                                    ((16, 1024, 16, 64), 10, 64),
                                    ((16, 2048, 16, 64), 5, 64),
                                    ((1, 8192, 16, 192), 10, 128)):
        many = jax.jit(lambda q, k, v: jax.lax.scan(
            lambda qc, _: (step(qc, k, v), ()), q, None, length=iters,
        )[0].astype(jnp.float32).sum())
        q, k, v = _seeded([
            jax.ShapeDtypeStruct((B, T, H, d), jnp.bfloat16)
            for d in (D, D, Dv)])
        float(many(q, k, v))  # compile + warm
        samples = []
        for _ in range(3):
            t0 = time.perf_counter()
            float(many(q, k, v))
            samples.append((time.perf_counter() - t0) / iters * 1e3)
        width = f"D{D}" if Dv == D else f"D{D}v{Dv}"
        rows.append({"shape": f"B{B}xT{T}xH{H}x{width}_bf16_causal",
                     "flash_fwdbwd_ms": [round(x, 4) for x in samples]})
    return rows + _in_block_timings() + _grouped_matmul_timings() \
        + _short_conv_timings()


def _chained_ms(fn, iters: int, *args):
    """ms a call of ``fn``, which runs ``iters`` chained calls in one
    program: three samples after a compiling one."""
    float(fn(*args))  # compile + warm
    samples = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(fn(*args))
        samples.append((time.perf_counter() - t0) / iters * 1e3)
    return [round(x, 4) for x in samples]


def _in_block_timings(iters: int = 10):
    """Block diffusion's in-block call at the SDAR cell's shape (8192
    noised rows, 32 / 4 heads of 128, blocks of 4): the forward kernel,
    and forward + dq + dk/dv, ms a call."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops import block_diffusion as bd

    kw, specs = _sdar_in_block()
    q, k, v = _seeded(specs)

    def fwd(qc, k, v):
        return bd.in_block_fwd(qc, k, v, **kw)[0]

    def fwdbwd(qc, k, v):
        out, lse = bd.in_block_fwd(qc, k, v, **kw)
        dq, dk, dv = bd.in_block_bwd(qc, k, v, out, lse, out, **kw)
        return (qc + 0.0001 * (dq + (dk + dv).sum(2, keepdims=True))
                ).astype(qc.dtype)

    def chained(step):
        return jax.jit(lambda q, k, v: jax.lax.scan(
            lambda qc, _: (step(qc, k, v), ()), q, None, length=iters,
        )[0].astype(jnp.float32).sum())

    return [{"shape": "bd_in_block_L8192xH32/4xD128_bl4_t512_bf16",
             "fwd_ms": _chained_ms(chained(fwd), iters, q, k, v),
             "fwdbwd_ms": _chained_ms(chained(fwdbwd), iters, q, k, v)}]


def _short_conv_timings(iters: int = 20):
    """The gated short convolution's chain at the LFM2 cell's shape
    (``[2, 8192, 6144]`` bf16, 3 taps): the two kernels and the plain
    spelling, forward, and forward + both gradients, ms a call. The chain
    runs through the taps (a few KB), so nothing but the op itself moves
    the operands."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops import short_conv

    keys = jax.random.split(jax.random.PRNGKey(41), 2)
    bcx = jax.random.normal(keys[0], (2, 8192, 3 * 2048), jnp.bfloat16)
    taps = jax.random.uniform(keys[1], (3, 2048), jnp.float32, -0.5, 0.5)

    def chained(step):
        return jax.jit(lambda a, w: jax.lax.scan(
            lambda w, _: (step(a, w), ()), w, None, length=iters)[0].sum())

    def fwd(op):
        return chained(lambda a, w: w + 1e-9 * op(a, w)[0, :3].astype(
            jnp.float32))

    def fwdbwd(op):
        def step(a, w):
            y, vjp = jax.vjp(op, a, w)
            da, dw = vjp(y)
            return w + 1e-9 * dw + 1e-9 * da[0, :3, :2048].astype(
                jnp.float32)
        return chained(step)

    row = {"shape": "B2xT8192xD2048xL3_bf16"}
    for name, op in (("short_conv", short_conv.gated_short_conv),
                     ("short_conv_plain", short_conv.plain)):
        row[f"{name}_fwd_ms"] = _chained_ms(fwd(op), iters, bcx, taps)
        row[f"{name}_fwdbwd_ms"] = _chained_ms(fwdbwd(op), iters, bcx, taps)
    return [row]


def _grouped_matmul_timings(iters: int = 5):
    """The grouped matmul at the OLMoE cell's shapes (131,072 rows of
    uneven groups over 64 experts, bf16 rows, float32 master weights) and
    at the LFM2 cell's gate|up under its share (65,536 rows of which a
    quarter reach the 8 held experts; the other 96 row tiles are the tail
    the row products write as zeros): forward, and forward + both
    gradients, ms a call, chained in one program like the flash timings."""
    import jax
    import jax.numpy as jnp

    from chainermn_tpu.ops.grouped_matmul import grouped_matmul

    rows = []
    for m, live, e, k_dim, n_dim in ((131072, 131072, 64, 2048, 2048),
                                     (131072, 131072, 64, 1024, 2048),
                                     (65536, 16384, 8, 2048, 3584)):
        keys = jax.random.split(jax.random.PRNGKey(25), 3)
        lhs = jax.random.normal(keys[0], (m, k_dim), jnp.bfloat16)
        rhs = jax.random.normal(keys[1], (e, k_dim, n_dim), jnp.float32)
        sizes = _group_sizes(jax.random.normal(keys[2], (e,)), live)

        # the weights and the sizes are arguments: closed over they would
        # be a gigabyte of constants in the program
        def fwd(x, w, gs):
            def step(x, _):
                out = grouped_matmul(x, w, gs)
                # the next iteration's rows: the result's first columns
                return (out[:, :k_dim] * 1e-2).astype(x.dtype), ()
            return jax.lax.scan(step, x, None, length=iters)[0] \
                .astype(jnp.float32).sum()

        def fwdbwd(x, w, gs):
            def step(x, _):
                out, vjp = jax.vjp(
                    lambda a, b: grouped_matmul(a, b, gs), x, w)
                dl, dr = vjp(out)  # the forward's result is needed: kept
                return (x + 1e-4 * dl + 1e-9 * dr[0, 0, 0]).astype(
                    x.dtype), ()
            return jax.lax.scan(step, x, None, length=iters)[0] \
                .astype(jnp.float32).sum()

        rows.append({"shape": f"M{m}xE{e}xK{k_dim}xN{n_dim}_bf16"
                              + (f"_live{live}" if live < m else ""),
                     "gmm_fwd_ms": _chained_ms(jax.jit(fwd), iters, lhs,
                                               rhs, sizes),
                     "gmm_fwdbwd_ms": _chained_ms(jax.jit(fwdbwd), iters,
                                                  lhs, rhs, sizes)})
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="also write the result rows to this path")
    ap.add_argument("--only", default=None,
                    help="run the cases whose name holds this, no timings")
    args = ap.parse_args()

    import jax

    backend = jax.devices()[0].platform
    rows = []
    for name, fn, specs, ref in _cases():
        if args.only and args.only not in name:
            continue
        _note(f"compiling {name} (backend={backend})")
        t0 = time.perf_counter()
        row = {"kernel": name}
        try:
            compiled = jax.jit(fn).lower(*specs).compile()
            row["compile_s"] = round(time.perf_counter() - t0, 2)
            row["ok"] = True
            if name in _MOSAIC_CALLS:
                row["mosaic_calls"] = compiled.as_text().count(
                    'custom_call_target="tpu_custom_call"')
                row["ok"] = row["mosaic_calls"] == _MOSAIC_CALLS[name]
            if ref is not None:
                _note(f"running {name} against its reference")
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
    if backend == "tpu" and not args.only:
        _note("timing flash forward+backward")
        out["timings"] = _timings()
    elif backend != "tpu":
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
