"""Attention of a block-diffusion language model's training pass (BD3-LMs,
arXiv:2503.09573): one forward over a clean and a noised copy of every
sequence, ``[x ; x~]``, ``2L`` rows at positions ``[0..L-1 ; 0..L-1]``,
under a mask by blocks of ``bl`` positions, ``b(i) = i // bl``:

* a clean row ``i`` sees the clean keys with ``b(j) <= b(i)``;
* a noised row ``i`` sees the clean keys with ``b(j) < b(i)`` and the
  noised keys with ``b(j) = b(i)``;
* no clean row sees a noised key.

The mask's support is two half squares of ``L`` and a band of ``bl``, a
quarter of the ``2L x 2L`` square, so it is assembled from its three
parts and no ``[2L, 2L]`` array exists: the flash kernels under an
inclusive mask by blocks (clean on clean), the same kernels under the
strict one (noised on clean), and the in-block part, ``bl`` keys a row,
in plain ``jax.numpy`` on the vector unit (at ``bl`` 4 a product of
``[B, L, H, D]`` by 4 keys a row: a matmul of 4 columns would leave the
MXU idle). The noised rows' two partials meet by their log-sum-exps, as
the ring's do (``parallel/ring_attention.py:merge_partials``), and the
backward hands the kernels the *merged* output and log-sum-exp
(``flash_block_bwd``), so a first block's row, which sees no clean key
and whose own log-sum-exp from the strict call is ``NEG_INF``, is given
its in-block one. ``flash_tiles`` counts each call's tiles; every
visited tile holds a pair the mask allows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from chainermn_tpu.observability import train_path
from chainermn_tpu.ops.flash_attention import (
    _use_interpret,
    flash_attention,
    flash_block_bwd,
    flash_block_fwd,
)


def _block_keys(x, j: int, bl: int):
    """``x [B, L, Hkv, D]`` -> for every row the ``j``-th row of its block,
    with a unit axis for the group's query heads: ``[B, L, Hkv, 1, D]``
    float32."""
    B, L, Hkv, D = x.shape
    rows = x.reshape(B, L // bl, bl, Hkv, D)[:, :, j]
    rows = jnp.broadcast_to(rows[:, :, None], (B, L // bl, bl, Hkv, D))
    return rows.reshape(B, L, Hkv, 1, D).astype(jnp.float32)


def _to_block_rows(x, bl: int):
    """:func:`_block_keys`' transpose: ``x [B, L, Hkv, G, D]``, every
    row's term for one row of its block, summed over the block's rows and
    the group's heads: ``[B, L // bl, Hkv, D]``."""
    B, L, Hkv, G, D = x.shape
    return x.reshape(B, L // bl, bl, Hkv, G, D).sum((2, 4))


def _in_block_scores(qg, k, bl: int, scale: float):
    """Scaled float32 scores of every row against the ``bl`` rows of its
    own block: a list of ``bl`` arrays ``[B, L, Hkv, G]``; ``qg`` is
    ``[B, L, Hkv, G, D]`` float32."""
    return [(qg * _block_keys(k, j, bl)).sum(-1) * scale for j in range(bl)]


def _grouped(x, Hkv: int):
    """``[B, L, H, D]`` -> ``[B, L, Hkv, G, D]`` float32: query head ``h``
    reads key-value head ``h // G``."""
    B, L, H, D = x.shape
    return x.reshape(B, L, Hkv, H // Hkv, D).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _noised_rows(q, k_clean, v_clean, k_noised, v_noised, bl, scale,
                 interpret):
    return _noised_rows_fwd(q, k_clean, v_clean, k_noised, v_noised, bl,
                            scale, interpret)[0]


def _noised_rows_fwd(q, k_clean, v_clean, k_noised, v_noised, bl, scale,
                     interpret):
    B, L, H, D = q.shape
    Hkv = k_noised.shape[2]
    out_c, lse_c = flash_block_fwd(
        q, k_clean, v_clean, causal=True, scale=scale, block_q=None,
        block_k=None, interpret=interpret, causal_block=bl,
        causal_strict=True)
    lse_c = lse_c.transpose(0, 2, 1).reshape(B, L, Hkv, H // Hkv)
    scores = _in_block_scores(_grouped(q, Hkv), k_noised, bl, scale)
    # the in-block keys are never all masked, so the merged log-sum-exp
    # is finite where the strict call's is NEG_INF
    lse = functools.reduce(jnp.logaddexp, scores, lse_c)
    out = _grouped(out_c, Hkv) * jnp.exp(lse_c - lse)[..., None]
    for j, s in enumerate(scores):
        out = out + jnp.exp(s - lse)[..., None] * _block_keys(v_noised, j, bl)
    # by the flash kernels' names, so that a remat policy that keeps what
    # the forward kernel made keeps the merged pair and the backward runs
    # no forward kernel again
    out = checkpoint_name(out.reshape(B, L, H, D).astype(q.dtype),
                          train_path.FLASH_OUT)
    lse = checkpoint_name(lse.reshape(B, L, H).transpose(0, 2, 1),
                          train_path.FLASH_LSE)
    return out, (q, k_clean, v_clean, k_noised, v_noised, out, lse)


def _noised_rows_bwd(bl, scale, interpret, res, do):
    q, k_clean, v_clean, k_noised, v_noised, out, lse = res
    B, L, H, D = q.shape
    Hkv = k_noised.shape[2]
    dq_c, dk_clean, dv_clean = flash_block_bwd(
        q, k_clean, v_clean, do, lse, out, causal=True, scale=scale,
        block_q=None, block_k=None, interpret=interpret, causal_block=bl,
        causal_strict=True, grad_dtype=None)
    qg, dog = _grouped(q, Hkv), _grouped(do, Hkv)
    lse = lse.transpose(0, 2, 1).reshape(B, L, Hkv, H // Hkv)
    delta = (dog * _grouped(out, Hkv)).sum(-1)
    dq = dq_c.reshape(qg.shape).astype(jnp.float32)
    dk, dv = [], []
    for j, s in enumerate(_in_block_scores(qg, k_noised, bl, scale)):
        p = jnp.exp(s - lse)
        dp = (dog * _block_keys(v_noised, j, bl)).sum(-1)
        ds = (p * (dp - delta) * scale)[..., None]
        dq = dq + ds * _block_keys(k_noised, j, bl)
        dk.append(_to_block_rows(ds * qg, bl))
        dv.append(_to_block_rows(p[..., None] * dog, bl))

    def rows(parts, like):  # bl x [B, L // bl, Hkv, D] -> [B, L, Hkv, D]
        return jnp.stack(parts, axis=2).reshape(like.shape).astype(
            like.dtype)

    return (dq.reshape(q.shape).astype(q.dtype), dk_clean, dv_clean,
            rows(dk, k_noised), rows(dv, v_noised))


_noised_rows.defvjp(_noised_rows_fwd, _noised_rows_bwd)


def block_diffusion_attention(q, k, v, *, block_length: int, scale=None,
                              interpret=None):
    """Attention over ``[x ; x~]``: ``q [B, 2L, H, D]``, ``k`` and ``v``
    ``[B, 2L, Hkv, D]``, the first ``L`` rows the clean copy's and the
    last ``L`` the noised one's, under the module's mask at blocks of
    ``block_length`` -> ``[B, 2L, H, D]``. Under the scope
    :data:`train_path.BD_ATTENTION`; sets the gauge
    :data:`train_path.BD_BLOCK_LENGTH`."""
    from chainermn_tpu.observability.metrics import registry

    B, rows, H, D = q.shape
    L, bl = rows // 2, int(block_length)
    if rows % 2 or L % bl or bl < 1:
        raise ValueError(
            f"block diffusion attends over a clean and a noised copy of "
            f"whole blocks: {rows} rows are no two copies of a multiple "
            f"of block_length={block_length}")
    if scale is None:
        scale = D ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    registry().gauge(
        train_path.BD_BLOCK_LENGTH,
        "positions a block of the block-diffusion mask holds, at the "
        "last attention traced",
    ).set(float(bl))
    with jax.named_scope(train_path.BD_ATTENTION):
        clean = flash_attention(
            q[:, :L], k[:, :L], v[:, :L], causal=True, scale=scale,
            interpret=interpret, causal_block=bl)
        noised = _noised_rows(q[:, L:], k[:, :L], v[:, :L], k[:, L:],
                              v[:, L:], bl, float(scale), bool(interpret))
        return jnp.concatenate([clean, noised], axis=1)
