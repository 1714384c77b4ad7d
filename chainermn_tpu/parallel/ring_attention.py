"""Ring attention — sequence/context parallelism over a mesh axis.

NEW capability relative to the reference (SURVEY.md section 5: ChainerMN is
2017-era and has no sequence parallelism; its seq2seq example bucketed long
sequences on one device). Designed as another communicator-consuming layer,
sitting where the model-parallel functions sit in the reference's stack
(``chainermn/functions/`` (dagger), SURVEY.md section 2.4).

Mechanism: the sequence is sharded over a ``'seq'`` mesh axis. Each shard
keeps its Q block resident and the K/V blocks *rotate around the ring* via
``lax.ppermute`` (ICI neighbour exchange — bandwidth-optimal, no all-gather
of the full sequence). Each arriving block is processed by the Pallas flash
kernel (:mod:`chainermn_tpu.ops.flash_attention`), which returns the block's
attention output plus its logsumexp row; successive blocks merge in log
space, so per-shard memory stays ``O(T_local * D)`` and the full ``[T, T]``
score matrix never exists anywhere — the SURVEY §5/§7 "ring attention as a
Pallas kernel" requirement.

Differentiability: a hand-written ``custom_vjp``. The backward pass is a
second ring pass — K/V blocks rotate again, now accompanied by their
gradient accumulators, and each stop adds that shard's (dq, dk, dv)
contribution via the Pallas backward kernels. This is the same send/recv
duality the reference hand-built in ``Send.backward``/``Recv.backward``
(``functions/point_to_point_communication.py`` (dagger)), lifted to whole
ring rotations. ``impl='einsum'`` keeps the lax/einsum path (differentiated
automatically through ``scan``+``ppermute``) as the correctness reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu.ops.attention import (
    NEG_INF,
    finalize_online_softmax,
    online_softmax_block,
)
from chainermn_tpu.ops.flash_attention import (
    _use_interpret,
    flash_block_bwd,
    flash_block_fwd,
    interpret_on,
)


def merge_partials(o, lse, o_blk, lse_blk):
    """Merge two normalised attention partials in log space.

    ``o``/``o_blk``: [B, T, H, D] f32 outputs, each normalised within its own
    key set; ``lse``/``lse_blk``: [B, H, T] logsumexps of those key sets. The
    merged pair is the attention over the union of the key sets.
    """
    lse_new = jnp.logaddexp(lse, lse_blk)
    # Both -inf (no keys seen yet, e.g. fully-masked rows): keep output 0.
    safe = lse_new > NEG_INF / 2
    a = jnp.where(safe, jnp.exp(lse - lse_new), 0.0)
    b = jnp.where(safe, jnp.exp(lse_blk - lse_new), 0.0)
    o_new = (
        o * a.transpose(0, 2, 1)[..., None]
        + o_blk.astype(jnp.float32) * b.transpose(0, 2, 1)[..., None]
    )
    return o_new, lse_new


def _ring_perm(n):
    return [(i, (i + 1) % n) for i in range(n)]


# ---------------------------------------------------------------------------
# Zigzag layout helpers (host/jit-level, run once per batch outside the ring)
# ---------------------------------------------------------------------------

def zigzag_indices(n: int, total: int):
    """Global→zigzag gather indices: the global sequence is split into ``2n``
    chunks and shard ``s`` holds the pair ``(s, 2n-1-s)``, so under a causal
    mask every shard owns exactly half a "past-heavy" and half a
    "future-heavy" chunk — per-(shard, ring-step) work becomes a constant 2
    chunk² instead of growing with the shard index (the load imbalance
    VERDICT r2 item 4 called out: contiguous shard ``s`` computes ``s+1`` of
    ``n`` blocks, so the ring's wall clock was the LAST shard's full-n work).
    """
    import numpy as np

    if total % (2 * n):
        raise ValueError(f"sequence length {total} not divisible by 2n={2*n}")
    c = total // (2 * n)
    idx = []
    for s in range(n):
        idx.extend(range(s * c, (s + 1) * c))
        idx.extend(range((2 * n - 1 - s) * c, (2 * n - s) * c))
    return np.asarray(idx, dtype=np.int32)


def to_zigzag(x, n: int, axis: int = 1):
    """Reorder a GLOBAL array's sequence axis so that contiguous equal
    slices correspond to zigzag shards (apply before sharding over the ring
    axis; one gather, done once per batch)."""
    return jnp.take(x, jnp.asarray(zigzag_indices(n, x.shape[axis])), axis=axis)


def from_zigzag(x, n: int, axis: int = 1):
    """Inverse of :func:`to_zigzag`."""
    import numpy as np

    idx = zigzag_indices(n, x.shape[axis])
    inv = np.empty_like(idx)
    inv[idx] = np.arange(idx.size, dtype=np.int32)
    return jnp.take(x, jnp.asarray(inv), axis=axis)


def _ring_flash_fwd_impl(q, k, v, seg_q, seg_kv, axis_name, causal, scale,
                         block_q, block_k, interpret):
    """Shared forward ring. ``seg_q``/``seg_kv`` are either both None or the
    local ``[B, T_local]`` packed-segment id slices; the kv ids travel with
    their K/V block around the ring."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    kw = dict(scale=scale, block_q=block_q, block_k=block_k,
              interpret=interpret)
    has_seg = seg_q is not None

    o = jnp.zeros((B, Tq, H, D), jnp.float32)
    lse = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    perm = _ring_perm(n)

    def _full(o, lse, k_blk, v_blk, sk):
        o_b, lse_b = flash_block_fwd(
            q, k_blk, v_blk, causal=False,
            seg_q=seg_q, seg_kv=sk, **kw,
        )
        return merge_partials(o, lse, o_b, lse_b)

    def _diag(o, lse, k_blk, v_blk, sk):
        # src == my: equal global offsets, so the causal mask is the static
        # relative mask — no dynamic offsets reach the kernel.
        o_b, lse_b = flash_block_fwd(
            q, k_blk, v_blk, causal=True,
            seg_q=seg_q, seg_kv=sk, **kw,
        )
        return merge_partials(o, lse, o_b, lse_b)

    def _skip(o, lse, k_blk, v_blk, sk):
        return o, lse

    def step(carry, s):
        k_blk, v_blk, sk, o, lse = carry
        # Rotate FIRST (depends only on the carry): the async
        # collective-permute overlaps this step's kernels.
        k_nxt, v_nxt, sk_nxt = lax.ppermute(
            (k_blk, v_blk, sk), axis_name, perm
        )
        sk_cur = sk if has_seg else None
        if causal:
            src = (my - s) % n
            # src < my: block is entirely in the past — full attention.
            # src == my: the diagonal block. src > my: entirely future — skip
            # (no matmul at all; the causal ring does ~half the FLOPs).
            branch = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
            o, lse = lax.switch(
                branch, (_full, _diag, _skip), o, lse, k_blk, v_blk, sk_cur
            )
        else:
            o, lse = _full(o, lse, k_blk, v_blk, sk_cur)
        return (k_nxt, v_nxt, sk_nxt, o, lse), None

    # A tiny dummy travels in place of kv segment ids when unused, keeping
    # one scan structure for both cases.
    sk0 = seg_kv if has_seg else jnp.zeros((1, 1), jnp.int32)
    (k, v, seg_kv, o, lse), _ = lax.scan(
        step, (k, v, sk0, o, lse), jnp.arange(n)
    )
    # After n rotations K/V are home again — return them as residuals so the
    # backward ring starts from the same layout without re-gathering.
    return o.astype(q.dtype), lse, k, v


def _ring_flash_bwd_impl(q, k, v, seg_q, seg_kv, out, lse, g, axis_name,
                         causal, scale, block_q, block_k, interpret):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    kw = dict(scale=scale, block_q=block_q, block_k=block_k,
              interpret=interpret)
    has_seg = seg_q is not None
    do = g

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    perm = _ring_perm(n)

    def _full(k_blk, v_blk, sk):
        return flash_block_bwd(q, k_blk, v_blk, do, lse, out,
                               causal=False, seg_q=seg_q, seg_kv=sk, **kw)

    def _diag(k_blk, v_blk, sk):
        return flash_block_bwd(q, k_blk, v_blk, do, lse, out,
                               causal=True, seg_q=seg_q, seg_kv=sk, **kw)

    def _skip(k_blk, v_blk, sk):
        return dq0, jnp.zeros(k_blk.shape, jnp.float32), \
            jnp.zeros(v_blk.shape, jnp.float32)

    def step(carry, s):
        k_blk, v_blk, sk, dk_t, dv_t, dq = carry
        # KV rotates eagerly (overlaps this step's kernels).
        k_nxt, v_nxt, sk_nxt = lax.ppermute(
            (k_blk, v_blk, sk), axis_name, perm
        )
        sk_cur = sk if has_seg else None
        if causal:
            src = (my - s) % n
            branch = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
            dq_c, dk_c, dv_c = lax.switch(
                branch, (_full, _diag, _skip), k_blk, v_blk, sk_cur
            )
        else:
            dq_c, dk_c, dv_c = _full(k_blk, v_blk, sk_cur)
        dq = dq + dq_c
        # The gradient accumulators travel WITH their K/V block: after the
        # full ring each block's dk/dv has collected every shard's
        # contribution and arrived back at the block's home shard. Rotating
        # them in their own ppermute (after accumulation) lets the transfer
        # overlap the NEXT step's kernels.
        dk_t, dv_t = lax.ppermute(
            (dk_t + dk_c, dv_t + dv_c), axis_name, perm
        )
        return (k_nxt, v_nxt, sk_nxt, dk_t, dv_t, dq), None

    sk0 = seg_kv if has_seg else jnp.zeros((1, 1), jnp.int32)
    (k, v, _sk, dk, dv, dq), _ = lax.scan(
        step, (k, v, sk0, dk0, dv0, dq0), jnp.arange(n)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _ring_flash(q, k, v, axis_name, causal, scale, block_q, block_k,
                interpret):
    out, _lse, _k, _v = _ring_flash_fwd_impl(
        q, k, v, None, None, axis_name, causal, scale, block_q, block_k,
        interpret
    )
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, block_q, block_k,
                    interpret):
    out, lse, k, v = _ring_flash_fwd_impl(
        q, k, v, None, None, axis_name, causal, scale, block_q, block_k,
        interpret
    )
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, block_q, block_k, interpret,
                    res, g):
    q, k, v, out, lse = res
    return _ring_flash_bwd_impl(
        q, k, v, None, None, out, lse, g, axis_name, causal, scale,
        block_q, block_k, interpret
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring_flash_seg(q, k, v, seg, axis_name, causal, scale, block_q,
                    block_k, interpret):
    out, _lse, _k, _v = _ring_flash_fwd_impl(
        q, k, v, seg, seg, axis_name, causal, scale, block_q, block_k,
        interpret
    )
    return out


def _ring_flash_seg_fwd(q, k, v, seg, axis_name, causal, scale, block_q,
                        block_k, interpret):
    out, lse, k, v = _ring_flash_fwd_impl(
        q, k, v, seg, seg, axis_name, causal, scale, block_q, block_k,
        interpret
    )
    return out, (q, k, v, seg, out, lse)


def _ring_flash_seg_bwd(axis_name, causal, scale, block_q, block_k,
                        interpret, res, g):
    q, k, v, seg, out, lse = res
    dq, dk, dv = _ring_flash_bwd_impl(
        q, k, v, seg, seg, out, lse, g, axis_name, causal, scale,
        block_q, block_k, interpret
    )
    return dq, dk, dv, None


_ring_flash_seg.defvjp(_ring_flash_seg_fwd, _ring_flash_seg_bwd)


# ---------------------------------------------------------------------------
# Zigzag causal ring (balanced): shard s holds chunks (s, 2n-1-s) of 2n.
#
# Work per (q-shard i, kv-block j), in chunk² units (chunk = T_local/2):
#   j < i ("past"):   [front_i + back_i] × front_j  = 2
#   j == i ("diag"):  ½ front-diag + back×front + ½ back-diag = 2
#   j > i ("future"): back_i × [front_j + back_j]  = 2
# — constant for every pair, so the causal ring's wall clock is ~half the
# non-causal ring's instead of equal to it. The KV ppermute for step s+1 is
# issued BEFORE step s's kernels (it depends only on the carried block), so
# XLA's async collective-permute overlaps the transfer with the compute; in
# the backward the travelling dk/dv accumulators rotate after accumulation
# and overlap the NEXT step's kernels.
# ---------------------------------------------------------------------------


def _zz_branch(my, s, n):
    src = (my - s) % n
    return jnp.where(src < my, 0, jnp.where(src == my, 1, 2))


def _zigzag_ring_flash_fwd_impl(q, k, v, seg, axis_name, scale, block_q,
                                block_k, interpret):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    C = Tq // 2
    kw = dict(scale=scale, block_q=block_q, block_k=block_k,
              interpret=interpret)
    has_seg = seg is not None
    qf, qb = q[:, :C], q[:, C:]
    sq_f = seg[:, :C] if has_seg else None
    sq_b = seg[:, C:] if has_seg else None
    of = jnp.zeros((B, C, H, D), jnp.float32)
    ob = jnp.zeros((B, C, H, D), jnp.float32)
    lf = jnp.full((B, H, C), NEG_INF, jnp.float32)
    lb = jnp.full((B, H, C), NEG_INF, jnp.float32)
    perm = _ring_perm(n)

    def _halves(sk):
        if not has_seg:
            return None, None
        return sk[:, :C], sk[:, C:]

    def _past(of, lf, ob, lb, k_blk, v_blk, sk):
        # Whole local q attends the block's FRONT chunk (fully past); the
        # block's back chunk is entirely in this shard's future.
        sk_f, _ = _halves(sk)
        o_n, l_n = flash_block_fwd(q, k_blk[:, :C], v_blk[:, :C],
                                   causal=False, seg_q=seg, seg_kv=sk_f,
                                   **kw)
        of, lf = merge_partials(of, lf, o_n[:, :C], l_n[..., :C])
        ob, lb = merge_partials(ob, lb, o_n[:, C:], l_n[..., C:])
        return of, lf, ob, lb

    def _diag(of, lf, ob, lb, k_blk, v_blk, sk):
        # Equal global offsets chunk-by-chunk: both diagonals are static
        # relative causal masks; back×front is fully past.
        sk_f, sk_b = _halves(sk)
        o_fd, l_fd = flash_block_fwd(qf, k_blk[:, :C], v_blk[:, :C],
                                     causal=True, seg_q=sq_f, seg_kv=sk_f,
                                     **kw)
        o_bf, l_bf = flash_block_fwd(qb, k_blk[:, :C], v_blk[:, :C],
                                     causal=False, seg_q=sq_b, seg_kv=sk_f,
                                     **kw)
        o_bd, l_bd = flash_block_fwd(qb, k_blk[:, C:], v_blk[:, C:],
                                     causal=True, seg_q=sq_b, seg_kv=sk_b,
                                     **kw)
        of, lf = merge_partials(of, lf, o_fd, l_fd)
        ob, lb = merge_partials(ob, lb, o_bf, l_bf)
        ob, lb = merge_partials(ob, lb, o_bd, l_bd)
        return of, lf, ob, lb

    def _future(of, lf, ob, lb, k_blk, v_blk, sk):
        # Only the local BACK chunk is after both of the block's chunks.
        o_n, l_n = flash_block_fwd(qb, k_blk, v_blk, causal=False,
                                   seg_q=sq_b, seg_kv=sk, **kw)
        ob, lb = merge_partials(ob, lb, o_n, l_n)
        return of, lf, ob, lb

    def step(carry, s):
        k_blk, v_blk, sk, of, lf, ob, lb = carry
        # Rotate FIRST: the permute depends only on the carried block, so it
        # runs concurrently with this step's kernels (double-buffered KV).
        k_nxt, v_nxt, sk_nxt = lax.ppermute(
            (k_blk, v_blk, sk), axis_name, perm
        )
        sk_cur = sk if has_seg else None
        of, lf, ob, lb = lax.switch(
            _zz_branch(my, s, n), (_past, _diag, _future),
            of, lf, ob, lb, k_blk, v_blk, sk_cur,
        )
        return (k_nxt, v_nxt, sk_nxt, of, lf, ob, lb), None

    sk0 = seg if has_seg else jnp.zeros((1, 1), jnp.int32)
    (k, v, _sk, of, lf, ob, lb), _ = lax.scan(
        step, (k, v, sk0, of, lf, ob, lb), jnp.arange(n)
    )
    o = jnp.concatenate([of, ob], axis=1).astype(q.dtype)
    lse = jnp.concatenate([lf, lb], axis=2)
    return o, lse, k, v


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _zigzag_ring_flash(q, k, v, axis_name, scale, block_q, block_k,
                       interpret):
    out, _lse, _k, _v = _zigzag_ring_flash_fwd_impl(
        q, k, v, None, axis_name, scale, block_q, block_k, interpret
    )
    return out


def _zigzag_ring_flash_fwd(q, k, v, axis_name, scale, block_q, block_k,
                           interpret):
    out, lse, k, v = _zigzag_ring_flash_fwd_impl(
        q, k, v, None, axis_name, scale, block_q, block_k, interpret
    )
    return out, (q, k, v, out, lse)


def _zigzag_ring_flash_bwd(axis_name, scale, block_q, block_k, interpret,
                           res, g):
    q, k, v, out, lse = res
    return _zigzag_ring_flash_bwd_impl(
        q, k, v, None, out, lse, g, axis_name, scale, block_q, block_k,
        interpret
    )


def _zigzag_ring_flash_bwd_impl(q, k, v, seg, out, lse, g, axis_name, scale,
                                block_q, block_k, interpret):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    C = Tq // 2
    kw = dict(scale=scale, block_q=block_q, block_k=block_k,
              interpret=interpret)
    has_seg = seg is not None
    qf, qb = q[:, :C], q[:, C:]
    sq_f = seg[:, :C] if has_seg else None
    sq_b = seg[:, C:] if has_seg else None
    do = g
    do_f, do_b = do[:, :C], do[:, C:]
    lse_f, lse_b = lse[..., :C], lse[..., C:]
    out_f, out_b = out[:, :C], out[:, C:]

    # dq pads at the Q head count; dk/dv pads at the KV head count (GQA:
    # flash_block_bwd group-sums dk/dv down to the kv heads).
    zQ = jnp.zeros((B, C, H, D), jnp.float32)
    zKV = jnp.zeros((B, C, k.shape[2], D), jnp.float32)
    perm = _ring_perm(n)

    def _halves(sk):
        if not has_seg:
            return None, None
        return sk[:, :C], sk[:, C:]

    def _past(k_blk, v_blk, sk):
        sk_f, _ = _halves(sk)
        dq_c, dkf, dvf = flash_block_bwd(
            q, k_blk[:, :C], v_blk[:, :C], do, lse, out,
            causal=False, seg_q=seg, seg_kv=sk_f, **kw,
        )
        return (dq_c,
                jnp.concatenate([dkf, zKV], axis=1),
                jnp.concatenate([dvf, zKV], axis=1))

    def _diag(k_blk, v_blk, sk):
        sk_f, sk_b = _halves(sk)
        dqf, dkf1, dvf1 = flash_block_bwd(
            qf, k_blk[:, :C], v_blk[:, :C], do_f, lse_f, out_f,
            causal=True, seg_q=sq_f, seg_kv=sk_f, **kw,
        )
        dqb1, dkf2, dvf2 = flash_block_bwd(
            qb, k_blk[:, :C], v_blk[:, :C], do_b, lse_b, out_b,
            causal=False, seg_q=sq_b, seg_kv=sk_f, **kw,
        )
        dqb2, dkb, dvb = flash_block_bwd(
            qb, k_blk[:, C:], v_blk[:, C:], do_b, lse_b, out_b,
            causal=True, seg_q=sq_b, seg_kv=sk_b, **kw,
        )
        dq_c = jnp.concatenate([dqf, dqb1 + dqb2], axis=1)
        return (dq_c,
                jnp.concatenate([dkf1 + dkf2, dkb], axis=1),
                jnp.concatenate([dvf1 + dvf2, dvb], axis=1))

    def _future(k_blk, v_blk, sk):
        dqb, dk_c, dv_c = flash_block_bwd(
            qb, k_blk, v_blk, do_b, lse_b, out_b, causal=False,
            seg_q=sq_b, seg_kv=sk, **kw,
        )
        return jnp.concatenate([zQ, dqb], axis=1), dk_c, dv_c

    def step(carry, s):
        k_blk, v_blk, sk, dk_t, dv_t, dq = carry
        # KV rotates eagerly (overlaps this step's kernels); the gradient
        # accumulators rotate after accumulation and overlap the next
        # step's kernels (they're consumed late in the next body).
        k_nxt, v_nxt, sk_nxt = lax.ppermute(
            (k_blk, v_blk, sk), axis_name, perm
        )
        sk_cur = sk if has_seg else None
        dq_c, dk_c, dv_c = lax.switch(
            _zz_branch(my, s, n), (_past, _diag, _future), k_blk, v_blk,
            sk_cur,
        )
        dk_t, dv_t = lax.ppermute(
            (dk_t + dk_c, dv_t + dv_c), axis_name, perm
        )
        return (k_nxt, v_nxt, sk_nxt, dk_t, dv_t, dq + dq_c), None

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    dq0 = jnp.zeros(q.shape, jnp.float32)
    sk0 = seg if has_seg else jnp.zeros((1, 1), jnp.int32)
    (k, v, _sk, dk, dv, dq), _ = lax.scan(
        step, (k, v, sk0, dk0, dv0, dq0), jnp.arange(n)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_zigzag_ring_flash.defvjp(_zigzag_ring_flash_fwd, _zigzag_ring_flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _zigzag_ring_flash_seg(q, k, v, seg, axis_name, scale, block_q, block_k,
                           interpret):
    out, _lse, _k, _v = _zigzag_ring_flash_fwd_impl(
        q, k, v, seg, axis_name, scale, block_q, block_k, interpret
    )
    return out


def _zigzag_ring_flash_seg_fwd(q, k, v, seg, axis_name, scale, block_q,
                               block_k, interpret):
    out, lse, k, v = _zigzag_ring_flash_fwd_impl(
        q, k, v, seg, axis_name, scale, block_q, block_k, interpret
    )
    return out, (q, k, v, seg, out, lse)


def _zigzag_ring_flash_seg_bwd(axis_name, scale, block_q, block_k,
                               interpret, res, g):
    q, k, v, seg, out, lse = res
    dq, dk, dv = _zigzag_ring_flash_bwd_impl(
        q, k, v, seg, out, lse, g, axis_name, scale, block_q, block_k,
        interpret
    )
    return dq, dk, dv, None


_zigzag_ring_flash_seg.defvjp(_zigzag_ring_flash_seg_fwd,
                              _zigzag_ring_flash_seg_bwd)


def ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "flash",
    layout: str = "contiguous",
    segment_ids: Optional[jax.Array] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ring attention over local shards — call INSIDE ``shard_map``.

    Args:
      q/k/v: local sequence shards ``[B, T_local, H, D]``; the global
        sequence is the concatenation over ``axis_name`` in ring order
        (``layout='contiguous'``) or the zigzag chunk-pair order
        (``layout='zigzag'`` — shard ``s`` holds global chunks
        ``(s, 2n-1-s)`` of ``2n``; see :func:`to_zigzag`).
      causal: apply a causal mask over *global* positions.
      impl: ``'flash'`` (Pallas block kernels, hand-written ring backward;
        the production path) or ``'einsum'`` (lax online-softmax blocks,
        autodiff through scan+ppermute; the correctness reference).
      layout: ``'zigzag'`` balances causal work across shards (constant 2
        chunk²/step everywhere vs the contiguous ring's last-shard
        bottleneck); requires ``causal=True`` and ``impl='flash'``.
      segment_ids: optional local ``[B, T_local]`` packed-segment id slice
        (flash impl only); kv ids travel with their block around the ring,
        so attention is confined to equal ids across the whole global
        sequence. K/V may also carry fewer heads than q (GQA/MQA) — kv
        blocks rotate at their own (smaller) size.
      interpret: run the Pallas kernels in interpreter mode. Inside
        ``shard_map`` the mesh platform is invisible, so the default guesses
        from the default backend/device — pass it explicitly when the
        enclosing mesh's platform differs (``make_ring_attention`` derives
        it from its mesh automatically).

    Returns:
      Local output shard ``[B, T_local, H, D]`` (dtype of ``q``).
    """
    if layout not in ("contiguous", "zigzag"):
        raise ValueError(
            f"layout must be 'contiguous' or 'zigzag', got {layout!r}"
        )
    if layout == "zigzag":
        if not causal or impl != "flash":
            raise ValueError(
                "layout='zigzag' exists to balance CAUSAL work and is "
                "implemented for impl='flash' (non-causal rings are already "
                "balanced — use layout='contiguous')"
            )
        if scale is None:
            scale = q.shape[-1] ** -0.5
        if interpret is None:
            interpret = _use_interpret()
        if segment_ids is not None:
            return _zigzag_ring_flash_seg(
                q, k, v, segment_ids.astype(jnp.int32), axis_name,
                float(scale), block_q, block_k, interpret
            )
        return _zigzag_ring_flash(
            q, k, v, axis_name, float(scale), block_q, block_k, interpret
        )
    if impl == "flash":
        if scale is None:
            scale = q.shape[-1] ** -0.5
        if interpret is None:
            interpret = _use_interpret()
        if segment_ids is not None:
            return _ring_flash_seg(
                q, k, v, segment_ids.astype(jnp.int32), axis_name, causal,
                float(scale), block_q, block_k, interpret
            )
        return _ring_flash(
            q, k, v, axis_name, causal, float(scale), block_q, block_k,
            interpret,
        )
    if impl != "einsum":
        raise ValueError(f"impl must be 'flash' or 'einsum', got {impl!r}")
    if segment_ids is not None:
        raise NotImplementedError(
            "segment_ids requires impl='flash' (the production path)"
        )

    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if k.shape[2] != H:
        # GQA in the reference path: materialize the head repeat (autodiff's
        # transpose sums the group — matching the kernel path's group-sum).
        rep = H // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    o = jnp.zeros((B, Tq, H, D), jnp.float32)
    m = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, Tq), jnp.float32)

    # Rotate kv by +1 each step: after step s this shard holds the block that
    # started on shard (my - s) % n.
    perm = _ring_perm(n)

    def body(carry, s):
        k_blk, v_blk, o, m, l = carry
        src = (my - s) % n
        o, m, l = online_softmax_block(
            q, k_blk, v_blk, o, m, l,
            causal=causal,
            q_offset=my * Tq,
            kv_offset=src * Tk,
            scale=scale,
        )
        k_blk, v_blk = lax.ppermute((k_blk, v_blk), axis_name, perm)
        return (k_blk, v_blk, o, m, l), None

    (k, v, o, m, l), _ = lax.scan(body, (k, v, o, m, l), jnp.arange(n))
    return finalize_online_softmax(o, l, q.dtype)


# ---------------------------------------------------------------------------
# Plan-provider ring (ISSUE 13): statically UNROLLED, n-1 forward hops.
#
# The scan-based rings above rotate n times (the last rotation brings K/V
# home for the backward's residuals); fine for a loop the HLO shows once,
# but the ParallelPlan's structural acceptance pins the compiled program's
# collective-permute COUNT at ``n_seq_shards - 1`` per layer per forward
# ring pass — the minimal neighbour exchange (block s needs n-1 hops to
# visit every other shard). So the plan's provider unrolls the ring over
# the static mesh size, rotates K and V as ONE stacked array (one
# collective-permute per hop), and skips the useless homing hop; the
# custom-vjp backward restarts from the saved home K/V (they are the
# function's own inputs — nothing to re-gather). Backward counts, also
# pinned: n-1 kv hops (same argument) plus n hops for the travelling
# dk/dv accumulator — it starts at home, must visit all n shards, and
# needs one extra hop to come home after the last accumulation.
# ---------------------------------------------------------------------------


def _seq_ring_fwd_impl(q, k, v, axis_name, causal, scale, block_q, block_k,
                       interpret):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    kw = dict(scale=scale, block_q=block_q, block_k=block_k,
              interpret=interpret)
    o = jnp.zeros((B, Tq, H, D), jnp.float32)
    lse = jnp.full((B, H, Tq), NEG_INF, jnp.float32)
    perm = _ring_perm(n)

    def _full(o, lse, k_blk, v_blk):
        o_b, lse_b = flash_block_fwd(q, k_blk, v_blk, causal=False, **kw)
        return merge_partials(o, lse, o_b, lse_b)

    def _diag(o, lse, k_blk, v_blk):
        o_b, lse_b = flash_block_fwd(q, k_blk, v_blk, causal=True, **kw)
        return merge_partials(o, lse, o_b, lse_b)

    def _skip(o, lse, k_blk, v_blk):
        return o, lse

    kv = jnp.stack([k, v])
    for s in range(n):
        # Rotate FIRST (depends only on the carried pair) so the async
        # collective-permute overlaps this step's kernels — but never
        # after the LAST step: the homing hop is pure waste and the
        # ppermute-count pin forbids it.
        kv_next = lax.ppermute(kv, axis_name, perm) if s + 1 < n else None
        k_blk, v_blk = kv[0], kv[1]
        if causal:
            src = (my - s) % n
            branch = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
            o, lse = lax.switch(
                branch, (_full, _diag, _skip), o, lse, k_blk, v_blk
            )
        else:
            o, lse = _full(o, lse, k_blk, v_blk)
        if kv_next is not None:
            kv = kv_next
    return o.astype(q.dtype), lse


def _seq_ring_bwd_impl(q, k, v, out, lse, g, axis_name, causal, scale,
                       block_q, block_k, interpret):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    kw = dict(scale=scale, block_q=block_q, block_k=block_k,
              interpret=interpret)
    do = g
    perm = _ring_perm(n)

    def _full(k_blk, v_blk):
        return flash_block_bwd(q, k_blk, v_blk, do, lse, out,
                               causal=False, **kw)

    def _diag(k_blk, v_blk):
        return flash_block_bwd(q, k_blk, v_blk, do, lse, out,
                               causal=True, **kw)

    def _skip(k_blk, v_blk):
        return (jnp.zeros(q.shape, jnp.float32),
                jnp.zeros(k_blk.shape, jnp.float32),
                jnp.zeros(v_blk.shape, jnp.float32))

    kv = jnp.stack([k, v])
    dkv = jnp.zeros((2,) + k.shape, jnp.float32)
    dq = jnp.zeros(q.shape, jnp.float32)
    for s in range(n):
        kv_next = lax.ppermute(kv, axis_name, perm) if s + 1 < n else None
        k_blk, v_blk = kv[0], kv[1]
        if causal:
            src = (my - s) % n
            branch = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
            dq_c, dk_c, dv_c = lax.switch(
                branch, (_full, _diag, _skip), k_blk, v_blk
            )
        else:
            dq_c, dk_c, dv_c = _full(k_blk, v_blk)
        dq = dq + dq_c
        # The accumulator travels WITH its block and rotates after EVERY
        # accumulation (n hops total): after the last one the block's
        # dk/dv sits one shard past its last visit — exactly home.
        dkv = lax.ppermute(dkv + jnp.stack([dk_c, dv_c]), axis_name, perm)
        if kv_next is not None:
            kv = kv_next
    return (dq.astype(q.dtype), dkv[0].astype(k.dtype),
            dkv[1].astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _seq_ring(q, k, v, axis_name, causal, scale, block_q, block_k,
              interpret):
    out, _lse = _seq_ring_fwd_impl(
        q, k, v, axis_name, causal, scale, block_q, block_k, interpret
    )
    return out


def _seq_ring_fwd(q, k, v, axis_name, causal, scale, block_q, block_k,
                  interpret):
    out, lse = _seq_ring_fwd_impl(
        q, k, v, axis_name, causal, scale, block_q, block_k, interpret
    )
    # Home k/v are the function's own inputs — saving them costs nothing
    # and lets the backward ring start without the scan rings' homing
    # rotation.
    return out, (q, k, v, out, lse)


def _seq_ring_bwd(axis_name, causal, scale, block_q, block_k, interpret,
                  res, g):
    q, k, v, out, lse = res
    return _seq_ring_bwd_impl(
        q, k, v, out, lse, g, axis_name, causal, scale, block_q, block_k,
        interpret
    )


_seq_ring.defvjp(_seq_ring_fwd, _seq_ring_bwd)


def seq_ring_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str = "seq",
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The ParallelPlan ``seq``-axis ring — call INSIDE ``shard_map``.

    Same contract as :func:`ring_attention_local` (contiguous layout,
    flash kernels, GQA via smaller K/V head counts), but the ring is
    statically unrolled with exactly ``n - 1`` K/V hops per forward pass
    and ``(n - 1) + n`` per backward (kv + travelling dk/dv accumulator)
    — each hop ONE ``collective-permute`` of the stacked (K, V) pair, so
    the plan's structural HLO-count acceptance can pin the program
    (tests/test_sequence_parallel.py). Signature matches the
    ``attention_fn`` contract of
    :class:`~chainermn_tpu.models.transformer.TransformerBlock`.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = _use_interpret()
    try:
        from chainermn_tpu.observability import trace as _trace

        rec = _trace.active()
    except Exception:
        rec = None
    if rec is not None:
        # Trace-time layout event (the in-jit bucketed schedules'
        # convention — what the compiled program COMMITTED to, once per
        # compile, no duration): one forward ring pass moves the
        # stacked (K, V) pair n-1 hops; overlapped=True because the
        # hop is issued before the step's kernels (async
        # collective-permute rides behind compute by construction).
        n = lax.axis_size(axis_name)
        per_hop = 2 * k.size * jnp.dtype(k.dtype).itemsize
        rec.event(
            "wire", schedule="seq_ring", axis=str(axis_name),
            hops=n - 1, bucket=0, n_buckets=1,
            nbytes=per_hop * (n - 1),
            wire_dtype=str(k.dtype), overlapped=True,
        )
    return _seq_ring(q, k, v, axis_name, bool(causal), float(scale),
                     int(block_q), int(block_k), bool(interpret))


def make_ring_attention(
    mesh: Mesh,
    axis_name: str = "seq",
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    batch_axis: Optional[str] = None,
    impl: str = "flash",
    layout: str = "contiguous",
    with_segments: bool = False,
):
    """Jitted ring attention over globally (sequence-)sharded BTHD arrays.

    Returns ``fn(q, k, v) -> out`` (or ``fn(q, k, v, segment_ids)`` when
    ``with_segments``) where inputs/outputs are global arrays whose sequence
    dim is sharded over ``axis_name`` (and batch over ``batch_axis`` when
    given). With ``layout='zigzag'`` the fn reorders the global sequence
    into zigzag chunk-pair order at entry and back at exit (two gathers;
    amortise them by keeping the whole model in zigzag layout and calling
    :func:`ring_attention_local` inside your own ``shard_map`` instead).
    The returned fn composes under a larger jitted program.
    """
    from jax import shard_map

    spec = P(batch_axis, axis_name, None, None)
    seg_spec = P(batch_axis, axis_name)
    # The mesh knows where this will execute; don't guess from the default
    # backend (a CPU test mesh can sit beside a live TPU backend).
    interpret = interpret_on(mesh.devices.flat[0].platform)
    n = mesh.shape[axis_name]

    def local(q, k, v, seg=None):
        return ring_attention_local(
            q, k, v, axis_name, causal=causal, scale=scale, impl=impl,
            layout=layout, segment_ids=seg, interpret=interpret,
        )

    if with_segments:
        fn = shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec, seg_spec),
            out_specs=spec, check_vma=False,
        )
    else:
        fn = shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )

    if layout == "zigzag":
        def zz(q, k, v, seg=None):
            q, k, v = (to_zigzag(t, n, axis=1) for t in (q, k, v))
            if with_segments:
                out = fn(q, k, v, to_zigzag(seg, n, axis=1))
            else:
                out = fn(q, k, v)
            return from_zigzag(out, n, axis=1)

        return jax.jit(zz)
    return jax.jit(fn)
