"""Ulysses (DeepSpeed-style) sequence parallelism: all_to_all head↔sequence
reshard.

NEW capability relative to the reference (SURVEY.md section 5). Where ring
attention streams K/V around the ring, Ulysses *re-shards*: inputs arrive
sequence-sharded, one ``all_to_all`` turns them head-sharded with the full
sequence locally, plain (flash/blockwise) attention runs per-head, and a
second ``all_to_all`` restores sequence sharding. Two collectives total —
cheaper than the ring when heads >= axis size and the full sequence fits.

Constraint: ``num_heads`` must be divisible by the axis size (heads are the
resharding currency).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu.ops.attention import blockwise_attention
from chainermn_tpu.ops.flash_attention import flash_attention, interpret_on


def check_ulysses_divisibility(q_heads: int, kv_heads: int, n: int,
                               *, axis_name: str = "seq") -> None:
    """Reject head counts Ulysses cannot reshard, naming BOTH numbers.

    Heads are the resharding currency: the two ``all_to_all``s split the
    head dim ``n`` ways, so ``q_heads % n`` and ``kv_heads % n`` must
    both be 0. Raised at ENTRY (``make_ulysses_attention``'s returned fn
    and the plan's ``seq_attn_impl`` resolver call this before any
    ``shard_map`` trace) so the caller sees the arithmetic, not a shape
    error from inside the collective (ISSUE 13 satellite — previously
    the check only fired mid-trace and had to be caught by the caller).
    """
    for name, h in (("q", int(q_heads)), ("kv", int(kv_heads))):
        if h % n != 0:
            raise ValueError(
                f"ulysses: {name} heads {h} not divisible by axis "
                f"{axis_name!r} size {n} — pad the head count, shrink "
                f"the seq axis, or use the ring provider (seq_attn_impl="
                f"'ring'), which has no divisibility constraint"
            )


def ulysses_attention_local(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    attn_fn: Optional[Callable] = None,
    impl: str = "flash",
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Ulysses attention over local shards — call INSIDE ``shard_map``.

    Args:
      q/k/v: local sequence shards ``[B, T_local, H, D]``; global heads H
        must be divisible by the axis size. K/V may carry fewer heads
        (GQA/MQA) — they too must be divisible by the axis size.
      attn_fn: local attention ``fn(q, k, v, causal=..., scale=...)`` on
        ``[B, T, H_local, D]``; overrides ``impl`` when given.
      impl: ``'flash'`` — the Pallas kernel (fwd+bwd; the production path,
        same kernels as ring attention) — or ``'blockwise'`` (lax scan
        reference). ``interpret`` as in
        :func:`chainermn_tpu.parallel.ring_attention.ring_attention_local`.
      segment_ids: optional local ``[B, T_local]`` packed-segment slice;
        all-gathered (ids only — tiny) so the head-sharded full-sequence
        attention sees the whole mask. Requires ``impl='flash'`` or a
        segment-capable ``attn_fn``.
      window: causal sliding-window width, handed to the flash kernel
        (banded grids — heads are sharded here, so each device runs the
        full-sequence window band over its own heads). Requires
        ``causal=True`` and ``impl='flash'``.

    Returns:
      Local output shard ``[B, T_local, H, D]``.
    """
    n = lax.axis_size(axis_name)
    check_ulysses_divisibility(q.shape[2], k.shape[2], n,
                               axis_name=axis_name)
    if window is not None and (impl != "flash" or attn_fn is not None):
        raise ValueError(
            "window is implemented by the flash kernel — use impl='flash' "
            "without a custom attn_fn (or honour the window inside your "
            "attn_fn yourself)"
        )
    if attn_fn is None:
        if impl == "flash":
            def attn_fn(q, k, v, *, causal, scale, **kw):
                return flash_attention(
                    q, k, v, causal=causal, scale=scale, interpret=interpret,
                    window=window, **kw,
                )
        elif impl == "blockwise":
            if segment_ids is not None:
                raise ValueError(
                    "segment_ids requires impl='flash' (or a "
                    "segment-capable attn_fn)"
                )
            attn_fn = blockwise_attention
        else:
            raise ValueError(
                f"impl must be 'flash' or 'blockwise', got {impl!r}"
            )

    def seq_to_heads(x):
        # [B, T/n, H, D] -> [B, T, H/n, D]
        return lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    kw = {}
    if segment_ids is not None:
        kw["segment_ids"] = lax.all_gather(
            segment_ids, axis_name, axis=1, tiled=True
        )
    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = attn_fn(qh, kh, vh, causal=causal, scale=scale, **kw)
    return heads_to_seq(out)


def make_ulysses_attention(
    mesh: Mesh,
    axis_name: str = "seq",
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    attn_fn: Optional[Callable] = None,
    batch_axis: Optional[str] = None,
    impl: str = "flash",
    with_segments: bool = False,
    window: Optional[int] = None,
):
    """Jitted Ulysses attention over globally sequence-sharded BTHD arrays
    (counterpart of :func:`chainermn_tpu.parallel.make_ring_attention`).
    With ``with_segments`` the returned fn takes ``(q, k, v, segment_ids)``."""
    from jax import shard_map

    spec = P(batch_axis, axis_name, None, None)
    seg_spec = P(batch_axis, axis_name)
    interpret = interpret_on(mesh.devices.flat[0].platform)
    n = mesh.shape[axis_name]

    def local(q, k, v, seg=None):
        return ulysses_attention_local(
            q, k, v, axis_name, causal=causal, scale=scale, attn_fn=attn_fn,
            impl=impl, segment_ids=seg, window=window, interpret=interpret,
        )

    in_specs = (spec, spec, spec) + ((seg_spec,) if with_segments else ())
    fn = shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=spec,
        check_vma=False,
    )
    jitted = jax.jit(fn)

    def checked(q, k, v, *rest):
        # Divisibility rejected at ENTRY, with global head counts —
        # not from inside the shard_map trace.
        check_ulysses_divisibility(q.shape[2], k.shape[2], n,
                                   axis_name=axis_name)
        return jitted(q, k, v, *rest)

    return checked
