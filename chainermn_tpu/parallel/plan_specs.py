"""Spec layer of the :class:`~chainermn_tpu.parallel.plan.ParallelPlan`.

The reference expressed every parallel form as a *call-site wrapper* around
a per-process communicator (``communicators/`` (dagger), SURVEY.md
section 2.1); here the per-axis modules are **spec providers** instead:
each publishes a small descriptor — how its parameter/opt-state leaves lay
out over its mesh axis, and which HLO collectives it owes the compiled
step — and this module turns those descriptors plus the user's per-leaf
``PartitionSpec`` tree into the concrete shard_map specs and update groups
one compiled train step composes.

Provider contract (``{tensor,zero,pipeline}.{tp,zero,pipe}_plan_axis``):

- ``name``: the mesh axis name;
- ``stacked``: parameter leaves sharded by this axis stack a leading
  ``[n, ...]`` shard dim (``stack_tp_params`` / ``stack_stage_params``
  layout) carried with ``P(axis)`` and collapsed inside the program;
- ``state_stacked``: the axis shards the *optimizer state* (ZeRO): state
  leaves stack ``[n, ...]`` chunks over the axis, params stay replicated;
- ``collectives``: the HLO collective ops the axis owes the step — the
  vocabulary of the structural count tests (``all-reduce``,
  ``reduce-scatter``, ``all-gather``, ``collective-permute``).

The ``data`` axis is the plain data-parallel provider and lives here (it
has no module of its own: its only artifact is the gradient ``pmean``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import PartitionSpec as P

PyTree = Any

#: Canonical mesh-axis order: DCN-tolerant axes first, ICI-hungry last
#: (the repo's mesh convention — the fast/intra axis sits last). ``data``
#: tolerates DCN (one allreduce/step), ``model`` wants ICI (one psum per
#: layer pair), ``zero``/``pipe`` sit between; ``seq`` (ring-attention
#: neighbour exchange per layer, ISSUE 13) sits just before ``model`` —
#: its ppermutes want ICI, but only to a neighbour, so ``model``'s
#: all-reduces keep the fastest slot. ``expert`` (MoE all_to_all token
#: dispatch, ISSUE 20) sits between ``seq`` and ``model``: its two
#: per-layer all_to_alls move full token payloads and want ICI, but
#: ``model``'s per-layer-pair all-reduces still claim the fastest slot
#: (an a2a moves 1/n of the payload per link the allreduce moves twice).
CANONICAL_AXES = ("data", "zero", "pipe", "seq", "expert", "model")

#: the ``seq_attn_impl`` tuning decision's candidates and the HLO
#: collectives each routes the compiled step through (what
#: :meth:`~chainermn_tpu.parallel.plan.ParallelPlan.seq_attention`
#: substitutes into the axis descriptor once the impl is resolved).
SEQ_ATTN_IMPLS = ("ring", "ulysses")
SEQ_IMPL_COLLECTIVES = {
    # n-1 kv hops/layer/pass (the unrolled plan ring) + the one grad mean
    "ring": ("collective-permute", "all-reduce"),
    # two reshards in, one out, per layer + the one grad mean
    "ulysses": ("all-to-all", "all-reduce"),
}


def seq_plan_axis(impl: str = "ring", axis_name: str = "seq") -> dict:
    """Spec-provider descriptor for the ``seq`` axis (ISSUE 13): the
    batch's SEQUENCE dim shards over it (``ParallelPlan.batch_spec``
    appends it after the dp axes), params and optimizer state stay
    replicated (it is token parallelism, not weight parallelism), and it
    owes the compiled step one gradient all-reduce plus the per-layer
    attention collectives of the routed impl —
    :func:`~chainermn_tpu.parallel.ring_attention.
    seq_ring_attention_local` (``collective-permute``, the default) or
    :func:`~chainermn_tpu.parallel.ulysses.ulysses_attention_local`
    (``all-to-all``)."""
    if impl not in SEQ_ATTN_IMPLS:
        raise ValueError(
            f"seq_plan_axis impl must be one of {SEQ_ATTN_IMPLS}, got "
            f"{impl!r}"
        )
    return {
        "name": axis_name,
        "stacked": False,
        "state_stacked": False,
        "collectives": SEQ_IMPL_COLLECTIVES[impl],
    }


def moe_plan_axis(axis_name: str = "expert") -> dict:
    """Spec-provider descriptor for the ``expert`` axis (ISSUE 20 — MoE
    expert parallelism over :func:`~chainermn_tpu.parallel.moe.
    moe_layer_local`): expert parameter leaves STACK a leading
    ``[n, ...]`` shard dim (``P('expert')`` — each shard hosts its slice
    of the expert set, :func:`~chainermn_tpu.parallel.moe.
    make_expert_params` layout), the batch's token dim shards over the
    axis too (``ParallelPlan.batch_spec`` folds it into the dp tuple —
    the axis is extra data parallelism for every NON-expert leaf), and
    it owes the compiled step exactly two ``all-to-all``s per MoE layer
    per pass (dispatch + combine; their backward transposes are again
    all_to_alls) plus the one fused gradient all-reduce that makes
    replicated leaves' grads the global token mean. Expert-stacked
    leaves take NO collective over the axis: the all_to_all's exact
    transpose already accumulates every shard's cotangents onto the
    owning shard (the plan rescales them to the mean)."""
    return {
        "name": axis_name,
        "stacked": True,
        "state_stacked": False,
        "collectives": ("all-to-all", "all-reduce"),
    }


@dataclasses.dataclass(frozen=True)
class AxisSpec:
    """One resolved plan axis: the provider descriptor plus its size."""

    name: str
    size: int
    stacked: bool
    state_stacked: bool
    collectives: tuple[str, ...]


def _provider(role: str) -> dict:
    if role == "data":
        return {
            "name": "data",
            "stacked": False,
            "state_stacked": False,
            "collectives": ("all-reduce",),
        }
    if role == "zero":
        from chainermn_tpu.parallel.zero import zero_plan_axis

        return zero_plan_axis()
    if role == "model":
        from chainermn_tpu.parallel.tensor import tp_plan_axis

        return tp_plan_axis()
    if role == "pipe":
        from chainermn_tpu.parallel.pipeline import pipe_plan_axis

        return pipe_plan_axis()
    if role == "seq":
        return seq_plan_axis()
    if role == "expert":
        return moe_plan_axis()
    raise ValueError(
        f"unknown plan axis {role!r}: a ParallelPlan composes "
        f"{CANONICAL_AXES} (any subset)"
    )


def resolve_axes(sizes: Mapping[str, int]) -> dict[str, AxisSpec]:
    """Resolve provider descriptors for ``sizes`` (name -> size), in
    canonical mesh order."""
    for name in sizes:
        if name not in CANONICAL_AXES:
            _provider(name)  # raises with the canonical list
    out: dict[str, AxisSpec] = {}
    for name in CANONICAL_AXES:
        if name not in sizes:
            continue
        d = _provider(name)
        out[name] = AxisSpec(
            name=d["name"],
            size=int(sizes[name]),
            stacked=bool(d["stacked"]),
            state_stacked=bool(d["state_stacked"]),
            collectives=tuple(d["collectives"]),
        )
    return out


def normalize_param_specs(
    params: PyTree,
    specs: PyTree | None,
    axes: Mapping[str, AxisSpec],
) -> PyTree:
    """Expand the user's spec tree to a FULL per-leaf ``PartitionSpec``
    tree over ``params`` and validate it against the plan's axes.

    ``specs`` may be ``None`` (everything replicated), a single ``P``
    (broadcast), or a prefix pytree of ``P`` leaves (each broadcast over
    its params subtree). Each leaf spec must be ``P()``, ``P(axis)``,
    or a canonical-order run of *stacked* plan axes
    (``P('pipe', 'model')`` — the composed pipe x model plan, ISSUE 13)
    — the leading-stack convention of
    :func:`~chainermn_tpu.parallel.tensor.stack_tp_params` /
    :func:`~chainermn_tpu.parallel.pipeline.stack_stage_params`,
    one leading dim per named axis — and each leading dim must equal
    its axis's size.
    """
    if specs is None:
        specs = P()
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    if is_spec(specs):
        full = jax.tree.map(lambda _: specs, params)
    else:
        full = jax.tree.map(
            lambda s, sub: jax.tree.map(lambda _: s, sub),
            specs,
            params,
            is_leaf=is_spec,
        )

    def check(spec, leaf):
        if not isinstance(spec, P):
            raise TypeError(
                f"param specs must be jax.sharding.PartitionSpec leaves, "
                f"got {type(spec).__name__}"
            )
        entries = tuple(spec)
        if not entries:
            return spec
        if any(e is None for e in entries):
            raise ValueError(
                f"plan param specs use the leading-stack convention: "
                f"P() or P(<stacked axes...>), got {spec}"
            )
        for ax in entries:
            if ax not in axes or not axes[ax].stacked:
                stacked = [a for a, s in axes.items() if s.stacked]
                raise ValueError(
                    f"param spec {spec} names {ax!r}, but this plan's "
                    f"stacked axes are {stacked} (zero/data/seq shard "
                    f"state, batch and activations, never parameter "
                    f"leaves)"
                )
        order = [CANONICAL_AXES.index(a) for a in entries]
        if len(set(entries)) != len(entries) or order != sorted(order):
            raise ValueError(
                f"multi-axis param spec {spec} must name distinct "
                f"stacked axes in canonical order {CANONICAL_AXES}"
            )
        shape = jax.numpy.shape(leaf)
        for d, ax in enumerate(entries):
            lead = shape[d] if len(shape) > d else None
            if lead != axes[ax].size:
                raise ValueError(
                    f"leaf sharded {spec} must stack "
                    f"[{axes[ax].size}, ...] over {ax!r} at dim {d}; "
                    f"got leading dim {lead} "
                    f"(use stack_tp_params / stack_stage_params)"
                )
        return spec

    return jax.tree.map(check, full, params)


def partition_groups(
    flat_specs: Sequence[P],
    axes: Mapping[str, AxisSpec],
) -> dict[str, list[int]]:
    """Split flattened param leaves into update groups by their spec.

    - each stacked spec (``model``, ``pipe``, or the composed
      ``pipe+model`` — keyed by ``'+'.join(axes)``) gets its own group:
      state mirrors the stacked params (already factored ``1/n`` over
      those axes), updated per shard;
    - replicated leaves form the ``'zero'`` group when a
      ``state_stacked`` axis is present (their state chunks over it), or
      the plain ``'rep'`` group otherwise.

    A leaf cannot belong to both a stacked axis AND the zero group by
    default: a TP/pipe-sharded parameter's optimizer state is already
    sharded ``n``-ways by construction, so ZeRO applies to the
    replicated leaves — the spec-provider contract (docs/parallelism.md).
    ``ParallelPlan(zero_stacked_groups=True)`` additionally chunks the
    STACKED groups' state over the zero axis (the cross-replica
    weight-update sharding of arXiv:2004.13336 applied per TP/pipe
    shard, ISSUE 13) — that changes the state layout and update wiring,
    not the grouping here.
    """
    has_zero = any(s.state_stacked for s in axes.values())
    groups: dict[str, list[int]] = {}
    for i, spec in enumerate(flat_specs):
        entries = tuple(spec)
        if entries:
            key = "+".join(entries)
        else:
            key = "zero" if has_zero else "rep"
        groups.setdefault(key, []).append(i)
    return groups


def group_stack_axes(group: str) -> tuple[str, ...]:
    """The stacked mesh axes a :func:`partition_groups` key names (empty
    for the ``zero``/``rep`` groups)."""
    if group in ("zero", "rep"):
        return ()
    return tuple(group.split("+"))


def owed_collectives(axes: Mapping[str, AxisSpec]) -> dict[str, tuple]:
    """Per-axis collective vocabulary — what the structural tests count."""
    return {name: spec.collectives for name, spec in axes.items()}
