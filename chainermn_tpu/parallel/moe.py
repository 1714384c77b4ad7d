"""Expert parallelism (MoE) — token routing to experts, in two forms.

Absent from the reference (SURVEY.md section 2.2 lists EP as the optional
TPU-era extension).

**Capacity-bounded, over an ``'expert'`` mesh axis** (:func:`moe_layer_local`
and what it is built from; the serving engine's and the plan's path): each
shard hosts one or more experts; a top-1 or top-k router scores tokens,
tokens travel to their expert's shard via ``all_to_all``, the expert MLP
runs, and a second ``all_to_all`` returns outputs — the same two-collective
shape as Ulysses sequence parallelism, with fixed-size queues making every
shape static for XLA. Each expert processes at most ``capacity =
ceil(tokens/experts * capacity_factor)`` tokens per shard; **this path
drops what overflows** (standard Switch-style routing) and a dropped
token's output falls back to zero — callers add the residual path so it
passes through unchanged. ``capacity_factor=None`` sets ``capacity =
tokens`` and drops nothing, at a queue of ``tokens`` rows an expert.

**Dropless, sorted and ragged** (:func:`dropless_topk`, :func:`dispatch`,
:func:`combine`; the training path of a model with many small experts):
every token's top-k (token, slot) rows are sorted by expert, the experts
run as grouped matmuls over ragged groups
(:func:`chainermn_tpu.ops.grouped_matmul.grouped_matmul`), and the rows
are summed back with their gates. **This path drops nothing** and pads
nothing: exactly ``tokens * k`` rows pass through the experts whatever the
routing. On one chip there is no collective; an expert axis puts its two
``all_to_all``s between :func:`dispatch` and the experts and between the
experts and :func:`combine`. A caller that holds a share of the experts
(``held``) gets the part of the result its own experts give: the choice
and the gates are over all experts, the rows of the absent ones lie in no
group and add nothing, and nothing stands in for the exchange. Such a
caller's section from :func:`dispatch` to :func:`combine` is
:func:`experts_in_rounds`: the same values from rounds of a static number
of sorted rows, as many rounds as the held rows need, so the absent
experts' rows cost their place in the sort and in the sum back and
nothing between.

Differentiable end to end: routing uses straight-through softmax gating
(gradient flows through the gate probability, not the indices), and
``all_to_all`` has an exact transpose.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from chainermn_tpu.observability import train_path

PyTree = Any


def _dense_from_slots(slots, logits, capacity):
    """Expand index-form routing into the dense ``(dispatch, combine)``
    pair (``[T, E, C]`` each, ``logits.dtype`` dispatch / f32-promoted
    gates as before)."""
    n_experts = logits.shape[-1]
    sentinel = n_experts * capacity
    tokens = logits.shape[0]
    dispatch = jnp.zeros((tokens, n_experts, capacity), logits.dtype)
    combine = None
    for slot, gate in slots:
        # one_hot over sentinel+1 classes; the sentinel (dropped) column is
        # sliced off, zeroing dropped tokens.
        oh = jax.nn.one_hot(slot, sentinel + 1, dtype=logits.dtype)
        oh = oh[:, :sentinel].reshape(tokens, n_experts, capacity)
        dispatch = dispatch + oh
        term = oh * gate[:, None, None]
        combine = term if combine is None else combine + term
    return dispatch, combine


def top1_route(
    logits: jax.Array,  # [tokens, n_experts]
    capacity: int,
):
    """Switch-style top-1 routing with capacity.

    Returns:
      dispatch: ``[tokens, n_experts, capacity]`` one-hot dispatch mask.
      combine:  same shape, dispatch * gate probability (for the return
        trip, carries the gradient to the router).
    """
    return _dense_from_slots(
        route_slots(logits, capacity, 1), logits, capacity
    )


def topk_route(
    logits: jax.Array,  # [tokens, n_experts]
    capacity: int,
    k: int = 2,
):
    """GShard-style top-k routing with capacity (k=2 is the classic
    configuration; k=1 degenerates to :func:`top1_route` up to gate
    normalisation).

    Each token's k chosen experts receive it in slot order (slot 0 fills
    queues first); gates are the chosen experts' softmax probabilities
    normalised over the k choices. An overflowed (dropped) choice's share
    is simply lost — the kept choice keeps its normalised weight
    ``g_kept/(g1+..+gk)``, it is NOT re-scaled to 1 (GShard semantics;
    the residual path covers the dropped mass). Returns the same
    ``(dispatch, combine)`` pair as :func:`top1_route`
    (``[tokens, n_experts, capacity]``).

    All routing bookkeeping lives in :func:`route_slots` (shared with the
    sort dispatch path, so the two ``dispatch_impl``s cannot drift).
    """
    return _dense_from_slots(
        route_slots(logits, capacity, k), logits, capacity
    )


def load_balancing_loss(
    logits: jax.Array, axis_name=None, k: int = 1
) -> jax.Array:
    """Switch/GShard auxiliary load-balancing loss:
    ``n_experts * sum_e(fraction_of_tokens_e * mean_router_prob_e)``,
    ``fraction_of_tokens_e`` the share of tokens that hold expert ``e``
    among their top ``k`` (so the fractions sum to ``k``: the form of the
    OLMoE paper and of Hugging Face's ``load_balancing_loss_func``; with
    the default ``k=1`` the top-1 assignment fraction of Switch) —
    ``k`` at perfect balance, grows as routing collapses onto few experts.
    Add ``aux_weight * load_balancing_loss(logits)`` to the task loss.

    ``axis_name``: when the token dim is SHARDED over mesh axes, pass
    the axis name (or tuple of names — e.g. ``('data', 'expert')`` under
    a composed plan) — the per-expert fraction and mean probability are
    pmean'd over the axes before the product, so the value is invariant
    to token-shard layout (the loss of the GLOBAL batch, identical to
    computing it locally over the gathered logits; equal-sized shards
    assumed, as everywhere in the plan).
    """
    n_experts = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    if k == 1:
        # fraction of tokens whose top-1 choice is each expert
        held = jax.nn.one_hot(jnp.argmax(probs, -1), n_experts,
                              dtype=probs.dtype)
    else:
        # ... that hold each expert among their k (ties: the lower index)
        held = jax.nn.one_hot(lax.top_k(probs, k)[1], n_experts,
                              dtype=probs.dtype).sum(-2)
    frac = held.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    if axis_name is not None:
        frac = lax.pmean(frac, axis_name)
        mean_prob = lax.pmean(mean_prob, axis_name)
    return n_experts * jnp.sum(frac * mean_prob)


def routing_stats(logits: jax.Array, capacity: int, k: int = 1) -> dict:
    """Drop/pad accounting for one routing pass (shard-local; callers
    inside ``shard_map`` psum the counts over the expert axis —
    :func:`moe_layer_local` with ``return_stats=True`` does).

    Returns float32 scalars/vectors (so they ride the plan's metric
    pmean): ``expert_load`` ``[n_experts]`` kept-token counts per
    expert, ``dropped`` (capacity-overflow assignments, the tokens the
    residual path carries), ``padded`` (empty queue slots shipped over
    the wire anyway — the static-shape tax), and ``capacity``.
    """
    n_experts = logits.shape[-1]
    sentinel = n_experts * capacity
    load = jnp.zeros((n_experts,), jnp.float32)
    dropped = jnp.zeros((), jnp.float32)
    for slot, _ in route_slots(logits, capacity, k):
        kept = slot != sentinel
        expert = jnp.where(kept, slot // capacity, 0)
        load = load + jnp.where(
            kept[:, None],
            jax.nn.one_hot(expert, n_experts, dtype=jnp.float32),
            0.0,
        ).sum(0)
        dropped = dropped + (~kept).astype(jnp.float32).sum()
    return {
        "expert_load": load,
        "dropped": dropped,
        "padded": jnp.float32(sentinel) - load.sum(),
        "capacity": jnp.float32(capacity),
    }


def route_slots(
    logits: jax.Array,  # [tokens, n_experts]
    capacity: int,
    k: int = 1,
):
    """Index-form routing: the same Switch/GShard bookkeeping as
    :func:`top1_route` / :func:`topk_route`, but returning per-choice
    ``(slot, gate)`` pairs instead of dense ``[T, E, C]`` tensors.

    ``slot[t] = expert[t]*capacity + queue_pos[t]`` for kept tokens and
    the sentinel ``n_experts*capacity`` for dropped ones; ``gate`` carries
    the (k-normalised) router weight. O(T·E) bookkeeping, nothing O(T·E·C).
    """
    n_experts = logits.shape[-1]
    if k > n_experts:
        raise ValueError(f"k={k} exceeds n_experts={n_experts}")
    probs = jax.nn.softmax(logits, axis=-1)
    sentinel = n_experts * capacity

    if k == 1:
        expert = jnp.argmax(probs, axis=-1)
        gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
        onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)
        pos = ((jnp.cumsum(onehot, axis=0) - 1) * onehot).sum(-1)
        keep = pos < capacity
        slot = jnp.where(keep, expert * capacity + pos, sentinel)
        return [(slot, gate)]

    # Top-k selection in LOGIT space with an explicit taken-mask:
    # prob-space masking re-selects expert 0 when remaining softmax mass
    # underflows (diverged router), and -inf masking alone still re-picks
    # a taken expert when the CALLER pads disallowed experts with -inf. A
    # duplicate pick (only possible when every untaken expert is -inf) is
    # zeroed outright — no queue slot, no gate weight. Queue bookkeeping
    # stays int32: a low-precision logits dtype must never round slot
    # indices (bf16 cumsum collides queue slots past 256 tokens).
    taken = jnp.zeros_like(logits, dtype=jnp.int32)
    chosen = []
    for _ in range(k):
        avail = jnp.where(taken > 0, -jnp.inf, logits)
        expert = jnp.argmax(avail, axis=-1)
        onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)
        onehot = onehot * (1 - taken)
        gate = (probs * onehot).sum(-1)
        chosen.append((expert, onehot, gate))
        taken = taken + onehot

    denom = sum(g for _, _, g in chosen) + 1e-9
    counts = jnp.zeros((n_experts,), jnp.int32)
    out = []
    for expert, onehot, gate in chosen:
        pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot + counts[None, :]
        pos_tok = (pos * onehot).sum(-1)
        keep = (pos_tok < capacity) & (onehot.sum(-1) > 0)
        slot = jnp.where(keep, expert * capacity + pos_tok, sentinel)
        out.append((slot, gate / denom))
        counts = counts + (onehot * keep[:, None]).sum(0)
        counts = jnp.minimum(counts, capacity)
    return out


def dispatch_einsum(x, logits, capacity, k):
    """Dense one-hot dispatch (reference): builds ``[T, E, C]`` dispatch /
    combine tensors. Returns ``(queues [E, C, d], combine_fn)`` where
    ``combine_fn(back [E, C, d]) -> [T, d]``."""
    if k == 1:
        dispatch, combine = top1_route(logits, capacity)
    else:
        dispatch, combine = topk_route(logits, capacity, k)
    queues = jnp.einsum("td,tec->ecd", x, dispatch)

    def combine_fn(back):
        return jnp.einsum("ecd,tec->td", back, combine)

    return queues, combine_fn


def dispatch_sort(x, logits, capacity, k):
    """Index-based dispatch: queue assembly is one int scatter of slot ids
    plus one row gather — O(T·d + E·C·d) work and memory, no ``[T, E, C]``
    tensor anywhere (the scalable form at LM scale, where the dense form's
    O(T·E·C·d) dispatch einsum dominates the layer).

    Same routing bookkeeping as :func:`dispatch_einsum` (via
    :func:`route_slots`), so results are identical. Returns the same
    ``(queues, combine_fn)`` pair."""
    tokens, d = x.shape
    n_experts = logits.shape[-1]
    slots = route_slots(logits, capacity, k)
    sentinel = n_experts * capacity
    # Match the einsum path's promotion semantics exactly: its queue einsum
    # promotes (x, dispatch[logits.dtype]) and its combine einsum promotes
    # (back, combine[f32-promoted gates]) — switching dispatch_impl must
    # not change dtypes or gate precision.
    q_dtype = jnp.promote_types(x.dtype, logits.dtype)

    # token_of_slot: which token fills each queue slot (sentinel-initialised
    # so empty slots gather the zero row). Dropped tokens write the
    # sentinel slot, which is sliced off.
    token_of_slot = jnp.full((sentinel + 1,), tokens, jnp.int32)
    for slot, _ in slots:
        token_of_slot = token_of_slot.at[slot].set(
            jnp.arange(tokens, dtype=jnp.int32)
        )
    x_pad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)]).astype(q_dtype)
    queues = x_pad[token_of_slot[:sentinel]].reshape(n_experts, capacity, d)

    def combine_fn(back):
        gate_dtype = slots[0][1].dtype
        out_dtype = jnp.promote_types(back.dtype, gate_dtype)
        flat = jnp.concatenate(
            [back.reshape(sentinel, d),
             jnp.zeros((1, d), back.dtype)]
        ).astype(out_dtype)
        out = jnp.zeros((tokens, d), out_dtype)
        for slot, gate in slots:
            out = out + flat[slot] * gate[:, None].astype(out_dtype)
        return out

    return queues, combine_fn


_DISPATCH = {"einsum": dispatch_einsum, "sort": dispatch_sort}


def resolve_dispatch_impl(
    tokens: int, n_experts: int, d_model: int, dtype,
    impl: str = "auto",
) -> str:
    """Dispatch choice through the decision registry
    (:mod:`chainermn_tpu.tuning`), keyed on ``(device_kind,
    bucket(T, E, d), dtype)``; the table says ``sort`` for every
    backend. ``impl`` other than ``"auto"`` short-circuits (explicit
    caller choice is never overridden).
    """
    if impl != "auto":
        return impl
    from chainermn_tpu import tuning

    key = tuning.decision_key(shape=(tokens, n_experts, d_model),
                              dtype=dtype)
    return tuning.choice("moe_dispatch", ("sort", "einsum"), key)


def moe_capacity(
    tokens: int, n_experts: int, k: int,
    capacity_factor: Optional[float],
) -> int:
    """The static per-expert queue depth: ``ceil(tokens*k/n_experts *
    capacity_factor)``, floored at 1 (``capacity_factor=0`` is the
    legal minimal-capacity extreme: one slot per expert, everything
    else drops to the residual). ``capacity_factor=None`` means NO-DROP
    capacity (``tokens`` — the worst case of every local token choosing
    the same expert), the serving contract: routing decouples across
    co-resident rows, so streams stay bit-identical to sequential
    ``generate`` whatever else shares the batch."""
    if capacity_factor is None:
        return max(1, tokens)
    if capacity_factor < 0:
        raise ValueError(
            f"capacity_factor must be >= 0 (or None for no-drop), got "
            f"{capacity_factor}"
        )
    return max(1, math.ceil(tokens * k / n_experts * capacity_factor))


def resolve_expert_parallel(
    tokens: int, n_experts: int, d_model: int, dtype,
    choice: str = "auto",
) -> str:
    """``'on'``/``'off'`` — whether this MoE workload should spread over
    an ``'expert'`` mesh axis (two all_to_alls per layer, experts
    sharded) or stay replicated-local (every shard hosts every expert,
    zero collectives). Resolved through the decision registry (decision
    ``expert_parallel``, keyed like ``moe_dispatch``); the table says
    ``off`` everywhere until a multi-chip MoE cell has measured both
    sides. ``choice`` other than ``'auto'`` short-circuits."""
    if choice != "auto":
        return choice
    from chainermn_tpu import tuning

    key = tuning.decision_key(shape=(tokens, n_experts, d_model),
                              dtype=dtype)
    return tuning.choice("expert_parallel", ("off", "on"), key)


def moe_layer_local(
    x: jax.Array,              # [tokens_local, d_model]
    router_w: jax.Array,       # [d_model, n_experts_global]
    expert_fn: Callable,       # expert_fn(params, x[capacity, d]) -> same
    expert_params: PyTree,     # THIS shard's expert params
    axis_name: str = "expert",
    *,
    capacity_factor: Optional[float] = 1.25,
    k: int = 1,
    dispatch_impl: str = "auto",
    experts_per_shard: int = 1,
    return_stats: bool = False,
    stats_axes=None,
):
    """One MoE layer inside ``shard_map``: ``experts_per_shard`` experts
    per shard along ``axis_name`` (global expert ``e`` lives on shard
    ``e // experts_per_shard``); tokens ride two ``all_to_all``s. ``k=1``
    is Switch-style top-1 routing, ``k=2`` GShard-style top-2 (capacity
    scales with k).

    ``dispatch_impl``: ``'einsum'`` (dense one-hot [T,E,C] tensors — the
    reference form, fine at test scale), ``'sort'`` (index scatter +
    gather, O(T·d) — the scalable form; same routing, same numbers), or
    ``'auto'`` (default): device-aware choice via the autotune registry
    — see :func:`resolve_dispatch_impl` for the measured crossover the
    default encodes. Either impl is numerically identical (tested), so
    the choice is pure performance.

    ``experts_per_shard > 1``: ``expert_params`` leaves stack a leading
    ``[experts_per_shard, ...]`` dim (:func:`make_expert_params` over
    this shard's slice) and ``expert_fn`` is vmapped over it; the
    ``all_to_all`` ships ``experts_per_shard`` queues per peer, so the
    collective count is UNCHANGED (still exactly two per layer).

    ``capacity_factor=None`` selects no-drop capacity (see
    :func:`moe_capacity`).

    Returns the combined expert outputs for the local tokens (zeros for
    dropped tokens — add the residual outside); with
    ``return_stats=True``, ``(out, aux)`` where ``aux`` carries the
    layout-invariant ``load_balance`` loss plus :func:`routing_stats`
    totals psum'd over ``stats_axes`` (``expert_load`` ``[n_experts]``,
    ``dropped``, ``padded``, ``capacity`` — float32). ``stats_axes``
    defaults to ``axis_name`` but under a composed plan must name EVERY
    axis the token dim shards over (``dp_axes + ('expert',)``) or the
    aux loss is the mean of per-data-shard values, not the global one.
    """
    n = lax.axis_size(axis_name)
    eps = int(experts_per_shard)
    tokens, d = x.shape
    e_global = n * eps
    if router_w.shape[-1] != e_global:
        raise ValueError(
            f"router_w scores {router_w.shape[-1]} experts but the "
            f"'{axis_name}' axis hosts {e_global} "
            f"({n} shards x {eps} experts/shard)"
        )
    capacity = moe_capacity(tokens, e_global, k, capacity_factor)

    logits = x @ router_w  # [tokens, e_global]
    impl = resolve_dispatch_impl(tokens, e_global, d, x.dtype,
                                 dispatch_impl)
    queues, combine_fn = _DISPATCH[impl](x, logits, capacity, k)

    # Exchange: shard i sends queue rows [j*eps:(j+1)*eps] to shard j,
    # receives ITS experts' queues from every shard
    # -> [n(senders) * eps, capacity, d], sender-major
    recv = lax.all_to_all(queues, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)
    recv = recv.reshape(n, eps, capacity, d).transpose(1, 0, 2, 3)
    if eps == 1:
        # one expert per shard: keep the original expert_fn contract
        # (params un-stacked, one MXU-batched call over n*capacity rows)
        out = expert_fn(expert_params, recv.reshape(n * capacity, d))
        out = out.reshape(1, n, capacity, d)
    else:
        out = jax.vmap(expert_fn)(
            expert_params, recv.reshape(eps, n * capacity, d)
        ).reshape(eps, n, capacity, d)
    # restore global-expert-major order for the return trip
    out = out.transpose(1, 0, 2, 3).reshape(e_global, capacity, d)
    back = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                          tiled=True)
    combined = combine_fn(back)
    if not return_stats:
        return combined
    stats = routing_stats(logits, capacity, k)
    red = axis_name if stats_axes is None else tuple(stats_axes)
    aux = {
        "load_balance": load_balancing_loss(logits, red),
        "expert_load": lax.psum(stats["expert_load"], red),
        "dropped": lax.psum(stats["dropped"], red),
        "padded": lax.psum(stats["padded"], red),
        "capacity": stats["capacity"],
    }
    return combined, aux


def record_moe_dispatch(stats, *, layer: Optional[int] = None) -> None:
    """Emit one ``moe_dispatch`` trace event from a host-fetched MoE
    stats/aux mapping (ISSUE 20 observability row).

    ``stats`` is the dict :func:`routing_stats` (or the ``aux`` of
    ``moe_layer_local(..., return_stats=True)`` / the plan's
    ``moe_layer`` metrics) returns: ``expert_load`` ``[n_experts]``,
    ``dropped``, ``padded``, ``capacity``. Values may still be device
    arrays — they are fetched here, so call this OUTSIDE jit, after the
    step that produced them (trace events cannot fire from compiled
    code; same host-side-mirror shape as the scheduler's ``serving``
    events). No-op when no recorder is active; never raises into the
    training/serving loop.

    The metrics tap mirrors the event as ``moe_dropped_tokens_total`` /
    ``moe_padded_tokens_total`` counters and per-expert
    ``moe_expert_load`` / ``moe_capacity`` gauges
    (docs/observability.md name table)."""
    try:
        from chainermn_tpu.observability import trace as _trace

        rec = _trace.active()
    except Exception:
        return
    if rec is None:
        return
    try:
        import numpy as _np

        load = _np.asarray(
            jax.device_get(stats["expert_load"]), dtype=_np.float64
        ).ravel()
        fields = {
            "expert_load": [round(float(v), 3) for v in load],
            "n_experts": int(load.size),
            "dropped": round(float(jax.device_get(stats["dropped"])), 3),
            "padded": round(float(jax.device_get(stats["padded"])), 3),
            "capacity": float(jax.device_get(stats["capacity"])),
        }
        if layer is not None:
            fields["layer"] = int(layer)
        rec.event("moe_dispatch", **fields)
    except Exception:
        pass


def make_expert_params(init_fn: Callable, rng: jax.Array, n_experts: int):
    """Stack ``n_experts`` independently-initialised expert param trees
    along a leading axis (shard over the ``'expert'`` mesh axis)."""
    rngs = jax.random.split(rng, n_experts)
    trees = [init_fn(r) for r in rngs]
    return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)


# ---------------------------------------------------------------------------
# Dropless routing: sorted, ragged, nothing dropped and nothing padded
# ---------------------------------------------------------------------------

class Routing(NamedTuple):
    """What :func:`dropless_topk` decides for ``T`` tokens and ``k`` slots.

    ``order[j]`` is the (token, slot) row (``token * k + slot``) that sits
    at position ``j`` of the expert-sorted rows, ``inverse`` its inverse
    permutation; rows of one expert are contiguous, experts ascending, and
    within an expert in token order. ``logits`` are the router's float32
    scores, kept for the auxiliary losses. Where only a share of the
    experts is held, ``group_sizes`` has one entry a *held* expert, the
    rows routed to an absent one sort behind the last group and lie in
    none, and ``rows_held`` counts the rows whose expert is held, from
    the choice and not from the groups. The held rows are therefore the
    first ``rows_held`` of the sorted order, and a **round** of a share's
    expert section (:func:`experts_in_rounds`) is ``R`` consecutive
    positions of it, ``order[r * R : (r + 1) * R]``, with the groups
    clipped to that window: ``ceil(rows_held / R)`` rounds cover every
    held row whatever the routing."""

    gates: jax.Array        # [T, k] float32
    experts: jax.Array      # [T, k] int32
    order: jax.Array        # [T * k] int32
    inverse: jax.Array      # [T * k] int32
    group_sizes: jax.Array  # [held] int32, sums to rows_held
    logits: jax.Array       # [T, E] float32
    rows_held: jax.Array    # [] int32; T * k where every expert is held


def dropless_topk(u, router_w, k: int, renormalise: bool = False, *,
                  score: str = "softmax", select_bias=None,
                  gate_eps: float = 0.0, scale: float = 1.0,
                  held: tuple[int, int] | None = None) -> Routing:
    """Route every row of ``u [T, D]`` to its ``k`` best of ``E`` experts.

    The scores are ``softmax(u @ router_w)`` (or, with ``score='sigmoid'``,
    each logit's sigmoid) in float32 at full matmul precision (a TPU's
    default would round the operands to bf16 and flip near-tied experts).
    The choice is by ``score + select_bias`` (``[E]``, not differentiated)
    where a bias is given, ties to the lower expert index; the gates are
    the chosen experts' scores without the bias, over ``sum + gate_eps``
    where ``renormalise``, times ``scale``. Differentiable in the gates,
    not in the choice.

    ``held = (lo, hi)``: the caller holds experts ``lo <= e < hi`` alone
    (a chip's share under expert parallelism). The choice and the gates
    are over all ``E``; the groups are the held experts' (see
    :class:`Routing`)."""
    from chainermn_tpu.observability.metrics import registry

    n_experts = router_w.shape[-1]
    if k > n_experts:
        raise ValueError(f"k={k} exceeds n_experts={n_experts}")
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"score must be 'softmax' or 'sigmoid', got "
                         f"{score!r}")
    lo, hi = held if held is not None else (0, n_experts)
    if not 0 <= lo < hi <= n_experts:
        raise ValueError(f"held={held} is no range of {n_experts} experts")
    # set while the caller's program is traced; the last layer traced is
    # what a scrape sees
    registry().gauge(
        train_path.MOE_ROWS_PER_STEP,
        "(token, slot) rows a dropless MoE layer routes in one call "
        "(tokens x experts per token), at the last call traced",
    ).set(float(u.shape[0] * k))
    registry().gauge(
        train_path.MOE_EXPERTS_TOTAL,
        "experts a dropless MoE layer routes among, at the last call "
        "traced",
    ).set(float(n_experts))
    registry().gauge(
        train_path.MOE_EXPERTS_HELD,
        "experts whose weights a dropless MoE layer holds (its share of "
        "moe_experts_total), at the last call traced",
    ).set(float(hi - lo))
    with jax.named_scope(train_path.MOE_ROUTE):
        logits = jnp.dot(u.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
            else jax.nn.sigmoid(logits)
        if select_bias is None:
            gates, experts = lax.top_k(probs, k)
        else:
            _, experts = lax.top_k(
                probs + lax.stop_gradient(select_bias.astype(jnp.float32)),
                k)
            # the chosen scores without the bias, by a one-hot product: one
            # term a sum, so exact, and no gather of T * k rows either way
            gates = jnp.einsum(
                "te,tke->tk", probs,
                jax.nn.one_hot(experts, n_experts, dtype=probs.dtype))
        if renormalise:
            gates = gates / (gates.sum(-1, keepdims=True) + gate_eps)
        if scale != 1.0:
            gates = gates * scale
    with jax.named_scope(train_path.MOE_DISPATCH):
        flat = experts.reshape(-1).astype(jnp.int32)
        rows = jnp.arange(flat.shape[0], dtype=jnp.int32)
        if (lo, hi) == (0, n_experts):
            # every expert held: the one key path below gives the same
            # values and costs the OLMoE cell 0.08% of its step (PERF.md
            # section 6, PR 40)
            keys = flat
            rows_held = jnp.int32(flat.shape[0])
        else:
            # an absent expert's rows take the key past the last group
            here = (flat >= lo) & (flat < hi)
            keys = jnp.where(here, flat - lo, hi - lo)
            rows_held = here.sum(dtype=jnp.int32)
        # a stable sort by expert keeps token order within an expert
        by_expert, order = lax.sort((keys, rows), num_keys=1, is_stable=True)
        _, inverse = lax.sort((order, rows), num_keys=1)
        # where each expert's rows end in the sorted keys (cheaper on a
        # TPU than a scatter-add of one a row into the experts' counters)
        ends = jnp.searchsorted(
            by_expert, jnp.arange(1, hi - lo + 1, dtype=jnp.int32))
        group_sizes = jnp.diff(ends, prepend=0).astype(jnp.int32)
    return Routing(gates, experts.astype(jnp.int32), order, inverse,
                   group_sizes, logits, rows_held)


@jax.custom_vjp
def _rows_to_experts(x, order, inverse):
    k = order.shape[0] // x.shape[0]
    return x[order // k]


def _rows_to_experts_fwd(x, order, inverse):
    return _rows_to_experts(x, order, inverse), (order, inverse, x.shape[0])


def _rows_to_experts_bwd(res, g):
    # the transpose of the gather is a scatter-add of k rows into each
    # token; through the inverse permutation it is a gather and a sum
    order, inverse, tokens = res
    back = g[inverse].reshape(tokens, -1, g.shape[-1])
    return back.sum(1, dtype=jnp.float32).astype(g.dtype), None, None


_rows_to_experts.defvjp(_rows_to_experts_fwd, _rows_to_experts_bwd)


@jax.custom_vjp
def _rows_from_experts(y, order, inverse):
    return y[inverse]


def _rows_from_experts_fwd(y, order, inverse):
    return y[inverse], (order,)


def _rows_from_experts_bwd(res, g):
    return g[res[0]], None, None  # a permutation's transpose: its inverse


_rows_from_experts.defvjp(_rows_from_experts_fwd, _rows_from_experts_bwd)


def dispatch(x, routing: Routing):
    """Gather the rows of ``x [T, D]`` into expert order: ``[T * k, D]``,
    row ``j`` the token of ``routing.order[j]``; the groups of
    ``routing.group_sizes`` are the operands of the experts' grouped
    matmuls. Differentiable in ``x``."""
    with jax.named_scope(train_path.MOE_DISPATCH):
        return _rows_to_experts(x, routing.order, routing.inverse)


def combine(y, routing: Routing):
    """Sum the experts' outputs ``y [T * k, D]`` (expert order) back into
    ``[T, D]``, each row weighted by its gate, accumulated in float32.
    Differentiable in ``y`` and in the gates."""
    return _sum_back(y, routing.gates, routing.order, routing.inverse)


def _sum_back(y, gates, order, inverse):
    tokens, k = gates.shape
    with jax.named_scope(train_path.MOE_COMBINE):
        back = _rows_from_experts(y, order, inverse)
        back = back.reshape(tokens, k, y.shape[-1])
        out = jnp.einsum("tkd,tk->td", back, gates.astype(y.dtype),
                         preferred_element_type=jnp.float32)
        return out.astype(y.dtype)


def gated_experts(rows, w_gate_up, w_down, group_sizes):
    """``down(silu(gate(rows)) * up(rows))`` expert by expert, for rows in
    expert order: gate and up of an expert are one matrix (``w_gate_up
    [held, D, 2F]``, gate's columns first), so one grouped matmul makes
    both; ``w_down`` is ``[held, F, D]``. The straight-line core of the
    expert section, differentiable in all three."""
    from chainermn_tpu.ops.grouped_matmul import grouped_matmul

    width = w_down.shape[1]
    gate_up = grouped_matmul(rows, w_gate_up, group_sizes)
    with jax.named_scope(train_path.MOE_EXPERTS):
        act = jax.nn.silu(gate_up[:, :width]) * gate_up[:, width:]
    return grouped_matmul(act, w_down, group_sizes)


#: rows of a round of a share's expert section over the rows a balanced
#: router gives the chip (``T * k * held / E``). At 2 the three cells that
#: train a share run one round a layer (``rows_held`` a layer ~16k of
#: 32,768 in LFM2, 7.5-17.5k of 32,768 in SDAR, ~6.1k of 12,288 in
#: DeepSeek-V2-Lite). Read on the v5e against 1.5, two seeds each: LFM2
#: 310.05 / 308.47 ms a step at 2 and 310.36 / 308.69 at 1.5, SDAR 553.29 /
#: 549.68 and 569.94 / 564.39 (PERF.md section 6, PR 48): a smaller round
#: buys nothing there, and the rounds make any value exact
_ROUND_SHARE = 2


def rows_bound(rows: int, held: int, n_experts: int) -> int:
    """``R``: the expert-sorted rows one round of a share's expert section
    takes, from static shapes alone: :data:`_ROUND_SHARE` times the
    expected share of ``rows`` (token, slot) rows where ``held`` of
    ``n_experts`` experts are held, rounded up to the grouped matmul's row
    tile, and no more than ``rows`` (every expert held: one round of all
    rows)."""
    from chainermn_tpu.ops.grouped_matmul import _TILE_M

    tiles = math.ceil(_ROUND_SHARE * rows * held / (n_experts * _TILE_M))
    return min(rows, tiles * _TILE_M)


def _round_sizes(group_sizes, r, bound: int):
    """The groups' sizes inside round ``r``'s window of the sorted rows,
    ``[r * bound, (r + 1) * bound)``: a group that straddles an edge is
    split, one outside the window is empty, and the window's rows behind
    the last group are the grouped matmul's tail."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = ends - group_sizes.astype(jnp.int32)
    lo = r * bound
    return jnp.clip(ends, lo, lo + bound) - jnp.clip(starts, lo, lo + bound)


def rounds_aux(routing: Routing, bound: int) -> dict:
    """What a share's expert section does at ``bound`` rows a round:
    ``rounds`` (rounds run, ``ceil(rows_held / bound)``: 1 while the held
    rows fit the bound, 0 where none is held) and ``tail_tiles`` (the row
    tiles the rounds run hand the grouped matmuls that lie wholly behind
    the last held group: written as zeros, not multiplied), float32."""
    from chainermn_tpu.ops.grouped_matmul import tail_tiles

    rounds = -(-routing.rows_held // bound)
    last = _round_sizes(routing.group_sizes, jnp.maximum(rounds - 1, 0), bound)
    return {
        "rounds": rounds.astype(jnp.float32),
        "tail_tiles": jnp.where(rounds > 0, tail_tiles(last, bound), 0
                                ).astype(jnp.float32),
    }


def _whole_rounds(order, bound: int):
    """``order`` padded with row 0 to a whole number of rounds (the padding
    lies behind every group)."""
    rows = order.shape[0]
    return jnp.pad(order, (0, -(-rows // bound) * bound - rows))


def _round(r, bound: int, x, order_p, group_sizes, k: int):
    """Round ``r``: its (token, slot) rows, their token rows gathered from
    ``x``, and the groups' sizes in its window."""
    idx = lax.dynamic_slice_in_dim(order_p, r * bound, bound)
    with jax.named_scope(train_path.MOE_DISPATCH):
        rows = x[idx // k]
    return idx, rows, _round_sizes(group_sizes, r, bound)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _section(bound, x, w_gate_up, w_down, gates, order, inverse, group_sizes,
             rows_held):
    tokens, k = gates.shape
    order_p = _whole_rounds(order, bound)

    def one_round(r, y):
        _, rows, sizes = _round(r, bound, x, order_p, group_sizes, k)
        out = gated_experts(rows, w_gate_up, w_down, sizes)
        with jax.named_scope(train_path.MOE_EXPERTS):
            return lax.dynamic_update_slice_in_dim(y, out, r * bound, 0)

    with jax.named_scope(train_path.MOE_EXPERTS):
        # rows behind the rounds run: zeros, as the tail's are
        y = jnp.zeros((order_p.shape[0], x.shape[1]), x.dtype)
    y = lax.fori_loop(0, -(-rows_held // bound), one_round, y)
    return _sum_back(y[:tokens * k], gates, order, inverse)


def _section_fwd(bound, *args):
    return _section(bound, *args), args  # the inputs alone are kept


def _section_bwd(bound, res, g):
    x, w_gate_up, w_down, gates, order, inverse, group_sizes, rows_held = res
    tokens, k = gates.shape
    order_p = _whole_rounds(order, bound)
    gate_of = gates.reshape(-1)

    def one_round(r, carry):
        d_gate_up, d_down, d_rows, d_gate = carry
        idx, rows, sizes = _round(r, bound, x, order_p, group_sizes, k)
        out, vjp = jax.vjp(
            lambda a, b, c: gated_experts(a, b, c, sizes), rows, w_gate_up,
            w_down)
        with jax.named_scope(train_path.MOE_COMBINE):
            # the weighted sum's transpose for these rows alone: the
            # cotangent of a row is its token's times its gate (rounded as
            # the sum rounds it), and the gate's is <row's output, token's>
            g_rows = g[idx // k]
            gate = gate_of[idx].astype(out.dtype).astype(jnp.float32)
            d_out = (g_rows.astype(jnp.float32) * gate[:, None]
                     ).astype(out.dtype)
            d_gate_r = jnp.einsum("rd,rd->r", out, g_rows,
                                  preferred_element_type=jnp.float32)
        d_rows_r, d_gate_up_r, d_down_r = vjp(d_out)
        with jax.named_scope(train_path.MOE_EXPERTS):
            return (d_gate_up + d_gate_up_r, d_down + d_down_r,
                    lax.dynamic_update_slice_in_dim(
                        d_rows, d_rows_r, r * bound, 0),
                    lax.dynamic_update_slice_in_dim(
                        d_gate, d_gate_r, r * bound, 0))

    with jax.named_scope(train_path.MOE_EXPERTS):
        carry = (jnp.zeros(w_gate_up.shape, jnp.float32),
                 jnp.zeros(w_down.shape, jnp.float32),
                 jnp.zeros((order_p.shape[0], x.shape[1]), x.dtype),
                 jnp.zeros(order_p.shape, jnp.float32))
    d_gate_up, d_down, d_rows, d_gate = lax.fori_loop(
        0, -(-rows_held // bound), one_round, carry)
    with jax.named_scope(train_path.MOE_DISPATCH):
        d_x, _, _ = _rows_to_experts_bwd((order, inverse, tokens),
                                         d_rows[:tokens * k])
    with jax.named_scope(train_path.MOE_COMBINE):
        d_gates = d_gate[:tokens * k][inverse].reshape(tokens, k)
    return (d_x, d_gate_up.astype(w_gate_up.dtype),
            d_down.astype(w_down.dtype), d_gates.astype(gates.dtype),
            None, None, None, None)


_section.defvjp(_section_fwd, _section_bwd)


def experts_in_rounds(x, w_gate_up, w_down, routing: Routing):
    """The expert section of a layer that holds a share of the experts:
    :func:`dispatch`, :func:`gated_experts` and :func:`combine` of ``x [T,
    D]`` as one function, everything of it that lies in expert order run
    over **rounds** of ``R`` rows (:func:`rows_bound`, from the shapes
    here: ``held`` is the weights' leading dimension, ``E`` the router's
    width). Round ``r`` takes ``routing.order[r * R : (r + 1) * R]``,
    gathers those ``R`` token rows and multiplies them with the groups
    clipped to its window; a loop runs ``ceil(rows_held / R)`` rounds (a
    traced count: 1 while the held rows fit ``R``, up to ``T * k / R``
    where every row's expert is held), so the rows of absent experts
    behind the last round are never gathered, multiplied or gated, and
    nothing is dropped at any routing. With one round the values are those
    of the straight-line spelling, bit for bit.

    Differentiable in ``x``, both weights and ``routing.gates`` by a rule
    of its own (a loop of a traced length has no reverse mode): the
    backward runs the same rounds, each re-gathering its rows, computing
    :func:`gated_experts` again and transposing it, the weights' gradients
    summed over the rounds in float32. **Nothing but the inputs is kept
    for the backward**, so under ``jax.checkpoint`` the replay of the
    forward is dead code, and with no checkpoint the section is computed
    twice where the straight-line spelling would keep its products."""
    bound = rows_bound(routing.order.shape[0], w_gate_up.shape[0],
                       routing.logits.shape[-1])
    return _section(bound, x, w_gate_up, w_down, routing.gates,
                    routing.order, routing.inverse, routing.group_sizes,
                    routing.rows_held)


def dropless_aux(routing: Routing, losses: bool = True) -> dict:
    """The router's auxiliary losses and statistics of one layer:
    ``load_balance`` (:func:`load_balancing_loss` over the top ``k``) and
    ``z_loss`` (``mean(logsumexp(logits)^2)``), both of a softmax router
    and left out with ``losses=False``; ``expert_load`` (rows a held
    expert received, float32 ``[held]``), ``rows_held`` (the (token,
    slot) rows whose expert is held: all ``tokens * k`` unless the layer
    holds a share) and ``dropped`` (the rows routed to a held expert that
    lie in no expert's group, ``rows_held - sum(group_sizes)``, counted
    from the routing the experts are given: 0 while this path keeps its
    word, since it has no capacity)."""
    _, k = routing.gates.shape
    with jax.named_scope(train_path.MOE_ROUTE):
        aux = {
            "load_balance": load_balancing_loss(routing.logits, k=k),
            "z_loss": jnp.mean(
                jax.nn.logsumexp(routing.logits, axis=-1) ** 2),
        } if losses else {}
        return {
            **aux,
            "expert_load": routing.group_sizes.astype(jnp.float32),
            "rows_held": routing.rows_held.astype(jnp.float32),
            "dropped": (routing.rows_held - routing.group_sizes.sum()
                        ).astype(jnp.float32),
        }
