"""The jitted SPMD train step.

TPU mapping of the reference's hot loop (SURVEY.md section 3.2): where
ChainerMN ran eager backward, then packed gradients into a flat buffer,
``ncclAllReduce``-d it, scaled and unpacked (``pure_nccl_communicator.py``
(dagger)), here the *entire iteration* — forward, backward, gradient pmean
over the mesh, optimizer update — is one ``jax.jit`` program, and XLA fuses
the packing/scaling away. What it does not do by itself is overlap the
collective: on a TPU an all-reduce is one synchronous op wherever the
scheduler puts it (PERF.md, PR 39). So the default reduction averages
every large leaf with two ``all_to_all``s (``allreduce_gradients``), and
the step's ``jax.jit`` carries the option under which XLA compiles those
to asynchronous pairs and flies them under the rest of the backward and
the optimizer's sweep: what double buffering bought on GPU, without its
step of staleness.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu.communicators.base import CommunicatorBase
from chainermn_tpu.observability import train_path
from chainermn_tpu.optimizers import (
    MultiNodeOptimizer,
    _ErrorFeedbackState,
    allreduce_gradients,
)
from chainermn_tpu.parallel.collectives import async_collective_options

PyTree = Any


def _arity(fn: Callable) -> int:
    """Number of positional parameters ``fn`` accepts (inf if *args)."""
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return 2
    n = 0
    for p in sig.parameters.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            n += 1
        elif p.kind == p.VAR_POSITIONAL:
            return 99
    return n


class TrainState(NamedTuple):
    """Replicated training state. ``model_state`` carries non-gradient
    collections (e.g. BatchNorm running stats — the values the reference's
    ``AllreducePersistent`` synchronized)."""

    params: PyTree
    opt_state: Any
    step: jax.Array
    model_state: PyTree = ()


def create_train_state(
    params: PyTree,
    optimizer,
    comm: Optional[CommunicatorBase] = None,
    *,
    model_state: PyTree = (),
) -> TrainState:
    """Initialise (and replicate, when a communicator is given) the state —
    the explicit version of the reference's first-update ``bcast_data``.

    With an error-feedback optimizer the EF residual is PER-RANK state:
    it is initialised stacked ``[n_slots, ...]`` and SHARDED over the
    communicator's grad axes, so the jitted train step can carry it with
    honest per-rank sharding (see ``make_train_step``'s EF state spec)."""
    if comm is not None:
        params = comm.bcast_data(params)
        if jax.tree.leaves(model_state):
            model_state = comm.bcast_data(model_state)
    opt_state = optimizer.init(params)
    if getattr(optimizer, "error_feedback", False):
        if comm is None:
            raise ValueError(
                "error_feedback training state needs a communicator "
                "(the residual is sharded over its grad axes)"
            )
        sharding = NamedSharding(comm.mesh, P(comm.grad_axes))
        n = comm.size

        def stack(r):
            # Created directly sharded: a bare jnp.zeros + device_put
            # would commit the full n x params array to device 0 first
            # (the same spike trainer.py's prefetch placement avoids).
            shape = (n,) + r.shape
            return jax.make_array_from_callback(
                shape, sharding,
                lambda idx: np.zeros(
                    tuple(len(range(*sl.indices(dim)))
                          for sl, dim in zip(idx, shape)),
                    r.dtype,
                ),
            )

        opt_state = opt_state._replace(
            residual=jax.tree.map(stack, opt_state.residual)
        )
    state = TrainState(
        params=params,
        opt_state=opt_state,
        step=jnp.zeros((), jnp.int32),
        model_state=model_state,
    )
    if comm is not None:
        state = _place_state(state, optimizer, comm)
    return state


def _train_state_spec(optimizer, comm):
    """The :class:`TrainState` prefix-spec the jitted step carries
    (``P()`` when fully replicated) — ONE owner shared by
    ``make_train_step`` (shard_map in/out specs) and
    ``create_train_state`` (initial placement): the state is created
    already laid out exactly as the compiled step expects, so the
    second step cannot recompile on a committed-ness change — step
    compiles stay pinned at 1 (the ISSUE 12 dryrun's trainer pin)."""
    if getattr(optimizer, "error_feedback", False):
        # The EF residual is PER-RANK state: stacked [n_slots, ...] over
        # the COMMUNICATOR's grad axes (the layout create_train_state
        # initialises), the rest replicated.
        return TrainState(
            params=P(),
            opt_state=_ErrorFeedbackState(
                inner=P(), residual=P(comm.grad_axes)
            ),
            step=P(),
            model_state=P(),
        )
    # Schedule-aware state carry: a 'zero' reduction schedule's
    # optimizer state is 1/n per shard (stacked [n, ...] leaves) — the
    # optimizer publishes the prefix spec and the step threads it, the
    # same honest-sharding pattern as the EF residual.
    opt_spec = P()
    spec_fn = getattr(optimizer, "opt_state_spec", None)
    if spec_fn is not None:
        opt_spec = spec_fn()
    if opt_spec != P():
        return TrainState(
            params=P(), opt_state=opt_spec, step=P(), model_state=P()
        )
    return P()


def _place_state(state: "TrainState", optimizer, comm) -> "TrainState":
    """Commit every state leaf to ``comm.mesh`` per the step's own spec
    (:func:`_train_state_spec`): already-placed leaves (bcast params,
    the EF residual's sharded stack) pass through untouched, everything
    else lands replicated (or per its prefix spec). Placement at
    creation time is what pins the step's jit cache at 1 — an
    uncommitted opt_state would compile once unspecified and once
    committed. Multi-process meshes are left alone: ``device_put`` of a
    host array onto non-addressable devices is not a local operation
    (the 4-proc scaling rehearsal caught a gloo wire fault from it) —
    there the jit boundary keeps owning placement, at the documented
    cost of its one extra compile."""
    mesh_devices = comm.mesh.devices.flat
    try:
        pidx = jax.process_index()
    except Exception:
        return state
    if any(d.process_index != pidx for d in mesh_devices):
        return state
    spec = _train_state_spec(optimizer, comm)

    def put(x, s):
        if not isinstance(x, (jax.Array, np.ndarray)):
            return x  # exotic leaf: leave its semantics alone
        sharding = NamedSharding(comm.mesh, s)
        if isinstance(x, jax.Array) and x.sharding == sharding:
            return x  # already placed (no copy)
        return jax.device_put(jnp.asarray(x), sharding)

    if isinstance(spec, P):
        return jax.tree.map(lambda x: put(x, spec), state)
    # prefix tree: broadcast each P leaf over its state subtree
    return jax.tree.map(
        lambda s, sub: jax.tree.map(lambda x: put(x, s), sub),
        spec, state, is_leaf=lambda s: isinstance(s, P),
    )


def normalize_loss_fn(loss_fn: Callable) -> Callable:
    """Wrap the user's ``loss_fn`` into the canonical
    ``(params, batch, model_state) -> (loss, (metrics, new_model_state))``
    form, accepting every documented return shape: plain ``loss``,
    ``(loss, metrics)``, or ``(loss, (metrics, new_model_state))``; with or
    without the ``model_state`` argument. The single place that owns this
    contract — used by the shard_map step here and the FSDP step
    (:mod:`chainermn_tpu.parallel.fsdp`)."""
    takes_model_state = _arity(loss_fn) >= 3

    def _loss_with_aux(params, batch, model_state):
        if takes_model_state:
            out = loss_fn(params, batch, model_state)
        else:
            out = loss_fn(params, batch)
        if isinstance(out, tuple):
            loss, aux = out
            if isinstance(aux, tuple) and len(aux) == 2:
                metrics, new_model_state = aux
            else:
                metrics, new_model_state = aux, model_state
        else:
            loss, metrics, new_model_state = out, {}, model_state
        return loss, (metrics, new_model_state)

    return _loss_with_aux


def make_train_step(
    loss_fn: Callable,
    optimizer,
    comm: Optional[CommunicatorBase] = None,
    *,
    axis_name: Optional[str] = None,
    batch_spec: P | None = None,
    donate: bool = True,
    accum_steps: int = 1,
    plan=None,
    param_specs=None,
    pipeline=None,
):
    """Build the jitted data-parallel train step.

    Args:
      loss_fn: ``loss_fn(params, batch, model_state) -> (loss, (metrics_dict,
        new_model_state))`` or ``loss_fn(params, batch) -> loss``. The loss
        must be the *local-batch mean*; cross-shard averaging is applied by
        the step (gradient pmean — the reference's ``allreduce_grad``).
      optimizer: a :class:`MultiNodeOptimizer` (does its own reduction,
        honouring compression/double-buffering) or any plain optax transform
        (the step then reduces gradients itself).
      comm: the communicator whose mesh the step compiles over. May be
        omitted when ``plan`` is given.
      batch_spec: PartitionSpec for every batch leaf; defaults to sharding
        the leading dim over the communicator's grad axes.
      plan: a :class:`~chainermn_tpu.parallel.plan.ParallelPlan` — the
        global-view path: the step is compiled by the plan (one shard_map
        over the plan's ``data x zero x pipe x model`` mesh, spec
        providers instead of call-site wrappers, donation threaded
        through). ``optimizer`` is unwrapped to its plain inner transform
        via :func:`chainermn_tpu.optimizers.inner_transform`; build the
        state with ``plan.create_train_state``. ``param_specs`` marks
        model/pipe-stacked leaves and ``pipeline`` passes the
        :class:`~chainermn_tpu.parallel.plan.PipelinePlanSpec` of a
        ``pipe`` plan; ``axis_name``/``accum_steps``/``batch_spec`` do
        not apply on this path.
      accum_steps: gradient accumulation — each shard's batch is split into
        this many microbatches, run through a ``lax.scan`` (one compiled
        program, activations live for ONE microbatch at a time), and the
        averaged gradient crosses the wire in a SINGLE allreduce. The
        large-effective-batch regime of the reference's 32K-batch ImageNet
        runs (SURVEY.md section 6) without the memory of the full batch.
        Microbatches see identical params; for STATELESS models the
        accumulated step equals the full-batch step exactly. Models with
        ``model_state`` (BatchNorm) thread it sequentially through the
        microbatches — batch statistics become per-microbatch and running
        averages get ``accum_steps`` momentum updates per step, the
        standard grad-accumulation semantics but NOT identical to one
        full-batch pass.

    Returns:
      ``step(state, batch) -> (state, metrics)``, jitted over ``comm.mesh``
      (or the plan's mesh).
    """
    if plan is not None:
        if accum_steps != 1 or axis_name is not None or batch_spec is not None:
            raise ValueError(
                "plan= owns the batch/axis layout: axis_name, batch_spec "
                "and accum_steps do not apply to a plan-compiled step"
            )
        return plan.compile_train_step(
            loss_fn, optimizer,
            param_specs=param_specs, donate=donate, pipeline=pipeline,
        )
    if comm is None:
        raise ValueError("pass a communicator (or plan=)")
    if param_specs is not None or pipeline is not None:
        raise ValueError(
            "param_specs/pipeline only apply to the plan= path"
        )
    mesh = comm.mesh
    axes = axis_name if axis_name is not None else comm.grad_axes
    if batch_spec is None:
        batch_spec = P(axes)
    reduce_in_step = not getattr(optimizer, "handles_cross_rank_sync",
                                 False)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    # The EF residual is PER-RANK state: carry it with an honest
    # per-rank spec (stacked [n_slots, ...] over the COMMUNICATOR's grad
    # axes — the layout create_train_state initialises; independent of
    # any axis_name override, because the EF reduction itself always
    # runs over comm.grad_axes) instead of the replicated P() the rest
    # of the state uses. The optimizer sees a single layout: local_step
    # squeezes the per-slot [1, ...] slice around opt.update.
    ef = getattr(optimizer, "error_feedback", False)
    # One owner for the state layout (_train_state_spec): the same spec
    # create_train_state places the initial state with, so the compiled
    # step's inputs arrive exactly as laid out — no second compile.
    state_spec: Any = _train_state_spec(optimizer, comm)

    _loss_with_aux = normalize_loss_fn(loss_fn)

    def _grads_single(state, batch):
        grad_fn = jax.value_and_grad(_loss_with_aux, has_aux=True)
        with jax.named_scope(train_path.LOSS_AND_GRAD):
            (loss, (metrics, model_state)), grads = grad_fn(
                state.params, batch, state.model_state
            )
        return grads, loss, metrics, model_state

    def _grads_accumulated(state, batch):
        def to_micro(leaf):
            if leaf.shape[0] % accum_steps != 0:
                raise ValueError(
                    f"local batch dim {leaf.shape[0]} not divisible by "
                    f"accum_steps={accum_steps}"
                )
            return leaf.reshape(
                accum_steps, leaf.shape[0] // accum_steps, *leaf.shape[1:]
            )

        micro = jax.tree.map(to_micro, batch)
        grad_fn = jax.value_and_grad(_loss_with_aux, has_aux=True)

        def body(carry, mb):
            gsum, model_state = carry
            with jax.named_scope(train_path.LOSS_AND_GRAD):
                (loss, (metrics, model_state)), g = grad_fn(
                    state.params, mb, model_state
                )
            gsum = jax.tree.map(jnp.add, gsum, g)
            return (gsum, model_state), (loss, metrics)

        zeros = jax.tree.map(jnp.zeros_like, state.params)
        (gsum, model_state), (losses, metrics_stack) = lax.scan(
            body, (zeros, state.model_state), micro
        )
        grads = jax.tree.map(lambda g: g / accum_steps, gsum)
        loss = losses.mean()
        metrics = jax.tree.map(lambda m: m.mean(0), metrics_stack)
        return grads, loss, metrics, model_state

    def local_step(state: TrainState, batch):
        if accum_steps == 1:
            grads, loss, metrics, model_state = _grads_single(state, batch)
        else:
            grads, loss, metrics, model_state = _grads_accumulated(
                state, batch
            )
        if reduce_in_step:
            with jax.named_scope(train_path.GRAD_REDUCE):
                grads = allreduce_gradients(grads, comm)
        opt_in = state.opt_state
        if ef:
            # Hand the optimizer its single supported layout: this
            # slot's squeezed residual (the [n_slots, ...] layout is
            # validated host-side before the jitted call).
            opt_in = opt_in._replace(
                residual=jax.tree.map(lambda e: e[0], opt_in.residual)
            )
        updates, opt_state = optimizer.update(grads, opt_in, state.params)
        if ef:
            opt_state = opt_state._replace(
                residual=jax.tree.map(lambda e: e[None],
                                      opt_state.residual)
            )
        with jax.named_scope(train_path.OPTIMIZER_UPDATE):
            params = optax.apply_updates(state.params, updates)
        metrics = {"loss": loss, **metrics}
        metrics = lax.pmean(metrics, axes)
        # model_state (e.g. BN stats) must not drift across shards:
        model_state = lax.pmean(model_state, axes)
        new_state = TrainState(
            params=params,
            opt_state=opt_state,
            step=state.step + 1,
            model_state=model_state,
        )
        return new_state, metrics

    sharded = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(state_spec, batch_spec),
        out_specs=(state_spec, P()),
        check_vma=False,
    )
    # The default reduction's all_to_alls are asynchronous on a TPU only
    # under an option of XLA's own: the step's policy, attached to its
    # jit (whoever lowers and compiles the step ahead of time gets it too).
    jitted = jax.jit(sharded, donate_argnums=(0,) if donate else (),
                     compiler_options=async_collective_options(mesh))
    # Overlap metadata for the observability layer: the Trainer emits
    # this once as an ``overlap_config`` trace event, so a trace's
    # comm-hidden numbers carry the mode that produced them (schedule,
    # staleness, donation). Best-effort — the jit wrapper may refuse
    # attributes on some jax versions.
    db = bool(getattr(optimizer, "double_buffering", False))
    overlap_info = {
        "double_buffering": db,
        "staleness": 1 if db else 0,
        "schedule": getattr(optimizer, "reduction_schedule", None),
        "donate": bool(donate),
    }
    try:
        jitted.overlap_info = overlap_info
    except (AttributeError, TypeError):
        pass
    if not ef:
        return jitted

    template_cache: dict = {}

    def step_with_residual_check(state, batch):
        # Host-side shape gate BEFORE shard_map applies its specs: a
        # bare optimizer.init() state (unstacked residual) would
        # otherwise die in a generic divisibility/rank sharding error
        # that never names the real mistake. The expected per-slot
        # shapes come from the OPTIMIZER's own residual template
        # (eval_shape of init — abstract, no allocation): full-param
        # leaves for the flat wire, per-bucket shard buffers for the
        # topology-aware wire. Cached per params-structure.
        key = (
            jax.tree.structure(state.params),
            tuple((np.shape(p), str(getattr(p, "dtype", "?")))
                  for p in jax.tree.leaves(state.params)),
        )
        if key not in template_cache:
            template_cache[key] = jax.tree.leaves(
                jax.eval_shape(optimizer.init, state.params).residual
            )
        t_leaves = template_cache[key]
        e_leaves = jax.tree.leaves(state.opt_state.residual)
        if len(e_leaves) != len(t_leaves):
            raise ValueError(
                "error-feedback residual has "
                f"{len(e_leaves)} leaves but this optimizer's residual "
                f"template has {len(t_leaves)} — a partially restored or "
                "hand-edited opt_state cannot be carried by "
                "make_train_step; rebuild it with create_train_state(...)"
            )
        for e, t in zip(e_leaves, t_leaves):
            eshape = np.shape(e)
            if eshape != (comm.size,) + t.shape:
                raise ValueError(
                    "error-feedback residual leaf has shape "
                    f"{eshape}, expected {(comm.size,) + t.shape} "
                    "(stacked per mesh slot) — build the state with "
                    "create_train_state(...); a bare "
                    "optimizer.init(params) state cannot be carried by "
                    "make_train_step"
                )
        return jitted(state, batch)

    step_with_residual_check.overlap_info = overlap_info
    return step_with_residual_check


def make_eval_step(
    metric_fn: Callable,
    comm: CommunicatorBase,
    *,
    batch_spec: P | None = None,
):
    """Jitted eval step: ``metric_fn(params, batch, model_state) -> dict`` of
    local-batch-mean metrics, pmean-ed over the mesh (device plane of the
    reference's multi-node evaluator)."""
    mesh = comm.mesh
    axes = comm.grad_axes
    if batch_spec is None:
        batch_spec = P(axes)

    takes_model_state = _arity(metric_fn) >= 3

    def local(params, batch, model_state):
        if takes_model_state:
            metrics = metric_fn(params, batch, model_state)
        else:
            metrics = metric_fn(params, batch)
        return lax.pmean(metrics, axes)

    sharded = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), batch_spec, P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)
