"""ParallelPlan — one global-view mesh program for
DP x TP x ZeRO x pipeline x sequence.

The reference's training stack was per-process communicator-style: every
parallel form was a wrapper at the call site (``communicators/`` (dagger),
``optimizers.py`` (dagger) — SURVEY.md sections 2.1-2.3), so composing two
of them meant composing wrappers and hoping their collectives interleaved.
A :class:`ParallelPlan` inverts that: it lays out ONE named mesh
(``data x zero x pipe x model``, any subset, device layout via
:mod:`chainermn_tpu.parallel.mesh` — ICI-aware placement, balanced
auto-factorisation through :func:`~chainermn_tpu.parallel.mesh.
best_mesh_shape`) and compiles ONE ``shard_map`` train step in which the
per-axis modules participate as *spec providers*
(:mod:`chainermn_tpu.parallel.plan_specs`):

- ``data`` — plain data parallelism: batch shards over it, gradients
  ``pmean`` over it (one all-reduce);
- ``zero`` — data parallelism with a ZeRO-1 sharded update
  (:mod:`chainermn_tpu.parallel.zero`, arXiv:2004.13336): batch shards
  over it too, but the gradient mean arrives as a reduce-scatter, the
  inner optimizer updates a 1/n state chunk, and an all-gather returns
  the parameter updates — same wire bytes as the allreduce it replaces;
- ``model`` — Megatron-style tensor parallelism
  (:mod:`chainermn_tpu.parallel.tensor`): marked leaves stack
  ``[n, ...]`` shards, the loss is written with the ``copy_to_tp`` /
  ``reduce_from_tp`` adjoint pairs, one psum per column->row pair;
- ``pipe`` — GPipe micro-batch pipelining
  (:mod:`chainermn_tpu.parallel.pipeline`): stage leaves stack
  ``[n_stages, ...]``, the conveyor's ppermute rides the schedule;
- ``seq`` — sequence/context parallelism (ISSUE 13): the batch's
  sequence dim shards over it (``batch_spec`` appends it after the dp
  axes), attention routes through the ring
  (:func:`~chainermn_tpu.parallel.ring_attention.
  seq_ring_attention_local` — ``n - 1`` ppermutes per layer per forward
  pass) or Ulysses (:mod:`chainermn_tpu.parallel.ulysses` — two
  all_to_alls in, one out) via the ``seq_attn_impl`` tuning decision
  (:meth:`ParallelPlan.seq_attention`), and gradients take one extra
  all-reduce over the axis (mean over token shards) before the dp
  reduction;
- ``expert`` — MoE expert parallelism (ISSUE 20): expert parameter
  leaves stack ``[n, ...]`` shards (``P('expert')``), the batch's token
  dim shards over the axis (extra data parallelism for every non-expert
  leaf), and tokens ride exactly two ``all_to_all``s per MoE layer per
  pass (:func:`~chainermn_tpu.parallel.moe.moe_layer_local`, routed via
  :meth:`ParallelPlan.moe_layer` — the ``moe_dispatch`` tuning
  decision). Replicated leaves' gradients take one fused all-reduce
  over the axis; expert-stacked leaves take NONE — the all_to_all's
  exact transpose already lands every shard's cotangents on the owning
  shard, and the plan rescales them to the global token mean.

Two composed forms ride the same contract (ISSUE 13 sweep-ins):
``zero_stacked_groups=True`` chunks the STACKED groups' optimizer state
over the ``zero`` axis too (TP x ZeRO — the arXiv:2004.13336
cross-replica update sharding applied per TP/pipe shard: the stacked
groups' dp gradient mean becomes the same rs > ar > update > ag
pipeline the zero group runs, identical wire bytes); and a leaf spec
``P('pipe', 'model')`` stacks a leaf over BOTH axes (the pipe x model
composed plan — stage slices that are themselves tensor-parallel,
``stage_fn`` written with the :mod:`~chainermn_tpu.parallel.tensor`
helpers).

Buffer donation is threaded through the compiled step by construction
(``donate_argnums=(0,)`` on the whole :class:`TrainState`): step ``t+1``
reuses step ``t``'s buffers in place, so the H2D-after-D2H degradation the
verify skill documents (a fetched metric followed by a state re-upload)
cannot occur — there is no re-upload.

Acceptance is structural, not prose (tests/test_plan.py): the compiled
plan step carries exactly the hand-wired paths' HLO collective counts,
dist == single values AND gradients for every composed plan, and the jit
cache stays pinned at 1 across steps.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu.parallel import plan_specs as _ps
from chainermn_tpu.parallel.mesh import best_mesh_shape, make_mesh

PyTree = Any


@dataclasses.dataclass(frozen=True)
class PipelinePlanSpec:
    """How a plan with a ``pipe`` axis runs the pipelined region.

    ``stage_fn(params_local, x_mb) -> y_mb`` is one homogeneous stage
    (output shape == input shape) receiving the COLLAPSED param tree —
    pipe-stacked leaves arrive as this stage's slice. Every TRAINABLE
    leaf of a pipe plan must be pipe-stacked: a replicated leaf consumed
    inside ``stage_fn`` would need a cross-stage gradient sum the
    schedule does not owe (the same embed/head-outside contract as
    :func:`~chainermn_tpu.parallel.pipeline.make_pipeline`).
    ``loss_fn(y, batch) -> loss`` (or ``(loss, metrics_dict)``) maps the
    reassembled pipeline output back to the local-batch-mean loss.
    """

    stage_fn: Callable
    loss_fn: Callable
    n_microbatches: Optional[int] = None
    #: pull the pipeline input out of the batch (default: ``batch[0]``
    #: for tuple/list batches, else the batch itself)
    input_of: Optional[Callable] = None


def _pipe_input(batch):
    if isinstance(batch, (tuple, list)):
        return batch[0]
    return batch


class ParallelPlan:
    """One named mesh + the specs to compile a composed train step.

    Args:
      axes: either a mapping ``{axis: size}`` (at most one size may be
        ``-1`` — inferred from the device count) or a sequence of axis
        names, auto-factorised balanced with larger factors first
        (:func:`~chainermn_tpu.parallel.mesh.best_mesh_shape`; the
        largest factor lands on the first — DCN-most — axis). Axis names
        come from :data:`~chainermn_tpu.parallel.plan_specs.
        CANONICAL_AXES`; mesh order is canonical regardless of input
        order (``model`` last — the ICI-fastest slot, the repo's mesh
        convention).
      devices: device list (default ``jax.devices()``). Layout is
        ICI-topology-aware via :func:`~chainermn_tpu.parallel.mesh.
        make_mesh` — on a pod slice the 2-D ``(dcn, ici)`` factorisation
        falls out of the canonical order.
      zero_stacked_groups: chunk the STACKED groups' (``model``/``pipe``)
        optimizer state over the ``zero`` axis too (ISSUE 13 — TP x ZeRO
        per arXiv:2004.13336): their dp gradient mean becomes the zero
        group's rs > ar > sharded-update > ag per leaf (same wire
        bytes), state leaves stack ``[n_stack, n_zero, ...]``. Requires
        a ``zero`` axis and at least one stacked axis.
    """

    def __init__(
        self,
        axes: Mapping[str, int] | Sequence[str],
        *,
        devices=None,
        zero_stacked_groups: bool = False,
    ) -> None:
        if devices is None:
            devices = jax.devices()
        devices = list(devices)
        n = len(devices)
        if isinstance(axes, Mapping):
            sizes = dict(axes)
            unknown = [a for a, s in sizes.items() if s == -1]
            if len(unknown) > 1:
                raise ValueError(
                    f"at most one axis size may be -1, got {unknown}"
                )
            if unknown:
                rest = math.prod(
                    s for a, s in sizes.items() if a not in unknown
                )
                if rest == 0 or n % rest:
                    raise ValueError(
                        f"cannot infer {unknown[0]!r}: {n} devices do not "
                        f"factor over the explicit sizes {sizes}"
                    )
                sizes[unknown[0]] = n // rest
        else:
            names = list(axes)
            if len(set(names)) != len(names):
                raise ValueError(f"duplicate plan axes: {names}")
            # canonical order first, THEN factorise: the largest factor
            # must land on the first (DCN-most) canonical axis, not on
            # whatever order the caller spelled the names in.
            ordered = [a for a in _ps.CANONICAL_AXES if a in names]
            _ps.resolve_axes(dict.fromkeys(names, 1))  # name validation
            shape = best_mesh_shape(n, len(ordered))
            sizes = dict(zip(ordered, shape))
        self.axes: dict[str, _ps.AxisSpec] = _ps.resolve_axes(sizes)
        shape = tuple(s.size for s in self.axes.values())
        if math.prod(shape) != n:
            raise ValueError(
                f"plan axes {dict((a, s.size) for a, s in self.axes.items())} "
                f"cover {math.prod(shape)} mesh slots but {n} devices were "
                f"given"
            )
        self.mesh = make_mesh(tuple(self.axes), shape, devices)
        #: decision records the plan resolved (``seq_attn_impl``
        #: provenance — the dryrun/bench line and tests read it; same
        #: shape as ``ServingEngine.decisions``).
        self.decisions: list[dict] = []
        self._seq_impl: Optional[str] = None
        self._moe_impl: Optional[str] = None
        self._zsg = bool(zero_stacked_groups)
        if self._zsg:
            if "zero" not in self.axes:
                raise ValueError(
                    "zero_stacked_groups=True needs a 'zero' axis to "
                    "chunk the stacked groups' state over"
                )
            if not any(s.stacked for s in self.axes.values()):
                raise ValueError(
                    "zero_stacked_groups=True needs a stacked axis "
                    "('model'/'pipe') whose state it can chunk — a plain "
                    "zero plan already chunks everything"
                )

    # -- topology accessors -------------------------------------------------

    def axis_size(self, name: str) -> int:
        return self.axes[name].size if name in self.axes else 1

    @property
    def dp_axes(self) -> tuple[str, ...]:
        """Axes the batch shards (and gradients reduce) over."""
        return tuple(a for a in ("data", "zero") if a in self.axes)

    @property
    def dp_size(self) -> int:
        return math.prod(self.axis_size(a) for a in self.dp_axes) or 1

    def batch_spec(self) -> P:
        """Batch sharding: dim 0 over the dp axes (plus ``expert`` when
        present — the expert axis shards tokens too, by batch row), and
        — with a ``seq`` axis — dim 1 (the sequence) over it: every
        batch leaf must then carry ``[B, T, ...]`` with ``T`` divisible
        by the seq size."""
        row_axes = self.dp_axes + (
            ("expert",) if "expert" in self.axes else ()
        )
        if "seq" in self.axes:
            return P(row_axes if row_axes else None, "seq")
        return P(row_axes) if row_axes else P()

    def describe(self) -> dict:
        """Axis sizes + the collectives each spec provider owes the step
        (the dryrun/bench provenance line)."""
        out = {
            "mesh": {a: s.size for a, s in self.axes.items()},
            "collectives": _ps.owed_collectives(self.axes),
            "batch_spec": str(self.batch_spec()),
        }
        if self._zsg:
            out["zero_stacked_groups"] = True
        if self._seq_impl is not None:
            out["seq_attn_impl"] = self._seq_impl
        if self._moe_impl is not None:
            out["moe_dispatch_impl"] = self._moe_impl
        return out

    # -- the seq axis's attention router (ISSUE 13) -------------------------

    @staticmethod
    def seq_local_positions(t_local: int, axis_name: str = "seq"):
        """GLOBAL positions of this shard's ``t_local`` tokens — call
        INSIDE the compiled step (``axis_index * t_local + arange``);
        what sequence-parallel loss functions pass as the model's
        ``positions=`` so rope/learned tables line up across shards."""
        import jax.numpy as jnp

        return (lax.axis_index(axis_name) * t_local
                + jnp.arange(t_local, dtype=jnp.int32))

    def seq_attention(
        self,
        *,
        heads: int,
        t_local: int,
        kv_heads: Optional[int] = None,
        impl: str = "auto",
        causal: bool = True,
        block_q: int = 512,
        block_k: int = 1024,
    ):
        """Resolve the ``seq_attn_impl`` tuning decision and return
        ``(attn_fn, record)`` — ``attn_fn`` matches the ``attention_fn``
        contract of :class:`~chainermn_tpu.models.transformer.
        TransformerBlock` and runs INSIDE the compiled step's shard_map.

        ``impl='auto'`` resolves through the registry (decision
        ``seq_attn_impl``, keyed device_kind x seq-shards x heads x
        T-bucket; table default ``ring`` — no divisibility constraint,
        ``O(T_local)`` resident K/V). An 'auto' resolution to
        ``ulysses`` with ``heads % seq_size != 0`` (or kv heads — GQA)
        force-falls back to ``ring`` with ``source:
        'forced:heads-indivisible'`` recorded in ``plan.decisions``; an
        EXPLICIT ``impl='ulysses'`` with indivisible heads is rejected
        at entry with both numbers named
        (:func:`~chainermn_tpu.parallel.ulysses.
        check_ulysses_divisibility`). The resolved impl's owed HLO
        collectives replace the seq axis's descriptor entry
        (:data:`~chainermn_tpu.parallel.plan_specs.
        SEQ_IMPL_COLLECTIVES`), so :meth:`describe` names what actually
        compiles.
        """
        from chainermn_tpu import tuning
        from chainermn_tpu.ops.flash_attention import interpret_on
        from chainermn_tpu.parallel.ring_attention import (
            seq_ring_attention_local,
        )
        from chainermn_tpu.parallel.ulysses import (
            check_ulysses_divisibility,
            ulysses_attention_local,
        )

        if "seq" not in self.axes:
            raise ValueError("seq_attention needs a 'seq' plan axis")
        n = self.axis_size("seq")
        kvh = int(kv_heads or heads)
        key = tuning.decision_key(
            shape=(n, int(heads), max(1, int(t_local))), dtype="seqattn"
        )
        if impl == "auto":
            winner = tuning.choice(
                "seq_attn_impl", _ps.SEQ_ATTN_IMPLS, key
            )
            source = next(
                (d["source"] for d in tuning.decisions_taken()
                 if d["name"] == "seq_attn_impl" and d["key"] == key),
                "table",
            )
            if winner == "ulysses" and (heads % n or kvh % n):
                winner, source = "ring", "forced:heads-indivisible"
        elif impl in _ps.SEQ_ATTN_IMPLS:
            if impl == "ulysses":
                # explicit request: reject at entry, naming both numbers
                check_ulysses_divisibility(heads, kvh, n)
            winner, source = impl, "explicit"
        else:
            raise ValueError(
                f"seq_attn_impl must be one of "
                f"{_ps.SEQ_ATTN_IMPLS + ('auto',)}, got {impl!r}"
            )
        record = {"name": "seq_attn_impl", "key": key, "winner": winner,
                  "source": source}
        self.decisions.append(record)
        self._seq_impl = winner
        self.axes["seq"] = dataclasses.replace(
            self.axes["seq"],
            collectives=_ps.SEQ_IMPL_COLLECTIVES[winner],
        )
        interpret = interpret_on(self.mesh.devices.flat[0].platform)

        if winner == "ring":
            def attn_fn(q, k, v, *, causal=causal, scale=None, **kw):
                return seq_ring_attention_local(
                    q, k, v, "seq", causal=causal, scale=scale,
                    block_q=block_q, block_k=block_k,
                    interpret=interpret, **kw,
                )
        else:
            def attn_fn(q, k, v, *, causal=causal, scale=None, **kw):
                return ulysses_attention_local(
                    q, k, v, "seq", causal=causal, scale=scale,
                    impl="flash", interpret=interpret, **kw,
                )
        return attn_fn, record

    # -- the expert axis's MoE router (ISSUE 20) ----------------------------

    def moe_layer(
        self,
        *,
        tokens_local: int,
        d_model: int,
        experts_per_shard: int = 1,
        capacity_factor: Optional[float] = 1.25,
        k: int = 1,
        impl: str = "auto",
        dtype=None,
    ):
        """Resolve the ``moe_dispatch`` tuning decision for the
        ``expert`` axis and return ``(moe_fn, record)`` — ``moe_fn(x,
        router_w, expert_fn, expert_params) -> (out, aux)`` runs INSIDE
        the compiled step's shard_map
        (:func:`~chainermn_tpu.parallel.moe.moe_layer_local` with
        ``return_stats=True``). ``aux`` carries the axis-invariant
        ``load_balance`` loss (add ``aux_weight * aux['load_balance']``
        to the task loss) plus the drop/pad accounting
        (``expert_load`` ``[E]``, ``dropped``, ``padded``, ``capacity``
        — globals over the axis, float32 so they ride the plan's metric
        pmean). The resolved impl is recorded in ``plan.decisions``
        (same provenance shape as :meth:`seq_attention`) and named by
        :meth:`describe`."""
        from chainermn_tpu import tuning
        from chainermn_tpu.parallel import moe as _moe

        if "expert" not in self.axes:
            raise ValueError("moe_layer needs an 'expert' plan axis")
        n = self.axis_size("expert")
        e_global = n * int(experts_per_shard)
        if k > e_global:
            raise ValueError(
                f"moe_layer k={k} exceeds n_experts={e_global} "
                f"({n} shards x {experts_per_shard} experts/shard)"
            )
        key = tuning.decision_key(
            shape=(max(1, int(tokens_local)), e_global, int(d_model)),
            dtype=dtype if dtype is not None else jnp.float32,
        )
        if impl == "auto":
            winner = tuning.choice("moe_dispatch", ("sort", "einsum"), key)
            source = next(
                (d["source"] for d in tuning.decisions_taken()
                 if d["name"] == "moe_dispatch" and d["key"] == key),
                "table",
            )
        elif impl in ("sort", "einsum"):
            winner, source = impl, "explicit"
        else:
            raise ValueError(
                f"moe_dispatch impl must be 'sort', 'einsum' or 'auto', "
                f"got {impl!r}"
            )
        record = {"name": "moe_dispatch", "key": key, "winner": winner,
                  "source": source}
        self.decisions.append(record)
        self._moe_impl = winner

        # the token dim shards over every row axis (batch_spec), so the
        # aux stats must reduce over ALL of them — reducing over 'expert'
        # alone would leave per-data-shard aux losses under expert x data
        stats_axes = self.dp_axes + ("expert",)

        def moe_fn(x, router_w, expert_fn, expert_params):
            return _moe.moe_layer_local(
                x, router_w, expert_fn, expert_params, "expert",
                capacity_factor=capacity_factor, k=k,
                dispatch_impl=winner,
                experts_per_shard=experts_per_shard,
                return_stats=True,
                stats_axes=stats_axes,
            )

        return moe_fn, record

    # -- specs --------------------------------------------------------------

    def param_specs(self, params: PyTree, specs: PyTree | None = None) -> PyTree:
        """Full per-leaf ``PartitionSpec`` tree for ``params`` (validated
        against this plan's axes; see :func:`~chainermn_tpu.parallel.
        plan_specs.normalize_param_specs`)."""
        return _ps.normalize_param_specs(params, specs, self.axes)

    def _groups(self, flat_specs):
        return _ps.partition_groups(flat_specs, self.axes)

    @staticmethod
    def _inner(optimizer):
        """Accept a plain optax transform OR a communicator-style
        wrapper: unwrapped through :func:`chainermn_tpu.optimizers.
        inner_transform` so create_train_state / state_specs /
        compile_train_step all agree on the state layout (a wrapper's
        own ``init`` would chunk by the communicator's size, not this
        plan's axes)."""
        from chainermn_tpu.optimizers import inner_transform

        return inner_transform(optimizer)

    def _group_state_init(self, inner, group: str, leaves):
        from chainermn_tpu.parallel.zero import zero_stacked_init

        if group == "zero":
            return zero_stacked_init(inner, leaves, self.axis_size("zero"))
        if group == "rep":
            return inner.init(leaves)
        stack_axes = _ps.group_stack_axes(group)
        if self._zsg:
            z = self.axis_size("zero")

            def fn(ls):
                return zero_stacked_init(inner, ls, z)
        else:
            fn = inner.init
        for _ in stack_axes:
            fn = jax.vmap(fn)
        return fn(leaves)

    def _group_state_spec_leaf(self, group: str) -> P:
        if group == "zero":
            return P("zero")
        if group == "rep":
            return P()
        axes = _ps.group_stack_axes(group)
        if self._zsg:
            axes = axes + ("zero",)
        return P(*axes)

    def state_specs(self, params: PyTree, inner, specs: PyTree | None = None):
        """The full :class:`TrainState` spec pytree the compiled step
        carries — params per their specs, each opt-state group stacked
        over its axis, step/model_state replicated."""
        from chainermn_tpu.training.train_step import TrainState

        inner = self._inner(inner)
        spec_tree = self.param_specs(params, specs)
        flat_p, treedef = jax.tree.flatten(params)
        flat_s = jax.tree.leaves(spec_tree)
        groups = self._groups(flat_s)
        opt_spec = {}
        for grp, idx in groups.items():
            template = jax.eval_shape(
                lambda ls, g=grp: self._group_state_init(inner, g, ls),
                [flat_p[i] for i in idx],
            )
            leaf_spec = self._group_state_spec_leaf(grp)
            opt_spec[grp] = jax.tree.map(lambda _: leaf_spec, template)
        return TrainState(
            params=spec_tree, opt_state=opt_spec, step=P(), model_state=P()
        )

    # -- state --------------------------------------------------------------

    def create_train_state(
        self,
        params: PyTree,
        inner: optax.GradientTransformation,
        *,
        param_specs: PyTree | None = None,
        model_state: PyTree = (),
    ):
        """Initialise the plan-sharded :class:`TrainState`: params placed
        per their specs, each opt-state group created directly in its
        stacked layout and placed sharded (``[n, ...]`` over its axis) —
        no full-state replica ever materialises on one device."""
        from chainermn_tpu.training.train_step import TrainState

        inner = self._inner(inner)
        spec_tree = self.param_specs(params, param_specs)
        flat_p, treedef = jax.tree.flatten(params)
        flat_s = jax.tree.leaves(spec_tree)
        groups = self._groups(flat_s)
        mesh = self.mesh

        def put(leaf, spec):
            # A COPY, not the caller's buffer: device_put aliases when the
            # sharding already matches, and the donating step would then
            # delete the user's template params out from under them (the
            # LocalSGD anchor lesson, measured here too).
            return jax.device_put(
                jnp.array(leaf, copy=True), NamedSharding(mesh, spec)
            )

        placed = jax.tree.unflatten(
            treedef, [put(l, s) for l, s in zip(flat_p, flat_s)]
        )
        opt_state = {}
        for grp, idx in groups.items():
            st = self._group_state_init(inner, grp, [flat_p[i] for i in idx])
            leaf_spec = self._group_state_spec_leaf(grp)
            opt_state[grp] = jax.tree.map(
                lambda e: put(e, leaf_spec), st
            )
        repl = NamedSharding(mesh, P())
        if jax.tree.leaves(model_state):
            model_state = jax.tree.map(
                lambda x: jax.device_put(jnp.asarray(x), repl), model_state
            )
        return TrainState(
            params=placed,
            opt_state=opt_state,
            step=jax.device_put(jnp.zeros((), jnp.int32), repl),
            model_state=model_state,
        )

    # -- the compiled step --------------------------------------------------

    def compile_train_step(
        self,
        loss_fn: Callable,
        inner: optax.GradientTransformation,
        params: PyTree | None = None,
        *,
        param_specs: PyTree | None = None,
        donate: bool = True,
        pipeline: PipelinePlanSpec | None = None,
    ):
        """Compile the ONE composed train step:
        ``step(state, batch) -> (state, metrics)``.

        ``loss_fn`` is the shard-local loss (local-batch mean) in any of
        the :func:`~chainermn_tpu.training.train_step.normalize_loss_fn`
        forms, written against the COLLAPSED param tree (stacked leaves
        arrive as this shard's slice — use the
        :mod:`~chainermn_tpu.parallel.tensor` helpers for model-axis
        leaves). With a ``pipe`` axis pass ``pipeline=`` instead of
        relying on ``loss_fn`` alone (see :class:`PipelinePlanSpec`; the
        plan then calls ``loss_fn`` only if ``pipeline`` is ``None``).

        ``inner`` is a plain optax transform (elementwise when a
        ``zero`` axis is present — the ZeRO constraint); a
        :class:`~chainermn_tpu.optimizers.MultiNodeOptimizer` is
        auto-unwrapped via :func:`~chainermn_tpu.optimizers.
        inner_transform` (wrapper-wire features refused loudly).

        ``params`` is the template the specs compile against; omitting it
        defers the build to the first call (same jit cache — still one
        compile). ``donate=True`` (default) donates the whole state:
        params and opt-state buffers are updated in place, a second step
        re-uploads nothing (pinned structurally in tests/test_plan.py).
        """
        if "pipe" in self.axes and pipeline is None:
            raise ValueError(
                "this plan has a 'pipe' axis: pass pipeline="
                "PipelinePlanSpec(stage_fn, loss_fn, ...)"
            )
        if pipeline is not None and "pipe" not in self.axes:
            raise ValueError("pipeline= given but the plan has no 'pipe' axis")
        inner = self._inner(inner)
        if params is not None:
            return self._build_step(
                loss_fn, inner, params, param_specs, donate, pipeline
            )

        built: list = []

        def step(state, batch):
            if not built:
                built.append(
                    self._build_step(
                        loss_fn, inner, state.params, param_specs, donate,
                        pipeline,
                    )
                )
            return built[0](state, batch)

        step.cache_size = lambda: (
            _jit_cache_size(built[0]) if built else 0
        )
        return step

    def _build_step(self, loss_fn, inner, params, param_specs, donate,
                    pipeline):
        from jax import shard_map

        from chainermn_tpu.parallel.zero import (
            zero_gather_updates,
            zero_grad_scatter,
            zero_param_chunk,
        )
        from chainermn_tpu.training.train_step import (
            TrainState,
            normalize_loss_fn,
        )

        mesh = self.mesh
        dp_axes = self.dp_axes
        dp_total = self.dp_size
        has_seq = "seq" in self.axes
        has_expert = "expert" in self.axes
        n_expert = self.axis_size("expert")
        red_axes = (dp_axes + (("seq",) if has_seq else ())
                    + (("expert",) if has_expert else ()))
        zsg = self._zsg
        #: the dp axes a zero chunk is all-reduced over after the
        #: reduce-scatter over 'zero'
        zero_extra = tuple(a for a in dp_axes if a != "zero")

        def zero_mean_chunk(g):
            return zero_grad_scatter(g, "zero", extra_axes=zero_extra,
                                     total=dp_total)

        spec_tree = self.param_specs(params, param_specs)
        treedef = jax.tree.structure(params)
        flat_specs = jax.tree.leaves(spec_tree)
        #: leaf indices stacked over the expert axis (their grads arrive
        #: fully accumulated via the all_to_all transpose — see below)
        expert_leaves = {
            i for i, s in enumerate(flat_specs) if "expert" in tuple(s)
        }
        if pipeline is not None:
            # Enforce the PipelinePlanSpec contract structurally, not by
            # docstring: a replicated leaf consumed inside stage_fn would
            # receive per-stage gradients with no cross-stage sum, and
            # check_vma=False would mask the divergence as silently wrong
            # params — reject anything not pipe-stacked up front. A
            # composed pipe x model leaf (P('pipe', 'model')) leads with
            # pipe and satisfies the same contract: its stage slice is
            # itself tensor-parallel.
            bad = [
                jax.tree_util.keystr(path)
                for (path, _), spec in zip(
                    jax.tree_util.tree_flatten_with_path(params)[0],
                    flat_specs,
                )
                if not (tuple(spec) and tuple(spec)[0] == "pipe")
            ]
            if bad:
                raise ValueError(
                    "every trainable leaf of a pipe plan must be "
                    f"pipe-stacked (P('pipe') or P('pipe', 'model')); "
                    f"got {bad[:8]} — stage "
                    "leaves carry their own slice per stage, and "
                    "replicated leaves have no cross-stage gradient sum "
                    "(the embed/head-outside contract of make_pipeline)"
                )
        groups = self._groups(flat_specs)
        #: leaf index -> leading stacked dims its local view collapses
        stack_depth = {
            i: len(_ps.group_stack_axes(grp))
            for grp, idx in groups.items() for i in idx
        }
        state_spec = self.state_specs(params, inner, param_specs)
        batch_spec = self.batch_spec()
        n_pipe = self.axis_size("pipe")
        lfn = None if pipeline is not None else normalize_loss_fn(loss_fn)

        def _peel(leaf, n):
            for _ in range(n):
                leaf = leaf[0]
            return leaf

        def _wrap(leaf, n):
            for _ in range(n):
                leaf = leaf[None]
            return leaf

        def collapse(tree):
            flat = treedef.flatten_up_to(tree)
            return jax.tree.unflatten(
                treedef,
                [_peel(l, stack_depth.get(i, 0))
                 for i, l in enumerate(flat)],
            )

        def expand(tree):
            flat = treedef.flatten_up_to(tree)
            return jax.tree.unflatten(
                treedef,
                [_wrap(l, stack_depth.get(i, 0))
                 for i, l in enumerate(flat)],
            )

        def pipe_loss(params_c, batch):
            from chainermn_tpu.parallel.pipeline import (
                pipeline_local,
                unscale_replicated_grads,
            )

            x = (pipeline.input_of or _pipe_input)(batch)
            n_micro = pipeline.n_microbatches or n_pipe
            b = x.shape[0]
            if b % n_micro:
                raise ValueError(
                    f"local batch {b} not divisible by n_microbatches "
                    f"{n_micro}"
                )
            xm = x.reshape((n_micro, b // n_micro) + x.shape[1:])
            ym = pipeline_local(
                lambda p, mb: pipeline.stage_fn(p, mb), params_c, xm, "pipe"
            )
            # every stage computes the same loss from the replicated
            # outputs; the psum replication's shard-local transpose
            # would scale the cotangent by n_stages — undo it exactly.
            ym = unscale_replicated_grads(ym, "pipe")
            y = ym.reshape((b,) + ym.shape[2:])
            out = pipeline.loss_fn(y, batch)
            if isinstance(out, tuple):
                loss, metrics = out
            else:
                loss, metrics = out, {}
            return loss, (metrics, ())

        def local_step(state, batch):
            params_c = collapse(state.params)
            if pipeline is None:
                grad_fn = jax.value_and_grad(lfn, has_aux=True)
                (loss, (metrics, model_state)), grads_c = grad_fn(
                    params_c, batch, state.model_state
                )
            else:
                grad_fn = jax.value_and_grad(pipe_loss, has_aux=True)
                (loss, (metrics, _)), grads_c = grad_fn(params_c, batch)
                model_state = state.model_state

            flat_p = treedef.flatten_up_to(params_c)
            flat_g = treedef.flatten_up_to(grads_c)
            if has_seq:
                # The seq shards each computed the mean loss of their
                # OWN tokens: one fused all-reduce makes every gradient
                # the global token mean before the dp reduction (mean of
                # equal-sized shard means).
                flat_g = lax.pmean(flat_g, "seq")
            if has_expert:
                # Expert shards also each computed their OWN tokens'
                # mean loss, but only the NON-expert leaves need the
                # fused all-reduce: an expert-stacked leaf's gradient
                # already accumulated every shard's cotangents through
                # the all_to_all transpose — reducing it again would mix
                # different experts' grads. Rescale it to the same
                # mean-of-shard-means the pmean gives the rest.
                rep = {i: g for i, g in enumerate(flat_g)
                       if i not in expert_leaves}
                if rep:
                    rep = lax.pmean(rep, "expert")
                flat_g = [
                    flat_g[i] / n_expert if i in expert_leaves else rep[i]
                    for i in range(len(flat_g))
                ]
            flat_u: list = [None] * len(flat_p)
            new_opt = {}

            # Stacked groups + plain replicated: the dp-axes gradient
            # reduction is one fused pmean (TP/pipe leaves included —
            # those axes are extra data parallelism for them; the
            # model/pipe axes themselves are never reduced). With
            # zero_stacked_groups the stacked groups run the zero
            # group's pipeline instead: rs(zero) > ar(other dp) >
            # 1/z-chunk update > ag(zero) per leaf — same wire bytes as
            # the fused pmean they replace, state 1/z per TP/pipe shard.
            for grp, idx in groups.items():
                if grp == "zero" or not idx:
                    continue
                depth = len(_ps.group_stack_axes(grp))
                g = [flat_g[i] for i in idx]
                p_sub = [flat_p[i] for i in idx]
                st = state.opt_state[grp]
                if depth and zsg:
                    gch = [zero_mean_chunk(gi) for gi in g]
                    pch = [zero_param_chunk(pi, "zero") for pi in p_sub]
                    stc = jax.tree.map(
                        lambda e: _peel(e, depth + 1), st
                    )
                    uch, st_out = inner.update(gch, stc, pch)
                    st_out = jax.tree.map(
                        lambda e: _wrap(e, depth + 1), st_out
                    )
                    for i, uc, pi in zip(idx, uch, p_sub):
                        flat_u[i] = zero_gather_updates(uc, pi, "zero")
                    new_opt[grp] = st_out
                    continue
                if dp_axes:
                    g = lax.pmean(g, dp_axes)
                new_in = st
                if depth:
                    new_in = jax.tree.map(lambda e: _peel(e, depth), st)
                u, st_out = inner.update(g, new_in, p_sub)
                if depth:
                    st_out = jax.tree.map(
                        lambda e: _wrap(e, depth), st_out
                    )
                for i, ui in zip(idx, u):
                    flat_u[i] = ui
                new_opt[grp] = st_out

            # ZeRO group: rs(zero) > ar(other dp) > the inner update on
            # the 1/z chunk > ag(zero), per leaf.
            idx = groups.get("zero")
            if idx:
                gch = [zero_mean_chunk(flat_g[i]) for i in idx]
                pch = [zero_param_chunk(flat_p[i], "zero") for i in idx]
                st = jax.tree.map(
                    lambda e: e[0], state.opt_state["zero"]
                )
                uch, st_out = inner.update(gch, st, pch)
                new_opt["zero"] = jax.tree.map(lambda e: e[None], st_out)
                for i, uc in zip(idx, uch):
                    flat_u[i] = zero_gather_updates(uc, flat_p[i], "zero")

            updates_c = jax.tree.unflatten(treedef, flat_u)
            params_c2 = optax.apply_updates(params_c, updates_c)
            metrics = {"loss": loss, **metrics}
            if red_axes:
                metrics = lax.pmean(metrics, red_axes)
                if jax.tree.leaves(model_state):
                    model_state = lax.pmean(model_state, red_axes)
            new_state = TrainState(
                params=expand(params_c2),
                opt_state=new_opt,
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
        jitted = jax.jit(sharded, donate_argnums=(0,) if donate else ())

        def cache_size():
            return _jit_cache_size(jitted)

        try:
            jitted.cache_size = cache_size
            jitted.plan_info = self.describe()
        except (AttributeError, TypeError):
            pass
        return jitted


def _jit_cache_size(jitted) -> Optional[int]:
    try:
        return jitted._cache_size()
    except (AttributeError, TypeError):
        return None


__all__ = ["ParallelPlan", "PipelinePlanSpec"]
