"""ISSUE 12 — topology-composed collective schedules.

Covers, per the repo's conventions (dist==single equivalence for every
distributed feature; structural/HLO-level assertions for communication
claims — measured, not asserted in prose):

- the VALIDATOR rejection suite: double-reduce, missing axis,
  non-conjugate scatter/gather, empty stage list, misplaced
  sharded_update — each a loud :class:`CompositionError` naming the
  broken invariant;
- the DERIVER property sweep: every derived composition for 1-, 2- and
  3-axis meshes passes the validator, parses back from its signature,
  and reduces EXACTLY like ``flat`` (bitwise, on dyadic inputs whose
  partial sums are exact in f32 — so any reduction order must agree to
  the last bit);
- per-composition structural pins: the compiled HLO's collective
  counts equal :func:`predicted_collectives` for every derived
  composition (the menu's ``flat``/``two_level``/``zero`` pins live in
  test_reduction_schedule.py and must not move — they now route
  through the same executor);
- dist == single equivalence (values AND gradients) for every derived
  composition on the 2x2x2 mesh, through the real train step;
- a composition driving the ParallelPlan-compiled step: the
  single-stage ``ar(all)`` composition compiles to the hand-wired
  plan's exact collective counts AND trajectory, a ladder compiles to
  its predicted per-leaf counts, and ZeRO is expressed as the
  composition ``rs > [ar] > su > ag`` with zero behavior change;
- the satellite error-path fix: ``reduce_tree``'s schedule-name errors
  enumerate valid choices dynamically from ``SCHEDULES``, and
  ``resolve_schedule`` provenance names the composition signature.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu.communicators.xla_communicator import XlaCommunicator
from chainermn_tpu.parallel.composition import (
    Composition,
    CompositionError,
    Stage,
    bind_composition,
    canonical_axis_names,
    compile_schedule,
    derive_compositions,
    flat_composition,
    parse_signature,
    predicted_collectives,
    reduce_composed,
    schedule_candidates,
    signature_for,
    stage_wire_layout,
    two_level_composition,
    validate_composition,
    zero_composition,
)
from chainermn_tpu.parallel.reduction_schedule import (
    SCHEDULES,
    reduce_tree,
    resolve_schedule,
)

N = 8
AXES3 = ("a0", "a1", "a2")


def _comm(shape, names):
    devs = np.array(jax.devices("cpu")[:N]).reshape(shape)
    return XlaCommunicator(mesh=Mesh(devs, names))


@pytest.fixture(scope="module")
def comm3():
    return _comm((2, 2, 2), AXES3)


# ----------------------------------------------------------------------
# Validator rejection suite (each invariant named in the error)
# ----------------------------------------------------------------------


class TestValidator:
    def test_empty_stage_list(self):
        with pytest.raises(CompositionError, match="empty stage list"):
            validate_composition(Composition(()), AXES3)

    def test_double_reduce(self):
        comp = parse_signature("ar(a0+a1+a2)>ar(a0)")
        with pytest.raises(CompositionError,
                           match="reduced more than once"):
            validate_composition(comp, AXES3)

    def test_missing_axis(self):
        comp = parse_signature("rs(a2)>ag(a2)")
        with pytest.raises(CompositionError, match="never reduced"):
            validate_composition(comp, AXES3)

    def test_non_conjugate_gather_axes(self):
        comp = parse_signature("rs(a2)>ar(a0+a1)>ag(a1)")
        with pytest.raises(CompositionError,
                           match="does not conjugate"):
            validate_composition(comp, AXES3)

    def test_non_conjugate_gather_order(self):
        # LIFO violation: inner scatter must close first
        comp = parse_signature("rs(a2)>rs(a1)>ar(a0)>ag(a2)>ag(a1)")
        with pytest.raises(CompositionError,
                           match="does not conjugate"):
            validate_composition(comp, AXES3)

    def test_gather_without_scatter(self):
        comp = parse_signature("ar(a0+a1+a2)>ag(a2)")
        with pytest.raises(CompositionError,
                           match="no open reduce_scatter"):
            validate_composition(comp, AXES3)

    def test_unclosed_scatter(self):
        comp = parse_signature("rs(a2)>ar(a0+a1)")
        with pytest.raises(CompositionError, match="never gathered back"):
            validate_composition(comp, AXES3)

    def test_update_before_reduction_complete(self):
        comp = parse_signature("rs(a2)>su>ar(a0+a1)>ag(a2)")
        with pytest.raises(CompositionError,
                           match="before every axis is reduced"):
            validate_composition(comp, AXES3)

    def test_update_needs_open_scatter(self):
        comp = parse_signature("ar(a0+a1+a2)>su")
        with pytest.raises(CompositionError,
                           match="no open reduce_scatter"):
            validate_composition(comp, AXES3)

    def test_double_update(self):
        comp = parse_signature("rs(a0+a1+a2)>su>su>ag(a0+a1+a2)")
        with pytest.raises(CompositionError,
                           match="more than one sharded_update"):
            validate_composition(comp, AXES3)

    def test_unknown_axis(self):
        comp = parse_signature("ar(bogus)")
        with pytest.raises(CompositionError, match="not on the mesh"):
            validate_composition(comp, AXES3)

    def test_unknown_primitive_and_empty_axes(self):
        with pytest.raises(CompositionError, match="unknown primitive"):
            validate_composition(
                Composition((Stage("alltoall", ("a0",)),)), AXES3
            )
        with pytest.raises(CompositionError, match="empty axis group"):
            validate_composition(
                Composition((Stage("allreduce", ()),)), AXES3
            )

    def test_duplicate_axis_within_stage(self):
        with pytest.raises(CompositionError, match="duplicate axis"):
            validate_composition(
                Composition((Stage("allreduce", ("a0", "a0", "a1", "a2")),)),
                AXES3,
            )

    def test_parse_rejects_garbage(self):
        with pytest.raises(CompositionError, match="unparseable"):
            parse_signature("rs(a0)>frobnicate")
        with pytest.raises(CompositionError, match="carries no axes"):
            parse_signature("rs(a0+a1+a2)>su(a0)>ag(a0+a1+a2)")

    def test_bind_rejects_foreign_axes(self):
        comp = parse_signature("ar(x0+x1)")
        with pytest.raises(CompositionError, match="neither on the mesh"):
            bind_composition(comp, ("data", "model"))


# ----------------------------------------------------------------------
# Deriver property sweep: validate + parse roundtrip + bitwise vs flat
# ----------------------------------------------------------------------


MESHES = {
    1: ((8,), ("a0",)),
    2: ((2, 4), ("a0", "a1")),
    3: ((2, 2, 2), AXES3),
}


class TestDerivation:
    @pytest.mark.parametrize("k", [1, 2, 3])
    def test_derived_set_validates_and_roundtrips(self, k):
        names = canonical_axis_names(k)
        comps = derive_compositions(names)
        # 2^k: every contiguous partition of the reversed axes x the
        # innermost primitive choice, deduped
        assert len(comps) == 2 ** k
        sigs = set()
        for comp in comps:
            validate_composition(comp, names)  # must not raise
            sig = comp.signature()
            assert sig not in sigs
            sigs.add(sig)
            assert parse_signature(sig).signature() == sig
        # the menu's entries are derived instances
        assert flat_composition(names).signature() in sigs
        assert two_level_composition(names).signature() in sigs

    def test_schedule_candidates_menu_plus_novel(self):
        cands = schedule_candidates(3)
        assert cands[:3] == SCHEDULES
        novel = cands[3:]
        assert len(novel) == 2 ** 3 - 2  # minus the two menu signatures
        for sig in novel:
            comp = parse_signature(sig)
            validate_composition(comp, canonical_axis_names(3))

    def test_zero_composition_shapes(self):
        assert zero_composition(("d",)).signature() == "rs(d)>su>ag(d)"
        assert (zero_composition(("data", "zero")).signature()
                == "rs(zero)>ar(data)>su>ag(zero)")
        # the menu labels compile to their derived signatures
        assert signature_for("flat", 3) == "ar(a0+a1+a2)"
        assert signature_for("two_level", 3) == "rs(a2)>ar(a0+a1)>ag(a2)"
        assert signature_for("zero", 3) == "rs(a2)>ar(a0+a1)>su>ag(a2)"

    def test_stage_wire_layout_conjugate_sizes(self):
        comp = parse_signature("rs(a2)>rs(a1)>ar(a0)>ag(a1)>ag(a2)")
        rows = stage_wire_layout(
            comp, {"a0": 2, "a1": 2, "a2": 2}, 4, 100
        )
        assert [r["op"] for r in rows] == [
            "reduce-scatter", "reduce-scatter", "all-reduce",
            "all-gather", "all-gather",
        ]
        # scatter frame: 100 -> 50 -> 25 elements; gathers mirror it
        assert [r["nbytes"] for r in rows] == [400, 200, 100, 200, 400]


# ----------------------------------------------------------------------
# Structural + bitwise: every derived composition vs flat
# ----------------------------------------------------------------------


def _dyadic_tree(rs, shape_map):
    """f32 trees of small integers / 8: every partial sum and the /8
    mean are exact in f32, so ANY reduction order is bitwise equal."""
    return {
        k: jnp.asarray(rs.randint(-16, 16, shape), jnp.float32) / 8.0
        for k, shape in shape_map.items()
    }


def _reduce_counts_and_out(comm, sched, tree):
    axes = comm.grad_axes

    def local(t):
        sq = jax.tree.map(lambda l: l[0], t)
        out = reduce_tree(sq, schedule=sched, axes=axes)
        return jax.tree.map(lambda l: l[None], out)

    spec = jax.tree.map(
        lambda l: P(axes, *([None] * (l.ndim - 1))), tree
    )
    f = jax.jit(shard_map(local, mesh=comm.mesh, in_specs=(spec,),
                          out_specs=spec, check_vma=False))
    txt = f.lower(tree).compile().as_text()
    counts = {
        "reduce-scatter": txt.count("reduce-scatter("),
        "all-reduce": txt.count("all-reduce("),
        "all-gather": txt.count("all-gather("),
    }
    return counts, jax.device_get(f(tree))


class TestDerivedStructuralAndBitwise:
    @pytest.mark.parametrize("k", [1, 2, 3])
    def test_every_derived_composition_counts_and_bitwise_vs_flat(self, k):
        shape, names = MESHES[k]
        comm = _comm(shape, names)
        rs = np.random.RandomState(k)
        tree = _dyadic_tree(rs, {"w": (N, 40, 8), "b": (N, 9)})
        _, ref = _reduce_counts_and_out(comm, "flat", tree)
        for comp in derive_compositions(names):
            counts, out = _reduce_counts_and_out(
                comm, comp.signature(), tree
            )
            assert counts == predicted_collectives(comp), (
                comp.signature(), counts,
            )
            for key in tree:
                np.testing.assert_array_equal(
                    out[key], ref[key],
                    err_msg=f"{comp.signature()} != flat bitwise ({key})",
                )

    def test_menu_names_route_through_the_executor_unchanged(self, comm3):
        """flat/two_level spelled as names and as their signatures are
        the SAME program (signature-spelled pins can't drift from the
        menu pins in test_reduction_schedule.py)."""
        rs = np.random.RandomState(7)
        tree = _dyadic_tree(rs, {"w": (N, 33, 5)})
        for name in ("flat", "two_level"):
            sig = signature_for(name, 3)
            c_name, o_name = _reduce_counts_and_out(comm3, name, tree)
            c_sig, o_sig = _reduce_counts_and_out(comm3, sig, tree)
            assert c_name == c_sig, (name, c_name, c_sig)
            np.testing.assert_array_equal(o_name["w"], o_sig["w"])

    def test_int8_wire_refuses_beyond_menu_compositions(self, comm3):
        ladder = derive_compositions(comm3.grad_axes)[0]
        with pytest.raises(ValueError, match="int8 two-phase wire"):
            reduce_tree(
                {"w": jnp.ones((4,))}, schedule=ladder.signature(),
                axes=comm3.grad_axes, compress_dtype=jnp.int8,
            )


# ----------------------------------------------------------------------
# Satellite: dynamic error path + provenance names the composition
# ----------------------------------------------------------------------


class TestErrorPathAndProvenance:
    def test_reduce_tree_zero_error_enumerates_dynamically(self, comm3):
        valid = tuple(s for s in SCHEDULES if s != "zero")
        with pytest.raises(ValueError) as e:
            reduce_tree({"w": jnp.ones((4,))}, schedule="zero",
                        axes=comm3.grad_axes)
        assert str(valid) in str(e.value)  # derived from SCHEDULES
        assert "MultiNodeOptimizer" in str(e.value)

    def test_reduce_tree_unknown_schedule_names_the_menu(self, comm3):
        with pytest.raises(ValueError, match="unknown schedule"):
            reduce_tree({"w": jnp.ones((4,))}, schedule="ring",
                        axes=comm3.grad_axes)

    def test_resolve_schedule_provenance_names_composition(self):
        winner, rec = resolve_schedule("cpu", 3 << 20, (2, 2, 2))
        assert winner == "flat"  # table default, still a candidate
        assert rec["composition"] == "ar(a0+a1+a2)"
        # candidates include the derived beyond-menu pipelines
        winner2, rec2 = resolve_schedule(
            "cpu", 3 << 20, (2, 2, 2),
            candidates=("rs(a2)>rs(a1)>ar(a0)>ag(a1)>ag(a2)",),
        )
        assert winner2 == "rs(a2)>rs(a1)>ar(a0)>ag(a1)>ag(a2)"
        assert rec2["composition"] == winner2

    def test_optimizer_rejects_update_composition_and_bad_signature(
        self, comm3
    ):
        from chainermn_tpu import create_multi_node_optimizer

        with pytest.raises(ValueError, match="sharded_update"):
            create_multi_node_optimizer(
                optax.sgd(0.1), comm3,
                reduction_schedule="rs(a0+a1+a2)>su>ag(a0+a1+a2)",
            )
        with pytest.raises(ValueError, match="reduction_schedule"):
            create_multi_node_optimizer(
                optax.sgd(0.1), comm3,
                reduction_schedule="rs(a2)>ag(a2)",  # a0/a1 never reduced
            )


# ----------------------------------------------------------------------
# dist == single equivalence for every derived 2x2x2 composition
# ----------------------------------------------------------------------


def _loss_fn(p, batch):
    xb, yb = batch
    logits = xb @ p["w"] + p["b"]
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, yb
    ).mean()


def _train(c, params, batch, *, steps=2, **opt_kwargs):
    from chainermn_tpu import create_multi_node_optimizer
    from chainermn_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    opt = create_multi_node_optimizer(optax.adam(1e-2), c, **opt_kwargs)
    state = create_train_state(params, opt, c)
    step = make_train_step(_loss_fn, opt, c, donate=False)
    for _ in range(steps):
        state, m = step(state, batch)
    return jax.device_get(state.params), float(m["loss"])


class TestTrainerEquivalence:
    @pytest.fixture(scope="class")
    def problem(self):
        rs = np.random.RandomState(0)
        params = {"w": jnp.asarray(rs.randn(5, 3), jnp.float32),
                  "b": jnp.asarray(rs.randn(3), jnp.float32)}
        x = jnp.asarray(rs.randn(16, 5), jnp.float32)
        y = jnp.asarray(np.arange(16) % 3, np.int32)
        return params, (x, y)

    def test_every_derived_composition_dist_equals_single(
        self, comm3, problem
    ):
        """The suite's core invariant per DERIVED composition: the
        2x2x2 distributed trajectory (values AND gradients — two adam
        steps exercise both) equals the single-device one. The
        single-device reference runs the default reduction (a 1-device
        mean is the identity; a 3-axis signature cannot bind there)."""
        params, batch = problem
        single_p, single_l = _train(
            comm3.sub_communicator([0]), params, batch
        )
        for comp in derive_compositions(comm3.grad_axes):
            dist_p, dist_l = _train(
                comm3, params, batch,
                reduction_schedule=comp.signature(),
            )
            for k in params:
                np.testing.assert_allclose(
                    dist_p[k], single_p[k], rtol=1e-5, atol=1e-6,
                    err_msg=comp.signature(),
                )
            assert abs(dist_l - single_l) < 1e-6, comp.signature()


# ----------------------------------------------------------------------
# A composition drives the ParallelPlan-compiled step
# ----------------------------------------------------------------------


def _plan_loss(p, batch):
    xb, yb = batch
    return jnp.mean((xb @ p["w"] - yb) ** 2)


class TestPlanComposition:
    def _mk(self, grad_reduction=None, axes=("data", "zero")):
        from chainermn_tpu.parallel.plan import ParallelPlan

        return ParallelPlan(
            dict.fromkeys(axes, 2) if len(axes) == 3
            else {a: (2 if i == 0 else 4) for i, a in enumerate(axes)},
            devices=jax.devices("cpu")[:N],
            grad_reduction=grad_reduction,
        )

    def _counts(self, plan):
        d = 8
        rs = np.random.RandomState(3)
        params = {"w": jnp.asarray(rs.randn(d, d), jnp.float32)}
        x = jnp.asarray(rs.randn(16, d), jnp.float32)
        y = jnp.asarray(rs.randn(16, d), jnp.float32)
        inner = optax.adam(1e-2)
        step = plan.compile_train_step(_plan_loss, inner, params,
                                       donate=False)
        state = plan.create_train_state(params, inner)
        txt = step.lower(state, (x, y)).compile().as_text()
        counts = {op: txt.count(op + "(") for op in
                  ("all-reduce", "reduce-scatter", "all-gather")}
        for _ in range(2):
            state, m = step(state, (x, y))
        return counts, jax.device_get(state.params), float(m["loss"])

    def test_flat_composition_matches_handwired_dp_plan_exactly(self):
        """Acceptance: a composition drives the plan-compiled step with
        the SAME collective counts as the hand-wired path — on a pure
        dp plan (the rep group actually carries the leaves) the
        ar(data) composition IS the hand-wired fused pmean: identical
        counts AND bitwise-equal trajectory."""
        from chainermn_tpu.parallel.plan import ParallelPlan

        def run(grad_reduction):
            plan = ParallelPlan({"data": 8},
                                devices=jax.devices("cpu")[:N],
                                grad_reduction=grad_reduction)
            return self._counts(plan)

        base, base_p, base_l = run(None)
        comp, comp_p, comp_l = run("flat")
        assert base == comp, (base, comp)
        np.testing.assert_array_equal(base_p["w"], comp_p["w"])
        assert base_l == comp_l

    def test_ladder_on_zero_plan_is_provenance_only(self):
        """On a data x zero plan every replicated leaf is in the ZERO
        group (its own composition), so a grad_reduction ladder must
        change NOTHING in the compiled program — it only re-describes
        the data axis's owed collectives. Counts and trajectory pinned
        equal to the hand-wired base."""
        base, base_p, base_l = self._counts(self._mk(None))
        ladder = "rs(a1)>rs(a0)>ag(a0)>ag(a1)"  # a0=data, a1=zero
        plan = self._mk(ladder)
        assert plan.describe()["grad_reduction"] == \
            "rs(zero)>rs(data)>ag(data)>ag(zero)"
        # the composition is the data axis's spec provider now
        assert plan.describe()["collectives"]["data"] == (
            "reduce-scatter", "all-gather",
        )
        # the zero axis keeps its own provider entry
        assert plan.describe()["collectives"]["zero"] == (
            "reduce-scatter", "all-gather",
        )
        counts, comp_p, l = self._counts(plan)
        assert counts == base, (counts, base)
        np.testing.assert_array_equal(base_p["w"], comp_p["w"])
        assert l == base_l

    def test_composition_drives_tp_plan_with_predicted_stages(self):
        """dp x model plan (no zero): the rep group's gradients ride
        the composed pipeline; compiled counts move EXACTLY by the
        composition's extra stages vs the hand-wired pmean, and the
        trajectory is bitwise-unchanged (dyadic inputs)."""
        from chainermn_tpu.parallel.plan import ParallelPlan

        d = 8
        rs = np.random.RandomState(5)
        params = {"w": (jnp.asarray(
            rs.randint(-8, 8, (d, d)), jnp.float32) / 8.0)}
        x = jnp.asarray(rs.randint(-8, 8, (16, d)), jnp.float32) / 8.0
        y = jnp.asarray(rs.randint(-8, 8, (16, d)), jnp.float32) / 8.0
        inner = optax.sgd(0.5)

        def run(grad_reduction):
            plan = ParallelPlan({"data": 8}, devices=jax.devices("cpu")[:N],
                                grad_reduction=grad_reduction)
            step = plan.compile_train_step(_plan_loss, inner, params,
                                           donate=False)
            state = plan.create_train_state(params, inner)
            txt = step.lower(state, (x, y)).compile().as_text()
            counts = {op: txt.count(op + "(") for op in
                      ("all-reduce", "reduce-scatter", "all-gather")}
            state, m = step(state, (x, y))
            return counts, jax.device_get(state.params)["w"]

        base_counts, base_w = run(None)
        sig = "rs(a0)>ag(a0)"  # the decomposed pipeline over 'data'
        comp_counts, comp_w = run(sig)
        comp = compile_schedule(sig, ("data",))
        pred = predicted_collectives(comp)
        # one param leaf: the composed step carries the base counts
        # minus the grad all-reduce plus the composition's stages
        assert comp_counts["reduce-scatter"] == (
            base_counts["reduce-scatter"] + pred["reduce-scatter"]
        )
        assert comp_counts["all-gather"] == (
            base_counts["all-gather"] + pred["all-gather"]
        )
        assert comp_counts["all-reduce"] == base_counts["all-reduce"] - 1
        np.testing.assert_array_equal(base_w, comp_w)

    def test_zero_is_a_composition_with_zero_behavior_change(self):
        """The acceptance's ZeRO clause, stated structurally: the plan's
        zero group runs rs(zero)>ar(data)>su>ag(zero) (the derived
        instance) and the existing hand-wired count pins in
        test_plan.py keep passing — here we assert the composition the
        group compiles from and that the optimizer's structural 'zero'
        equals it."""
        assert (zero_composition(("data", "zero")).signature()
                == "rs(zero)>ar(data)>su>ag(zero)")
        # the optimizer's 'zero' schedule compiles to the same shape
        assert signature_for("zero", 1) == "rs(a0)>su>ag(a0)"

    def test_grad_reduction_validation(self):
        from chainermn_tpu.parallel.plan import ParallelPlan

        with pytest.raises(ValueError, match="sharded_update"):
            ParallelPlan({"data": 8}, devices=jax.devices("cpu")[:N],
                         grad_reduction="zero")
        with pytest.raises(ValueError, match="needs a data-parallel"):
            ParallelPlan({"model": 8}, devices=jax.devices("cpu")[:N],
                         grad_reduction="flat")
        with pytest.raises(CompositionError, match="never reduced"):
            ParallelPlan({"data": 2, "zero": 4},
                         devices=jax.devices("cpu")[:N],
                         grad_reduction="rs(zero)>ag(zero)")


# ----------------------------------------------------------------------
# ISSUE 15: bucket-sliced composed reduction
# ----------------------------------------------------------------------


class TestSlicedComposition:
    """The sliced-stage DSL: grammar roundtrip, validator invariants,
    the slice_bounds zero-leaf contract, and the structural pin — a
    sliced composition's compiled HLO carries exactly S× the per-stage
    collective count at 1/S payload (total wire bytes unchanged) and
    is BITWISE == flat on exact-dyadic inputs."""

    def test_signature_roundtrip_compact_and_expanded(self):
        from chainermn_tpu.parallel.composition import (
            expand_slices,
            sliced_composition,
        )

        comp = sliced_composition(two_level_composition(AXES3), 4)
        sig = comp.signature()
        assert sig == "rs(a2)[s0..3]>ar(a0+a1)>ag(a2)"
        assert parse_signature(sig) == comp
        validate_composition(comp, AXES3)
        # expanded spelling: per-stage [sI:S] addresses, skewed order,
        # parseable and valid (per-slice conjugacy)
        ex = expand_slices(comp, 64)
        assert len(ex) == 12 and ex[0].signature() == "rs(a2)[s0:4]"
        ex_sig = ">".join(s.signature() for s in ex)
        ex_comp = parse_signature(ex_sig)
        validate_composition(ex_comp, AXES3)
        assert ex_comp.signature() == ex_sig
        # the skew: slice 1's rs is issued before slice 0's ar
        order = [s.signature() for s in ex]
        assert order.index("rs(a2)[s1:4]") < order.index(
            "ar(a0+a1)[s0:4]")
        # the ONE front door reconstitutes the expanded spelling to
        # the compact executable form (review finding: an expanded
        # composition validated but would have executed as a flat
        # double-reduction) — and a heterogeneous expansion, where
        # slices run different pipelines, is refused loudly.
        from chainermn_tpu.parallel.composition import compact_slices

        assert compile_schedule(ex_sig, AXES3) == comp
        assert compact_slices(ex_comp) == comp
        het = parse_signature(
            "rs(a2)[s0:2]>ar(a0+a1)[s0:2]>ag(a2)[s0:2]"
            ">ar(a0+a1+a2)[s1:2]")
        validate_composition(het, AXES3)  # mathematically fine...
        with pytest.raises(CompositionError,
                           match="different pipeline"):
            compact_slices(het)  # ...but not executable

    def test_slice_bounds_contract(self):
        from chainermn_tpu.parallel.composition import (
            effective_slices,
            slice_bounds,
        )

        # degrade: S > elements -> min(S, elements); S == elements ok
        assert effective_slices(8, 3) == 3
        assert effective_slices(4, 4) == 4
        assert effective_slices(4, 0) == 1  # zero-leaf floor
        with pytest.raises(CompositionError, match=">= 1"):
            effective_slices(0, 10)
        for n, s in ((10, 4), (8, 8), (7, 3), (1, 1)):
            bounds = slice_bounds(n, s)
            assert len(bounds) == s
            assert bounds[0][0] == 0 and bounds[-1][1] == n
            for (a0, b0), (a1, b1) in zip(bounds, bounds[1:]):
                assert b0 == a1  # disjoint, covering
            assert all(hi > lo for lo, hi in bounds)  # never empty

    def test_validator_rejections(self):
        from chainermn_tpu.parallel.composition import sliced_composition

        with pytest.raises(CompositionError, match="unsliceable"):
            validate_composition(
                Composition(zero_composition(AXES3).stages, slices=2),
                AXES3,
            )
        with pytest.raises(CompositionError, match="cannot be sliced"):
            sliced_composition(zero_composition(AXES3), 2)
        with pytest.raises(CompositionError, match="slices must be"):
            validate_composition(
                Composition(flat_composition(AXES3).stages, slices=0),
                AXES3,
            )
        # expanded form: a slice whose pipeline is incomplete
        with pytest.raises(CompositionError, match="slice s1:2"):
            validate_composition(
                parse_signature("rs(a2)[s0:2]>rs(a2)[s1:2]"
                                ">ar(a0+a1)[s0:2]>ag(a2)[s0:2]"),
                AXES3,
            )
        # expanded form: mixed addressed/unaddressed stages
        with pytest.raises(CompositionError, match="no slice address"):
            validate_composition(
                parse_signature("ar(a0+a1+a2)[s0:2]>ar(a0+a1+a2)"),
                ("a0", "a1", "a2"),
            )
        # conflicting totals
        with pytest.raises(CompositionError, match="slice totals"):
            validate_composition(
                parse_signature("ar(a0+a1+a2)[s0:2]>ar(a0+a1+a2)[s1:3]"),
                AXES3,
            )
        with pytest.raises(CompositionError, match="must start at s0"):
            parse_signature("rs(a2)[s1..3]>ar(a0+a1)>ag(a2)")

    def test_sliced_wire_layout_bytes_conserved(self):
        """Per-slice rows at 1/S payload each; summed over slices the
        per-stage wire bytes equal the unsliced rendering's (divisible
        size, so no padding slack)."""
        from chainermn_tpu.parallel.composition import sliced_composition

        sizes = {"a0": 2, "a1": 2, "a2": 2}
        base = parse_signature("rs(a2)>rs(a1)>ar(a0)>ag(a1)>ag(a2)")
        flat_rows = stage_wire_layout(base, sizes, 4, 128)
        for S in (2, 4, 8):
            rows = stage_wire_layout(
                sliced_composition(base, S), sizes, 4, 128)
            assert len(rows) == S * len(flat_rows)
            per_stage: dict = {}
            for r in rows:
                assert r["n_slices"] == S and 0 <= r["slice"] < S
                per_stage[r["stage"]] = (
                    per_stage.get(r["stage"], 0) + r["nbytes"])
            assert per_stage == {
                r["stage"]: r["nbytes"] for r in flat_rows
            }, S

    @pytest.mark.parametrize("k", [1, 2, 3])
    def test_sliced_counts_and_bitwise_vs_flat(self, k):
        """The acceptance pin, per mesh depth: every slice count of
        the two_level instance compiles to EXACTLY S× the per-stage
        collectives and reduces bitwise == flat through the real
        bucketed reduction (dyadic inputs)."""
        from chainermn_tpu.parallel.composition import sliced_composition

        shape, names = MESHES[k]
        comm = _comm(shape, names)
        rs = np.random.RandomState(k + 40)
        tree = _dyadic_tree(rs, {"w": (N, 40, 8), "b": (N, 16)})
        _, ref = _reduce_counts_and_out(comm, "flat", tree)
        base = two_level_composition(names)
        for S in (2, 4):
            comp = sliced_composition(base, S)
            counts, out = _reduce_counts_and_out(
                comm, comp.signature(), tree
            )
            pred = predicted_collectives(comp, size=40 * 8 + 16)
            assert counts == pred, (comp.signature(), counts, pred)
            for key in tree:
                np.testing.assert_array_equal(
                    out[key], ref[key],
                    err_msg=f"{comp.signature()} != flat ({key})",
                )

    def test_degrade_below_slice_count(self, comm3):
        """A bucket smaller than S runs min(S, elements) slices —
        never an empty stage or zero-size collective (the PR 3
        zero-leaf contract): a 3-element bucket under S=8 compiles
        exactly 3 of each stage."""
        from chainermn_tpu.parallel.composition import sliced_composition

        comp = sliced_composition(two_level_composition(AXES3), 8)
        rs = np.random.RandomState(9)
        tree = {"b": jnp.asarray(
            rs.randint(-8, 8, (N, 3)), jnp.float32) / 8.0}
        _, ref = _reduce_counts_and_out(comm3, "flat", tree)
        counts, out = _reduce_counts_and_out(
            comm3, comp.signature(), tree)
        assert counts == predicted_collectives(comp, size=3)
        assert counts["all-reduce"] == 3  # min(8, 3), not 8, never 0
        np.testing.assert_array_equal(out["b"], ref["b"])

    def test_sliced_dist_equals_single_through_trainer(self, comm3):
        """The suite's core invariant for the sliced rendering: the
        2x2x2 trajectory (values AND gradients, two adam steps) under
        a sliced schedule equals the single-device one."""
        from chainermn_tpu.parallel.composition import sliced_composition

        rs = np.random.RandomState(0)
        params = {"w": jnp.asarray(rs.randn(5, 3), jnp.float32),
                  "b": jnp.asarray(rs.randn(3), jnp.float32)}
        x = jnp.asarray(rs.randn(16, 5), jnp.float32)
        y = jnp.asarray(np.arange(16) % 3, np.int32)
        single_p, single_l = _train(
            comm3.sub_communicator([0]), params, (x, y)
        )
        sig = sliced_composition(
            two_level_composition(comm3.grad_axes), 4).signature()
        dist_p, dist_l = _train(
            comm3, params, (x, y), reduction_schedule=sig
        )
        for key in params:
            np.testing.assert_allclose(
                dist_p[key], single_p[key], rtol=1e-5, atol=1e-6,
                err_msg=sig,
            )
        assert abs(dist_l - single_l) < 1e-6

    def _int8_counts_and_out(self, comm, sched, tree):
        axes = comm.grad_axes

        def local(t):
            sq = jax.tree.map(lambda m: m[0], t)
            out = reduce_tree(sq, schedule=sched, axes=axes,
                              compress_dtype=jnp.int8)
            return jax.tree.map(lambda m: m[None], out)

        spec = jax.tree.map(
            lambda m: P(axes, *([None] * (m.ndim - 1))), tree
        )
        f = jax.jit(shard_map(local, mesh=comm.mesh, in_specs=(spec,),
                              out_specs=spec, check_vma=False))
        txt = f.lower(tree).compile().as_text()
        return txt.count("all-to-all("), jax.device_get(f(tree))

    def test_int8_wire_sliced_renders_per_slice(self, comm3):
        """ISSUE 16 satellite: sliced spellings of the two int8
        renderings are ACCEPTED (the PR 15 refusal is lifted) and
        render the two-phase wire per bucket slice — S× the
        all_to_all phases in HLO, equivalent to the unsliced int8
        wire within quantization tolerance (per-slice max-abs scales,
        so not bitwise) and to the exact mean within the wire's
        stated error."""
        from chainermn_tpu.parallel.composition import sliced_composition

        S = 4
        rs = np.random.RandomState(5)
        tree = {"w": jnp.asarray(rs.randn(N, 67), jnp.float32)}
        exact = np.mean(np.asarray(tree["w"]), axis=0)
        tol = 4.0 * float(np.abs(tree["w"]).max()) / 127.0
        for base_name in ("flat", "two_level"):
            base = compile_schedule(base_name, AXES3)
            a2a_1, out_1 = self._int8_counts_and_out(
                comm3, base_name, tree)
            sig = sliced_composition(base, S).signature()
            a2a_s, out_s = self._int8_counts_and_out(comm3, sig, tree)
            assert a2a_s == S * a2a_1, (sig, a2a_s, a2a_1)
            np.testing.assert_allclose(
                out_s["w"][0], exact, atol=tol, err_msg=sig)
            np.testing.assert_allclose(
                out_s["w"][0], out_1["w"][0], atol=tol, err_msg=sig)

    def test_int8_wire_sliced_zigzag_layout(self, comm3):
        """The zigzag cut rides the sliced int8 wire too: same HLO
        phase count as contiguous, equivalent within quantization
        tolerance (slice membership differs, so scales differ)."""
        from chainermn_tpu.parallel.composition import sliced_composition

        rs = np.random.RandomState(6)
        tree = {"w": jnp.asarray(rs.randn(N, 53), jnp.float32)}
        exact = np.mean(np.asarray(tree["w"]), axis=0)
        tol = 4.0 * float(np.abs(tree["w"]).max()) / 127.0
        base = two_level_composition(AXES3)
        sig_s = sliced_composition(base, 4).signature()
        sig_z = sliced_composition(base, 4, layout="zigzag").signature()
        a2a_s, out_s = self._int8_counts_and_out(comm3, sig_s, tree)
        a2a_z, out_z = self._int8_counts_and_out(comm3, sig_z, tree)
        assert a2a_z == a2a_s
        np.testing.assert_allclose(out_z["w"][0], exact, atol=tol)
        np.testing.assert_allclose(
            out_z["w"][0], out_s["w"][0], atol=tol)

    def test_int8_wire_still_refuses_beyond_menu_sliced(self, comm3):
        """Slicing does not widen the int8 gate: a sliced spelling of
        a composition whose UNSLICED base is not flat/two_level is
        still refused."""
        from chainermn_tpu.parallel.composition import sliced_composition

        ladder = derive_compositions(comm3.grad_axes)[0]
        sig = sliced_composition(ladder, 2).signature()
        with pytest.raises(ValueError, match="int8 two-phase wire"):
            reduce_tree(
                {"w": jnp.ones((16,))}, schedule=sig,
                axes=comm3.grad_axes, compress_dtype=jnp.int8,
            )

    def test_plan_grad_reduction_accepts_sliced_signature(self):
        """ParallelPlan grad_reduction= accepts a sliced spelling and
        reports it in describe() — the end-to-end plumbing pin (the
        compiled-step equivalence rides dryrun phase M)."""
        from chainermn_tpu.parallel.plan import ParallelPlan

        plan = ParallelPlan(
            {"data": 2, "zero": 4}, devices=jax.devices("cpu")[:N],
            grad_reduction="rs(a1)[s0..1]>rs(a0)>ag(a0)>ag(a1)",
        )
        assert plan.describe()["grad_reduction"] == \
            "rs(zero)[s0..1]>rs(data)>ag(data)>ag(zero)"


# ----------------------------------------------------------------------
# ISSUE 16: broadcast/multicast tree stages + zigzag slice layout
# ----------------------------------------------------------------------


def _bc_counts_and_out(comm, comp, x):
    """Compile a broadcast composition through the one executor and
    return (HLO collective counts incl. collective-permute, output)."""
    axes = comm.grad_axes

    def local(v):
        return reduce_composed(v, comp, op="sum")

    f = jax.jit(shard_map(local, mesh=comm.mesh, in_specs=P(axes),
                          out_specs=P(axes)))
    txt = f.lower(x).compile().as_text()
    import re as _re

    counts = {
        "reduce-scatter": txt.count("reduce-scatter("),
        "all-reduce": txt.count("all-reduce("),
        "all-gather": txt.count("all-gather("),
        "collective-permute": len(
            _re.findall(r"collective-permute(?:-start)?\(", txt)),
    }
    return counts, jax.device_get(f(x))


class TestBroadcastStages:
    """The bc multicast-tree stage family: grammar, validator family
    separation, tree_depth/tree_sends arithmetic, and the structural
    pin — a bc composition's compiled HLO carries exactly
    tree_sends(n, radix) collective-permutes per stage and delivers
    the root's buffer to every member."""

    def test_signature_roundtrip_and_radix_spelling(self):
        from chainermn_tpu.parallel.composition import (
            broadcast_composition,
        )

        comp = parse_signature("bc(a0+a1)@4>bc(a2)")
        assert comp.signature() == "bc(a0+a1)@4>bc(a2)"
        assert parse_signature(comp.signature()) == comp
        validate_composition(comp, AXES3)
        # default radix (@2) is never printed
        one = broadcast_composition(AXES3)
        assert one.signature() == "bc(a0+a1+a2)"
        assert parse_signature("bc(a0+a1+a2)@2") == one
        # compile_schedule front door accepts the spelling
        assert compile_schedule("bc(a0+a1)@4>bc(a2)", AXES3) == comp

    def test_tree_depth_and_sends(self):
        from chainermn_tpu.parallel.composition import (
            tree_depth,
            tree_sends,
        )

        assert tree_depth(8, 2) == 3 and tree_sends(8, 2) == 3
        assert tree_depth(8, 4) == 2 and tree_sends(8, 4) == 4
        assert tree_depth(4, 4) == 1 and tree_sends(4, 4) == 3
        assert tree_depth(1, 2) == 0 and tree_sends(1, 2) == 0
        with pytest.raises(CompositionError, match="radix must be >= 2"):
            tree_depth(8, 1)

    def test_validator_family_separation(self):
        # bc mixed into a reduction pipeline
        with pytest.raises(CompositionError, match="never compose"):
            validate_composition(
                parse_signature("bc(a0)>ar(a1+a2)"), AXES3)
        # missing axis in a broadcast family
        with pytest.raises(CompositionError, match="never broadcast"):
            validate_composition(parse_signature("bc(a0+a1)"), AXES3)
        # doubled axis across stages
        with pytest.raises(CompositionError, match="more than once"):
            validate_composition(
                parse_signature("bc(a0+a1+a2)>bc(a0)"), AXES3)
        # radix on a reduction stage: refused at parse AND validate
        with pytest.raises(CompositionError, match="radix"):
            parse_signature("rs(a2)@4>ar(a0+a1)>ag(a2)")
        with pytest.raises(CompositionError, match="radix"):
            validate_composition(Composition((
                Stage("reduce_scatter", ("a2",), radix=4),
                Stage("allreduce", ("a0", "a1")),
                Stage("allgather", ("a2",)),
            )), AXES3)

    def test_predicted_collectives_contract(self):
        sizes = {"a0": 2, "a1": 2, "a2": 2}
        comp = parse_signature("bc(a0+a1+a2)")
        pred = predicted_collectives(comp, axis_sizes=sizes)
        assert pred == {"reduce-scatter": 0, "all-reduce": 0,
                        "all-gather": 0, "collective-permute": 3}
        # a bc composition without axis_sizes degrades loudly
        with pytest.raises(CompositionError, match="axis_sizes"):
            predicted_collectives(comp)
        # reduction-only counts keep the exact three-key dict
        assert set(predicted_collectives(
            parse_signature("ar(a0+a1+a2)"), axis_sizes=sizes)) == {
                "reduce-scatter", "all-reduce", "all-gather"}

    @pytest.mark.parametrize("sig,cp", [
        ("bc(a0+a1+a2)", 3),       # radix 2: ceil(log2 8) rounds
        ("bc(a0+a1+a2)@4", 4),     # radix 4: 2 rounds x up to 3 sends
        ("bc(a0+a1)@4>bc(a2)", 4),  # 3 sends over n=4 + 1 over n=2
    ])
    def test_hlo_counts_and_root_delivery(self, comm3, sig, cp):
        comp = compile_schedule(sig, comm3.grad_axes)
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(64), jnp.float32)
        counts, out = _bc_counts_and_out(comm3, comp, x)
        sizes = {a: 2 for a in comm3.grad_axes}
        assert counts == predicted_collectives(comp, axis_sizes=sizes), (
            sig, counts)
        assert counts["collective-permute"] == cp, (sig, counts)
        # every member returns the root shard's buffer
        np.testing.assert_array_equal(out, np.tile(np.asarray(x[:8]), 8))


class TestZigzagLayout:
    """ISSUE 16 satellite: the zigzag (strided) slice layout — same
    per-slice element counts as contiguous, so wire layout and HLO
    counts do not move; only the cut/reassembly indexing does, and
    both layouts reduce bitwise-equal."""

    def test_signature_roundtrip_and_rejections(self):
        from chainermn_tpu.parallel.composition import sliced_composition

        comp = sliced_composition(
            two_level_composition(AXES3), 4, layout="zigzag")
        assert comp.signature() == "rs(a2)[z0..3]>ar(a0+a1)>ag(a2)"
        assert parse_signature(comp.signature()) == comp
        validate_composition(comp, AXES3)
        with pytest.raises(CompositionError, match="composition-level"):
            parse_signature("rs(a2)[z1:4]>ar(a0+a1)>ag(a2)")
        with pytest.raises(CompositionError, match="layout"):
            sliced_composition(two_level_composition(AXES3), 4,
                               layout="diagonal")
        with pytest.raises(CompositionError, match="layout"):
            validate_composition(
                Composition(two_level_composition(AXES3).stages,
                            slices=2, slice_layout="diagonal"),
                AXES3)

    def test_wire_layout_identical_to_contiguous(self):
        from chainermn_tpu.parallel.composition import sliced_composition

        sizes = {"a0": 2, "a1": 2, "a2": 2}
        base = two_level_composition(AXES3)
        for n_elems in (128, 103):  # divisible and ragged
            cont = stage_wire_layout(
                sliced_composition(base, 4), sizes, 4, n_elems)
            zig = stage_wire_layout(
                sliced_composition(base, 4, layout="zigzag"),
                sizes, 4, n_elems)
            assert cont == zig, n_elems

    @pytest.mark.parametrize("k", [2, 3])
    def test_bitwise_vs_contiguous_and_flat(self, k):
        shape, names = MESHES[k]
        comm = _comm(shape, names)
        from chainermn_tpu.parallel.composition import sliced_composition

        rs = np.random.RandomState(k + 60)
        # ragged size: the gather tails are where the layouts differ
        tree = _dyadic_tree(rs, {"w": (N, 13, 5), "b": (N, 9)})
        _, ref = _reduce_counts_and_out(comm, "flat", tree)
        base = two_level_composition(names)
        for S in (2, 4):
            zig = sliced_composition(base, S, layout="zigzag")
            counts, out = _reduce_counts_and_out(
                comm, zig.signature(), tree)
            assert counts == predicted_collectives(zig, size=9), (
                zig.signature(), counts)
            for key in tree:
                np.testing.assert_array_equal(
                    out[key], ref[key],
                    err_msg=f"{zig.signature()} != flat ({key})",
                )
