"""ISSUE 3 — the overlapped hierarchical gradient-reduction pipeline.

Covers, per the repo's conventions (dist==single equivalence for every
distributed feature; structural/HLO-level assertions for communication
claims; measured, not asserted in prose):

- bucket-partition edge contract (zero-size leaves, sub-bucket
  payloads, oversized leaves — the satellite fix's unit cases);
- dist == single equivalence (values AND gradients) for all three
  schedules (flat / two_level / zero), through the real train step;
- double-buffered mode bit-matches a hand-rolled one-step-stale
  reference loop (the reference ``double_buffering_optimizer.py``
  (dagger) semantics, as an executable model rather than prose);
- compiled-HLO collective counts pinned per schedule (the
  ppermute-count convention);
- per-bucket ``wire`` trace events (layout + overlapped flag) and the
  eager :class:`OverlappedBucketReducer`'s measured events feeding
  ``summarize_overlap``;
- the ``'auto'`` schedule resolution through the tuning registry.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from chainermn_tpu import create_communicator, create_multi_node_optimizer
from chainermn_tpu.observability import trace
from chainermn_tpu.parallel.reduction_schedule import (
    SCHEDULES,
    OverlappedBucketReducer,
    bucket_partition,
    reduce_tree,
    resolve_schedule,
)

N = 8


@pytest.fixture(scope="module")
def comm():
    return create_communicator("naive")


@pytest.fixture(autouse=True)
def _recorder_off():
    trace.disable()
    yield
    trace.disable()


# ----------------------------------------------------------------------
# Bucket partition edge contract (satellite fix)
# ----------------------------------------------------------------------


class TestBucketPartition:
    def test_payload_smaller_than_bucket_is_one_bucket(self):
        out = bucket_partition([0, 1, 2], [10, 20, 30], 4, 1 << 20)
        assert out == [[0, 1, 2]]

    def test_zero_size_entries_are_skipped_never_empty_buckets(self):
        # all-zero payload: NO buckets (the old code emitted one bucket
        # whose concatenated payload was empty — no max-abs for the
        # int8 scale)
        assert bucket_partition([0, 1], [0, 0], 4, 1 << 20) == []
        # mixed: zero-size entries vanish, the rest keep their layout
        out = bucket_partition([0, 1, 2, 3], [5, 0, 7, 0], 4, 1 << 20)
        assert out == [[0, 2]]
        assert all(b for b in out)  # no empty bucket, ever

    def test_oversized_entry_gets_its_own_bucket_unsplit(self):
        big = (1 << 20)  # 4 MB at itemsize 4 vs 1 MB bucket
        out = bucket_partition([0, 1, 2], [4, big, 4], 4, 1 << 20)
        assert out == [[0], [1], [2]]

    def test_no_degenerate_tail_after_oversized_entry(self):
        big = (1 << 20)
        out = bucket_partition([0, 1], [big, 4], 4, 1 << 20)
        assert out == [[0], [1]]
        assert all(b for b in out)

    def test_float_bucket_partition_wrapper_shares_the_contract(self):
        from chainermn_tpu.optimizers import _float_bucket_partition

        assert _float_bucket_partition([0, 1], [0, 3]) == [[1]]
        assert _float_bucket_partition([0], [0]) == []

    def test_ef_optimizer_survives_zero_size_float_leaf(self, comm):
        """The regression the fix exists for: an EF int8 optimizer with
        a zero-size float leaf must not quantize an empty bucket."""
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8, error_feedback=True,
        )
        params = {"w": jnp.zeros((4,), jnp.float32),
                  "empty": jnp.zeros((0,), jnp.float32)}
        grads = {"w": jnp.full((4,), 0.5, jnp.float32),
                 "empty": jnp.zeros((0,), jnp.float32)}
        state = opt.init(params)

        @jax.jit
        def step(g):
            def body(g):
                updates, _ = opt.update(g, state, params)
                return updates

            return shard_map(
                body, mesh=comm.mesh, in_specs=P(),
                out_specs=P(), check_vma=False,
            )(g)

        updates = step(grads)
        np.testing.assert_allclose(
            np.asarray(updates["w"]), -0.5 * np.ones(4), rtol=2e-2
        )
        assert updates["empty"].shape == (0,)


# ----------------------------------------------------------------------
# dist == single equivalence, all schedules (values AND gradients)
# ----------------------------------------------------------------------


def _loss_fn(p, batch):
    xb, yb = batch
    logits = xb @ p["w"] + p["b"]
    return optax.softmax_cross_entropy_with_integer_labels(
        logits, yb
    ).mean()


def _train(c, params, batch, *, steps=3, inner=None, **opt_kwargs):
    from chainermn_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    opt = create_multi_node_optimizer(
        inner if inner is not None else optax.adam(1e-2), c, **opt_kwargs
    )
    state = create_train_state(params, opt, c)
    step = make_train_step(_loss_fn, opt, c, donate=False)
    for _ in range(steps):
        state, m = step(state, batch)
    return jax.device_get(state.params), float(m["loss"])


class TestScheduleEquivalence:
    @pytest.fixture(scope="class")
    def problem(self, comm):
        rs = np.random.RandomState(0)
        params = {"w": jnp.asarray(rs.randn(5, 3), jnp.float32),
                  "b": jnp.asarray(rs.randn(3), jnp.float32)}
        x = jnp.asarray(rs.randn(16, 5), jnp.float32)
        y = jnp.asarray(np.arange(16) % 3, np.int32)
        return params, (x, y)

    @pytest.mark.parametrize("schedule", SCHEDULES)
    def test_dist_equals_single_values_and_gradients(
        self, comm, problem, schedule
    ):
        """The suite's core invariant, per schedule: the 8-slot
        distributed trajectory (gradients reduced by THIS schedule)
        equals the single-slot one and the legacy default."""
        params, batch = problem
        dist_p, dist_l = _train(comm, params, batch,
                                reduction_schedule=schedule)
        single_p, single_l = _train(comm.sub_communicator([0]), params,
                                    batch, reduction_schedule=schedule)
        legacy_p, legacy_l = _train(comm, params, batch)
        for k in params:
            np.testing.assert_allclose(dist_p[k], single_p[k],
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(dist_p[k], legacy_p[k],
                                       rtol=1e-5, atol=1e-6)
        assert abs(dist_l - single_l) < 1e-6
        assert abs(dist_l - legacy_l) < 1e-6

    def test_two_level_matches_on_two_axis_mesh(self, problem):
        from jax.sharding import Mesh
        from chainermn_tpu.communicators.xla_communicator import (
            HierarchicalCommunicator,
        )

        devs = np.array(jax.devices("cpu")[:8]).reshape(2, 4)
        c2 = HierarchicalCommunicator(mesh=Mesh(devs, ("inter", "intra")))
        params, batch = problem
        p2, l2 = _train(c2, params, batch, reduction_schedule="two_level")
        p1, l1 = _train(c2, params, batch)  # legacy fused pmean
        for k in params:
            np.testing.assert_allclose(p2[k], p1[k], rtol=1e-5, atol=1e-6)
        assert abs(l2 - l1) < 1e-6

    def test_zero_schedule_state_is_sharded_1_over_n(self, comm, problem):
        """The point of 'zero': each shard holds 1/n of the adam state
        (stacked [n, ceil(size/n)] leaves, sharded over the data axis)."""
        from chainermn_tpu.training.train_step import create_train_state

        params, _ = problem
        opt = create_multi_node_optimizer(
            optax.adam(1e-2), comm, reduction_schedule="zero"
        )
        state = create_train_state(params, opt, comm)
        mu = state.opt_state.inner[0].mu
        for k, leaf in params.items():
            chunk = -(-leaf.size // N)
            assert mu[k].shape == (N, chunk), (k, mu[k].shape)
        spec = opt.opt_state_spec()
        assert spec.inner == P(comm.grad_axes[-1])

    def test_zero_schedule_eager_degrade_matches_full_update(
        self, comm, problem
    ):
        """Outside any named-axis context the zero schedule runs the
        vectorised per-chunk update with NO collective — elementwise
        inner => exactly the full-parameter update."""
        params, _ = problem
        g = jax.tree.map(lambda p: jnp.ones_like(p) * 0.1, params)
        opt = create_multi_node_optimizer(
            optax.adam(1e-2), comm, reduction_schedule="zero"
        )
        ref = optax.adam(1e-2)
        state, rstate = opt.init(params), ref.init(params)
        for _ in range(2):
            u, state = jax.jit(opt.update)(g, state, params)
            ru, rstate = ref.update(g, rstate, params)
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
                ),
                u, ru,
            )

    def test_zero_schedule_rejects_unsharded_state_in_context(
        self, comm, problem
    ):
        """A replicated (closed-over) zero state inside shard_map would
        silently update the WRONG chunk — the guard must name the fix."""
        params, _ = problem
        opt = create_multi_node_optimizer(
            optax.adam(1e-2), comm, reduction_schedule="zero"
        )
        state = opt.init(params)  # stacked [n, ...], NOT sharded
        g = jax.tree.map(jnp.ones_like, params)

        def body(gg):
            return opt.update(gg, state, params)[0]

        with pytest.raises(ValueError, match="opt_state_spec"):
            jax.jit(shard_map(
                body, mesh=comm.mesh, in_specs=P(), out_specs=P(),
                check_vma=False,
            ))(g)

    def test_zero_rejects_incompatible_compositions(self, comm):
        with pytest.raises(ValueError, match="double_buffering"):
            create_multi_node_optimizer(
                optax.sgd(0.1), comm, reduction_schedule="zero",
                double_buffering=True,
            )
        with pytest.raises(ValueError, match="int8"):
            create_multi_node_optimizer(
                optax.sgd(0.1), comm, reduction_schedule="zero",
                allreduce_grad_dtype=jnp.int8,
            )
        with pytest.raises(ValueError, match="error_feedback"):
            create_multi_node_optimizer(
                optax.sgd(0.1), comm, reduction_schedule="two_level",
                allreduce_grad_dtype=jnp.int8, error_feedback=True,
            )
        with pytest.raises(ValueError, match="reduction_schedule"):
            create_multi_node_optimizer(
                optax.sgd(0.1), comm, reduction_schedule="ring"
            )


# ----------------------------------------------------------------------
# Double buffering: the stale-update reference model, bit-matched
# ----------------------------------------------------------------------


def test_double_buffer_matches_stale_update_reference_model(comm):
    """An EXECUTABLE reference model of chainermn's documented one-step
    staleness (``double_buffering_optimizer.py`` (dagger)): a
    hand-rolled loop carrying ``bank`` — step t applies ``bank`` (the
    t-1 mean), then banks step t's mean — must bit-match the
    double-buffered optimizer over multiple steps of VARYING gradients.
    The per-step means come from the eager communicator (identical
    psum arithmetic), so the model is independent of the optimizer
    wrapper under test."""
    rs = np.random.RandomState(7)
    steps = 4
    grads_per_step = [rs.randn(N, 6).astype(np.float32) for _ in range(steps)]
    params0 = jnp.zeros((6,), jnp.float32)
    lr = 1.0

    opt = create_multi_node_optimizer(
        optax.sgd(lr), comm, double_buffering=True
    )
    mesh, axes = comm.mesh, comm.grad_axes
    state = opt.init(params0)
    params = params0

    @jax.jit
    def step(params, state, gstack):
        def body(gl):
            updates, new_state = opt.update(gl[0], state, params)
            return optax.apply_updates(params, updates), new_state

        return shard_map(body, mesh=mesh, in_specs=P(axes),
                         out_specs=P(), check_vma=False)(gstack)

    for g in grads_per_step:
        params, state = step(params, state, jnp.asarray(g))

    # Hand-rolled stale-update loop: identical reduction arithmetic via
    # the eager wire, staleness written out literally.
    bank = np.zeros((6,), np.float32)
    ref = np.zeros((6,), np.float32)
    for g in grads_per_step:
        ref = ref - lr * bank                       # apply step t-1's mean
        bank = np.asarray(comm.allreduce_grad(jnp.asarray(g)))  # bank t's
    np.testing.assert_array_equal(np.asarray(params), ref)
    # and the bank in the optimizer state is the LAST step's mean, exactly
    np.testing.assert_array_equal(
        np.asarray(state.communicated_grads), bank
    )


# ----------------------------------------------------------------------
# Structural: compiled-HLO collective counts per schedule
# ----------------------------------------------------------------------


def _compiled_counts(comm, fn, tree, spec_tree=None):
    """Compile fn under shard_map over comm's mesh; count collectives."""
    axes = comm.grad_axes

    def local(t):
        sq = jax.tree.map(lambda l: l[0], t)
        out = fn(sq)
        return jax.tree.map(lambda l: l[None], out)

    spec = jax.tree.map(
        lambda l: P(axes, *([None] * (l.ndim - 1))), tree
    )
    f = jax.jit(shard_map(local, mesh=comm.mesh, in_specs=(spec,),
                          out_specs=spec, check_vma=False))
    txt = f.lower(tree).compile().as_text()
    return {op: txt.count(op) for op in
            ("reduce-scatter(", "all-gather(", "all-reduce(")}


class TestStructural:
    def test_flat_schedule_is_one_allreduce_per_bucket(self, comm):
        tree = {"w": jnp.ones((N, 64, 32)), "b": jnp.ones((N, 32))}
        counts = _compiled_counts(
            comm,
            lambda t: reduce_tree(t, schedule="flat", axes=comm.grad_axes,
                                  compress_dtype=jnp.bfloat16),
            tree,
        )
        assert counts == {"reduce-scatter(": 0, "all-gather(": 0,
                          "all-reduce(": 1}, counts

    def test_two_level_on_flat_mesh_is_rs_plus_ag(self, comm):
        """On a 1-axis mesh the two_level schedule pins the decomposed
        reduce-scatter -> all-gather form: NO all-reduce survives."""
        tree = {"w": jnp.ones((N, 64, 32)), "b": jnp.ones((N, 32))}
        counts = _compiled_counts(
            comm,
            lambda t: reduce_tree(t, schedule="two_level",
                                  axes=comm.grad_axes,
                                  compress_dtype=jnp.bfloat16),
            tree,
        )
        assert counts == {"reduce-scatter(": 1, "all-gather(": 1,
                          "all-reduce(": 0}, counts

    def test_two_level_on_two_axis_mesh_is_rs_ar_ag(self):
        """2-axis mesh: intra reduce-scatter -> inter all-reduce of the
        shard -> intra all-gather, exactly once per bucket (the existing
        TwoDimensionalCommunicator pins, now via the shared layer)."""
        from jax.sharding import Mesh
        from chainermn_tpu.communicators.xla_communicator import (
            TwoDimensionalCommunicator,
        )

        devs = np.array(jax.devices("cpu")[:8]).reshape(2, 4)
        c2 = TwoDimensionalCommunicator(
            mesh=Mesh(devs, ("inter", "intra"))
        )
        tree = {"w": jnp.ones((8, 16, 8)), "b": jnp.ones((8, 8))}

        def local(t):
            sq = jax.tree.map(lambda l: l[0], t)
            out = reduce_tree(sq, schedule="two_level", axes=c2.grad_axes,
                              compress_dtype=jnp.bfloat16)
            return jax.tree.map(lambda l: l[None], out)

        spec = jax.tree.map(
            lambda l: P(("inter", "intra"), *([None] * (l.ndim - 1))),
            tree,
        )
        f = jax.jit(shard_map(local, mesh=c2.mesh, in_specs=(spec,),
                              out_specs=spec, check_vma=False))
        txt = f.lower(tree).compile().as_text()
        counts = {op: txt.count(op) for op in
                  ("reduce-scatter(", "all-gather(", "all-reduce(")}
        assert counts == {"reduce-scatter(": 1, "all-gather(": 1,
                          "all-reduce(": 1}, counts

    def test_zero_schedule_is_rs_plus_ag_per_leaf_no_allreduce(self, comm):
        """The sharded-update pipeline: one reduce-scatter in, one
        all-gather out per parameter leaf, and NO gradient all-reduce
        anywhere in the reduction+update program."""
        from chainermn_tpu.testing import count_primitives

        params = {"w": jnp.ones((5, 3), jnp.float32),
                  "b": jnp.ones((3,), jnp.float32)}
        opt = create_multi_node_optimizer(
            optax.adam(1e-2), comm, reduction_schedule="zero"
        )
        full = opt.init(params)
        sliced = jax.tree.map(lambda e: e[:1], full)
        g = jax.tree.map(jnp.ones_like, params)
        counts = count_primitives(
            lambda gg: opt.update(gg, sliced, params)[0], g,
            axis_env=[(comm.axis_name, N)],
        )
        assert counts.get("reduce_scatter") == 2    # one per leaf
        assert counts.get("all_gather") == 2
        assert not counts.get("psum")               # no grad all-reduce

    def test_wire_events_record_bucket_layout_and_overlap_flag(self, comm):
        """Per-bucket, per-STAGE trace-time wire events: schedule label,
        composition signature, stage payload bytes, and overlapped=True
        exactly under double buffering."""
        from chainermn_tpu.testing import count_primitives

        rec = trace.enable(None)
        tree = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
        env = [(comm.axis_name, N)]
        count_primitives(
            lambda t: reduce_tree(t, schedule="two_level",
                                  axes=comm.grad_axes,
                                  compress_dtype=jnp.bfloat16),
            tree, axis_env=env,
        )
        wires = [e for e in rec.events if e["kind"] == "wire"]
        # on the flat mesh two_level IS rs(data)>ag(data): one wire
        # event per stage, both carrying the composition signature
        assert len(wires) == 2
        assert [w["stage"] for w in wires] == ["rs(data)", "ag(data)"]
        assert all(w["schedule"] == "two_level" for w in wires)
        assert all(w["composition"] == "rs(data)>ag(data)" for w in wires)
        # both stages carry the full bucket payload (in / out of the
        # scatter frame) on the bf16 wire
        assert all(w["nbytes"] == (64 * 32 + 32) * 2 for w in wires)
        assert all(w["overlapped"] is False for w in wires)

        # the double-buffered optimizer tags its buckets overlapped
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm, double_buffering=True
        )
        state = opt.init(jnp.zeros((8,)))
        count_primitives(
            lambda g: opt.update(g, state, jnp.zeros((8,)))[0],
            jnp.ones((8,)), axis_env=env,
        )
        wires = [e for e in rec.events if e["kind"] == "wire"]
        assert wires[-1]["overlapped"] is True
        assert wires[-1]["schedule"] == "flat"

    def test_recorder_does_not_change_the_scheduled_program(self, comm):
        """The observability invariant holds for the new schedules:
        identical jaxpr with the recorder on and off."""
        from chainermn_tpu.testing import count_primitives

        tree = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
        env = [(comm.axis_name, N)]

        def counts(schedule):
            return count_primitives(
                lambda t: reduce_tree(t, schedule=schedule,
                                      axes=comm.grad_axes),
                tree, axis_env=env,
            )

        off = {s: counts(s) for s in ("flat", "two_level")}
        trace.enable(None)
        on = {s: counts(s) for s in ("flat", "two_level")}
        assert on == off


# ----------------------------------------------------------------------
# 'auto' resolution + provenance
# ----------------------------------------------------------------------


class TestAutoResolution:
    def test_table_default_is_flat_with_provenance(self, comm):
        winner, rec = resolve_schedule("cpu", 3 << 20, (8,))
        assert winner == "flat"
        assert rec["name"] == "reduction_schedule"
        assert rec["source"] == "table"
        assert rec["key"].endswith("|sched")

    def test_forced_override_reaches_the_optimizer(self, comm, monkeypatch):
        monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_FORCE",
                           "reduction_schedule=zero")
        opt = create_multi_node_optimizer(
            optax.adam(1e-2), comm, reduction_schedule="auto"
        )
        params = {"w": jnp.ones((6,), jnp.float32)}
        state = opt.init(params)
        from chainermn_tpu.optimizers import _ZeroShardState

        assert isinstance(state, _ZeroShardState)
        assert opt._auto_resolved == "zero"
        assert opt._schedule_provenance["source"] == "forced"
        # resolution is one-shot: spec agrees with the state layout
        assert opt.opt_state_spec().inner == P(comm.grad_axes[-1])

    def test_auto_excludes_zero_under_double_buffering(
        self, comm, monkeypatch
    ):
        monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_FORCE",
                           "reduction_schedule=zero")
        opt = create_multi_node_optimizer(
            optax.sgd(0.1), comm, reduction_schedule="auto",
            double_buffering=True,
        )
        assert "zero" not in opt._auto_candidates
        # the forced override names a non-candidate -> loud error, not
        # a silently wrong layout
        with pytest.raises(ValueError):
            opt.init({"w": jnp.ones((4,))})


# ----------------------------------------------------------------------
# The eager overlapped per-bucket reducer (measured wire events)
# ----------------------------------------------------------------------


class TestOverlappedBucketReducer:
    def test_mean_correct_and_events_measured(self, comm):
        rec = trace.enable(None)
        rs = np.random.RandomState(1)
        stacked = {
            "a": jnp.asarray(rs.randn(N, 100), jnp.float32),
            "b": jnp.asarray(rs.randn(N, 7, 3), jnp.float32),
            "empty": jnp.zeros((N, 0), jnp.float32),
        }
        red = OverlappedBucketReducer(comm, bucket_bytes=100 * 4)
        n_buckets = red.dispatch(stacked)
        assert n_buckets == 2  # 'a' fills one bucket, 'b' the next
        assert red.in_flight
        out = red.collect()
        assert not red.in_flight
        np.testing.assert_allclose(
            np.asarray(out["a"]), np.asarray(stacked["a"]).mean(0),
            rtol=1e-5, atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(out["b"]), np.asarray(stacked["b"]).mean(0),
            rtol=1e-5, atol=1e-6,
        )
        assert out["empty"].shape == (0,)
        wires = [e for e in rec.events if e["kind"] == "wire"]
        assert len(wires) == 2
        for w in wires:
            assert w["schedule"] == "overlap_eager"
            assert w["dur_s"] >= w["blocked_s"] >= 0
        # the rollup trace_report consumes
        ov = trace.summarize_overlap(rec.events)
        assert ov["measured"]["n"] == 2
        assert 0.0 <= ov["measured"]["hidden_fraction"] <= 1.0

    def test_double_dispatch_raises(self, comm):
        red = OverlappedBucketReducer(comm)
        red.dispatch({"g": jnp.ones((N, 4))})
        with pytest.raises(RuntimeError, match="in flight"):
            red.dispatch({"g": jnp.ones((N, 4))})
        red.collect()
        with pytest.raises(RuntimeError, match="no dispatched"):
            red.collect()

    def test_measured_composed_reducer(self, comm):
        """ISSUE 13 satellite (PR 11 follow-up): the eager per-STAGE
        composed executor — mean correct for every derived pipeline,
        one measured ``wire`` event per stage carrying the composition
        signature + ``dur_s``, and the overlap rollup's per-signature
        stage rows gain the measured ``dur_ms`` column."""
        from chainermn_tpu.parallel.reduction_schedule import (
            MeasuredComposedReducer,
        )

        rec = trace.enable(None)
        rs = np.random.RandomState(5)
        stacked = {
            "a": jnp.asarray(rs.randn(N, 33), jnp.float32),
            "b": jnp.asarray(rs.randn(N, 4, 2), jnp.float32),
        }
        for sched, n_stages in (("flat", 1), ("two_level", 2)):
            red = MeasuredComposedReducer(comm, schedule=sched)
            out = red.reduce(stacked)
            jax.tree.map(
                lambda o, g: np.testing.assert_allclose(
                    np.asarray(o), np.asarray(g).mean(0),
                    rtol=1e-5, atol=1e-6,
                ),
                out, stacked,
            )
            sig = red.comp.signature()
            wires = [e for e in rec.events
                     if e["kind"] == "wire"
                     and e.get("composition") == sig]
            assert len(wires) == n_stages, (sig, wires)
            for i, w in enumerate(wires):
                assert w["schedule"] == "composed_eager"
                assert w["stage_index"] == i
                assert w["dur_s"] >= 0
                assert w["nbytes"] > 0
        ov = trace.summarize_overlap(rec.events)
        for sig, row in ov["compositions"].items():
            for st, srow in row["stages"].items():
                assert srow.get("dur_ms") is not None, (sig, st)

    def test_measured_composed_refuses_update_stage(self, comm):
        from chainermn_tpu.parallel.composition import CompositionError
        from chainermn_tpu.parallel.reduction_schedule import (
            MeasuredComposedReducer,
        )

        with pytest.raises(CompositionError, match="sharded_update"):
            MeasuredComposedReducer(comm, schedule="zero")

    def test_staleness_one_loop_matches_reference(self, comm):
        """The reducer's intended double-buffered usage: dispatch step
        t, collect at t+1 — each step's mean arrives exactly once, one
        step late (the async-host reducer's contract, device plane)."""
        rs = np.random.RandomState(3)
        gs = [jnp.asarray(rs.randn(N, 5), jnp.float32) for _ in range(3)]
        red = OverlappedBucketReducer(comm)
        got = []
        for g in gs:
            if red.in_flight:
                got.append(np.asarray(red.collect()))
            red.dispatch(g)
        got.append(np.asarray(red.collect()))
        for g, m in zip(gs, got):
            np.testing.assert_allclose(
                m, np.asarray(g).mean(0), rtol=1e-5, atol=1e-6
            )


# ----------------------------------------------------------------------
# overlap_config plumbing (train step -> trainer -> trace)
# ----------------------------------------------------------------------


def test_trainer_emits_overlap_config(comm):
    from chainermn_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )
    from chainermn_tpu.training.trainer import Trainer

    rec = trace.enable(None)
    params = {"w": jnp.zeros((4, 3), jnp.float32),
              "b": jnp.zeros((3,), jnp.float32)}
    opt = create_multi_node_optimizer(
        optax.sgd(0.1), comm, double_buffering=True,
        reduction_schedule="two_level",
    )
    state = create_train_state(params, opt, comm)
    step = make_train_step(_loss_fn, opt, comm, donate=False)
    data = [
        [(np.ones((4,), np.float32), np.int32(0)) for _ in range(8)]
        for _ in range(2)
    ]

    class It:
        def __iter__(self):
            return iter(data)

    def collate(batch):
        x = np.stack([b[0] for b in batch])
        y = np.stack([b[1] for b in batch])
        return x, y

    tr = Trainer(step, state, It(), comm, collate=collate,
                 out=open(os.devnull, "w"))
    tr.run(2)
    cfgs = [e for e in rec.events if e["kind"] == "overlap_config"]
    assert len(cfgs) == 1
    assert cfgs[0]["double_buffering"] is True
    assert cfgs[0]["staleness"] == 1
    assert cfgs[0]["schedule"] == "two_level"


# ----------------------------------------------------------------------
# ISSUE 15: sliced eager reducers + the comp_slices decision
# ----------------------------------------------------------------------


class TestSlicedEagerReducers:
    def test_overlapped_reducer_sliced_mean_and_slice_events(self, comm):
        """slices=4: one collective flies PER SLICE (the real async
        interleave), each wire event carries its slice address beside
        dur_s/blocked_s, the mean is exact, and the rollup still
        yields a hidden_fraction."""
        rec = trace.enable(None)
        rs = np.random.RandomState(2)
        stacked = {
            "a": jnp.asarray(rs.randn(N, 100), jnp.float32),
            "b": jnp.asarray(rs.randn(N, 7, 3), jnp.float32),
            "empty": jnp.zeros((N, 0), jnp.float32),
        }
        red = OverlappedBucketReducer(comm, bucket_bytes=100 * 4,
                                      slices=4)
        n_buckets = red.dispatch(stacked)
        assert n_buckets == 2
        out = red.collect()
        for k in ("a", "b"):
            np.testing.assert_allclose(
                np.asarray(out[k]), np.asarray(stacked[k]).mean(0),
                rtol=1e-5, atol=1e-6,
            )
        assert out["empty"].shape == (0,)
        wires = [e for e in rec.events if e["kind"] == "wire"]
        assert len(wires) == 8  # 2 buckets x 4 slices
        for w in wires:
            assert w["schedule"] == "overlap_eager"
            assert w["n_slices"] == 4 and 0 <= w["slice"] < 4
            assert w["dur_s"] >= w["blocked_s"] >= 0
        ov = trace.summarize_overlap(rec.events)
        assert ov["measured"]["n"] == 8
        assert 0.0 <= ov["measured"]["hidden_fraction"] <= 1.0

    def test_overlapped_reducer_slice_degrade(self, comm):
        """A 3-element bucket under slices=8 flies 3 collectives —
        min(S, elements), never a zero-size one (the zero-leaf
        contract on the eager path)."""
        rec = trace.enable(None)
        red = OverlappedBucketReducer(comm, slices=8)
        red.dispatch({"g": jnp.ones((N, 3), jnp.float32)})
        out = red.collect()
        np.testing.assert_allclose(np.asarray(out["g"]),
                                   np.ones(3), rtol=1e-6)
        wires = [e for e in rec.events if e["kind"] == "wire"]
        assert len(wires) == 3
        assert all(w["n_slices"] == 3 and w["nbytes"] > 0
                   for w in wires)
        with pytest.raises(ValueError, match="slices"):
            OverlappedBucketReducer(comm, slices=0)

    def test_measured_composed_reducer_sliced(self, comm):
        """The sliced measured executor: 3 stages x 4 slices of wire
        events in skewed order, every one carrying slice address +
        dur_s + blocked_s, the mean exact, and summarize_overlap's
        per-signature stage rows growing the per-slice sub-table with
        measured dur_ms/blocked_ms."""
        from chainermn_tpu.parallel.reduction_schedule import (
            MeasuredComposedReducer,
        )

        rec = trace.enable(None)
        rs = np.random.RandomState(6)
        stacked = {
            "a": jnp.asarray(rs.randn(N, 33), jnp.float32),
            "b": jnp.asarray(rs.randn(N, 4, 2), jnp.float32),
        }
        red = MeasuredComposedReducer(comm, schedule="two_level",
                                      slices=4)
        sig = red.comp.signature()
        assert "[s0..3]" in sig
        out = red.reduce(stacked)
        jax.tree.map(
            lambda o, g: np.testing.assert_allclose(
                np.asarray(o), np.asarray(g).mean(0),
                rtol=1e-5, atol=1e-6,
            ),
            out, stacked,
        )
        wires = [e for e in rec.events
                 if e["kind"] == "wire" and e.get("composition") == sig]
        n_stages = len(red.comp.stages)
        assert len(wires) == n_stages * 4
        for i, w in enumerate(wires):
            assert w["stage_index"] == i
            assert w["n_slices"] == 4 and 0 <= w["slice"] < 4
            assert w["dur_s"] >= 0 and w["blocked_s"] >= 0
            assert w["nbytes"] > 0
        # skew: slice 1's rs event precedes slice 0's inter-level ar
        stages_in_order = [(w["stage"], w["slice"]) for w in wires]
        rs_name = red.comp.stages[0].signature()
        ar_name = red.comp.stages[1].signature()
        assert stages_in_order.index((rs_name, 1)) < \
            stages_in_order.index((ar_name, 0))
        ov = trace.summarize_overlap(rec.events)
        row = ov["compositions"][sig]
        for st, srow in row["stages"].items():
            assert srow["n"] == 4, (st, srow)
            slices = srow["slices"]
            assert set(slices) == {"s0", "s1", "s2", "s3"}
            for sl in slices.values():
                assert sl.get("dur_ms") is not None
                assert sl.get("blocked_ms") is not None

    def test_measured_composed_reducer_zigzag(self, comm):
        """ISSUE 16: the eager measured executor honors the zigzag cut
        — strided slice membership on the way in, comb reassembly on
        the way out, mean still exact."""
        from chainermn_tpu.parallel.reduction_schedule import (
            MeasuredComposedReducer,
        )

        rs = np.random.RandomState(16)
        stacked = {"a": jnp.asarray(rs.randn(N, 37), jnp.float32)}
        sig = "rs(a0)[z0..3]>ag(a0)"
        red = MeasuredComposedReducer(comm, schedule=sig)
        assert red.comp.slice_layout == "zigzag"
        out = red.reduce(stacked)
        np.testing.assert_allclose(
            np.asarray(out["a"]), np.asarray(stacked["a"]).mean(0),
            rtol=1e-5, atol=1e-6,
        )

    def test_measured_composed_sliced_degrade(self, comm):
        from chainermn_tpu.parallel.reduction_schedule import (
            MeasuredComposedReducer,
        )

        rec = trace.enable(None)
        red = MeasuredComposedReducer(comm, schedule="two_level",
                                      slices=8)
        out = red.reduce({"g": jnp.ones((N, 3), jnp.float32)})
        np.testing.assert_allclose(np.asarray(out["g"]), np.ones(3),
                                   rtol=1e-6)
        wires = [e for e in rec.events
                 if e["kind"] == "wire" and e.get("composition")]
        # min(8, 3) slices x the pipeline's stages (2 on a flat mesh)
        assert len(wires) == 3 * len(red.comp.stages)
        assert all(w["n_slices"] == 3 for w in wires)


class TestCompSlicesDecision:
    def test_table_default_is_one(self):
        from chainermn_tpu.parallel.reduction_schedule import (
            resolve_comp_slices,
        )

        assert resolve_comp_slices("cpu", 3 << 20, (2, 2, 2)) == 1
        # ...and the auto schedule resolution stays unsliced
        winner, rec = resolve_schedule("cpu", 3 << 20, (2, 2, 2),
                                       slices="auto")
        assert winner == "flat"
        assert "comp_slices" not in (rec or {})

    def test_forced_slices_slice_the_auto_winner(self, monkeypatch):
        monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_FORCE",
                           "comp_slices=4")
        winner, rec = resolve_schedule("cpu", 3 << 20, (2, 2, 2),
                                       slices="auto")
        assert winner == "ar(a0+a1+a2)[s0..3]"
        assert rec["comp_slices"] == 4
        assert rec["composition"] == winner
        # an explicit integer pins without consulting the registry
        winner2, rec2 = resolve_schedule("cpu", 3 << 20, (2, 2, 2),
                                         slices=2)
        assert winner2 == "ar(a0+a1+a2)[s0..1]"
        # slices=None (the default) is the pre-ISSUE-15 behaviour
        winner3, _ = resolve_schedule("cpu", 3 << 20, (2, 2, 2))
        assert winner3 == "flat"

    def test_sliced_auto_winner_runs_through_the_optimizer(
        self, comm, monkeypatch
    ):
        """End to end: a forced comp_slices=2 'auto' optimizer reduces
        a dyadic tree identically to the flat schedule — the sliced
        winner compiles and runs through the standard update path."""
        monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_FORCE",
                           "comp_slices=2")
        opt = create_multi_node_optimizer(
            optax.sgd(0.5), comm, reduction_schedule="auto"
        )
        params = {"w": jnp.asarray(
            np.arange(N * 24).reshape(N, 24) % 8, jnp.float32) / 8.0}

        def local(p):
            sq = {"w": p["w"][0]}
            sched = opt._effective_schedule(sq)
            out = opt._reduce_scheduled(sq, sched)
            return {"w": out["w"][None]}

        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        f = jax.jit(shard_map(
            local, mesh=comm.mesh,
            in_specs=({"w": P(comm.grad_axes, None)},),
            out_specs={"w": P(comm.grad_axes, None)},
            check_vma=False,
        ))
        out = jax.device_get(f(params))
        assert "[s0..1]" in opt._auto_resolved
        assert opt._schedule_provenance["comp_slices"] == 2
        ref = np.asarray(params["w"]).reshape(N, -1).mean(0)
        np.testing.assert_array_equal(out["w"].reshape(N, -1)[0], ref)


def test_sliced_wire_events_and_pack_degrade_note(comm):
    """ISSUE 15: trace-time events of a SLICED in-jit schedule — one
    wire event per stage per slice (slice/n_slices fields, per-slice
    payloads summing to the unsliced stage bytes), and the pack event
    carrying the requested slice count plus the LOUD min(S, elements)
    degrade provenance when a bucket is smaller than S."""
    from chainermn_tpu.testing import count_primitives

    rec = trace.enable(None)
    tree = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((32,))}
    env = [(comm.axis_name, N)]
    sig = "rs(data)[s0..3]>ag(data)"
    count_primitives(
        lambda t: reduce_tree(t, schedule=sig, axes=comm.grad_axes,
                              compress_dtype=jnp.bfloat16),
        tree, axis_env=env,
    )
    wires = [e for e in rec.events if e["kind"] == "wire"]
    assert len(wires) == 8  # 2 stages x 4 slices
    assert all(w["composition"] == sig for w in wires)
    assert all(w["n_slices"] == 4 and 0 <= w["slice"] < 4
               for w in wires)
    per_stage: dict = {}
    for w in wires:
        per_stage[w["stage"]] = per_stage.get(w["stage"], 0) + w["nbytes"]
    total = (64 * 32 + 32) * 2  # the unsliced bucket on the bf16 wire
    assert per_stage == {"rs(data)": total, "ag(data)": total}
    pack = [e for e in rec.events if e["kind"] == "pack"][-1]
    assert pack["comp_slices"] == 4
    assert "comp_slices_degraded" not in pack  # 2080 elems >> 4

    # degrade: a 3-element payload under S=4 → 3 slices, loud note
    rec2 = trace.enable(None)
    count_primitives(
        lambda t: reduce_tree(t, schedule=sig, axes=comm.grad_axes),
        {"b": jnp.zeros((3,))}, axis_env=env,
    )
    pack2 = [e for e in rec2.events if e["kind"] == "pack"][-1]
    assert pack2["comp_slices"] == 4
    assert pack2["comp_slices_degraded"] == {0: 3}
    assert "min(S, elements)" in pack2["comp_slices_note"]
    wires2 = [e for e in rec2.events if e["kind"] == "wire"]
    assert len(wires2) == 6  # 2 stages x min(4, 3) slices
    assert all(w["n_slices"] == 3 and w["nbytes"] > 0 for w in wires2)
