"""The named gradient-reduction schedules and their double-buffered mode.

Covers, per the repo's conventions (dist==single equivalence for every
distributed feature; structural assertions for communication claims;
measured, not asserted in prose):

- bucket-partition edge contract (zero-size leaves, sub-bucket
  payloads, oversized leaves);
- ``flat`` and ``two_level`` on meshes of 8, 2x4 and 2x2x2 devices and
  the float32, bf16 and int8 wires: the mean, and the collectives a
  bucket is written as;
- dist == single through the real train step for ``None``, ``flat``,
  ``two_level`` and ``zero`` on the three meshes;
- the ``zero`` update: state 1/n a shard, a reduce-scatter and an
  all-gather a leaf, updates equal to the replicated update;
- double-buffered mode bit-matches a hand-rolled one-step-stale
  reference loop (the reference ``double_buffering_optimizer.py``
  (dagger) semantics, as an executable model rather than prose);
- ``wire`` trace events a bucket a stage, and the eager
  :class:`OverlappedBucketReducer`'s measured events feeding
  ``summarize_overlap``.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu import create_communicator, create_multi_node_optimizer
from chainermn_tpu.communicators.xla_communicator import XlaCommunicator
from chainermn_tpu.observability import trace
from chainermn_tpu.parallel.reduction_schedule import (
    OverlappedBucketReducer,
    bucket_partition,
    reduce_tree,
)

N = 8


@pytest.fixture(scope="module")
def comm():
    return create_communicator("naive")


#: the meshes the named schedules are held to: one axis, the reference's
#: (inter, intra), and three levels (mesh order: slowest axis first)
MESHES = {"8": ((8,), ("data",)),
          "2x4": ((2, 4), ("inter", "intra")),
          "2x2x2": ((2, 2, 2), ("dcn", "y", "x"))}
WIRES = {"none": None, "bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def mesh_comm():
    """``mesh name -> communicator`` over the eight CPU devices, made
    once a mesh."""
    made = {}

    def get(name):
        if name not in made:
            shape, names = MESHES[name]
            devs = np.array(jax.devices("cpu")[:N]).reshape(shape)
            made[name] = XlaCommunicator(mesh=Mesh(devs, names))
        return made[name]

    return get


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
# The trainer's problem, and what 'zero' refuses
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
# flat / two_level / zero over the three meshes and the three wires
# ----------------------------------------------------------------------


def _stacked_spec(axes, tree):
    return jax.tree.map(lambda l: P(axes, *([None] * (l.ndim - 1))), tree)


def _collectives(txt, ops):
    return {op: txt.count(op) for op in ops}


_LOWERED = ("stablehlo.all_reduce", "stablehlo.reduce_scatter",
            "stablehlo.all_gather", "stablehlo.all_to_all")


def _bucket_as_written(schedule, n_axes, int8):
    """The collectives ONE bucket is written as (the lowered program,
    before XLA combines or splits anything)."""
    ar, rs, ag, a2a = _LOWERED
    if int8 and (schedule == "flat" or n_axes == 1):
        # the two-phase wire: int8 chunks out, scales, int8 shards and
        # their scales back
        return {ar: 0, rs: 0, ag: 3, a2a: 1}
    if int8:
        # exact scatter and gather over the last axis, the two-phase
        # wire over the others
        return {ar: 0, rs: 1, ag: 4, a2a: 1}
    if schedule == "flat":
        return {ar: 1, rs: 0, ag: 0, a2a: 0}
    return {ar: 1 if n_axes > 1 else 0, rs: 1, ag: 1, a2a: 0}


class TestNamedSchedules:
    @pytest.mark.parametrize("wire", sorted(WIRES))
    @pytest.mark.parametrize("mesh", sorted(MESHES))
    @pytest.mark.parametrize("schedule", ["flat", "two_level"])
    def test_mean_and_collective_counts(self, mesh_comm, schedule, mesh,
                                        wire):
        """A bucket is the collectives its schedule's name says, and
        what comes back is the mean: to the bit on the float32 wire
        (eighths, whose sums are exact in any order), inside the wire's
        stated error on the others (bf16: the input rounded once and a
        bf16 sum; int8: two roundings of 1/254 of the largest value
        each)."""
        c = mesh_comm(mesh)
        axes = c.grad_axes
        rs = np.random.RandomState(11)
        if wire == "none":
            vals = lambda *sh: rs.randint(-8, 9, sh) / 8.0  # noqa: E731
        else:
            vals = lambda *sh: rs.randn(*sh)  # noqa: E731
        tree = {"w": jnp.asarray(vals(N, 37, 5), jnp.float32),
                "b": jnp.asarray(vals(N, 5), jnp.float32),
                "e": jnp.zeros((N, 0), jnp.float32)}

        def local(t):
            sq = jax.tree.map(lambda l: l[0], t)
            out = reduce_tree(sq, schedule=schedule, axes=axes,
                              compress_dtype=WIRES[wire])
            return jax.tree.map(lambda l: l[None], out)

        spec = _stacked_spec(axes, tree)
        f = jax.jit(shard_map(local, mesh=c.mesh, in_specs=(spec,),
                              out_specs=spec, check_vma=False))
        lowered = f.lower(tree)
        # (the zero-size leaf takes its own exact path and sends nothing)
        want = _bucket_as_written(schedule, len(axes), wire == "int8")
        assert _collectives(lowered.as_text(), _LOWERED) == want
        if wire != "int8":
            # and what XLA's CPU compiler leaves of them: it neither
            # fuses a scatter and a gather back into an all-reduce nor
            # splits one
            compiled = _collectives(
                lowered.compile().as_text(),
                ("all-reduce(", "reduce-scatter(", "all-gather("))
            assert list(compiled.values()) == list(want.values())[:3], \
                compiled

        out = jax.device_get(f(tree))
        for k in ("w", "b"):
            got = out[k]
            mean = np.asarray(tree[k], np.float64).mean(0)
            # every member holds the mean
            assert all((got[i] == got[0]).all() for i in range(N))
            if wire == "none":
                np.testing.assert_array_equal(got[0], mean.astype(np.float32))
            else:
                amax = float(np.abs(np.asarray(tree[k])).max())
                atol = {"bf16": amax * 2.0 ** -6,
                        "int8": amax / 127 * 1.01}[wire]
                for i in range(N):
                    np.testing.assert_allclose(got[i], mean, rtol=0,
                                               atol=atol)
        assert out["e"].shape == (N, 0)

    @pytest.mark.parametrize("mesh", sorted(MESHES))
    @pytest.mark.parametrize("schedule", [None, "flat", "two_level", "zero"])
    def test_trainer_dist_equals_single(self, mesh_comm, schedule, mesh):
        """The suite's core invariant, per schedule and mesh: three Adam
        steps of the real train step, gradients reduced by THIS
        schedule, against three steps on the whole batch on one device
        (values, and through Adam's first step the gradients)."""
        rs = np.random.RandomState(0)
        params = {"w": jnp.asarray(rs.randn(5, 3), jnp.float32),
                  "b": jnp.asarray(rs.randn(3), jnp.float32)}
        batch = (jnp.asarray(rs.randn(16, 5), jnp.float32),
                 jnp.asarray(np.arange(16) % 3, np.int32))
        dist_p, dist_l = _train(mesh_comm(mesh), params, batch,
                                reduction_schedule=schedule)

        inner = optax.adam(1e-2)
        p, st = params, inner.init(params)
        for _ in range(3):
            loss, g = jax.value_and_grad(_loss_fn)(p, batch)
            u, st = inner.update(g, st, p)
            p = optax.apply_updates(p, u)
        for k in params:
            np.testing.assert_allclose(dist_p[k], np.asarray(p[k]),
                                       rtol=1e-5, atol=1e-6)
        assert abs(dist_l - float(loss)) < 1e-6

    @pytest.mark.parametrize("inner_name", ["sgdm", "adamw"])
    @pytest.mark.parametrize("wire", ["none", "bf16"])
    @pytest.mark.parametrize("mesh", sorted(MESHES))
    def test_zero_update(self, mesh_comm, mesh, wire, inner_name):
        """``'zero'``: each shard of the last axis holds 1/n of the
        inner state (stacked ``[n, ceil(size/n)]``, sharded over that
        axis); a leaf is one reduce-scatter over it in and one
        all-gather out, with no all-reduce on one axis and one of the
        chunk over the others on several; the updates are the inner
        optimizer's on the mean gradient (eighths: exact on both
        wires)."""
        c = mesh_comm(mesh)
        axes = c.grad_axes
        n_last = c.mesh.shape[axes[-1]]
        make = {"sgdm": lambda: optax.sgd(0.5, momentum=0.5),
                "adamw": lambda: optax.adamw(1e-2)}[inner_name]
        rs = np.random.RandomState(5)
        params = {"w": jnp.asarray(rs.randint(-8, 9, (37, 5)) / 8.0,
                                   jnp.float32),
                  "b": jnp.asarray(rs.randint(-8, 9, (5,)) / 8.0,
                                   jnp.float32)}
        grads = {"w": jnp.asarray(rs.randint(-8, 9, (N, 37, 5)) / 8.0,
                                  jnp.float32),
                 "b": jnp.asarray(rs.randint(-8, 9, (N, 5)) / 8.0,
                                  jnp.float32)}
        opt = create_multi_node_optimizer(
            make(), c, reduction_schedule="zero",
            allreduce_grad_dtype=WIRES[wire])
        state = opt.init(params)
        for leaf in jax.tree.leaves(state.inner):
            if leaf.ndim >= 2:
                assert leaf.shape[0] == n_last
                assert leaf.shape[1] in (-(-185 // n_last),
                                         -(-5 // n_last))
        sspec = opt.opt_state_spec()
        assert sspec.inner == P(axes[-1])

        def local(g, st, p):
            return opt.update(jax.tree.map(lambda l: l[0], g), st, p)

        f = jax.jit(shard_map(
            local, mesh=c.mesh,
            in_specs=(_stacked_spec(axes, grads), sspec, P()),
            out_specs=(P(), sspec), check_vma=False))
        counts = _collectives(f.lower(grads, state, params).as_text(),
                              _LOWERED)
        assert counts == {
            "stablehlo.all_reduce": 2 if len(axes) > 1 else 0,
            "stablehlo.reduce_scatter": 2, "stablehlo.all_gather": 2,
            "stablehlo.all_to_all": 0,
        }, counts

        ref = make()
        rstate = ref.init(params)
        mean = jax.tree.map(lambda g: g.mean(0), grads)
        for _ in range(2):
            u, state = f(grads, state, params)
            ru, rstate = ref.update(mean, rstate, params)
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7),
                u, ru)

    @pytest.mark.parametrize("mesh", ["8", "2x4"])
    @pytest.mark.parametrize("schedule", ["flat", "two_level"])
    def test_wire_events_name_their_stage_and_bytes(self, mesh_comm,
                                                    schedule, mesh):
        """Trace-time ``wire`` events: one a bucket a stage, the stage
        named by its collective and axes, its bytes what that stage
        carries (a shard's all-reduce is the bucket over the intra
        size), ``overlapped`` exactly under double buffering."""
        from chainermn_tpu.testing import count_primitives

        c = mesh_comm(mesh)
        axes = c.grad_axes
        env = [(a, c.mesh.shape[a]) for a in axes]
        rec = trace.enable(None)
        tree = {"w": jnp.zeros((64, 32)), "b": jnp.zeros((30,))}
        count_primitives(
            lambda t: reduce_tree(t, schedule=schedule, axes=axes,
                                  compress_dtype=jnp.bfloat16,
                                  bucket_bytes=64 * 32 * 2),
            tree, axis_env=env,
        )
        wires = [e for e in rec.events if e["kind"] == "wire"]
        last, rest = axes[-1], "+".join(axes[:-1])
        stages = {
            ("flat", "8"): ["ar(data)"],
            ("flat", "2x4"): ["ar(inter+intra)"],
            ("two_level", "8"): ["rs(data)", "ag(data)"],
            ("two_level", "2x4"): ["rs(intra)", "ar(inter)", "ag(intra)"],
        }[schedule, mesh]
        assert [(w["bucket"], w["stage"]) for w in wires] == [
            (b, st) for b in (0, 1) for st in stages]
        buckets = [30 * 2, 64 * 32 * 2]  # 'b' sorts first; the bf16 wire
        n_intra = c.mesh.shape[last]
        for w in wires:
            assert w["schedule"] == schedule and w["n_buckets"] == 2
            assert w["stage_index"] == stages.index(w["stage"])
            assert w["wire_dtype"] == "bfloat16"
            assert w["overlapped"] is False
            assert not {"composition", "slice", "n_slices"} & set(w)
            whole = buckets[w["bucket"]]
            if w["stage"] == f"ar({rest})":
                assert w["nbytes"] == -(-whole // 2 // n_intra) * 2
            else:
                assert w["nbytes"] == whole
        pack = [e for e in rec.events if e["kind"] == "pack"][-1]
        assert pack["n_buckets"] == 2 and pack["nbytes"] == sum(buckets)

        # the double-buffered optimizer tags its buckets overlapped
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), c, double_buffering=True,
            reduction_schedule=schedule,
        )
        state = opt.init(jnp.zeros((8,)))
        count_primitives(
            lambda g: opt.update(g, state, jnp.zeros((8,)))[0],
            jnp.ones((8,)), axis_env=env,
        )
        wires = [e for e in rec.events if e["kind"] == "wire"]
        assert wires[-1]["overlapped"] is True
        assert wires[-1]["schedule"] == schedule

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
