"""MultiNodeOptimizer tests — the TPU analog of
``tests/optimizer_tests/test_multi_node_optimizer.py`` (dagger) (SURVEY.md
section 4): applied grads equal the mean of per-rank grads; double-buffering
applies grads with exactly one step of staleness; compressed allreduce stays
close to f32.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from chainermn_tpu import create_communicator, create_multi_node_optimizer
from chainermn_tpu.optimizers import allreduce_gradients, allreduce_grads_transform

N = 8


@pytest.fixture(scope="module")
def comm():
    return create_communicator("naive")


def _per_rank_grads(comm):
    """A jitted step where every mesh slot contributes a different gradient;
    returns what the optimizer applied, for comparison with the numpy mean."""
    rng = np.random.RandomState(0)
    return rng.randn(N, 4).astype(np.float32)


def _run_sharded_update(comm, opt, grads_stacked, params, n_steps=1,
                        state=None):
    """Run `opt.update` inside shard_map over the comm's mesh: the production
    usage pattern (gradient reduction happens in-program). ``state``
    threads a prior run's optimizer state (default: fresh init)."""
    mesh = comm.mesh
    axes = comm.grad_axes

    if state is None:
        state = opt.init(params)

    @jax.jit
    def step(params, state, gstack):
        def body(gstack_local):
            g = gstack_local[0]
            updates, new_state = opt.update(g, state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_state

        return shard_map(
            body,
            mesh=mesh,
            in_specs=P(axes),
            out_specs=P(),
            check_vma=False,
        )(gstack)

    out_params, out_state = params, state
    for _ in range(n_steps):
        out_params, out_state = step(out_params, out_state, grads_stacked)
        state = out_state
        params = out_params
    return out_params, out_state


def test_update_applies_mean_gradient(comm):
    grads = _per_rank_grads(comm)
    params = jnp.zeros((4,), jnp.float32)
    opt = create_multi_node_optimizer(optax.sgd(1.0), comm)
    new_params, _ = _run_sharded_update(comm, opt, grads, params)
    np.testing.assert_allclose(
        np.asarray(new_params), -grads.mean(0), rtol=1e-5, atol=1e-6
    )


def test_outside_axis_context_is_identity_reduction(comm):
    # pjit auto-parallel mode: no named axis => reduction is a no-op and XLA
    # handles averaging via sharding propagation. Single-device: exact.
    opt = create_multi_node_optimizer(optax.sgd(1.0), comm)
    params = jnp.zeros((4,), jnp.float32)
    g = jnp.ones((4,), jnp.float32)
    state = opt.init(params)
    updates, _ = jax.jit(opt.update)(g, state, params)
    np.testing.assert_allclose(np.asarray(updates), -np.ones(4), rtol=1e-6)


def test_double_buffering_staleness_semantics(comm):
    """Step t applies grads reduced at step t-1 (reference
    ``_DoubleBufferingOptimizer`` semantics); step 0 applies zeros."""
    grads = _per_rank_grads(comm)
    params = jnp.zeros((4,), jnp.float32)
    opt = create_multi_node_optimizer(optax.sgd(1.0), comm, double_buffering=True)

    # one step: nothing applied yet
    p1, s1 = _run_sharded_update(comm, opt, grads, params, n_steps=1)
    np.testing.assert_allclose(np.asarray(p1), np.zeros(4), atol=1e-7)
    assert int(jax.device_get(s1.step)) == 1

    # two steps with the same grads: exactly one application
    p2, s2 = _run_sharded_update(comm, opt, grads, params, n_steps=2)
    np.testing.assert_allclose(np.asarray(p2), -grads.mean(0), rtol=1e-5, atol=1e-6)


def test_double_buffer_update_independent_of_same_step_collective(comm):
    """Structural certificate of the overlap PRECONDITION (round-4 VERDICT
    item 3), measured on the traced program: with double buffering, the
    parameter update consumed at step t must NOT data-depend on step t's
    psum — only the banked ``communicated_grads`` state may. That
    independence is exactly what lets an async scheduler run the
    collective concurrently with the update (and, across a scan, with
    step t+1's compute); without it (plain mode) the collective sits on
    the critical path by construction."""
    from chainermn_tpu.testing import collective_taint

    params = jnp.zeros((4,), jnp.float32)
    g = jnp.ones((4,), jnp.float32)

    def updates_of(double_buffering):
        opt = create_multi_node_optimizer(
            optax.sgd(1.0, momentum=0.9), comm,
            double_buffering=double_buffering,
        )
        state = opt.init(params)

        def fn(g, params):
            updates, new_state = opt.update(g, state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_state

        return collective_taint(
            fn, g, params, targets={"psum"},
            axis_env=[(ax, n) for ax, n in
                      zip(comm.mesh.axis_names, comm.mesh.devices.shape)],
        )

    buf_params, buf_state = updates_of(True)
    # The new params are psum-free; the banked grads are psum-derived.
    assert not any(jax.tree.leaves(buf_params))
    assert all(jax.tree.leaves(buf_state.communicated_grads))

    # Sanity check of the analysis itself: plain mode's params DO depend
    # on the same step's psum.
    plain_params, _ = updates_of(False)
    assert all(jax.tree.leaves(plain_params))


def test_double_buffer_scan_next_step_compute_is_collective_free(comm):
    """The scan-level corollary: in a 2-step scanned loop, step t+1's
    forward/backward depends only on params updated with BANKED grads —
    trace one scanned double-buffered step pair and certify the final
    params never acquire a same-step psum dependency."""
    from chainermn_tpu.testing import collective_taint

    opt = create_multi_node_optimizer(
        optax.sgd(1.0), comm, double_buffering=True
    )
    params = jnp.zeros((4,), jnp.float32)
    state = opt.init(params)

    def two_steps(params, state, x):
        def one(carry, _):
            params, state = carry
            loss, g = jax.value_and_grad(
                lambda p: jnp.sum((p * x) ** 2)
            )(params)
            updates, state = opt.update(g, state, params)
            return (optax.apply_updates(params, updates), state), loss

        (params, state), losses = jax.lax.scan(
            one, (params, state), None, length=2
        )
        return params, losses

    taint_params, taint_losses = collective_taint(
        two_steps, params, state, jnp.ones((4,)), targets={"psum"},
        axis_env=[(ax, n) for ax, n in
                  zip(comm.mesh.axis_names, comm.mesh.devices.shape)],
    )
    # After 2 steps the params HAVE absorbed step 0's psum (via the bank)
    # — that is the staleness-1 semantic, not a scheduling hazard. The
    # losses, computed BEFORE each step's update applies, stay psum-free
    # in step 0 and absorb the bank only one step later; the live
    # property certified here is that the scan carry keeps compute and
    # collective decoupled within a step, which the single-step test
    # pins. This scan-level trace guards the carry plumbing: the psum
    # must flow ONLY through communicated_grads.
    assert bool(jax.tree.leaves(taint_params)[0]) is True  # via the bank
    # Step-0 loss precedes any update: must be psum-free.
    # (losses is a stacked [2] array — taint is per-leaf, so assert via a
    # per-step trace instead.)

    def one_step_loss(params, state, x):
        loss, g = jax.value_and_grad(
            lambda p: jnp.sum((p * x) ** 2)
        )(params)
        updates, state = opt.update(g, state, params)
        return optax.apply_updates(params, updates), loss

    t_params, t_loss = collective_taint(
        one_step_loss, params, state, jnp.ones((4,)), targets={"psum"},
        axis_env=[(ax, n) for ax, n in
                  zip(comm.mesh.axis_names, comm.mesh.devices.shape)],
    )
    assert not t_loss      # loss of step t: no same-step collective
    assert not t_params    # update of step t: no same-step collective


def test_collective_taint_tracks_control_dependencies(comm):
    """The analysis must not certify collective-independence for values
    SELECTED by a collective-derived predicate (cond) or loop condition
    (while) — the code-review counterexample for the naive data-only
    propagation."""
    from chainermn_tpu.testing import collective_taint

    ax = comm.axis_name
    env = [(ax, N)]

    def via_cond(g):
        pred = jax.lax.psum(g, ax).sum() > 0
        return jax.lax.cond(pred, lambda: 1.0, lambda: 2.0)

    assert collective_taint(
        via_cond, jnp.ones((4,)), targets={"psum"}, axis_env=env
    )

    def via_while(g):
        s = jax.lax.psum(g, ax).sum()

        def cond(c):
            return c[1] < s

        def body(c):
            return (c[0] + 1.0, c[1] + 1.0)

        return jax.lax.while_loop(cond, body, (0.0, 0.0))[0]

    assert collective_taint(
        via_while, jnp.ones((4,)), targets={"psum"}, axis_env=env
    )

    # And the negative: a cond whose predicate is local stays clean.
    def clean_cond(g):
        return jax.lax.cond(g.sum() > 0, lambda: 1.0, lambda: 2.0)

    assert not collective_taint(
        clean_cond, jnp.ones((4,)), targets={"psum"}, axis_env=env
    )


def test_double_buffer_state_carries_reduced_grads(comm):
    grads = _per_rank_grads(comm)
    params = jnp.zeros((4,), jnp.float32)
    opt = create_multi_node_optimizer(optax.sgd(1.0), comm, double_buffering=True)
    _, state = _run_sharded_update(comm, opt, grads, params, n_steps=1)
    np.testing.assert_allclose(
        np.asarray(state.communicated_grads), grads.mean(0), rtol=1e-5, atol=1e-6
    )


def test_bf16_compressed_allreduce_close(comm):
    grads = _per_rank_grads(comm)
    params = jnp.zeros((4,), jnp.float32)
    opt = create_multi_node_optimizer(
        optax.sgd(1.0), comm, allreduce_grad_dtype=jnp.bfloat16
    )
    new_params, _ = _run_sharded_update(comm, opt, grads, params)
    np.testing.assert_allclose(
        np.asarray(new_params), -grads.mean(0), rtol=2e-2, atol=2e-2
    )


def test_transform_composes_with_chain(comm):
    grads = _per_rank_grads(comm)
    params = jnp.zeros((4,), jnp.float32)
    opt = optax.chain(allreduce_grads_transform(comm), optax.sgd(1.0))

    mesh = comm.mesh
    state = opt.init(params)

    @jax.jit
    def step(gstack):
        def body(g):
            updates, _ = opt.update(g[0], state, params)
            return optax.apply_updates(params, updates)

        return shard_map(
            body, mesh=mesh, in_specs=P(comm.grad_axes), out_specs=P(),
            check_vma=False,
        )(gstack)

    np.testing.assert_allclose(
        np.asarray(step(grads)), -grads.mean(0), rtol=1e-5, atol=1e-6
    )


def test_adam_end_to_end_matches_single_process(comm):
    """Distributed Adam on mean grads == single-process Adam on the big
    batch's mean gradient — the reference's core invariant."""
    grads = _per_rank_grads(comm)
    params = jnp.ones((4,), jnp.float32)
    opt = create_multi_node_optimizer(optax.adam(1e-2), comm)
    dist_params, _ = _run_sharded_update(comm, opt, grads, params, n_steps=3)

    ref_opt = optax.adam(1e-2)
    ref_state = ref_opt.init(params)
    ref_params = params
    for _ in range(3):
        upd, ref_state = ref_opt.update(jnp.asarray(grads.mean(0)), ref_state, ref_params)
        ref_params = optax.apply_updates(ref_params, upd)
    np.testing.assert_allclose(
        np.asarray(dist_params), np.asarray(ref_params), rtol=1e-5, atol=1e-6
    )


def test_broadcast_replicates_params(comm):
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    params = {"w": np.ones((3, 3), np.float32)}
    out = opt.broadcast(params)
    assert out["w"].sharding.is_fully_replicated


def test_allreduce_gradients_function_requires_args():
    with pytest.raises(ValueError):
        allreduce_gradients({"g": jnp.zeros(2)})


class TestInt8CompressedAllreduce:
    """Quantized int8-wire gradient allreduce (beyond the reference's
    fp16 compression): accuracy against the exact mean, the structural
    int8-wire certificate, multi-axis meshes, and the optimizer path."""

    def _exact_and_quant(self, comm, x, axes=None):
        from chainermn_tpu.parallel.collectives import int8_allreduce_mean

        axes = axes or comm.grad_axes
        mesh = comm.mesh

        def run(fn):
            def body(xl):
                return fn(xl[0])[None]

            return jax.jit(shard_map(
                body, mesh=mesh,
                in_specs=P(axes), out_specs=P(axes), check_vma=False,
            ))(x)

        quant = run(lambda v: int8_allreduce_mean(v, axes))
        exact = run(lambda v: jax.lax.pmean(v, axes))
        return np.asarray(quant), np.asarray(exact)

    def test_matches_exact_mean_within_quantization_noise(self, comm):
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(N, 1000).astype(np.float32))
        quant, exact = self._exact_and_quant(comm, x)
        # two rounding stages, each <= amax/254 absolute
        amax = np.abs(np.asarray(x)).max()
        np.testing.assert_allclose(quant[0], exact[0], atol=2 * amax / 100)
        # identical on every shard (it IS an allreduce)
        for r in range(1, N):
            np.testing.assert_array_equal(quant[r], quant[0])

    def test_odd_sizes_and_zero_grads(self, comm):
        rng = np.random.RandomState(8)
        # size not divisible by 8 exercises the pad/unpad path
        x = jnp.asarray(rng.randn(N, 37).astype(np.float32))
        quant, exact = self._exact_and_quant(comm, x)
        amax = np.abs(np.asarray(x)).max()
        np.testing.assert_allclose(quant[0], exact[0], atol=2 * amax / 100)
        # all-zero gradients survive the scale floor exactly
        z = jnp.zeros((N, 16), jnp.float32)
        quant, _ = self._exact_and_quant(comm, z)
        np.testing.assert_array_equal(quant, np.zeros((N, 16)))

    def test_wire_is_int8_structurally(self, comm):
        """The compression claim, measured on the program: the bulk
        collectives (all_to_all chunks + the phase-2 all_gather) carry
        int8; only the two scalar scale gathers are f32."""
        from jax.extend import core as jex_core

        from chainermn_tpu.parallel.collectives import int8_allreduce_mean
        from chainermn_tpu.testing import _subjaxprs

        closed = jax.make_jaxpr(
            lambda g: int8_allreduce_mean(g, "data"),
            axis_env=[("data", N)],
        )(jnp.zeros((1024,), jnp.float32))

        found = {"all_to_all": [], "all_gather": []}

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name in found:
                    found[eqn.primitive.name].append(
                        eqn.invars[0].aval.dtype
                        if not isinstance(eqn.invars[0], jex_core.Literal)
                        else eqn.invars[0].val.dtype
                    )
                for _, sub in _subjaxprs(eqn.params):
                    walk(sub)

        walk(closed.jaxpr)
        assert [str(d) for d in found["all_to_all"]] == ["int8"], found
        gather_dtypes = sorted(str(d) for d in found["all_gather"])
        # one int8 payload gather + three f32/int8... exactly: scales
        # (f32), phase-2 shards (int8), phase-2 scales (f32)
        assert gather_dtypes.count("int8") == 1, found
        assert all(d in ("int8", "float32") for d in gather_dtypes), found

    def test_two_axis_mesh(self):
        comm = create_communicator(
            "hierarchical", devices=jax.devices("cpu")[:N]
        )
        rng = np.random.RandomState(9)
        x = jnp.asarray(rng.randn(N, 65).astype(np.float32))
        quant, exact = self._exact_and_quant(
            comm, x, axes=("inter", "intra")
        )
        amax = np.abs(np.asarray(x)).max()
        np.testing.assert_allclose(quant[0], exact[0], atol=2 * amax / 100)

    @pytest.mark.parametrize("name", ["naive", "two_dimensional"])
    def test_optimizer_path_applies_quantized_mean(self, name):
        comm = create_communicator(
            name, devices=jax.devices("cpu")[:N],
            allreduce_grad_dtype=jnp.int8,
        )
        grads = _per_rank_grads(comm)
        params = jnp.zeros((4,), jnp.float32)
        opt = create_multi_node_optimizer(optax.sgd(1.0), comm)
        new_params, _ = _run_sharded_update(comm, opt, grads, params)
        amax = np.abs(grads).max()
        np.testing.assert_allclose(
            np.asarray(new_params), -grads.mean(0), atol=2 * amax / 100
        )

    def test_identity_outside_axis_context(self):
        from chainermn_tpu.optimizers import allreduce_gradients

        g = jnp.asarray(np.random.RandomState(10).randn(16), jnp.float32)
        out = allreduce_gradients(
            {"g": g}, axis_names=("data",), compress_dtype=jnp.int8
        )
        np.testing.assert_array_equal(np.asarray(out["g"]), np.asarray(g))

    def test_gradient_is_straight_through(self, comm):
        """CLAUDE.md gradient invariant: jax.grad through the quantized
        allreduce equals jax.grad through the exact pmean (the custom
        VJP is the exact mean's transpose — straight-through)."""
        from chainermn_tpu.parallel.collectives import int8_allreduce_mean

        rng = np.random.RandomState(11)
        x = jnp.asarray(rng.randn(N, 24).astype(np.float32))
        W = jnp.asarray(rng.randn(N, 24).astype(np.float32))

        def grad_of(red):
            def body(xl):
                def lf(v):
                    y = red(v[0])
                    idx = jax.lax.axis_index("data")
                    return jnp.sum(y * jax.lax.dynamic_index_in_dim(
                        W, idx, 0, keepdims=False))

                return jax.grad(lf)(xl)

            return np.asarray(jax.jit(shard_map(
                body, mesh=comm.mesh,
                in_specs=P("data"), out_specs=P("data"), check_vma=False,
            ))(x))

        g_quant = grad_of(lambda v: int8_allreduce_mean(v, "data"))
        g_exact = grad_of(lambda v: jax.lax.pmean(v, "data"))
        np.testing.assert_allclose(g_quant, g_exact, rtol=1e-6)

    def test_eager_allreduce_grad_not_truncated(self):
        """The eager debugging path must quantize-dequantize, never raw
        astype(int8) (which truncates sub-1.0 gradients to zero)."""
        comm = create_communicator(
            "naive", devices=jax.devices("cpu")[:N],
            allreduce_grad_dtype=jnp.int8,
        )
        rng = np.random.RandomState(12)
        g = (rng.randn(N, 32) * 0.01).astype(np.float32)  # all |g| << 1
        out = np.asarray(comm.allreduce_grad({"g": g})["g"])
        exact = g.mean(0)
        assert np.abs(out).max() > 0  # not zeroed
        amax = np.abs(g).max()
        np.testing.assert_allclose(out, exact, atol=2 * amax / 100)

    def test_two_dimensional_int8_stays_bucketed(self):
        """The flat-buffer discipline survives the int8 wire: MANY small
        float leaves ride ONE quantized pipeline (1 all_to_all), not one
        per leaf."""
        from jax.sharding import Mesh

        from chainermn_tpu.communicators.xla_communicator import (
            TwoDimensionalCommunicator,
        )
        from chainermn_tpu.testing import count_primitives

        devs = np.array(jax.devices("cpu")[:8]).reshape(2, 4)
        mesh = Mesh(devs, ("inter", "intra"))
        comm2d = TwoDimensionalCommunicator(mesh=mesh)
        tree = {f"p{i}": jnp.zeros((5, 3)) for i in range(12)}
        c = count_primitives(
            lambda t: comm2d.reduce_gradients_in_jit(
                t, compress_dtype=jnp.int8
            ),
            tree, axis_env=[("inter", 2), ("intra", 4)],
        )
        assert c.get("all_to_all") == 1, c  # one bucket -> one pipeline


class TestErrorFeedback:
    """EF-SGD over the int8 wire: the stage-1 quantization error is
    carried in optimizer state and fed into the next message, so the
    CUMULATIVE applied gradient tracks the exact mean to one-step noise
    — where plain deterministic rounding drifts linearly.

    The residual is PER-RANK state: these tests thread it across steps
    explicitly stacked [N, ...] under a P(axes) spec (make_train_step
    refuses EF optimizers for exactly this reason — replicated state
    specs cannot carry per-rank values)."""

    def _run_ef_update(self, comm, opt, grads_stacked, params,
                       n_steps=1):
        from chainermn_tpu.optimizers import _ErrorFeedbackState

        mesh, axes = comm.mesh, comm.grad_axes
        state0 = opt.init(params)
        res = jax.tree.map(
            lambda r: jnp.broadcast_to(r[None], (N,) + r.shape),
            state0.residual,
        )
        inner = state0.inner

        @jax.jit
        def step(params, inner, res, gstack):
            def body(gl, rl):
                st = _ErrorFeedbackState(
                    inner=inner,
                    residual=jax.tree.map(lambda x: x[0], rl),
                )
                updates, new_state = opt.update(gl[0], st, params)
                new_params = optax.apply_updates(params, updates)
                return (
                    new_params,
                    new_state.inner,
                    jax.tree.map(lambda x: x[None], new_state.residual),
                )

            return shard_map(
                body, mesh=mesh,
                in_specs=(P(axes), P(axes)),
                out_specs=(P(), P(), P(axes)), check_vma=False,
            )(gstack, res)

        for _ in range(n_steps):
            params, inner, res = step(params, inner, res, grads_stacked)
        return params, inner, res

    def _cumulative_error(self, error_feedback, steps=30):
        comm = create_communicator("naive")
        rng = np.random.RandomState(21)
        # small values with a deliberate sub-quantum spread: one int8
        # quantum is amax/127, so per-rank rounding bias is material
        grads = (rng.randn(N, 6) * 0.01).astype(np.float32)
        grads[0, :] = 0.9  # sets amax; makes tiny entries sub-quantum
        params = jnp.zeros((6,), jnp.float32)
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8,
            error_feedback=error_feedback,
        )
        if error_feedback:
            new_params, _, _ = self._run_ef_update(
                comm, opt, jnp.asarray(grads), params, n_steps=steps
            )
        else:
            new_params, _ = _run_sharded_update(
                comm, opt, jnp.asarray(grads), params, n_steps=steps
            )
        # params = -sum(applied grads); exact would be -steps * mean
        exact = -steps * grads.mean(0)
        return np.abs(np.asarray(new_params) - exact).max(), grads

    def test_cumulative_bias_removed(self):
        err_plain, grads = self._cumulative_error(False)
        err_ef, _ = self._cumulative_error(True)
        quantum = np.abs(grads).max() / 127.0
        # EF keeps the total error bounded by ~a couple of quanta
        assert err_ef < 4 * quantum, (err_ef, quantum)
        # and beats plain rounding (which accumulates its per-step bias)
        assert err_ef < err_plain / 3, (err_ef, err_plain)

    def test_residuals_are_per_rank_distinct(self):
        """The reason the residual needs a per-rank spec: after one step
        with distinct per-rank grads, the residuals differ by rank."""
        comm = create_communicator("naive")
        grads = _per_rank_grads(comm)
        params = jnp.zeros((4,), jnp.float32)
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8, error_feedback=True,
        )
        _, _, res = self._run_ef_update(
            comm, opt, jnp.asarray(grads), params, n_steps=1
        )
        stacked = np.asarray(jax.tree.leaves(res)[0])  # [N, 4]
        assert not all(
            np.allclose(stacked[r], stacked[0]) for r in range(1, N)
        ), "per-rank residuals should differ for distinct grads"

    def test_non_float_leaves_still_reduced(self):
        """EF must not skip integer leaves: they take the exact pmean
        (reference parity), keeping all ranks' state in sync."""
        from chainermn_tpu.optimizers import _ErrorFeedbackState

        comm = create_communicator("naive")
        mesh, axes = comm.mesh, comm.grad_axes
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8, error_feedback=True,
        )
        g = {
            "w": jnp.asarray(
                np.random.RandomState(3).randn(N, 4), jnp.float32),
            "count": jnp.asarray(
                np.arange(N, dtype=np.int32)[:, None] * np.ones(
                    (1, 2), np.int32)),
        }
        params = {"w": jnp.zeros((4,)),
                  "count": jnp.zeros((2,), jnp.int32)}
        state = opt.init(params)

        def body(gl, rl):
            st = _ErrorFeedbackState(
                inner=state.inner,
                residual=jax.tree.map(lambda x: x[0], rl),
            )
            updates, _ = opt.update(
                jax.tree.map(lambda x: x[0], gl), st, params
            )
            return updates["count"][None]

        res = jax.tree.map(
            lambda r: jnp.broadcast_to(r[None], (N,) + r.shape),
            state.residual,
        )
        out = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P(axes), P(axes)),
            out_specs=P(axes), check_vma=False,
        ))(g, res)
        stacked = np.asarray(out)  # [N, 2]
        # every rank got the same (mean) value for the int leaf
        for r in range(1, N):
            np.testing.assert_array_equal(stacked[r], stacked[0])

    def test_requires_int8_wire(self):
        comm = create_communicator("naive")
        with pytest.raises(ValueError, match="error_feedback requires"):
            create_multi_node_optimizer(
                optax.sgd(1.0), comm,
                allreduce_grad_dtype=jnp.bfloat16, error_feedback=True,
            )

    def test_train_step_carries_residual_per_rank(self):
        """EF through the STANDARD trainer path: make_train_step carries
        the residual sharded over the grad axes (stacked [n, ...]), the
        cumulative applied gradient tracks the exact mean (EF working),
        and the residual array is genuinely per-rank-sharded."""
        from chainermn_tpu.training.train_step import (
            create_train_state,
            make_train_step,
        )

        comm = create_communicator("naive")
        rng = np.random.RandomState(22)
        grads_np = (rng.randn(N, 6) * 0.01).astype(np.float32)
        grads_np[0, :] = 0.9  # amax row: makes tiny entries sub-quantum
        params = {"w": jnp.zeros((6,), jnp.float32)}
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8, error_feedback=True,
        )
        state = create_train_state(params, opt, comm)
        res0 = jax.tree.leaves(state.opt_state.residual)[0]
        assert res0.shape == (N, 6)
        assert not res0.sharding.is_fully_replicated

        # loss = sum(params * batch-row): grad per shard = its batch row
        def loss_fn(p, batch):
            return jnp.sum(p["w"] * batch[0])

        step = make_train_step(loss_fn, opt, comm, donate=False)
        batch = jnp.asarray(grads_np)
        steps = 30
        for _ in range(steps):
            state, _ = step(state, batch)
        exact = -steps * grads_np.mean(0)
        err = np.abs(np.asarray(state.params["w"]) - exact).max()
        quantum = np.abs(grads_np).max() / 127.0
        assert err < 4 * quantum, (err, quantum)
        # residuals differ per rank (per-rank state survived the loop)
        stacked = np.asarray(
            jax.tree.leaves(state.opt_state.residual)[0]
        )
        assert not all(
            np.allclose(stacked[r], stacked[0]) for r in range(1, N)
        )

    def test_train_step_rejects_unstacked_residual(self):
        """A bare optimizer.init() state (unstacked residual) must fail
        LOUDLY at trace time, naming create_train_state as the fix."""
        from chainermn_tpu.training.train_step import (
            TrainState,
            make_train_step,
        )

        comm = create_communicator("naive")
        params = {"w": jnp.zeros((8,), jnp.float32)}
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8, error_feedback=True,
        )
        bad_state = TrainState(
            params=params, opt_state=opt.init(params),
            step=jnp.zeros((), jnp.int32), model_state=(),
        )
        step = make_train_step(
            lambda p, b: jnp.sum(p["w"] * b[0]), opt, comm, donate=False
        )
        with pytest.raises(ValueError, match="create_train_state"):
            step(bad_state, jnp.ones((N, 8)))
        # Non-divisible / scalar-leaf shapes must hit the SAME message,
        # not a generic shard_map divisibility error.
        params6 = {"w": jnp.zeros((6,), jnp.float32)}
        bad6 = TrainState(
            params=params6, opt_state=opt.init(params6),
            step=jnp.zeros((), jnp.int32), model_state=(),
        )
        with pytest.raises(ValueError, match="create_train_state"):
            step(bad6, jnp.ones((N, 6)))

    def test_composes_with_double_buffering(self):
        """EF + double buffering: staleness-1 semantics intact (step 0
        applies zeros; two steps apply exactly one reduced grad) and
        both state layers are present."""
        comm = create_communicator("naive")
        grads = _per_rank_grads(comm)
        params = jnp.zeros((4,), jnp.float32)
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8,
            double_buffering=True, error_feedback=True,
        )
        state = opt.init(params)
        from chainermn_tpu.optimizers import (
            _DoubleBufferState,
            _ErrorFeedbackState,
        )

        assert isinstance(state, _ErrorFeedbackState)
        assert isinstance(state.inner, _DoubleBufferState)

        p1, _, _ = self._run_ef_update(comm, opt, grads, params,
                                       n_steps=1)
        np.testing.assert_allclose(np.asarray(p1), np.zeros(4), atol=1e-7)
        p2, _, _ = self._run_ef_update(comm, opt, grads, params,
                                       n_steps=2)
        amax = np.abs(grads).max()
        np.testing.assert_allclose(
            np.asarray(p2), -grads.mean(0), atol=2 * amax / 100
        )

    def test_identity_outside_axis_context_keeps_residual(self):
        comm = create_communicator("naive")
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8, error_feedback=True,
        )
        params = jnp.zeros((4,), jnp.float32)
        g = jnp.full((4,), 0.25, jnp.float32)
        state = opt.init(params)
        updates, new_state = jax.jit(opt.update)(g, state, params)
        np.testing.assert_allclose(np.asarray(updates), -0.25 * np.ones(4),
                                   rtol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(new_state.residual)[0]),
            np.zeros(4),
        )

    def test_train_step_ef_on_hierarchical_mesh(self):
        """EF through the trainer on a TWO-axis ('inter','intra') mesh:
        the residual shards over the flattened axes tuple and the
        quantized mean still tracks the exact mean."""
        from chainermn_tpu.training.train_step import (
            create_train_state,
            make_train_step,
        )

        comm = create_communicator(
            "hierarchical", devices=jax.devices("cpu")[:N],
            allreduce_grad_dtype=jnp.int8,
        )
        rng = np.random.RandomState(23)
        grads_np = (rng.randn(N, 4) * 0.01).astype(np.float32)
        grads_np[0, :] = 0.9
        params = {"w": jnp.zeros((4,), jnp.float32)}
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8, error_feedback=True,
        )
        state = create_train_state(params, opt, comm)
        assert jax.tree.leaves(state.opt_state.residual)[0].shape == (N, 4)

        def loss_fn(p, batch):
            return jnp.sum(p["w"] * batch[0])

        step = make_train_step(loss_fn, opt, comm, donate=False)
        batch = jnp.asarray(grads_np)
        steps = 20
        for _ in range(steps):
            state, _ = step(state, batch)
        exact = -steps * grads_np.mean(0)
        err = np.abs(np.asarray(state.params["w"]) - exact).max()
        quantum = np.abs(grads_np).max() / 127.0
        assert err < 4 * quantum, (err, quantum)


class TestInt8TwoLevel:
    """Topology-aware quantized reduction (round-4): exact psum_scatter
    over intra (ICI), int8 two-phase ONLY over inter (DCN), exact
    all_gather back — the quantized rendering of the reference's
    TwoDimensionalCommunicator algorithm."""

    def _mesh_comm(self):
        from jax.sharding import Mesh

        devs = np.array(jax.devices("cpu")[:N]).reshape(2, 4)
        return Mesh(devs, ("inter", "intra"))

    def test_matches_exact_mean_within_single_stage_noise(self):
        from chainermn_tpu.parallel.collectives import (
            int8_two_level_allreduce_mean,
        )

        mesh = self._mesh_comm()
        rng = np.random.RandomState(31)
        x = jnp.asarray(rng.randn(N, 501).astype(np.float32))  # odd size
        spec = P(("inter", "intra"))

        def run(fn):
            def body(xl):
                return fn(xl[0])[None]

            return np.asarray(jax.jit(shard_map(
                body, mesh=mesh, in_specs=spec, out_specs=spec,
                check_vma=False,
            ))(x))

        quant = run(lambda v: int8_two_level_allreduce_mean(
            v, "intra", "inter"))
        exact = run(lambda v: jax.lax.pmean(v, ("inter", "intra")))
        amax = np.abs(np.asarray(x)).max()
        # intra stays exact; only the inter stage quantizes (2 roundings
        # of the int8 scheme over the intra-summed shard)
        np.testing.assert_allclose(quant[0], exact[0],
                                   atol=2 * N * amax / 100)
        for r in range(1, N):
            np.testing.assert_array_equal(quant[r], quant[0])

    def test_topology_structure(self):
        """Structural certificate: exact reduce_scatter + all_gather ride
        INTRA; the int8 all_to_all + payload gather ride INTER only."""
        from chainermn_tpu.parallel.collectives import (
            int8_two_level_allreduce_mean,
        )
        from chainermn_tpu.testing import collect_collectives

        seen = collect_collectives(
            lambda g: int8_two_level_allreduce_mean(g, "intra", "inter"),
            jnp.zeros((1024,), jnp.float32),
            axis_env=[("inter", 2), ("intra", 4)],
        )
        _assert_int8_rides_inter_only(seen)

    def test_gradient_is_straight_through(self):
        """CLAUDE.md values-AND-gradients invariant: jax.grad through
        the topology-aware quantized reduction equals jax.grad through
        the exact two-axis pmean (straight-through custom VJP)."""
        from chainermn_tpu.parallel.collectives import (
            int8_two_level_allreduce_mean,
        )

        mesh = self._mesh_comm()
        rng = np.random.RandomState(32)
        x = jnp.asarray(rng.randn(N, 16).astype(np.float32))
        W = jnp.asarray(rng.randn(N, 16).astype(np.float32))
        spec = P(("inter", "intra"))

        def grad_of(red):
            def body(xl):
                def lf(v):
                    y = red(v[0])
                    ii = jax.lax.axis_index("inter")
                    jj = jax.lax.axis_index("intra")
                    idx = ii * 4 + jj
                    return jnp.sum(y * jax.lax.dynamic_index_in_dim(
                        W, idx, 0, keepdims=False))

                return jax.grad(lf)(xl)

            return np.asarray(jax.jit(shard_map(
                body, mesh=mesh, in_specs=spec, out_specs=spec,
                check_vma=False,
            ))(x))

        g_quant = grad_of(lambda v: int8_two_level_allreduce_mean(
            v, "intra", "inter"))
        g_exact = grad_of(lambda v: jax.lax.pmean(v, ("inter", "intra")))
        np.testing.assert_allclose(g_quant, g_exact, rtol=1e-6)


class TestShardLevelEF:
    """Round-5 shard-level error feedback for the TOPOLOGY-AWARE wire
    (``int8_two_level_allreduce_mean_with_feedback``): the intra stage
    is exact, so the residual lives at the int8 inter stage's shard
    shape. Same invariants as the flat-wire ``TestErrorFeedback``,
    applied at the stage where the error actually arises."""

    def _mesh_comm(self, shape=(2, 4)):
        from jax.sharding import Mesh

        from chainermn_tpu.communicators.xla_communicator import (
            TwoDimensionalCommunicator,
        )

        devs = np.array(jax.devices("cpu")[:N]).reshape(shape)
        return TwoDimensionalCommunicator(
            mesh=Mesh(devs, ("inter", "intra"))
        )

    def test_zero_residual_matches_bare_two_level(self):
        """With a zero residual the feedback form must equal the bare
        topology-aware wire EXACTLY (same frame, same rounding), and
        return a shard-shaped residual."""
        from chainermn_tpu.parallel.collectives import (
            int8_two_level_allreduce_mean,
            int8_two_level_allreduce_mean_with_feedback,
            two_level_shard_len,
        )

        comm = self._mesh_comm()
        L = 33  # deliberately not divisible by intra=4
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(N, L).astype(np.float32))
        shard_len = two_level_shard_len(L, 4)
        spec = P(("inter", "intra"))

        def body(xl):
            v = xl[0]
            bare = int8_two_level_allreduce_mean(v, "intra", "inter")
            mean, res = int8_two_level_allreduce_mean_with_feedback(
                v, jnp.zeros((shard_len,), jnp.float32),
                "intra", "inter",
            )
            return bare[None], mean[None], res[None]

        bare, mean, res = jax.jit(shard_map(
            body, mesh=comm.mesh, in_specs=spec,
            out_specs=(spec, spec, spec), check_vma=False,
        ))(x)
        np.testing.assert_array_equal(np.asarray(bare), np.asarray(mean))
        assert res.shape == (N, shard_len)

    def _grads(self):
        """Per-member grads whose INTER-stage message is
        quantization-hostile: coordinate 0 carries an adversarial
        component (sign flipping between the two inter groups, exactly
        cancelling in the mean) that pins the j=0 shard message's amax;
        coordinate 1 (same shard slice) carries a persistent
        sub-half-quantum signal that plain deterministic rounding kills
        every step."""
        g = np.zeros((N, 6), np.float32)
        g[:4, 0], g[4:, 0] = 0.225, -0.225  # intra sums +-0.9, mean 0
        g[:, 1] = 0.003 / 4                 # inter msg 0.003 < q/2
        g[:, 2:] = 0.05                     # healthy super-quantum coords
        return g

    def _trainer(self, **opt_kwargs):
        """Shared trainer setup over the (2,4) mesh with the
        quantization-hostile grads: returns (state, step, batch,
        grads_np, opt)."""
        from chainermn_tpu.training.train_step import (
            create_train_state,
            make_train_step,
        )

        comm = self._mesh_comm()
        grads_np = self._grads()
        params = {"w": jnp.zeros((6,), jnp.float32)}
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8, **opt_kwargs,
        )

        def loss_fn(p, batch):
            return jnp.sum(p["w"] * batch[0])

        state = create_train_state(params, opt, comm)
        step = make_train_step(loss_fn, opt, comm, donate=False)
        return state, step, jnp.asarray(grads_np), grads_np, opt

    def _cumulative(self, error_feedback, steps=30):
        state, step, batch, grads_np, _ = self._trainer(
            error_feedback=error_feedback)
        for _ in range(steps):
            state, _ = step(state, batch)
        exact = -steps * grads_np.mean(0)
        return (np.abs(np.asarray(state.params["w"]) - exact).max(),
                state, grads_np)

    def test_cumulative_bias_removed_at_the_inter_stage(self):
        err_plain, _, grads_np = self._cumulative(False)
        err_ef, state, _ = self._cumulative(True)
        # message-level quantum at the pinned shard: intra-sum amax 0.9
        msg_quantum = 0.9 / 127.0
        # output-level: /(n_inter * n_intra)... but the telescoping
        # bound is at message level divided by the inter mean only.
        assert err_ef < 4 * msg_quantum, (err_ef, msg_quantum)
        assert err_ef < err_plain / 3, (err_ef, err_plain)
        # the per-member shard residuals are genuinely distinct state
        stacked = np.asarray(
            jax.tree.leaves(state.opt_state.residual)[0]
        )
        assert stacked.shape[0] == N
        assert not all(
            np.allclose(stacked[r], stacked[0]) for r in range(1, N)
        )

    def test_plain_two_level_kills_the_subquantum_coordinate(self):
        """The mechanism the EF exists for, asserted directly: without
        feedback the persistent sub-half-quantum coordinate never
        trains."""
        err_plain, state, grads_np = self._cumulative(False)
        w = np.asarray(state.params["w"])
        # coordinate 1's exact target moved; plain int8 left it at ~0
        assert abs(w[1]) < 1e-6, w[1]
        assert abs(30 * grads_np[:, 1].mean()) > 0.02

    def test_topology_structure_with_feedback(self):
        """Structural certificate for the EF form (CLAUDE.md: measured,
        not asserted in prose): adding the residual must not move any
        collective — the exact reduce_scatter and the f32 payload
        all_gather ride INTRA; every int8 collective (all_to_all +
        payload gathers) rides INTER only. A refactor routing f32
        across inter (or int8 across intra) fails here even if every
        numeric test still passes."""
        from chainermn_tpu.parallel.collectives import (
            int8_two_level_allreduce_mean_with_feedback,
            two_level_shard_len,
        )
        from chainermn_tpu.testing import collect_collectives

        L = 1024
        seen = collect_collectives(
            lambda g, e: int8_two_level_allreduce_mean_with_feedback(
                g, e, "intra", "inter"),
            jnp.zeros((L,), jnp.float32),
            jnp.zeros((two_level_shard_len(L, 4),), jnp.float32),
            axis_env=[("inter", 2), ("intra", 4)],
        )
        _assert_int8_rides_inter_only(seen)
        # the residual path adds NO intra-axis traffic beyond the f32
        # scatter/gather pair of the exact frame
        intra_ops = [e for e in seen if "intra" in e[1]]
        assert all(e[2] == "float32" for e in intra_ops), seen

    def test_composes_with_double_buffering_on_two_level_mesh(self):
        """Shard-level EF + double buffering on the (2,4) mesh through
        the standard trainer: staleness-1 intact (step 0 applies
        zeros; two steps apply one reduced grad) with the shard-shaped
        residual carried alongside the banked grads."""
        from chainermn_tpu.optimizers import (
            _DoubleBufferState,
            _ErrorFeedbackState,
        )

        state, step, batch, grads_np, opt = self._trainer(
            double_buffering=True, error_feedback=True)
        assert isinstance(state.opt_state, _ErrorFeedbackState)
        assert isinstance(state.opt_state.inner, _DoubleBufferState)
        state, _ = step(state, batch)
        np.testing.assert_allclose(
            np.asarray(state.params["w"]), np.zeros(6), atol=1e-7)
        state, _ = step(state, batch)
        # exactly one (quantized) mean applied; the healthy coords are
        # super-quantum so they land within one message quantum
        msg_quantum = 0.9 / 127.0
        np.testing.assert_allclose(
            np.asarray(state.params["w"])[2:], -grads_np.mean(0)[2:],
            atol=msg_quantum,
        )

    def test_multi_bucket_layout_and_invariant(self, monkeypatch):
        """The >64 MB path, exercised at test scale by shrinking the
        bucket budget: several float leaves split across MULTIPLE
        buckets, each with its own shard residual. init's layout must
        match the reduction's (the shared _float_bucket_partition), and
        the cumulative-bias invariant must hold across every bucket."""
        import chainermn_tpu.optimizers as opt_mod
        from chainermn_tpu.training.train_step import (
            create_train_state,
            make_train_step,
        )

        monkeypatch.setattr(opt_mod, "_EF_BUCKET_BYTES", 64)  # ~16 floats
        comm = self._mesh_comm()
        rng = np.random.RandomState(9)
        # three leaves of 12/8/6 floats -> 64-byte buckets: [12], [8, 6]
        params = {"a": jnp.zeros((12,), jnp.float32),
                  "b": jnp.zeros((8,), jnp.float32),
                  "c": jnp.zeros((6,), jnp.float32)}
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8, error_feedback=True,
        )
        st = opt.init(params)
        from chainermn_tpu.parallel.collectives import two_level_shard_len
        assert [r.shape for r in st.residual] == [
            (two_level_shard_len(12, 4),),
            (two_level_shard_len(14, 4),),
        ]

        grads_np = rng.randn(N, 26).astype(np.float32) * 0.01
        grads_np[0, :] = 0.9  # amax rows: sub-quantum spread elsewhere

        def loss_fn(p, batch):
            flat = jnp.concatenate([p["a"], p["b"], p["c"]])
            return jnp.sum(flat * batch[0])

        state = create_train_state(params, opt, comm)
        step = make_train_step(loss_fn, opt, comm, donate=False)
        batch = jnp.asarray(grads_np)
        steps = 30
        for _ in range(steps):
            state, _ = step(state, batch)
        got = np.concatenate([
            np.asarray(state.params[k]) for k in ("a", "b", "c")
        ])
        exact = -steps * grads_np.mean(0)
        # intra sums can reach 4 * 0.9; EF keeps the cumulative error
        # bounded by a few message-level quanta in EVERY bucket
        msg_quantum = 4 * 0.9 / 127.0
        assert np.abs(got - exact).max() < 4 * msg_quantum

    @pytest.mark.parametrize("shape", [(1, 8), (8, 1), (4, 2)])
    def test_degenerate_and_alternate_factorisations(self, shape):
        """Shard-EF across mesh factorisations: (1,8) has a degenerate
        inter axis — the wire quantizes NOTHING, the mean is exact and
        the residual stays zero; (8,1) has a degenerate intra axis —
        the full buffer is the 'shard' and everything is quantized
        (flat-wire-equivalent); (4,2) is the transposed split. One
        trainer step each, mean within one message quantum, residual
        shaped by two_level_shard_len."""
        from chainermn_tpu.parallel.collectives import two_level_shard_len
        from chainermn_tpu.training.train_step import (
            create_train_state,
            make_train_step,
        )

        comm = self._mesh_comm(shape)
        params = {"w": jnp.zeros((10,), jnp.float32)}
        opt = create_multi_node_optimizer(
            optax.sgd(1.0), comm,
            allreduce_grad_dtype=jnp.int8, error_feedback=True,
        )
        state = create_train_state(params, opt, comm, model_state={})
        g = np.random.RandomState(1).randn(N, 10).astype(np.float32)

        def loss_fn(p, b, ms):
            return jnp.sum(p["w"] * b[0]), ({}, ms)

        step = make_train_step(loss_fn, opt, comm, donate=False)
        state, _ = step(state, (jnp.asarray(g), jnp.zeros(N)))
        w = np.asarray(state.params["w"])
        res = np.asarray(jax.tree.leaves(state.opt_state.residual)[0])
        n_intra = shape[1]
        assert res.shape == (N, two_level_shard_len(10, n_intra))
        err = np.abs(w + g.mean(0)).max()
        if shape[0] == 1:
            # degenerate inter: nothing was quantized
            assert err == 0.0 and np.abs(res).max() == 0.0
        else:
            # quantized inter leg: within ~one message quantum, and the
            # dropped error was captured in the residual
            intra_amax = np.abs(
                g.reshape(shape[0], shape[1], 10).sum(1)).max()
            assert err < 2 * intra_amax / 127.0, (err, intra_amax)
            assert np.abs(res).max() > 0.0


def _assert_int8_rides_inter_only(seen):
    """Shared assertions of the topology-aware wire's structural
    certificates (bare and EF forms): int8 all_to_all + int8 payload
    gathers on INTER only; the exact f32 reduce_scatter on INTRA only.
    ``seen`` is ``chainermn_tpu.testing.collect_collectives`` output."""
    a2a = [e for e in seen if e[0] == "all_to_all"]
    assert a2a and all(e[1] == ("inter",) and e[2] == "int8"
                       for e in a2a), seen
    rs = [e for e in seen if e[0] == "reduce_scatter"]
    assert rs and all(e[1] == ("intra",) and e[2] == "float32"
                      for e in rs), seen
    int8_gathers = [e for e in seen
                    if e[0] == "all_gather" and e[2] == "int8"]
    assert int8_gathers and all(e[1] == ("inter",)
                                for e in int8_gathers), seen


def test_nonfinite_skip_via_optax_composition(comm):
    """``optax.apply_if_finite`` composes with the multi-node wrapper out
    of the box: the finiteness check runs on the REDUCED gradients, so
    every rank sees the same verdict and skips in lockstep (no parameter
    divergence across the mesh). One poisoned rank therefore poisons —
    and skips — the whole step, and the next clean step applies
    normally. Documented in docs/fault_tolerance.md."""
    inner = optax.apply_if_finite(optax.sgd(1.0), max_consecutive_errors=3)
    opt = create_multi_node_optimizer(inner, comm)
    params = jnp.zeros((4,), jnp.float32)

    grads = _per_rank_grads(comm).copy()
    grads[3, 2] = np.nan  # ONE rank contributes a NaN
    poisoned, state = _run_sharded_update(comm, opt, grads, params)
    # allreduce-mean spreads the NaN to every rank; apply_if_finite skips
    # the whole update — params unchanged everywhere.
    np.testing.assert_array_equal(np.asarray(poisoned), np.asarray(params))

    # Recovery is tested THROUGH the post-skip state (a fresh init would
    # only re-test the clean path): notfinite bookkeeping must reset and
    # the inner state must still be valid.
    clean = _per_rank_grads(comm)
    recovered, _ = _run_sharded_update(
        comm, opt, clean, params, state=state
    )
    np.testing.assert_allclose(
        np.asarray(recovered), -clean.mean(0), rtol=1e-5, atol=1e-6
    )


# ---------------------------------------------------------------------------
# Local SGD / DiLoCo periodic averaging (beyond the reference)
# ---------------------------------------------------------------------------


def test_local_sgd_sync_every_1_equals_per_step_dp(comm):
    """With sync_every=1 and a LINEAR inner (sgd), averaging the locally
    updated candidates equals averaging the gradients: local SGD must
    reproduce the per-step data-parallel wrapper exactly."""
    from chainermn_tpu import create_local_sgd

    grads = _per_rank_grads(comm)
    params = jnp.ones((4,), jnp.float32)
    local = create_local_sgd(optax.sgd(0.5), comm, sync_every=1)
    dp = create_multi_node_optimizer(optax.sgd(0.5), comm)
    p_local, _ = _run_sharded_update(comm, local, grads, params, n_steps=3)
    p_dp, _ = _run_sharded_update(comm, dp, grads, params, n_steps=3)
    np.testing.assert_allclose(
        np.asarray(p_local), np.asarray(p_dp), rtol=1e-5, atol=1e-6
    )


def test_local_sgd_matches_per_worker_simulation(comm):
    """sync_every=3 with a NONLINEAR inner (adam): each member must
    evolve on its own gradients for 3 steps and only then average — the
    oracle is a literal per-worker optax simulation. A linear inner
    cannot distinguish local from per-step averaging; adam's
    second-moment normalisation can, so this pins the actual local-SGD
    semantics (and that NO averaging happened in between)."""
    from chainermn_tpu import create_local_sgd

    grads = _per_rank_grads(comm)
    params = jnp.full((4,), 0.25, jnp.float32)
    local = create_local_sgd(optax.adam(0.1), comm, sync_every=3)
    p_got, state = _run_sharded_update(
        comm, local, grads, params, n_steps=3
    )

    # Oracle: run adam per worker, then average the candidates.
    finals = []
    for r in range(N):
        p = params
        inner = optax.adam(0.1)
        s = inner.init(p)
        for _ in range(3):
            u, s = inner.update(jnp.asarray(grads[r]), s, p)
            p = optax.apply_updates(p, u)
        finals.append(np.asarray(p))
    expect = np.stack(finals).mean(0)
    np.testing.assert_allclose(np.asarray(p_got), expect, rtol=1e-5,
                               atol=1e-6)
    # mid-window steps must NOT have synced: step 2's params diverge per
    # worker, which the oracle equality above only certifies indirectly —
    # the anchor must equal the step-3 target, proving exactly one sync.
    np.testing.assert_allclose(
        np.asarray(state.anchor), expect, rtol=1e-5, atol=1e-6
    )


def test_local_sgd_outer_momentum_closed_form(comm):
    """DiLoCo outer momentum at sync_every=1 with sgd inner: the outer
    recursion is heavy ball on the mean gradient scaled by the inner lr:
    v_t = m v_{t-1} + lr*mean(g); p_t = p_{t-1} - outer_lr * v_t."""
    from chainermn_tpu import create_local_sgd

    lr, m, olr = 0.5, 0.9, 0.7
    grads = _per_rank_grads(comm)
    gbar = grads.mean(0)
    params = jnp.zeros((4,), jnp.float32)
    opt = create_local_sgd(
        optax.sgd(lr), comm, sync_every=1, outer_lr=olr, outer_momentum=m
    )
    p_got, _ = _run_sharded_update(comm, opt, grads, params, n_steps=3)

    p = np.zeros(4, np.float32)
    v = np.zeros(4, np.float32)
    for _ in range(3):
        v = m * v + lr * gbar
        p = p - olr * v
    np.testing.assert_allclose(np.asarray(p_got), p, rtol=1e-5, atol=1e-6)


def test_local_sgd_single_device_degrades_to_inner():
    """Outside any named-axis context the mean is the identity: local
    SGD is exactly the inner chain (dist==single invariant)."""
    from chainermn_tpu import create_communicator, create_local_sgd

    comm = create_communicator("single_node")
    params = jnp.ones((3,), jnp.float32)
    g = jnp.asarray([0.1, -0.2, 0.3], jnp.float32)

    opt = create_local_sgd(optax.adam(0.05), comm, sync_every=4)
    inner = optax.adam(0.05)
    s_l, s_i = opt.init(params), inner.init(params)
    p_l = p_i = params
    for _ in range(5):
        u_l, s_l = jax.jit(opt.update)(g, s_l, p_l)
        p_l = optax.apply_updates(p_l, u_l)
        u_i, s_i = jax.jit(inner.update)(g, s_i, p_i)
        p_i = optax.apply_updates(p_i, u_i)
    np.testing.assert_allclose(np.asarray(p_l), np.asarray(p_i),
                               rtol=1e-6, atol=1e-7)


def test_local_sgd_rejects_bad_cadence(comm):
    from chainermn_tpu import create_local_sgd

    with pytest.raises(ValueError, match="sync_every"):
        create_local_sgd(optax.sgd(0.1), comm, sync_every=0)


# ----------------------------------------------------------------------
# The default reduction as two all_to_alls a matrix (ISSUE 39), on the
# traced program: what each leaf's reduction is made of and what it
# waits for.
# ----------------------------------------------------------------------


def _flat_eqns(jaxpr):
    """Equations of a jaxpr with the calls that only wrap one (pjit,
    custom-AD) opened up, their variables renamed to the caller's."""
    from jax.extend import core as jex_core

    from chainermn_tpu.testing import _subjaxprs

    out = []

    def walk(j, rename):
        def name(v):
            return v if isinstance(v, jex_core.Literal) \
                else rename.get(v, v)

        for eqn in j.eqns:
            subs = _subjaxprs(eqn.params)
            if len(subs) == 1 and \
                    len(subs[0][1].invars) == len(eqn.invars) and \
                    len(subs[0][1].outvars) == len(eqn.outvars):
                sub = subs[0][1]
                inner = dict(zip(sub.invars, map(name, eqn.invars)))
                walk(sub, inner)
                for o, so in zip(eqn.outvars, sub.outvars):
                    rename[o] = so if isinstance(so, jex_core.Literal) \
                        else inner.get(so, so)
            else:
                out.append((eqn.primitive.name,
                            [name(v) for v in eqn.invars
                             if not isinstance(v, jex_core.Literal)],
                            list(eqn.outvars)))
        return rename

    rename = walk(jaxpr, {})
    return out, [rename.get(v, v) for v in jaxpr.outvars]


def _slice_of(eqns, var):
    """The equations ``var`` depends on, as indices into ``eqns``."""
    made_by = {o: i for i, (_, _, outs) in enumerate(eqns) for o in outs}
    seen, todo = set(), [var]
    while todo:
        i = made_by.get(todo.pop())
        if i is not None and i not in seen:
            seen.add(i)
            todo.extend(eqns[i][1])
    return seen


def test_each_matrix_is_two_all_to_alls_that_wait_for_its_gradient_alone(
        monkeypatch):
    """Three dense blocks of distinct shapes on four devices. Each
    matrix crosses as two all_to_alls and each vector as a psum, at the
    wire dtype. The last block's reduced gradient, the first the
    backward makes, has no data path from any other leaf's collectives
    nor from the matmul that makes the first block's gradient, the last
    the backward makes: nothing in the program makes it wait for the end
    of the backward, so a scheduler is free to fly it under the rest."""
    from chainermn_tpu.parallel import collectives

    monkeypatch.setattr(collectives, "ALL_TO_ALL_MIN_BYTES", 0)
    comm = create_communicator("xla", devices=jax.devices("cpu")[:4],
                               allreduce_grad_dtype=jnp.bfloat16)
    widths = [8, 16, 24, 12]
    rs = np.random.RandomState(11)
    params = {f"block_{i}": {
        "kernel": jnp.asarray(rs.randn(a, b), jnp.float32),
        "bias": jnp.zeros((b,), jnp.float32)}
        for i, (a, b) in enumerate(zip(widths, widths[1:]))}
    x = jnp.asarray(rs.randn(4, widths[0]), jnp.float32)

    def loss(p, x):
        for i in range(3):
            x = jnp.tanh(x @ p[f"block_{i}"]["kernel"]
                         + p[f"block_{i}"]["bias"])
        return jnp.mean(x ** 2)

    closed = jax.make_jaxpr(
        lambda p, x: allreduce_gradients(jax.grad(loss)(p, x), comm),
        axis_env=[("data", 4)])(params, x)
    eqns, outs = _flat_eqns(closed.jaxpr)
    grads = jax.tree.unflatten(jax.tree.structure(params), outs)

    def collectives_of(indices):
        return sorted(eqns[i][0] for i in indices
                      if eqns[i][0] in ("all_to_all", "psum"))

    everything = collectives_of(range(len(eqns)))
    assert everything == ["all_to_all"] * 6 + ["psum"] * 3
    for i in range(3):
        block = grads[f"block_{i}"]
        assert collectives_of(_slice_of(eqns, block["kernel"])) == \
            ["all_to_all"] * 2
        assert collectives_of(_slice_of(eqns, block["bias"])) == ["psum"]

    # the matmul that makes block 0's kernel gradient: the only
    # dot_general whose result has that kernel's shape (or its transpose)
    first_block = [i for i, (name, _, o) in enumerate(eqns)
                   if name == "dot_general"
                   and sorted(o[0].aval.shape) == sorted(widths[:2])]
    assert len(first_block) == 1
    last = _slice_of(eqns, grads["block_2"]["kernel"]) \
        | _slice_of(eqns, grads["block_2"]["bias"])
    assert first_block[0] in _slice_of(eqns, grads["block_0"]["kernel"])
    assert first_block[0] not in last
    # and the wire carries bf16
    wire = {str(ins[0].aval.dtype) for name, ins, _ in eqns
            if name in ("all_to_all", "psum")}
    assert wire == {"bfloat16"}


@pytest.mark.parametrize("shape,dtype,wire,moved", [
    ((1024, 512), jnp.float32, jnp.bfloat16, True),    # 1 MiB on the wire
    ((1024, 511), jnp.float32, jnp.bfloat16, False),   # just under it
    ((1023, 513), jnp.float32, None, True),   # no dimension 4 divides
    ((1 << 20,), jnp.float32, None, False),   # a vector, however long
    ((1024, 512), jnp.int32, None, False),    # not a floating leaf
])
def test_which_leaves_the_default_reduction_sends_as_all_to_alls(
        shape, dtype, wire, moved):
    """By bytes on the wire, not by what the device count divides: a
    floating leaf of two or more dimensions and 1 MiB or more."""
    comm = create_communicator("xla", devices=jax.devices("cpu")[:4],
                               allreduce_grad_dtype=wire)
    text = str(jax.make_jaxpr(
        lambda g: allreduce_gradients(g, comm),
        axis_env=[("data", 4)])(jnp.zeros(shape, dtype)))
    assert ("all_to_all" in text) == moved
    assert ("psum" in text) != moved


#: leaf kind -> (shape, dtype, whether allreduce_gradients moves it by
#: all_to_alls); the row and the vector and the integers take ``pmean``
_DEFAULT_LEAVES = {
    "matrix_n_divides": ((1024, 512), np.float32, True),     # 2 MiB
    "matrix_padded": ((1023, 513), np.float32, True),  # no dim n divides
    "row_under_1mib": ((4, 300), np.float32, False),
    "vector_2mib": ((1 << 19,), np.float32, False),
    "integer_matrix": ((1024, 512), np.int32, False),
}


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("leaf,wire", [
    *((k, None) for k in _DEFAULT_LEAVES),
    ("matrix_n_divides", jnp.bfloat16), ("matrix_padded", jnp.bfloat16),
])
def test_the_default_reduction_is_the_mean_with_the_same_bits_on_every_member(
        leaf, wire, n):
    """``allreduce_gradients`` as the four-chip cell runs it, executed:
    every member ends with the same bits (what the benchmark's check (e)
    stands on), and they are the float32 mean rounded once to the wire
    dtype. A leaf that goes by ``all_to_all`` is summed in member order,
    so random values are held to the bit; a ``pmean`` may sum in any
    order, so those leaves are eighths, whose sums are exact."""
    import ml_dtypes

    shape, dtype, moved = _DEFAULT_LEAVES[leaf]
    comm = create_communicator("xla", devices=jax.devices("cpu")[:n],
                               allreduce_grad_dtype=wire)
    axes = comm.grad_axes
    rs = np.random.RandomState(n)
    if dtype == np.int32:
        x = (rs.randint(-8, 9, (n,) + shape) * 8).astype(np.int32)
    elif moved:
        x = rs.randn(n, *shape).astype(np.float32)
    else:
        x = (rs.randint(-8, 9, (n,) + shape) / 8.0).astype(np.float32)
    grads = {"leaf": jnp.asarray(x),
             "bias": jnp.asarray(rs.randint(-8, 9, (n, 5)) / 8.0,
                                 jnp.float32)}
    text = str(jax.make_jaxpr(
        lambda g: allreduce_gradients(g, comm),
        axis_env=[(axes[0], n)])(jax.tree.map(lambda l: l[0], grads)))
    assert ("all_to_all" in text) == moved

    def local(g):
        out = allreduce_gradients(jax.tree.map(lambda l: l[0], g), comm)
        return jax.tree.map(lambda l: l[None], out)

    spec = jax.tree.map(lambda l: P(axes, *([None] * (l.ndim - 1))), grads)
    out = jax.device_get(jax.jit(shard_map(
        local, mesh=comm.mesh, in_specs=(spec,), out_specs=spec,
        check_vma=False))(grads))

    for k, got in out.items():
        assert got.dtype == np.asarray(grads[k]).dtype
        for i in range(1, n):
            np.testing.assert_array_equal(got[i], got[0])
    np.testing.assert_array_equal(
        out["bias"][0], np.asarray(grads["bias"]).mean(0))
    on_wire = x.astype(ml_dtypes.bfloat16) if wire is not None else x
    total = on_wire[0].astype(np.float32)
    for j in range(1, n):
        total = total + on_wire[j].astype(np.float32)
    mean = (total / np.float32(n)).astype(on_wire.dtype).astype(dtype)
    np.testing.assert_array_equal(out["leaf"][0], mean)


@pytest.mark.parametrize("value", [
    "auto", "rs(data)>ag(data)", "ar(data)[s0..3]", "ring", object(),
], ids=["auto", "signature", "sliced_signature", "ring", "object"])
def test_reduction_schedule_takes_the_four_names(comm, value):
    """``None``, ``'flat'``, ``'two_level'``, ``'zero'`` and nothing
    else: no resolution by table, no pipeline spelled as a string or an
    object; the error names the four."""
    with pytest.raises(ValueError,
                       match="None, 'flat', 'two_level' or 'zero'"):
        create_multi_node_optimizer(optax.sgd(0.1), comm,
                                    reduction_schedule=value)
    for name in (None, "flat", "two_level", "zero"):
        opt = create_multi_node_optimizer(optax.sgd(0.1), comm,
                                          reduction_schedule=name)
        assert opt.reduction_schedule == name


def test_the_default_reduction_outside_any_axis_is_the_identity():
    """Outside shard_map there is nothing to reduce over, whatever the
    leaf's size: the gradient passes (through the wire dtype's rounding)
    unchanged, and no collective is traced."""
    comm = create_communicator("xla", devices=jax.devices("cpu")[:4],
                               allreduce_grad_dtype=jnp.bfloat16)
    grads = {"w": jnp.full((1024, 512), 2.0), "b": jnp.ones((3,))}
    text = str(jax.make_jaxpr(lambda g: allreduce_gradients(g, comm))(grads))
    assert "all_to_all" not in text and "psum" not in text
    out = allreduce_gradients(grads, comm)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.full((1024, 512), 2.0))
    np.testing.assert_array_equal(np.asarray(out["b"]), np.ones((3,)))
