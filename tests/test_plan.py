"""ParallelPlan (ISSUE 10): one global-view mesh program for
DP x TP x ZeRO x pipeline.

Acceptance is structural, per the repo convention:

- dist == single VALUES AND GRADIENTS for every composed plan (gradients
  certified through the first sgd step's delta, values through multi-step
  adam trajectories);
- the compiled plan step carries exactly the hand-wired paths' HLO
  collective counts (the ppermute-count convention);
- buffer donation pinned in XLA's own input_output_alias table — a
  second step re-uploads nothing;
- the jit cache stays pinned at 1 across steps.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from chainermn_tpu.parallel.mesh import best_mesh_shape
from chainermn_tpu.parallel.plan import ParallelPlan, PipelinePlanSpec
from chainermn_tpu.parallel.tensor import stack_tp_params, tp_mlp


def _devices():
    return jax.devices("cpu")[:8]


# ---------------------------------------------------------------------------
# Satellite: best_mesh_shape past the 2-dim wall
# ---------------------------------------------------------------------------


class TestBestMeshShape:
    def test_two_dim_unchanged(self):
        assert best_mesh_shape(8, 2) == (4, 2)
        assert best_mesh_shape(16, 2) == (4, 4)
        assert best_mesh_shape(6, 2) == (3, 2)
        assert best_mesh_shape(7, 2) == (7, 1)
        assert best_mesh_shape(12, 2) == (4, 3)

    def test_n_dim_balanced_larger_first(self):
        assert best_mesh_shape(8, 3) == (2, 2, 2)
        assert best_mesh_shape(16, 3) == (4, 2, 2)
        assert best_mesh_shape(12, 3) == (3, 2, 2)
        assert best_mesh_shape(24, 4) == (3, 2, 2, 2)
        assert best_mesh_shape(64, 3) == (4, 4, 4)
        assert best_mesh_shape(7, 3) == (7, 1, 1)
        assert best_mesh_shape(1, 3) == (1, 1, 1)

    def test_one_dim_and_errors(self):
        assert best_mesh_shape(5, 1) == (5,)
        with pytest.raises(ValueError):
            best_mesh_shape(8, 0)
        with pytest.raises(ValueError):
            best_mesh_shape(0, 2)

    def test_covers_device_count(self):
        import math

        for n in (4, 8, 12, 30, 36):
            for k in (2, 3, 4):
                assert math.prod(best_mesh_shape(n, k)) == n


# ---------------------------------------------------------------------------
# Spec providers
# ---------------------------------------------------------------------------


class TestSpecProviders:
    def test_modules_publish_their_axis(self):
        from chainermn_tpu.parallel.pipeline import pipe_plan_axis
        from chainermn_tpu.parallel.tensor import tp_plan_axis
        from chainermn_tpu.parallel.zero import zero_plan_axis

        assert tp_plan_axis()["collectives"] == ("all-reduce",)
        assert tp_plan_axis()["stacked"] is True
        assert zero_plan_axis()["collectives"] == (
            "reduce-scatter", "all-gather",
        )
        assert zero_plan_axis()["state_stacked"] is True
        assert pipe_plan_axis()["collectives"] == ("collective-permute",)

    def test_describe_aggregates_owed_collectives(self):
        plan = ParallelPlan(("data", "model", "zero"), devices=_devices())
        desc = plan.describe()
        assert desc["mesh"] == {"data": 2, "zero": 2, "model": 2}
        assert desc["collectives"]["zero"] == (
            "reduce-scatter", "all-gather",
        )
        assert desc["collectives"]["model"] == ("all-reduce",)

    def test_auto_factorisation_uses_canonical_order(self):
        # larger factor lands on the first canonical (DCN-most) axis,
        # regardless of the order the names were spelled in
        plan = ParallelPlan(("model", "data"), devices=_devices())
        assert plan.axis_size("data") == 4
        assert plan.axis_size("model") == 2
        assert tuple(plan.mesh.axis_names) == ("data", "model")

    def test_explicit_sizes_and_inference(self):
        plan = ParallelPlan({"data": 2, "zero": -1}, devices=_devices())
        assert plan.axis_size("zero") == 4
        with pytest.raises(ValueError, match="cover"):
            ParallelPlan({"data": 3}, devices=_devices())
        with pytest.raises(ValueError, match="data"):
            ParallelPlan(("data", "data"), devices=_devices())
        with pytest.raises(ValueError, match="subset"):
            ParallelPlan({"tower": 8}, devices=_devices())
        # 'expert' became a first-class axis in ISSUE 20
        assert ParallelPlan(
            {"expert": 8}, devices=_devices()
        ).axis_size("expert") == 8

    def test_param_spec_validation(self):
        plan = ParallelPlan({"data": 4, "model": 2}, devices=_devices())
        params = {"w": jnp.zeros((2, 4, 4)), "b": jnp.zeros((4,))}
        full = plan.param_specs(params, {"w": P("model"), "b": P()})
        assert full["w"] == P("model") and full["b"] == P()
        with pytest.raises(ValueError, match="stacked axes"):
            plan.param_specs(params, {"w": P("data"), "b": P()})
        with pytest.raises(ValueError, match="leading dim"):
            plan.param_specs({"w": jnp.zeros((3, 4)), "b": params["b"]},
                             {"w": P("model"), "b": P()})
        with pytest.raises(ValueError, match="leading-stack"):
            plan.param_specs(params, {"w": P(None, "model"), "b": P()})


# ---------------------------------------------------------------------------
# dist == single, values AND gradients, for every composed plan
# ---------------------------------------------------------------------------


def _mlp_params(key, d=8, d_ff=8):
    ks = jax.random.split(key, 3)
    return (
        jax.random.normal(ks[0], (d, d_ff)) * 0.3,
        jax.random.normal(ks[1], (d_ff, d)) * 0.3,
        jnp.zeros((d,)),
    )


def _ref_loss(w1, w2, b2, x, y):
    return jnp.mean((jax.nn.gelu(x @ w1) @ w2 + b2 - y) ** 2)


def _run_ref(inner, w1, w2, b2, x, y, steps):
    p = {"w1": w1, "w2": w2, "b2": b2}
    st = inner.init(p)
    losses, grads0 = [], None
    for i in range(steps):
        l, g = jax.value_and_grad(
            lambda p: _ref_loss(p["w1"], p["w2"], p["b2"], x, y)
        )(p)
        if i == 0:
            grads0 = g
        u, st = inner.update(g, st, p)
        p = optax.apply_updates(p, u)
        losses.append(float(l))
    return p, losses, grads0


class TestPlanEquivalence:
    def _drive(self, plan, inner, params, specs, loss_fn, x, y, steps):
        state = plan.create_train_state(params, inner, param_specs=specs)
        step = plan.compile_train_step(loss_fn, inner, params,
                                       param_specs=specs)
        losses = []
        for _ in range(steps):
            state, m = step(state, (x, y))
            losses.append(float(m["loss"]))
        return state, losses, step

    def test_dp_zero_values_and_grads(self):
        w1, w2, b2 = _mlp_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        y = jax.random.normal(jax.random.PRNGKey(2), (16, 8))
        params = {"w1": w1, "w2": w2, "b2": b2}

        def loss_fn(p, batch):
            xb, yb = batch
            return _ref_loss(p["w1"], p["w2"], p["b2"], xb, yb)

        plan = ParallelPlan({"data": 2, "zero": 4}, devices=_devices())

        # values: 3 adam steps
        inner = optax.adamw(1e-2)
        state, losses, _ = self._drive(
            plan, inner, params, None, loss_fn, x, y, 3
        )
        _, ref_losses, _ = _run_ref(inner, w1, w2, b2, x, y, 3)
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)

        # gradients: one sgd step, delta / lr == grad
        lr = 0.1
        state, _, _ = self._drive(
            plan, optax.sgd(lr), params, None, loss_fn, x, y, 1
        )
        _, _, g0 = _run_ref(optax.sgd(lr), w1, w2, b2, x, y, 1)
        for k in ("w1", "w2", "b2"):
            got = (np.asarray(params[k])
                   - np.asarray(jax.device_get(state.params[k]))) / lr
            np.testing.assert_allclose(got, np.asarray(g0[k]),
                                       rtol=1e-4, atol=1e-6)

    def test_dp_tp_zero_values_and_grads(self):
        w1, w2, b2 = _mlp_params(jax.random.PRNGKey(3))
        x = jax.random.normal(jax.random.PRNGKey(4), (16, 8))
        y = jax.random.normal(jax.random.PRNGKey(5), (16, 8))
        plan = ParallelPlan(("data", "model", "zero"), devices=_devices())
        m = plan.axis_size("model")
        params = {
            "w1": stack_tp_params(w1, m, 1),
            "w2": stack_tp_params(w2, m, 0),
            "b2": b2,
        }
        specs = {"w1": P("model"), "w2": P("model"), "b2": P()}

        def loss_fn(p, batch):
            xb, yb = batch
            out = tp_mlp(xb, p["w1"], None, p["w2"], p["b2"],
                         axis_name="model")
            return jnp.mean((out - yb) ** 2)

        inner = optax.adamw(1e-2)
        state, losses, step = self._drive(
            plan, inner, params, specs, loss_fn, x, y, 3
        )
        ref_p, ref_losses, _ = _run_ref(inner, w1, w2, b2, x, y, 3)
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-5, atol=1e-6)
        # values: reassemble the TP shards and compare every leaf
        w1_dist = np.concatenate(
            list(np.asarray(jax.device_get(state.params["w1"]))), axis=-1
        )
        w2_dist = np.concatenate(
            list(np.asarray(jax.device_get(state.params["w2"]))), axis=0
        )
        np.testing.assert_allclose(w1_dist, np.asarray(ref_p["w1"]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(w2_dist, np.asarray(ref_p["w2"]),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(jax.device_get(state.params["b2"])),
            np.asarray(ref_p["b2"]), rtol=1e-4, atol=1e-5,
        )
        # the jit cache stayed pinned across the trajectory
        assert step.cache_size() in (None, 1)

        # gradients via the sgd delta
        lr = 0.1
        state, _, _ = self._drive(
            plan, optax.sgd(lr), params, specs, loss_fn, x, y, 1
        )
        _, _, g0 = _run_ref(optax.sgd(lr), w1, w2, b2, x, y, 1)
        w1_after = np.concatenate(
            list(np.asarray(jax.device_get(state.params["w1"]))), axis=-1
        )
        np.testing.assert_allclose(
            (np.asarray(w1) - w1_after) / lr, np.asarray(g0["w1"]),
            rtol=1e-4, atol=1e-6,
        )
        b2_after = np.asarray(jax.device_get(state.params["b2"]))
        np.testing.assert_allclose(
            (np.asarray(b2) - b2_after) / lr, np.asarray(g0["b2"]),
            rtol=1e-4, atol=1e-6,
        )

    def test_dp_pipe_values_and_grads(self):
        d, n_pipe = 8, 4
        plan = ParallelPlan({"data": 2, "pipe": n_pipe},
                            devices=_devices())
        keys = jax.random.split(jax.random.PRNGKey(6), n_pipe)
        stages = jnp.stack(
            [jax.random.normal(k, (d, d)) * 0.4 for k in keys]
        )
        params = {"w": stages}
        x = jax.random.normal(jax.random.PRNGKey(7), (16, d))
        y = jax.random.normal(jax.random.PRNGKey(8), (16, d))

        pipe = PipelinePlanSpec(
            stage_fn=lambda p, mb: jnp.tanh(mb @ p["w"]),
            loss_fn=lambda yh, b: jnp.mean((yh - b[1]) ** 2),
            n_microbatches=n_pipe,
        )
        lr = 0.1
        state = plan.create_train_state(params, optax.sgd(lr),
                                        param_specs={"w": P("pipe")})
        step = plan.compile_train_step(None, optax.sgd(lr), params,
                                       param_specs={"w": P("pipe")},
                                       pipeline=pipe)
        state, m = step(state, (x, y))

        def seq_loss(ws, xb, yb):
            h = xb
            for w in ws:
                h = jnp.tanh(h @ w)
            return jnp.mean((h - yb) ** 2)

        wlist = [stages[i] for i in range(n_pipe)]
        ref_l, ref_g = jax.value_and_grad(seq_loss)(wlist, x, y)
        np.testing.assert_allclose(float(m["loss"]), float(ref_l),
                                   rtol=1e-5)
        new_w = np.asarray(jax.device_get(state.params["w"]))
        for i in range(n_pipe):
            np.testing.assert_allclose(
                (np.asarray(stages[i]) - new_w[i]) / lr,
                np.asarray(ref_g[i]), rtol=1e-4, atol=1e-6,
            )

    def test_pipe_plan_rejects_replicated_trainable_leaves(self):
        """A replicated leaf consumed inside stage_fn would get
        per-stage gradients with no cross-stage sum (and check_vma=False
        would mask the divergence) — the contract is enforced
        structurally, not by docstring."""
        plan = ParallelPlan({"data": 2, "pipe": 4}, devices=_devices())
        params = {"w": jnp.zeros((4, 4, 4)), "b": jnp.zeros((4,))}
        pipe = PipelinePlanSpec(
            stage_fn=lambda p, mb: jnp.tanh(mb @ p["w"] + p["b"]),
            loss_fn=lambda yh, b: jnp.mean(yh ** 2),
            n_microbatches=4,
        )
        with pytest.raises(ValueError, match="pipe-stacked"):
            plan.compile_train_step(
                None, optax.sgd(0.1), params,
                param_specs={"w": P("pipe"), "b": P()}, pipeline=pipe,
            )

    def test_pipe_axis_requires_pipeline_spec(self):
        plan = ParallelPlan({"pipe": 8}, devices=_devices())
        with pytest.raises(ValueError, match="PipelinePlanSpec"):
            plan.compile_train_step(lambda p, b: 0.0, optax.sgd(0.1),
                                    {"w": jnp.zeros((8, 2, 2))})
        plan2 = ParallelPlan({"data": 8}, devices=_devices())
        with pytest.raises(ValueError, match="no 'pipe' axis"):
            plan2.compile_train_step(
                None, optax.sgd(0.1), {"w": jnp.zeros((2, 2))},
                pipeline=PipelinePlanSpec(
                    stage_fn=lambda p, x: x, loss_fn=lambda y, b: 0.0
                ),
            )


# ---------------------------------------------------------------------------
# Structural: HLO collective counts == the hand-wired paths'
# ---------------------------------------------------------------------------


def _collective_counts(txt: str) -> dict:
    return {op: txt.count(op) for op in
            ("all-reduce(", "reduce-scatter(", "all-gather(",
             "collective-permute(")}


class TestPlanStructural:
    def test_dp_tp_zero_counts_match_handwired(self):
        """The acceptance pin: one compiled DP x TP x ZeRO plan step
        carries exactly the collective counts of the same step hand-wired
        from the pre-plan modules (tensor helpers + zero_shard_optimizer
        + call-site pmeans)."""
        from jax import shard_map
        from chainermn_tpu.parallel.zero import zero_shard_optimizer

        devices = _devices()
        plan = ParallelPlan(("data", "model", "zero"), devices=devices)
        m = plan.axis_size("model")
        w1, w2, b2 = _mlp_params(jax.random.PRNGKey(0))
        params = {
            "w1": stack_tp_params(w1, m, 1),
            "w2": stack_tp_params(w2, m, 0),
            "b2": b2,
        }
        specs = {"w1": P("model"), "w2": P("model"), "b2": P()}
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        y = jax.random.normal(jax.random.PRNGKey(2), (16, 8))
        lr = 0.1

        def loss_fn(p, batch):
            xb, yb = batch
            out = tp_mlp(xb, p["w1"], None, p["w2"], p["b2"],
                         axis_name="model")
            return jnp.mean((out - yb) ** 2)

        inner = optax.sgd(lr)
        state = plan.create_train_state(params, inner, param_specs=specs)
        step = plan.compile_train_step(loss_fn, inner, params,
                                       param_specs=specs)
        plan_counts = _collective_counts(
            step.lower(state, (x, y)).compile().as_text()
        )

        # hand-wired: the composition a user wrote before the plan
        mesh = plan.mesh

        def hand_local(params, batch):
            p = {
                "w1": params["w1"][0],
                "w2": params["w2"][0],
                "b2": params["b2"],
            }
            loss, g = jax.value_and_grad(loss_fn)(p, batch)
            # TP leaves: grads average over BOTH data-parallel axes
            gtp = jax.lax.pmean({"w1": g["w1"], "w2": g["w2"]},
                                ("data", "zero"))
            # replicated leaves: data-mean, then the zero wrapper's
            # scatter/update/gather over the zero axis
            grep = {"b2": jax.lax.pmean(g["b2"], ("data",))}
            zopt = zero_shard_optimizer(optax.sgd(lr), "zero")
            zstate = zopt.init({"b2": p["b2"]})
            urep, _ = zopt.update(grep, zstate, {"b2": p["b2"]})
            new = {
                "w1": (p["w1"] - lr * gtp["w1"])[None],
                "w2": (p["w2"] - lr * gtp["w2"])[None],
                "b2": p["b2"] + urep["b2"],
            }
            return new, jax.lax.pmean(loss, ("data", "zero"))

        pspec = {"w1": P("model"), "w2": P("model"), "b2": P()}
        hand = jax.jit(shard_map(
            hand_local, mesh=mesh,
            in_specs=(pspec, P(("data", "zero"))),
            out_specs=(pspec, P()),
            check_vma=False,
        ))
        hand_counts = _collective_counts(
            hand.lower(params, (x, y)).compile().as_text()
        )
        assert plan_counts == hand_counts, (plan_counts, hand_counts)
        # and the vocabulary is what the providers owe: TP's psums +
        # zero's scatter/gather are all present, no ppermute
        assert plan_counts["reduce-scatter("] >= 1
        assert plan_counts["all-gather("] >= 1
        assert plan_counts["all-reduce("] >= 2
        assert plan_counts["collective-permute("] == 0

    def test_pipe_counts_match_handwired(self):
        from jax import shard_map
        from chainermn_tpu.parallel.pipeline import pipeline_local

        devices = _devices()
        d, n_pipe = 8, 4
        plan = ParallelPlan({"data": 2, "pipe": n_pipe}, devices=devices)
        stages = jnp.stack([jnp.eye(d) * 0.5 for _ in range(n_pipe)])
        params = {"w": stages}
        x = jax.random.normal(jax.random.PRNGKey(0), (16, d))
        y = jnp.zeros_like(x)
        lr = 0.1
        pipe = PipelinePlanSpec(
            stage_fn=lambda p, mb: jnp.tanh(mb @ p["w"]),
            loss_fn=lambda yh, b: jnp.mean((yh - b[1]) ** 2),
            n_microbatches=n_pipe,
        )
        state = plan.create_train_state(params, optax.sgd(lr),
                                        param_specs={"w": P("pipe")})
        step = plan.compile_train_step(None, optax.sgd(lr), params,
                                       param_specs={"w": P("pipe")},
                                       pipeline=pipe)
        plan_counts = _collective_counts(
            step.lower(state, (x, y)).compile().as_text()
        )

        def hand_local(params, batch):
            xb, yb = batch
            w = {"w": params["w"][0]}

            def loss(w):
                xm = xb.reshape((n_pipe, xb.shape[0] // n_pipe, d))
                ym = pipeline_local(
                    lambda p, mb: jnp.tanh(mb @ p["w"]), w, xm, "pipe"
                )
                yh = ym.reshape(xb.shape)
                return jnp.mean((yh - yb) ** 2)

            l, g = jax.value_and_grad(loss)(w)
            g = jax.lax.pmean(g, ("data",))
            return ({"w": (w["w"] - lr * g["w"])[None]},
                    jax.lax.pmean(l, ("data",)))

        hand = jax.jit(shard_map(
            hand_local, mesh=plan.mesh,
            in_specs=({"w": P("pipe")}, P(("data",))),
            out_specs=({"w": P("pipe")}, P()),
            check_vma=False,
        ))
        hand_counts = _collective_counts(
            hand.lower(params, (x, y)).compile().as_text()
        )
        assert plan_counts["collective-permute("] == \
            hand_counts["collective-permute("] >= 1

    def test_step_donates_every_state_buffer(self):
        """Satellite: compiled plan step donates params/opt-state buffers
        (XLA's own input_output_alias table), and a second step re-uploads
        nothing — the donated first-step buffers are consumed in place."""
        devices = _devices()
        plan = ParallelPlan({"data": 2, "zero": 4}, devices=devices)
        w1, w2, b2 = _mlp_params(jax.random.PRNGKey(0))
        params = {"w1": w1, "w2": w2, "b2": b2}
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        y = jax.random.normal(jax.random.PRNGKey(2), (16, 8))

        def loss_fn(p, batch):
            xb, yb = batch
            return _ref_loss(p["w1"], p["w2"], p["b2"], xb, yb)

        inner = optax.adamw(1e-2)
        state = plan.create_train_state(params, inner)
        step = plan.compile_train_step(loss_fn, inner, params)
        txt = step.lower(state, (x, y)).compile().as_text()
        n_state_leaves = len(jax.tree.leaves(state))
        assert "input_output_alias" in txt
        n_alias = txt.count("may-alias") + txt.count("must-alias")
        assert n_alias >= n_state_leaves, (n_alias, n_state_leaves)

        # behavioural pin: after a step, every input state buffer is
        # consumed (donated) — nothing left to re-upload
        old = state
        state, _ = step(state, (x, y))
        assert all(l.is_deleted() for l in jax.tree.leaves(old))
        # and the batch was NOT donated
        assert not x.is_deleted()

        # donate=False: no aliasing, inputs stay live
        step_nd = plan.compile_train_step(loss_fn, inner, params,
                                          donate=False)
        txt_nd = step_nd.lower(state, (x, y)).compile().as_text()
        assert (txt_nd.count("may-alias") + txt_nd.count("must-alias")
                == 0)

    def test_jit_cache_pinned_at_one(self):
        devices = _devices()
        plan = ParallelPlan({"zero": 8}, devices=devices)
        params = {"w": jnp.ones((8, 8)) * 0.1}
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))

        def loss_fn(p, batch):
            return jnp.mean((batch @ p["w"]) ** 2)

        inner = optax.adamw(1e-2)
        state = plan.create_train_state(params, inner)
        step = plan.compile_train_step(loss_fn, inner, params)
        for _ in range(3):
            state, m = step(state, x)
        assert step.cache_size() in (None, 1)
        assert np.isfinite(float(m["loss"]))

    def test_zero_state_is_sharded_and_one_nth(self):
        devices = _devices()
        plan = ParallelPlan({"zero": 8}, devices=devices)
        params = {"w": jnp.ones((64, 8)) * 0.1}
        inner = optax.adamw(1e-2)
        state = plan.create_train_state(params, inner)
        leaves = jax.tree.leaves(state.opt_state["zero"])
        assert leaves, "zero group state missing"
        for leaf in leaves:
            assert leaf.shape[0] == 8  # stacked [n, ...]
            assert "zero" in tuple(leaf.sharding.spec)
            # per-device bytes = 1/n of the stacked whole
            shard = leaf.addressable_shards[0].data
            assert shard.size * 8 == leaf.size


# ---------------------------------------------------------------------------
# ISSUE 13 sweep-ins: TP x ZeRO stacked-group state + pipe x model specs
# ---------------------------------------------------------------------------


_ZSG_LOWERED_COUNTS = {"reduce_scatter": 3, "all_gather": 3,
                       "all_reduce": 5, "collective_permute": 0}


class TestZeroStackedGroups:
    """``zero_stacked_groups=True``: the stacked groups' optimizer state
    chunks over the zero axis too (arXiv:2004.13336 applied per TP
    shard) — dist == single values AND grads, state 1/z per shard,
    and the stacked groups' dp reduction becomes the zero group's
    rs/ag (pinned in the compiled HLO)."""

    def _workload(self):
        w1, w2, b2 = _mlp_params(jax.random.PRNGKey(3))
        x = jax.random.normal(jax.random.PRNGKey(4), (16, 8))
        y = jax.random.normal(jax.random.PRNGKey(5), (16, 8))

        def loss_fn(p, batch):
            xb, yb = batch
            out = tp_mlp(xb, p["w1"], None, p["w2"], p["b2"],
                         axis_name="model")
            return jnp.mean((out - yb) ** 2)

        return w1, w2, b2, x, y, loss_fn

    def _plan_and_params(self, w1, w2, b2):
        plan = ParallelPlan(("data", "model", "zero"),
                            devices=_devices(), zero_stacked_groups=True)
        m = plan.axis_size("model")
        params = {
            "w1": stack_tp_params(w1, m, 1),
            "w2": stack_tp_params(w2, m, 0),
            "b2": b2,
        }
        specs = {"w1": P("model"), "w2": P("model"), "b2": P()}
        return plan, params, specs

    def test_values_and_grads_match_reference(self):
        w1, w2, b2, x, y, loss_fn = self._workload()
        plan, params, specs = self._plan_and_params(w1, w2, b2)

        inner = optax.adamw(1e-2)
        state = plan.create_train_state(params, inner, param_specs=specs)
        step = plan.compile_train_step(loss_fn, inner, params,
                                       param_specs=specs)
        losses = []
        for _ in range(3):
            state, m = step(state, (x, y))
            losses.append(float(m["loss"]))
        _, ref_losses, _ = _run_ref(inner, w1, w2, b2, x, y, 3)
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-5,
                                   atol=1e-6)
        assert step.cache_size() in (None, 1)

        lr = 0.1
        state = plan.create_train_state(params, optax.sgd(lr),
                                        param_specs=specs)
        step = plan.compile_train_step(loss_fn, optax.sgd(lr), params,
                                       param_specs=specs)
        state, _ = step(state, (x, y))
        _, _, g0 = _run_ref(optax.sgd(lr), w1, w2, b2, x, y, 1)
        w1_after = np.concatenate(
            list(np.asarray(jax.device_get(state.params["w1"]))), axis=-1
        )
        np.testing.assert_allclose(
            (np.asarray(w1) - w1_after) / lr, np.asarray(g0["w1"]),
            rtol=1e-4, atol=1e-6,
        )

    def test_state_layout_and_hlo(self):
        """Model-group state leaves stack [m, z, ...] with
        P('model', 'zero'), per-device bytes 1/(m*z); the compiled step
        carries one rs + one ag per FLOAT LEAF (TP leaves now included
        — that is the feature) and no ppermute."""
        w1, w2, b2, x, y, loss_fn = self._workload()
        plan, params, specs = self._plan_and_params(w1, w2, b2)
        desc = plan.describe()
        assert desc["zero_stacked_groups"] is True
        inner = optax.adamw(1e-2)
        state = plan.create_train_state(params, inner, param_specs=specs)
        z = plan.axis_size("zero")
        m = plan.axis_size("model")
        for leaf in jax.tree.leaves(state.opt_state["model"]):
            assert leaf.shape[:2] == (m, z), leaf.shape
            assert tuple(leaf.sharding.spec)[:2] == ("model", "zero")
            shard = leaf.addressable_shards[0].data
            assert shard.size * m * z == leaf.size
        step = plan.compile_train_step(loss_fn, inner, params,
                                       param_specs=specs)
        txt = step.lower(state, (x, y)).compile().as_text()
        counts = _collective_counts(txt)
        # per-leaf rs/ag for w1, w2 (model group) AND b2 (zero group):
        # the stacked groups joined the zero pipeline
        assert counts["reduce-scatter("] == 3, counts
        assert counts["all-gather("] == 3, counts
        assert counts["collective-permute("] == 0

    def test_validation(self):
        with pytest.raises(ValueError, match="zero"):
            ParallelPlan({"data": 4, "model": 2},
                         devices=_devices(), zero_stacked_groups=True)
        with pytest.raises(ValueError, match="stacked axis"):
            ParallelPlan({"data": 2, "zero": 4},
                         devices=_devices(), zero_stacked_groups=True)

    def test_zero_stacked_groups_program_is_the_parents(self):
        """The step as it is traced, before XLA combines anything: a
        reduce-scatter over ``zero``, an all-reduce of the chunk over
        ``data`` and an all-gather over ``zero`` for each of the three
        leaves, one all-reduce over ``model`` for the row-parallel
        product and one for the metrics, no permute: the numbers PR 45's
        tree lowers to."""
        w1, w2, b2, x, y, loss_fn = self._workload()
        plan, params, specs = self._plan_and_params(w1, w2, b2)
        inner = optax.adamw(1e-2)
        state = plan.create_train_state(params, inner, param_specs=specs)
        step = plan.compile_train_step(loss_fn, inner, params,
                                       param_specs=specs)
        txt = step.lower(state, (x, y)).as_text()
        counts = {op: txt.count(f"stablehlo.{op}") for op in
                  ("reduce_scatter", "all_gather", "all_reduce",
                   "collective_permute")}
        assert counts == _ZSG_LOWERED_COUNTS, counts


def test_plan_takes_no_grad_reduction():
    """The plan's data-parallel reduction is its fused ``pmean``; there
    is no schedule to hand it and nothing in ``describe()`` names one."""
    import inspect

    assert "grad_reduction" not in inspect.signature(
        ParallelPlan.__init__).parameters
    with pytest.raises(TypeError, match="grad_reduction"):
        ParallelPlan({"data": 8}, devices=_devices(), grad_reduction="flat")
    assert "grad_reduction" not in ParallelPlan(
        {"data": 8}, devices=_devices()).describe()


class TestPipeModelComposed:
    """``P('pipe', 'model')`` leaves: stage slices that are themselves
    tensor-parallel — the composed plan the PR 9 follow-up named.
    dist == single values AND grads through the one compiled step."""

    def test_values_and_grads(self):
        d, n_pipe, n_tp = 8, 2, 2
        plan = ParallelPlan({"data": 2, "pipe": n_pipe, "model": n_tp},
                            devices=_devices())
        keys = jax.random.split(jax.random.PRNGKey(6), n_pipe)
        stage_w = [jax.random.normal(k, (d, d)) * 0.4 for k in keys]
        pw = jnp.stack([stack_tp_params(w, n_tp, 1) for w in stage_w])
        params = {"w": pw}  # [pipe, model, d, d/n_tp]
        from chainermn_tpu.parallel.tensor import (
            copy_to_tp,
            gather_from_tp,
        )

        def stage_fn(p, mb):
            h = copy_to_tp(mb, "model") @ p["w"]  # column-parallel
            h = gather_from_tp(h, "model", 1)
            return jnp.tanh(h)

        pipe = PipelinePlanSpec(
            stage_fn=stage_fn,
            loss_fn=lambda yh, b: jnp.mean((yh - b[1]) ** 2),
            n_microbatches=n_pipe,
        )
        lr = 0.1
        state = plan.create_train_state(
            params, optax.sgd(lr), param_specs={"w": P("pipe", "model")}
        )
        step = plan.compile_train_step(
            None, optax.sgd(lr), params,
            param_specs={"w": P("pipe", "model")}, pipeline=pipe,
        )
        x = jax.random.normal(jax.random.PRNGKey(7), (8, d))
        y = jax.random.normal(jax.random.PRNGKey(8), (8, d))
        state, m = step(state, (x, y))

        def seq_loss(ws, xb, yb):
            h = xb
            for w in ws:
                h = jnp.tanh(h @ w)
            return jnp.mean((h - yb) ** 2)

        ref_l, ref_g = jax.value_and_grad(seq_loss)(stage_w, x, y)
        np.testing.assert_allclose(float(m["loss"]), float(ref_l),
                                   rtol=1e-5)
        new_w = np.asarray(jax.device_get(state.params["w"]))
        for i in range(n_pipe):
            full_after = np.concatenate(list(new_w[i]), axis=-1)
            np.testing.assert_allclose(
                (np.asarray(stage_w[i]) - full_after) / lr,
                np.asarray(ref_g[i]), rtol=1e-4, atol=1e-6,
            )
        assert step.cache_size() in (None, 1)
        assert "pipe+model" in state.opt_state
        # state mirrors the double stack (adam: non-empty state leaves)
        adam_state = plan.create_train_state(
            params, optax.adamw(1e-2),
            param_specs={"w": P("pipe", "model")},
        )
        leaf = jax.tree.leaves(adam_state.opt_state["pipe+model"])[0]
        assert leaf.shape[:2] == (n_pipe, n_tp)
        assert tuple(leaf.sharding.spec)[:2] == ("pipe", "model")

    def test_spec_validation(self):
        plan = ParallelPlan({"data": 2, "pipe": 2, "model": 2},
                            devices=_devices())
        params = {"w": jnp.zeros((2, 2, 4, 4))}
        full = plan.param_specs(params, {"w": P("pipe", "model")})
        assert full["w"] == P("pipe", "model")
        # non-canonical order rejected
        with pytest.raises(ValueError, match="canonical order"):
            plan.param_specs(params, {"w": P("model", "pipe")})
        # each leading dim checked against its axis
        with pytest.raises(ValueError, match="leading dim"):
            plan.param_specs({"w": jnp.zeros((2, 3, 4))},
                             {"w": P("pipe", "model")})


# ---------------------------------------------------------------------------
# Satellite: checkpoint round-trip over a plan-sharded [n, ...] ZeRO state
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip_plan_zero_state(comm, tmp_path):
    from chainermn_tpu.extensions.checkpoint import (
        create_multi_node_checkpointer,
    )

    devices = _devices()
    plan = ParallelPlan({"data": 2, "zero": 4}, devices=devices)
    w1, w2, b2 = _mlp_params(jax.random.PRNGKey(0))
    params = {"w1": w1, "w2": w2, "b2": b2}
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    y = jax.random.normal(jax.random.PRNGKey(2), (16, 8))

    def loss_fn(p, batch):
        xb, yb = batch
        return _ref_loss(p["w1"], p["w2"], p["b2"], xb, yb)

    inner = optax.adamw(1e-2)
    state = plan.create_train_state(params, inner)
    step = plan.compile_train_step(loss_fn, inner, params)
    state, _ = step(state, (x, y))

    ckpt = create_multi_node_checkpointer(
        "plan", comm, path=str(tmp_path)
    )
    ckpt.save(state, 1)

    template = plan.create_train_state(params, inner)
    restored, it = ckpt.maybe_load(template)
    assert it == 1
    # restored zero-state leaves keep the stacked [n, ...] layout
    for a, b in zip(jax.tree.leaves(restored.opt_state["zero"]),
                    jax.tree.leaves(state.opt_state["zero"])):
        assert np.shape(a) == np.shape(b)

    # one more step from the restored state == one more from the live one
    s_live, m_live = step(state, (x, y))
    s_rest, m_rest = step(restored, (x, y))
    assert abs(float(m_live["loss"]) - float(m_rest["loss"])) < 1e-6
    for a, b in zip(jax.tree.leaves(jax.device_get(s_live.params)),
                    jax.tree.leaves(jax.device_get(s_rest.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# make_train_step integration + optimizer unwrap
# ---------------------------------------------------------------------------


class TestTrainerIntegration:
    def test_make_train_step_plan_path(self):
        from chainermn_tpu.training.train_step import make_train_step

        plan = ParallelPlan({"data": 2, "zero": 4}, devices=_devices())
        params = {"w": jnp.ones((8, 8)) * 0.1}
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))

        def loss_fn(p, batch):
            return jnp.mean((batch @ p["w"]) ** 2)

        inner = optax.adamw(1e-2)
        step = make_train_step(loss_fn, inner, plan=plan)
        state = plan.create_train_state(params, inner)
        for _ in range(2):
            state, m = step(state, x)
        assert np.isfinite(float(m["loss"]))
        assert step.cache_size() in (None, 1)

    def test_make_train_step_plan_rejects_comm_only_knobs(self):
        from chainermn_tpu.training.train_step import make_train_step

        plan = ParallelPlan({"data": 8}, devices=_devices())
        with pytest.raises(ValueError, match="accum_steps"):
            make_train_step(lambda p, b: 0.0, optax.sgd(0.1), plan=plan,
                            accum_steps=2)
        with pytest.raises(ValueError, match="communicator"):
            make_train_step(lambda p, b: 0.0, optax.sgd(0.1))
        with pytest.raises(ValueError, match="plan"):
            make_train_step(lambda p, b: 0.0, optax.sgd(0.1),
                            comm=None, param_specs={"w": P()})

    def test_make_train_step_pipe_plan_path(self):
        """The trainer delegation can express pipe plans: pipeline=
        threads through to the plan."""
        from chainermn_tpu.training.train_step import make_train_step

        d, n_pipe = 8, 4
        plan = ParallelPlan({"data": 2, "pipe": n_pipe},
                            devices=_devices())
        stages = jnp.stack([jnp.eye(d) * 0.5 for _ in range(n_pipe)])
        params = {"w": stages}
        pipe = PipelinePlanSpec(
            stage_fn=lambda p, mb: jnp.tanh(mb @ p["w"]),
            loss_fn=lambda yh, b: jnp.mean(yh ** 2),
            n_microbatches=n_pipe,
        )
        step = make_train_step(None, optax.sgd(0.1), plan=plan,
                               param_specs={"w": P("pipe")},
                               pipeline=pipe)
        state = plan.create_train_state(params, optax.sgd(0.1),
                                        param_specs={"w": P("pipe")})
        x = jax.random.normal(jax.random.PRNGKey(0), (16, d))
        state, m = step(state, x)
        assert np.isfinite(float(m["loss"]))

    def test_inner_transform_unwraps_and_refuses(self, comm):
        from chainermn_tpu.optimizers import (
            create_local_sgd,
            create_multi_node_optimizer,
            inner_transform,
        )

        sgd = optax.sgd(0.1)
        assert inner_transform(sgd) is sgd
        wrapped = create_multi_node_optimizer(sgd, comm)
        assert inner_transform(wrapped) is sgd
        with pytest.raises(ValueError, match="double_buffering"):
            inner_transform(create_multi_node_optimizer(
                sgd, comm, double_buffering=True))
        with pytest.raises(ValueError, match="LocalSGD"):
            inner_transform(create_local_sgd(sgd, comm, sync_every=4))
        # a configured compressed wire must not be dropped silently
        with pytest.raises(ValueError, match="compress"):
            inner_transform(create_multi_node_optimizer(
                sgd, comm, allreduce_grad_dtype=jnp.bfloat16))

    def test_plan_unwraps_wrapper_consistently(self, comm):
        """The documented migration flow: the user's existing
        MultiNodeOptimizer (even with reduction_schedule='zero') goes to
        BOTH create_train_state and the step — the plan unwraps it at
        every entry point, so the state layout matches the compiled
        step's specs instead of the wrapper's comm-sized chunking."""
        from chainermn_tpu.optimizers import create_multi_node_optimizer

        plan = ParallelPlan({"data": 2, "zero": 4}, devices=_devices())
        params = {"w": jnp.ones((8, 8)) * 0.1}
        x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))

        def loss_fn(p, batch):
            return jnp.mean((batch @ p["w"]) ** 2)

        wrapped = create_multi_node_optimizer(
            optax.adamw(1e-2), comm, reduction_schedule="zero"
        )
        state = plan.create_train_state(params, wrapped)
        step = plan.compile_train_step(loss_fn, wrapped, params)
        state, m = step(state, x)
        assert np.isfinite(float(m["loss"]))
        # state chunked by the PLAN's zero axis (4), not comm.size (8)
        lead = jax.tree.leaves(state.opt_state["zero"])[0].shape[0]
        assert lead == 4

    def test_make_train_step_plan_matches_comm_path(self, comm):
        """The delegation really is the same math: plan-compiled DP step
        == the communicator-path step on the same workload."""
        from chainermn_tpu.training.train_step import (
            create_train_state,
            make_train_step,
        )
        from chainermn_tpu.optimizers import create_multi_node_optimizer

        w1, w2, b2 = _mlp_params(jax.random.PRNGKey(0))
        params = {"w1": w1, "w2": w2, "b2": b2}
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
        y = jax.random.normal(jax.random.PRNGKey(2), (16, 8))

        def loss_fn(p, batch):
            xb, yb = batch
            return _ref_loss(p["w1"], p["w2"], p["b2"], xb, yb)

        inner = optax.adamw(1e-2)
        opt = create_multi_node_optimizer(inner, comm)
        c_state = create_train_state(
            jax.tree.map(lambda p: jnp.array(p, copy=True), params),
            opt, comm,
        )
        c_step = make_train_step(loss_fn, opt, comm, donate=False)

        plan = ParallelPlan({"data": 8}, devices=_devices())
        p_state = plan.create_train_state(params, inner)
        p_step = make_train_step(loss_fn, inner, plan=plan)

        for _ in range(2):
            c_state, cm = c_step(c_state, (x, y))
            p_state, pm = p_step(p_state, (x, y))
        assert abs(float(cm["loss"]) - float(pm["loss"])) < 1e-6
        for k in params:
            np.testing.assert_allclose(
                np.asarray(jax.device_get(p_state.params[k])),
                np.asarray(jax.device_get(c_state.params[k])),
                rtol=1e-5, atol=1e-6,
            )


def test_dryrun_phase_table_wires_plan_phase():
    """Satellite: dryrun phase K is in __graft_entry__'s phase table."""
    src = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "__graft_entry__.py")).read()
    assert "_phase_parallel_plan" in src
    assert '"K:parallel-plan 3-D mesh", _phase_parallel_plan' in src
