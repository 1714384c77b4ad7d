"""Train-step/trainer tests: the core reference invariant — distributed
training result == single-process result on the concatenated batch
(SURVEY.md section 4, "Key invariant tested everywhere")."""

import io

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from chainermn_tpu import create_communicator, create_multi_node_optimizer
from chainermn_tpu.training import Trainer, make_eval_step, make_train_step
from chainermn_tpu.training.train_step import create_train_state
from chainermn_tpu.training.trainer import default_collate

N = 8


@pytest.fixture(scope="module")
def comm():
    return create_communicator("naive")


def _linreg_loss(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    loss = jnp.mean((pred - y) ** 2)
    return loss, {"mse": loss}


def _data(n=64, d=4, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d).astype(np.float32)
    w_true = rng.randn(d).astype(np.float32)
    y = x @ w_true + 0.01 * rng.randn(n).astype(np.float32)
    return x, y


def test_distributed_step_equals_single_device(comm):
    x, y = _data()
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    # single-device reference on the full batch (computed BEFORE the
    # distributed step: make_train_step donates its state, which may alias
    # these param buffers)
    ref_opt = optax.sgd(0.1)
    (loss, _), grads = jax.value_and_grad(_linreg_loss, has_aux=True)(
        params, (jnp.asarray(x), jnp.asarray(y))
    )
    upd, _ = ref_opt.update(grads, ref_opt.init(params), params)
    ref_params = jax.device_get(optax.apply_updates(params, upd))
    loss = float(loss)

    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    state = create_train_state(params, opt, comm)
    step = make_train_step(_linreg_loss, opt, comm)

    new_state, metrics = step(state, (x, y))

    np.testing.assert_allclose(
        np.asarray(new_state.params["w"]), np.asarray(ref_params["w"]), rtol=1e-4
    )
    np.testing.assert_allclose(float(metrics["loss"]), float(loss), rtol=1e-4)
    assert int(new_state.step) == 1


def test_accumulated_step_equals_full_batch(comm):
    """accum_steps=K over the same total batch must produce the SAME update
    as the plain step (microbatches see identical params; the mean of
    microbatch gradients of batch-mean losses is the full-batch gradient)."""
    x, y = _data()
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)

    state_plain = create_train_state(params, opt, comm)
    plain = make_train_step(_linreg_loss, opt, comm, donate=False)
    state_plain, m_plain = plain(state_plain, (x, y))

    state_acc = create_train_state(params, opt, comm)
    acc = make_train_step(_linreg_loss, opt, comm, donate=False,
                          accum_steps=4)
    state_acc, m_acc = acc(state_acc, (x, y))

    np.testing.assert_allclose(
        np.asarray(state_acc.params["w"]),
        np.asarray(state_plain.params["w"]), rtol=1e-5, atol=1e-6,
    )
    np.testing.assert_allclose(
        float(m_acc["loss"]), float(m_plain["loss"]), rtol=1e-5
    )

    with pytest.raises(ValueError):
        make_train_step(_linreg_loss, opt, comm, accum_steps=0)
    bad = make_train_step(_linreg_loss, opt, comm, donate=False,
                          accum_steps=3)
    with pytest.raises(ValueError):
        bad(create_train_state(params, opt, comm), (x, y))  # 8 % 3 != 0


def test_multi_step_convergence(comm):
    x, y = _data(n=256)
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    opt = create_multi_node_optimizer(optax.adam(0.05), comm)
    state = create_train_state(params, opt, comm)
    step = make_train_step(_linreg_loss, opt, comm)
    for _ in range(100):
        state, metrics = step(state, (x, y))
        # wait for each step: a hundred steps of CPU collectives in
        # flight at once abort the process on a loaded host
        loss = float(metrics["loss"])
    assert loss < 1e-2


def test_eval_step_matches_full_batch(comm):
    x, y = _data()
    params = {"w": jnp.ones(4), "b": jnp.zeros(())}

    def metric_fn(params, batch):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        return {"mse": jnp.mean((pred - y) ** 2)}

    ev = make_eval_step(metric_fn, comm)
    out = ev(params, (x, y), ())
    want = float(np.mean((x @ np.ones(4) - y) ** 2))
    np.testing.assert_allclose(float(out["mse"]), want, rtol=1e-5)


def test_trainer_runs_and_logs(comm):
    x, y = _data(n=128)
    data = [(x[i], y[i]) for i in range(len(x))]
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    state = create_train_state(params, opt, comm)
    step = make_train_step(_linreg_loss, opt, comm)

    class _Iter:
        def __iter__(self):
            for i in range(0, 128, 32):
                yield data[i : i + 32]

    buf = io.StringIO()
    calls = []
    trainer = Trainer(step, state, _Iter(), comm, log_interval=2, out=buf)
    trainer.extend(lambda tr: calls.append(tr.iteration), interval=3)
    final = trainer.run(6)
    assert int(final.step) == 6
    assert calls == [3, 6]
    logged = buf.getvalue()
    assert "iter 2/6" in logged and "loss=" in logged


def test_trainer_raises_on_empty_epoch(comm):
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    state = create_train_state(params, opt, comm)
    step = make_train_step(_linreg_loss, opt, comm)

    class _Empty:
        def __iter__(self):
            return iter([])

    trainer = Trainer(step, state, _Empty(), comm, out=io.StringIO())
    with pytest.raises(RuntimeError, match="no batches"):
        trainer.run(5)


def test_optimizer_survives_pickle_roundtrip(comm):
    import pickle

    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    # __getattr__ must not recurse during copy/pickle protocol probing
    import copy

    c = copy.copy(opt)
    assert c.actual_optimizer is opt.actual_optimizer
    with pytest.raises(AttributeError):
        opt.__getstate_nonexistent__


def test_default_collate():
    batch = [(np.zeros(3), np.int32(1)), (np.ones(3), np.int32(2))]
    x, y = default_collate(batch)
    assert x.shape == (2, 3) and y.shape == (2,)
    d = default_collate([{"a": np.zeros(2)}, {"a": np.ones(2)}])
    assert d["a"].shape == (2, 2)
    arr = default_collate([np.zeros(4), np.zeros(4)])
    assert arr.shape == (2, 4)


def test_mnist_model_parallel_example_runs():
    import examples.mnist.train_mnist_model_parallel as ex

    acc = ex.main(["--iterations", "60", "--batchsize", "64", "--n-units", "64"])
    assert acc > 0.9  # synthetic blobs are easy; must actually learn


def test_mnist_example_runs():
    import examples.mnist.train_mnist as ex

    final = ex.main(["--communicator", "naive", "--iterations", "20",
                     "--batchsize", "64"])
    assert "val_acc" in final and final["val_acc"] > 0.3


def test_prefetch_to_device_order_and_count():
    from chainermn_tpu.training import prefetch_to_device

    batches = [{"x": np.full((2,), i, np.float32)} for i in range(7)]
    out = list(prefetch_to_device(iter(batches), size=3))
    assert len(out) == 7
    for i, b in enumerate(out):
        assert isinstance(b["x"], jax.Array)  # placed on device
        np.testing.assert_array_equal(np.asarray(b["x"]), np.full((2,), i))

    with pytest.raises(ValueError, match=">= 1"):
        next(prefetch_to_device(iter(batches), size=0))

    # shorter than the buffer: everything still comes out
    out = list(prefetch_to_device(iter(batches[:2]), size=5))
    assert len(out) == 2


def test_trainer_prefetch_matches_unprefetched(comm):
    """prefetch=2 must not change training: same batches in the same
    order -> bit-identical final parameters."""
    x, y = _data()
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    step = make_train_step(_linreg_loss, opt, comm, donate=False)

    class FixedIter:
        def __iter__(self):
            rng = np.random.RandomState(0)
            for _ in range(6):
                idx = rng.permutation(len(x))[:16]
                yield [(x[i], y[i]) for i in idx]

    results = []
    for prefetch in (0, 2):
        state = create_train_state(params, opt, comm)
        tr = Trainer(step, state, FixedIter(), comm, log_interval=100,
                     out=io.StringIO(), prefetch=prefetch)
        state = tr.run(12)  # 2 epochs of 6 batches
        results.append(jax.device_get(state.params))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        results[0], results[1],
    )


def test_trainer_prefetch_accepts_nondivisible_batches(comm):
    """Enabling prefetch must not change which batch sizes are accepted:
    a leading dim not divisible by the mesh falls back to default
    placement instead of crashing in device_put."""
    x, y = _data(n=24)
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
    # plain jit step (not mesh-sharded): accepts any batch size
    inner = optax.sgd(0.1)

    @jax.jit
    def step(state, batch):
        (loss, _), grads = jax.value_and_grad(_linreg_loss, has_aux=True)(
            state[0], batch
        )
        upd, opt_state = inner.update(grads, state[1], state[0])
        return (optax.apply_updates(state[0], upd), opt_state), {"loss": loss}

    class _Iter:
        def __iter__(self):
            # 12 examples per batch: 12 % 8 != 0
            yield [(x[i], y[i]) for i in range(12)]
            yield [(x[i], y[i]) for i in range(12, 24)]

    tr = Trainer(step, (params, inner.init(params)), _Iter(), comm,
                 log_interval=100, out=io.StringIO(), prefetch=2)
    state = tr.run(2)
    assert np.isfinite(float(jax.device_get(state[0]["w"])[0]))


def test_train_step_local_sgd_true_local_evolution(comm):
    """THROUGH make_train_step (not opt.update directly): with
    ``create_local_sgd`` the trainer must NOT pre-reduce gradients — the
    inner adam evolves on each member's LOCAL gradients and members only
    meet at the sync. The oracle is a per-member optax simulation over
    the member's own batch shard. This pins the
    ``handles_cross_rank_sync`` protocol: an isinstance-style dispatch
    regression in make_train_step (which once silently kept the
    per-step wire for this wrapper) fails the oracle equality."""
    from chainermn_tpu import create_local_sgd

    x, y = _data(n=N * 4)
    params = {"w": jnp.zeros(4), "b": jnp.zeros(())}
    opt = create_local_sgd(optax.adam(0.1), comm, sync_every=2)
    state = create_train_state(params, opt, comm)
    step = make_train_step(_linreg_loss, opt, comm, donate=False)
    batch = (jnp.asarray(x), jnp.asarray(y))
    for _ in range(2):
        state, _ = step(state, batch)

    # Oracle: each member adams on ITS shard for 2 steps; then average.
    finals = []
    for r in range(N):
        shard = (jnp.asarray(x[r * 4:(r + 1) * 4]),
                 jnp.asarray(y[r * 4:(r + 1) * 4]))
        p = params
        inner = optax.adam(0.1)
        s = inner.init(p)
        for _ in range(2):
            g = jax.grad(lambda pp: _linreg_loss(pp, shard)[0])(p)
            u, s = inner.update(g, s, p)
            p = optax.apply_updates(p, u)
        finals.append(p)
    expect = jax.tree.map(
        lambda *leaves: np.mean([np.asarray(v) for v in leaves], axis=0),
        *finals,
    )
    got = jax.tree.map(np.asarray, state.params)
    for k in ("w", "b"):
        np.testing.assert_allclose(got[k], expect[k], rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------------
# The default reduction as two all_to_alls a large leaf (ISSUE 39). The
# all-reduce it took the place of, kept here as the oracle, is the same
# function with the size from which a leaf goes that way out of reach.
# ----------------------------------------------------------------------


@pytest.fixture
def min_bytes(monkeypatch):
    """Set ``collectives.ALL_TO_ALL_MIN_BYTES``: 0 sends every matrix of
    these toy models where a real model's matrices go, ``None`` leaves
    none to go there (the all-reduce a leaf, as before ISSUE 39)."""
    from chainermn_tpu.parallel import collectives

    def set_to(n):
        monkeypatch.setattr(collectives, "ALL_TO_ALL_MIN_BYTES",
                            float("inf") if n is None else n)
    return set_to


def _replicas(tree):
    """Each leaf's per-device copies as numpy arrays."""
    return [[np.asarray(s.data) for s in leaf.addressable_shards]
            for leaf in jax.tree.leaves(tree)]


def _lm_job(n_dev, wire="bfloat16"):
    from chainermn_tpu.models import TransformerLM

    comm = create_communicator(
        "xla", devices=jax.devices("cpu")[:n_dev], allreduce_grad_dtype=wire)
    model = TransformerLM(vocab_size=64, num_layers=2, num_heads=2,
                          d_model=32, d_ff=64, max_len=16)
    tokens = jax.random.randint(jax.random.key(0), (8, 16), 0, 64)
    params = model.init(jax.random.key(1), tokens[:1])["params"]

    def loss_fn(p, t):
        logits = model.apply({"params": p}, t)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], t[:, 1:]).mean()

    return comm, params, tokens, loss_fn


def _two_steps(comm, params, batch, loss_fn, lr=0.5, **kwargs):
    opt = create_multi_node_optimizer(optax.sgd(lr), comm, **kwargs)
    state = create_train_state(params, opt, comm)
    step = make_train_step(loss_fn, opt, comm, donate=False)
    for _ in range(2):
        state, metrics = step(state, batch)
    return state, metrics


def test_all_to_all_reduction_equals_the_all_reduce_on_four_devices(
        min_bytes):
    """Two steps of a small TransformerLM on 4 devices at the bf16 wire:
    the parameters equal the all-reduce's within the wire's rounding and
    every replica holds the same bits."""
    comm, params, tokens, loss_fn = _lm_job(4)
    min_bytes(None)
    want, _ = _two_steps(comm, params, tokens, loss_fn)
    min_bytes(0)
    got, metrics = _two_steps(comm, params, tokens, loss_fn)
    assert np.isfinite(float(metrics["loss"]))
    for g, w, p0 in zip(jax.tree.leaves(got.params),
                        jax.tree.leaves(want.params),
                        jax.tree.leaves(params)):
        moved = float(jnp.max(jnp.abs(w - p0)))
        # a bf16 wire keeps 8 bits: two steps' updates agree to 2^-7
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=moved * 2 ** -7 + 1e-7, rtol=0)
    for copies in _replicas(got.params):
        for c in copies[1:]:
            np.testing.assert_array_equal(c, copies[0])


@pytest.mark.parametrize("rows", [8, 7])
def test_all_to_all_reduction_is_the_mean_over_the_devices(min_bytes, rows):
    """Per-device gradients that differ by a known factor: device r's
    rows are r + 1, so the matrix's gradient there is r + 1 and the
    vector's 1; a float32 wire and a unit step leave -mean exactly,
    through both paths (the all_to_alls for the matrix, with 8 rows and
    with 7, which four devices do not divide; the all-reduce for the
    vector)."""
    min_bytes(0)
    n = 4
    comm = create_communicator("xla", devices=jax.devices("cpu")[:n])
    params = {"w": jnp.zeros((rows, 3)), "b": jnp.zeros((3,))}
    x = jnp.repeat(jnp.arange(1.0, n + 1), 2)[:, None, None] \
        * jnp.ones((2 * n, rows, 3))

    def loss_fn(p, batch):
        return jnp.mean(jnp.sum(batch * p["w"], axis=(1, 2))) \
            + jnp.sum(p["b"])

    opt = create_multi_node_optimizer(optax.sgd(1.0), comm)
    state = create_train_state(params, opt, comm)
    step = make_train_step(loss_fn, opt, comm, donate=False)
    assert "all_to_all" in str(jax.make_jaxpr(step)(state, x))
    state, _ = step(state, x)
    np.testing.assert_array_equal(
        np.asarray(state.params["w"]),
        np.full((rows, 3), -(1 + 2 + 3 + 4) / n))
    np.testing.assert_array_equal(np.asarray(state.params["b"]),
                                  np.full((3,), -1.0))


def test_one_device_step_is_the_program_it_was(min_bytes):
    """On one device there is nothing to reduce over: the traced step
    is, equation for equation, the step with the all-reduce a leaf, and
    holds no all_to_all."""
    comm, params, tokens, loss_fn = _lm_job(1)

    def traced():
        opt = create_multi_node_optimizer(optax.adamw(1e-3), comm)
        state = create_train_state(params, opt, comm)
        step = make_train_step(loss_fn, opt, comm, donate=False)
        return str(jax.make_jaxpr(step)(state, tokens))

    min_bytes(0)
    text = traced()
    min_bytes(None)
    assert text == traced()
    assert "all_to_all" not in text


@pytest.mark.parametrize("how", [
    "accum_steps", "plain_optax", "double_buffering", "error_feedback",
    "int8_wire", "zero", "flat_schedule", "two_dimensional",
])
def test_every_path_gives_what_it_gave(how, min_bytes):
    """Accumulation and a plain optax optimizer reduce through the same
    function (the sum crosses once, after the last microbatch) and agree
    with the all-reduce to float32's rounding; double buffering, error
    feedback, the int8 wire, an explicit schedule and a communicator
    with a pipeline of its own never reach it and give the same bits."""
    name = "two_dimensional" if how == "two_dimensional" else "xla"
    wire = jnp.int8 if how in ("error_feedback", "int8_wire") else None
    comm = create_communicator(name, devices=jax.devices("cpu")[:4],
                               allreduce_grad_dtype=wire)
    accum = 2 if how == "accum_steps" else 1
    x, y = _data(n=16)
    params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros(())}

    def loss_fn(p, batch):
        return _linreg_loss({"w": p["w"][:, 0], "b": p["b"]}, batch)

    def run():
        opt = optax.sgd(0.1) if how == "plain_optax" else \
            create_multi_node_optimizer(
                optax.sgd(0.1), comm,
                double_buffering=how == "double_buffering",
                error_feedback=how == "error_feedback",
                reduction_schedule={"zero": "zero",
                                    "flat_schedule": "flat"}.get(how))
        state = create_train_state(params, opt, comm)
        step = make_train_step(loss_fn, opt, comm, donate=False,
                               accum_steps=accum)
        for _ in range(2):
            state, metrics = step(state, (x, y))
        assert np.isfinite(float(metrics["loss"]))
        return jax.tree.leaves(state.params)

    min_bytes(None)
    want = run()
    min_bytes(0)
    got = run()
    for g, w in zip(got, want):
        if how in ("accum_steps", "plain_optax"):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=1e-6, atol=1e-7)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_accumulated_step_reduces_once_and_equals_the_full_batch(min_bytes):
    """accum_steps=2 on 4 devices: the sum crosses the wire once, after
    the last microbatch (two all_to_alls for the one matrix, as without
    accumulation), and the step equals the unaccumulated one."""
    min_bytes(0)
    comm = create_communicator("xla", devices=jax.devices("cpu")[:4])
    x, y = _data(n=32)
    params = {"w": jnp.zeros((4, 1)), "b": jnp.zeros(())}

    def loss_fn(p, batch):
        return _linreg_loss({"w": p["w"][:, 0], "b": p["b"]}, batch)

    out = []
    for accum in (1, 2):
        opt = create_multi_node_optimizer(optax.sgd(0.1), comm)
        state = create_train_state(params, opt, comm)
        step = make_train_step(loss_fn, opt, comm, donate=False,
                               accum_steps=accum)
        text = str(jax.make_jaxpr(step)(state, (x, y)))
        assert text.count(" all_to_all[") == 2
        out.append(step(state, (x, y))[0].params)
    for a, b in zip(jax.tree.leaves(out[0]), jax.tree.leaves(out[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_a_leaf_used_twice_arrives_summed(min_bytes):
    """A tied leaf (the loss reads it in two places) is reduced once, as
    the sum of both uses' cotangents."""
    comm = create_communicator("xla", devices=jax.devices("cpu")[:4])
    rs = np.random.RandomState(5)
    params = {"tied": jnp.asarray(rs.randn(8, 4), jnp.float32),
              "own": jnp.asarray(rs.randn(4, 4), jnp.float32)}
    rows = jnp.asarray(rs.randn(16, 8), jnp.float32)

    def loss_fn(p, x):
        h = jnp.tanh(x @ p["tied"]) @ p["own"]
        return jnp.mean((h @ p["tied"].T - x) ** 2)

    results = []
    for n in (0, None):
        min_bytes(n)
        state, _ = _two_steps(comm, params, rows, loss_fn, lr=0.05)
        results.append(state.params)
    single = params
    for _ in range(2):
        g = jax.grad(loss_fn)(single, rows)
        single = jax.tree.map(lambda p, d: p - 0.05 * d, single, g)
    for got, want, ref in zip(*(jax.tree.leaves(t)
                                for t in (*results, single))):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)
