"""The fused head makes its gradient in its forward loop (ISSUE 31): a
``jax.custom_vjp`` whose forward rule forms ``softmax - onehot`` while a
chunk's logits are there and multiplies it out, three matmuls a chunk
and no chunk computed twice. Held against the loop it replaced (a
``lax.scan`` of ``jax.checkpoint``-ed chunks, kept here as the oracle)
and against ``lm_loss`` on full float32 logits, at a tiny size on the
CPU."""

import jax
import jax.numpy as jnp
import pytest

from chainermn_tpu.models import (
    MODEL_CONFIGS,
    lm_from_config,
    lm_loss,
    lm_loss_fused,
    lm_loss_looped,
    lm_loss_moe,
)
from chainermn_tpu.models import transformer
from chainermn_tpu.observability import train_path
from chainermn_tpu.observability.metrics import registry


def scan_of_checkpoints(hidden, emb_table, tokens, *, n_chunks=8,
                        compute_dtype=jnp.bfloat16, weights=None):
    """``lm_loss_fused`` as it was before ISSUE 31: every chunk's logits
    and log-sum-exp made again in the backward pass, the gradient
    autodiff's (four matmuls a chunk)."""
    B, T, D = hidden.shape
    h = hidden[:, :-1].reshape(-1, D)
    t = tokens[:, 1:].reshape(-1)
    n = h.shape[0]
    chunk = -(-n // n_chunks)  # ceil
    pad = chunk * n_chunks - n
    h = jnp.pad(h, ((0, pad), (0, 0)))
    t = jnp.pad(t, (0, pad))
    valid = jnp.pad(
        jnp.ones((n,), jnp.float32) if weights is None
        else weights.astype(jnp.float32).reshape(n), (0, pad))
    w = emb_table.astype(compute_dtype).T  # [D, vocab]

    @jax.checkpoint
    def chunk_loss(hc, tc, mc):
        logits = jnp.dot(
            hc.astype(compute_dtype), w,
            preferred_element_type=jnp.float32,
        )
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - gold) * mc)

    def body(acc, xs):
        hc, tc, mc = xs
        return acc + chunk_loss(hc, tc, mc), ()

    total, _ = jax.lax.scan(
        body, jnp.float32(0.0),
        (h.reshape(n_chunks, chunk, D),
         t.reshape(n_chunks, chunk),
         valid.reshape(n_chunks, chunk)),
    )
    return total / n


def dense(hidden, emb_table, tokens, *, weights=None, **_):
    """``lm_loss`` on the full float32 logits (its mask is tokens-shaped
    and its mean is over the mask)."""
    with jax.default_matmul_precision("highest"):
        logits = hidden.astype(jnp.float32) @ emb_table.T
    if weights is None:
        return lm_loss(logits, tokens)
    mask = jnp.pad(weights, ((0, 0), (1, 0)))
    return lm_loss(logits, tokens, mask) * weights.sum() / weights.size


B, T, D, V = 3, 37, 64, 211  # 108 rows: 8 chunks of 14 pad 4


@pytest.fixture(scope="module")
def operands():
    k = jax.random.split(jax.random.key(31), 5)
    return dict(
        tokens=jax.random.randint(k[0], (B, T), 0, V),
        table=jax.random.normal(k[1], (V, D)) * 0.3,
        mix=jax.random.normal(k[2], (D, D)) / 8.0,
        states=jax.random.normal(k[3], (B, T, D)),
        weights=jax.random.uniform(k[4], (B, T - 1), minval=0.1, maxval=2.0),
    )


def _loss_of(head, ops, *, tied, weighted, **kw):
    """``f(states, table, weights)`` through ``head``. Tied: the hidden
    states are made from the table's own rows, so its gradient is the sum
    of the head's and the gather's."""
    tokens = ops["tokens"]

    def f(states, table, weights):
        hidden = jnp.tanh(table[tokens] @ ops["mix"]) + states if tied \
            else states
        return head(hidden, table, tokens,
                    weights=weights if weighted else None, **kw)

    return f


def _vjp(f, ops, cotangent):
    args = (ops["states"], ops["table"], ops["weights"])
    value, pull = jax.vjp(f, *args)
    return value, pull(jnp.float32(cotangent))


def _whole(got, want):
    """Relative error of the whole gradient: all leaves as one vector."""
    num = sum(float(jnp.sum((g.astype(jnp.float32) - w) ** 2))
              for g, w in zip(got, want))
    den = sum(float(jnp.sum(w.astype(jnp.float32) ** 2)) for w in want)
    return (num / den) ** 0.5


@pytest.mark.parametrize("cotangent", [1.0, 3.7])
@pytest.mark.parametrize("compute_dtype,limit",
                         [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("n_chunks", [1, 3, 8])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["mean", "weights"])
@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
def test_value_and_gradients_are_the_old_loops_and_the_dense_losses(
        operands, tied, weighted, n_chunks, compute_dtype, limit, cotangent):
    kw = dict(tied=tied, weighted=weighted, n_chunks=n_chunks,
              compute_dtype=compute_dtype)
    got, got_g = _vjp(_loss_of(lm_loss_fused, operands, **kw), operands,
                      cotangent)
    for oracle in (scan_of_checkpoints, dense):
        want, want_g = _vjp(_loss_of(oracle, operands, **kw), operands,
                            cotangent)
        assert float(got) == pytest.approx(float(want), rel=limit)
        # states, table, and the weights' own where they were given
        leaves = slice(0, 3 if weighted else 2)
        assert _whole(got_g[leaves], want_g[leaves]) <= limit
        if weighted:
            assert _whole(got_g[2:], want_g[2:]) <= limit
        else:
            assert not float(jnp.abs(got_g[2]).max())
    assert all(g.dtype == jnp.float32 for g in got_g)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_cotangents_come_back_in_their_primals_dtypes(operands, dtype):
    """bf16 hidden states and a bf16 table take bf16 gradients; the
    table's is summed over the chunks in float32 first (the oracle's is a
    bf16 sum: the new one lies nearer the dense float32 gradient)."""
    tokens = operands["tokens"]
    hidden = operands["states"].astype(dtype)
    table = operands["table"].astype(dtype)

    def grads(head):
        return jax.grad(lambda h, w: head(h, w, tokens, n_chunks=8),
                        (0, 1))(hidden, table)

    got, old = grads(lm_loss_fused), grads(scan_of_checkpoints)
    want = jax.grad(lambda h, w: dense(h, w, tokens), (0, 1))(
        hidden.astype(jnp.float32), table.astype(jnp.float32))
    assert [g.dtype for g in got] == [dtype, dtype]
    assert _whole(got, want) <= 1e-2
    assert _whole(got, want) <= _whole(old, want) * 1.05


def _primitives(jaxpr, inside_scan=False):
    """``(primitive name, inside a scan's body)`` of every equation of a
    jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, inside_scan
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(
                        sub, inside_scan or eqn.primitive.name == "scan")


@pytest.mark.parametrize("weighted", [False, True], ids=["mean", "weights"])
def test_three_matmuls_a_chunk_and_none_rematerialised(operands, weighted):
    f = _loss_of(lm_loss_fused, operands, tied=False, weighted=weighted,
                 n_chunks=4)
    args = (operands["states"], operands["table"], operands["weights"])
    grad = list(_primitives(
        jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(*args).jaxpr))
    # one loop, its body traced once: logits, d hidden, d table
    assert [p for p in grad if p[0] == "dot_general"] \
        == [("dot_general", True)] * 3
    assert [p for p in grad if p[0] == "scan"] == [("scan", False)]
    assert not any("checkpoint" in name or "remat" in name
                   for name, _ in grad)
    # the oracle: four, and a checkpoint a chunk
    old = list(_primitives(jax.make_jaxpr(jax.grad(_loss_of(
        scan_of_checkpoints, operands, tied=False, weighted=weighted,
        n_chunks=4), (0, 1, 2)))(*args).jaxpr))
    assert sum(name == "dot_general" for name, _ in old) == 4
    assert any("checkpoint" in name or "remat" in name for name, _ in old)


def test_the_primal_makes_the_loss_alone(operands):
    f = _loss_of(lm_loss_fused, operands, tied=False, weighted=True,
                 n_chunks=4)
    args = (operands["states"], operands["table"], operands["weights"])
    primal = list(_primitives(jax.make_jaxpr(f)(*args).jaxpr))
    assert [p for p in primal if p[0] == "dot_general"] \
        == [("dot_general", True)]
    assert float(f(*args)) == pytest.approx(float(_loss_of(
        dense, operands, tied=False, weighted=True)(*args)), rel=1e-2)


def test_the_gauge_says_whether_the_gradient_was_made_in_the_forward(
        operands):
    f = _loss_of(lm_loss_fused, operands, tied=False, weighted=False)
    args = (operands["states"], operands["table"], operands["weights"])
    gauge = registry().gauge(train_path.LM_HEAD_GRAD_IN_FORWARD)
    jax.make_jaxpr(jax.grad(f))(*args)
    assert gauge.value() == 1.0
    jax.make_jaxpr(f)(*args)
    assert gauge.value() == 0.0
    # differentiated after it was traced (a jit inside the gradient): the
    # forward rule is traced last
    jax.make_jaxpr(jax.grad(jax.jit(f)))(*args)
    assert gauge.value() == 1.0
    assert train_path.LM_HEAD_GRAD_IN_FORWARD in registry().exposition()


@pytest.mark.parametrize("weighted", [False, True], ids=["mean", "weights"])
def test_under_an_outer_checkpoint_and_a_jit_with_traced_tokens(
        operands, weighted):
    def through(head):
        def f(states, table, weights, tokens):
            return head(states, table, tokens, n_chunks=3,
                        compute_dtype=jnp.float32,
                        weights=weights if weighted else None)
        return f

    args = (operands["states"], operands["table"], operands["weights"],
            operands["tokens"])
    want = jax.value_and_grad(through(scan_of_checkpoints), (0, 1, 2))(*args)
    for wrap in (jax.checkpoint, jax.jit,
                 lambda f: jax.jit(jax.checkpoint(f))):
        got = jax.jit(jax.value_and_grad(wrap(through(lm_loss_fused)),
                                         (0, 1, 2)))(*args)
        assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
        assert _whole(got[1], want[1]) <= 1e-5


MOE_TINY = dict(
    MODEL_CONFIGS["olmoe-1b-7b"], num_hidden_layers=2, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, vocab_size=128,
    max_position_embeddings=32)
LOOP_TINY = dict(
    MODEL_CONFIGS["ouro-2.6b"], num_hidden_layers=2, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=96, vocab_size=128, max_position_embeddings=32,
    total_ut_steps=3)


@pytest.mark.parametrize("config,loss_fn", [(MOE_TINY, lm_loss_moe),
                                            (LOOP_TINY, lm_loss_looped)],
                         ids=["lm_loss_moe", "lm_loss_looped"])
def test_a_models_gradient_is_the_oracles(config, loss_fn, monkeypatch):
    """Untied heads, the looped one with weights that carry the exit
    gate's gradient and a cotangent of ``R``."""
    model = lm_from_config(config, compute_dtype=jnp.float32,
                           return_hidden=True)
    tokens = jax.random.randint(jax.random.key(7), (2, 32), 0, 128)
    params = model.init(jax.random.key(8), tokens)["params"]

    def value_and_grad():
        return jax.value_and_grad(
            lambda p: loss_fn(model, p, tokens, n_chunks=3)[0])(params)

    got = value_and_grad()
    monkeypatch.setattr(transformer, "lm_loss_fused", scan_of_checkpoints)
    want = value_and_grad()
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    got_g, want_g = jax.tree.leaves(got[1]), jax.tree.leaves(want[1])
    assert _whole(got_g, want_g) <= 1e-5
    assert max(_whole([g], [w]) for g, w in zip(got_g, want_g)) <= 1e-4
