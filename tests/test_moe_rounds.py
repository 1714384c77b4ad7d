"""A share's expert section in rounds of a static row bound (ISSUE 48):
``parallel.moe.experts_in_rounds`` against the straight-line spelling it
replaces under a share (dispatch -> gated experts -> combine), bit for bit
where one round holds the rows, and against a loop over the experts under
``highest`` where a router deals the chip more than a round holds; on the
CPU with the kernels interpreted."""

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import (
    MODEL_CONFIGS,
    ROUTER_STATE,
    lm_from_config,
    lm_loss_moe,
)
from chainermn_tpu.observability import train_path
from chainermn_tpu.observability.metrics import registry
from chainermn_tpu.ops.flash_attention import flash_attention
from chainermn_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WHAT = ["forward", "d_tokens", "d_gate_up", "d_down", "d_gates"]


def _straight(x, w_gate_up, w_down, gates, routing):
    """The section as every layer spelled it before the rounds."""
    r = routing._replace(gates=gates)
    rows = moe.dispatch(x, r)
    return moe.combine(
        moe.gated_experts(rows, w_gate_up, w_down, r.group_sizes), r)


def _rounds(x, w_gate_up, w_down, gates, routing):
    return moe.experts_in_rounds(x, w_gate_up, w_down,
                                 routing._replace(gates=gates))


def _by_expert(x, w_gate_up, w_down, gates, routing, lo):
    """A loop over the held experts, every token through each."""
    width = w_down.shape[1]
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate_up.shape[0]):
        gate_up = x @ w_gate_up[e]
        y = (jax.nn.silu(gate_up[:, :width]) * gate_up[:, width:]) @ w_down[e]
        weight = jnp.where(routing.experts == lo + e, gates, 0.0).sum(-1)
        out = out + weight[:, None] * y
    return out


def _values(fn, x, w_gate_up, w_down, routing, *extra):
    """Forward and the four gradients of ``<fn(...), weight>``."""
    weight = jax.random.normal(jax.random.key(7), x.shape)

    def loss(x, a, b, g):
        out = fn(x, a, b, g, routing, *extra)
        return (out.astype(jnp.float32) * weight).sum()

    grads = jax.jit(jax.grad(loss, (0, 1, 2, 3)))(
        x, w_gate_up, w_down, routing.gates)
    out = jax.jit(lambda *a: fn(*a, routing, *extra))(
        x, w_gate_up, w_down, routing.gates)
    return dict(zip(WHAT, (out, *grads)))


def _weights(held, d, width):
    return (0.1 * jax.random.normal(jax.random.key(3), (held, d, 2 * width)),
            0.1 * jax.random.normal(jax.random.key(4), (held, width, d)))


# -- one round: the parent's values -----------------------------------------

#: tokens, k, experts, the range held, score, renormalised: the three
#: cells' routers at a small size (a round is 512 / 512 / 1024 rows of
#: 1024 / 2048 / 3072 and holds what a drawn router deals the chip)
FAMILIES = {
    "lfm2": (256, 4, 32, (0, 8), "sigmoid", True),
    "sdar": (256, 8, 128, (16, 32), "softmax", True),
    "dsv2lite": (512, 6, 64, (0, 8), "softmax", False),
}


@functools.lru_cache(maxsize=None)
def _one_round(family):
    tokens, k, n, held, score, renormalise = FAMILIES[family]
    d, width = 64, 32
    x = jax.random.normal(jax.random.key(1), (tokens, d)).astype(jnp.bfloat16)
    router = 0.5 * jax.random.normal(jax.random.key(2), (d, n))
    bias = 0.1 * jax.random.normal(jax.random.key(5), (n,)) \
        if score == "sigmoid" else None
    routing = moe.dropless_topk(x, router, k, renormalise, score=score,
                                select_bias=bias, held=held)
    w = _weights(held[1] - held[0], d, width)
    bound = moe.rows_bound(tokens * k, held[1] - held[0], n)
    assert bound < tokens * k and 0 < int(routing.rows_held) <= bound
    return (_values(_rounds, x, *w, routing),
            _values(_straight, x, *w, routing))


@pytest.mark.parametrize("what", WHAT)
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_one_round_is_the_straight_line_sections_values(family, what):
    """bf16 rows on float32 masters, as the cells train: the output, the
    tokens' gradient and both weights' are the straight-line section's bit
    for bit (the same products in the same tiles, the same sums); the
    gates' is summed in float32 where the weighted sum's transpose rounds
    it to bf16."""
    got, want = (side[what] for side in _one_round(family))
    assert got.dtype == want.dtype and got.shape == want.shape
    if what == "d_gates":
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got, want, atol=2 ** -7 * scale)
    else:
        assert jnp.array_equal(got, want)


# -- more than a round holds ------------------------------------------------

def _steered(tokens, n, d, choices):
    """Rows whose first ``n`` features decide the router's choice:
    ``choices`` is ``[(first token, one past the last, (experts...))]``."""
    x = jax.random.normal(jax.random.key(1), (tokens, d))
    lead = jnp.zeros((tokens, n))
    for start, stop, experts in choices:
        for rank, e in enumerate(experts):
            lead = lead.at[start:stop, e].set(4.0 - rank)
    router = jnp.zeros((d, n)).at[:n, :n].set(jnp.eye(n))
    return x.at[:, :n].set(lead), router


#: tokens, k, experts, held, who chooses what, the held experts' rows by
#: hand, rounds by hand (a round is 512 rows in all three)
OVERFLOWS = {
    # every row's experts are held: T k / R rounds
    "every_row_held": (1024, 2, 16, (3, 5), [(0, 1024, (3, 4))],
                       [1024, 1024], 4),
    # expert 3's 400 rows lie over the first round's edge at 512
    "a_group_straddles_a_rounds_edge": (
        512, 2, 8, (2, 4),
        [(0, 112, (2, 5)), (112, 300, (2, 3)), (300, 512, (3, 6))],
        [300, 400], 2),
    # 600 rows are no whole number of rounds: the last one's window ends
    # behind the rows
    "the_rows_fill_no_whole_round": (300, 2, 8, (0, 2), [(0, 300, (1, 0))],
                                     [300, 300], 2),
}


@functools.lru_cache(maxsize=None)
def _overflow(case):
    tokens, k, n, held, choices, sizes, rounds = OVERFLOWS[case]
    d, width = 32, 16
    x, router = _steered(tokens, n, d, choices)
    routing = moe.dropless_topk(x, router, k, False, held=held)
    w = _weights(held[1] - held[0], d, width)
    with jax.default_matmul_precision("highest"):
        want = _values(_by_expert, x, *w, routing, held[0])
    return routing, _values(_rounds, x, *w, routing), want


@pytest.mark.parametrize("what", WHAT + ["counters"])
@pytest.mark.parametrize("case", sorted(OVERFLOWS))
def test_rounds_beyond_the_first_drop_nothing(case, what):
    tokens, k, n, held, _, sizes, rounds = OVERFLOWS[case]
    routing, got, want = _overflow(case)
    bound = moe.rows_bound(tokens * k, held[1] - held[0], n)
    if what == "counters":
        assert bound == 512 and sum(sizes) > bound
        assert list(np.asarray(routing.group_sizes)) == sizes
        assert int(routing.rows_held) == sum(sizes)
        aux = moe.rounds_aux(routing, bound)
        assert float(aux["rounds"]) == rounds == -(-sum(sizes) // bound)
        # the last round's tiles behind the last group (a tile is the
        # round where a round is one)
        assert float(aux["tail_tiles"]) == 0.0
        assert float(moe.dropless_aux(routing, False)["dropped"]) == 0.0
        return
    np.testing.assert_allclose(got[what], want[what], rtol=1e-4, atol=1e-5)
    if what == "d_gates":  # a slot whose expert is absent weighs nothing
        absent = (routing.experts < held[0]) | (routing.experts >= held[1])
        assert float(jnp.abs(jnp.where(absent, got[what], 0.0)).max()) == 0.0


# -- the share's path at R = T k is the all-held path -------------------------

@functools.lru_cache(maxsize=None)
def _all_held():
    tokens, k, n, d, width = 256, 2, 8, 64, 32
    x = jax.random.normal(jax.random.key(1), (tokens, d)).astype(jnp.bfloat16)
    router = 0.5 * jax.random.normal(jax.random.key(2), (d, n))
    routing = moe.dropless_topk(x, router, k, True)
    assert moe.rows_bound(tokens * k, n, n) == tokens * k
    assert float(moe.rounds_aux(routing, tokens * k)["rounds"]) == 1.0
    w = _weights(n, d, width)
    return _values(_rounds, x, *w, routing), _values(_straight, x, *w, routing)


@pytest.mark.parametrize("what", WHAT)
def test_every_expert_held_is_one_round_of_all_the_rows(what):
    """One algorithm: handed every expert the rounds' bound is ``T k``,
    and the one round gives what the straight-line section (which such a
    layer keeps) gives."""
    got, want = (side[what] for side in _all_held())
    if what == "d_gates":
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got, want, atol=2 ** -7 * scale)
    else:
        assert jnp.array_equal(got, want)


# -- what an absent expert's rows hold reaches nothing ------------------------

@functools.lru_cache(maxsize=None)
def _poisoned():
    """Tokens 0..383 choose held experts, 384..1023 absent ones alone: 768
    held rows of 2048 in one round of 512... two: the second round's window
    holds 256 rows of absent experts (gathered, multiplied by nothing),
    the other 1,024 lie behind the rounds (never gathered)."""
    tokens, k, n, held, d, width = 1024, 2, 16, (3, 5), 32, 16
    x, router = _steered(tokens, n, d, [(0, 384, (3, 4)),
                                        (384, 1024, (7, 9))])
    routing = moe.dropless_topk(x, router, k, False, held=held)
    assert int(routing.rows_held) == 768
    assert float(moe.rounds_aux(routing, 512)["rounds"]) == 2.0
    bad = jnp.where(jnp.arange(tokens)[:, None] % 2, jnp.nan, jnp.inf)
    # the router has read the clean rows; the section is handed bad ones
    poisoned = jnp.where((jnp.arange(tokens) >= 384)[:, None], bad, x)
    w = _weights(2, d, width)
    return (_values(_rounds, poisoned, *w, routing),
            _values(_rounds, x, *w, routing))


@pytest.mark.parametrize("what", WHAT)
def test_nan_and_inf_in_absent_experts_rows_reach_nothing(what):
    got, clean = (side[what] for side in _poisoned())
    assert bool(jnp.isfinite(got).all())
    assert jnp.array_equal(got, clean)
    if what in ("forward", "d_tokens", "d_gates"):
        assert float(jnp.abs(got[384:]).max()) == 0.0


# -- through the model: the counters and the reference -------------------------

def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


#: tests/test_hybrid_lm.py's tiny LFM2 with 2 of 8 experts held: at 16
#: sequences of 64 a layer routes 2,048 rows in rounds of 1,024
TINY = dict(
    MODEL_CONFIGS["lfm2-8b-a1b"], num_hidden_layers=3, num_dense_layers=1,
    layer_types=["conv", "full_attention", "conv"],
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=96, moe_intermediate_size=32, num_experts=2,
    experts_published=8, experts_held_range=[2, 4], num_experts_per_tok=2,
    vocab_size=128, max_position_embeddings=64,
)


def _gauge(name):
    return registry().snapshot()[name]["values"][0]["value"]


@pytest.mark.parametrize("bias, rounds", [(0.0, 2), (1.0, 4)],
                         ids=["a_drawn_router", "every_row_held"])
def test_the_model_counts_its_rounds_and_matches_the_reference(bias, rounds):
    """Two expert layers. A drawn router deals the chip about a quarter
    of the rows: a round a layer. A selection bias of 1 on the two held
    experts (the scores are sigmoids) sends every row to them: two rounds
    a layer, nothing dropped, and the loss and every gradient leaf are the
    reference's."""
    ref = _load("benchmark/reference/hybrid_moe_lm.py",
                "reference_hybrid_moe_lm_rounds")
    model = lm_from_config(
        TINY, compute_dtype=jnp.float32, return_hidden=True,
        attention_fn=lambda q, k, v, *, causal, scale: flash_attention(
            q, k, v, causal=causal, scale=scale))
    tokens = jax.random.randint(jax.random.key(0), (16, 64), 0, 128)
    v = model.init(jax.random.key(1), tokens)
    params = v["params"]
    state = jax.tree.map(
        lambda b: b.at[2:4].set(bias), v[ROUTER_STATE])

    def loss(p):
        return lm_loss_moe(model, p, tokens, n_chunks=2,
                           load_balance_coef=0.0, z_loss_coef=0.0,
                           router_state=state)

    (got, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
    assert float(metrics[train_path.MOE_ROUNDS]) == rounds
    assert float(metrics["moe/dropped"]) == 0.0
    if bias:
        assert float(metrics["moe/rows_held"]) == 2 * 2048
    assert float(metrics[train_path.MOE_TAIL_TILES]) == \
        rounds * 2 - sum(-(-int(h) // 512) for h in [
            model.apply({"params": params, ROUTER_STATE: state}, tokens,
                        mutable=["moe_aux"])[1]["moe_aux"][f"block_{i}"][
                            "rows_held"][0] for i in (1, 2)])
    assert _gauge(train_path.MOE_ROWS_BOUND) == 1024.0
    assert _gauge(train_path.MOE_ROWS_PER_STEP) == 2048.0
    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(
            lambda p: ref.loss(p, state, tokens, TINY))(params)
    assert abs(float(got) - float(want)) / float(want) < 1e-5
    for (path, w), g in zip(
            jax.tree_util.tree_flatten_with_path(want_grads)[0],
            jax.tree.leaves(grads)):
        rel = float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w))
        assert rel < 2e-5, jax.tree_util.keystr(path)


def test_a_layer_that_holds_every_expert_sets_the_bound_to_its_rows():
    whole = {k: v for k, v in TINY.items()
             if k not in ("experts_published", "experts_held_range")}
    model = lm_from_config({**whole, "num_experts": 8},
                           compute_dtype=jnp.float32, return_hidden=True)
    tokens = jnp.zeros((2, 64), jnp.int32)
    v = jax.eval_shape(lambda: model.init(jax.random.key(1), tokens))
    _, metrics = jax.eval_shape(lambda p, s: lm_loss_moe(
        model, p, tokens, n_chunks=2, load_balance_coef=0.0,
        z_loss_coef=0.0, router_state=s), v["params"], v[ROUTER_STATE])
    assert train_path.MOE_ROUNDS in metrics
    assert _gauge(train_path.MOE_ROWS_BOUND) == 2 * 64 * 2.0
