"""What ``remat_policy='dots'`` keeps of the flash kernel: the attention
output and the log-sum-exp, by the names their wrapper gives them
(``train_path.FLASH_RESIDUALS``), so that the recomputation inside the
backward no longer calls the forward kernel. Interpreted kernels, tiny
shapes."""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import TransformerLM, lm_loss
from chainermn_tpu.models import transformer as transformer_mod
from chainermn_tpu.observability import train_path
from chainermn_tpu.ops import flash_attention  # the function, not its module

flash_mod = importlib.import_module("chainermn_tpu.ops.flash_attention")

LAYERS = 2
VOCAB = 61


def _attn(q, k, v, *, causal, scale):
    return flash_attention(q, k, v, causal=causal, scale=scale,
                           block_q=16, block_k=16, interpret=True)


def _lm(**kw):
    return TransformerLM(
        vocab_size=VOCAB, num_layers=LAYERS, num_heads=2, d_model=32,
        d_ff=64, max_len=32, compute_dtype=jnp.float32, attention_fn=_attn,
        **kw)


def _tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, VOCAB)


def _params():
    return _lm().init(jax.random.PRNGKey(0), _tokens())["params"]


def _loss_of(model):
    def loss(params, tokens):
        return lm_loss(model.apply({"params": params}, tokens), tokens)
    return loss


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in value if isinstance(value, (list, tuple)) else (value,):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def kernel_calls(jaxpr, name: str) -> int:
    """``pallas_call``s called ``name`` anywhere in ``jaxpr`` (a kernel's
    own body is not looked into)."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += eqn.params["name"] == name
        else:
            n += sum(kernel_calls(j, name) for j in _sub_jaxprs(eqn))
    return n


def _forward_calls(fn, *args) -> int:
    return kernel_calls(jax.make_jaxpr(fn)(*args).jaxpr,
                        train_path.FLASH_FWD)


def _assert_trees_equal(got, want):
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        got, want)


@pytest.mark.parametrize("remat, calls_a_layer", [
    (dict(remat=True, remat_policy="dots"), 1),
    (dict(remat=True, remat_policy="nothing"), 2),
    (dict(remat=False), 1),
])
def test_flash_forward_calls_in_the_gradient(remat, calls_a_layer):
    """(a) 'dots' keeps the kernel's results and its forward runs once a
    layer, as without remat; 'nothing' recomputes it as before."""
    jaxpr = jax.make_jaxpr(jax.grad(_loss_of(_lm(**remat))))(
        _params(), _tokens()).jaxpr
    assert kernel_calls(jaxpr, train_path.FLASH_FWD) \
        == calls_a_layer * LAYERS
    # the backward kernels are none of remat's business
    assert kernel_calls(jaxpr, train_path.FLASH_BWD_DQ) == LAYERS
    assert kernel_calls(jaxpr, train_path.FLASH_BWD_DKV) == LAYERS


def _value_and_grad(**remat):
    return jax.jit(jax.value_and_grad(_loss_of(_lm(**remat))))(
        _params(), _tokens())


def test_dots_is_bit_identical_to_the_policy_without_names(monkeypatch):
    """(b) saved and recomputed values are the same bits, so loss and
    every gradient leaf are; against no remat at all, float32 rounding
    (XLA fuses the two programs differently)."""
    new = _value_and_grad(remat=True, remat_policy="dots")
    # 'dots' as it was before the kernel's results had names: XLA's dots
    # alone, which does recompute the kernel
    monkeypatch.setitem(
        transformer_mod._REMAT_POLICIES, "dots",
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    assert _forward_calls(
        jax.grad(_loss_of(_lm(remat=True, remat_policy="dots"))),
        _params(), _tokens()) == 2 * LAYERS
    old = _value_and_grad(remat=True, remat_policy="dots")
    _assert_trees_equal(new, old)
    plain = _value_and_grad(remat=False)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        new, plain)


def _gradient_text_and_jaxpr():
    # `_flash_call` keeps its traces: a name taken away has to be traced
    jax.clear_caches()
    grad = jax.jit(jax.grad(_loss_of(_lm(remat=False))))
    args = _params(), _tokens()
    # a private function's name ends in a running number of the lowering,
    # which counts a name's (empty) lowering too
    text = re.sub(r"(@\w+?)_\d+\b", r"\1", grad.lower(*args).as_text())
    return text, str(jax.make_jaxpr(grad)(*args))


def test_names_change_nothing_without_remat(monkeypatch):
    """(c) a name is an identity outside ``jax.checkpoint``: the model's
    gradient lowers to the same text with the names and without them."""
    named, named_jaxpr = _gradient_text_and_jaxpr()
    try:
        with monkeypatch.context() as patch:
            patch.setattr(flash_mod, "checkpoint_name", lambda x, name: x)
            bare, bare_jaxpr = _gradient_text_and_jaxpr()
    finally:
        jax.clear_caches()  # no later test meets a trace without names
    # the comparison is between a program with the names and one without
    assert f"name={train_path.FLASH_OUT}" in named_jaxpr
    assert f"name={train_path.FLASH_OUT}" not in bare_jaxpr
    assert named == bare


def _op_cases():
    B, T, H, D = 2, 32, 2, 16
    keys = jax.random.split(jax.random.PRNGKey(7), 7)
    q, k, v = (jax.random.normal(key, (B, T, H, D), jnp.float32)
               for key in keys[:3])
    kv_heads = (jax.random.normal(key, (B, T, 1, D), jnp.float32)
                for key in keys[3:5])
    seg = jnp.cumsum(jax.random.bernoulli(keys[5], 0.1, (B, T)),
                     axis=1, dtype=jnp.int32)
    bias = 0.1 * jax.random.normal(keys[6], (1, H, T, T), jnp.float32)
    common = dict(causal=True, block_q=16, block_k=16, interpret=True)
    return {
        "plain": ((q, k, v), lambda q_, k_, v_: flash_attention(
            q_, k_, v_, **common)),
        "segment_ids": ((q, k, v), lambda q_, k_, v_: flash_attention(
            q_, k_, v_, segment_ids=seg, **common)),
        "trained_bias": ((q, k, v, bias),
                         lambda q_, k_, v_, b_: flash_attention(
                             q_, k_, v_, bias=b_, bias_grad=True, **common)),
        "window_gqa": ((q, *kv_heads), lambda q_, k_, v_: flash_attention(
            q_, k_, v_, window=8, **common)),
    }


@pytest.mark.parametrize("case", ["plain", "segment_ids", "trained_bias",
                                  "window_gqa"])
def test_op_under_checkpoint_keeps_every_residual(case):
    """(d) the op alone under ``jax.checkpoint`` with the block's policy:
    one forward call in the gradient, the same bits as recomputing it, so
    every residual the backward takes from the kernel is covered."""
    args, attn = _op_cases()[case]

    def grads(policy):
        fn = jax.checkpoint(attn, policy=policy)
        return jax.grad(lambda *a: (fn(*a) ** 2).sum(),
                        argnums=tuple(range(len(args))))

    kept = grads(transformer_mod._REMAT_POLICIES["dots"])
    recomputed = grads(None)
    assert _forward_calls(kept, *args) == 1
    assert _forward_calls(recomputed, *args) == 2
    _assert_trees_equal(jax.jit(kept)(*args), jax.jit(recomputed)(*args))


def test_names_are_the_policys():
    """The names the wrapper gives are the ones the policy saves, spelled
    once: both appear in the forward rule's jaxpr."""
    args, attn = _op_cases()["plain"]
    text = str(jax.make_jaxpr(jax.grad(lambda *a: attn(*a).sum()))(*args))
    for name in train_path.FLASH_RESIDUALS:
        assert f"name={name}" in text


# -- the form in which the kernels pass their row statistics ---------------

_FLASH_KERNELS = (train_path.FLASH_FWD, train_path.FLASH_BWD_DQ,
                  train_path.FLASH_BWD_DKV)


def _all_jaxprs(jaxpr):
    """``jaxpr`` and every jaxpr nested in its equations, kernel bodies
    left out."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "pallas_call":
            for inner in _sub_jaxprs(eqn):
                yield from _all_jaxprs(inner)


def _source(jaxpr, var):
    """``(var, eqn)`` behind ``var`` in ``jaxpr`` with the reshapes that
    move no element walked back through; ``eqn`` None: an input of
    ``jaxpr``."""
    made_by = {out: eqn for eqn in jaxpr.eqns for out in eqn.outvars}
    while var in made_by and made_by[var].primitive.name == "reshape":
        var = made_by[var].invars[0]
    return var, made_by.get(var)


def _gradient_jaxpr(which):
    if which == "remat_block":
        return jax.make_jaxpr(jax.grad(_loss_of(
            _lm(remat=True, remat_policy="dots"))))(_params(),
                                                    _tokens()).jaxpr
    args, attn = _op_cases()["plain"]
    return jax.make_jaxpr(jax.grad(
        lambda *a: (attn(*a) ** 2).sum(), argnums=(0, 1, 2)))(*args).jaxpr


@pytest.mark.parametrize("which", ["remat_block", "plain"])
def test_flash_kernels_pass_their_row_statistics_lane_dense(which):
    """No operand or result of a flash kernel has a unit minor dimension
    (such an array sits in 128-lane tiles, 128 times its bytes), and what
    the backward kernels take as log-sum-exp is padded and broadcast by
    nobody: the residual as it came. ``delta`` is no array at all: the
    backward kernels take the forward's output, the other residual, and
    sum its rows against ``dO`` themselves."""
    seen = {name: 0 for name in _FLASH_KERNELS}
    for jaxpr in _all_jaxprs(_gradient_jaxpr(which)):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "pallas_call" \
                    or eqn.params["name"] not in seen:
                continue
            seen[eqn.params["name"]] += 1
            for var in (*eqn.invars, *eqn.outvars):
                assert var.aval.shape[-1] != 1, (eqn.params["name"],
                                                 var.aval)
            if eqn.params["name"] == train_path.FLASH_FWD:
                continue
            q, _, _, out, do, lse = eqn.invars[:6]
            assert out.aval == do.aval == q.aval
            # [B * H, T, D] here: heads of 16 take the transposed form
            N, T, _ = q.aval.shape
            assert lse.aval.shape[2:] == (1, T)
            assert lse.aval.size == N * T
            for residual in (out, lse):
                _, made = _source(jaxpr, residual)
                assert made is None or made.primitive.name == "pallas_call", \
                    made
    assert all(seen.values()), seen


def test_flash_lse_residual_is_the_forward_kernels_own_result():
    """What the policy saves under ``flash_lse`` is the array the forward
    kernel wrote: no squeeze between them that XLA has to run."""
    named = 0
    for jaxpr in _all_jaxprs(_gradient_jaxpr("remat_block")):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "name" \
                    and eqn.params["name"] == train_path.FLASH_LSE:
                named += 1
                _, made = _source(jaxpr, eqn.invars[0])
                assert made.primitive.name == "pallas_call"
                assert made.params["name"] == train_path.FLASH_FWD
    assert named >= LAYERS


# -- the layout the kernels read and write -----------------------------------

@pytest.mark.parametrize("heads, width, moved", [
    (4, 64, False),   # two heads a 128-lane block of [B, T, H*D]
    (2, 128, False),  # one head a block of columns
    (2, 96, True),    # no lane-tile fit: [B*H, T, D], transposed outside
])
def test_no_op_moves_or_casts_an_operand_round_the_kernels(heads, width,
                                                           moved):
    """In the projections' layout the op and its gradient hold, outside
    the kernels, no ``transpose`` and no ``convert_element_type`` of an
    array as large as an operand: ``[B, T, H, D]`` reaches the kernels
    through reshapes that move nothing, and dq, dk, dv leave them in the
    operands' dtype. A head width that fits no lane tile is transposed
    by the wrapper as before (and this test sees it)."""
    B, T = 2, 256
    x = jax.ShapeDtypeStruct((B, T, heads, width), jnp.bfloat16)

    def op_and_gradient(q, k, v, g):
        out, vjp = jax.vjp(
            lambda *a: flash_attention(*a, causal=True, interpret=True),
            q, k, v)
        return out, vjp(g)

    found = set()
    for jaxpr in _all_jaxprs(jax.make_jaxpr(op_and_gradient)(x, x, x,
                                                              x).jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("transpose", "convert_element_type") \
                    and eqn.invars[0].aval.size == B * T * heads * width:
                found.add(eqn.primitive.name)
    assert found == ({"transpose"} if moved else set())
