"""The looped model (ISSUE 30, Ouro's LoopLM): one set of blocks applied
several times, the norm after each sub-layer, the exit gate, the fused
head with per-row weights and the expected loss over the exits, against
the plain reference the benchmark holds the system to
(``benchmark/reference/loop_lm.py``), at a tiny size in float32 on the
CPU (kernels interpreted)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import (
    MODEL_CONFIGS,
    Architecture,
    TransformerLM,
    beam_search,
    generate,
    head_table,
    init_cache,
    lm_from_config,
    lm_loss,
    lm_loss_fused,
    lm_loss_looped,
    lm_loss_moe,
)
from chainermn_tpu.models import transformer
from chainermn_tpu.observability import train_path
from chainermn_tpu.ops.flash_attention import flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/loop_lm.py", "reference_loop_lm")


#: the tiny preset: 2 layers, 3 passes, d 64, 4 heads of 16, gated SiLU of
#: 96, T 32, vocabulary 128; the reference reads the same dict
L, R, T, V = 2, 3, 32, 128
TINY = dict(
    MODEL_CONFIGS["ouro-2.6b"], num_hidden_layers=L, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=96, vocab_size=V, max_position_embeddings=T,
    total_ut_steps=R, assumed={"exit_entropy_beta": 0.1},
)


def _model(config=TINY, **kw):
    return lm_from_config(config, compute_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def tiny():
    tokens = jax.random.randint(jax.random.key(0), (2, T), 0, V)
    params = _model().init(jax.random.key(1), tokens)["params"]
    return params, tokens


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _system_loss(params, tokens, config=TINY, beta=0.1, **kw):
    return lm_loss_looped(_model(config, return_hidden=True, **kw), params,
                          tokens, n_chunks=2, beta=beta)


def _worst(grads, want_grads):
    return max(_rel(g, w) for g, w in zip(
        jax.tree.leaves(grads), jax.tree.leaves(want_grads)))


# -- the system against the reference ------------------------------------

def test_logits_are_the_last_passs_and_match_the_reference(tiny, ref):
    params, tokens = tiny
    got = _model().apply({"params": params}, tokens)
    want = _highest(ref.logits, params, tokens, TINY)
    assert got.shape == (2, T, V)
    assert _rel(got, want) < 1e-5


def test_loss_and_every_gradient_leaf_match_the_reference(tiny, ref):
    params, tokens = tiny
    (loss, metrics), grads = jax.value_and_grad(
        _system_loss, has_aux=True)(params, tokens)
    want, want_grads = _highest(
        jax.value_and_grad(lambda p: ref.loss(p, (), tokens, TINY)), params)
    assert abs(float(loss) - float(want)) / float(want) < 1e-5
    flat = jax.tree_util.tree_flatten_with_path(want_grads)[0]
    assert len(flat) == len(jax.tree.leaves(grads)) == L * 9 + 5
    for (path, w), g in zip(flat, jax.tree.leaves(grads)):
        assert _rel(g, w) < 1e-5, jax.tree_util.keystr(path)
    masses = [float(metrics[f"loop/exit_mass_{t}"]) for t in (1, 2, 3)]
    assert sum(masses) == pytest.approx(1.0, abs=1e-6)
    assert float(metrics["loop/expected_pass"]) == pytest.approx(
        sum(t * m for t, m in zip((1, 2, 3), masses)), rel=1e-6)
    assert 0.0 < float(metrics["loop/exit_entropy"]) < np.log(3)


def _constant_weights(real):
    def fused(*a, weights=None, **kw):
        return real(*a, weights=jax.lax.stop_gradient(weights), **kw)
    return fused


#: what the comparison must catch: the system changed alone, or held
#: against the reference with an equation changed (``controls``)
MUTATIONS = {
    "three_passes_for_four": dict(controls=dict(passes=R - 1)),
    "one_pass_more": dict(config={**TINY, "total_ut_steps": R + 1}),
    "norm_between_passes_left_out": dict(
        controls=dict(norm_between_passes=False)),
    "entropy_term_left_out": dict(beta=0.0),
    "norm_after_a_sublayer_left_out": dict(
        controls=dict(sublayer_norms=False)),
    "gates_gradient_stopped": dict(patch=_constant_weights),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_the_comparison_catches(name, tiny, ref, monkeypatch):
    """Each departure from the equations moves the loss or a gradient
    leaf far past the 1e-5 the faithful system keeps to."""
    params, tokens = tiny
    m = MUTATIONS[name]
    if "patch" in m:
        monkeypatch.setattr(transformer, "lm_loss_fused",
                            m["patch"](transformer.lm_loss_fused))
    (loss, _), grads = jax.value_and_grad(
        lambda p, t: _system_loss(p, t, m.get("config", TINY),
                                  m.get("beta", 0.1)),
        has_aux=True)(params, tokens)
    want, want_grads = _highest(jax.value_and_grad(lambda p: ref.loss(
        p, (), tokens, TINY, **m.get("controls", {}))), params)
    loss_err = abs(float(loss) - float(want)) / float(want)
    assert max(_worst(grads, want_grads), loss_err) > 1e-3


def test_unshared_copies_gradients_add_up_to_the_looped_models(tiny, ref):
    """A model of ``R * L`` blocks with parameters of their own, set to
    the shared values: the gradient of copy ``t`` of block ``i`` is what
    pass ``t`` alone contributes, and the ``R`` copies' gradients add up
    to the looped model's gradient for block ``i``."""
    params, tokens = tiny
    rest = {k: v for k, v in params.items() if not k.startswith("block_")}
    copies = [[params[f"block_{i}"] for i in range(L)] for _ in range(R)]

    def unshared_loss(copies, rest):
        x = rest["tok_emb"]["embedding"][tokens]
        exits = []
        for blocks in copies:
            for p in blocks:
                x = ref.block(x, p, TINY)
            x = ref.rms_norm(x, rest["RMSNorm_0"], TINY["rms_norm_eps"])
            exits.append(x)
        h = jnp.stack(exits)
        gate = rest["exit_gate"]
        p = ref.exit_distribution(
            (h @ gate["kernel"])[..., 0] + gate["bias"])[:, :, :-1]
        logp = jax.nn.log_softmax(
            h[:, :, :-1] @ rest["lm_head"]["embedding"].T, axis=-1)
        ce = -jnp.take_along_axis(
            logp, jnp.broadcast_to(tokens[:, 1:, None], (R, 2, T - 1, 1)),
            axis=-1)[..., 0]
        return ((p * ce).sum(0) + 0.1 * (p * jnp.log(p)).sum(0)).mean()

    per_copy, rest_grads = _highest(
        jax.grad(unshared_loss, (0, 1)), copies, rest)
    looped = jax.grad(lambda p: _system_loss(p, tokens)[0])(params)
    for i in range(L):
        summed = jax.tree.map(lambda *g: sum(g),
                              *[per_copy[t][i] for t in range(R)])
        for s, g in zip(jax.tree.leaves(summed),
                        jax.tree.leaves(looped[f"block_{i}"])):
            assert _rel(g, s) < 1e-5
        # and no copy's share is the whole of it
        assert _rel(per_copy[0][i]["qkv"]["kernel"],
                    looped[f"block_{i}"]["qkv"]["kernel"]) > 0.1
    for k in rest:
        for s, g in zip(jax.tree.leaves(rest_grads[k]),
                        jax.tree.leaves(looped[k])):
            assert _rel(g, s) < 1e-5


@pytest.mark.parametrize("policy", ["dots", "nothing"])
def test_remat_of_shared_blocks_changes_nothing(policy, tiny):
    """One block's parameters read by ``R`` rematerialised applications,
    with the flash kernel (interpreted) and the names it keeps under
    ``dots``: loss and gradients are those without remat."""
    params, tokens = tiny

    def attn(q, k, v, *, causal, scale):
        return flash_attention(q, k, v, causal=causal, scale=scale)

    plain = jax.value_and_grad(lambda p: _system_loss(
        p, tokens, attention_fn=attn)[0])(params)
    remat = jax.value_and_grad(lambda p: _system_loss(
        p, tokens, attention_fn=attn, remat=True,
        remat_policy=policy)[0])(params)
    assert float(plain[0]) == pytest.approx(float(remat[0]), rel=1e-6)
    assert _worst(remat[1], plain[1]) < 1e-5


# -- one pass and no gate is today's model ----------------------------------

#: losses and squared gradient norms of the parent commit (0de82aa) on a
#: seed, float32 on the CPU, from a checkout of it. The loss holds to the
#: bit; the gradient to float32 rounding since ISSUE 31, whose fused head
#: forms ``softmax - onehot`` itself, the row count inside it: the same
#: float32 operations in another order than autodiff's
PARENT = {
    "gpt2": ({"model_type": "gpt2", "n_layer": 2, "n_embd": 32, "n_head": 2,
              "n_inner": 64, "n_positions": 16, "vocab_size": 96},
             "0x1.4286560000000p+2", "0x1.292c6e6580000p+4"),
    "olmoe": (dict(MODEL_CONFIGS["olmoe-1b-7b"], num_hidden_layers=2,
                   hidden_size=64, num_attention_heads=4,
                   num_key_value_heads=4, intermediate_size=32,
                   num_experts=8, num_experts_per_tok=2, vocab_size=128,
                   max_position_embeddings=32),
              "0x1.4cbd660000000p+2", "0x1.e2c1548720000p+2"),
}


@pytest.mark.parametrize("name", sorted(PARENT))
def test_one_pass_and_no_gate_is_the_parents_loss_to_the_bit(name):
    config, parent_loss, parent_grad_sq = PARENT[name]
    model = lm_from_config(config, compute_dtype=jnp.float32,
                           return_hidden=True)
    assert (model.total_ut_steps, model.looped) == (1, False)
    t = config.get("n_positions") or config["max_position_embeddings"]
    tokens = jax.random.randint(jax.random.key(40), (2, t), 0,
                                config["vocab_size"])
    params = model.init(jax.random.key(41), tokens)["params"]
    assert "exit_gate" not in params
    assert "attn_out_norm" not in params["block_0"]

    def loss_fn(p, weights=None):
        if model.arch.n_experts:
            return lm_loss_moe(model, p, tokens, n_chunks=2)[0]
        return lm_loss_fused(
            model.apply({"params": p}, tokens), head_table(p, model.arch),
            tokens, n_chunks=2, compute_dtype=jnp.float32, weights=weights)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    assert float(loss).hex() == parent_loss
    assert float(sum(float(jnp.sum(g ** 2)) for g in jax.tree.leaves(
        grads))) == pytest.approx(float.fromhex(parent_grad_sq), rel=1e-6)
    if not model.arch.n_experts:
        # the mask is the case of constant weights
        ones = jnp.ones((2, t - 1), jnp.float32)
        assert float(loss_fn(params, ones)).hex() == parent_loss


# -- the fused head with weights ---------------------------------------------

@pytest.mark.parametrize("n_chunks", [1, 3, 4])
def test_fused_head_with_weights_against_the_dense_loss(n_chunks):
    """Values and gradients, the gradient with respect to the weights
    among them (each row's cross-entropy over the number of rows)."""
    B, t, D, vocab = 3, 9, 16, 40
    k = jax.random.split(jax.random.key(5), 4)
    hidden = jax.random.normal(k[0], (B, t, D))
    table = jax.random.normal(k[1], (vocab, D)) * 0.3
    tokens = jax.random.randint(k[2], (B, t), 0, vocab)
    weights = jax.random.uniform(k[3], (B, t - 1), minval=0.1, maxval=2.0)

    def fused(h, tab, w):
        return lm_loss_fused(h, tab, tokens, n_chunks=n_chunks,
                             compute_dtype=jnp.float32, weights=w)

    def dense(h, tab, w):
        # lm_loss's mask is tokens-shaped and its mean is over the mask
        mask = jnp.pad(w, ((0, 0), (1, 0)))
        return lm_loss(h @ tab.T, tokens, mask) * w.sum() / w.size

    got, got_g = jax.value_and_grad(fused, (0, 1, 2))(hidden, table, weights)
    want, want_g = jax.value_and_grad(dense, (0, 1, 2))(
        hidden, table, weights)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, w in zip(got_g, want_g):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-6)
    ce = -jnp.take_along_axis(jax.nn.log_softmax(
        hidden[:, :-1] @ table.T), tokens[:, 1:, None], axis=-1)[..., 0]
    np.testing.assert_allclose(got_g[2], ce / ce.size, rtol=1e-4)


# -- the model description -----------------------------------------------------

def test_the_description_reads_ouros_config_json():
    arch = Architecture.from_config(MODEL_CONFIGS["ouro-2.6b"])
    assert arch == Architecture(
        norm="rmsnorm", norm_eps=1e-6, ffn="gated_silu", positions="rope",
        rope_base=1e6, tied_head=False, post_norm=True, exit_gate=True)
    model = lm_from_config(MODEL_CONFIGS["ouro-2.6b"], num_layers=6)
    assert (model.num_layers, model.total_ut_steps, model.d_model,
            model.num_heads, model.d_ff, model.vocab_size, model.max_len) \
        == (6, 4, 2048, 16, 5632, 49152, 65536)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    # L blocks, never R * L: 6 x 51.38M + 201.3M + norms and the gate
    assert sorted(k for k in shapes if k.startswith("block_")) == \
        [f"block_{i}" for i in range(6)]
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 509_661_185


def test_the_tiny_tree_holds_l_blocks_four_norms_each_and_one_gate(tiny):
    params, _ = tiny
    assert sorted(params) == ["RMSNorm_0", "block_0", "block_1", "exit_gate",
                              "lm_head", "tok_emb"]
    assert sorted(params["block_0"]) == [
        "RMSNorm_0", "RMSNorm_1", "attn_out_norm", "ff_down", "ff_gate",
        "ff_up", "ffn_out_norm", "proj", "qkv"]
    assert params["exit_gate"]["kernel"].shape == (64, 1)
    assert params["exit_gate"]["bias"].shape == (1,)


@pytest.mark.parametrize("bad", [
    dict(use_sliding_window=True), dict(rope_scaling={"type": "yarn"}),
    dict(hidden_act="gelu"), dict(head_dim=64),
])
def test_an_ouro_config_the_block_cannot_express_is_refused(bad):
    with pytest.raises(ValueError, match="not built"):
        Architecture.from_config({**MODEL_CONFIGS["ouro-2.6b"], **bad})


def test_a_looped_model_with_experts_or_no_pass_is_refused():
    tokens = jnp.zeros((1, 8), jnp.int32)
    sizes = dict(vocab_size=32, num_layers=1, num_heads=2, d_model=16,
                 d_ff=32, max_len=8)
    with pytest.raises(ValueError, match="total_ut_steps"):
        TransformerLM(**sizes, total_ut_steps=0).init(
            jax.random.key(0), tokens)
    moe = Architecture(norm="rmsnorm", ffn="gated_silu", positions="rope",
                       n_experts=4, experts_per_token=2, expert_width=8)
    with pytest.raises(ValueError, match="looped model with experts"):
        TransformerLM(**sizes, arch=moe, total_ut_steps=2).init(
            jax.random.key(0), tokens)
    # several passes without a gate hand back the exits and no gate logits
    model = TransformerLM(**sizes, total_ut_steps=2, return_hidden=True)
    params = model.init(jax.random.key(0), tokens)["params"]
    hidden, gate_logits = model.apply({"params": params}, tokens)
    assert hidden.shape == (2, 1, 8, 16) and gate_logits is None
    with pytest.raises(ValueError, match="exit gate"):
        lm_loss_looped(model, params, tokens)


# -- decoding is refused, never a silent single pass -------------------------

def _serve(model, params, prompt):
    from chainermn_tpu.serving.engine import ServingEngine

    ServingEngine(model, params, num_slots=1)


DECODERS = {
    "generate": lambda m, p, prompt: generate(m, p, prompt, 8),
    "beam_search": lambda m, p, prompt: beam_search(m, p, prompt, 8, 2),
    "init_cache": lambda m, p, prompt: init_cache(m, p, 1),
    "serving_engine": _serve,
}


@pytest.mark.parametrize("entry", sorted(DECODERS))
def test_decoding_a_looped_model_is_refused_by_name(entry, tiny):
    params, tokens = tiny
    with pytest.raises(NotImplementedError, match="total_ut_steps"):
        DECODERS[entry](_model(), {"params": params}, tokens[:1, :4])


# -- what the loop publishes -----------------------------------------------

def test_scopes_and_gauge_of_a_traced_step(tiny):
    from chainermn_tpu.observability.metrics import registry

    params, tokens = tiny
    text = jax.jit(jax.grad(
        lambda p: _system_loss(p, tokens)[0])).lower(params).as_text(
            debug_info=True)
    for scope in [train_path.LOOP_STACK, train_path.EXIT_GATE] + [
            f"{train_path.LOOP_STACK}/{train_path.pass_scope(t)}"
            for t in range(R)]:
        assert f"/{scope}" in text, scope
    assert f"({train_path.LM_HEAD})" in text  # jvp(lm_head): one head call
    assert f"/{train_path.pass_scope(R)}" not in text
    snap = registry().snapshot()
    assert snap[train_path.LOOP_PASSES]["values"][0]["value"] == R
    # a plain model's step carries neither scope
    config = PARENT["gpt2"][0]
    model = lm_from_config(config, return_hidden=True)
    toks = jnp.zeros((1, 16), jnp.int32)
    plain = jax.jit(lambda p: model.apply({"params": p}, toks)).lower(
        model.init(jax.random.key(0), toks)["params"]).as_text(
            debug_info=True)
    assert train_path.LOOP_STACK not in plain
    assert train_path.EXIT_GATE not in plain


def test_the_controls_tool_takes_every_reading_at_the_tiny_size(
        tmp_path, monkeypatch):
    """``tools/loop_controls.py`` (the readings the reference's limits lie
    between) on the benchmark tests' throw-away configuration: every row
    is there, the sound system is taken and every control is refused."""
    import json
    import sys

    # the path the tool's helper (tools/_controls.py) sets where this
    # process imports it first; an earlier file's tool may have, under a
    # path that is restored by now
    monkeypatch.setattr(sys, "path", [os.path.join(REPO, "benchmark"), REPO]
                        + list(sys.path))
    # and would turn the persistent compile cache on for this process
    from chainermn_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "")
    tool = _load("tools/loop_controls.py", "loop_controls")
    out = tmp_path / "controls.jsonl"
    assert tool.main(["--tiny", "--seeds", "11", "--out", str(out)]) == 0
    rows = {r["what"]: r for r in map(json.loads,
                                      out.read_text().splitlines()[1:])}
    assert sorted(rows) == sorted([
        "sound", "the gate's gradient stopped", "reference computed in bf16",
        "three passes for four", "the norm between passes left out",
        "the entropy term left out", "the norm after a sub-layer left out"])
    assert rows["sound"]["loop/exit_mass_1"] > 0
    assert transformer.lm_loss_fused.__name__ == "lm_loss_fused"  # restored
    for what, row in rows.items():
        assert np.isfinite(row["loss_rel_err"]), what
        # a precision's reading means nothing at this size (bf16 compute
        # of a 64-wide model): the equations' controls are refused
        if what not in ("sound", "reference computed in bf16"):
            assert row["refused"], what
