"""DeepSeek-V2's layers as its Lite model spells them (ISSUE 47): latent
attention with keys wider than the values through the flash kernels, a
rope key shared by the heads under YaRN, a shared expert beside the routed
ones and the per-sequence balance loss, against the plain reference the
benchmark holds the system to (``benchmark/reference/latent_moe_lm.py``),
at a tiny size in float32 on the CPU (kernels interpreted)."""

import functools
import importlib
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import (
    MODEL_CONFIGS,
    Architecture,
    beam_search,
    generate,
    init_cache,
    lm_from_config,
    lm_loss_moe,
)
from chainermn_tpu.models import transformer
from chainermn_tpu.models.transformer import TransformerBlock, Yarn
from chainermn_tpu.observability import train_path
from chainermn_tpu.observability.metrics import registry
from chainermn_tpu.ops.flash_attention import flash_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PUBLISHED = MODEL_CONFIGS["deepseek-v2-lite"]
#: the tiny preset: a dense layer and an expert layer at d 64, 4 heads
#: with keys of 16 + 8 and values of 16 through a latent of 32, dense 96,
#: 8 experts of width 16 of which 4 are held, top-3, 2 shared, T 64,
#: vocabulary 128; YaRN as published but for an original length of 16, so
#: that 64 positions lie past it and the blend is in use; the reference
#: reads the same dict
TINY = dict(
    PUBLISHED, num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
    num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=16, n_routed_experts=4, experts_published=8,
    experts_held_range=[2, 6], num_experts_per_tok=3, vocab_size=128,
    max_position_embeddings=64,
    rope_scaling={**PUBLISHED["rope_scaling"],
                  "original_max_position_embeddings": 16},
    assumed={"aux_loss_alpha": 0.01},
)
T = 64
ALPHA = TINY["assumed"]["aux_loss_alpha"]


def _whole(config):
    """The same model with every expert held."""
    whole = {k: v for k, v in config.items()
             if k not in ("experts_published", "experts_held_range")}
    return {**whole, "n_routed_experts": config["experts_published"]}


def _attn(q, k, v, *, causal, scale):
    return flash_attention(q, k, v, causal=causal, scale=scale)


def _model(config=TINY, **kw):
    kw.setdefault("return_hidden", True)
    kw.setdefault("attention_fn", _attn)
    return lm_from_config(config, compute_dtype=jnp.float32, **kw)


def _init(config=TINY, seed=1):
    """Parameters drawn leaf by leaf (no program is compiled for them):
    matrices at ``fan_in ** -0.5``, every router far from uniform (the
    chosen experts then differ by token and sequence), the norms' scales
    off one."""
    tokens = jax.random.randint(jax.random.key(0), (2, T), 0,
                                config["vocab_size"])
    shapes = jax.eval_shape(_model(config).init, jax.random.key(0),
                            tokens)["params"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(jax.random.key(seed), len(flat))

    def draw(path, shape, key):
        noise = jax.random.normal(key, shape.shape)
        if len(shape.shape) == 1:
            return 1 + 0.3 * noise
        if "moe_router" in jax.tree_util.keystr(path):
            return 2.5 * noise
        return noise * shape.shape[-2] ** -0.5

    return jax.tree.unflatten(treedef, [
        draw(path, shape, key) for (path, shape), key in zip(flat, keys)
    ]), tokens


@pytest.fixture(scope="module")
def tiny():
    return _init()


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _system_loss(params, tokens, config=TINY, **kw):
    return lm_loss_moe(_model(config, **kw), params, tokens, n_chunks=2,
                       load_balance_coef=0.0, z_loss_coef=0.0,
                       seq_aux_coef=ALPHA)


@functools.lru_cache(maxsize=None)
def _reference(held=(2, 6), dtype=jnp.float32):
    """``(params, tokens, loss, gradients)`` of the reference on the tiny
    preset with experts ``held`` (all eight: the whole model)."""
    ref = _load("benchmark/reference/latent_moe_lm.py",
                "reference_latent_moe_lm")
    config = TINY if held != (0, 8) else _whole(TINY)
    params, tokens = _init(config)
    want, want_grads = _highest(jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, (), tokens, config, dtype=dtype))), params)
    return params, tokens, want, want_grads


# -- the kernels with a value width of their own ------------------------------

def _plain_attention(q, k, v, scale):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


#: ``(q heads, kv heads, key width, value width)`` and the heads a grid
#: step then takes: 0 the transposed form (latent attention's 192 / 128
#: is of this kind: 1.5 lane tiles), 1 the projections' own rows
LAYOUTS = {
    "transposed_24_16": ((2, 2, 24, 16), 0),
    "transposed_gqa_24_16": ((4, 2, 24, 16), 0),
    "own_rows_256_128": ((2, 2, 256, 128), 1),
}


@functools.lru_cache(maxsize=None)
def _flash_and_plain(layout):
    (H, Hkv, D, Dv), _ = LAYOUTS[layout]
    ks = jax.random.split(jax.random.key(3), 4)
    q = jax.random.normal(ks[0], (2, T, H, D))
    k = jax.random.normal(ks[1], (2, T, Hkv, D))
    v = jax.random.normal(ks[2], (2, T, Hkv, Dv))
    weight = jax.random.normal(ks[3], (2, T, H, Dv))

    def of(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(weight))

    return _highest(lambda: (
        of(lambda q, k, v: flash_attention(q, k, v, causal=True, scale=0.2)),
        of(lambda q, k, v: _plain_attention(q, k, v, 0.2))))


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_flash_attention_takes_a_value_width_of_its_own(layout, what):
    """Output and the three gradients against plain softmax attention, in
    each layout the op can take with values narrower than the keys."""
    got, want = _flash_and_plain(layout)
    i = ["out", "dq", "dk", "dv"].index(what)
    (H, Hkv, D, Dv), heads = LAYOUTS[layout]
    assert got[i].shape == want[i].shape == (
        2, T, H if what in ("out", "dq") else Hkv,
        Dv if what in ("out", "dv") else D)
    assert _rel(got[i], want[i]) < 1e-5
    layout_of = importlib.import_module(
        "chainermn_tpu.ops.flash_attention")._Layout.of
    assert layout_of((2, T, H, D), (2, T, Hkv, D),
                     (2, T, Hkv, Dv)).heads == heads


def test_values_wider_than_the_keys_are_refused():
    q = jnp.zeros((1, 8, 2, 16))
    with pytest.raises(ValueError, match="wider than the keys"):
        flash_attention(q, q, jnp.zeros((1, 8, 2, 24)), causal=True)


# -- YaRN's numbers -----------------------------------------------------------

YARN = Architecture.from_config(PUBLISHED).rope_scaling


def _closed_form_frequencies():
    """The issue's closed forms, in float64."""
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)

    def corr(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) \
            / (2 * math.log(10000))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return low, high, extra / 40 * ramp + extra * (1 - ramp)


@pytest.mark.parametrize("what", ["low_and_high", "frequencies",
                                  "softmax_scale", "rotation_scale",
                                  "apply_rope"])
def test_yarns_numbers_are_the_closed_forms(what):
    low, high, freqs = _closed_form_frequencies()
    if what == "low_and_high":
        assert (low, high) == (10, 23)
        assert YARN.correction_range(64, 10000.0) == (10, 23)
    elif what == "frequencies":
        got = np.asarray(YARN.frequencies(64, 10000.0))
        np.testing.assert_allclose(got, freqs, rtol=2e-6)
        # the fast pairs keep the base's frequency, the slow ones a 40th
        assert got[0] == 1.0 and got[10] == np.float32(freqs[10])
        np.testing.assert_allclose(
            got[23:], 10000.0 ** (-2 * np.arange(23, 32) / 64) / 40,
            rtol=2e-6)
    elif what == "softmax_scale":
        m = 0.1 * 0.707 * math.log(40) + 1
        assert abs(m - 1.26080) < 1e-5
        assert abs(192 ** -0.5 * YARN.softmax_scale - 0.11472) < 1e-5
        assert YARN.softmax_scale == pytest.approx(m * m, rel=1e-12)
    elif what == "rotation_scale":
        assert YARN.rotation_scale == 1.0
        other = Yarn(factor=40, original_max_position=4096, mscale=1.0,
                     mscale_all_dim=0.707)
        assert other.rotation_scale == pytest.approx(
            (0.1 * math.log(40) + 1) / (0.0707 * math.log(40) + 1))
        assert Yarn(factor=1.0, original_max_position=4096,
                    mscale_all_dim=0.707).softmax_scale == 1.0
    else:
        x = jax.random.normal(jax.random.key(0), (1, 8, 1, 64))
        pos = jnp.arange(8) * 1000
        got = transformer.apply_rope(x, pos, 10000.0, YARN)
        ang = np.asarray(pos)[:, None] * freqs
        a, b = np.asarray(x[0, :, 0, :32]), np.asarray(x[0, :, 0, 32:])
        want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               a * np.sin(ang) + b * np.cos(ang)], -1)
        np.testing.assert_allclose(got[0, :, 0], want, atol=2e-3)
        # and without scaling the rotation is the one it always was
        plain = transformer.apply_rope(x, pos, 10000.0)
        assert float(jnp.abs(plain - got).max()) > 0.1


# -- the system against the reference ----------------------------------------

#: one layer of each new kind
LAYER_KINDS = {"latent_attention_and_dense": 1,
               "latent_attention_and_experts": 0}


@functools.lru_cache(maxsize=None)
def _one_layer(kind):
    ref = _load("benchmark/reference/latent_moe_lm.py",
                "reference_latent_moe_lm")
    config = {**TINY, "num_hidden_layers": 1,
              "first_k_dense_replace": LAYER_KINDS[kind]}
    params, tokens = _init(config)
    model = _model(config)
    weight = jax.random.normal(jax.random.key(9), (2, T, 64))

    def got(p):
        out = model.apply({"params": p}, tokens, mutable=["moe_aux"])[0]
        return (out * weight).sum(), out

    def want(p):
        out = ref.hidden(p, tokens, config)[0]
        return (out * weight).sum(), out

    def both(fn):
        (_, out), grads = jax.jit(jax.value_and_grad(fn, has_aux=True))(
            params)
        return out, grads

    return both(got), _highest(both, want)


@pytest.mark.parametrize("what", ["forward", "gradients"])
@pytest.mark.parametrize("kind", sorted(LAYER_KINDS))
def test_each_new_layer_kind_matches_the_reference(kind, what):
    """A one-layer model of each kind, its normed hidden states (and their
    gradient in every leaf) against the reference's."""
    (out, grads), (want, want_grads) = _one_layer(kind)
    if what == "forward":
        assert _rel(out, want) < 1e-5
        return
    flat = jax.tree_util.tree_flatten_with_path(want_grads)[0]
    assert len(flat) == len(jax.tree.leaves(grads))
    for (path, w), g in zip(flat, jax.tree.leaves(grads)):
        if "lm_head" in jax.tree_util.keystr(path):
            assert float(jnp.abs(g).max()) == float(jnp.abs(w).max()) == 0.0
            continue
        assert _rel(g, w) < 5e-5, jax.tree_util.keystr(path)


@pytest.mark.parametrize("held", [(2, 6), (0, 8)])
def test_loss_and_every_gradient_leaf_match_the_reference(held):
    """The whole model with a share of the experts (and with all of them:
    ``(0, 8)``): the loss and its gradient in every leaf."""
    config = TINY if held != (0, 8) else _whole(TINY)
    params, tokens, want, want_grads = _reference(held)
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: _system_loss(p, tokens, config), has_aux=True))(params)
    assert abs(float(loss) - float(want)) / float(want) < 1e-5
    flat = jax.tree_util.tree_flatten_with_path(want_grads)[0]
    assert len(flat) == len(jax.tree.leaves(grads)) == 25
    for (path, w), g in zip(flat, jax.tree.leaves(grads)):
        assert _rel(g, w) < 5e-5, jax.tree_util.keystr(path)
    block = params["block_1"]
    assert block["moe_w_gate_up"].shape == (held[1] - held[0], 64, 32)
    assert block["moe_router"].shape == (64, 8)
    assert block["shared_gate_up"]["kernel"].shape == (64, 64)
    assert block["q_proj"]["kernel"].shape == (64, 4 * 24)
    assert block["kv_a"]["kernel"].shape == (64, 32 + 8)
    assert block["kv_b"]["kernel"].shape == (32, 4 * 32)
    assert block["proj"]["kernel"].shape == (64, 64)
    assert "moe_router" not in params["block_0"]
    assert float(metrics["moe/dropped"]) == 0.0
    assert float(metrics["moe/seq_aux"]) > 1.0  # far from balanced
    assert float(metrics["moe/rows_held"]) == (
        2 * T * 3 if held == (0, 8) else metrics["moe/expert_load"].sum())


def _no_mscale(_real):
    return property(lambda self: 1.0)


def _rope_key_not_rotated(real):
    def apply_rope(x, positions, base=10000.0, scaling=None):
        return x if x.shape[2] == 1 else real(x, positions, base, scaling)
    return apply_rope


def _latent_norm_skipped(real):
    def norm_layer(arch, dtype, name=None):
        return (lambda x: x) if name == "kv_a_norm" \
            else real(arch, dtype, name)
    return norm_layer


def _batchwise_balance(real):
    def sequence_balance_loss(logits, experts):
        E, k = logits.shape[-1], experts.shape[-1]
        return real(logits.reshape(1, -1, E), experts.reshape(1, -1, k))
    return sequence_balance_loss


def _shared_expert_by_a_gate(real):
    def shared(self, h, width):
        return real(self, h, width) * jax.nn.sigmoid(h[..., :1])
    return shared


def _shared_expert_left_out(real):
    def shared(self, h, width):
        return jnp.zeros_like(real(self, h, width))
    return shared


#: what the comparison must catch, each a change to the system alone (but
#: the last, which changes the reference's precision)
MUTATIONS = {
    "mscale_squared_missing": (Yarn, "softmax_scale", _no_mscale),
    "rope_key_not_rotated": (transformer, "apply_rope",
                             _rope_key_not_rotated),
    "latent_norm_skipped": (transformer, "_norm_layer",
                            _latent_norm_skipped),
    "shared_expert_left_out": (TransformerBlock, "_shared_expert",
                               _shared_expert_left_out),
    "shared_expert_scaled_by_a_gate": (TransformerBlock, "_shared_expert",
                                       _shared_expert_by_a_gate),
    "balance_loss_batchwise": (transformer, "sequence_balance_loss",
                               _batchwise_balance),
    "reference_computed_in_bf16": None,
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_the_comparison_catches(name, monkeypatch):
    """Each departure from the equations moves the loss or a gradient
    leaf far past the 1e-5 the faithful system keeps to."""
    dtype = jnp.float32
    if MUTATIONS[name] is None:
        dtype = jnp.bfloat16
    else:
        where, attr, mutate = MUTATIONS[name]
        monkeypatch.setattr(where, attr, mutate(getattr(where, attr)))
    params, tokens, want, want_grads = _reference(dtype=dtype)

    def attn(q, k, v, *, causal, scale):
        # plain attention: what is held here is the comparison's reach,
        # and the interpreted kernels take longer to compile
        return _plain_attention(q, k, v, scale)

    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: _system_loss(p, tokens, attention_fn=attn),
        has_aux=True))(params)
    worst = max(_rel(g, w) for g, w in zip(
        jax.tree.leaves(grads), jax.tree.leaves(want_grads)))
    loss_err = abs(float(loss) - float(want)) / float(want)
    assert max(worst, loss_err) > 1e-3, (loss_err, worst)


# -- the model description ---------------------------------------------------

def test_the_description_reads_the_published_config_json():
    arch = Architecture.from_config(PUBLISHED)
    assert arch.layers == (("latent_attention", "dense"),) \
        + (("latent_attention", "experts"),) * 26
    assert (arch.latent_rank, arch.qk_nope_dim, arch.qk_rope_dim,
            arch.v_head_dim) == (512, 128, 64, 128)
    assert (arch.n_experts, arch.experts_per_token, arch.expert_width,
            arch.shared_expert_width) == (64, 6, 1408, 2816)
    assert arch.seq_aux and not arch.renormalise_gates
    assert arch.router_score == "softmax" and arch.routed_scaling == 1.0
    assert not arch.tied_head and arch.norm_eps == 1e-6
    assert arch.rope_scaling == Yarn(
        factor=40.0, original_max_position=4096, beta_fast=32.0,
        beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)
    assert arch.router_kwargs() == {}
    model = lm_from_config(PUBLISHED, num_layers=2)
    assert (model.num_layers, model.num_heads, model.d_model, model.d_ff,
            model.vocab_size) == (2, 16, 2048, 10944, 102400)
    held = Architecture.from_config(TINY)
    assert held.experts_held == (2, 6) and held.n_experts == 8
    assert held.router_kwargs() == {"held": (2, 6)}


@pytest.mark.parametrize("bad, match", [
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"n_group": 8}, "group-limited"),
    ({"topk_method": "group_limited_greedy"}, "group-limited"),
    ({"scoring_func": "sigmoid"}, "scoring_func"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"rope_scaling": {"type": "linear", "factor": 4}}, "rope_scaling"),
    ({"attention_bias": True}, "attention biases"),
    ({"num_key_value_heads": 2}, "key-value heads"),
    ({"experts_published": 128}, "share of the experts"),
])
def test_a_config_that_is_not_built_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        Architecture.from_config({**PUBLISHED, **bad})


@pytest.mark.parametrize("bad", [
    dict(layers=(("latent_attention", "dense"),)),        # no sizes
    dict(layers=(("latent_attention", "dense"),), latent_rank=8,
         qk_nope_dim=8, qk_rope_dim=4, v_head_dim=16,
         positions="rope"),                                # values too wide
    dict(layers=(("latent_attention", "dense"),), latent_rank=8,
         qk_nope_dim=8, qk_rope_dim=4, v_head_dim=8),      # no RoPE
    dict(positions="rope", rope_scaling=YARN),             # plain attention
    dict(shared_expert_width=8),                           # no router
    dict(seq_aux=True),
])
def test_a_description_that_names_no_latent_stack_is_refused(bad):
    with pytest.raises(ValueError):
        Architecture(**bad)


def test_the_balance_loss_needs_the_description_to_sow_it():
    config = dict(
        MODEL_CONFIGS["olmoe-1b-7b"], num_hidden_layers=1, hidden_size=32,
        num_attention_heads=2, num_key_value_heads=2, intermediate_size=16,
        num_experts=4, num_experts_per_tok=2, vocab_size=64,
        max_position_embeddings=16)
    model = lm_from_config(config, compute_dtype=jnp.float32,
                           return_hidden=True)
    tokens = jnp.zeros((1, 16), jnp.int32)

    def loss():
        params = model.init(jax.random.key(0), tokens)["params"]
        return lm_loss_moe(model, params, tokens, n_chunks=1,
                           seq_aux_coef=0.001)[0]

    with pytest.raises(ValueError, match="seq_aux"):
        jax.eval_shape(loss)


# -- refusals ----------------------------------------------------------------

def _served(model, params, prompt):
    from chainermn_tpu.serving import ServingEngine

    return ServingEngine(model, params, num_slots=2, max_len=32)


ENTRY_POINTS = {
    "generate": lambda m, p, prompt: generate(m, p, prompt, 4),
    "beam_search": lambda m, p, prompt: beam_search(m, p, prompt, 4, 2),
    "init_cache": lambda m, p, prompt: init_cache(m, p, 2),
    "ServingEngine": _served,
}


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
def test_decoding_and_serving_refuse_latent_attention(entry):
    """By name, and with every expert held: it is the mixer they refuse."""
    config = _whole(TINY)
    model = _model(config, return_hidden=False)
    params = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), params)
    with pytest.raises(NotImplementedError, match="latent_attention"):
        ENTRY_POINTS[entry](model, {"params": params},
                            jnp.ones((2, 4), jnp.int32))


# -- scopes, gauges and the loss's metric -------------------------------------

def _gauge(name):
    rows = registry().snapshot()[name]["values"]
    return {tuple(sorted(r["labels"].items())): r["value"] for r in rows}


def test_the_scopes_the_gauges_and_the_metric_appear(tiny):
    params, tokens = tiny
    model = _model(remat=True)

    def loss(p):
        return lm_loss_moe(model, p, tokens, n_chunks=2,
                           load_balance_coef=0.0, z_loss_coef=0.0,
                           seq_aux_coef=ALPHA)

    lowered = jax.jit(jax.grad(lambda p: loss(p)[0])).lower(params)
    text = lowered.as_text(debug_info=True)
    lines = text.splitlines()
    assert (train_path.MLA_ATTENTION, train_path.MOE_SHARED) == (
        "mla_attention", "moe_shared")
    assert _gauge(train_path.MLA_LATENT_RANK) == {(): 32.0}
    assert _gauge(train_path.MLA_QK_WIDTH) == {(): 24.0}
    assert _gauge(train_path.MLA_V_WIDTH) == {(): 16.0}
    assert not hasattr(train_path, "MLA_VALUE_PADDED")  # no fallback built
    assert _gauge(train_path.MOE_SHARED_WIDTH) == {(): 32.0}
    assert _gauge(train_path.FLASH_HEADS_PER_BLOCK) == {
        (("kernel", k),): 0.0 for k in (
            train_path.FLASH_FWD, train_path.FLASH_BWD_DQ,
            train_path.FLASH_BWD_DKV)}
    assert _gauge(train_path.STACK_LAYERS_BY_KIND) == {
        (("kind", "attention"),): 0.0, (("kind", "short_conv"),): 0.0,
        (("kind", "latent_attention"),): 2.0,
        (("kind", "dense_ffn"),): 1.0, (("kind", "expert_ffn"),): 1.0}

    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))

    def under(scope, *words):
        return [line for line in lines if f"/{scope}/" in line
                and all(w in line for w in words)]

    # the whole mixer lies under its scope: the four projections, the
    # latent's norm, the kernels, forward and transposed ...
    for name in ("q_proj", "kv_a", "kv_b", "proj"):
        assert under("mla_attention", name, "dot_general"), name
    assert under("mla_attention", "kv_a_norm")
    # the kernels are a function of their own (one trace for all layers),
    # called under the scope forward, recomputed (the kept output's way
    # back to [B, T, H, Dv] alone) and transposed: two layers of each
    sites = [locs[m.group(1)] for m in re.finditer(
        r"call @_flash_core\w*\(.*loc\((#loc\d+)\)", text)]
    assert len(sites) == 6
    assert all("/mla_attention/jit(_flash_core)" in s for s in sites)
    for kernel in (train_path.FLASH_FWD, train_path.FLASH_BWD_DQ,
                   train_path.FLASH_BWD_DKV):
        assert f'"{kernel}/{kernel}/pallas_call"' in text
    assert under("mla_attention", train_path.BACKWARD_MARKER, "dot_general")
    # ... and no feed-forward does
    assert not under("mla_attention", "shared_gate_up")
    assert not under("mla_attention", "ff_gate")
    # the shared expert's matmuls and gate, forward, recomputed, transposed
    for name in ("shared_gate_up", "shared_down"):
        assert under("moe_shared", name, "dot_general"), name
    assert under("moe_shared", "jit(silu)")
    assert under("moe_shared", train_path.REMAT_MARKER)
    assert under("moe_shared", train_path.BACKWARD_MARKER, "dot_general")
    assert not under("moe_shared", "moe_w")
    metrics = jax.eval_shape(loss, params)[1]
    assert {"moe/seq_aux", "moe/rows_held", "moe/dropped"} <= set(metrics)
    assert "moe/load_balance" in metrics  # a softmax router's, unweighted
