"""LFM2-MoE's hybrid stack (ISSUE 40): the stack described layer by layer,
the gated short convolution, per-head QK-norm, the sigmoid router with a
selection bias and a chip's share of the experts, against the plain
reference the benchmark holds the system to
(``benchmark/reference/hybrid_moe_lm.py``), at a tiny size in float32 on
the CPU (kernels interpreted); and what the other models keep: their
parameter trees and the softmax router's bits."""

import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import (
    MODEL_CONFIGS,
    ROUTER_STATE,
    Architecture,
    TransformerLM,
    beam_search,
    generate,
    init_cache,
    lm_from_config,
    lm_loss_moe,
)
from chainermn_tpu.models.transformer import TransformerBlock
from chainermn_tpu.observability import train_path
from chainermn_tpu.observability.metrics import registry
from chainermn_tpu.ops import short_conv
from chainermn_tpu.ops.flash_attention import flash_attention
from chainermn_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/hybrid_moe_lm.py",
                 "reference_hybrid_moe_lm")


#: the tiny preset: the cell's five layers at d 64, 4 / 2 heads of 16,
#: dense 96, 8 experts of width 32 of which 4 are held, top-2, T 64,
#: vocabulary 128; the reference reads the same dict
TINY = dict(
    MODEL_CONFIGS["lfm2-8b-a1b"], num_hidden_layers=5, num_dense_layers=1,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    intermediate_size=96, moe_intermediate_size=32, num_experts=4,
    experts_published=8, experts_held_range=[2, 6], num_experts_per_tok=2,
    vocab_size=128, max_position_embeddings=64,
)
T = 64


def _whole(config):
    """The same model with every expert held."""
    whole = {k: v for k, v in config.items()
             if k not in ("experts_published", "experts_held_range")}
    return {**whole, "num_experts": config["experts_published"]}


def _attn(q, k, v, *, causal, scale):
    return flash_attention(q, k, v, causal=causal, scale=scale)


def _model(config=TINY, **kw):
    kw.setdefault("return_hidden", True)
    return lm_from_config(config, compute_dtype=jnp.float32,
                          attention_fn=_attn, **kw)


def _init(config=TINY, seed=1, bias_std=0.3):
    """Parameters, and a router state drawn far from zero (the scores'
    spread at this size is about 0.1)."""
    tokens = jax.random.randint(jax.random.key(0), (2, T), 0,
                                config["vocab_size"])
    v = _model(config).init(jax.random.key(seed), tokens)
    leaves, treedef = jax.tree.flatten(v.get(ROUTER_STATE, {}))
    keys = jax.random.split(jax.random.key(seed + 100), max(len(leaves), 1))
    state = jax.tree.unflatten(treedef, [
        bias_std * jax.random.normal(k, b.shape) for k, b in
        zip(keys, leaves)])
    return v["params"], state, tokens


@pytest.fixture(scope="module")
def tiny():
    return _init()


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _system_loss(params, state, tokens, config=TINY):
    return lm_loss_moe(_model(config), params, tokens, n_chunks=2,
                       load_balance_coef=0.0, z_loss_coef=0.0,
                       router_state=state)


# -- the system against the reference ------------------------------------

#: one layer of each pair of kinds the stack can hold
LAYER_KINDS = {
    "short_conv_and_dense": (["conv"], 1),
    "short_conv_and_experts": (["conv"], 0),
    "attention_and_dense": (["full_attention"], 1),
    "attention_and_experts": (["full_attention"], 0),
}


@pytest.mark.parametrize("what", ["forward", "gradients"])
@pytest.mark.parametrize("kind", sorted(LAYER_KINDS))
def test_each_layer_kind_matches_the_reference(kind, what, ref):
    """A one-layer model of each kind, its normed hidden states (and their
    gradient in every leaf) against the reference's."""
    layer_types, dense = LAYER_KINDS[kind]
    config = {**TINY, "num_hidden_layers": 1, "layer_types": layer_types,
              "num_dense_layers": dense}
    params, state, tokens = _init(config)
    model = _model(config)
    weight = jax.random.normal(jax.random.key(9), (2, T, 64))

    def got(p):
        return model.apply({"params": p, ROUTER_STATE: state}, tokens)

    def want(p):
        return ref.hidden(p, state, tokens, config)

    if what == "forward":
        assert _rel(got(params), _highest(want, params)) < 1e-5
        return
    g = jax.grad(lambda p: (got(p) * weight).sum())(params)
    w = _highest(jax.grad(lambda p: (want(p) * weight).sum()), params)
    flat = jax.tree_util.tree_flatten_with_path(w)[0]
    assert len(flat) == len(jax.tree.leaves(g))
    for (path, wl), gl in zip(flat, jax.tree.leaves(g)):
        assert _rel(gl, wl) < 2e-5, jax.tree_util.keystr(path)


@pytest.mark.parametrize("held", [[0, 4], [2, 6], [0, 8]])
def test_loss_and_every_gradient_leaf_match_the_reference(held, ref):
    """The whole model with a share of the experts (and with all of them:
    ``[0, 8]``): the loss and its gradient in every leaf."""
    config = {**TINY, "experts_held_range": held,
              "num_experts": held[1] - held[0]}
    if held == [0, 8]:
        config = _whole(TINY)
    params, state, tokens = _init(config)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: _system_loss(p, state, tokens, config), has_aux=True)(
        params)
    want, want_grads = _highest(jax.value_and_grad(
        lambda p: ref.loss(p, state, tokens, config)), params)
    assert abs(float(loss) - float(want)) / float(want) < 1e-5
    flat = jax.tree_util.tree_flatten_with_path(want_grads)[0]
    assert len(flat) == len(jax.tree.leaves(grads)) == 43
    for (path, w), g in zip(flat, jax.tree.leaves(grads)):
        assert _rel(g, w) < 2e-5, jax.tree_util.keystr(path)
    assert params["block_1"]["moe_w_gate_up"].shape[0] == held[1] - held[0]
    assert params["block_1"]["moe_router"].shape == (64, 8)
    assert float(metrics["moe/dropped"]) == 0.0
    assert set(metrics) == {"moe/dropped", "moe/rows_held",
                            "moe/tail_tiles", "moe/rounds",
                            "moe/expert_load",
                            "moe/expert_load_max_over_mean"}
    # a round of twice the expected share holds what this router deals
    assert float(metrics["moe/rounds"]) == 4.0


def _gates_by_choice(real):
    """The departure the non-zero bias is there to catch: the gates taken
    from score + bias."""
    def dropless_topk(u, router_w, k, renormalise=False, **kw):
        r = real(u, router_w, k, renormalise, **kw)
        s = jax.nn.sigmoid(r.logits) + kw["select_bias"]
        g = jnp.take_along_axis(s, r.experts, axis=-1)
        return r._replace(gates=g / (g.sum(-1, keepdims=True) + 1e-6))
    return dropless_topk


def _with(**changes):
    def mutate(real):
        def dropless_topk(u, router_w, k, renormalise=False, **kw):
            kw = {a: b for a, b in {**kw, **changes}.items()
                  if b is not None}
            return real(u, router_w, k, renormalise, **kw)
        return dropless_topk
    return mutate


def _bf16_router(real):
    def dropless_topk(u, router_w, k, renormalise=False, **kw):
        return real(u.astype(jnp.bfloat16).astype(jnp.float32),
                    router_w.astype(jnp.bfloat16).astype(jnp.float32), k,
                    renormalise, **kw)
    return dropless_topk


def _causal_conv_reads_ahead(real):
    def pad(x, widths, **kw):
        # the convolution's left padding moved to the right: taps read
        # the future
        if len(widths) == 3 and widths[1] == (widths[1][0], 0) \
                and widths[1][0] > 0:
            return jnp.roll(real(x, widths, **kw), -widths[1][0], axis=1)
        return real(x, widths, **kw)
    return pad


#: what the comparison must catch, each a change to the system alone
MUTATIONS = {
    "gates_from_score_plus_bias": dict(patch=("dropless_topk",
                                              _gates_by_choice)),
    "bias_left_out_of_the_choice": dict(patch=("dropless_topk",
                                               _with(select_bias=None))),
    "softmax_for_the_sigmoid": dict(patch=("dropless_topk",
                                           _with(score="softmax"))),
    "gate_eps_left_out": dict(patch=("dropless_topk", _with(gate_eps=1e-2))),
    "gates_not_renormalised": dict(config={**TINY, "norm_topk_prob": False}),
    "top_k_less_one": dict(config={**TINY, "num_experts_per_tok": 1}),
    "another_share": dict(config={**TINY, "experts_held_range": [0, 4]}),
    "bf16_router": dict(patch=("dropless_topk", _bf16_router)),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_the_comparison_catches(name, tiny, ref, monkeypatch):
    """Each departure from the equations moves the loss or a gradient
    leaf far past the 1e-5 the faithful system keeps to."""
    params, state, tokens = tiny
    m = MUTATIONS[name]
    if "patch" in m:
        attr, mutate = m["patch"]
        monkeypatch.setattr(moe, attr, mutate(getattr(moe, attr)))
    (loss, _), grads = jax.value_and_grad(
        lambda p: _system_loss(p, state, tokens, m.get("config", TINY)),
        has_aux=True)(params)
    want, want_grads = _highest(jax.value_and_grad(
        lambda p: ref.loss(p, state, tokens, TINY)), params)
    worst = max(_rel(g, w) for g, w in zip(
        jax.tree.leaves(grads), jax.tree.leaves(want_grads)))
    loss_err = abs(float(loss) - float(want)) / float(want)
    assert max(worst, loss_err) > 1e-3, (loss_err, worst)


# -- the share ---------------------------------------------------------------

def _expert_layer(held, params, state, x, whole_config, positions=None,
                  num_kv_heads=2):
    """One attention-and-experts block holding experts ``held`` of the
    whole layer's, applied to ``x`` (``state``: the router's selection
    bias, where the router has one)."""
    lo, hi = held
    arch = dataclasses.replace(
        Architecture.from_config(whole_config), experts_held=(lo, hi))
    block = TransformerBlock(
        num_heads=4, num_kv_heads=num_kv_heads, d_ff=96,
        compute_dtype=jnp.float32, attention_fn=_attn, arch=arch,
        layer_index=0)
    share = {**params, "moe_w_gate_up": params["moe_w_gate_up"][lo:hi],
             "moe_w_down": params["moe_w_down"][lo:hi]}
    variables = {"params": share}
    if state is not None:
        variables[ROUTER_STATE] = state
    if positions is None:
        positions = jnp.arange(x.shape[1])
    out, _ = block.apply(variables, x, None, positions, mutable=["moe_aux"])
    return out


def _lfm2_share_case(ref):
    """LFM2's layer: four shares of 2 of 8 experts, sigmoid top-2 with a
    selection bias."""
    config = {**_whole(TINY), "num_hidden_layers": 1,
              "layer_types": ["full_attention"], "num_dense_layers": 0}
    params, state, _ = _init(config, bias_std=0.05)  # every share chosen
    p, s = params["block_0"], state["block_0"]
    x = jax.random.normal(jax.random.key(4), (2, T, 64))
    eps = config["norm_eps"]
    return dict(
        config=config, p=p, state=s, x=x, positions=None,
        shares=[(0, 2), (2, 4), (4, 6), (6, 8)],
        mixed=lambda h: ref.by_row(
            lambda row: ref.attention(row, p, config), h),
        norm=lambda y, name: ref.rms_norm(y, p[name], eps),
        experts=lambda h, p_, c: ref.experts(
            h, p_, s["moe_router_bias"], c))


def _sdar_share_case(_ref):
    """SDAR's layer under block diffusion: eight shares of 16 of 128
    experts, softmax top-8 renormalised, over the rows ``[x ; x~]``."""
    ref = _load("benchmark/reference/block_diffusion_moe_lm.py",
                "reference_block_diffusion_moe_lm")
    config = dict(
        MODEL_CONFIGS["sdar-30b-a3b"], num_hidden_layers=1, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        moe_intermediate_size=16, vocab_size=128,
        max_position_embeddings=64)
    model = lm_from_config(config, compute_dtype=jnp.float32,
                           return_hidden=True)
    p = model.init(jax.random.key(1), jnp.zeros((1, 2 * T), jnp.int32))[
        "params"]["block_0"]
    # a router far from uniform, so that the eight chosen differ by token
    p = {**p, "moe_router": p["moe_router"] * 20}
    x = jax.random.normal(jax.random.key(4), (2, 2 * T, 64))
    eps = config["rms_norm_eps"]
    return dict(
        config=config, p=p, state=None, x=x,
        positions=jnp.tile(jnp.arange(T), 2),
        shares=[(16 * i, 16 * i + 16) for i in range(8)],
        mixed=lambda h: ref.attention(h, p, config),
        norm=lambda y, name: ref.rms_norm(y, p[name], eps),
        experts=lambda h, p_, c: ref.experts(h, p_, c)[0])


def _dsv2_share_case(_ref):
    """DeepSeek-V2's layer: eight shares of 2 of 16 routed experts,
    softmax top-3 not renormalised, under latent attention, beside a
    shared expert that every share computes whole and the sum counts
    once."""
    ref = _load("benchmark/reference/latent_moe_lm.py",
                "reference_latent_moe_lm")
    config = dict(
        MODEL_CONFIGS["deepseek-v2-lite"], num_hidden_layers=1,
        first_k_dense_replace=0, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
        moe_intermediate_size=16, n_routed_experts=16,
        num_experts_per_tok=3, vocab_size=128, max_position_embeddings=64)
    model = lm_from_config(config, compute_dtype=jnp.float32,
                           return_hidden=True, attention_fn=_attn)
    p = jax.jit(model.init)(jax.random.key(1), jnp.zeros((1, T), jnp.int32))[
        "params"]["block_0"]
    # a router far from uniform, so that the three chosen differ by token
    p = {**p, "moe_router": p["moe_router"] * 20}
    x = jax.random.normal(jax.random.key(4), (2, T, 64))
    eps = config["rms_norm_eps"]

    def shared(h):
        return ref.gated(h, p["shared_gate_up"]["kernel"],
                         p["shared_down"]["kernel"])

    return dict(
        config=config, p=p, state=None, x=x, positions=None,
        shares=[(2 * i, 2 * i + 2) for i in range(8)],
        experts_key="n_routed_experts", block=dict(num_kv_heads=4),
        alike=shared,
        mixed=lambda h: ref.attention(h, p, config),
        norm=lambda y, name: ref.rms_norm(y, p[name], eps),
        experts=lambda h, p_, c: ref.experts(
            h.reshape(2, T, 64), p_, c)[0].reshape(-1, 64))


SHARE_CASES = {"lfm2_four_shares_of_2": _lfm2_share_case,
               "sdar_eight_shares_of_16": _sdar_share_case,
               "dsv2_eight_shares_of_2_and_a_shared_expert":
               _dsv2_share_case}


@pytest.mark.parametrize("side", ["system", "reference"])
@pytest.mark.parametrize("family", sorted(SHARE_CASES))
def test_the_four_shares_add_up_to_the_uncut_layer(family, side, ref):
    """The expert outputs of the shares, summed, are the uncut
    reference's for the whole layer: ``sum_s (y_s - r) = y - r`` with
    ``r`` the residual stream after the mixer, which every chip computes
    alike. LFM2's four shares of 2 experts, SDAR's eight shares of 16
    under its mask by blocks (no shared expert in either, so nothing is
    counted once), and DeepSeek-V2's eight shares of 2 under latent
    attention, whose shared expert every share computes whole (``alike``)
    and the sum counts once."""
    case = SHARE_CASES[family](ref)
    config, p, x = case["config"], case["p"], case["x"]

    def r_and_h():
        r = x + case["mixed"](case["norm"](x, "RMSNorm_0"))
        return r, case["norm"](r, "RMSNorm_1").reshape(-1, 64)

    r, h = _highest(r_and_h)
    uncut = _highest(case["experts"], h, p, config)
    shares = case["shares"]
    if side == "system":
        parts = [_expert_layer(held, p, case["state"], x, config,
                               case["positions"], **case.get("block", {}))
                 - r for held in shares]
    else:
        parts = [_highest(
            case["experts"], h,
            {**p, "moe_w_gate_up": p["moe_w_gate_up"][lo:hi],
             "moe_w_down": p["moe_w_down"][lo:hi]},
            {**config, case.get("experts_key", "num_experts"): hi - lo,
             "experts_published": shares[-1][1],
             "experts_held_range": [lo, hi]}).reshape(x.shape)
            for lo, hi in shares]
    assert all(float(jnp.linalg.norm(part)) > 0.05 * float(
        jnp.linalg.norm(uncut)) for part in parts)
    total = sum(parts).reshape(-1, 64)
    if "alike" in case:
        # what every chip computes alike is in every part: counted once
        alike = _highest(case["alike"], h)
        assert float(jnp.linalg.norm(alike)) > 0.05 * float(
            jnp.linalg.norm(uncut))
        total = total - (len(shares) - 1) * alike
    assert _rel(total, uncut) < 1e-5


def test_a_bias_changes_the_choice_and_not_the_weights():
    u = jax.random.normal(jax.random.key(2), (64, 16))
    w = jax.random.normal(jax.random.key(3), (16, 8))
    scores = jax.nn.sigmoid(u @ w)
    plain = moe.dropless_topk(u, w, 2, True, score="sigmoid", gate_eps=1e-6)
    bias = jnp.zeros(8).at[5].set(10.0)  # expert 5 wins every choice
    r = moe.dropless_topk(u, w, 2, True, score="sigmoid", gate_eps=1e-6,
                          select_bias=bias)
    assert (np.asarray(r.experts)[:, 0] == 5).all()
    assert (np.asarray(plain.experts) != np.asarray(r.experts)).any()
    chosen = np.take_along_axis(np.asarray(scores), np.asarray(r.experts), 1)
    np.testing.assert_allclose(
        r.gates, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    assert float(r.gates.max()) < 1.0  # no 10 in any weight
    # the gradient reaches the router through the gates, never the bias
    g = jax.grad(lambda b: moe.dropless_topk(
        u, w, 2, True, score="sigmoid", select_bias=b).gates.sum())(bias)
    assert float(jnp.abs(g).max()) == 0.0
    scaled = moe.dropless_topk(u, w, 2, True, score="sigmoid",
                               gate_eps=1e-6, select_bias=bias, scale=2.5)
    np.testing.assert_allclose(scaled.gates, 2.5 * r.gates, rtol=1e-6)


#: experts every token is sent to, and the rows of 64 x 2 that then reach
#: the held experts 2..5
SKEWS = {"both_held": ((2, 3), 128), "one_held": ((1, 2), 64),
         "none_held": ((0, 7), 0), "last_held_and_absent": ((5, 6), 64)}


@pytest.mark.parametrize("name", sorted(SKEWS))
def test_rows_held_counts_right_and_nothing_drops_under_a_skewed_router(
        name):
    """A router that sends every token to the same two experts: the rows
    of the held ones fill their groups, the others lie behind the last
    group, and dispatch -> experts -> combine is the held experts' part
    of the dense sum."""
    pair, rows_held = SKEWS[name]
    Tn, D, E, F = 64, 16, 8, 8
    u = jax.random.normal(jax.random.key(4), (Tn, D)).at[:, 0].set(1.0)
    router = jnp.zeros((D, E)).at[0, jnp.array(pair)].set(
        jnp.array([6.0, 3.0]))
    r = moe.dropless_topk(u, router, 2, True, score="sigmoid", held=(2, 6))
    assert int(r.rows_held) == rows_held
    sizes = np.asarray(r.group_sizes)
    assert sizes.shape == (4,) and sizes.sum() == rows_held
    assert [int(sizes[e - 2]) for e in pair if 2 <= e < 6] == \
        [Tn] * (rows_held // Tn)
    aux = moe.dropless_aux(r, losses=False)
    assert float(aux["dropped"]) == 0.0
    assert float(aux["rows_held"]) == rows_held
    assert set(aux) == {"expert_load", "rows_held", "dropped"}
    assert sorted(np.asarray(r.order)) == list(range(Tn * 2))
    # the held rows come first, expert by expert
    sorted_experts = np.asarray(r.experts).reshape(-1)[np.asarray(r.order)]
    here = (sorted_experts >= 2) & (sorted_experts < 6)
    assert here[:rows_held].all() and not here[rows_held:].any()
    from chainermn_tpu.ops.grouped_matmul import grouped_matmul

    w = jax.random.normal(jax.random.key(5), (4, D, F))
    out = moe.combine(grouped_matmul(moe.dispatch(u, r), w, r.group_sizes),
                      r)
    want = sum(
        jnp.where(((r.experts[:, s] >= 2) & (r.experts[:, s] < 6))[:, None],
                  r.gates[:, s, None] * jnp.einsum(
                      "td,tdf->tf", u, w[jnp.clip(r.experts[:, s] - 2, 0,
                                                  3)]), 0.0)
        for s in range(2))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


#: rows, K, N and the held groups' sizes: most rows lie past the groups,
#: over several row tiles of 512 (a share's absent experts' rows)
TAILS = {
    "a_quarter_live": (4096, 128, 256, [300, 0, 500, 224]),
    "nothing_live": (2048, 128, 256, [0, 0, 0, 0]),
    "groups_end_inside_a_tile": (3000, 128, 256, [513, 511, 1, 0, 700]),
    # where the tail opens (ISSUE 44): on a tile boundary; in a tile an
    # earlier group ends in, behind an empty last group; in the one tile
    # there is, of fewer rows than 512
    "the_tail_opens_on_a_tile_boundary": (2560, 128, 256, [512, 0, 512]),
    "an_empty_last_group_in_the_tails_first_tile":
        (2048, 128, 256, [200, 400, 0, 0]),
    "fewer_rows_than_a_tile": (200, 128, 256, [30, 0, 50]),
}


def _by_group(a, b, sizes):
    out, start = [], 0
    for e, size in enumerate(sizes):
        out.append(a[start:start + size] @ b[e])
        start += size
    out.append(jnp.zeros((a.shape[0] - start, b.shape[2]), a.dtype))
    return jnp.concatenate(out)


@pytest.mark.parametrize("tail", ["random", "nan_and_inf"])
@pytest.mark.parametrize("what", ["forward", "grad_lhs", "grad_rhs"])
@pytest.mark.parametrize("case", sorted(TAILS))
def test_rows_past_the_held_groups_are_multiplied_by_nothing(case, what,
                                                             tail):
    """The grouped matmul under a share: the rows behind the last held
    group come out zero, hand back a zero gradient and add nothing to any
    expert's, **whatever they hold** (NaN and inf in ``lhs`` and in the
    cotangent: they are written as zeros, not multiplied and masked), and
    a held row's result is the bits of the call that ends with the last
    live tile."""
    from chainermn_tpu.ops.grouped_matmul import grouped_matmul

    m, k, n, sizes = TAILS[case]
    lhs = jax.random.normal(jax.random.key(9), (m, k))
    rhs = jax.random.normal(jax.random.key(10), (len(sizes), k, n))
    weight = jax.random.normal(jax.random.key(11), (m, n))
    gs, live = jnp.array(sizes, jnp.int32), sum(sizes)
    if tail == "nan_and_inf":
        behind = (jnp.arange(m) >= live)[:, None]
        bad = jnp.where(jnp.arange(m)[:, None] % 2, jnp.nan, jnp.inf)
        lhs = jnp.where(behind, bad, lhs)
        if what == "grad_lhs":  # the weights' gradient contracts a tile's
            # cotangent rows against zeros (``_tgmm`` masks ``lhs`` alone)
            weight = jnp.where(behind, bad, weight)
    arg = {"forward": None, "grad_lhs": 0, "grad_rhs": 1}[what]

    def run(fn, a, w):
        if arg is None:
            return fn(a, rhs)
        # the cotangent is ``w``: the rows behind the groups take theirs
        # as it comes, NaN and all
        out, vjp = jax.vjp(fn, a, rhs)
        return vjp(w.astype(out.dtype))[arg]

    got = run(lambda a, b: grouped_matmul(a, b, gs), lhs, weight)
    # the reference never touches a row behind the groups: a clean tail
    clean = [jnp.where((jnp.arange(m) < live)[:, None], x, 0.0)
             for x in (lhs, weight)]
    want = run(lambda a, b: _by_group(a, b, sizes), *clean)
    assert bool(jnp.isfinite(got).all())
    if what != "grad_rhs":
        assert float(jnp.abs(got[live:]).max()) == 0.0
        # cut off at the end of the last live tile: the same bits
        tile = min(512, -(-m // 8) * 8)
        cut = -(-live // tile) * tile
        if 0 < cut < m:
            short = run(lambda a, b: grouped_matmul(a, b, gs), lhs[:cut],
                        weight[:cut])
            assert jnp.array_equal(got[:live], short[:live])
            assert float(jnp.abs(short[live:]).max(initial=0.0)) == 0.0
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("case", sorted(TAILS))
def test_tail_tiles_is_the_tiles_wholly_behind_the_groups(case):
    from chainermn_tpu.ops.grouped_matmul import tail_tiles

    m, _, _, sizes = TAILS[case]
    tile = min(512, -(-m // 8) * 8)
    behind = [t for t in range(-(-m // tile)) if t * tile >= sum(sizes)]
    assert int(tail_tiles(jnp.array(sizes, jnp.int32), m)) == len(behind)


@pytest.mark.parametrize("batch", [2, 16])
def test_the_losss_metrics_count_the_rows_that_reached_a_held_expert(
        batch, tiny):
    """``moe/rows_held`` is the rows whose expert is held, summed over the
    four expert layers, as each layer's own routing counts them;
    ``moe/dropped`` counts a held row that falls out of its group;
    ``moe/tail_tiles`` the row tiles behind the last held group (with 16
    sequences a layer routes 2,048 rows, four tiles of 512, about half of
    them to a held expert)."""
    params, state, _ = tiny
    tokens = jax.random.randint(jax.random.key(0), (batch, T), 0,
                                TINY["vocab_size"])
    _, metrics = _system_loss(params, state, tokens)
    _, sown = _model().apply({"params": params, ROUTER_STATE: state}, tokens,
                             mutable=["moe_aux"])
    rows = batch * T * 2
    tile = min(512, rows)
    want = tiles = 0
    for i in range(1, 5):
        held = float(sown["moe_aux"][f"block_{i}"]["rows_held"][0])
        want += held
        tiles += rows // tile - -(-int(held) // tile)
    assert float(metrics["moe/rows_held"]) == want
    assert 0 < want < 4 * rows
    assert float(metrics["moe/expert_load"].sum()) == want
    assert metrics["moe/expert_load"].shape == (4,)
    assert float(metrics["moe/dropped"]) == 0.0
    assert float(metrics[train_path.MOE_TAIL_TILES]) == tiles
    assert (tiles > 0) == (batch == 16)


@pytest.mark.parametrize("n", [1, 3])
def test_dropped_counts_held_rows_that_lie_in_no_group(n, tiny,
                                                       monkeypatch):
    params, state, tokens = tiny
    real = moe.dropless_topk

    def loses_rows(*a, **kw):
        r = real(*a, **kw)
        last = jnp.argmax(r.group_sizes >= n)
        return r._replace(group_sizes=r.group_sizes.at[last].add(-n))

    monkeypatch.setattr(moe, "dropless_topk", loses_rows)
    _, metrics = _system_loss(params, state, tokens)
    assert float(metrics["moe/dropped"]) == 4 * n
    assert float(metrics["moe/rows_held"]) \
        - float(metrics["moe/expert_load"].sum()) == 4 * n


# -- scopes and gauges -------------------------------------------------------

def _gauge(name):
    rows = registry().snapshot()[name]["values"]
    return {tuple(sorted(r["labels"].items())): r["value"] for r in rows}


#: the tiny preset at a width the short convolution's kernels tile (128
#: lanes; 64 positions are four sublane tiles of float32), under remat
TILING = {**TINY, "hidden_size": 128}


def _call_sites(text, callee):
    """The name stacks of the lowered module's calls of the functions named
    ``callee`` (a jit of its own is a function of the module, and its ops
    carry their names from the call's on)."""
    locs = dict(re.findall(r"^(#loc\d+) = loc\((.*)\)$", text, re.M))
    return [locs[m.group(1)] for m in re.finditer(
        rf"call @{callee}\w*\(.*loc\((#loc\d+)\)", text)]


@pytest.mark.parametrize("which", ["tiny", "tiling"])
def test_the_short_conv_scope_and_the_new_gauges_appear(which, tiny):
    if which == "tiny":
        config, model_kw = TINY, {}
        params, state, tokens = tiny
    else:
        config, model_kw = TILING, {"remat": True}
        params, state, tokens = _init(TILING)

    def loss(p):
        return lm_loss_moe(_model(config, **model_kw), p, tokens,
                           n_chunks=2, load_balance_coef=0.0,
                           z_loss_coef=0.0, router_state=state)[0]

    lowered = jax.jit(jax.grad(loss)).lower(params)
    text = lowered.as_text(debug_info=True)
    assert train_path.SHORT_CONV == "short_conv"
    assert _gauge(train_path.MOE_EXPERTS_HELD) == {(): 4.0}
    assert _gauge(train_path.MOE_EXPERTS_TOTAL) == {(): 8.0}
    assert _gauge(train_path.STACK_LAYERS_BY_KIND) == {
        (("kind", "attention"),): 1.0, (("kind", "short_conv"),): 4.0,
        (("kind", "latent_attention"),): 0.0,
        (("kind", "dense_ffn"),): 1.0, (("kind", "expert_ffn"),): 4.0}
    # the projections are matmuls outside the scope
    assert not any("dot_general" in line and "/short_conv/" in line
                   and "conv_in" in line for line in text.splitlines())
    if which == "tiny":
        # a width of 64 does not tile: the plain spelling, under the scope
        assert _gauge(train_path.SHORT_CONV_FUSED) == {(): 0.0}
        assert "/short_conv/" in text
        # the backward of the scope is under it too
        assert any("transpose(" in line and "/short_conv/" in line
                   for line in text.splitlines())
        return
    assert _gauge(train_path.SHORT_CONV_FUSED) == {(): 1.0}
    # both kernels are named and lie under the scope ...
    for kernel in (short_conv.FWD, short_conv.BWD):
        assert f'"short_conv/{kernel}/pallas_call"' in text
    # ... in functions of their own, called forward, recomputed and
    # transposed: four layers of each
    sites = _call_sites(text, "_fused")
    recomputed = [s for s in sites if train_path.REMAT_MARKER in s]
    transposed = [s for s in sites if train_path.BACKWARD_MARKER in s
                  and train_path.REMAT_MARKER not in s]
    assert (len(sites), len(recomputed), len(transposed)) == (12, 4, 4)
    assert all("_short_conv/jit(_fused)" in s for s in sites)


def test_a_model_without_a_description_by_layer_counts_its_kinds():
    gpt2 = TransformerLM(vocab_size=32, num_layers=3, num_heads=2,
                         d_model=16, d_ff=32, max_len=8)
    jax.eval_shape(lambda: gpt2.init(jax.random.key(0),
                                     jnp.zeros((1, 8), jnp.int32)))
    assert _gauge(train_path.STACK_LAYERS_BY_KIND) == {
        (("kind", "attention"),): 3.0, (("kind", "short_conv"),): 0.0,
        (("kind", "latent_attention"),): 0.0,
        (("kind", "dense_ffn"),): 3.0, (("kind", "expert_ffn"),): 0.0}


# -- the model description ---------------------------------------------------

def test_the_description_reads_lfm2s_config_json():
    config = MODEL_CONFIGS["lfm2-8b-a1b"]
    arch = Architecture.from_config(config)
    assert arch == Architecture(
        norm="rmsnorm", norm_eps=1e-5, ffn="gated_silu", qk_norm="head",
        positions="rope", rope_base=1000000.0, tied_head=True,
        n_experts=32, experts_per_token=4, expert_width=1792,
        renormalise_gates=True, router_score="sigmoid", router_bias=True,
        gate_eps=1e-6, routed_scaling=1.0, conv_width=3,
        layers=tuple(
            ("short_conv" if kind == "conv" else "attention",
             "dense" if i < 2 else "experts")
            for i, kind in enumerate(config["layer_types"])))
    assert arch.experts_held is None and arch.n_experts_held == 32
    assert [m for m, _ in arch.layers].count("attention") == 6
    assert arch.router_kwargs() == {"score": "sigmoid", "gate_eps": 1e-6}
    model = lm_from_config(config)
    assert (model.num_layers, model.d_model, model.num_heads,
            model.num_kv_heads, model.d_ff, model.vocab_size,
            model.max_len) == (24, 2048, 32, 8, 7168, 65536, 128000)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    n = sum(x.size for x in jax.tree.leaves(shapes["params"]))
    assert n == 8_339_929_856  # the model card's 8.3B
    assert sorted(shapes[ROUTER_STATE]) == sorted(
        f"block_{i}" for i in range(2, 24))
    # the first layers of a stack described layer by layer
    short = lm_from_config(config, num_layers=3)
    assert short.arch.layers == arch.layers[:3]
    with pytest.raises(ValueError, match="exceeds"):
        lm_from_config(config, num_layers=25)


def test_olmoes_and_a_share_free_router_take_no_new_argument():
    """What the tools that wrap ``dropless_topk`` with its old signature
    rely on: a softmax router over experts that are all held is called as
    it always was."""
    assert Architecture.from_config(
        MODEL_CONFIGS["olmoe-1b-7b"]).router_kwargs() == {}
    assert Architecture.from_config(TINY).router_kwargs() == {
        "score": "sigmoid", "gate_eps": 1e-6, "held": (2, 6)}
    assert Architecture.from_config(
        {**TINY, "routed_scaling_factor": 2.5}).router_kwargs()[
        "scale"] == 2.5


@pytest.mark.parametrize("bad, match", [
    (dict(conv_bias=True), "not built"),
    (dict(layer_types=["conv", "sliding_attention", "conv", "conv",
                       "conv"]), "sliding_attention"),
    (dict(layer_types=["conv"]), "not built"),
    (dict(rope_scaling={"factor": 2}), "not built"),
    (dict(experts_held_range=[0, 3]), "share"),
    (dict(experts_published=None, num_experts=8), "share"),
])
def test_a_config_the_stack_cannot_express_is_refused(bad, match):
    config = {k: v for k, v in {**TINY, **bad}.items() if v is not None}
    with pytest.raises(ValueError, match=match):
        Architecture.from_config(config)


@pytest.mark.parametrize("bad", [
    dict(qk_norm="rows"), dict(router_score="tanh"),
    dict(n_experts=8, experts_per_token=2, expert_width=4,
         ffn="gated_silu", experts_held=(4, 9)),
    dict(layers=(("attention", "mlp"),)),
    dict(layers=(("lstm", "dense"),)),
    dict(layers=(("short_conv", "dense"),)),             # no conv_width
    dict(layers=(("attention", "experts"),)),            # no experts
    dict(layers=(("attention", "dense"),), post_norm=True),
    dict(layers=(("attention", "dense"),), exit_gate=True),
])
def test_a_description_that_names_no_stack_is_refused(bad):
    with pytest.raises(ValueError):
        Architecture(**bad)


def test_the_stack_and_the_model_must_count_the_same_layers():
    arch = Architecture(layers=(("attention", "dense"),) * 2)
    sizes = dict(vocab_size=32, num_heads=2, d_model=16, d_ff=32, max_len=8)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="2 layers"):
        TransformerLM(**sizes, num_layers=3, arch=arch).init(
            jax.random.key(0), tokens)
    with pytest.raises(ValueError, match="looped"):
        TransformerLM(**sizes, num_layers=2, arch=arch,
                      total_ut_steps=2).init(jax.random.key(0), tokens)


def test_a_router_with_a_bias_is_applied_with_its_state(tiny):
    params, _, tokens = tiny
    with pytest.raises(ValueError, match="router_state"):
        _model().apply({"params": params}, tokens)


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
#: a stack with short convolutions (and a share), and plain attention
#: layers whose experts are a share
UNBUILT = {
    "short_conv": (TINY, "short_conv layers"),
    "share": ({**TINY, "num_hidden_layers": 2, "num_dense_layers": 1,
               "layer_types": ["full_attention"] * 2}, "holds experts"),
}


@pytest.mark.parametrize("entry", sorted(ENTRY_POINTS))
@pytest.mark.parametrize("kind", sorted(UNBUILT))
def test_decoding_and_serving_refuse_what_they_do_not_build(kind, entry):
    config, message = UNBUILT[kind]
    params, state, _ = _init(config)
    model = _model(config, return_hidden=False)
    prompt = jnp.ones((2, 4), jnp.int32)
    with pytest.raises(NotImplementedError, match=message):
        ENTRY_POINTS[entry](model, {"params": params, ROUTER_STATE: state},
                            prompt)


# -- what the other models keep ----------------------------------------------

TINY_OTHERS = {
    "gpt2": {"model_type": "gpt2", "n_layer": 2, "n_embd": 32, "n_head": 2,
             "n_inner": 64, "n_positions": 16, "vocab_size": 96},
    "olmoe": dict(
        MODEL_CONFIGS["olmoe-1b-7b"], num_hidden_layers=2, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=4, intermediate_size=32,
        num_experts=8, num_experts_per_tok=2, vocab_size=128,
        max_position_embeddings=32),
    "ouro": dict(
        MODEL_CONFIGS["ouro-2.6b"], num_hidden_layers=2, hidden_size=64,
        num_attention_heads=2, num_key_value_heads=2, head_dim=32,
        intermediate_size=96, vocab_size=128, max_position_embeddings=32,
        total_ut_steps=3),
}
#: leaf names and shapes of those three at the parent commit (248ce0b),
#: from a checkout of it; a block's are every block's. And the bits of
#: ``dropless_topk(normal(key 2, [6, 8]), normal(key 3, [8, 5]), 2)``
#: there, float32 read as int32
PINNED_TREES = {'gpt2': {'top': {"['LayerNorm_0']['bias']": (32,),
                  "['LayerNorm_0']['scale']": (32,),
                  "['pos_emb']": (16, 32),
                  "['tok_emb']['embedding']": (96, 32)},
          'block': {"['LayerNorm_0']['bias']": (32,),
                    "['LayerNorm_0']['scale']": (32,),
                    "['LayerNorm_1']['bias']": (32,),
                    "['LayerNorm_1']['scale']": (32,),
                    "['ff_down']['bias']": (32,),
                    "['ff_down']['kernel']": (64, 32),
                    "['ff_up']['bias']": (64,),
                    "['ff_up']['kernel']": (32, 64),
                    "['proj']['kernel']": (32, 32),
                    "['qkv']['kernel']": (32, 96)}},
 'olmoe': {'top': {"['RMSNorm_0']['scale']": (64,),
                   "['lm_head']['embedding']": (128, 64),
                   "['tok_emb']['embedding']": (128, 64)},
           'block': {"['RMSNorm_0']['scale']": (64,),
                     "['RMSNorm_1']['scale']": (64,),
                     "['k_norm']['scale']": (64,),
                     "['moe_router']": (64, 8),
                     "['moe_w_down']": (8, 32, 64),
                     "['moe_w_gate_up']": (8, 64, 64),
                     "['proj']['kernel']": (64, 64),
                     "['q_norm']['scale']": (64,),
                     "['qkv']['kernel']": (64, 192)}},
 'ouro': {'top': {"['RMSNorm_0']['scale']": (64,),
                  "['exit_gate']['bias']": (1,),
                  "['exit_gate']['kernel']": (64, 1),
                  "['lm_head']['embedding']": (128, 64),
                  "['tok_emb']['embedding']": (128, 64)},
          'block': {"['RMSNorm_0']['scale']": (64,),
                    "['RMSNorm_1']['scale']": (64,),
                    "['attn_out_norm']['scale']": (64,),
                    "['ff_down']['kernel']": (96, 64),
                    "['ff_gate']['kernel']": (64, 96),
                    "['ff_up']['kernel']": (64, 96),
                    "['ffn_out_norm']['scale']": (64,),
                    "['proj']['kernel']": (64, 64),
                    "['qkv']['kernel']": (64, 192)}}}
PINNED_TOPK = {'as_they_are': {'gates': [[1055976191, 1051464037],
                           [1061385612, 1041953219],
                           [1054283644, 1049426929],
                           [1061910079, 1044172138],
                           [1065041062, 1014276400],
                           [1059408017, 1050316775]],
                 'experts': [[2, 3], [0, 4], [1, 0], [2, 1], [3, 1],
                             [3, 1]],
                 'order': [2, 5, 4, 7, 9, 11, 0, 6, 1, 8, 10, 3],
                 'inverse': [6, 8, 0, 11, 2, 1, 7, 3, 9, 4, 10, 5],
                 'group_sizes': [2, 4, 2, 3, 1],
                 'logits': [[-1069278372, 1054447068, 1068311651,
                             1065488322, -1064690884],
                            [1080558510, -1067446155, -1073271726,
                             1068668672, 1073769695],
                            [1053069482, 1062104029, -1085970697,
                             -1070472670, 1036081708],
                            [-1081584950, 1081021633, 1084640123,
                             1069376304, -1062122695],
                            [-1079198684, 1074077818, 1054256928,
                             1086881826, -1076586808],
                            [-1081867978, 1076460713, 1060669281,
                             1079649278, -1069001578]]},
 'renormalised': {'gates': [[1058363096, 1054167633],
                            [1062578250, 1042898647],
                            [1058710471, 1053472884],
                            [1062194009, 1044435611],
                            [1065101791, 1014335532],
                            [1060007995, 1050877835]],
                  'experts': [[2, 3], [0, 4], [1, 0], [2, 1], [3, 1],
                              [3, 1]],
                  'order': [2, 5, 4, 7, 9, 11, 0, 6, 1, 8, 10, 3],
                  'inverse': [6, 8, 0, 11, 2, 1, 7, 3, 9, 4, 10, 5],
                  'group_sizes': [2, 4, 2, 3, 1],
                  'logits': [[-1069278372, 1054447068, 1068311651,
                              1065488322, -1064690884],
                             [1080558510, -1067446155, -1073271726,
                              1068668672, 1073769695],
                             [1053069482, 1062104029, -1085970697,
                              -1070472670, 1036081708],
                             [-1081584950, 1081021633, 1084640123,
                              1069376304, -1062122695],
                             [-1079198684, 1074077818, 1054256928,
                              1086881826, -1076586808],
                             [-1081867978, 1076460713, 1060669281,
                              1079649278, -1069001578]]}}


@pytest.mark.parametrize("name", sorted(TINY_OTHERS))
def test_the_other_models_parameter_trees_are_the_parents(name):
    model = lm_from_config(TINY_OTHERS[name])
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    assert set(shapes) <= {"params", "moe_aux"}  # no router state
    got = {jax.tree_util.keystr(p): s.shape for p, s in
           jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
    pinned = PINNED_TREES[name]
    want = dict(pinned["top"])
    for i in range(2):
        want.update({f"['block_{i}']{k}": v
                     for k, v in pinned["block"].items()})
    assert got == want


@pytest.mark.parametrize("gates", sorted(PINNED_TOPK))
def test_a_softmax_router_over_held_experts_returns_the_parents_bits(gates):
    u = jax.random.normal(jax.random.key(2), (6, 8))
    w = jax.random.normal(jax.random.key(3), (8, 5))
    r = moe.dropless_topk(u, w, 2, gates == "renormalised")
    for field, want in PINNED_TOPK[gates].items():
        got = np.asarray(getattr(r, field))
        if got.dtype == np.float32:
            got = got.view(np.int32)
        assert got.tolist() == want, field
    assert int(r.rows_held) == 12
    # and spelled out, the defaults are the same call
    again = moe.dropless_topk(u, w, 2, gates == "renormalised",
                              score="softmax", select_bias=None,
                              gate_eps=0.0, scale=1.0, held=(0, 5))
    for a, b in zip(r, again):
        assert (np.asarray(a) == np.asarray(b)).all()
