"""The OLMoE-class block (ISSUE 25): the model description, dropless
top-k routing, the grouped matmul and the loss with the router's
auxiliary terms, against the plain reference the benchmark holds the
system to (``benchmark/reference/moe_lm.py``), at a tiny size in float32
on the CPU (kernels interpreted)."""

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
    head_table,
    lm_from_config,
    lm_loss_fused,
    lm_loss_moe,
)
from chainermn_tpu.observability import train_path
from chainermn_tpu.ops.grouped_matmul import grouped_matmul, tail_tiles
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
    return _load("benchmark/reference/moe_lm.py", "reference_moe_lm")


#: the tiny preset: 2 layers, d 64, 4 heads of 16, 8 experts of width 32,
#: top-2, T 32, vocabulary 128; the reference reads the same dict
TINY = dict(
    MODEL_CONFIGS["olmoe-1b-7b"], num_hidden_layers=2, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=4, intermediate_size=32,
    num_experts=8, num_experts_per_tok=2, vocab_size=128,
    max_position_embeddings=32,
    assumed={"router_aux_loss_coef": 0.01, "router_z_loss_coef": 0.001},
)
COEFS = dict(load_balance_coef=0.01, z_loss_coef=0.001)


def _model(config=TINY, **kw):
    return lm_from_config(config, compute_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def tiny():
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 128)
    params = _model().init(jax.random.key(1), tokens)["params"]
    return params, tokens


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _system_loss(params, tokens, config=TINY, **coefs):
    model = _model(config, return_hidden=True)
    return lm_loss_moe(model, params, tokens, n_chunks=2,
                       **{**COEFS, **coefs})


# -- the system against the reference ------------------------------------

def test_logits_match_the_reference(tiny, ref):
    params, tokens = tiny
    got = _model().apply({"params": params}, tokens)
    want = _highest(ref.logits, params, tokens, TINY)
    assert got.shape == (2, 32, 128)
    assert _rel(got, want) < 1e-5


def test_loss_and_every_gradient_leaf_match_the_reference(tiny, ref):
    params, tokens = tiny
    (loss, metrics), grads = jax.value_and_grad(
        _system_loss, has_aux=True)(params, tokens)
    want, want_grads = _highest(
        jax.value_and_grad(lambda p: ref.loss(p, (), tokens, TINY)), params)
    assert abs(float(loss) - float(want)) / float(want) < 1e-5
    flat = jax.tree_util.tree_flatten_with_path(want_grads)[0]
    assert len(flat) == len(jax.tree.leaves(grads)) == 21
    for (path, w), g in zip(flat, jax.tree.leaves(grads)):
        assert _rel(g, w) < 1e-5, jax.tree_util.keystr(path)
    assert float(metrics["moe/dropped"]) == 0.0
    assert float(metrics["moe/expert_load"].sum()) == 2 * 2 * 32 * 2
    assert metrics["moe/expert_load_max_over_mean"] >= 1.0
    # every expert held and the rows fill their one tile: no tail
    assert float(metrics[train_path.MOE_TAIL_TILES]) == 0.0


def _bf16_router(real):
    def dropless_topk(u, router_w, k, renormalise=False):
        return real(u.astype(jnp.bfloat16).astype(jnp.float32),
                    router_w.astype(jnp.bfloat16).astype(jnp.float32), k,
                    renormalise)
    return dropless_topk


def _drop_a_token(real):
    def combine(y, routing):
        return real(y, routing._replace(
            gates=routing.gates.at[0].set(0.0)))
    return combine


def _rows_in_no_group(n):
    """What a capacity does to its overflow: the last ``n`` rows of the
    last expert that has them lie in no group (the grouped matmul gives
    rows past the groups zeros)."""
    def mutate(real):
        def dropless_topk(u, router_w, k, renormalise=False):
            r = real(u, router_w, k, renormalise)
            last = r.group_sizes.shape[0] - 1 - jnp.argmax(
                r.group_sizes[::-1] >= n)
            return r._replace(group_sizes=r.group_sizes.at[last].add(-n))
        return dropless_topk
    return mutate


#: what the comparison must catch, each a change to the system alone
MUTATIONS = {
    "gates_renormalised": dict(config={**TINY, "norm_topk_prob": True}),
    "top_k_less_one": dict(config={**TINY, "num_experts_per_tok": 1}),
    "load_balance_left_out": dict(coefs={"load_balance_coef": 0.0}),
    "z_loss_left_out": dict(coefs={"z_loss_coef": 0.0}),
    "a_dropped_token": dict(patch=("combine", _drop_a_token)),
    "a_row_in_no_group": dict(patch=("dropless_topk", _rows_in_no_group(1))),
    "bf16_router": dict(patch=("dropless_topk", _bf16_router)),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_the_comparison_catches(name, tiny, ref, monkeypatch):
    """Each departure from the equations moves the loss or a gradient
    leaf far past the 1e-5 the faithful system keeps to."""
    params, tokens = tiny
    m = MUTATIONS[name]
    if "patch" in m:
        attr, mutate = m["patch"]
        monkeypatch.setattr(moe, attr, mutate(getattr(moe, attr)))
    (loss, _), grads = jax.value_and_grad(
        lambda p, t: _system_loss(p, t, m.get("config", TINY),
                                  **m.get("coefs", {})),
        has_aux=True)(params, tokens)
    want, want_grads = _highest(
        jax.value_and_grad(lambda p: ref.loss(p, (), tokens, TINY)), params)
    worst = max(_rel(g, w) for g, w in zip(
        jax.tree.leaves(grads), jax.tree.leaves(want_grads)))
    loss_err = abs(float(loss) - float(want)) / float(want)
    assert max(worst, loss_err) > 1e-3, (loss_err, worst)


@pytest.mark.parametrize("n", [1, 3])
def test_dropped_counts_the_rows_that_lie_in_no_group(n, tiny, monkeypatch):
    """``moe/dropped`` is counted from the group sizes the experts are
    given, a layer at a time: 0 for the path as it is (asserted with the
    gradients above), ``n`` a layer once ``n`` rows fall out of them."""
    params, tokens = tiny
    monkeypatch.setattr(moe, "dropless_topk",
                        _rows_in_no_group(n)(moe.dropless_topk))
    _, metrics = _system_loss(params, tokens)
    assert float(metrics["moe/dropped"]) == n * TINY["num_hidden_layers"]
    assert float(metrics["moe/expert_load"].sum()) == \
        (2 * 32 * 2 - n) * TINY["num_hidden_layers"]


# -- routing ----------------------------------------------------------------

def test_gates_are_the_softmax_probabilities_not_renormalised():
    u = jax.random.normal(jax.random.key(2), (64, 16))
    w = jax.random.normal(jax.random.key(3), (16, 8))
    r = moe.dropless_topk(u, w, 3)
    probs = jax.nn.softmax(u @ w, axis=-1)
    want = jnp.sort(probs, axis=-1)[:, ::-1][:, :3]
    np.testing.assert_allclose(r.gates, want, rtol=1e-5)
    assert float(r.gates.sum(-1).min()) < 0.9  # not 1
    renorm = moe.dropless_topk(u, w, 3, renormalise=True)
    np.testing.assert_allclose(renorm.gates.sum(-1), 1.0, rtol=1e-6)
    assert (np.asarray(renorm.experts) == np.asarray(r.experts)).all()


def test_ties_go_to_the_lower_expert_index():
    r = moe.dropless_topk(jnp.ones((4, 8)), jnp.zeros((8, 6)), 2)
    assert (np.asarray(r.experts) == [0, 1]).all()


@pytest.mark.parametrize("k", [1, 2, 4])
def test_every_row_is_computed_exactly_once_when_one_expert_takes_all(k):
    """A router that sends every token to the same ``k`` experts: the
    group sizes sum to ``tokens * k``, the sorted rows are a permutation,
    and dispatch -> experts -> combine still equals the dense sum."""
    T, D, E, F = 24, 16, 6, 8
    u = jax.random.normal(jax.random.key(4), (T, D))
    # expert e's score is bias_e, whatever the token: the top k are 5, 4..
    router = jnp.zeros((D, E)).at[0].set(jnp.arange(E) * 3.0)
    u = u.at[:, 0].set(1.0)
    r = moe.dropless_topk(u, router, k)
    sizes = np.asarray(r.group_sizes)
    assert sizes.sum() == T * k
    assert (sizes[E - k:] == T).all() and (sizes[:E - k] == 0).all()
    assert sorted(np.asarray(r.order)) == list(range(T * k))
    assert (np.asarray(r.order)[np.asarray(r.inverse)]
            == np.arange(T * k)).all()
    w = jax.random.normal(jax.random.key(5), (E, D, F))
    out = moe.combine(grouped_matmul(moe.dispatch(u, r), w, r.group_sizes),
                      r)
    want = sum(r.gates[:, s, None] * jnp.einsum(
        "td,tdf->tf", u, w[r.experts[:, s]]) for s in range(k))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def test_dispatch_and_combine_are_differentiable_in_rows_and_gates():
    T, D, E, k = 16, 8, 4, 2
    u = jax.random.normal(jax.random.key(6), (T, D))
    router = jax.random.normal(jax.random.key(7), (D, E))
    scale = jnp.arange(1.0, E + 1)

    def via_routing(u, router):
        r = moe.dropless_topk(u, router, k)
        rows = moe.dispatch(u, r)
        # "expert" e multiplies its rows by e + 1
        per_row = jnp.repeat(scale, r.group_sizes, total_repeat_length=T * k)
        return (moe.combine(rows * per_row[:, None], r) ** 2).sum()

    def dense(u, router):
        probs = jax.nn.softmax(u @ router, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)
        y = sum(gates[:, s, None] * scale[idx[:, s], None] * u
                for s in range(k))
        return (y ** 2).sum()

    got = jax.grad(via_routing, (0, 1))(u, router)
    want = jax.grad(dense, (0, 1))(u, router)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("k", [1, 2, 8])
def test_load_balancing_loss_is_the_papers_form(k):
    """``E * sum_e f_e P_e`` with ``f_e`` the share of tokens that hold
    ``e`` among their ``k`` (so the shares sum to ``k``), by hand."""
    logits = jax.random.normal(jax.random.key(8), (50, 16)) * 2
    probs = np.asarray(jax.nn.softmax(logits, -1))
    top = np.argsort(-probs, axis=-1, kind="stable")[:, :k]
    f = np.zeros(16)
    for row in top:
        f[row] += 1
    f /= 50
    assert f.sum() == pytest.approx(k)
    want = 16 * (f * probs.mean(0)).sum()
    got = moe.load_balancing_loss(logits, k=k)
    assert float(got) == pytest.approx(want, rel=1e-5)


# -- the grouped matmul -----------------------------------------------------

def _by_group(lhs, rhs, sizes):
    out, start = [], 0
    for e, n in enumerate(sizes):
        out.append(lhs[start:start + n] @ rhs[e])
        start += n
    out.append(jnp.zeros((lhs.shape[0] - start, rhs.shape[2]), lhs.dtype))
    return jnp.concatenate(out)


GROUPS = {
    "uneven_with_empty": (40, 16, 24, [0, 7, 0, 20, 13, 0]),
    "tiles_straddle_groups": (1100, 128, 256, [0, 600, 0, 1, 499, 0, 0, 0]),
    "one_group_takes_all": (1024, 64, 128, [1024, 0]),
    "rows_past_the_groups": (1030, 64, 128, [500, 500]),
    "single_rows": (16, 8, 8, [1] * 16),
    # a tail behind the groups (ISSUE 44): its tiles are written, not
    # multiplied; opening on a tile boundary, inside the last group's tile,
    # behind an empty last group, behind nothing, inside the one tile
    "tail_opens_on_a_tile_boundary": (2048, 64, 128, [512, 512]),
    "tail_shares_the_last_groups_tile": (2048, 64, 128, [300, 400]),
    "tail_behind_an_empty_last_group": (2048, 64, 128, [700, 0]),
    "every_group_empty": (1536, 64, 128, [0, 0, 0]),
    "fewer_rows_than_a_tile": (100, 16, 24, [7, 20]),
}


@pytest.mark.parametrize("case", sorted(GROUPS))
@pytest.mark.parametrize("what", ["forward", "grad_lhs", "grad_rhs"])
def test_grouped_matmul_against_a_loop_over_groups(case, what):
    m, k, n, sizes = GROUPS[case]
    lhs = jax.random.normal(jax.random.key(9), (m, k))
    rhs = jax.random.normal(jax.random.key(10), (len(sizes), k, n))
    weight = jax.random.normal(jax.random.key(11), (m, n))
    gs = jnp.array(sizes, jnp.int32)
    if what == "forward":
        got = grouped_matmul(lhs, rhs, gs)
        want = _by_group(lhs, rhs, sizes)
    else:
        arg = 0 if what == "grad_lhs" else 1
        got = jax.grad(lambda a, b: (grouped_matmul(a, b, gs) * weight).sum(),
                       arg)(lhs, rhs)
        want = jax.grad(lambda a, b: (_by_group(a, b, sizes) * weight).sum(),
                        arg)(lhs, rhs)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("case", sorted(GROUPS))
def test_tail_tiles_counts_the_tiles_behind_the_last_group(case):
    m, _, _, sizes = GROUPS[case]
    tile = 512 if m >= 512 else -(-m // 8) * 8
    behind = [t for t in range(-(-m // tile)) if t * tile >= sum(sizes)]
    got = tail_tiles(jnp.array(sizes, jnp.int32), m)
    assert got.dtype == jnp.int32 and int(got) == len(behind)


@pytest.mark.parametrize("case", sorted(GROUPS))
def test_a_tail_item_fetches_no_tile_of_lhs(case):
    """The block of ``lhs`` follows an item's tile while the item is a
    group's; over the tail's items it stays on the groups' last tile, so
    Pallas copies nothing for them (the block index does not change)."""
    from chainermn_tpu.ops import grouped_matmul as gm

    m, _, _, sizes = GROUPS[case]
    tile = 512 if m >= 512 else -(-m // 8) * 8
    rows = -(-m // tile) * tile
    group_of, tile_of, _, _, total = gm._plan(
        jnp.array(sizes, jnp.int32), rows, tile, cover_tail=True)
    read = np.asarray(gm._tiles_read(group_of, tile_of, len(sizes)))
    group_of, tile_of = np.asarray(group_of), np.asarray(tile_of)
    held = group_of < len(sizes)
    assert (read[held] == tile_of[held]).all()
    assert held[0] and (read[~held] == tile_of[held][-1]).all()
    # every tile is still visited, the tail's once each, in order
    assert sorted(set(tile_of[:int(total[0])])) == list(range(rows // tile))
    tails = tile_of[:int(total[0])][~held[:int(total[0])]]
    assert (np.diff(tails) == 1).all() and tails[-1] == rows // tile - 1


def test_grouped_matmul_bf16_operands_keep_f32_weights_gradient():
    lhs = jax.random.normal(jax.random.key(12), (64, 32), jnp.bfloat16)
    rhs = jax.random.normal(jax.random.key(13), (4, 32, 16), jnp.float32)
    gs = jnp.array([10, 0, 50, 4], jnp.int32)
    out, (dl, dr) = jax.value_and_grad(
        lambda a, b: grouped_matmul(a, b, gs).astype(jnp.float32).sum(),
        (0, 1))(lhs, rhs)
    assert (dl.dtype, dr.dtype) == (jnp.bfloat16, jnp.float32)
    assert float(jnp.abs(dr[1]).max()) == 0.0  # the empty group's block
    with pytest.raises(ValueError, match="group_sizes"):
        grouped_matmul(lhs, rhs, gs[:3])


# -- the model description --------------------------------------------------

def test_the_description_reads_olmoes_config_json():
    arch = Architecture.from_config(MODEL_CONFIGS["olmoe-1b-7b"])
    assert arch == Architecture(
        norm="rmsnorm", norm_eps=1e-5, ffn="gated_silu", qk_norm=True,
        positions="rope", rope_base=10000.0, tied_head=False, n_experts=64,
        experts_per_token=8, expert_width=1024, renormalise_gates=False)
    model = lm_from_config(MODEL_CONFIGS["olmoe-1b-7b"], num_layers=1)
    assert (model.num_layers, model.d_model, model.num_heads,
            model.vocab_size, model.max_len) == (1, 2048, 16, 50304, 4096)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros((1, 8), jnp.int32))["params"])
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == 625_616_896  # one layer 419.6M, embedding + head 206.0M


@pytest.mark.parametrize("bad", [
    dict(norm="batchnorm"), dict(ffn="relu"), dict(positions="alibi"),
    dict(n_experts=8), dict(n_experts=8, experts_per_token=9,
                            expert_width=4, ffn="gated_silu"),
])
def test_a_description_that_names_no_block_is_refused(bad):
    with pytest.raises(ValueError):
        Architecture(**bad)


def test_a_config_the_block_cannot_express_is_refused():
    with pytest.raises(ValueError, match="not built"):
        Architecture.from_config(
            {**MODEL_CONFIGS["olmoe-1b-7b"], "clip_qkv": 8.0})
    with pytest.raises(ValueError, match="model_type"):
        Architecture.from_config({"model_type": "mamba"})


def test_positions_come_from_the_description_alone():
    """``pos_encoding`` is the input of a model without a description;
    given one, the model reads its ``positions`` and nothing else."""
    sizes = dict(vocab_size=32, num_layers=1, num_heads=2, d_model=16,
                 d_ff=32, max_len=8)
    tokens = jnp.zeros((1, 8), jnp.int32)
    rope = TransformerLM(**sizes, arch=Architecture(positions="rope"))
    assert "pos_emb" not in rope.init(jax.random.key(0), tokens)["params"]
    learned = TransformerLM(**sizes, pos_encoding="rope",
                            arch=Architecture())
    assert "pos_emb" in learned.init(jax.random.key(0), tokens)["params"]
    with pytest.raises(ValueError, match="pos_encoding"):
        TransformerLM(**sizes, pos_encoding="alibi").init(
            jax.random.key(0), tokens)


@pytest.mark.parametrize("name", sorted(MODEL_CONFIGS))
def test_a_named_model_is_the_benchmarks_configuration(name):
    """``MODEL_CONFIGS`` (what ``--model`` of the example builds) and the
    benchmark's configuration file are two copies of one published
    ``config.json``: every key of the first is in the second with the
    same value, but for the keys the file lists as ``reduced``
    (``model_type`` is GPT-2's where a file has none), and both describe
    the same block."""
    import json

    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        bench = json.load(f)
    ours = MODEL_CONFIGS[name]
    differ = {k for k in ours
              if bench.get(k, "gpt2" if k == "model_type" else KeyError)
              != ours[k]}
    assert differ <= set(bench["reduced"]), {
        k: (ours[k], bench.get(k)) for k in differ}
    # the same block: a description reads no reduced key but where the
    # stack goes layer by layer or the file holds a share of the experts
    # (LFM2's), and with the published values back they agree there too
    published = {k: v for k, v in {**bench, **{
        k: ours[k] for k in bench["reduced"]}}.items()
        if k not in ("experts_published", "experts_held_range")}
    assert Architecture.from_config(published) == \
        Architecture.from_config(ours)


GPT2_TINY = dict(vocab_size=96, num_layers=2, num_heads=2, d_model=32,
                 d_ff=64, max_len=16)


def _gpt2_loss(model):
    tokens = jax.random.randint(jax.random.key(20), (2, 16), 0, 96)
    params = model.init(jax.random.key(21), tokens)["params"]
    hidden = model.apply({"params": params}, tokens)
    return params, lm_loss_fused(hidden, head_table(params), tokens,
                                 n_chunks=2)


def test_gpt2_is_one_instance_and_its_tree_and_loss_are_the_parents():
    """No description, the default description and GPT-2's ``config.json``
    build the same model: the parameter tree the parent commit (f364f40)
    built and, on a seed, its loss to the bit (pinned from a checkout of
    the parent, float32 on the CPU)."""
    built = [
        TransformerLM(**GPT2_TINY, return_hidden=True),
        TransformerLM(**GPT2_TINY, return_hidden=True, arch=Architecture()),
        lm_from_config({"model_type": "gpt2", "n_layer": 2, "n_embd": 32,
                        "n_head": 2, "n_inner": 64, "n_positions": 16,
                        "vocab_size": 96}, return_hidden=True),
    ]
    results = [_gpt2_loss(m) for m in built]
    params, loss = results[0]
    paths = sorted(jax.tree_util.keystr(p) + str(x.shape) for p, x in
                   jax.tree_util.tree_flatten_with_path(params)[0])
    assert paths == PARENT_GPT2_TREE
    assert float(loss).hex() == PARENT_GPT2_LOSS
    for other_params, other_loss in results[1:]:
        assert float(other_loss).hex() == PARENT_GPT2_LOSS
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool((a == b).all()), params, other_params))


PARENT_GPT2_TREE = sorted(
    [f"['{norm}']['{leaf}'](32,)" for norm in ("LayerNorm_0",)
     for leaf in ("bias", "scale")]
    + [f"['block_{i}']{rest}" for i in (0, 1) for rest in (
        "['LayerNorm_0']['bias'](32,)", "['LayerNorm_0']['scale'](32,)",
        "['LayerNorm_1']['bias'](32,)", "['LayerNorm_1']['scale'](32,)",
        "['ff_down']['bias'](32,)", "['ff_down']['kernel'](64, 32)",
        "['ff_up']['bias'](64,)", "['ff_up']['kernel'](32, 64)",
        "['proj']['kernel'](32, 32)", "['qkv']['kernel'](32, 96)")]
    + ["['pos_emb'](16, 32)", "['tok_emb']['embedding'](96, 32)"])
PARENT_GPT2_LOSS = "0x1.3869660000000p+2"


def test_dense_gated_silu_block_is_the_formula():
    """The description's feed-forward kind without experts: three
    matrices, no bias, ``down(silu(gate(x)) * up(x))``."""
    arch = Architecture(norm="rmsnorm", ffn="gated_silu", positions="rope")
    model = TransformerLM(vocab_size=32, num_layers=1, num_heads=2,
                          d_model=16, d_ff=24, max_len=8, arch=arch,
                          pos_encoding="rope", compute_dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(30), (1, 8), 0, 32)
    params = model.init(jax.random.key(31), tokens)["params"]
    block = params["block_0"]
    assert sorted(block) == ["RMSNorm_0", "RMSNorm_1", "ff_down", "ff_gate",
                             "ff_up", "proj", "qkv"]
    assert all("bias" not in block[n] for n in ("ff_up", "ff_gate",
                                                "ff_down"))
    assert "pos_emb" not in params and "lm_head" not in params


# -- what the layer publishes -------------------------------------------

def test_scopes_and_gauges_of_a_traced_step(tiny):
    from chainermn_tpu.observability.metrics import registry

    params, tokens = tiny
    text = jax.jit(jax.grad(
        lambda p: _system_loss(p, tokens)[0])).lower(params).as_text(
            debug_info=True)
    for scope in (train_path.MOE_ROUTE, train_path.MOE_DISPATCH,
                  train_path.MOE_EXPERTS, train_path.MOE_COMBINE):
        assert f"/{scope}" in text, scope
    snap = registry().snapshot()
    rows = snap[train_path.MOE_ROWS_PER_STEP]["values"][0]["value"]
    experts = snap[train_path.MOE_EXPERTS_TOTAL]["values"][0]["value"]
    assert (rows, experts) == (2 * 32 * 2, 8)


def test_trainer_hands_the_expert_load_to_record_moe_dispatch():
    from chainermn_tpu.observability import trace
    from chainermn_tpu.training.trainer import Trainer

    logged = []
    trainer = Trainer.__new__(Trainer)
    trainer.iteration, trainer._obs_agg = 1, lambda m: None
    trainer._log = logged.append
    metrics = {"loss": jnp.float32(2.0), "moe/dropped": jnp.float32(0.0),
               "moe/expert_load": jnp.array([3.0, 5.0])}
    import time

    trainer._log_metrics(metrics, 10, time.perf_counter() - 1.0)
    assert "loss=2.0000" in logged[0] and "expert_load" not in logged[0]
    assert set(trainer.observation) == {"loss", "moe/dropped"}
    assert trace.active() is None  # and with no recorder nothing is written


def test_the_controls_tool_takes_every_reading_at_the_tiny_size(
        tmp_path, monkeypatch):
    """``tools/moe_controls.py`` (the readings the reference's limits lie
    between) on the benchmark tests' throw-away configuration: every row
    is there, a row in no group is counted and refused through the
    family's loss and leaves the norms alone finite, and the reference
    computed in bf16 is refused."""
    import json
    import sys

    monkeypatch.setattr(sys, "path", list(sys.path))  # the tool adds to it
    # and would turn the persistent compile cache on for this process
    from chainermn_tpu.utils import compile_cache
    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: "")
    tool = _load("tools/moe_controls.py", "moe_controls")
    out = tmp_path / "controls.jsonl"
    assert tool.main(["--tiny", "--seeds", "11", "--out", str(out)]) == 0
    rows = {r["what"]: r for r in map(json.loads,
                                      out.read_text().splitlines()[1:])}
    assert len(rows) == 12
    assert rows["sound"]["dropped"] == 0.0
    held = rows["one row in no group, the family's loss"]
    assert held["dropped"] == 2.0 and held["refused"]  # one a layer
    assert np.isnan(held["loss_rel_err"])
    assert np.isfinite(
        rows["one row in no group, the norms alone"]["loss_rel_err"])
    assert rows["reference computed in bf16"]["refused"]
