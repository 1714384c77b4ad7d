"""Block diffusion on the trained path (SDAR's): the flash kernels' causal
mask by blocks against a dense-mask attention, the in-block call on the
diagonal tiles against a dense same-block mask, the attention over ``[x ;
x~]`` assembled from its three parts, ``lm_loss_block_diffusion`` against
the benchmark's plain reference, what a row may depend on, the noise, and
the entry points that refuse the model."""

import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models import (
    MODEL_CONFIGS,
    beam_search,
    block_diffusion_noise,
    block_diffusion_rows,
    diffusion_noise_key,
    diffusion_noise_state,
    generate,
    init_cache,
    lm_from_config,
    lm_loss_block_diffusion,
)
from chainermn_tpu.observability import train_path
from chainermn_tpu.observability.metrics import registry
from chainermn_tpu.ops import block_diffusion as bd
from chainermn_tpu.ops.block_diffusion import block_diffusion_attention
from chainermn_tpu.ops.flash_attention import flash_attention

#: the module (``chainermn_tpu.ops`` exports the function of that name)
fa = importlib.import_module("chainermn_tpu.ops.flash_attention")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/block_diffusion_moe_lm.py",
                 "reference_block_diffusion_moe_lm")


def _highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return fn(*args)


# -- (a) the kernels' mask by blocks ----------------------------------------

def _dense_attention(q, k, v, allowed, scale):
    """Softmax attention under a dense boolean ``[Tq, Tk]`` mask, grouped
    queries by repetition; a row that sees nothing comes out zero."""
    g = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, g, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(allowed, s, -jnp.inf)
    m = jnp.max(s, -1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
    total = p.sum(-1, keepdims=True)
    p = jnp.where(total > 0, p / jnp.maximum(total, 1e-37), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _block_mask(T, bl, strict):
    qb, kb = jnp.arange(T)[:, None] // bl, jnp.arange(T)[None, :] // bl
    return qb > kb if strict else qb >= kb


def _operands(shape_q, shape_k, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(keys[0], shape_q),
            jax.random.normal(keys[1], shape_k),
            jax.random.normal(keys[2], shape_k),
            jax.random.normal(keys[3], shape_q))


#: (T, heads, kv heads, head width, block_q, block_k): grouped queries 8 / 2
#: in the transposed layout over several tiles, a length the tiles do not
#: divide (96 under a 64 request: tiles of 32), and heads of 128 in the
#: projections' own layout
SHAPES = {
    "gqa_8_2": (96, 8, 2, 16, 32, 32),
    "tiles_do_not_divide": (96, 4, 4, 16, 64, 64),
    "heads_of_128": (64, 4, 2, 128, 32, 64),
}


@pytest.mark.parametrize("strict", [False, True], ids=["inclusive", "strict"])
@pytest.mark.parametrize("bl", [1, 4, 32])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_block_mask_kernels_against_a_dense_mask(shape, bl, strict):
    """Forward and the three gradients, interpret mode. Under the strict
    mask the first block's queries see no key: their output and every
    gradient through them are zero, and nothing is NaN."""
    T, H, Hkv, D, bq, bk = SHAPES[shape]
    q, k, v, do = _operands((2, T, H, D), (2, T, Hkv, D))
    scale = D ** -0.5

    def system(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               causal_block=bl, causal_strict=strict,
                               block_q=bq, block_k=bk)

    def dense(q, k, v):
        return _dense_attention(q, k, v, _block_mask(T, bl, strict), scale)

    out, want = system(q, k, v), dense(q, k, v)
    grads = jax.grad(lambda *a: (system(*a) * do).sum(), (0, 1, 2))(q, k, v)
    wants = jax.grad(lambda *a: (dense(*a) * do).sum(), (0, 1, 2))(q, k, v)
    for got, ref_ in zip((out, *grads), (want, *wants)):
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(got, ref_, atol=2e-5, rtol=2e-5)
    if strict:
        first = min(bl, T)
        assert float(jnp.abs(out[:, :first]).max()) == 0.0
        assert float(jnp.abs(grads[0][:, :first]).max()) == 0.0


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_blocks_of_one_inclusive_are_the_causal_mask_bit_for_bit(shape):
    T, H, Hkv, D, bq, bk = SHAPES[shape]
    q, k, v, do = _operands((2, T, H, D), (2, T, Hkv, D), seed=1)

    def run(**kw):
        f = lambda *a: flash_attention(  # noqa: E731
            *a, causal=True, block_q=bq, block_k=bk, **kw)
        return (f(q, k, v), *jax.grad(
            lambda *a: (f(*a) * do).sum(), (0, 1, 2))(q, k, v))

    for a, b in zip(run(), run(causal_block=1, causal_strict=False)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the same program: the kernels are traced with no mask by blocks
    assert fa._blocks(1, False) is None


@pytest.mark.parametrize("bad", [
    dict(causal=False, causal_block=4),
    dict(causal=True, causal_block=4, window=8),
    dict(causal=True, causal_block=0),
    dict(causal=False, causal_strict=True),
])
def test_a_mask_by_blocks_needs_a_causal_mask_without_a_window(bad):
    q, k, v, _ = _operands((1, 16, 2, 16), (1, 16, 2, 16))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, **bad)


@pytest.mark.parametrize("strict", [False, True], ids=["inclusive", "strict"])
@pytest.mark.parametrize("T,bl", [(8192, 4), (2048, 32), (96, 32)])
def test_no_tile_outside_the_masks_support_is_visited(T, bl, strict):
    """``flash_tiles`` counts by ``_tile_class``: every tile a kernel
    visits holds a pair the mask allows, every tile it skips holds none,
    and a tile it leaves unmasked holds allowed pairs only; at the cell's
    length the tiles visited are the causal mask's."""
    blocks = fa._blocks(bl, strict)
    allowed = np.asarray(_block_mask(T, bl, strict))
    for kernel, walks in fa._WALKS.items():
        bq, _, bk = fa._geometry(T, T, walks=walks, causal=True,
                                 blocks=blocks, row_bytes=256)
        nq, nk = T // bq, T // bk
        live, full = fa._tile_class(
            np.arange(nk)[None, :], np.arange(nq)[:, None], bq, bk, True,
            None, 0, blocks)
        tiles = allowed.reshape(nq, bq, nk, bk)
        np.testing.assert_array_equal(live, tiles.any((1, 3)))
        np.testing.assert_array_equal(full, tiles.all((1, 3)))
        if T == 8192:
            causal, _ = fa._tile_class(
                np.arange(nk)[None, :], np.arange(nq)[:, None], bq, bk, True)
            assert live.sum() == np.broadcast_to(causal, live.shape).sum()
    q, k, v, _ = _operands((1, 96, 2, 16), (1, 96, 2, 16))
    flash_attention(q, k, v, causal=True, causal_block=32,
                    causal_strict=strict, block_q=32, block_k=32)
    gauge = registry().snapshot()[train_path.FLASH_TILES]["values"]
    fwd = {row["labels"]["kind"]: row["value"] for row in gauge
           if row["labels"]["kernel"] == train_path.FLASH_FWD}
    assert fwd == {"total": 9.0, "visited": 3.0 if strict else 6.0,
                   "masked": 0.0}


# -- the attention over [x ; x~] ---------------------------------------------

def _two_copy_mask(L, bl):
    """The dense ``[2L, 2L]`` mask from the three rules."""
    rows = jnp.arange(2 * L)
    noised, block = rows >= L, (rows % L) // bl
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = block[:, None], block[None, :]
    return (~qn & ~kn & (kb <= qb)) | (qn & ~kn & (kb < qb)) \
        | (qn & kn & (kb == qb))


@pytest.mark.parametrize("L,H,Hkv,D,bl", [
    (48, 8, 2, 16, 4), (64, 4, 2, 128, 4), (96, 4, 4, 32, 32),
    (40, 4, 1, 16, 1)], ids=["gqa_8_2", "heads_of_128", "blocks_of_32",
                             "blocks_of_1"])
def test_the_three_parts_are_the_two_copy_mask(L, H, Hkv, D, bl, ref):
    q, k, v, do = _operands((2, 2 * L, H, D), (2, 2 * L, Hkv, D), seed=2)

    @jax.jit
    def system(q, k, v):
        return block_diffusion_attention(q, k, v, block_length=bl)

    def dense(q, k, v):
        return _dense_attention(q, k, v, _two_copy_mask(L, bl), D ** -0.5)

    np.testing.assert_array_equal(
        np.asarray(ref.mask_rows(0, 2 * L, L, bl)),
        np.asarray(_two_copy_mask(L, bl)))
    np.testing.assert_allclose(system(q, k, v), dense(q, k, v), atol=2e-5,
                               rtol=2e-5)
    grads = jax.grad(lambda *a: (system(*a) * do).sum(), (0, 1, 2))(q, k, v)
    wants = jax.grad(lambda *a: (dense(*a) * do).sum(), (0, 1, 2))(q, k, v)
    for got, want in zip(grads, wants):
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)
    assert registry().snapshot()[train_path.BD_BLOCK_LENGTH]["values"][0][
        "value"] == float(bl)


# -- the in-block call: a noised row's own block on the diagonal tiles -------

def _same_block_mask(L, bl):
    return jnp.arange(L)[:, None] // bl == jnp.arange(L)[None, :] // bl


def _dense_lse(q, k, allowed, scale):
    g = q.shape[2] // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, g, axis=2)) * scale
    return jax.nn.logsumexp(jnp.where(allowed, s, -jnp.inf), axis=-1)


@pytest.mark.parametrize("tile_blocks", [2, 4])
@pytest.mark.parametrize("bl", [4, 6], ids=["bl4", "bl6_divides_no_lane_tile"])
@pytest.mark.parametrize("H,Hkv,D", [(2, 2, 16), (8, 1, 128)],
                         ids=["group_of_1", "group_of_8"])
def test_the_in_block_call_against_a_dense_same_block_mask(H, Hkv, D, bl,
                                                           tile_blocks):
    """Output, log-sum-exp and the three gradients of the call alone, at
    two tile lengths ``t`` (the tiles are ``L / t`` sequences of their
    own: nothing of a neighbouring tile may leak, and a block that ends
    where a tile ends sees its own keys only)."""
    L, t, scale = 48, bl * tile_blocks, D ** -0.5
    q, k, v, do = _operands((2, L, H, D), (2, L, Hkv, D), seed=5)
    allowed = _same_block_mask(L, bl)
    out, lse = bd.in_block_fwd(q, k, v, bl=bl, t=t, scale=scale,
                               interpret=True)
    np.testing.assert_allclose(
        out, _dense_attention(q, k, v, allowed, scale), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(lse, _dense_lse(q, k, allowed, scale),
                               atol=2e-5, rtol=2e-5)
    grads = bd.in_block_bwd(q, k, v, do, lse, out, bl=bl, t=t, scale=scale,
                            interpret=True)
    wants = jax.grad(lambda *a: (_dense_attention(*a, allowed, scale)
                                 * do).sum(), (0, 1, 2))(q, k, v)
    for got, want in zip(grads, wants):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_a_first_blocks_rows_are_their_in_block_part(direction):
    """A noised row of the first block sees no clean key: the strict
    call's partial for it is ``(0, NEG_INF)``, the merged pair is the
    in-block call's, and in the backward nothing reaches the clean copy
    through it and nothing is NaN."""
    L, H, Hkv, D, bl = 32, 4, 2, 16, 4
    scale = D ** -0.5
    q, kc, vc, do = _operands((2, L, H, D), (2, L, Hkv, D), seed=6)
    _, kn, vn, _ = _operands((2, L, H, D), (2, L, Hkv, D), seed=7)
    out, res = bd._noised_rows_fwd(q, kc, vc, kn, vn, bl, scale, True)
    own, own_lse = bd.in_block_fwd(q, kn, vn, bl=bl, t=L, scale=scale,
                                   interpret=True)
    if direction == "forward":
        _, strict_lse = bd.flash_block_fwd(
            q, kc, vc, scale=scale, interpret=True, causal_block=bl,
            **bd._STRICT)
        assert float(strict_lse[:, :, :bl].max()) <= fa.NEG_INF
        assert bool(jnp.isfinite(res[-1]).all())
        np.testing.assert_array_equal(np.asarray(res[-1][:, :, :bl]),
                                      np.asarray(own_lse[:, :, :bl]))
        np.testing.assert_array_equal(np.asarray(out[:, :bl]),
                                      np.asarray(own[:, :bl]))
        # and a later row's pair is the softmax over both key sets
        assert float(jnp.abs(out[:, bl:] - own[:, bl:]).max()) > 1e-3
        return
    first = do.at[:, bl:].set(0.0)  # a cotangent on the first block alone
    grads = bd._noised_rows_bwd(bl, scale, True, res, first)
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    dq, dk_clean, dv_clean, dk_noised, dv_noised = grads
    assert float(jnp.abs(dk_clean).max()) == 0.0
    assert float(jnp.abs(dv_clean).max()) == 0.0
    for g in (dq, dk_noised, dv_noised):
        assert float(jnp.abs(g[:, bl:]).max()) == 0.0
    wants = jax.grad(lambda *a: (_dense_attention(
        *a, _same_block_mask(L, bl), scale) * first).sum(), (0, 1, 2))(
        q, kn, vn)
    for got, want in zip((dq, dk_noised, dv_noised), wants):
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("L,bl,tiles", [
    (8192, 4, 16), (1536, 4, 3), (96, 32, 1), (2048, 1024, 2), (2036, 4, 509)],
    ids=["the_cells", "three_tiles_of_512", "one_tile", "a_block_a_tile",
         "a_prime_count_of_blocks"])
def test_the_gauge_says_which_diagonal_tiles_were_visited(L, bl, tiles):
    """``bd_in_block_tiles`` is ``L / t`` of the last attention traced: the
    in-block call's ``L / t`` sequences are one tile each (``t`` the most
    whole blocks that divide ``L`` within the kernels' 512-row tile), so
    what it visits is the diagonal and nothing else."""
    t = bd.in_block_tile(L, bl)
    assert L % t == 0 and t % bl == 0 and L // t == tiles
    assert t <= max(fa._TILES[0], bl)
    q = jax.ShapeDtypeStruct((1, 2 * L, 2, 16), jnp.float32)
    jax.eval_shape(lambda q, k, v: block_diffusion_attention(
        q, k, v, block_length=bl), q, q, q)
    assert registry().snapshot()[train_path.BD_IN_BLOCK_TILES]["values"][0][
        "value"] == float(tiles)


def test_rows_that_are_no_two_copies_of_whole_blocks_are_refused():
    q, k, v, _ = _operands((1, 36, 2, 16), (1, 36, 2, 16))
    with pytest.raises(ValueError, match="whole blocks"):
        block_diffusion_attention(q, k, v, block_length=4)


# -- (b) the loss against the reference --------------------------------------

#: 2 layers at d 64, 4 / 2 heads of 32, 8 experts of width 64 of which 4
#: are held, top-2, blocks of 4, L 32 (64 rows), vocabulary 97 whose last
#: id is the mask
TINY = dict(
    MODEL_CONFIGS["sdar-30b-a3b"], num_hidden_layers=2, hidden_size=64,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    moe_intermediate_size=64, num_experts=4, experts_published=8,
    experts_held_range=[2, 6], num_experts_per_tok=2, vocab_size=97,
    mask_token_id=96, max_position_embeddings=64,
    assumed={"router_aux_loss_coef": 0.001},
)
L = 32


def _model(config=TINY, **kw):
    kw.setdefault("return_hidden", True)
    return lm_from_config(config, compute_dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def tiny():
    tokens = jax.random.randint(jax.random.key(0), (2, L), 0, 96)
    params = _model().init(jax.random.key(1),
                           jnp.zeros((1, 2 * L), jnp.int32))["params"]
    masked, t = block_diffusion_noise(jax.random.key(2), (2, L),
                                      block_length=4)
    return params, tokens, masked, t


def _system_loss(params, tokens, noise, **kw):
    return lm_loss_block_diffusion(
        _model(**kw), params, tokens, noise=noise, mask_id=96, n_chunks=2,
        load_balance_coef=0.001)[0]


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat_dots"])
def test_loss_and_gradients_against_the_reference(remat, tiny, ref):
    """Float32 on both sides at full matmul precision: what is left is
    the order of sums (the kernels' tiles, the fused head's chunks, the
    sort by expert), 1e-6 relative on the loss and 1e-5 on a leaf (read:
    2e-7 and 1e-6). The reference computed in bf16 is a hundred times
    outside both, which is what the chip's limits have to part at the
    published widths too."""
    params, tokens, masked, t = tiny
    batch = {"tokens": tokens, "masked": masked, "t": t}
    loss, grads = _highest(jax.jit(jax.value_and_grad(
        lambda p: _system_loss(p, tokens, (masked, t), remat=remat))), params)
    want, want_grads = _highest(jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, None, batch, TINY))), params)
    assert abs(float(loss) - float(want)) / float(want) < 1e-6
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert _rel(g, w) < 1e-5, jax.tree_util.keystr(path)
    low, low_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(
        p, None, batch, TINY, dtype=jnp.bfloat16)))(params)
    worst = max(_rel(g.astype(jnp.float32), w) for g, w in zip(
        jax.tree.leaves(low_grads), jax.tree.leaves(want_grads)))
    assert abs(float(low) - float(want)) / float(want) > 1e-4
    assert worst > 1e-3


@pytest.mark.parametrize("what", [
    "shifted_targets", "unweighted", "noised_rows_see_their_clean_block",
    "clean_rows_see_one_position", "mask_id_off_by_one"])
def test_a_changed_equation_fails_the_comparison(what, tiny, ref, monkeypatch):
    """Each departure from the training pass's equations moves the loss
    by far more than the sound system's 1e-6."""
    params, tokens, masked, t = tiny
    batch = {"tokens": tokens, "masked": masked, "t": t}
    want = float(_highest(jax.jit(
        lambda p, b: ref.loss(p, None, b, TINY)), params, batch))
    mask_id = 96
    if what == "shifted_targets":
        tokens = jnp.roll(tokens, -1, axis=1)
    elif what == "unweighted":
        t = jnp.ones_like(t)
    elif what == "mask_id_off_by_one":
        mask_id = 95
    else:
        from chainermn_tpu.ops import block_diffusion as bd

        strict = what != "noised_rows_see_their_clean_block"
        block = 1 if what == "clean_rows_see_one_position" else None
        real_fwd, real_attn = bd.flash_block_fwd, bd.flash_attention
        if not strict:
            monkeypatch.setattr(bd, "flash_block_fwd", lambda *a, **kw:
                                real_fwd(*a, **{**kw,
                                                "causal_strict": False}))
        if block:
            monkeypatch.setattr(bd, "flash_attention", lambda *a, **kw:
                                real_attn(*a, **{**kw,
                                                 "causal_block": block}))
    got = float(_highest(jax.jit(lambda p: lm_loss_block_diffusion(
        _model(), p, tokens, noise=(masked, t), mask_id=mask_id,
        n_chunks=2, load_balance_coef=0.001)[0]), params))
    assert abs(got - want) / want > 1e-3, (what, got, want)


# -- (c) what a row may depend on --------------------------------------------

@jax.jit
def _hidden(params, clean, noised):
    rows = jnp.concatenate([clean, noised], axis=1)
    return _model().apply({"params": params}, rows,
                          mutable=["moe_aux"])[0]


def test_a_row_depends_on_what_the_mask_lets_it_see(tiny):
    """Block ``b``'s noised rows do not move when later blocks' tokens, or
    other blocks' noised tokens, change; they do when an earlier clean
    token or a noised token of their own block does. No clean row moves
    when ``x~`` changes."""
    params, tokens, masked, _ = tiny
    noised = jnp.where(masked, 96, tokens)
    base = _hidden(params, tokens, noised)
    b = 3  # rows 12..15 of the noised copy
    mine = slice(L + 4 * b, L + 4 * b + 4)

    def changed(clean=None, noise=None):
        c = tokens if clean is None else tokens.at[:, clean].add(1)
        n = noised if noise is None else noised.at[:, noise].set(5)
        return _hidden(params, c, n)

    later = changed(clean=slice(4 * b, None), noise=slice(4 * b + 4, None))
    np.testing.assert_array_equal(np.asarray(later[:, mine]),
                                  np.asarray(base[:, mine]))
    others = changed(noise=slice(0, 4 * b))
    np.testing.assert_array_equal(np.asarray(others[:, mine]),
                                  np.asarray(base[:, mine]))
    # every clean row, whatever x~ is
    everything = changed(noise=slice(None))
    np.testing.assert_array_equal(np.asarray(everything[:, :L]),
                                  np.asarray(base[:, :L]))
    # and it does see: an earlier clean token, a noised one of its block
    earlier = changed(clean=slice(0, 4))
    assert float(jnp.abs(earlier[:, mine] - base[:, mine]).max()) > 1e-4
    own = changed(noise=slice(4 * b, 4 * b + 1))
    assert float(jnp.abs(own[:, mine] - base[:, mine]).max()) > 1e-4
    # the noised copy sits at the clean copy's positions: where nothing is
    # masked and a block holds one position, the two copies are one
    one = dict(TINY, block_length=1)
    same = lm_from_config(one, compute_dtype=jnp.float32,
                          return_hidden=True).apply(
        {"params": params}, jnp.concatenate([tokens, tokens], axis=1),
        mutable=["moe_aux"])[0]
    np.testing.assert_allclose(same[:, L:], same[:, :L], atol=1e-5)


# -- (d) the noise -----------------------------------------------------------

def test_the_noise_and_the_rows_it_makes():
    B, Ln, bl, t_min = 64, 512, 4, 0.05
    tokens = jax.random.randint(jax.random.key(3), (B, Ln), 0, 96)
    masked, t = block_diffusion_noise(jax.random.key(4), (B, Ln),
                                      block_length=bl, t_min=t_min)
    assert masked.shape == (B, Ln) and masked.dtype == jnp.bool_
    assert t.shape == (B, Ln // bl) and t.dtype == jnp.float32
    assert float(t.min()) >= t_min and float(t.max()) <= 1.0
    # the masked share within 3 sigma of E[t] = (1 + t_min) / 2: a token's
    # mask is Bernoulli(t) with t uniform, variance at most 1/4, and the
    # tokens of a block share a level (a factor of at most bl)
    n, mean = B * Ln, (1 + t_min) / 2
    assert abs(float(masked.mean()) - mean) < 3 * (bl / (4 * n)) ** 0.5
    assert abs(float(t.mean()) - mean) < 3 * ((1 - t_min) ** 2 / 12
                                              / (n / bl)) ** 0.5
    rows, positions, weights = block_diffusion_rows(tokens, masked, t,
                                                    mask_id=96)
    np.testing.assert_array_equal(np.asarray(rows[:, :Ln]),
                                  np.asarray(tokens))
    np.testing.assert_array_equal(
        np.asarray(rows[:, Ln:]),
        np.where(np.asarray(masked), 96, np.asarray(tokens)))
    np.testing.assert_array_equal(np.asarray(positions),
                                  np.tile(np.arange(Ln), 2))
    np.testing.assert_allclose(
        weights, np.asarray(masked) / np.repeat(np.asarray(t), bl, axis=1))
    # the same key the same draw, another key another
    again = block_diffusion_noise(jax.random.key(4), (B, Ln),
                                  block_length=bl, t_min=t_min)
    np.testing.assert_array_equal(np.asarray(again[0]), np.asarray(masked))
    other = block_diffusion_noise(jax.random.key(5), (B, Ln),
                                  block_length=bl, t_min=t_min)
    assert (np.asarray(other[0]) != np.asarray(masked)).mean() > 0.2


def test_the_step_counts_its_draws_and_the_metrics_say_what_was_drawn(tiny):
    params, tokens, _, _ = tiny
    state = diffusion_noise_state(4_242_000_777)  # above 2**31
    key, after = diffusion_noise_key(state)
    assert float(after["draw"]) == 1.0
    np.testing.assert_array_equal(np.asarray(after["seed"]),
                                  np.asarray(state["seed"]))
    next_key, _ = diffusion_noise_key(after)
    assert (jax.random.key_data(key) != jax.random.key_data(next_key)).any()
    other, _ = diffusion_noise_key(diffusion_noise_state(4_242_000_778))
    assert (jax.random.key_data(key) != jax.random.key_data(other)).any()
    loss, metrics = jax.jit(lambda p, b, k: lm_loss_block_diffusion(
        _model(), p, b, k, mask_id=96, n_chunks=2))(params, tokens, key)
    masked, t = block_diffusion_noise(key, tokens.shape, block_length=4)
    assert float(metrics["bd/masked_share"]) == pytest.approx(
        float(masked.mean()))
    assert float(metrics["bd/mean_weight"]) == pytest.approx(float(
        (masked / jnp.repeat(t, 4, axis=1)).mean()), rel=1e-6)
    assert float(metrics["moe/dropped"]) == 0.0
    assert float(loss) == pytest.approx(float(_system_loss(
        params, tokens, (masked, t))), rel=1e-6)
    assert registry().snapshot()[train_path.BD_ROWS_PER_STEP]["values"][0][
        "value"] == 2.0 * 2 * L
    with pytest.raises(ValueError, match="one of the two"):
        lm_loss_block_diffusion(_model(), params, tokens, mask_id=96)
    with pytest.raises(ValueError, match="whole blocks"):
        lm_loss_block_diffusion(_model(), params, tokens[:, :30], key,
                                mask_id=96)


def test_the_scopes_reach_the_compiled_step(tiny):
    """``bd_noise`` round the draw and the rows, ``bd_attention`` round
    the kernels, forward and backward, and their merge."""
    params, tokens, _, _ = tiny
    assert (train_path.BD_NOISE, train_path.BD_ATTENTION) == (
        "bd_noise", "bd_attention")
    text = jax.jit(jax.grad(lambda p, b, k: lm_loss_block_diffusion(
        _model(remat=True), p, b, k, mask_id=96, n_chunks=2)[0])).lower(
        params, tokens, jax.random.key(0)).as_text(debug_info=True)
    lines = text.splitlines()
    assert any("bd_noise" in line for line in lines)
    for kernel in (train_path.FLASH_FWD, train_path.FLASH_BWD_DQ,
                   train_path.FLASH_BWD_DKV):
        assert any(f"bd_attention/{kernel}" in line for line in lines)
    assert any("transpose(" in line and "bd_attention" in line
               for line in lines)


# -- (f) refusals ------------------------------------------------------------

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
def test_decoding_and_serving_refuse_a_block_diffusion_model(entry, tiny):
    """Also where every expert is held: it is the denoising loop that is
    not built."""
    whole = {k: v for k, v in TINY.items()
             if k not in ("experts_published", "experts_held_range")}
    model = lm_from_config(whole, compute_dtype=jnp.float32)
    params = model.init(jax.random.key(1),
                        jnp.zeros((1, 2 * L), jnp.int32))
    with pytest.raises(NotImplementedError,
                       match="block-diffusion model .blocks of 4."):
        ENTRY_POINTS[entry](model, params, jnp.ones((2, 4), jnp.int32))


@pytest.mark.parametrize("bad,match", [
    (dict(hidden_act="gelu"), "another activation"),
    (dict(attention_bias=True), "attention biases"),
    (dict(use_sliding_window=True), "sliding window"),
    (dict(mlp_only_layers=[0]), "without experts"),
    (dict(decoder_sparse_step=2), "without experts"),
    (dict(experts_held_range=[0, 3]), "share of the experts"),
])
def test_a_config_the_stack_cannot_express_is_refused(bad, match):
    with pytest.raises(ValueError, match=match):
        lm_from_config({**TINY, **bad})
