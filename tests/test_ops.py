"""Op-layer tests: the Pallas flash-attention kernel (interpreter mode — the
CPU analogue of the reference's CPU-only CI paths, SURVEY.md section 4)
against plain attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.ops import (
    blockwise_attention,
    dot_product_attention,
    flash_attention,
)

B, T, H, D = 2, 64, 4, 32


def _qkv(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, T, H, D)) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_full(causal):
    q, k, v = _qkv()
    out = flash_attention(
        q, k, v, causal=causal, block_q=32, block_k=32, interpret=True
    )
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_flash_grads_match_full():
    q, k, v = _qkv(1)

    def loss_f(q, k, v):
        return (
            flash_attention(
                q, k, v, causal=True, block_q=32, block_k=32, interpret=True
            )
            ** 2
        ).sum()

    def loss_r(q, k, v):
        return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        ),
        gf,
        gr,
    )


def _segments(seed=7):
    """Random packed-segment ids: 3 documents of uneven length per row."""
    rng = np.random.RandomState(seed)
    seg = np.zeros((B, T), np.int32)
    for b in range(B):
        cuts = sorted(rng.choice(np.arange(4, T - 4), 2, replace=False))
        seg[b, cuts[0]:cuts[1]] = 1
        seg[b, cuts[1]:] = 2
    return jnp.asarray(seg)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_segment_mask_matches_full(causal):
    """Packed-sequence masking: flash with segment_ids == dense attention
    with the same per-document mask (composed with causal)."""
    q, k, v = _qkv(3)
    seg = _segments()
    out = flash_attention(
        q, k, v, causal=causal, segment_ids=seg,
        block_q=16, block_k=16, interpret=True,
    )
    ref = dot_product_attention(q, k, v, causal=causal, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_segment_grads_match_full():
    q, k, v = _qkv(4)
    seg = _segments(8)

    def loss_f(q, k, v):
        return (flash_attention(
            q, k, v, causal=True, segment_ids=seg,
            block_q=16, block_k=16, interpret=True) ** 2).sum()

    def loss_r(q, k, v):
        return (dot_product_attention(
            q, k, v, causal=True, segment_ids=seg) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        ),
        gf,
        gr,
    )


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_flash_gqa_matches_full(kv_heads):
    """Grouped/multi-query attention: q has H heads, kv has fewer; the
    kernel shares kv blocks across the group via its index map."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, kv_heads, D))
    v = jax.random.normal(ks[2], (B, T, kv_heads, D))
    out = flash_attention(
        q, k, v, causal=True, block_q=32, block_k=32, interpret=True
    )
    ref = dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_gqa_grads_match_full():
    """GQA backward: dk/dv group-sum across the q heads they serve."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, 2, D))
    v = jax.random.normal(ks[2], (B, T, 2, D))

    def loss_f(q, k, v):
        return (flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32,
            interpret=True) ** 2).sum()

    def loss_r(q, k, v):
        return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        ),
        gf,
        gr,
    )


def _alibi_bias(n_heads, T):
    """ALiBi-style additive bias [1, H, T, T]."""
    slopes = 2.0 ** (-np.arange(1, n_heads + 1))
    dist = np.arange(T)[None, :] - np.arange(T)[:, None]
    return jnp.asarray(
        (slopes[:, None, None] * np.minimum(dist, 0)[None])[None],
        jnp.float32,
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bias_matches_full(causal):
    """Additive score bias (ALiBi hook): flash == dense with the same
    bias, fwd values and q/k/v grads (static bias — zero cotangent)."""
    q, k, v = _qkv(9)
    bias = _alibi_bias(H, T)
    out = flash_attention(q, k, v, causal=causal, bias=bias,
                          block_q=16, block_k=16, interpret=True)
    ref = dot_product_attention(q, k, v, causal=causal, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    gf = jax.grad(lambda a, b, c: (flash_attention(
        a, b, c, causal=causal, bias=bias, block_q=16, block_k=16,
        interpret=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: (dot_product_attention(
        a, b, c, causal=causal, bias=bias) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        ),
        gf, gr,
    )


def test_flash_bias_grad_opt_in():
    """bias_grad=True materializes the true bias gradient; default is a
    zero cotangent (static-bias contract)."""
    q, k, v = _qkv(10)
    bias = _alibi_bias(H, T)

    def loss(b, grad_flag):
        return (flash_attention(q, k, v, causal=True, bias=b,
                                bias_grad=grad_flag, block_q=16,
                                block_k=16, interpret=True) ** 2).sum()

    def loss_ref(b):
        return (dot_product_attention(q, k, v, causal=True,
                                      bias=b) ** 2).sum()

    g_true = jax.grad(lambda b: loss(b, True))(bias)
    g_ref = jax.grad(loss_ref)(bias)
    np.testing.assert_allclose(np.asarray(g_true), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)
    g_zero = jax.grad(lambda b: loss(b, False))(bias)
    np.testing.assert_allclose(np.asarray(g_zero), 0.0)


def test_flash_bias_shape_validated():
    q, k, v = _qkv(11)
    with pytest.raises(ValueError, match="bias must be"):
        flash_attention(q, k, v, bias=jnp.zeros((2, H, T, T + 1)),
                        interpret=True)
    with pytest.raises(ValueError, match="bias_grad"):
        flash_attention(q, k, v, bias_grad=True, interpret=True)


def test_flash_gqa_head_mismatch_rejected():
    q = jnp.zeros((1, 16, 4, 8))
    kv = jnp.zeros((1, 16, 3, 8))
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, kv, kv, interpret=True)


def test_flash_adapts_indivisible_blocks():
    """Requested blocks that don't divide T are adapted (halved / collapsed
    to one block), never an error — and numerics are unchanged."""
    q, k, v = _qkv(2)
    out = flash_attention(q, k, v, block_q=48, block_k=48, interpret=True)
    ref = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5
    )


def test_flash_attention_odd_sequence_lengths():
    """Sequence lengths not divisible by the large default blocks must
    still run (block sizes adapt by halving, or fall back to one block)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chainermn_tpu.ops.attention import dot_product_attention
    from chainermn_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(5)
    for T in (96, 136, 768):
        q = jnp.asarray(rng.randn(1, T, 2, 32), jnp.float32)
        out = flash_attention(q, q, q, causal=True)
        ref = dot_product_attention(q, q, q, causal=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
        )
        g = jax.grad(lambda x: jnp.sum(flash_attention(x, x, x)))(q)
        assert np.isfinite(np.asarray(g)).all()


# ---------------------------------------------------------------------------
# Causal sliding window (local attention)
# ---------------------------------------------------------------------------


def _window_bias(window, T):
    """Dense emulation of the sliding window: 0 inside the band
    ``0 <= i - j < window``, -inf outside (the causal flag handles j > i)."""
    i = np.arange(T)[:, None]
    j = np.arange(T)[None, :]
    band = (i - j) < window
    return jnp.asarray(
        np.where(band, 0.0, -1e30)[None, None].astype(np.float32)
    )


@pytest.mark.parametrize("window", [1, 7, 16, 64])
def test_flash_window_matches_masked_full(window):
    q, k, v = _qkv(11)
    out = flash_attention(
        q, k, v, causal=True, window=window,
        block_q=16, block_k=16, interpret=True,
    )
    ref = dot_product_attention(
        q, k, v, causal=True, bias=_window_bias(window, T)
    )
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_flash_window_grads_match_masked_full():
    q, k, v = _qkv(12)
    window = 10

    def loss_f(q, k, v):
        return (flash_attention(
            q, k, v, causal=True, window=window,
            block_q=16, block_k=16, interpret=True,
        ) ** 2).sum()

    def loss_r(q, k, v):
        return (dot_product_attention(
            q, k, v, causal=True, bias=_window_bias(window, T)
        ) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        ),
        gf, gr,
    )


def test_flash_window_geq_T_equals_plain_causal():
    q, k, v = _qkv(13)
    w = flash_attention(q, k, v, causal=True, window=T,
                        block_q=16, block_k=16, interpret=True)
    c = flash_attention(q, k, v, causal=True,
                        block_q=16, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(w), np.asarray(c),
                               rtol=1e-6, atol=1e-6)


def test_flash_window_composes_with_segments_and_gqa():
    ks = jax.random.split(jax.random.PRNGKey(14), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, 2, D))
    v = jax.random.normal(ks[2], (B, T, 2, D))
    seg = _segments()
    window = 9
    out = flash_attention(
        q, k, v, causal=True, window=window, segment_ids=seg,
        block_q=16, block_k=16, interpret=True,
    )
    ref = dot_product_attention(
        q, k, v, causal=True, segment_ids=seg,
        bias=_window_bias(window, T),
    )
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_flash_window_validation():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=4, interpret=True)
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention(q, k, v, causal=True, window=0, interpret=True)


@pytest.mark.parametrize("bq,bk", [(8, 16), (16, 8), (8, 8)])
def test_flash_window_banded_grid_mixed_blocks(bq, bk):
    """The band-narrowed grid must be exact for unequal block sizes and
    windows that don't align to either block edge. Each case ASSERTS the
    banding is actually active (span < n blocks) — an earlier version of
    this test used block pairs whose spans covered the whole axis, so the
    banded geometry ran nowhere."""
    from chainermn_tpu.ops.flash_attention import _band_k, _band_q

    q, k, v = _qkv(15)
    nq, nk = T // bq, T // bk
    for window in (2, 10):
        span_k, _ = _band_k(bq, bk, window, nk)
        span_q, _ = _band_q(bq, bk, window, nq)
        assert span_k < nk, f"k-banding inactive: {span_k} >= {nk}"
        assert span_q < nq, f"q-banding inactive: {span_q} >= {nq}"
        out = flash_attention(
            q, k, v, causal=True, window=window,
            block_q=bq, block_k=bk, interpret=True,
        )
        ref = dot_product_attention(
            q, k, v, causal=True, bias=_window_bias(window, T)
        )
        np.testing.assert_allclose(
            np.asarray(out), ref, rtol=1e-5, atol=1e-5,
            err_msg=f"window={window} bq={bq} bk={bk}",
        )

        def loss_f(q, k, v):
            return (flash_attention(
                q, k, v, causal=True, window=window,
                block_q=bq, block_k=bk, interpret=True,
            ) ** 2).sum()

        def loss_r(q, k, v):
            return (dot_product_attention(
                q, k, v, causal=True, bias=_window_bias(window, T)
            ) ** 2).sum()

        gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4,
                err_msg=f"grad window={window} bq={bq} bk={bk}",
            ),
            gf, gr,
        )


def test_flash_window_with_trainable_bias():
    """bias_grad forces the dkv kernel back to the full grid (its dbias
    output tiles every (iq, ik)); the dq kernel stays banded — gradients
    must still be exact."""
    q, k, v = _qkv(16)
    window = 12
    bias = jax.random.normal(jax.random.PRNGKey(17), (1, 1, T, T)) * 0.1

    def loss_f(q, k, v, bias):
        return (flash_attention(
            q, k, v, causal=True, window=window, bias=bias, bias_grad=True,
            block_q=16, block_k=16, interpret=True,
        ) ** 2).sum()

    def loss_r(q, k, v, bias):
        return (dot_product_attention(
            q, k, v, causal=True, bias=bias + _window_bias(window, T)
        ) ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2, 3))(q, k, v, bias)
    gr = jax.grad(loss_r, argnums=(0, 1, 2, 3))(q, k, v, bias)
    # dbias entries outside the window band are zero in the kernel but
    # nonzero-noise in the dense reference only where masked-out -> both
    # are zero there because masked softmax kills the path; compare all.
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        ),
        gf, gr,
    )


# ---------------------------------------------------------------------------
# Derived iteration geometry: the causal triangle, not the square
# ---------------------------------------------------------------------------

_FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def _traced_tiles(T, D, H=16, causal=True, **kw):
    """``{kernel: {kind: tiles}}`` of the ``flash_tiles`` gauge after
    tracing (never running) a forward + backward at ``[1, T, H, D]``."""
    from chainermn_tpu.observability import train_path
    from chainermn_tpu.observability.metrics import registry

    x = jax.ShapeDtypeStruct((1, T, H, D), jnp.bfloat16)
    jax.eval_shape(
        jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, interpret=True, **kw
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        x, x, x,
    )
    gauge = registry().gauge(train_path.FLASH_TILES)
    return {k: {kind: gauge.value(kernel=k, kind=kind)
                for kind in ("total", "visited", "masked")}
            for k in _FLASH_KERNELS}


# (total, visited, masked) sub-tiles: of the kernels that walk K per Q
# block (512 x 512 inside tiles of 512 x 1024), and of dk/dv (whole tiles
# of 1024 x 1024). At the LM cells' length that is two sub-tiles a side
# and one dk/dv tile: finer ones measured slower on the chip (PERF.md,
# PR 24), which is why the shares there are 0.75 and 1 and not 0.625.
@pytest.mark.parametrize("T,D,share,walks_k,walks_q", [
    (1024, 64, 0.75, (4, 3, 2), (1, 1, 1)),
    (2048, 128, 0.65, (16, 10, 4), (4, 3, 2)),
    (4096, 128, 0.60, (64, 36, 8), (16, 10, 4)),
    # 512 does not divide 768: sub-tiles of 256 in a tile of 768
    (768, 64, 0.70, (9, 6, 3), (1, 1, 1)),
])
def test_flash_derived_geometry_walks_the_triangle(T, D, share, walks_k,
                                                   walks_q):
    got = {k: (v["total"], v["visited"], v["masked"])
           for k, v in _traced_tiles(T, D).items()}
    assert got == {"flash_fwd": walks_k, "flash_bwd_dq": walks_k,
                   "flash_bwd_dkv": walks_q}
    total, visited, masked = walks_k
    assert visited / total <= share
    # the masked sub-tiles are those the diagonal crosses: one a row
    for total, visited, masked in (walks_k, walks_q):
        assert masked ** 2 == total
        assert visited == masked * (masked + 1) // 2


def test_flash_tiles_follow_the_last_call_not_the_last_trace():
    """The op sits in a jit of its own; a call whose trace that jit
    already holds still sets the gauge."""
    first = _traced_tiles(2048, 64)
    assert _traced_tiles(1024, 64) != first
    assert _traced_tiles(2048, 64) == first


@pytest.mark.parametrize("name,walks_k,walks_q", [
    # segment ids and a bias add tile-sized work to every visited tile,
    # so dk/dv keeps the 512 x 1024 tile; K is walked in sub-tiles still
    ("segments", (16, 10, 4), (8, 6, 4)),
    ("bias", (16, 10, 4), (8, 6, 4)),
    # two heads of 64 a step hold two bias squares: half the keys a tile
    ("bias_two_heads", (16, 10, 4), (16, 10, 4)),
    # a window: every kernel masks its crossed 512 x 1024 tiles whole
    ("window", (8, 5, 5), (8, 5, 5)),
])
def test_flash_dkv_tile_is_whole_only_under_a_bare_causal_mask(
        name, walks_k, walks_q):
    T = 2048
    bias = dict(bias=jnp.zeros((1, 1, T, T), jnp.bfloat16))
    kw = {"segments": dict(segment_ids=jnp.zeros((1, T), jnp.int32)),
          "bias": bias, "bias_two_heads": bias,
          "window": dict(window=300)}[name]
    D = 128 if name == "bias" else 64
    got = {k: (v["total"], v["visited"], v["masked"])
           for k, v in _traced_tiles(T, D, H=2, **kw).items()}
    assert got == {"flash_fwd": walks_k, "flash_bwd_dq": walks_k,
                   "flash_bwd_dkv": walks_q}


@pytest.mark.parametrize("kernel", _FLASH_KERNELS)
def test_flash_noncausal_visits_every_tile_unmasked(kernel):
    got = _traced_tiles(1024, 64, causal=False)[kernel]
    assert got["visited"] == got["total"] >= 1
    assert got["masked"] == 0


@pytest.mark.parametrize("bq,bk,walks_k,walks_q", [
    # the former default: K per Q block in halves of its 1024 keys, the
    # dk/dv tile whole
    (512, 1024, (4, 3, 2), (2, 2, 2)),
    (128, 256, (64, 36, 8), (32, 20, 8)),
    (256, 128, (32, 20, 8), (32, 20, 8)),
    # no divisor of 1024: one whole-T tile
    (48, 48, (1, 1, 1), (1, 1, 1)),
])
def test_flash_explicit_blocks_are_honoured(bq, bk, walks_k, walks_q):
    """The tiles are the caller's; where K is walked the triangle is
    walked inside them in sub-tiles of ``block_q`` keys, if that divides
    ``block_k``."""
    got = {k: (v["total"], v["visited"], v["masked"]) for k, v in
           _traced_tiles(1024, 64, block_q=bq, block_k=bk).items()}
    assert got == {"flash_fwd": walks_k, "flash_bwd_dq": walks_k,
                   "flash_bwd_dkv": walks_q}


def test_flash_geometry_from_the_shapes():
    from chainermn_tpu.ops.flash_attention import _geometry

    def geo(T, walks, **kw):
        kw.setdefault("causal", True)
        return _geometry(T, T, walks=walks, **kw)

    # (block_q, block_k, sub): where K is walked, long along K with the
    # triangle inside the tile in sub-tiles of block_q; dk/dv whole
    assert geo(1024, "k") == (512, 1024, 512)
    assert geo(1024, "q") == (1024, 1024, 1024)
    assert geo(4096, "k") == (512, 1024, 512)
    assert geo(4096, "q") == (1024, 1024, 1024)
    assert geo(768, "k") == (256, 768, 256)
    # a window or an offset off the sub-tile grid: the tile is masked
    # whole
    assert geo(1024, "k", window=300) == (512, 1024, 1024)
    assert geo(1024, "k", q_offset=100) == (512, 1024, 1024)
    assert geo(1024, "k", q_offset=512) == (512, 1024, 512)
    # segment ids, a bias or a window: dk/dv keeps the former tile
    assert geo(1024, "q", bare=False) == (512, 1024, 1024)
    assert geo(1024, "q", window=300) == (512, 1024, 1024)
    assert geo(1024, "k", bare=False) == (512, 1024, 512)
    # rows wider than 512 bytes (f32 heads of 256): the same
    assert geo(1024, "q", row_bytes=512) == (1024, 1024, 1024)
    assert geo(1024, "q", row_bytes=1024) == (512, 1024, 1024)
    # no mask to skip by: the fewest, largest steps
    assert geo(4096, "k", causal=False) == (512, 1024, 1024)
    assert geo(4096, "q", causal=False) == (512, 1024, 1024)
    # a caller's numbers are taken as given
    assert geo(4096, "k", block_q=256, block_k=512) == (256, 512, 256)
    assert geo(4096, "q", block_q=256, block_k=512) == (256, 512, 512)


def _dense_block(q, k, v, *, causal, scale, seg_q=None, seg_kv=None,
                 window=None, q_offset=0, bias=None):
    """XLA reference of one flash block call: ``(out, lse)``, with the
    mask handed to ``ops.attention`` as a bias."""
    Tq, Tk = q.shape[1], k.shape[1]
    i = q_offset + np.arange(Tq)[:, None]
    j = np.arange(Tk)[None, :]
    ok = np.ones((Tq, Tk), bool)
    if causal:
        ok &= j <= i
        if window is not None:
            ok &= i - j < window
    ok = jnp.asarray(ok)[None, None]
    if seg_q is not None:
        ok = ok & (seg_q[:, None, :, None] == seg_kv[:, None, None, :])
    mask = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
    bias = mask if bias is None else mask + bias
    out = dot_product_attention(q, k, v, scale=scale, bias=bias)
    g = q.shape[2] // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, g, axis=2),
                   precision="highest") * scale + bias
    return out, jax.nn.logsumexp(s, axis=-1)


_T1K = 1024


def _block_case(name):
    """Operands of one ``flash_block_fwd`` call at T 1024, D 64, B 1,
    H 4 and the keywords of the variant ``name``."""
    ks = jax.random.split(jax.random.PRNGKey(24), 4)
    kv_heads = 2 if name == "gqa" else 4
    Tk = _T1K + 256 if name == "q_offset" else _T1K
    q = jax.random.normal(ks[0], (1, _T1K, 4, 64))
    k = jax.random.normal(ks[1], (1, Tk, kv_heads, 64))
    v = jax.random.normal(ks[2], (1, Tk, kv_heads, 64))
    do = jax.random.normal(ks[3], (1, _T1K, 4, 64))
    kw = dict(causal=True, scale=0.125)
    if name == "segments":
        seg = jnp.asarray(np.repeat([0, 1, 2, 3], [300, 212, 412, 100])
                          [None].astype(np.int32))
        kw.update(seg_q=seg, seg_kv=seg)
    elif name == "window":
        kw.update(window=300)
    elif name == "q_offset":
        kw.update(q_offset=256)
    elif name == "noncausal":
        kw.update(causal=False)
    return (q, k, v, do), kw


@pytest.mark.parametrize(
    "name", ["causal", "gqa", "segments", "window", "q_offset",
             "noncausal"])
def test_flash_derived_geometry_matches_reference_and_former_tiles(name):
    """Output, LSE and dq/dk/dv of the derived geometry (K walked in
    sub-tiles of 512 inside tiles of 1024, dk/dv on one whole tile)
    against XLA's attention and against the same
    call under the former explicit 512 x 1024 tiles."""
    from chainermn_tpu.ops.flash_attention import (
        flash_block_bwd,
        flash_block_fwd,
    )

    (q, k, v, do), kw = _block_case(name)

    def flash(block_q, block_k):
        blocks = dict(block_q=block_q, block_k=block_k, interpret=True)
        out, lse = flash_block_fwd(q, k, v, **kw, **blocks)
        return (out, lse) + flash_block_bwd(q, k, v, do, lse, out, **kw,
                                            **blocks)

    (ref_out, ref_lse), vjp = jax.vjp(
        lambda *a: _dense_block(*a, **kw), q, k, v)
    ref = (ref_out, ref_lse) + vjp((do, jnp.zeros_like(ref_lse)))
    derived = flash(None, None)
    former = flash(512, 1024)
    for what, a, b, c in zip(("out", "lse", "dq", "dk", "dv"), derived,
                             former, ref):
        np.testing.assert_allclose(a, c, rtol=2e-4, atol=2e-4,
                                   err_msg=f"{what} against XLA")
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                   err_msg=f"{what} against 512x1024")


def test_flash_derived_geometry_bias_grad_matches_reference():
    ks = jax.random.split(jax.random.PRNGKey(25), 4)
    q, k, v = (jax.random.normal(kk, (1, _T1K, 4, 64)) for kk in ks[:3])
    bias = 0.1 * jax.random.normal(ks[3], (1, 4, _T1K, _T1K))

    def grads(attn):
        return jax.grad(lambda *a: (attn(*a) ** 2).sum(),
                        argnums=(0, 1, 2, 3))(q, k, v, bias)

    def flash(**blocks):
        return grads(lambda q, k, v, b: flash_attention(
            q, k, v, causal=True, bias=b, bias_grad=True, interpret=True,
            **blocks))

    ref = grads(lambda q, k, v, b: dot_product_attention(
        q, k, v, causal=True, bias=b))
    derived = flash()
    former = flash(block_q=512, block_k=1024)
    for what, a, b, c in zip(("dq", "dk", "dv", "dbias"), derived, former,
                             ref):
        np.testing.assert_allclose(a, c, rtol=2e-4, atol=2e-4,
                                   err_msg=f"{what} against XLA")
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                   err_msg=f"{what} against 512x1024")


# ---------------------------------------------------------------------------
# The row statistics (log-sum-exp, delta) cross HBM as rows: [B, H, 1, Tq]
# ---------------------------------------------------------------------------

def _row_case(name):
    """``(q, k, v, do), seg, bias, kw`` in BTHD for one variant of the
    kernels' masks at a length that decides the row block: 1024 (two q
    blocks of 512 lanes in the forward and dq), 200 (the block is the
    whole row), 576 (halving 512 would end at 64, under a lane tile: the
    whole row again)."""
    T = {"whole_T_200": 200, "T_576": 576}.get(name, _T1K)
    kv_heads = 2 if name == "window_gqa" else 4
    ks = jax.random.split(jax.random.PRNGKey(29), 5)
    q = jax.random.normal(ks[0], (1, T, 4, 64))
    k = jax.random.normal(ks[1], (1, T, kv_heads, 64))
    v = jax.random.normal(ks[2], (1, T, kv_heads, 64))
    do = jax.random.normal(ks[3], (1, T, 4, 64))
    seg = bias = None
    kw = dict(causal=name != "noncausal")
    if name == "segment_ids":
        seg = jnp.asarray(np.repeat([0, 1, 2, 3], [300, 212, 412, 100])
                          [None].astype(np.int32))
    elif name == "trained_bias":
        bias = 0.1 * jax.random.normal(ks[4], (1, 4, T, T))
    elif name == "window_gqa":
        kw.update(window=300)
    return (q, k, v, do), seg, bias, kw


@pytest.mark.parametrize("name", [
    "causal", "noncausal", "segment_ids", "trained_bias", "window_gqa",
    "whole_T_200", "T_576"])
def test_flash_row_statistics_match_reference(name):
    """The kernels hand the log-sum-exp over as ``[B, H, 1, Tq]`` and
    take it back in that form (``delta`` they make themselves, from the
    forward's output): out, log-sum-exp, dq, dk, dv (and the bias
    gradient) against XLA's attention, at the geometry the kernels
    derive."""
    from chainermn_tpu.ops.flash_attention import (
        _flash_bwd,
        _flash_fwd,
        _geometry,
        _Layout,
    )

    (q, k, v, do), seg, bias, kw = _row_case(name)
    T = q.shape[1]
    # the q block is a multiple of 128 lanes or the whole row
    for walks in ("k", "q"):
        block_q = _geometry(T, T, walks=walks, **kw)[0]
        assert block_q % 128 == 0 or block_q == T, (walks, block_q)
    lay = _Layout.of(q.shape, k.shape)
    common = dict(scale=0.125, block_q=None, block_k=None, interpret=True,
                  **kw)
    out, lse = _flash_fwd(lay, lay.enter(q), lay.enter(k), lay.enter(v),
                          seg, seg, bias, **common)
    assert lse.shape == (1, 4, 1, T) and lse.dtype == jnp.float32
    got = _flash_bwd(lay, lay.enter(q), lay.enter(k), lay.enter(v), out,
                     lay.enter(do), lse, seg, seg, bias, bias is not None,
                     **common)
    got = tuple(lay.leave(x) for x in got[:3]) + tuple(got[3:])

    args = (q, k, v) if bias is None else (q, k, v, bias)
    (ref_out, ref_lse), vjp = jax.vjp(
        lambda q_, k_, v_, b_=None: _dense_block(
            q_, k_, v_, scale=0.125, seg_q=seg, seg_kv=seg, bias=b_, **kw),
        *args)
    ref = vjp((do, jnp.zeros_like(ref_lse)))
    want = (ref_out, ref_lse[:, :, None]) + tuple(ref)
    names = ("out", "lse", "dq", "dk", "dv", "dbias")
    for what, a, b in zip(names, (lay.leave(out), lse) + got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                   err_msg=f"{what} against XLA")


def test_flash_fully_masked_rows_keep_neg_inf_and_zero_gradients():
    """A query that sees no key (its segment id is on no key of the
    arriving block, as in a ring step): the output is 0, the log-sum-exp
    stays ``NEG_INF`` in its row and, under the ring's merged
    log-sum-exp, the row gives dq = 0 and nothing to dk or dv; the other
    rows are the reference's on the keys they see."""
    from chainermn_tpu.ops.attention import NEG_INF
    from chainermn_tpu.ops.flash_attention import (
        flash_block_bwd,
        flash_block_fwd,
    )

    T, dead = 256, slice(64, 192)
    ks = jax.random.split(jax.random.PRNGKey(31), 4)
    q, k, v, do = (jax.random.normal(kk, (1, T, 2, 32)) for kk in ks)
    seg_q = jnp.zeros((1, T), jnp.int32).at[:, dead].set(7)
    seg_kv = jnp.zeros((1, T), jnp.int32)
    kw = dict(causal=False, scale=32 ** -0.5, seg_q=seg_q, seg_kv=seg_kv,
              block_q=128, block_k=128, interpret=True)
    out, lse = flash_block_fwd(q, k, v, **kw)
    assert lse.shape == (1, 2, T)  # the ring's interface: [B, H, Tq]
    # the backward takes the ring's merged log-sum-exp, which is finite
    # in such a row (it saw another block's keys): exp(NEG_INF - lse) = 0
    merged = jnp.where(lse > NEG_INF, lse, 0.0)
    dq, dk, dv = flash_block_bwd(q, k, v, do, merged, out, **kw)

    np.testing.assert_array_equal(lse[:, :, dead], np.float32(NEG_INF))
    np.testing.assert_array_equal(out[:, dead], 0.0)
    np.testing.assert_array_equal(dq[:, dead], 0.0)
    live = np.r_[0:dead.start, dead.stop:T]
    (ref_out, ref_lse), vjp = jax.vjp(
        lambda q_, k_, v_: _dense_block(q_, k_, v_, causal=False,
                                        scale=kw["scale"]),
        q[:, live], k, v)
    ref_dq, ref_dk, ref_dv = vjp((do[:, live], jnp.zeros_like(ref_lse)))
    for what, a, b in (("out", out[:, live], ref_out),
                       ("lse", lse[:, :, live], ref_lse),
                       ("dq", dq[:, live], ref_dq), ("dk", dk, ref_dk),
                       ("dv", dv, ref_dv)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=what)


# ---------------------------------------------------------------------------
# The layout the kernels see: the projections' own rows, or [B*H, T, D]
# ---------------------------------------------------------------------------

#: name -> (q heads, kv heads, head width, heads a grid step takes from
#: ``[B, T, H*D]``; 0: the wrapper transposes)
_FORMS = {
    "heads_of_128": (2, 2, 128, 1),
    "heads_of_64_in_pairs": (4, 4, 64, 2),
    "width_96_transposed": (2, 2, 96, 0),
    "gqa_at_128": (4, 2, 128, 1),
    "gqa_at_64_transposed": (4, 2, 64, 0),
}
_MASKS = ("causal", "segment_ids", "bias_grad", "window")
_T_FORM = 256


def _form_case(form, mask, dtype=jnp.float32):
    """``(q, k, v, w), bias, flash keywords, reference keywords`` of one
    form under one mask, at B 2 and T 256."""
    H, Hkv, D, _ = _FORMS[form]
    ks = jax.random.split(jax.random.PRNGKey(37), 5)
    q = jax.random.normal(ks[0], (2, _T_FORM, H, D), dtype)
    k = jax.random.normal(ks[1], (2, _T_FORM, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (2, _T_FORM, Hkv, D), dtype)
    w = jax.random.normal(ks[3], (2, _T_FORM, H, D), dtype)
    bias, kw, ref_kw = None, {}, {}
    if mask == "segment_ids":
        seg = jnp.asarray(np.repeat([[0, 1, 2], [0, 3, 4]],
                                    [100, 56, 100], axis=1).astype(np.int32))
        kw, ref_kw = dict(segment_ids=seg), dict(seg_q=seg, seg_kv=seg)
    elif mask == "bias_grad":
        bias = 0.1 * jax.random.normal(ks[4], (1, H, _T_FORM, _T_FORM))
        kw = dict(bias_grad=True)
    elif mask == "window":
        kw = ref_kw = dict(window=72)
    return (q, k, v, w), bias, kw, ref_kw


def _out_and_grads(attn, q, k, v, w, bias):
    """``attn``'s output and the gradients of ``sum(out * w)`` by q, k,
    v (and the bias)."""
    args = (q, k, v) if bias is None else (q, k, v, bias)
    out, vjp = jax.vjp(attn, *args)
    return (out,) + vjp(w.astype(out.dtype))


@pytest.mark.parametrize("blocks", [None, 128], ids=["derived", "128x128"])
@pytest.mark.parametrize("mask", _MASKS)
@pytest.mark.parametrize("form", _FORMS)
def test_flash_layout_forms_match_reference(form, mask, blocks):
    """Every form of the kernels' layout under every mask: output and
    all gradients against XLA's attention (the bare causal case against
    ``blockwise_attention`` too), at the geometry the kernels derive
    (one step a row) and on tiles of 128 x 128, where the rows' running
    statistics and the accumulators are kept across steps; the gradients
    come back in their operands' dtype; the gauge says which form
    engaged."""
    from chainermn_tpu.observability import train_path
    from chainermn_tpu.observability.metrics import registry

    (q, k, v, w), bias, kw, ref_kw = _form_case(form, mask)
    scale = q.shape[-1] ** -0.5

    def flash(q_, k_, v_, b_=None):
        return flash_attention(q_, k_, v_, causal=True, bias=b_,
                               block_q=blocks, block_k=blocks,
                               interpret=True, **kw)

    def dense(q_, k_, v_, b_=None):
        return _dense_block(q_, k_, v_, causal=True, scale=scale, bias=b_,
                            **ref_kw)[0]

    got = _out_and_grads(flash, q, k, v, w, bias)
    want = _out_and_grads(dense, q, k, v, w, bias)
    for what, a, b in zip(("out", "dq", "dk", "dv", "dbias"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, what
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                   err_msg=f"{what} against XLA")
    if mask == "causal" and _FORMS[form][0] == _FORMS[form][1]:
        blockwise = _out_and_grads(
            lambda q_, k_, v_: blockwise_attention(
                q_, k_, v_, block_k=64, causal=True), q, k, v, w, None)
        for what, a, b in zip(("out", "dq", "dk", "dv"), got, blockwise):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4,
                                       err_msg=f"{what} against blockwise")
    gauge = registry().gauge(train_path.FLASH_HEADS_PER_BLOCK)
    assert [gauge.value(kernel=kernel) for kernel in _FLASH_KERNELS] \
        == [_FORMS[form][3]] * 3


@pytest.mark.parametrize("blocks", [None, 128], ids=["derived", "128x128"])
@pytest.mark.parametrize("mask", _MASKS)
def test_flash_pairs_equal_the_transposed_form_to_the_bit(
        monkeypatch, mask, blocks):
    """Two heads of 64 in one 128-lane block give the bits one head a
    ``[B*H, T, D]`` row gives on the same bf16 operands: a head is read
    and written through its own lanes of the block, so its matmuls and
    its rounding are those of a head alone."""
    import importlib

    flash_mod = importlib.import_module("chainermn_tpu.ops.flash_attention")
    (q, k, v, w), bias, kw, _ = _form_case("heads_of_64_in_pairs", mask,
                                           jnp.bfloat16)

    def run():
        # under no jit of the op's own: each call is traced as patched
        seg = kw.get("segment_ids", jnp.zeros((0,), jnp.int32))
        b = jnp.zeros((0,), q.dtype) if bias is None else bias
        return _out_and_grads(
            lambda q_, k_, v_, b_=b: flash_mod._flash_core(
                q_, k_, v_, seg, b_, "segment_ids" in kw, bias is not None,
                bias is not None, True, 0.125, blocks, blocks, True,
                kw.get("window")),
            q, k, v, w, bias)

    lay = flash_mod._Layout.of(q.shape, k.shape)
    assert lay.heads == 2
    pairs = run()
    monkeypatch.setattr(flash_mod._Layout, "of",
                        classmethod(lambda cls, *_: lay._replace(heads=0)))
    transposed = run()
    for what, a, b in zip(("out", "dq", "dk", "dv", "dbias"), pairs,
                          transposed):
        assert a.dtype == b.dtype, what
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32), what)


@pytest.mark.parametrize("form", _FORMS)
def test_flash_gradient_dtypes(form):
    """The op's gradients leave the kernels in their operands' dtype
    (with GQA dk/dv after the group sum); a ring step's partial
    gradients are float32 whatever the operands'."""
    from chainermn_tpu.ops.flash_attention import (
        flash_block_bwd,
        flash_block_fwd,
    )

    (q, k, v, w), _, _, _ = _form_case(form, "causal", jnp.bfloat16)
    grads = _out_and_grads(
        lambda *a: flash_attention(*a, causal=True, interpret=True),
        q, k, v, w, None)[1:]
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3
    kw = dict(causal=True, scale=q.shape[-1] ** -0.5, block_q=None,
              block_k=None, interpret=True)
    out, lse = flash_block_fwd(q, k, v, **kw)
    partial = flash_block_bwd(q, k, v, w, lse, out, **kw)
    assert [g.dtype for g in partial] == [jnp.float32] * 3
    assert [g.shape for g in partial] == [q.shape, k.shape, v.shape]
    for what, a, b in zip(("dq", "dk", "dv"), grads, partial):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32),
            np.asarray(b.astype(jnp.bfloat16), np.float32), what)
