"""The gated short convolution's two kernels (``ops/short_conv.py``, ISSUE
41) under the interpreter against the plain ``jax.numpy`` spelling kept
as the oracle: forward and both gradients over the taps' count, the
blocks' edges, the strips of columns, the batch and the two dtypes; the same
gradients through ``nn.remat`` under the ``dots`` policy; and the shapes
that do not tile, which take the plain path."""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models.transformer import _REMAT_POLICIES
from chainermn_tpu.observability import train_path
from chainermn_tpu.observability.metrics import registry
from chainermn_tpu.ops import short_conv

B, D = 2, 256

#: ``(T, rows of a block, columns of a strip)``: one block walked whole;
#: four blocks, so that three edges lie between blocks (a tap reaches
#: across each), in two strips; two blocks of two tiles of rows each
LAYOUTS = {
    "one_block": (32, 32, 256),
    "several_blocks": (64, 16, 128),
    "two_blocks_of_two_tiles": (64, 32, 128),
}


def _fused_gauge():
    rows = registry().snapshot()[train_path.SHORT_CONV_FUSED]["values"]
    assert len(rows) == 1
    return rows[0]["value"]


def _oracle(bcx, taps):
    """The plain spelling in float32 on what the operands' dtype holds,
    with its gradients (the taps' rounding passed straight through)."""
    return jax.vjp(
        lambda a, w: short_conv.plain(a.astype(jnp.float32), w),
        bcx, taps.astype(bcx.dtype).astype(jnp.float32))


def _shaped(monkeypatch, dtype, T, rows, cols):
    """Make :func:`short_conv._geometry` cut ``[B, T, 3D]`` of ``dtype``
    into blocks of ``rows`` walked in strips of ``cols``."""
    item = jnp.dtype(dtype).itemsize
    monkeypatch.setattr(short_conv, "_BLOCK_BYTES", rows * 3 * D * item)
    monkeypatch.setattr(short_conv, "_STRIP_COLS", cols)
    assert short_conv._geometry(T, D, 3, dtype) == (rows, cols, 32 // item)


@functools.lru_cache(maxsize=None)
def _both(L, layout, dtype_name):
    """``(y, dbcx, dtaps)`` of the kernels and of the oracle on one seeded
    case."""
    dtype = jnp.dtype(dtype_name)
    T, rows, cols = LAYOUTS[layout]
    keys = jax.random.split(jax.random.key(7 + L), 3)
    bcx = jax.random.normal(keys[0], (B, T, 3 * D), dtype)
    taps = jax.random.uniform(keys[1], (L, D), jnp.float32, -1.0, 1.0)
    dy = jax.random.normal(keys[2], (B, T, D), dtype)
    with pytest.MonkeyPatch.context() as patch:
        _shaped(patch, dtype, T, rows, cols)
        y, vjp = jax.vjp(short_conv.gated_short_conv, bcx, taps)
        assert _fused_gauge() == 1.0
        got = (y, *vjp(dy))
    want_y, vjp = _oracle(bcx, taps)
    return got, (want_y, *vjp(dy.astype(jnp.float32)))


@pytest.mark.parametrize("what", ["forward", "grad_bcx", "grad_taps"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("L", [1, 2, 3, 4])
def test_the_kernels_match_the_plain_spelling(L, layout, dtype, what):
    got, want = _both(L, layout, dtype)
    i = ["forward", "grad_bcx", "grad_taps"].index(what)
    got, want = got[i], want[i]
    assert got.shape == want.shape
    if what == "grad_taps":
        # float32 sums over all B * T rows, whatever the operands' dtype
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    elif dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    else:
        # float32 inside, one rounding of each output
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(got.astype(jnp.float32), want,
                                   rtol=2.0 ** -7, atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_row_sees_zeros_before_it_and_no_other_row(dtype, monkeypatch):
    T, L = 64, 3
    _shaped(monkeypatch, dtype, T, 16, 128)
    keys = jax.random.split(jax.random.key(3), 2)
    bcx = jax.random.normal(keys[0], (3, T, 3 * D), dtype)
    taps = jax.random.uniform(keys[1], (L, D), jnp.float32, -1.0, 1.0)
    y = short_conv.gated_short_conv(bcx, taps)
    for row in range(3):
        alone = short_conv.gated_short_conv(bcx[row:row + 1], taps)
        assert jnp.array_equal(y[row:row + 1], alone)
    b, c, x = (t.astype(jnp.float32) for t in jnp.split(bcx, 3, axis=-1))
    w = taps.astype(dtype).astype(jnp.float32)
    first = c[:, 0] * (w[L - 1] * b[:, 0] * x[:, 0])
    np.testing.assert_allclose(y[:, 0].astype(jnp.float32), first,
                               rtol=2.0 ** -7, atol=1e-6)


class _Mixer(nn.Module):
    """A short convolution's two projections round the chain."""
    chain: callable

    @nn.compact
    def __call__(self, h):
        bcx = nn.Dense(3 * D, use_bias=False, name="conv_in")(h)
        taps = self.param("conv_w", nn.initializers.uniform(1.0), (3, D),
                          jnp.float32)
        return nn.Dense(D, use_bias=False, name="conv_out")(
            self.chain(bcx, taps))


@pytest.mark.parametrize("leaf", ["conv_in", "conv_w", "conv_out", "h"])
def test_gradients_through_remat_under_dots(leaf, remat_grads):
    got, want = remat_grads
    err = jnp.linalg.norm(got[leaf] - want[leaf]) / jnp.linalg.norm(
        want[leaf])
    assert float(err) < 1e-6


@pytest.fixture(scope="module")
def remat_grads():
    """Gradients of a remat'ed mixer on the kernels and of a plain one on
    the oracle, and the lowered text of the first."""
    T = 64
    h = jax.random.normal(jax.random.key(5), (B, T, D), jnp.float32)
    fused = nn.remat(_Mixer, policy=_REMAT_POLICIES["dots"])(
        short_conv.gated_short_conv)
    plain = _Mixer(short_conv.plain)
    params = plain.init(jax.random.key(6), h)["params"]

    def grads(model):
        def loss(p, h):
            with jax.default_matmul_precision("highest"):
                return (model.apply({"params": p}, h) ** 2).sum()
        dp, dh = jax.grad(loss, (0, 1))(params, h)
        return {"conv_in": dp["conv_in"]["kernel"], "conv_w": dp["conv_w"],
                "conv_out": dp["conv_out"]["kernel"], "h": dh}

    with pytest.MonkeyPatch.context() as patch:
        _shaped(patch, jnp.float32, T, 16, 128)
        got = grads(fused)
        assert _fused_gauge() == 1.0
    return got, grads(plain)


def test_remat_under_dots_runs_the_forward_kernel_again_and_keeps_no_y(
        monkeypatch):
    T = 64
    _shaped(monkeypatch, jnp.float32, T, 16, 128)
    h = jnp.zeros((B, T, D), jnp.float32)
    fused = nn.remat(_Mixer, policy=_REMAT_POLICIES["dots"])(
        short_conv.gated_short_conv)
    params = jax.eval_shape(lambda: fused.init(jax.random.key(0), h))[
        "params"]

    def loss(p, h):
        return fused.apply({"params": p}, h).sum()

    # the gradient's program: one forward kernel outside the remat'ed
    # part, and in it the forward again and the backward
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(params, h).jaxpr
    (remat,) = [e for e in jaxpr.eqns if "jaxpr" in e.params
                and "policy" in e.params]
    outside = _kernels(jaxpr, skip=remat)
    inside = _kernels(remat.params["jaxpr"])
    assert outside == [short_conv.FWD]
    assert sorted(inside) == [short_conv.BWD, short_conv.FWD]
    # what crosses from the forward to the backward: the dots' results
    # (``bcx`` among them) and the arguments; nothing of the chain, no
    # ``u``, ``conv`` or ``y``
    shapes = sorted(v.aval.shape for v in remat.invars
                    if v.aval.shape[:2] == (B, T))
    assert shapes == [(B, T, D), (B, T, D), (B, T, 3 * D)]


def _kernels(jaxpr, skip=None):
    """The names of the ``pallas_call``s of a jaxpr and what it calls."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn is skip:
            continue
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _kernels(sub)
    return names


@pytest.mark.parametrize("case", ["d_64", "t_24_bf16", "ten_taps",
                                  "integers"])
def test_a_shape_that_does_not_tile_takes_the_plain_path(case):
    shape, L, dtype = {
        "d_64": ((2, 32, 3 * 64), 3, jnp.float32),
        "t_24_bf16": ((2, 24, 3 * 128), 3, jnp.bfloat16),
        "ten_taps": ((2, 32, 3 * 128), 10, jnp.float32),
        "integers": ((2, 32, 3 * 128), 3, jnp.int32),
    }[case]
    d = shape[2] // 3
    assert short_conv._geometry(shape[1], d, L, dtype) is None
    registry().gauge(train_path.SHORT_CONV_FUSED).set(1.0)
    keys = jax.random.split(jax.random.key(11), 2)
    bcx = (4 * jax.random.normal(keys[0], shape)).astype(dtype)
    taps = jax.random.uniform(keys[1], (L, d), jnp.float32, -1.0, 1.0)
    y = short_conv.gated_short_conv(bcx, taps)
    assert _fused_gauge() == 0.0
    assert jnp.array_equal(y, short_conv.plain(bcx, taps))
    text = jax.jit(short_conv.gated_short_conv).lower(bcx, taps).as_text(
        debug_info=True)
    assert "/short_conv/" in text and "pallas_call" not in text


def test_the_geometry_of_the_cells_shape_and_what_it_refuses():
    assert short_conv._geometry(8192, 2048, 3, jnp.bfloat16) == (
        512, 512, 16)
    # the same bytes a block in float32; what a grid step of the backward
    # holds twice (``bcx``, ``dbcx`` and ``dy``) is under half the limit
    assert short_conv._geometry(8192, 2048, 3, jnp.float32) == (256, 512, 8)
    assert 2 * 7 * 512 * 2048 * 2 < short_conv._VMEM_LIMIT // 2
    # a short sequence is one block, a narrow model one strip
    assert short_conv._geometry(16, 128, 3, jnp.bfloat16) == (16, 128, 16)
    # rows that no block of the wanted size divides: the largest that does
    assert short_conv._geometry(8192 + 16, 2048, 3, jnp.bfloat16)[0] == 432
    with pytest.raises(ValueError, match="bcx \\[B, T, 3D\\]"):
        short_conv.gated_short_conv(jnp.zeros((2, 16, 256)),
                                    jnp.zeros((3, 128)))
