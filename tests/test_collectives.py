"""Direct unit coverage of the in-program collective primitives
(:mod:`chainermn_tpu.parallel.collectives`) — the L0/L2-equivalent layer
every communicator and parallelism module builds on (SURVEY.md section 1).
Most are exercised transitively by the communicator/parallelism suites;
these tests pin the primitive semantics themselves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from chainermn_tpu.parallel import collectives as C

N = 8


@pytest.fixture(scope="module")
def mesh():
    return Mesh(np.array(jax.devices("cpu")[:N]), ("x",))


def _run(mesh, fn, *args, in_specs=None, out_specs=P("x")):
    in_specs = in_specs if in_specs is not None else (P("x"),) * len(args)
    return jax.jit(
        shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    )(*args)


def test_allreduce_ops(mesh):
    x = jnp.arange(N, dtype=jnp.float32).reshape(N, 1)
    for op, want in [("sum", x.sum()), ("max", x.max()), ("min", x.min()),
                     ("mean", x.mean())]:
        out = _run(mesh, lambda v: C.allreduce(v, "x", op=op), x)
        np.testing.assert_allclose(np.asarray(out).ravel(),
                                   np.full(N, float(want)), rtol=1e-6)
    with pytest.raises(ValueError):
        _run(mesh, lambda v: C.allreduce(v, "x", op="prod"), x)


def test_shift_rotates_ring(mesh):
    x = jnp.arange(N, dtype=jnp.float32).reshape(N, 1)
    fwd = _run(mesh, lambda v: C.shift(v, "x", 1), x)
    # shard i's value travels to shard i+1: shard j now holds j-1's value
    np.testing.assert_array_equal(
        np.asarray(fwd).ravel(), np.roll(np.arange(N), 1)
    )
    back = _run(mesh, lambda v: C.shift(v, "x", -1), x)
    np.testing.assert_array_equal(
        np.asarray(back).ravel(), np.roll(np.arange(N), -1)
    )
    # a full loop restores the input
    def loop(v):
        for _ in range(N):
            v = C.shift(v, "x", 1)
        return v

    same = _run(mesh, loop, x)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(x))


def test_reduce_scatter_matches_psum_slice(mesh):
    rows = jnp.asarray(
        np.random.RandomState(0).randn(N, N, 3), np.float32
    )  # per-shard [N, 3] contribution

    def local(v):
        return C.reduce_scatter(v[0], "x")

    out = _run(mesh, local, rows)
    want = np.asarray(rows).sum(axis=0)  # [N, 3]; shard i keeps row i
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


def test_bcast_root_value_everywhere(mesh):
    x = jnp.arange(N, dtype=jnp.float32).reshape(N, 1)
    out = _run(mesh, lambda v: C.bcast(v, "x", root=3), x)
    np.testing.assert_array_equal(np.asarray(out).ravel(), np.full(N, 3.0))


def test_axes_bound_inside_and_outside(mesh):
    assert C.axes_bound("x") is False  # eager: no axis context

    def local(v):
        assert C.axes_bound("x")
        assert C.axes_bound(("x",))
        assert not C.axes_bound("nope")
        return v

    _run(mesh, local, jnp.zeros((N, 1)))


def test_two_level_allreduce_sum_op():
    devs = np.array(jax.devices("cpu")[:8]).reshape(2, 4)
    mesh2 = Mesh(devs, ("inter", "intra"))
    x = jnp.asarray(np.random.RandomState(1).randn(8, 5), np.float32)

    def local(v):
        return C.two_level_allreduce(v[0], "intra", "inter", op="sum")[None]

    out = jax.jit(shard_map(
        local, mesh=mesh2, in_specs=P(("inter", "intra")),
        out_specs=P(("inter", "intra")), check_vma=False,
    ))(x)
    want = np.asarray(x).sum(axis=0)
    for row in np.asarray(out):
        np.testing.assert_allclose(row, want, rtol=1e-5)


@pytest.mark.parametrize("mesh_shape,names", [
    ((4,), ("x",)), ((8,), ("x",)), ((3,), ("x",)), ((2, 2), ("a", "b")),
])
@pytest.mark.parametrize("shape,axis", [
    ((24, 5), 0), ((2, 48, 7), 1), ((24, 1), 0), ((25, 5), 0), ((2, 7, 3), 1),
])
def test_all_to_all_mean_is_the_mean_on_every_member(mesh_shape, names,
                                                     shape, axis):
    """Two all_to_alls along a dimension give every member the mean, in
    the array's own shape, and the same bits everywhere (a slice's mean
    is made on one member); a dimension the group's size does not divide
    is padded for the flight and cut back."""
    n = int(np.prod(mesh_shape))
    mesh = Mesh(np.array(jax.devices("cpu")[:n]).reshape(mesh_shape), names)
    x = jnp.asarray(np.random.RandomState(3).randn(n, *shape), jnp.float32)
    fn = jax.jit(shard_map(
        lambda v: C.all_to_all_mean(v[0], names, axis)[None],
        mesh=mesh, in_specs=P(names), out_specs=P(names), check_vma=False))
    got = np.asarray(fn(x))
    assert got.shape == (n,) + shape
    np.testing.assert_allclose(got[0], np.asarray(x).mean(0), rtol=1e-6,
                               atol=1e-6)
    for r in range(1, n):
        np.testing.assert_array_equal(got[r], got[0])


def test_all_to_all_mean_rounds_the_float32_sum_once():
    """On a bfloat16 wire the n pieces are summed in float32 and the mean
    rounded once: no coarser than an all-reduce that rounds at every hop."""
    n = 4
    mesh = Mesh(np.array(jax.devices("cpu")[:n]), ("x",))
    x = jnp.asarray(np.random.RandomState(4).randn(n, 8, 128),
                    jnp.bfloat16)
    fn = jax.jit(shard_map(
        lambda v: C.all_to_all_mean(v[0], "x")[None],
        mesh=mesh, in_specs=P("x"), out_specs=P("x"), check_vma=False))
    want = (np.asarray(x, np.float32).sum(0) / n).astype(jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(fn(x)[0]), want)


@pytest.mark.parametrize("shape,n,want", [
    ((50304, 1024), 4, 0),      # the first dimension n divides
    ((3, 3, 64, 64), 4, 2),     # never the minor one
    ((50257, 1024), 4, 0),      # none divides: the least padding
    ((3, 1000, 7), 4, 1),
    ((5, 6), 4, 0),
    ((1024,), 4, None),         # a vector is left to the all-reduce
    ((), 4, None),
])
def test_all_to_all_split_axis(shape, n, want):
    assert C.all_to_all_split_axis(shape, n) == want
