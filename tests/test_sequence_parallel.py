"""Sequence-parallelism tests: ring attention and Ulysses all_to_all
attention must equal single-device full attention on the concatenated
sequence (values AND gradients) — the reference test suite's distributed ==
single-process invariant (SURVEY.md section 4) applied to the new
long-context layer (section 5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from chainermn_tpu.ops.attention import (
    blockwise_attention,
    dot_product_attention,
)
from chainermn_tpu.parallel.ring_attention import make_ring_attention
from chainermn_tpu.parallel.ulysses import make_ulysses_attention

B, T, H, D = 2, 32, 8, 16  # T sharded 8-ways -> T_local = 4


def _qkv(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


class TestLocalAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_blockwise_matches_full(self, causal):
        q, k, v = _qkv()
        ref = dot_product_attention(q, k, v, causal=causal)
        blk = blockwise_attention(q, k, v, block_k=8, causal=causal)
        np.testing.assert_allclose(blk, ref, rtol=1e-5, atol=1e-5)

    def test_blockwise_grads_match_full(self):
        q, k, v = _qkv(1)

        def loss_ref(q, k, v):
            return dot_product_attention(q, k, v, causal=True).sum()

        def loss_blk(q, k, v):
            return blockwise_attention(q, k, v, block_k=8, causal=True).sum()

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_blk = jax.grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4),
            g_blk,
            g_ref,
        )


class TestRingAttention:
    """Both impls must satisfy the distributed == single-device invariant:
    'einsum' is the autodiff reference; 'flash' is the Pallas block-kernel
    path with the hand-written ring backward (the production path)."""

    @pytest.mark.parametrize("impl", ["einsum", "flash"])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, comm, causal, impl):
        q, k, v = _qkv(2)
        ref = dot_product_attention(q, k, v, causal=causal)

        fn = make_ring_attention(
            comm.mesh, comm.axis_name, causal=causal, impl=impl
        )
        sharding = NamedSharding(comm.mesh, P(None, comm.axis_name))
        qs, ks, vs = (jax.device_put(t, sharding) for t in (q, k, v))
        out = fn(qs, ks, vs)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("impl", ["einsum", "flash"])
    def test_grads_match_full_attention(self, comm, impl):
        q, k, v = _qkv(3)
        fn = make_ring_attention(
            comm.mesh, comm.axis_name, causal=True, impl=impl
        )

        def loss_ring(q, k, v):
            return (fn(q, k, v) ** 2).sum()

        def loss_ref(q, k, v):
            return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), b, rtol=1e-4, atol=1e-4
            ),
            g_ring,
            g_ref,
        )

    def test_zigzag_matches_full_attention(self, comm):
        """Zigzag layout (balanced causal ring): same values as dense causal
        attention on the ORIGINAL sequence order — ``make_ring_attention``
        converts to chunk-pair order and back internally."""
        q, k, v = _qkv(5)
        ref = dot_product_attention(q, k, v, causal=True)
        fn = make_ring_attention(
            comm.mesh, comm.axis_name, causal=True, layout="zigzag"
        )
        sharding = NamedSharding(comm.mesh, P(None, comm.axis_name))
        qs, ks, vs = (jax.device_put(t, sharding) for t in (q, k, v))
        np.testing.assert_allclose(
            np.asarray(fn(qs, ks, vs)), ref, rtol=1e-5, atol=1e-5
        )

    def test_zigzag_grads_match_full_attention(self, comm):
        q, k, v = _qkv(6)
        fn = make_ring_attention(
            comm.mesh, comm.axis_name, causal=True, layout="zigzag"
        )

        def loss_ring(q, k, v):
            return (fn(q, k, v) ** 2).sum()

        def loss_ref(q, k, v):
            return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), b, rtol=1e-4, atol=1e-4
            ),
            g_ring,
            g_ref,
        )

    def test_zigzag_layout_roundtrip(self):
        from chainermn_tpu.parallel.ring_attention import (
            from_zigzag,
            to_zigzag,
            zigzag_indices,
        )

        x = jnp.arange(64, dtype=jnp.float32).reshape(1, 32, 2)
        zz = to_zigzag(x, 8, axis=1)
        np.testing.assert_array_equal(np.asarray(from_zigzag(zz, 8, axis=1)),
                                      np.asarray(x))
        idx = zigzag_indices(4, 32)
        # shard 0 of 4 holds chunks 0 and 7 of 8 (chunk size 4)
        np.testing.assert_array_equal(idx[:8], [0, 1, 2, 3, 28, 29, 30, 31])

    def test_zigzag_requires_causal_flash(self, comm):
        from chainermn_tpu.parallel.ring_attention import ring_attention_local

        q = jnp.zeros((1, 4, 1, 8))
        with pytest.raises(ValueError, match="zigzag"):
            ring_attention_local(q, q, q, "seq", causal=False, layout="zigzag")
        with pytest.raises(ValueError, match="zigzag"):
            ring_attention_local(q, q, q, "seq", causal=True, impl="einsum",
                                 layout="zigzag")

    @pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
    def test_segment_ids_match_masked_dense(self, comm, layout):
        """Packed sequences across the ring: segment ids travel with their
        K/V blocks, so cross-document attention is masked even when the
        documents span shard boundaries. Values AND grads vs the dense
        masked reference."""
        q, k, v = _qkv(7)
        rng = np.random.RandomState(2)
        seg = np.zeros((B, T), np.int32)
        for b in range(B):
            cuts = sorted(rng.choice(np.arange(2, T - 2), 2, replace=False))
            seg[b, cuts[0]:cuts[1]] = 1
            seg[b, cuts[1]:] = 2
        seg = jnp.asarray(seg)

        fn = make_ring_attention(
            comm.mesh, comm.axis_name, causal=True, layout=layout,
            with_segments=True,
        )

        def loss_ring(q, k, v):
            return (fn(q, k, v, seg) ** 2).sum()

        def loss_ref(q, k, v):
            return (dot_product_attention(
                q, k, v, causal=True, segment_ids=seg) ** 2).sum()

        np.testing.assert_allclose(
            np.asarray(fn(q, k, v, seg)),
            np.asarray(dot_product_attention(q, k, v, causal=True,
                                             segment_ids=seg)),
            rtol=1e-5, atol=1e-5,
        )
        g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), b, rtol=1e-4, atol=1e-4
            ),
            g_ring,
            g_ref,
        )

    def test_gqa_zigzag_grads(self, comm):
        """GQA × zigzag layout: the backward's zero-pads must use the KV
        head count where dk/dv concatenate (regression: q-head-shaped pads
        crashed the trace)."""
        ks = jax.random.split(jax.random.PRNGKey(11), 3)
        q = jax.random.normal(ks[0], (B, T, H, D))
        k = jax.random.normal(ks[1], (B, T, 2, D))
        v = jax.random.normal(ks[2], (B, T, 2, D))
        fn = make_ring_attention(comm.mesh, comm.axis_name, causal=True,
                                 layout="zigzag")
        np.testing.assert_allclose(
            np.asarray(fn(q, k, v)),
            np.asarray(dot_product_attention(q, k, v, causal=True)),
            rtol=1e-5, atol=1e-5,
        )
        g = jax.grad(lambda a, b, c: (fn(a, b, c) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            lambda a, b, c: (dot_product_attention(
                a, b, c, causal=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            ),
            g, g_ref,
        )

    def test_gqa_kv_heads_rotate_small(self, comm):
        """GQA through the ring: kv blocks rotate at their own (smaller)
        head count; output matches the dense GQA reference."""
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(ks[0], (B, T, H, D))
        k = jax.random.normal(ks[1], (B, T, 2, D))
        v = jax.random.normal(ks[2], (B, T, 2, D))
        fn = make_ring_attention(comm.mesh, comm.axis_name, causal=True)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(fn(q, k, v)), ref,
                                   rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda a, b, c: (fn(a, b, c) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            lambda a, b, c: (dot_product_attention(
                a, b, c, causal=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            ),
            g, g_ref,
        )

    def test_bf16_inputs_f32_accumulation(self, comm):
        q, k, v = _qkv(4, jnp.bfloat16)
        fn = make_ring_attention(comm.mesh, comm.axis_name)
        out = fn(q, k, v)
        assert out.dtype == jnp.bfloat16
        ref = dot_product_attention(
            q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
        )
        np.testing.assert_allclose(
            np.asarray(out, np.float32), ref, rtol=2e-2, atol=2e-2
        )


class TestUlyssesAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, comm, causal):
        q, k, v = _qkv(5)
        ref = dot_product_attention(q, k, v, causal=causal)
        fn = make_ulysses_attention(comm.mesh, comm.axis_name, causal=causal)
        out = fn(q, k, v)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)

    def test_grads_match_full_attention(self, comm):
        q, k, v = _qkv(6)
        fn = make_ulysses_attention(comm.mesh, comm.axis_name, causal=True)

        def loss_u(q, k, v):
            return (fn(q, k, v) ** 2).sum()

        def loss_ref(q, k, v):
            return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

        g_u = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), b, rtol=1e-4, atol=1e-4
            ),
            g_u,
            g_ref,
        )

    def test_head_divisibility_enforced(self, comm):
        # H=6 not divisible by the 8-way axis
        q = jnp.zeros((B, T, 6, D))
        fn = make_ulysses_attention(comm.mesh, comm.axis_name)
        with pytest.raises(ValueError, match="not divisible"):
            fn(q, q, q)

    def test_segment_ids_match_masked_dense(self, comm):
        """Packed segments through Ulysses: local id slices are
        all-gathered for the head-sharded full-sequence kernel."""
        q, k, v = _qkv(8)
        rng = np.random.RandomState(3)
        seg = np.zeros((B, T), np.int32)
        for b in range(B):
            cut = rng.randint(4, T - 4)
            seg[b, cut:] = 1
        seg = jnp.asarray(seg)
        fn = make_ulysses_attention(
            comm.mesh, comm.axis_name, causal=True, with_segments=True
        )
        ref = dot_product_attention(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(np.asarray(fn(q, k, v, seg)), ref,
                                   rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda a, b_, c: (fn(a, b_, c, seg) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            lambda a, b_, c: (dot_product_attention(
                a, b_, c, causal=True, segment_ids=seg) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b_: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-4
            ),
            g, g_ref,
        )

    def test_gqa_kv_heads_reshard(self, comm):
        """GQA through Ulysses: 16 q heads with 8 kv heads (== axis size,
        the minimum reshardable count) — the reshard must keep head groups
        aligned with the kernel's kv-sharing index map. Values AND grads."""
        ks = jax.random.split(jax.random.PRNGKey(10), 3)
        q = jax.random.normal(ks[0], (B, T, 16, D))
        k = jax.random.normal(ks[1], (B, T, 8, D))
        v = jax.random.normal(ks[2], (B, T, 8, D))
        fn = make_ulysses_attention(comm.mesh, comm.axis_name, causal=True)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(fn(q, k, v)), ref,
                                   rtol=1e-5, atol=1e-5)
        g = jax.grad(lambda a, b_, c: (fn(a, b_, c) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(
            lambda a, b_, c: (dot_product_attention(
                a, b_, c, causal=True) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b_: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-4
            ),
            g, g_ref,
        )
        # a kv head count below the axis size is rejected with a clear error
        k2 = jnp.zeros((B, T, 2, D))
        with pytest.raises(ValueError, match="kv heads"):
            fn(q, k2, k2)


def test_zigzag_causal_work_is_balanced():
    """Structural evidence for VERDICT r2 item 4's done-criterion: under
    the zigzag layout every (shard, ring-step) dispatches to a branch
    costing the SAME 2 chunk-squared score evaluations, so causal-ring
    wall clock is the per-step constant times n — not the last shard's
    full-n work as in the contiguous layout. (Wall-clock itself is not
    honestly measurable on virtual CPU devices; the dispatch arithmetic
    is what the kernel schedule executes.)

    Uses the implementation's own `_zz_branch` dispatch; branch costs in
    chunk^2 units read off the kernel calls in
    `_zigzag_ring_flash_fwd_impl`: _past = full q x front kv = 2;
    _diag = 0.5 + 1 + 0.5 = 2; _future = back q x full kv = 2.
    """
    from chainermn_tpu.parallel.ring_attention import _zz_branch

    for n in (2, 4, 8):
        for my in range(n):
            hist = {0: 0, 1: 0, 2: 0}  # _past, _diag, _future
            for s in range(n):
                hist[int(_zz_branch(jnp.int32(my), jnp.int32(s), n))] += 1
            # Shard `my` must dispatch: `my` past steps, exactly ONE
            # diagonal, and n-1-my future steps — pinning the dispatch
            # itself, from which the constant cost follows (branch costs
            # read off the kernel calls are past=2, diag=0.5+1+0.5=2,
            # future=2 chunk^2, so any histogram summing to n gives the
            # same total; the histogram is the discriminating check).
            assert hist == {0: my, 1: 1, 2: n - 1 - my}, (n, my, hist)
    # (Contrast, not executable here: the CONTIGUOUS layout's causal ring
    # — step() at ring_attention.py:151 — gives shard s a cost of s full
    # blocks + 1 diagonal, a 15x last-vs-first spread at n=8; that is the
    # imbalance the zigzag layout removes.)


class TestSlidingWindowSP:
    """O(1)-communication sequence-parallel local attention: one neighbour
    -tail exchange must reproduce single-device windowed flash attention
    (values AND gradients) when window - 1 <= T_local."""

    def _dist(self, comm, window, seed=30, kv_heads=None, seg=None):
        from jax import shard_map

        from chainermn_tpu.parallel.local_attention import (
            sliding_window_attention_local,
        )

        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (B, T, H, D))
        hkv = kv_heads or H
        k = jax.random.normal(ks[1], (B, T, hkv, D))
        v = jax.random.normal(ks[2], (B, T, hkv, D))

        def local(q, k, v, s):
            return sliding_window_attention_local(
                q, k, v, comm.axis_name, window=window,
                segment_ids=None if seg is None else s,
                block_q=4, block_k=4, interpret=True,
            )

        ax = comm.axis_name
        s_arg = (seg if seg is not None
                 else jnp.zeros((B, T), jnp.int32))
        out = jax.jit(
            shard_map(
                local, mesh=comm.mesh,
                in_specs=(P(None, ax), P(None, ax), P(None, ax),
                          P(None, ax)),
                out_specs=P(None, ax), check_vma=False,
            )
        )(q, k, v, s_arg)
        return q, k, v, out

    def _ref(self, q, k, v, window, seg=None):
        from chainermn_tpu.ops.flash_attention import flash_attention

        return flash_attention(
            q, k, v, causal=True, window=window, segment_ids=seg,
            block_q=8, block_k=8, interpret=True,
        )

    @pytest.mark.parametrize("window", [2, 3, 5])  # T_local = 4: max W-1=4
    def test_matches_single_device_windowed(self, comm, window):
        q, k, v, out = self._dist(comm, window)
        ref = self._ref(q, k, v, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_window_one_no_comm(self, comm):
        q, k, v, out = self._dist(comm, 1)
        ref = self._ref(q, k, v, 1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_gqa(self, comm):
        q, k, v, out = self._dist(comm, 4, kv_heads=2)
        ref = self._ref(q, k, v, 4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_grads_match_single_device(self, comm):
        from jax import shard_map

        from chainermn_tpu.parallel.local_attention import (
            sliding_window_attention_local,
        )

        window = 4
        ks = jax.random.split(jax.random.PRNGKey(31), 3)
        q = jax.random.normal(ks[0], (B, T, H, D))
        k = jax.random.normal(ks[1], (B, T, H, D))
        v = jax.random.normal(ks[2], (B, T, H, D))
        ax = comm.axis_name

        def loss_dist(q, k, v):
            def local(q, k, v):
                o = sliding_window_attention_local(
                    q, k, v, ax, window=window,
                    block_q=4, block_k=4, interpret=True,
                )
                return jax.lax.psum((o.astype(jnp.float32) ** 2).sum(), ax)

            return shard_map(
                local, mesh=comm.mesh,
                in_specs=(P(None, ax),) * 3, out_specs=P(),
                check_vma=False,
            )(q, k, v)

        def loss_ref(q, k, v):
            o = self._ref(q, k, v, window)
            return (o.astype(jnp.float32) ** 2).sum()

        gd = jax.grad(loss_dist, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            ),
            gd, gr,
        )

    def test_packed_segments_cross_boundary(self, comm):
        """A document boundary NOT aligned to the shard cut: the tail's
        travelling segment ids must keep masking exact."""
        seg = np.zeros((B, T), np.int32)
        seg[:, 10:23] = 1  # cuts at 10 and 23 — neither on a 4-boundary
        seg[:, 23:] = 2
        seg = jnp.asarray(seg)
        window = 4
        q, k, v, out = self._dist(comm, window, seg=seg)
        ref = self._ref(q, k, v, window, seg=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("window", [6, 9, 13])  # m = 2, 2, 3
    def test_window_wider_than_shard(self, comm, window):
        """Multi-neighbour prefixes: the band spans several shard
        boundaries, gathered as one tail slice per predecessor."""
        q, k, v, out = self._dist(comm, window)
        ref = self._ref(q, k, v, window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_window_covering_whole_sequence_is_full_causal(self, comm):
        q, k, v, out = self._dist(comm, T + 5)
        from chainermn_tpu.ops.flash_attention import flash_attention

        ref = flash_attention(q, k, v, causal=True,
                              block_q=8, block_k=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_wide_window_grads_match_single_device(self, comm):
        from jax import shard_map

        from chainermn_tpu.parallel.local_attention import (
            sliding_window_attention_local,
        )

        window = 9  # spans 2 shard boundaries at T_local = 4
        ks = jax.random.split(jax.random.PRNGKey(35), 3)
        q = jax.random.normal(ks[0], (B, T, H, D))
        k = jax.random.normal(ks[1], (B, T, H, D))
        v = jax.random.normal(ks[2], (B, T, H, D))
        ax = comm.axis_name

        def loss_dist(q, k, v):
            def local(q, k, v):
                o = sliding_window_attention_local(
                    q, k, v, ax, window=window,
                    block_q=4, block_k=4, interpret=True,
                )
                return jax.lax.psum((o.astype(jnp.float32) ** 2).sum(), ax)

            return shard_map(
                local, mesh=comm.mesh,
                in_specs=(P(None, ax),) * 3, out_specs=P(),
                check_vma=False,
            )(q, k, v)

        def loss_ref(q, k, v):
            o = self._ref(q, k, v, window)
            return (o.astype(jnp.float32) ** 2).sum()

        gd = jax.grad(loss_dist, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            ),
            gd, gr,
        )


    def test_communication_volume_is_o_window(self, comm):
        """Structural certificate of the O(window) claim: one exchange
        per neighbour distance, NOT one per ring step. A distance-d
        exchange is one bundled shift of (k, v, ids) = 3 ppermute
        primitives, so the traced forward holds exactly 3m for
        m = ceil((W-1)/T_local); the grad program 8m (forward pass 3m +
        the backward's prefix rebuild 3m + the (dk, dv) slice returns
        2m) — all independent of mesh size, where the full causal ring
        issues a rotation per step."""
        from jax import shard_map

        from chainermn_tpu.parallel.local_attention import (
            sliding_window_attention_local,
        )

        ax = comm.axis_name

        def count_ppermutes(window, grad=False):
            def f(q, k, v):
                def local(q, k, v):
                    o = sliding_window_attention_local(
                        q, k, v, ax, window=window,
                        block_q=4, block_k=4, interpret=True,
                    )
                    return jax.lax.psum((o.astype(jnp.float32) ** 2).sum(),
                                        ax)

                return shard_map(
                    local, mesh=comm.mesh, in_specs=(P(None, ax),) * 3,
                    out_specs=P(), check_vma=False,
                )(q, k, v)

            fn = jax.grad(f, argnums=(0, 1, 2)) if grad else f
            q = jnp.zeros((1, T, 2, 8))
            return str(jax.make_jaxpr(fn)(q, q, q)).count("ppermute")

        for window, m in ((3, 1), (5, 1), (9, 2), (13, 3)):
            assert count_ppermutes(window) == 3 * m, (window, m)
            assert count_ppermutes(window, grad=True) == 8 * m, (window, m)

    def test_even_window_keeps_banded_grid(self):
        """Regression (round-4 ADVICE): an EVEN window makes the extended
        K length T_local + W - 1 odd, which no power-of-two block divides
        — without tile padding ``_pick_block`` collapses to one whole-T
        K/V block (nk = 1), reverting the banded grid to O(T + W) DMA per
        query block and risking a VMEM-busting single block at long
        context. ``_pad_ext_to_block`` must restore an exact multiple of
        the requested block at realistic sizes."""
        from chainermn_tpu.ops.flash_attention import _pick_block
        from chainermn_tpu.parallel.local_attention import (
            _pad_ext_to_block,
        )

        for T_local, window, block_k in (
            (4096, 2048, 1024),   # the common even-window case
            (8192, 4096, 1024),
            (2048, 2048, 512),    # prefix == T_local - ... still odd ext
            (4096, 1000, 1024),   # non-power-of-two window
        ):
            prefix = window - 1
            T_ext = T_local + prefix
            # Demonstrate the degenerate case first: without padding,
            # _pick_block can only fall back to ONE whole-T block here.
            assert _pick_block(block_k, T_ext) == T_ext, (T_local, window)
            k = jnp.zeros((1, T_ext, 1, 8))
            seg = jnp.zeros((1, T_ext), jnp.int32)
            k_p, v_p, seg_p = _pad_ext_to_block(k, k, seg, block_k)
            T_pad = k_p.shape[1]
            b = _pick_block(block_k, T_pad)
            assert b == block_k, (T_local, window, T_pad, b)
            assert T_pad - T_ext < block_k  # pad is bounded by one block
            assert v_p.shape[1] == T_pad and seg_p.shape[1] == T_pad
            # The pad slots carry the wrap sentinel (belt-and-braces on
            # top of the causal mask).
            if T_pad > T_ext:
                assert int(seg_p[0, -1]) == jnp.iinfo(jnp.int32).min


class TestSeqRingLocal:
    """The plan-provider ring (ISSUE 13): statically unrolled, n-1
    forward K/V hops — same dist == single invariant as the scan rings,
    plus the hop-count pins the ParallelPlan acceptance rests on."""

    def _dist(self, comm, q, k, v, grad=False):
        from jax import shard_map

        from chainermn_tpu.parallel.ring_attention import (
            seq_ring_attention_local,
        )

        ax = comm.axis_name

        def fwd(q, k, v):
            def local(q, k, v):
                return seq_ring_attention_local(
                    q, k, v, ax, causal=True, block_q=4, block_k=4,
                    interpret=True,
                )

            return shard_map(
                local, mesh=comm.mesh, in_specs=(P(None, ax),) * 3,
                out_specs=P(None, ax), check_vma=False,
            )(q, k, v)

        if not grad:
            return jax.jit(fwd)(q, k, v)
        return jax.jit(jax.grad(
            lambda a, b, c: (fwd(a, b, c).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2),
        ))(q, k, v)

    def test_matches_full_attention_values_and_grads(self, comm):
        q, k, v = _qkv(40)
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(self._dist(comm, q, k, v)), ref,
            rtol=1e-5, atol=1e-5,
        )
        g = self._dist(comm, q, k, v, grad=True)
        g_ref = jax.grad(
            lambda a, b, c: (dot_product_attention(
                a, b, c, causal=True).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            ),
            g, g_ref,
        )

    def test_gqa(self, comm):
        ks = jax.random.split(jax.random.PRNGKey(41), 3)
        q = jax.random.normal(ks[0], (B, T, H, D))
        k = jax.random.normal(ks[1], (B, T, 2, D))
        v = jax.random.normal(ks[2], (B, T, 2, D))
        ref = dot_product_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(self._dist(comm, q, k, v)), ref,
            rtol=1e-5, atol=1e-5,
        )
        g = self._dist(comm, q, k, v, grad=True)
        g_ref = jax.grad(
            lambda a, b, c: (dot_product_attention(
                a, b, c, causal=True).astype(jnp.float32) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            ),
            g, g_ref,
        )

    def test_seq_ring_wire_event(self, comm):
        """Tracing a seq-ring program emits ONE trace-time ``seq_ring``
        wire layout event per compile: n-1 hops of the stacked (K, V)
        pair, overlapped=True (the hop is issued before the step's
        kernels) — what the observability overlap rollup groups under
        'seq_ring'."""
        from chainermn_tpu.observability import trace

        rec = trace.enable(None)
        try:
            q, k, v = _qkv(45)
            self._dist(comm, q, k, v)
            wires = [e for e in rec.events
                     if e.get("kind") == "wire"
                     and e.get("schedule") == "seq_ring"]
            assert len(wires) == 1
            w = wires[0]
            n = comm.size
            assert w["hops"] == n - 1
            # per hop: the stacked K+V local shards
            per_hop = 2 * (B * (T // n) * H * D) * 4
            assert w["nbytes"] == per_hop * (n - 1)
            assert w["overlapped"] is True
            ov = trace.summarize_overlap(rec.events)
            assert "seq_ring" in ov["schedules"]
        finally:
            trace.disable()

    def test_hop_counts_pinned(self, comm):
        """The structural claim the plan's acceptance rests on: n-1
        collective-permutes per FORWARD ring pass (each hop one permute
        of the stacked K/V pair — no homing rotation), and
        (n-1) + n per backward (kv hops + the travelling dk/dv
        accumulator's n hops: it starts home, visits all n shards, and
        needs one extra hop back). Counted in the jaxpr — the unrolled
        program shows every hop, unlike the scan rings' loop body."""
        from jax import shard_map

        from chainermn_tpu.parallel.ring_attention import (
            seq_ring_attention_local,
        )

        ax = comm.axis_name
        n = comm.size

        def fwd(q, k, v):
            def local(q, k, v):
                o = seq_ring_attention_local(
                    q, k, v, ax, causal=True, block_q=4, block_k=4,
                    interpret=True,
                )
                return jax.lax.psum((o.astype(jnp.float32) ** 2).sum(), ax)

            return shard_map(
                local, mesh=comm.mesh, in_specs=(P(None, ax),) * 3,
                out_specs=P(), check_vma=False,
            )(q, k, v)

        q = jnp.zeros((1, T, 2, 8))
        assert str(jax.make_jaxpr(fwd)(q, q, q)).count("ppermute") == n - 1
        n_grad = str(jax.make_jaxpr(
            jax.grad(fwd, argnums=(0, 1, 2))
        )(q, q, q)).count("ppermute")
        assert n_grad == (n - 1) + (n - 1) + n, n_grad


class TestSeqPlanAxis:
    """ISSUE 13 tentpole: the ``seq`` axis as a ParallelPlan spec
    provider — plan-compiled ``data x seq`` / ``seq x model`` steps must
    equal the single-device reference (values AND gradients), the ring's
    compiled HLO must carry exactly ``n_seq - 1`` collective-permutes
    per layer per forward pass, the jit cache stays at 1 with
    whole-state donation intact, and composing TP adds ZERO collectives
    beyond what the providers owe."""

    LM_KW = dict(vocab_size=32, num_layers=2, num_heads=4, d_model=16,
                 d_ff=32, max_len=64, compute_dtype=jnp.float32,
                 pos_encoding="rope", return_hidden=True)

    def _lm(self, attn_fn=None, **kw):
        from chainermn_tpu.models.transformer import TransformerLM

        cfg = dict(self.LM_KW)
        cfg.update(kw)
        return TransformerLM(**cfg, attention_fn=attn_fn)

    def _params_and_tokens(self, seed=4, kv_heads=None):
        ref = self._lm(num_kv_heads=kv_heads)
        tok = jax.random.randint(jax.random.PRNGKey(3), (4, 32), 0, 32)
        variables = ref.init(
            jax.random.PRNGKey(seed), tok[:, :4], train=False
        )
        return ref, {"params": variables["params"]}, tok

    def _losses(self, model, sp=False):
        def sp_loss(p, batch):
            from chainermn_tpu.parallel.plan import ParallelPlan

            pos = ParallelPlan.seq_local_positions(batch.shape[1])
            h = model.apply({"params": p["params"]}, batch,
                            positions=pos, train=False)
            return jnp.mean(h.astype(jnp.float32) ** 2)

        def ref_loss(p, batch):
            h = model.apply({"params": p["params"]}, batch, train=False)
            return jnp.mean(h.astype(jnp.float32) ** 2)

        return sp_loss if sp else ref_loss

    @pytest.mark.parametrize("impl,seq,kv_heads", [
        ("ring", 4, None),
        ("ring", 4, 2),      # GQA through the plan ring
        ("ulysses", 2, None),
        ("ulysses", 2, 2),   # GQA through the plan Ulysses (kvh % n == 0)
    ])
    def test_data_seq_plan_values_and_grads(self, impl, seq, kv_heads):
        from chainermn_tpu.parallel.plan import ParallelPlan

        devices = jax.devices("cpu")[:2 * seq]
        plan = ParallelPlan({"data": 2, "seq": seq}, devices=devices)
        attn_fn, rec = plan.seq_attention(
            heads=4, kv_heads=kv_heads, t_local=32 // seq, impl=impl
        )
        assert rec["winner"] == impl
        ref_model, params, tok = self._params_and_tokens(kv_heads=kv_heads)
        sp_model = self._lm(attn_fn, num_kv_heads=kv_heads)

        lr = 0.1
        import optax

        state = plan.create_train_state(params, optax.sgd(lr))
        step = plan.compile_train_step(
            self._losses(sp_model, sp=True), optax.sgd(lr), params
        )
        state, m = step(state, tok)
        l_ref, g_ref = jax.value_and_grad(
            lambda p: self._losses(ref_model)(p, tok)
        )(params)
        np.testing.assert_allclose(float(m["loss"]), float(l_ref),
                                   rtol=1e-4)
        # gradients certified through the sgd delta, every leaf
        after = jax.device_get(state.params)
        jax.tree.map(
            lambda p0, p1, g: np.testing.assert_allclose(
                (np.asarray(p0) - np.asarray(p1)) / lr, np.asarray(g),
                rtol=2e-3, atol=2e-5,
            ),
            params, after, g_ref,
        )
        assert step.cache_size() in (None, 1)

    def test_ring_hlo_ppermute_count_and_donation(self):
        """The compiled ``data x seq`` train step carries EXACTLY
        ``(n-1) + (n-1) + n`` collective-permutes per layer (forward
        ring + backward kv ring + accumulator homing), the forward-only
        program exactly ``n - 1`` per layer, donation aliases every
        state buffer, and the jit cache stays at 1 across steps."""
        import optax

        from chainermn_tpu.parallel.plan import ParallelPlan

        seq, layers = 4, 2
        plan = ParallelPlan({"data": 2, "seq": seq},
                            devices=jax.devices("cpu")[:8])
        attn_fn, _ = plan.seq_attention(heads=4, t_local=32 // seq,
                                        impl="ring")
        sp_model = self._lm(attn_fn)
        _, params, tok = self._params_and_tokens()
        loss = self._losses(sp_model, sp=True)
        inner = optax.adamw(1e-2)
        state = plan.create_train_state(params, inner)
        step = plan.compile_train_step(loss, inner, params)
        txt = step.lower(state, tok).compile().as_text()
        assert txt.count("collective-permute(") == (3 * seq - 2) * layers
        assert "input_output_alias" in txt
        n_alias = txt.count("may-alias") + txt.count("must-alias")
        assert n_alias >= len(jax.tree.leaves(state))

        # forward-only: n-1 per layer per ring pass, nothing else
        from jax import shard_map

        fwd = jax.jit(shard_map(
            lambda p, t: loss(p, t), mesh=plan.mesh,
            in_specs=(plan.param_specs(params), plan.batch_spec()),
            out_specs=P(), check_vma=False,
        ))
        fwd_txt = fwd.lower(params, tok).compile().as_text()
        assert fwd_txt.count("collective-permute(") == (seq - 1) * layers

        for _ in range(2):
            state, m = step(state, tok)
        assert step.cache_size() in (None, 1)
        assert np.isfinite(float(m["loss"]))

    def test_seq_model_plan_zero_extra_collectives(self):
        """``seq x model``: the plan-compiled step carries exactly the
        collectives the two providers owe — the ring's ppermutes plus
        TP's all-reduces plus the one seq gradient mean — pinned
        against the hand-wired shard_map of the same computation (the
        test_plan.py convention), with zero all-to-alls and zero
        ppermutes beyond the ring's."""
        import optax
        from jax import shard_map

        from chainermn_tpu.parallel.plan import ParallelPlan
        from chainermn_tpu.parallel.ring_attention import (
            seq_ring_attention_local,
        )
        from chainermn_tpu.parallel.tensor import stack_tp_params, tp_mlp

        seq = n_tp = 2
        d, Hh, Dh = 8, 2, 4
        plan = ParallelPlan({"seq": seq, "model": n_tp},
                            devices=jax.devices("cpu")[:4])
        attn_fn, _ = plan.seq_attention(heads=Hh, t_local=8, impl="ring")
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        wq = jax.random.normal(ks[0], (d, d)) * 0.3
        w1 = jax.random.normal(ks[1], (d, d)) * 0.3
        w2 = jax.random.normal(ks[2], (d, d)) * 0.3
        params = {
            "wq": wq,
            "w1": stack_tp_params(w1, n_tp, 1),
            "w2": stack_tp_params(w2, n_tp, 0),
            "b2": jnp.zeros((d,)),
        }
        specs = {"wq": P(), "w1": P("model"), "w2": P("model"), "b2": P()}
        x = jax.random.normal(ks[3], (2, 16, d))
        y = jnp.zeros((2, 16, d))
        lr = 0.1

        def loss_fn(p, batch):
            xb, yb = batch
            Bb, Tb, _ = xb.shape
            q = (xb @ p["wq"]).reshape(Bb, Tb, Hh, Dh)
            a = attn_fn(q, q, q, causal=True, scale=Dh ** -0.5)
            h = a.reshape(Bb * Tb, d)
            out = tp_mlp(h, p["w1"], None, p["w2"], p["b2"],
                         axis_name="model")
            return jnp.mean((out.reshape(Bb, Tb, d) - yb) ** 2)

        inner = optax.sgd(lr)
        state = plan.create_train_state(params, inner, param_specs=specs)
        step = plan.compile_train_step(loss_fn, inner, params,
                                       param_specs=specs)
        plan_txt = step.lower(state, (x, y)).compile().as_text()
        plan_counts = {op: plan_txt.count(op) for op in
                       ("all-reduce(", "collective-permute(",
                        "all-to-all(", "reduce-scatter(", "all-gather(")}

        def hand_local(params, batch):
            p = {"wq": params["wq"], "w1": params["w1"][0],
                 "w2": params["w2"][0], "b2": params["b2"]}

            def loss(p):
                return loss_fn(p, batch)

            l, g = jax.value_and_grad(loss)(p)
            g = jax.lax.pmean(g, ("seq",))
            new = {
                "wq": p["wq"] - lr * g["wq"],
                "w1": (p["w1"] - lr * g["w1"])[None],
                "w2": (p["w2"] - lr * g["w2"])[None],
                "b2": p["b2"] - lr * g["b2"],
            }
            return new, jax.lax.pmean(l, ("seq",))

        pspec = {"wq": P(), "w1": P("model"), "w2": P("model"),
                 "b2": P()}
        hand = jax.jit(shard_map(
            hand_local, mesh=plan.mesh,
            in_specs=(pspec, P(None, "seq")),
            out_specs=(pspec, P()),
            check_vma=False,
        ))
        hand_txt = hand.lower(params, (x, y)).compile().as_text()
        hand_counts = {op: hand_txt.count(op) for op in plan_counts}
        assert plan_counts == hand_counts, (plan_counts, hand_counts)
        # the vocabulary: ring hops present, TP psums present, nothing
        # resharded head<->sequence (no all-to-all), no zero machinery
        assert plan_counts["collective-permute("] == 3 * seq - 2
        assert plan_counts["all-to-all("] == 0
        assert plan_counts["reduce-scatter("] == 0
        assert plan_counts["all-gather("] == 0
        assert plan_counts["all-reduce("] >= 2  # TP pair + grad mean

    def test_seq_attn_impl_forced_fallback_and_rejection(self,
                                                         monkeypatch):
        """Satellite: 'auto' resolving to ulysses with
        heads % seq_size != 0 force-falls back to ring with
        ``forced:heads-indivisible`` provenance; an EXPLICIT ulysses
        request is rejected at entry naming both numbers."""
        from chainermn_tpu.parallel.plan import ParallelPlan

        monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_FORCE",
                           "seq_attn_impl=ulysses")
        plan = ParallelPlan({"seq": 8}, devices=jax.devices("cpu")[:8])
        _, rec = plan.seq_attention(heads=4, t_local=4, impl="auto")
        assert rec["winner"] == "ring"
        assert rec["source"] == "forced:heads-indivisible"
        assert plan.decisions[-1] == rec
        assert plan.describe()["seq_attn_impl"] == "ring"
        # kv heads (GQA) gate the fallback too
        plan2 = ParallelPlan({"seq": 2}, devices=jax.devices("cpu")[:2])
        _, rec2 = plan2.seq_attention(heads=4, kv_heads=1, t_local=16,
                                      impl="auto")
        assert rec2["source"] == "forced:heads-indivisible"

        monkeypatch.delenv("CHAINERMN_TPU_AUTOTUNE_FORCE")
        plan3 = ParallelPlan({"seq": 8}, devices=jax.devices("cpu")[:8])
        with pytest.raises(ValueError) as e:
            plan3.seq_attention(heads=6, t_local=4, impl="ulysses")
        assert "6" in str(e.value) and "8" in str(e.value)

    def test_make_ulysses_rejects_at_entry(self, comm):
        """Satellite: the jitted Ulysses entry point rejects indivisible
        heads BEFORE the shard_map trace, naming both numbers."""
        fn = make_ulysses_attention(comm.mesh, comm.axis_name)
        q = jnp.zeros((B, T, 6, D))
        with pytest.raises(ValueError) as e:
            fn(q, q, q)
        assert "6" in str(e.value) and "8" in str(e.value)
        assert "not divisible" in str(e.value)

    def test_batch_spec_and_describe(self):
        from chainermn_tpu.parallel.plan import ParallelPlan

        plan = ParallelPlan({"data": 2, "seq": 4},
                            devices=jax.devices("cpu")[:8])
        assert plan.batch_spec() == P(("data",), "seq")
        desc = plan.describe()
        assert desc["mesh"] == {"data": 2, "seq": 4}
        assert desc["collectives"]["seq"] == (
            "collective-permute", "all-reduce",
        )
        plan2 = ParallelPlan({"seq": 8}, devices=jax.devices("cpu")[:8])
        assert plan2.batch_spec() == P(None, "seq")


def test_dryrun_phase_table_wires_seq_parallel_phase():
    """Satellite: dryrun phase N (8-device data x seq plan vs
    single-device ref + seq-parallel prefill streams == generate) is in
    __graft_entry__'s phase table."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    src = open(os.path.join(root, "__graft_entry__.py")).read()
    assert "_phase_seq_parallel" in src
    assert ('"N:seq-axis plan + seq-parallel prefill", '
            "_phase_seq_parallel" in src)


class TestUlyssesWindow:
    def test_ulysses_window_matches_single_device(self, comm):
        from chainermn_tpu.parallel.ulysses import make_ulysses_attention

        window = 5
        ks = jax.random.split(jax.random.PRNGKey(70), 3)
        q = jax.random.normal(ks[0], (B, T, H, D))
        k = jax.random.normal(ks[1], (B, T, H, D))
        v = jax.random.normal(ks[2], (B, T, H, D))
        fn = make_ulysses_attention(
            comm.mesh, comm.axis_name, causal=True, window=window
        )
        sharding = NamedSharding(comm.mesh, P(None, comm.axis_name))
        qs, ks_, vs = (jax.device_put(a, sharding) for a in (q, k, v))
        out = fn(qs, ks_, vs)

        from chainermn_tpu.ops.flash_attention import flash_attention

        ref = flash_attention(q, k, v, causal=True, window=window,
                              block_q=8, block_k=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_window_rejected_with_custom_attn_fn(self, comm):
        from jax import shard_map

        from chainermn_tpu.parallel.ulysses import ulysses_attention_local

        q = jnp.zeros((B, T, H, D))
        with pytest.raises(ValueError, match="flash kernel"):
            jax.jit(shard_map(
                lambda a: ulysses_attention_local(
                    a, a, a, comm.axis_name, causal=True, window=4,
                    attn_fn=blockwise_attention,
                ),
                mesh=comm.mesh,
                in_specs=P(None, comm.axis_name),
                out_specs=P(None, comm.axis_name), check_vma=False,
            ))(q)

    def test_ulysses_window_grads_match_single_device(self, comm):
        from jax import shard_map

        from chainermn_tpu.ops.flash_attention import flash_attention
        from chainermn_tpu.parallel.ulysses import ulysses_attention_local

        window = 5
        ks = jax.random.split(jax.random.PRNGKey(71), 3)
        q = jax.random.normal(ks[0], (B, T, H, D))
        k = jax.random.normal(ks[1], (B, T, H, D))
        v = jax.random.normal(ks[2], (B, T, H, D))
        ax = comm.axis_name

        def loss_dist(q, k, v):
            def local(q, k, v):
                o = ulysses_attention_local(
                    q, k, v, ax, causal=True, window=window, interpret=True
                )
                return jax.lax.psum((o.astype(jnp.float32) ** 2).sum(), ax)

            return shard_map(
                local, mesh=comm.mesh,
                in_specs=(P(None, ax),) * 3, out_specs=P(),
                check_vma=False,
            )(q, k, v)

        def loss_ref(q, k, v):
            o = flash_attention(q, k, v, causal=True, window=window,
                                block_q=8, block_k=8, interpret=True)
            return (o.astype(jnp.float32) ** 2).sum()

        # jit the distributed grad: the transposed all_to_all sets an XLA
        # sharding that eager grad-of-shard_map refuses to reconcile.
        gd = jax.jit(jax.grad(loss_dist, argnums=(0, 1, 2)))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
            ),
            gd, gr,
        )
