"""Fused paged-decode Pallas kernel (ISSUE 19).

Two layers of pins, per the repo's conventions:

- **Kernel contract** — :func:`~chainermn_tpu.ops.paged_decode.
  paged_flash_decode` (interpret mode on the CPU mesh) against the XLA
  paged path's own math: allclose at fp32-accumulation tolerance across
  T=1 / verify-span / GQA / MQA / window / stacked-TP variants, and the
  scratch/horizon edge cases BOTH impls must agree on — a released
  slot's scratch-block garbage and a beyond-horizon span must never
  leak into a live row (block 0 is poisoned with 1e9 so a leak is loud,
  not a rounding error).
- **Engine equivalence** — ``decode_attend_impl='fused'`` token streams
  IDENTICAL to sequential ``generate`` across dense == paged == TP ==
  single-device x speculative x chunked x sampled, with the jit caches
  still pinned at 1 and the TP decode HLO still exactly 2
  all-reduces/layer (zero collectives inside the kernel).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from chainermn_tpu.models.transformer import TransformerLM, generate
from chainermn_tpu.ops.paged_decode import (
    dense_flash_decode,
    paged_flash_decode,
)
from chainermn_tpu.serving import Request, Scheduler, ServingEngine

VOCAB = 32


def _ref_attend(q, keys, vals, positions, live_key_mask, window=None):
    """The XLA slot-decode attend math (transformer._slot_decode_attend)
    over an explicit dense view + key liveness mask — the equivalence
    yardstick for the kernel."""
    B, T, Hq, D = q.shape
    Hkv = keys.shape[2]
    L = keys.shape[1]
    pos_l = np.arange(L)
    qpos = positions[:, None] + np.arange(T)
    mask = pos_l[None, None, :] <= qpos[:, :, None]
    if window is not None:
        mask &= pos_l[None, None, :] > qpos[:, :, None] - window
    mask &= live_key_mask[:, None, :]
    g = Hq // Hkv
    qq = q.reshape(B, T, Hkv, g, D)
    s = np.einsum("btngd,blnd->btngl", qq.astype(np.float64),
                  keys.astype(np.float64)) * (D ** -0.5)
    s = np.where(mask[:, :, None, None, :], s, -np.inf)
    with np.errstate(invalid="ignore"):
        w = np.exp(s - s.max(-1, keepdims=True))
        w = np.nan_to_num(w / w.sum(-1, keepdims=True))
    o = np.einsum("btngl,blnd->btngd", w, vals.astype(np.float64))
    return o.reshape(B, T, Hq, D).astype(np.float32)


def _pool_case(rs, B=3, T=1, Hq=4, Hkv=4, D=8, nb=14, bs=8, M=4,
               poison=1e9):
    """A pool with POISONED scratch block 0 and per-row tables that mix
    live blocks, scratch entries past the live span, and rows at
    different depths."""
    kp = rs.randn(nb, bs, Hkv, D).astype(np.float32)
    vp = rs.randn(nb, bs, Hkv, D).astype(np.float32)
    kp[0] = poison  # released-slot / beyond-horizon garbage by contract
    vp[0] = poison
    tables = np.zeros((B, M), np.int32)
    free = list(range(1, nb))
    positions = np.zeros((B,), np.int32)
    for b in range(B):
        depth = int(rs.randint(0, M * bs - T))
        positions[b] = depth
        n_live = depth // bs + 1
        for j in range(n_live):
            tables[b, j] = free.pop(0)
    q = rs.randn(B, T, Hq, D).astype(np.float32)
    return q, kp, vp, tables, positions

def _dense_view(kp, vp, tables, bs):
    B, M = tables.shape
    keys = kp[tables].reshape(B, M * bs, kp.shape[2], kp.shape[3])
    vals = vp[tables].reshape(B, M * bs, vp.shape[2], vp.shape[3])
    live = np.repeat(tables != 0, bs, axis=1)  # scratch entries dead
    return keys, vals, live


class TestKernelContract:
    @pytest.mark.parametrize("T,Hq,Hkv,window", [
        (1, 4, 4, None),      # plain decode tick
        (3, 4, 4, None),      # verify span (K+1 rows)
        (1, 4, 2, None),      # GQA
        (4, 4, 1, None),      # MQA, chunked-width span
        (2, 4, 2, 6),         # GQA + sliding window
    ])
    def test_matches_xla_math_with_scratch_masking(self, T, Hq, Hkv,
                                                   window):
        rs = np.random.RandomState(hash((T, Hq, Hkv)) % 2**31)
        q, kp, vp, tables, positions = _pool_case(
            rs, T=T, Hq=Hq, Hkv=Hkv)
        got = np.asarray(paged_flash_decode(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(positions), window=window,
        ))
        keys, vals, live = _dense_view(kp, vp, tables, bs=8)
        want = _ref_attend(q, keys, vals, positions, live, window=window)
        # fp32 accumulation both sides; the poisoned scratch block makes
        # any masking leak a ~1e9 error, not a tolerance question.
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_beyond_horizon_span_rows_stay_finite_and_live_rows_exact(
        self,
    ):
        # A verify span straddling the horizon: positions + T - 1 runs
        # past M*bs. Beyond-horizon WRITES went to scratch (paged_update
        # contract); the kernel must keep every in-horizon row exact and
        # every over-the-edge row finite (the engine caps ACCEPTANCE, so
        # those rows are never consumed — but NaN would poison the jit).
        rs = np.random.RandomState(3)
        T, bs, M = 4, 8, 4
        q, kp, vp, tables, positions = _pool_case(rs, T=T)
        positions[0] = M * bs - 2  # rows 2..3 of slot 0 overhang
        tables[0] = [1, 2, 3, 4]
        got = np.asarray(paged_flash_decode(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(positions),
        ))
        assert np.isfinite(got).all()
        keys, vals, live = _dense_view(kp, vp, tables, bs=bs)
        want = _ref_attend(q, keys, vals, positions, live)
        np.testing.assert_allclose(got[:, :2], want[:, :2],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got[1:], want[1:],
                                   rtol=2e-5, atol=2e-5)

    def test_released_slot_all_scratch_row_emits_zero(self):
        # A released slot's table row is all scratch: every block is
        # masked, l stays 0, and the row must emit EXACT zeros (the
        # fully-masked-row finalize guard) — not 1e9 garbage.
        rs = np.random.RandomState(4)
        q, kp, vp, tables, positions = _pool_case(rs, B=2)
        tables[1] = 0
        positions[1] = 0
        got = np.asarray(paged_flash_decode(
            jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(positions),
        ))
        assert np.all(got[1] == 0.0)
        keys, vals, live = _dense_view(kp, vp, tables, bs=8)
        want = _ref_attend(q, keys, vals, positions, live)
        np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("slots", [None, "explicit"])
    def test_dense_wrapper_matches_dense_math(self, slots):
        rs = np.random.RandomState(5)
        B, T, Hq, Hkv, D, L = 3, 2, 4, 2, 8, 32
        n_cache = 5 if slots else B
        ck = rs.randn(n_cache, L, Hkv, D).astype(np.float32)
        cv = rs.randn(n_cache, L, Hkv, D).astype(np.float32)
        q = rs.randn(B, T, Hq, D).astype(np.float32)
        positions = np.array([0, 7, 29], np.int32)
        slot_ids = (np.array([4, 0, 2], np.int32) if slots
                    else np.arange(B, dtype=np.int32))
        got = np.asarray(dense_flash_decode(
            jnp.asarray(q), jnp.asarray(ck), jnp.asarray(cv),
            jnp.asarray(positions),
            slots=None if slots is None else jnp.asarray(slot_ids),
            window=9,
        ))
        keys, vals = ck[slot_ids], cv[slot_ids]
        live = np.ones((B, L), bool)
        want = _ref_attend(q, keys, vals, positions, live, window=9)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def test_stacked_tp_pools_share_the_program(self):
        # Leading stack axis (the copy_block convention): shared tables/
        # positions, per-shard pools and q — output == per-shard calls,
        # zero collectives by construction (no mesh in sight).
        rs = np.random.RandomState(6)
        q, kp, vp, tables, positions = _pool_case(rs, Hq=4, Hkv=2)
        qs = np.stack([q, 2 * q])
        kps = np.stack([kp, 0.5 * kp])
        vps = np.stack([vp, -vp])
        got = np.asarray(paged_flash_decode(
            jnp.asarray(qs), jnp.asarray(kps), jnp.asarray(vps),
            jnp.asarray(tables), jnp.asarray(positions),
        ))
        assert got.shape == qs.shape
        for s in range(2):
            want = np.asarray(paged_flash_decode(
                jnp.asarray(qs[s]), jnp.asarray(kps[s]),
                jnp.asarray(vps[s]), jnp.asarray(tables),
                jnp.asarray(positions),
            ))
            np.testing.assert_allclose(got[s], want, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Engine equivalence: fused streams == sequential generate
# ---------------------------------------------------------------------------

def tiny_lm(**kw):
    cfg = dict(vocab_size=VOCAB, num_layers=2, num_heads=4, d_model=16,
               d_ff=32, max_len=32, compute_dtype=jnp.float32)
    cfg.update(kw)
    return TransformerLM(**cfg)


@pytest.fixture(scope="module")
def lm():
    model = tiny_lm()
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32), train=False
    )
    return model, params


def _requests(n, seed=0, max_prompt=7, max_new=6):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        p_len = int(rs.randint(1, max_prompt))
        out.append((rs.randint(1, VOCAB, size=p_len).tolist(),
                    int(rs.randint(1, max_new))))
    return out


def _generate_ref(model, params, prompt, n_new):
    return np.asarray(generate(
        model, params, jnp.asarray([prompt], jnp.int32),
        len(prompt) + n_new,
    ))[0].tolist()


def _run_stream(engine, reqs):
    sched = Scheduler(engine, policy="fcfs")
    ids = [sched.submit(Request(prompt=p, max_new_tokens=g))
           for p, g in reqs]
    results = sched.run()
    return [results[rid]["tokens"] for rid in ids]


class TestEngineEquivalence:
    @pytest.mark.parametrize("impl,extra", [
        ("paged", {}),
        ("dense", {}),
        ("paged", {"spec_tokens": 2}),
        ("paged", {"prefill_chunk": 4}),
    ])
    def test_fused_streams_match_generate(self, lm, impl, extra):
        model, params = lm
        engine = ServingEngine(
            model, params, num_slots=2, max_len=32, decode_impl=impl,
            decode_attend_impl="fused", kv_block_size=8,
            prefill_buckets=(4, 8, 16), **extra,
        )
        reqs = _requests(6, seed=0)
        streams = _run_stream(engine, reqs)
        for (prompt, n_new), got in zip(reqs, streams):
            assert got == _generate_ref(model, params, prompt, n_new)
        # The impl is a static model field: every program's jit cache
        # stays pinned exactly where the xla engine pins it (the spec
        # arm drives the verify program instead of the plain decode).
        if "spec_tokens" in extra:
            assert engine.verify_compile_count() == 1
        else:
            assert engine.decode_compile_count() == 1

    def test_gqa_windowed_fused_stream_matches(self):
        model = tiny_lm(num_kv_heads=2, window=6)
        params = tiny_lm(num_kv_heads=2).init(
            jax.random.PRNGKey(4), jnp.zeros((1, 4), jnp.int32),
            train=False,
        )
        engine = ServingEngine(
            model, params, num_slots=2, max_len=32, decode_impl="paged",
            decode_attend_impl="fused", kv_block_size=8,
            prefill_buckets=(4, 8, 16),
        )
        reqs = _requests(3, seed=5, max_prompt=10, max_new=8)
        streams = _run_stream(engine, reqs)
        for (prompt, n_new), got in zip(reqs, streams):
            assert got == _generate_ref(model, params, prompt, n_new)

    def test_sampled_fused_stream_matches_xla_stream(self, lm):
        # Counter-based keys (ISSUE 18) make the draw depend only on
        # (seed, position, logits); fp32 logits agree to tolerance, so
        # the sampled streams must be IDENTICAL across the impls.
        model, params = lm

        def stream(attend):
            engine = ServingEngine(
                model, params, num_slots=2, max_len=32,
                decode_impl="paged", decode_attend_impl=attend,
                kv_block_size=8, prefill_buckets=(4, 8),
                temperature=0.8, top_k=8, rng=jax.random.PRNGKey(42),
            )
            return _run_stream(engine, _requests(3, seed=9))

        assert stream("fused") == stream("xla")

    def test_tp_fused_stream_and_collective_counts(self, lm):
        model, params = lm
        mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("model",))
        reqs = _requests(5, seed=11)
        engine = ServingEngine(
            model, params, num_slots=3, max_len=32, decode_impl="paged",
            decode_attend_impl="fused", kv_block_size=8,
            prefill_buckets=(4, 8), mesh=mesh,
        )
        streams = _run_stream(engine, reqs)
        for (prompt, n_new), got in zip(reqs, streams):
            assert got == _generate_ref(model, params, prompt, n_new)
        # Structural pin: the kernel adds NOTHING to the wire — still
        # exactly 2 all-reduces/layer, zero collectives anywhere else.
        args = (
            engine._cache, engine._vars,
            jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32),
            jnp.asarray(engine._dummy_tables()),
            jnp.asarray(engine._seeds),
        )
        txt = engine._decode_step_jit.lower(*args).compile().as_text()
        assert txt.count("all-reduce(") == 2 * model.num_layers
        for op in ("all-gather(", "collective-permute(", "all-to-all(",
                   "reduce-scatter("):
            assert txt.count(op) == 0, f"unexpected {op} in decode step"

    def test_decision_provenance_and_validation(self, lm):
        model, params = lm
        engine = ServingEngine(
            model, params, num_slots=2, max_len=32, decode_impl="paged",
            decode_attend_impl="fused", kv_block_size=8,
            prefill_buckets=(4,),
        )
        recs = [d for d in engine.decisions
                if d["name"] == "decode_attend_impl"]
        assert recs == [{"name": "decode_attend_impl",
                         "key": engine.decision_key, "winner": "fused",
                         "source": "explicit"}]
        with pytest.raises(ValueError, match="decode_attend_impl"):
            ServingEngine(
                model, params, num_slots=2, max_len=32,
                decode_impl="paged", decode_attend_impl="mosaic",
                kv_block_size=8, prefill_buckets=(4,),
            )

    def test_table_default_resolves_xla(self, lm, monkeypatch):
        # DEFAULT_TABLE says 'xla' until a cell adjudicates the kernel.
        model, params = lm
        engine = ServingEngine(
            model, params, num_slots=2, max_len=32, decode_impl="paged",
            kv_block_size=8, prefill_buckets=(4,),
        )
        assert engine.decode_attend_impl == "xla"
