"""Device-aware dispatch (chainermn_tpu.tuning).

Covers the subsystem's contracts hermetically (no hardware):

- every decision resolves from its :data:`DEFAULT_TABLE` entry, through
  its own call site where it has one, for a TPU key and a CPU key, and
  neither a file nor a mode variable in the environment can change that;
  ``CHAINERMN_TPU_AUTOTUNE_FORCE`` is the one override;
- dist==single equivalence (values AND grads) for BOTH sides of every
  tuned choice (MoE dispatch impls, attention variants, wire dtypes);
- a structural assertion that the auto-selected MoE path on the CPU
  mesh is the sort path (scatter in the lowering, decision recorded).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from chainermn_tpu import tuning
from chainermn_tpu.parallel.moe import (
    dispatch_einsum,
    dispatch_sort,
    make_expert_params,
    moe_layer_local,
    top1_route,
)

D = 8


@pytest.fixture(autouse=True)
def _clean_decisions(monkeypatch):
    """Every test starts with no override and a clean decision log."""
    monkeypatch.delenv("CHAINERMN_TPU_AUTOTUNE_FORCE", raising=False)
    tuning.reset_decisions()
    yield
    tuning.reset_decisions()


def expert_fn(params, x):
    w1, w2 = params
    return jnp.tanh(x @ w1) @ w2


def _expert_init(rng):
    k1, k2 = jax.random.split(rng)
    return (
        jax.random.normal(k1, (D, 16)) / 4.0,
        jax.random.normal(k2, (16, D)) / 4.0,
    )


# ---------------------------------------------------------------------------
# Registry mechanics
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_shape_bucket_keying(self):
        # nearby shapes share a bucket; far shapes do not
        assert tuning.shape_bucket((2000, 8, 60)) == "2048x8x64"
        assert tuning.shape_bucket((2048, 8, 64)) == "2048x8x64"
        assert tuning.shape_bucket((16384, 16, 512)) != \
            tuning.shape_bucket((2048, 8, 64))
        k1 = tuning.decision_key("cpu", shape=(1500, 7, 33), dtype="bf16")
        k2 = tuning.decision_key("cpu", shape=(2048, 8, 64), dtype="bf16")
        assert k1 == k2
        with pytest.raises(ValueError):
            tuning.shape_bucket((0,))

    def test_forced_override_wins_and_validates(self, monkeypatch):
        monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_FORCE",
                           "moe_dispatch=einsum")
        key = tuning.decision_key("cpu", shape=(64, 8, 8), dtype="float32")
        assert tuning.choice("moe_dispatch", ("sort", "einsum"),
                             key) == "einsum"
        monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_FORCE",
                           "moe_dispatch=bogus")
        with pytest.raises(ValueError, match="bogus"):
            tuning.choice("moe_dispatch", ("sort", "einsum"), key)

#: two candidates of each decision: whichever the table does not name
#: for a key is the contrary winner the decoy file offers there. A
#: decision added to the table needs its line here.
_SIDES = {
    "allreduce_wire": ("bf16", "int8"),
    "allreduce_bucket_mb": ("64", "256"),
    "attention": ("flash", "xla"),
    "attention_windowed": ("windowed", "xla"),
    "moe_dispatch": ("sort", "einsum"),
    "expert_parallel": ("off", "on"),
    "seq_attn_impl": ("ring", "ulysses"),
    "decode_impl": ("paged", "dense"),
    "kv_block_size": ("64", "16"),
    "decode_attend_impl": ("xla", "fused"),
    "spec_tokens": ("0", "4"),
    "prefix_cache": ("on", "off"),
    "min_shared_blocks": ("1", "2"),
    "prefill_chunk": ("0", "32"),
    "cluster_disagg": ("colocated", "disaggregated"),
    "prefill_seq_parallel": ("off", "on"),
    "adapter_impl": ("gather", "merged"),
}


def _call_sites():
    """``decision -> call(kind)``: the decision's own resolver in the
    program, where it has one that is not a constructor."""
    from chainermn_tpu.ops.attention import resolve_attention_impl
    from chainermn_tpu.parallel import collectives, moe
    from chainermn_tpu.parallel.plan import ParallelPlan
    from chainermn_tpu.serving import engine

    def plan_seq(kind):
        plan = ParallelPlan({"seq": 4}, devices=jax.devices("cpu")[:4])
        plan.seq_attention(heads=4, t_local=16, impl="auto")

    def moe_dispatch(kind):
        moe.resolve_dispatch_impl(2048, 8, 64, jnp.bfloat16)
        plan = ParallelPlan({"expert": 4}, devices=jax.devices("cpu")[:4])
        plan.moe_layer(tokens_local=16, d_model=D, impl="auto")

    served = {
        name: (lambda kind, f=getattr(engine, "resolve_" + name):
               f(64, 4, 64))
        for name in ("decode_impl", "kv_block_size", "decode_attend_impl",
                     "spec_tokens", "prefix_cache", "min_shared_blocks",
                     "prefill_chunk", "prefill_seq_parallel",
                     "adapter_impl")
    }
    return {
        "attention": lambda kind: resolve_attention_impl(
            (1, 256, 2, 64), jnp.bfloat16),
        "attention_windowed": lambda kind: resolve_attention_impl(
            (1, 256, 2, 64), jnp.bfloat16, windowed=True),
        "moe_dispatch": moe_dispatch,
        "expert_parallel": lambda kind: moe.resolve_expert_parallel(
            128, 8, 64, jnp.float32),
        "allreduce_wire": lambda kind: collectives.resolve_allreduce_wire(
            kind, 4),
        "allreduce_bucket_mb": lambda kind: collectives.tuned_bucket_bytes(
            kind, 4),
        "seq_attn_impl": plan_seq,
        **served,
    }


@pytest.mark.parametrize("name", sorted(tuning.DEFAULT_TABLE))
def test_decision_resolves_from_its_table_entry(name, tmp_path, monkeypatch):
    """The winner is the table's and the record says ``table``, on a TPU
    key and a CPU key, whatever a file of contrary winners and a mode
    variable in the environment say: nothing but the table and
    ``CHAINERMN_TPU_AUTOTUNE_FORCE`` decides a path."""
    from chainermn_tpu.tuning import registry

    table, sides = tuning.DEFAULT_TABLE[name], _SIDES[name]
    site = _call_sites().get(name)
    decoy = tmp_path / "decoy_cache.json"
    monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_CACHE", str(decoy))
    monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE", "measure")
    for kind in ("TPU v5 lite", "cpu"):
        # sites that take no device kind key on the live backend's
        monkeypatch.setattr(registry, "current_device_kind", lambda: kind)
        expect = table.get(tuning.device_class(kind)) or table["*"]

        def resolve():
            tuning.reset_decisions()
            if site is not None:
                site(kind)
            else:
                tuning.choice(name, sides, tuning.decision_key(
                    kind, shape=(4,), dtype="step"))
            recs = [r for r in tuning.decisions_taken()
                    if r["name"] == name]
            assert recs and all(r["key"].startswith(kind) for r in recs)
            return recs

        recs = resolve()
        # a contrary winner under exactly the keys the site asked for
        contrary = next(w for w in sides if w != expect)
        decoy.write_text(json.dumps({"version": 1, "decisions": {
            f"{name}|{r['key']}": {"winner": contrary, "source": "decoy"}
            for r in recs}}))
        for rec in recs + resolve():
            assert rec["winner"] == expect, (kind, rec)
            assert rec["source"] == "table", (kind, rec)


@functools.lru_cache(maxsize=None)
def _decisions_read_by_the_package():
    """Every decision name that is the first argument of a ``choice``
    call under ``chainermn_tpu/``: a string literal, or a name the same
    file assigns string literals to (``ops/attention.py`` picks between
    two)."""
    import ast
    import os

    import chainermn_tpu

    read = set()
    for folder, _, files in os.walk(os.path.dirname(chainermn_tpu.__file__)):
        for f in files:
            if not f.endswith(".py"):
                continue
            with open(os.path.join(folder, f)) as fh:
                tree = ast.parse(fh.read())
            assigned: dict = {}
            for node in ast.walk(tree):
                if isinstance(node, ast.Assign):
                    literals = {c.value for c in ast.walk(node.value)
                                if isinstance(c, ast.Constant)
                                and isinstance(c.value, str)}
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            assigned.setdefault(t.id, set()).update(literals)
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call) and node.args):
                    continue
                fn = node.func
                if (fn.attr if isinstance(fn, ast.Attribute)
                        else getattr(fn, "id", None)) != "choice":
                    continue
                first = node.args[0]
                if isinstance(first, ast.Constant):
                    read.add(first.value)
                elif isinstance(first, ast.Name):
                    read |= assigned.get(first.id, set())
    return read


@pytest.mark.parametrize("decision", sorted(tuning.DEFAULT_TABLE))
def test_every_table_key_has_a_reader(decision):
    """A table key is a path choice some code takes: a key no ``choice``
    call in the package names is a constant with a table entry, and goes
    (``double_buffering`` had none from PR 28 to PR 46)."""
    assert decision in _decisions_read_by_the_package()


def test_choice_opens_no_file_and_reads_no_clock(monkeypatch):
    """Resolving a decision is pure Python over the arguments, the
    override and the table: with ``open`` and the clocks taken away the
    call sites still resolve."""
    import builtins
    import time

    def refuse(*a, **k):
        raise AssertionError("a decision touched a file or a clock")

    sites = _call_sites()
    monkeypatch.setattr(builtins, "open", refuse)
    monkeypatch.setattr(time, "perf_counter", refuse)
    monkeypatch.setattr(time, "time", refuse)
    for name in ("attention", "allreduce_bucket_mb", "allreduce_wire",
                 "decode_impl"):
        sites[name]("cpu")
    assert {r["source"] for r in tuning.decisions_taken()} == {"table"}


def test_choice_takes_a_name_its_candidates_and_a_key():
    """No call site can hand ``choice`` a measurement, a table of its
    own or a cache path, and the package exports no way to store one."""
    import inspect

    assert list(inspect.signature(tuning.choice).parameters) == [
        "name", "candidates", "key"]
    assert sorted(tuning.__all__) == [
        "DEFAULT_TABLE", "choice", "current_device_kind", "decision_key",
        "decisions_taken", "device_class", "reset_decisions",
        "shape_bucket"]


# ---------------------------------------------------------------------------
# Call-site wiring + structural selection
# ---------------------------------------------------------------------------


class TestCallSites:
    def _moe_lowered(self, comm, impl):
        ax = comm.axis_name

        def local(x, rw, stacked):
            params = jax.tree.map(lambda l: l[0], stacked)
            return moe_layer_local(
                x, rw, expert_fn, params, ax,
                capacity_factor=2.0, dispatch_impl=impl,
            )

        n = comm.size
        x = jnp.zeros((8 * n, D))
        rw = jnp.zeros((D, n))
        stacked = make_expert_params(_expert_init, jax.random.PRNGKey(0), n)
        fn = jax.jit(shard_map(
            local, mesh=comm.mesh, in_specs=(P(), P(), P(ax)),
            out_specs=P(), check_vma=False,
        ))
        return fn.lower(x, rw, stacked).as_text()

    def test_moe_auto_selects_sort_path_on_cpu_mesh(self, comm):
        """STRUCTURAL: the auto-dispatched program on the CPU mesh IS
        the sort program (index scatter present, and no decision other
        than sort recorded), not the dense einsum one."""
        auto_txt = self._moe_lowered(comm, "auto")
        sort_txt = self._moe_lowered(comm, "sort")
        einsum_txt = self._moe_lowered(comm, "einsum")
        assert "scatter" in auto_txt  # the sort path's queue assembly
        assert "scatter" not in einsum_txt
        assert auto_txt == sort_txt
        recs = [r for r in tuning.decisions_taken()
                if r["name"] == "moe_dispatch"]
        assert recs and all(r["winner"] == "sort" for r in recs)

    def test_moe_dist_equals_single_for_both_sides(self, comm):
        """dist==single (values AND grads) for BOTH tuned candidates:
        the einsum and sort programs over the 8-way mesh each equal the
        same single-device dense evaluation."""
        n = comm.size
        ax = comm.axis_name
        tokens = 8 * n
        x = jax.random.normal(jax.random.PRNGKey(0), (tokens, D))
        rw = jax.random.normal(jax.random.PRNGKey(1), (D, n)) / 4.0
        stacked = make_expert_params(_expert_init, jax.random.PRNGKey(2), n)
        capacity = tokens  # generous: no drops

        def single(x, rw, stacked):
            # single-device dense evaluation of the same routing
            logits = x @ rw
            dispatch, combine = top1_route(logits, capacity)
            queues = jnp.einsum("td,tec->ecd", x, dispatch)
            outs = jax.vmap(expert_fn)(stacked, queues)
            return jnp.einsum("ecd,tec->td", outs, combine)

        def dist(impl):
            def local(x, rw, stacked):
                params = jax.tree.map(lambda l: l[0], stacked)
                return moe_layer_local(
                    x, rw, expert_fn, params, ax,
                    capacity_factor=float(n), dispatch_impl=impl,
                )

            return jax.jit(shard_map(
                local, mesh=comm.mesh, in_specs=(P(), P(), P(ax)),
                out_specs=P(), check_vma=False,
            ))

        ref = single(x, rw, stacked)
        g_ref = jax.grad(
            lambda xx, rr, ss: (single(xx, rr, ss) ** 2).mean(),
            argnums=(0, 1, 2),
        )(x, rw, stacked)
        for impl in ("einsum", "sort"):
            out = dist(impl)(x, rw, stacked)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)
            g = jax.grad(
                lambda xx, rr, ss, i=impl: (dist(i)(xx, rr, ss) ** 2).mean(),
                argnums=(0, 1, 2),
            )(x, rw, stacked)
            jax.tree.map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
                ),
                g, g_ref,
            )

    def test_attention_both_sides_equal(self):
        """Both sides of the attention choice (and of the windowed
        choice) compute the same function — values AND grads."""
        from chainermn_tpu.ops.attention import attention

        q = jax.random.normal(jax.random.PRNGKey(3), (1, 64, 2, 8),
                              jnp.float32)

        for kwargs in ({"causal": True}, {"causal": True, "window": 16}):
            o_x = attention(q, q, q, impl="xla", **kwargs)
            flash_impl = "windowed" if "window" in kwargs else "flash"
            o_f = attention(q, q, q, impl=flash_impl, interpret=True,
                            **kwargs)
            np.testing.assert_allclose(np.asarray(o_x), np.asarray(o_f),
                                       rtol=2e-5, atol=2e-5)

            def loss(fn_impl, interp):
                def f(qq):
                    return jnp.sum(
                        attention(qq, qq, qq, impl=fn_impl,
                                  interpret=interp, **kwargs) ** 2
                    )
                return jax.grad(f)(q)

            np.testing.assert_allclose(
                np.asarray(loss("xla", None)),
                np.asarray(loss(flash_impl, True)),
                rtol=2e-4, atol=2e-5,
            )

    def test_attention_auto_records_decision(self):
        from chainermn_tpu.ops.attention import attention

        q = jnp.zeros((1, 32, 2, 8), jnp.float32)
        attention(q, q, q, causal=True)  # auto -> xla on cpu
        recs = [r for r in tuning.decisions_taken()
                if r["name"] == "attention"]
        assert recs and recs[-1]["winner"] == "xla"

    def test_wire_both_sides_dist_equals_single(self, comm):
        """Both sides of the tuned wire (bf16 vs the f32 master wire,
        plus the int8 wire): the in-mesh mean of
        per-shard grads equals the single-device numpy mean within each
        wire's tolerance."""
        from chainermn_tpu.optimizers import allreduce_gradients

        n = comm.size
        ax = comm.axis_name
        g = jax.random.normal(jax.random.PRNGKey(4), (n, 64), jnp.float32)
        expect = np.asarray(g).mean(axis=0)

        def run(compress):
            def local(gs):
                return allreduce_gradients(
                    gs[0], axis_names=(ax,), compress_dtype=compress
                )[None]

            return jax.jit(shard_map(
                local, mesh=comm.mesh, in_specs=(P(ax),),
                out_specs=P(ax), check_vma=False,
            ))(g)

        for compress, tol in ((None, 1e-6), (jnp.bfloat16, 2e-2),
                              (jnp.int8, 6e-2)):
            out = np.asarray(run(compress))
            for i in range(n):
                np.testing.assert_allclose(out[i], expect, rtol=tol,
                                           atol=tol)

    def test_auto_wire_resolution_and_bucket(self, comm, monkeypatch):
        from chainermn_tpu.communicators.xla_communicator import (
            NaiveCommunicator,
        )
        from chainermn_tpu.parallel.collectives import tuned_bucket_bytes

        c = NaiveCommunicator(allreduce_grad_dtype="auto")
        assert c.allreduce_grad_dtype == jnp.dtype(jnp.bfloat16)
        assert tuned_bucket_bytes(c.device_kind, c.size) == 64 << 20
        # the override flips the wire for the next communicator
        monkeypatch.setenv("CHAINERMN_TPU_AUTOTUNE_FORCE",
                           "allreduce_wire=int8")
        c2 = NaiveCommunicator(allreduce_grad_dtype="auto")
        assert c2.allreduce_grad_dtype == jnp.dtype(jnp.int8)

    def test_double_buffering_is_the_callers_flag(self, comm):
        """The flag is honoured with faithful staleness-1 semantics (the
        first update applies the zero bank and banks this step's
        gradients), warns of nothing and asks the registry nothing."""
        import warnings

        import optax

        from chainermn_tpu import create_multi_node_optimizer

        with warnings.catch_warnings():
            warnings.simplefilter("error")
            opt = create_multi_node_optimizer(
                optax.sgd(0.1), comm, double_buffering=True
            )
        assert not [r for r in tuning.decisions_taken()
                    if r["name"] == "double_buffering"]
        params = {"w": jnp.ones((4,))}
        state = opt.init(params)
        grads = {"w": jnp.full((4,), 2.0)}
        updates, state = opt.update(grads, state, params)
        np.testing.assert_allclose(np.asarray(updates["w"]),
                                   np.zeros(4), atol=0)
        np.testing.assert_allclose(
            np.asarray(state.communicated_grads["w"]),
            np.asarray(grads["w"]), atol=1e-6,
        )
