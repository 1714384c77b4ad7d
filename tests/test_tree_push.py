"""Multicast tree fan-out on the serving host plane (ISSUE 16).

Pins the tree-push contract end to end:

- the send schedule is the holder-doubling walk (``tree_depth`` rounds,
  ``n-1`` total sends, every source a holder);
- :func:`tree_push` delivers over the loopback hub with O(log N)
  donor sends (vs the N-1 sequential baseline) and emits the
  ``tree_push`` trace event;
- :func:`push_adapter` lands BIT-IDENTICAL adapter rows on every
  replica's own bank (same rows a direct register produces);
- :func:`warm_prefix_trie` makes every replica's trie answer the
  shared prefix after ONE donor prefill, scratch slots released;
- a bankless fleet member refuses the push loudly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chainermn_tpu.models.transformer import TransformerLM
from chainermn_tpu.observability import trace
from chainermn_tpu.serving import Scheduler, ServingEngine
from chainermn_tpu.serving.adapters import AdapterBank, random_adapter
from chainermn_tpu.serving.cluster import (
    LoopbackHub,
    Replica,
    push_adapter,
    tree_push,
    tree_rounds,
    warm_prefix_trie,
)
from chainermn_tpu.serving.cluster.tree_push import tree_depth, tree_sends

VOCAB = 32


def tiny_lm(**kw):
    cfg = dict(vocab_size=VOCAB, num_layers=2, num_heads=4, d_model=16,
               d_ff=32, max_len=64, compute_dtype=jnp.float32)
    cfg.update(kw)
    return TransformerLM(**cfg)


@pytest.fixture(scope="module")
def lm():
    model = tiny_lm()
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 4), jnp.int32), train=False
    )
    return model, params


ENGINE_KW = dict(num_slots=2, max_len=32, decode_impl="paged",
                 kv_block_size=8, prefill_buckets=(4, 8, 16),
                 spec_tokens=0, prefill_chunk=0,
                 prefill_seq_parallel="off", adapter_impl="gather")


def _fleet(lm, n, *, banked=True, **kw):
    """n replicas, each with its OWN bank (the cluster reality —
    cross-replica state moves over the host plane only)."""
    model, params = lm
    cfg = dict(ENGINE_KW)
    cfg.update(kw)
    reps = []
    for r in range(n):
        bank = (AdapterBank(model, capacity=4, rank=2)
                if banked else None)
        eng = ServingEngine(model, params, adapter_bank=bank,
                            **(cfg if banked else
                               {k: v for k, v in cfg.items()
                                if k != "adapter_impl"}))
        reps.append(Replica(eng, Scheduler(eng), r))
    return reps


class TestTreeSchedule:
    def test_rounds_match_broadcast_walk(self):
        for n, r in [(2, 2), (4, 2), (8, 2), (8, 4), (5, 2), (7, 3)]:
            rounds = tree_rounds(n, r)
            assert len(rounds) == tree_depth(n, r), (n, r)
            pairs = [p for rnd in rounds for p in rnd]
            # every non-root receives exactly once
            assert sorted(d for _, d in pairs) == list(range(1, n))
            # every source holds the payload when its round starts
            holders = 1
            for rnd in rounds:
                assert all(s < holders for s, _ in rnd)
                holders *= r

    def test_radix_validation(self):
        with pytest.raises(ValueError, match="radix"):
            tree_rounds(4, 1)
        with pytest.raises(ValueError, match="radix"):
            tree_depth(4, 1)
        with pytest.raises(ValueError, match="radix"):
            tree_sends(4, 1)

    @pytest.mark.parametrize("n,radix,depth,sends", [
        (1, 2, 0, 0), (2, 2, 1, 1), (5, 2, 3, 3), (8, 2, 3, 3),
        (8, 4, 2, 4), (9, 4, 2, 5), (64, 4, 3, 9),
    ])
    def test_tree_depth_and_sends(self, n, radix, depth, sends):
        """Rounds to reach ``n`` members and the root's sends: radix - 1
        a full round, fewer in a last round that needs fewer."""
        assert tree_depth(n, radix) == depth
        assert tree_sends(n, radix) == sends
        assert len(tree_rounds(n, radix)) == depth
        assert sum(s == 0 for rnd in tree_rounds(n, radix)
                   for s, _ in rnd) == sends


class TestTreePush:
    def test_delivers_with_log_donor_sends(self):
        hub = LoopbackHub()
        ranks = [3, 7, 1, 0, 5, 2, 6, 4]  # order/ids arbitrary
        endpoints = {r: hub.endpoint(r) for r in ranks}
        rec = trace.enable(None)
        received, stats = tree_push(
            {"x": 1}, endpoints, ranks, root=3, payload_kind="probe")
        assert set(received) == set(ranks)
        assert all(v == {"x": 1} for v in received.values())
        assert stats["sends"] == 7 == stats["seq_sends"]
        assert stats["rounds"] == tree_depth(8, 2) == 3
        assert stats["donor_sends"] == 3  # one per round at radix 2
        ev = [e for e in rec.events if e["kind"] == "tree_push"]
        assert len(ev) == 1 and ev[0]["payload_kind"] == "probe"
        assert ev[0]["donor_sends"] == 3 and ev[0]["seq_sends"] == 7
        trace.disable()

    def test_radix4_flattens_the_tree(self):
        hub = LoopbackHub()
        ranks = list(range(8))
        endpoints = {r: hub.endpoint(r) for r in ranks}
        _, stats = tree_push("p", endpoints, ranks, radix=4)
        assert stats["rounds"] == tree_depth(8, 4) == 2
        assert stats["sends"] == 7
        # donor sends 3 in round one (holders 1..3) + 1 in round two
        assert stats["donor_sends"] == 4 == tree_sends(8, 4)

    def test_unknown_root_refused(self):
        hub = LoopbackHub()
        endpoints = {r: hub.endpoint(r) for r in (0, 1)}
        with pytest.raises(ValueError, match="root"):
            tree_push("p", endpoints, [0, 1], root=9)


class TestPushAdapter:
    def test_bit_identical_rows_everywhere(self, lm):
        model, _ = lm
        reps = _fleet(lm, 4)
        adapter = random_adapter(model, 2, seed=11, scale=1.5)
        hub = LoopbackHub()
        stats = push_adapter(adapter, "t1", reps, hub)
        assert stats["donor_sends"] == 2  # ceil(log2 4) rounds x 1
        # reference: a direct local register of the same adapter
        ref = AdapterBank(model, capacity=4, rank=2)
        ref_row = ref.register("t1", adapter)
        for rep in reps:
            bank = rep.engine.adapter_bank
            row = bank.row_of("t1")
            for li in range(model.num_layers):
                for tgt in bank.targets:
                    for k in (0, 1):  # A stack, B stack (scale folded)
                        np.testing.assert_array_equal(
                            bank._stacks[li][tgt][k][row],
                            ref._stacks[li][tgt][k][ref_row],
                            err_msg=f"replica {rep.replica_id} "
                                    f"layer {li} {tgt}",
                        )
            assert rep.engine.adapter_resident("t1")

    def test_bankless_member_refuses(self, lm):
        model, _ = lm
        reps = _fleet(lm, 2)
        reps += _fleet(lm, 1, banked=False)
        reps[2].replica_id = 2
        adapter = random_adapter(model, 2, seed=3)
        with pytest.raises(ValueError, match="adapter_bank"):
            push_adapter(adapter, "t1", reps, LoopbackHub())


class TestWarmPrefixTrie:
    def test_one_prefill_warms_every_trie(self, lm):
        reps = _fleet(lm, 4, banked=False, prefix_cache="on",
                      num_slots=4)
        shared = list(range(1, 17))  # 2 full blocks @ kv_block_size 8
        donor = reps[0].engine
        slot, _, _ = donor.prefill_join(shared + [20, 21])
        free_before = [r.engine.free_slot_count for r in reps[1:]]
        hub = LoopbackHub()
        stats = warm_prefix_trie(reps, slot, hub)
        assert stats["donor_sends"] == 2 and stats["sends"] == 3
        assert sorted(stats["adopted"]) == [1, 2, 3]
        for rep in reps[1:]:
            assert rep.engine.prefix_match_depth(shared) == 2, (
                rep.replica_id)
        # scratch slots released — warmth without held slots
        assert [r.engine.free_slot_count for r in reps[1:]] == \
            free_before
        # donor slot untouched (caller owns its lifecycle)
        donor.leave(slot)
