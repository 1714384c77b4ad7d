"""Multicast tree fan-out over the host p2p plane (ISSUE 16).

The serving plane's one-to-many paths — pushing a tenant adapter to N
replicas, warming N prefix tries from one prefilled donor — previously
cost the donor ``N-1`` sequential ``send_obj`` calls: the donor's
egress is the bottleneck and delivery latency is linear in the fleet.
A radix-``r`` multicast tree (multicast-tree collectives,
arXiv:2605.22428; :func:`tree_depth` / :func:`tree_sends` are its
arithmetic) delivers in ``ceil(log_r N)`` rounds: every member that
already holds the payload forwards it to up to ``r-1`` new members per
round, so the donor pays at most ``(r-1)·ceil(log_r N)`` sends — O(log
N) — and total wire sends stay ``N-1`` (every non-root receives exactly
once), just spread across the fleet instead of piled on the donor.

The transport contract is the existing one: anything with
``send_obj``/``recv_obj`` (``TcpHostComm`` across processes,
:class:`~chainermn_tpu.serving.cluster.kv_transfer.LoopbackHub` in
process). :func:`tree_push` is the HOST-ORCHESTRATED single-process
form — sends are issued strictly before their receives in topological
round order, which is exactly the ordering a per-rank distributed
driver would realize, and the in-process hub's recv-before-send
``LookupError`` makes any ordering bug loud instead of deadlocked.

Every push emits one ``tree_push`` trace event (``docs/
observability.md``): payload kind, fleet size, radix, rounds, total /
donor / sequential-baseline send counts, payload bytes.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence

import numpy as np

from chainermn_tpu.observability import journey as _journey
from chainermn_tpu.observability import trace as _trace
#: Default multicast-tree radix (binary tree: doubling rounds).
DEFAULT_RADIX = 2


def tree_rounds(
    n: int, radix: int = DEFAULT_RADIX
) -> list[list[tuple[int, int]]]:
    """The tree's send schedule in COORDINATE space (0 = root):
    ``rounds[t]`` is the list of ``(src, dst)`` pairs of round ``t``,
    topologically ordered (every ``src`` holds the payload before round
    ``t`` starts). ``len(rounds) == tree_depth(n, radix)`` and the
    total pair count is ``n - 1`` (each non-root receives exactly
    once)."""
    n, r = int(n), int(radix)
    if r < 2:
        raise ValueError(f"radix must be >= 2, got {radix}")
    rounds: list[list[tuple[int, int]]] = []
    holders = 1
    while holders < n:
        pairs = [
            (s, s + j * holders)
            for j in range(1, r)
            for s in range(holders)
            if s + j * holders < n
        ]
        rounds.append(pairs)
        holders *= r
    return rounds


def tree_depth(n: int, radix: int = DEFAULT_RADIX) -> int:
    """Rounds a radix-``radix`` multicast tree needs to cover ``n``
    members from one root, ``ceil(log_radix(n))``: the donor-send depth
    of the serving tree push."""
    return len(tree_rounds(n, radix))


def tree_sends(n: int, radix: int = DEFAULT_RADIX) -> int:
    """Sends the root of a radix-``radix`` multicast over ``n`` members
    makes: up to ``radix - 1`` a round — at radix 2 this equals
    :func:`tree_depth`; a larger radix trades rounds for per-round
    sends (``(r-1)*ceil(log_r(n))`` at full occupancy)."""
    return sum(src == 0 for pairs in tree_rounds(n, radix)
               for src, _ in pairs)


def tree_push(
    payload: Any,
    endpoints: Mapping[int, Any],
    ranks: Sequence[int],
    *,
    root: Optional[int] = None,
    radix: int = DEFAULT_RADIX,
    payload_kind: str = "object",
    nbytes: Optional[int] = None,
) -> tuple[dict[int, Any], dict]:
    """Deliver ``payload`` from ``root`` to every rank in ``ranks``
    along the radix-``radix`` tree. ``endpoints[rank]`` must expose
    ``send_obj(obj, dest)``/``recv_obj(source)`` for every
    participating rank. Forwarders relay the object THEY received
    (store-and-forward — exactly what a per-process driver would hold),
    so a transport that copies on the wire yields independent replicas
    of the payload, never N aliases of the donor's buffers.

    Returns ``(received, stats)``: ``received[rank]`` is what ``rank``
    holds afterwards (the original object at the root), ``stats`` the
    send accounting (``rounds``, ``sends``, ``donor_sends``,
    ``seq_sends`` — the N-1 sequential baseline)."""
    order = list(dict.fromkeys(int(r) for r in ranks))
    if root is None:
        root = order[0]
    root = int(root)
    if root not in order:
        raise ValueError(f"root {root} not in ranks {order}")
    order.remove(root)
    order.insert(0, root)
    n = len(order)
    for rk in order:
        if rk not in endpoints:
            raise ValueError(f"no endpoint for rank {rk}")
    received: dict[int, Any] = {root: payload}
    # Causal-id hop (ISSUE 17): a dict payload ALREADY carrying a
    # journey snapshot (a warm-up payload that started life as a
    # request's export_kv) continues that chain — the ADVANCED snapshot
    # is written back before any send so receivers (and any downstream
    # adoption) parent onto this push's span. A payload WITHOUT one
    # gets a chain minted for the trace event only: injecting the wire
    # key would change the delivered object, and delivery fidelity
    # (received == what the donor pushed) is the tree's contract.
    jfields: dict = {}
    if isinstance(payload, dict):
        wire = payload.get(_journey.WIRE_KEY)
        if wire:
            ctx = _journey.JourneyContext.from_wire(wire)
            jfields = ctx.begin_hop()
            payload[_journey.WIRE_KEY] = ctx.to_wire()
        else:
            jfields = _journey.new(f"{payload_kind}-push").begin_hop()
    donor_sends = 0
    total = 0
    rounds = tree_rounds(n, radix)
    for pairs in rounds:
        # sends strictly before receives, whole round at a time — the
        # ordering a distributed per-rank driver realizes, enforced
        # here so the loopback hub's recv-before-send guard stays loud
        for s, d in pairs:
            src, dst = order[s], order[d]
            endpoints[src].send_obj(received[src], dst)
            total += 1
            if src == root:
                donor_sends += 1
        for s, d in pairs:
            src, dst = order[s], order[d]
            received[dst] = endpoints[dst].recv_obj(src)
    stats = {
        "n": n,
        "radix": int(radix),
        "rounds": len(rounds),
        "depth": tree_depth(n, radix),
        "sends": total,
        "donor_sends": donor_sends,
        "seq_sends": max(0, n - 1),
    }
    assert total == max(0, n - 1), (total, n)  # every non-root once
    rec = _trace.active()
    if rec is not None:
        rec.event(
            "tree_push", payload_kind=payload_kind, **stats,
            **({"nbytes": int(nbytes)} if nbytes is not None else {}),
            **jfields,
        )
    return received, stats


def _adapter_payload(adapter, tenant_id: str) -> dict:
    layers = [
        {tgt: (np.asarray(A, np.float32), np.asarray(B, np.float32))
         for tgt, (A, B) in layer.items()}
        for layer in adapter.layers
    ]
    return {
        "schema": 1,
        "kind": "adapter",
        "tenant": str(tenant_id),
        "scale": float(adapter.scale),
        "layers": layers,
        "nbytes": sum(A.nbytes + B.nbytes
                      for layer in layers for A, B in layer.values()),
    }


def push_adapter(
    adapter,
    tenant_id: str,
    replicas: Sequence,
    hub,
    *,
    root: Optional[int] = None,
    radix: int = DEFAULT_RADIX,
) -> dict:
    """Install ``tenant_id``'s adapter on EVERY replica's bank via one
    tree push (the one-to-many serving-plane rendering of the ``bc``
    stage): the donor serializes once, the payload rides the
    radix-``radix`` tree over ``hub`` endpoints, and each replica
    registers its received copy into its OWN
    :class:`~chainermn_tpu.serving.adapters.AdapterBank` — bit-identical
    rows everywhere (registration is deterministic in the payload), the
    donor paying O(log N) sends instead of N-1.

    Replicas without a bank refuse loudly — silently skipping one would
    strand a tenant on a subset of the fleet. Returns the
    :func:`tree_push` stats."""
    from chainermn_tpu.serving.adapters import LowRankAdapter

    reps = {int(r.replica_id): r for r in replicas}
    for rid, rep in reps.items():
        if getattr(rep.engine, "adapter_bank", None) is None:
            raise ValueError(
                f"replica {rid} has no adapter_bank — cannot push "
                f"tenant {tenant_id!r} to a bankless fleet member"
            )
    payload = _adapter_payload(adapter, tenant_id)
    endpoints = {rid: hub.endpoint(rid) for rid in reps}
    received, stats = tree_push(
        payload, endpoints, list(reps), root=root, radix=radix,
        payload_kind="adapter", nbytes=payload["nbytes"],
    )
    for rid, rep in reps.items():
        got = received[rid]
        if not isinstance(got, dict) or got.get("kind") != "adapter":
            raise ValueError(
                f"replica {rid}: unexpected tree-push payload "
                f"{type(got).__name__}"
            )
        rep.engine.adapter_bank.register(
            got["tenant"],
            LowRankAdapter(got["layers"], scale=got["scale"]),
        )
    return stats


def warm_prefix_trie(
    replicas: Sequence,
    donor_slot: int,
    hub,
    *,
    root: Optional[int] = None,
    radix: int = DEFAULT_RADIX,
) -> dict:
    """Warm every replica's prefix trie from ONE prefilled donor slot:
    the donor exports the slot's KV payload once
    (``ServingEngine.export_kv``), it rides the tree, and each other
    replica adopts it (``import_kv`` — with prefix sharing on the full
    blocks land in that replica's trie) and immediately ``leave``\\ s
    the scratch slot, keeping the warmth without holding a slot. The
    donor's slot stays live (callers own its lifecycle).

    ``root`` defaults to the first replica; it must identify the
    replica that owns ``donor_slot``. Refuses loudly when a replica
    cannot place the payload (warm-up assumes capacity). Returns the
    :func:`tree_push` stats plus per-replica adopted slot bookkeeping
    under ``"adopted"``."""
    reps = {int(r.replica_id): r for r in replicas}
    rids = list(reps)
    if root is None:
        root = rids[0]
    root = int(root)
    donor = reps[root]
    payload = donor.engine.export_kv(donor_slot)
    endpoints = {rid: hub.endpoint(rid) for rid in reps}
    received, stats = tree_push(
        payload, endpoints, rids, root=root, radix=radix,
        payload_kind="kv_warm", nbytes=payload["nbytes"],
    )
    adopted: dict[int, int] = {}
    for rid, rep in reps.items():
        if rid == root:
            continue
        res = rep.engine.import_kv(received[rid])
        if res is None:
            raise RuntimeError(
                f"replica {rid} could not place the warm-up payload "
                "(no free slot/blocks) — trie warm-up assumes capacity"
            )
        slot, _ = res
        rep.engine.leave(slot)  # trie keeps the blocks, slot freed
        adopted[rid] = slot
    stats = dict(stats)
    stats["adopted"] = adopted
    return stats


__all__ = [
    "push_adapter",
    "tree_push",
    "tree_rounds",
    "tree_sends",
    "warm_prefix_trie",
]
