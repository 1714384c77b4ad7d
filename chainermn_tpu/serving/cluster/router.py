"""Cluster front door: least-loaded / prefix-cache-aware placement
over a replica set, sticky multi-turn routing, and disaggregated
prefill/decode with KV streaming (ISSUE 8 tentpole).

One host loop drives everything: ``submit`` places each request on a
replica (consulting the signals the PR 6/7 planes already expose —
queue depth, ``kv_blocks_free``, and each replica's prefix-trie hit
depth via a read-only probe), ``run`` interleaves every replica's
admissions and decode steps through ``Scheduler.tick``. Requests that
cannot be admitted right now ride the existing deferred-admission
path (``prefill_join``/``import_kv`` returning None keeps them queued
— requeue-on-full, never an error another capacity state wouldn't
raise).

**Disaggregated mode** (``mode='disaggregated'``, or ``'auto'``
through the tuning registry — decision ``cluster_disagg``, table
default colocated: the transfer hop must earn adoption): designated
prefill replicas run the bucketed prefill, the finished KV blocks
stream to a decode replica over the host plane
(:mod:`~chainermn_tpu.serving.cluster.kv_transfer`), and the decode
replica's scheduler adopts the in-flight stream
(``Scheduler.admit_prefilled``) — compute-bound prefill and
latency-bound decode stop competing for the same chips, and the
decode replicas' compiled steps carry exactly the pre-cluster
collective set (nothing new on the wire; pinned structurally).

**Equivalence contract** (the suite pins it end to end): every token
stream routed through the cluster is bit-identical to sequential
``generate`` on a single device — including streams whose KV was
prefilled on a different replica than the one that decoded them.

**Replica loss**: :meth:`Router.fail_replica` evacuates a dead
replica's queued AND in-flight requests and re-routes them to the
survivors (streams are deterministic — greedy, or counter-key sampled
under the ``Request.seed`` that rides the re-routed object — so the
re-prefilled stream is identical; the client never sees the loss, only
latency); see docs/fault_tolerance.md.

Observability: one ``route`` trace event per placement and one
``kv_transfer`` event per handoff (docs/observability.md), plus
``rank``-labeled per-replica gauges (``serving_replica_queue_depth`` /
``_inflight`` / ``_kv_blocks_free``) so a multi-replica process is
inspectable live (``tools/metrics_dump.py --ports`` merges several
replica endpoints into one table).
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from typing import Optional, Sequence

from chainermn_tpu.observability import journey as _journey
from chainermn_tpu.serving.cluster.replica import Replica
from chainermn_tpu.serving.scheduler import (
    Request,
    check_session_tenant,
    keep_arrival,
    pin_session_tenant,
)

ROUTE_POLICIES = ("least_loaded", "prefix_aware")
#: tuning-registry candidates for the cluster topology decision.
#: ``colocated_chunked`` (ISSUE 11) routes exactly like ``colocated``
#: but declares that the replicas run CHUNKED engines
#: (``prefill_chunk > 0``) — the third competitor the bench's bursty
#: phase prices against plain colocated and disaggregated: chunking
#: removes the monolithic-prefill decode stall WITHOUT the
#: disaggregation hop's transfer cost.
DISAGG_MODES = ("colocated", "disaggregated", "colocated_chunked")

#: process-global router id sequence: replica schedulers OUTLIVE any
#: one router (bench repeats build a fresh Router over warm replicas),
#: and their results dicts reject id reuse — so router-assigned ids
#: must never restart per instance.
_ROUTER_IDS = itertools.count()


class Router:
    """Front door over a replica set; see module docstring.

    Args:
      replicas: the :class:`~chainermn_tpu.serving.cluster.replica
        .Replica` set (``make_replicas``). All replicas a transfer can
        cross must share a KV layout (``import_kv`` refuses loudly).
      policy: ``'prefix_aware'`` (default — deepest trie hit wins,
        load breaks ties) or ``'least_loaded'``.
      mode: ``'colocated'`` | ``'disaggregated'`` | ``'auto'``
        (registry decision ``cluster_disagg`` under the first
        replica's serving key; forced colocated — with provenance —
        when the set is too small to split).
      prefill_replicas: replica ids that prefill in disaggregated mode
        (default: replicas whose ``role`` is ``'prefill'``, else the
        first replica). Every other replica decodes.
    """

    def __init__(self, replicas: Sequence[Replica], *,
                 policy: str = "prefix_aware", mode: str = "auto",
                 prefill_replicas: Optional[Sequence[int]] = None) -> None:
        if not replicas:
            raise ValueError("Router needs at least one replica")
        if policy not in ROUTE_POLICIES:
            raise ValueError(
                f"policy must be one of {ROUTE_POLICIES}, got {policy!r}")
        self.replicas = {r.replica_id: r for r in replicas}
        if len(self.replicas) != len(replicas):
            raise ValueError("duplicate replica_id in the replica set")
        self.policy = policy
        self.decisions: list[dict] = []

        # ---- mode resolution (the serving-decision pattern)
        if mode not in DISAGG_MODES + ("auto",):
            raise ValueError(
                f"mode must be one of {DISAGG_MODES + ('auto',)}, got "
                f"{mode!r}"
            )
        key = replicas[0].engine.decision_key
        chunked_engines = all(
            getattr(r.engine, "prefill_chunk", 0) > 0 for r in replicas
        )
        if mode == "auto":
            if len(replicas) < 2:
                mode = "colocated"
                self.decisions.append({
                    "name": "cluster_disagg", "key": key,
                    "winner": mode, "source": "forced:single-replica",
                })
            else:
                from chainermn_tpu import tuning

                mode = tuning.choice("cluster_disagg", DISAGG_MODES, key)
                recs = [d for d in tuning.decisions_taken()
                        if d["name"] == "cluster_disagg"
                        and d["key"] == key]
                if recs:
                    self.decisions.append(dict(recs[-1]))
                if mode == "colocated_chunked" and not chunked_engines:
                    # The decision says chunking wins this shape, but THIS
                    # replica set was built monolithic — route as plain
                    # colocated (honest provenance) rather than promise
                    # a mixed step nobody compiled.
                    mode = "colocated"
                    self.decisions.append({
                        "name": "cluster_disagg", "key": key,
                        "winner": mode,
                        "source": "forced:unchunked-engines",
                    })
        else:
            if mode == "disaggregated" and len(replicas) < 2:
                raise ValueError(
                    "disaggregated mode needs >= 2 replicas (one "
                    "prefill + one decode)"
                )
            if mode == "colocated_chunked" and not chunked_engines:
                raise ValueError(
                    "mode='colocated_chunked' needs every replica "
                    "engine built with prefill_chunk > 0"
                )
            self.decisions.append({"name": "cluster_disagg", "key": key,
                                   "winner": mode, "source": "explicit"})
        self.mode = mode

        # ---- role partition (disaggregated only)
        if self.mode == "disaggregated":
            if prefill_replicas is None:
                prefill_replicas = [r.replica_id for r in replicas
                                    if r.role == "prefill"]
                if not prefill_replicas:
                    prefill_replicas = [replicas[0].replica_id]
            self._prefill_ids = [int(i) for i in prefill_replicas]
            for i in self._prefill_ids:
                if i not in self.replicas:
                    raise ValueError(f"unknown prefill replica id {i}")
                self.replicas[i].role = "prefill"
            self._decode_ids = [i for i in self.replicas
                                if i not in self._prefill_ids]
            if not self._decode_ids:
                raise ValueError(
                    "disaggregated mode left no decode replicas")
            for i in self._decode_ids:
                self.replicas[i].role = "decode"
            # one signature across the transfer boundary, checked ONCE
            # here instead of per-handoff deep in a serving loop
            sigs = {i: self.replicas[i].engine.kv_signature()
                    for i in self.replicas}
            if len(set(sigs.values())) != 1:
                raise ValueError(
                    f"replicas disagree on KV layout — blocks are not "
                    f"portable across this set: {sigs}"
                )
            #: per-prefill-replica router queues (arrival-ordered)
            self._pqueues = {i: deque() for i in self._prefill_ids}
            #: per-decode-replica pending handoffs awaiting adoption
            self._pending = {i: deque() for i in self._decode_ids}
        else:
            self._prefill_ids = []
            self._decode_ids = list(self.replicas)
            self._pqueues = {}
            self._pending = {}

        # ---- expert-shard residency (ISSUE 20): the fleet's MoE
        # signature, derived ONCE like the KV-layout check above. MoE
        # replicas must agree on the expert set — a2a dispatch shapes
        # bake n_experts into the compiled programs, so a mismatched
        # replica would produce different streams, not just worse ones.
        # Dense replicas may coexist (they serve nothing in a MoE
        # fleet — the hard filter below excludes them) so a mixed pool
        # mid-migration fails at placement, loudly, not mid-decode.
        esigs = {i: r.expert_signature() for i, r in self.replicas.items()}
        moe_sigs = {s for s in esigs.values() if s is not None}
        if len(moe_sigs) > 1:
            raise ValueError(
                f"replicas disagree on the expert set — MoE dispatch "
                f"is not portable across this pool: {esigs}"
            )
        #: fleet-wide expert signature; None = dense fleet (no filter)
        self._expert_sig = moe_sigs.pop() if moe_sigs else None

        self._ids = _ROUTER_IDS
        self._seen_ids: set = set()
        self._sessions: dict = {}
        #: session -> tenant pinning (the ISSUE 14 consistency guard —
        #: same rule as Scheduler.submit's).
        self._session_tenants: dict = {}
        #: requests that finished at the router (done at prefill —
        #: no decode leg, no transfer); merged into :meth:`run`'s
        #: result dict beside the replicas' own results.
        self.results: dict = {}
        self._events: list[dict] = []
        self.events_dropped = 0
        self._route_counts: dict = {}
        self._ttfts: list[float] = []
        self.transfers = 0
        self.transfer_bytes = 0
        self._wall: Optional[float] = None
        # Live-telemetry front door, same gate as Scheduler.__init__
        try:
            from chainermn_tpu.observability import exporter as _exporter

            _exporter.maybe_start_from_env()
        except Exception:
            pass

    # ------------------------------------------------------------------

    def _event(self, _kind: str, **fields) -> None:
        from chainermn_tpu.observability import trace

        if len(self._events) < trace.MAX_BUFFERED_EVENTS:
            self._events.append({"kind": _kind, **fields})
        else:
            self.events_dropped += 1
        rec = trace.active()
        if rec is not None:
            rec.event(_kind, **fields)

    def _publish_gauges(self) -> None:
        """Per-replica ``rank``-labeled gauges (ISSUE 8): the router is
        the one place that sees every replica, so cluster-wide load
        lands on ONE endpoint — and ``merge_peer_snapshots`` / the
        multi-port ``metrics_dump`` merge keeps the same label when
        replicas live in separate processes."""
        from chainermn_tpu.observability import metrics

        reg = metrics.active_registry()
        if reg is None:
            return
        for i, rep in self.replicas.items():
            rank = str(i)
            # Dead replicas publish 0s (their load was evacuated) plus
            # an explicit liveness flag — frozen last-breath gauges
            # would read as "alive and loaded" to a monitor, masking
            # the exact failure they exist to surface (review finding).
            reg.gauge("serving_replica_alive",
                      "1 while the replica is in rotation, 0 after "
                      "fail_replica").set(
                1.0 if rep.alive else 0.0, rank=rank)
            if rep.alive:
                depth = rep.scheduler.pending + len(
                    self._pqueues.get(i, ())) + len(
                    self._pending.get(i, ()))
                inflight = rep.scheduler.in_flight
                free = rep.kv_blocks_free()
            else:
                depth, inflight, free = 0, 0, 0
            reg.gauge("serving_replica_queue_depth",
                      "requests waiting on a replica (scheduler queue "
                      "+ router prefill queue + pending KV handoffs)"
                      ).set(depth, rank=rank)
            reg.gauge("serving_replica_inflight",
                      "requests decoding on a replica").set(
                inflight, rank=rank)
            if free is not None:
                reg.gauge("serving_replica_kv_blocks_free",
                          "free paged KV blocks per replica").set(
                    free, rank=rank)

    # ------------------------------------------------------------------
    # placement

    def _alive(self, ids) -> list[Replica]:
        return [self.replicas[i] for i in ids if self.replicas[i].alive]

    def _resident(self, candidates: Sequence[Replica],
                  tenant_id) -> list[Replica]:
        """Restrict ``candidates`` to replicas whose bank holds
        ``tenant_id`` (review finding: residency was only a SCORE
        bonus, so a tenant resident nowhere in the candidate set was
        still placed — and crashed the drive loop with a KeyError at
        ``prefill_join``/``import_kv`` instead of refusing). Raises
        the front-door error when none qualify (a resident replica can
        die between submit and placement). ``tenant_id=None`` filters
        too: a merged replica serves exactly its folded tenant, so a
        base-model request must not be placed on it."""
        out = [rep for rep in candidates
               if rep.adapter_resident(tenant_id)]
        if not out:
            who = (f"tenant {tenant_id!r}" if tenant_id is not None
                   else "a base-model (tenantless) request")
            raise RuntimeError(
                f"{who} has no serving-capable candidate replica "
                "(adapter not resident / merged-tenant mismatch) — "
                "register it (or revive the replica) before routing "
                "traffic"
            )
        return out

    def _expert_hosts(self, candidates: Sequence[Replica]
                      ) -> list[Replica]:
        """Restrict ``candidates`` to replicas hosting the fleet's
        expert shards (ISSUE 20, the adapter-residency pattern made a
        HARD filter): a dense engine has no expert weights, so placing
        MoE traffic on it is not a degraded choice — it is impossible.
        No-op for dense fleets. Raises loudly when no candidate
        qualifies (e.g. every MoE replica died and only dense spares
        remain) instead of letting ``_choose`` pick an engine that
        cannot run the model."""
        if self._expert_sig is None:
            return list(candidates)
        out = [rep for rep in candidates
               if rep.experts_resident(self._expert_sig)]
        if not out:
            raise RuntimeError(
                f"no candidate replica hosts the model's expert shards "
                f"{self._expert_sig} — MoE traffic cannot be placed on "
                "a dense engine; revive an expert-bearing replica "
                "before routing traffic"
            )
        return out

    def _score(self, rep: Replica, prompt, tenant_id=None,
               extra_queue: int = 0):
        """Placement score, maximized. ADAPTER RESIDENCY dominates for
        tenant-bearing requests (ISSUE 14: a replica whose bank holds
        the tenant's rows can serve it NOW — anywhere else needs a
        registration first, and a merged replica serves exactly its
        folded tenant); then prefix hit depth under ``prefix_aware``
        (a deeper hit is prefill work NOT done — worth more than
        perfect load balance, and probed under the TENANT's namespace);
        load breaks ties; free pool blocks break those (a starved pool
        defers admissions, the latency the gauges exist to predict)."""
        resident = int(rep.adapter_resident(tenant_id))
        hit = rep.prefix_hit_blocks(prompt, tenant_id=tenant_id) if (
            self.policy == "prefix_aware") else 0
        load = rep.load() + extra_queue
        free = rep.kv_blocks_free()
        return (resident, hit, -load, free if free is not None else 0,
                -rep.replica_id)

    def _choose(self, candidates: Sequence[Replica], request: Request,
                extra=None) -> Replica:
        return max(candidates, key=lambda rep: self._score(
            rep, request.prompt, request.tenant_id,
            (extra or {}).get(rep.replica_id, 0)))

    def _route(self, request: Request, requeue: bool = False) -> int:
        """Place one request; returns the chosen replica id. Sticky:
        a session's first placement pins its later turns (while the
        replica lives) so the per-replica trie stays warm."""
        target_ids = (self._prefill_ids if self.mode == "disaggregated"
                      else self._decode_ids)
        candidates = self._alive(target_ids)
        if not candidates:
            raise RuntimeError("no alive replica can accept requests")
        candidates = self._expert_hosts(candidates)
        sticky = False
        rep = None
        sid = request.session_id
        if sid is not None and sid in self._sessions:
            pinned = self._sessions[sid]
            if (pinned in self.replicas and self.replicas[pinned].alive
                    and pinned in target_ids
                    and self.replicas[pinned].adapter_resident(
                        request.tenant_id)):
                rep = self.replicas[pinned]
                sticky = True
        if rep is None:
            extra = {i: len(self._pqueues.get(i, ()))
                     for i in self.replicas}
            rep = self._choose(
                self._resident(candidates, request.tenant_id),
                request, extra)
        if sid is not None:
            self._sessions[sid] = rep.replica_id
        if self.mode == "disaggregated":
            self._pqueues[rep.replica_id].append(request)
        else:
            rep.scheduler.submit(request)
        rid = rep.replica_id
        self._route_counts[rid] = self._route_counts.get(rid, 0) + 1
        ev_extra = ({"tenant": request.tenant_id,
                     "adapter_resident": rep.adapter_resident(
                         request.tenant_id)}
                    if request.tenant_id is not None else {})
        self._event(
            "route", request=request.request_id, replica=rid,
            policy=self.policy, mode=self.mode, sticky=sticky,
            requeue=bool(requeue),
            hit_blocks=rep.prefix_hit_blocks(
                request.prompt, tenant_id=request.tenant_id),
            load=rep.load(),
            kv_blocks_free=rep.kv_blocks_free(),
            **ev_extra,
            **_journey.fields(request),
        )
        self._publish_gauges()
        return rid

    def submit(self, request: Request) -> str:
        """Admit one request into the cluster; returns its id. The
        horizon check runs here (every replica shares the engine
        shape) so an impossible request fails at the front door, not
        mid-stream on whichever replica drew it."""
        engine = next(iter(self.replicas.values())).engine
        total = len(request.prompt) + request.max_new_tokens
        if total > engine.max_len:
            raise ValueError(
                f"request needs {total} positions but the cluster "
                f"engine horizon is max_len={engine.max_len}"
            )
        if request.request_id is None:
            request.request_id = f"c{next(self._ids)}"
        if request.request_id in self._seen_ids:
            raise ValueError(
                f"duplicate request_id {request.request_id!r}")
        # Sticky-session/tenant consistency (ISSUE 14 satellite): the
        # ONE shared validate half; the pin commits below, after the
        # residency validation — a refused submission must not poison
        # the session id (review finding).
        check_session_tenant(self._session_tenants, request)
        # Tenant must be placeable on EVERY role its journey touches
        # (review finding: "resident somewhere" passed a tenant whose
        # adapter lived only on a decode replica, and the prefill pump
        # then crashed mid-run): colocated needs a resident decode
        # replica; disaggregated needs one per plane — prefill runs
        # the forward, and import_kv validates residency on the decode
        # side before adopting.
        needed = ([("prefill", self._prefill_ids),
                   ("decode", self._decode_ids)]
                  if self.mode == "disaggregated"
                  else [("decode", self._decode_ids)])
        for role, ids in needed:
            if not any(rep.adapter_resident(request.tenant_id)
                       for rep in self._alive(ids)):
                who = (f"tenant {request.tenant_id!r} has no resident "
                       "adapter"
                       if request.tenant_id is not None
                       else "a base-model (tenantless) request has no "
                            "serving-capable replica")
                raise ValueError(
                    f"{who} on any alive {role} replica — register "
                    "the adapter (or add a non-merged replica) before "
                    "routing traffic"
                )
        self._seen_ids.add(request.request_id)
        pin_session_tenant(self._session_tenants, request)
        # The ONE stamp rule (ISSUE 11 satellite): set only when unset,
        # so this front door, Scheduler.submit and the preemption
        # requeue can never disagree about when the journey began.
        keep_arrival(request)
        _journey.ensure(request)  # the causal-id sibling of the rule
        self._route(request)
        return request.request_id

    # ------------------------------------------------------------------
    # disaggregated pumps

    def _pump_prefill(self) -> bool:
        """Admit router-queued requests into prefill replicas (strict
        arrival order per replica — the scheduler's FCFS discipline),
        export + release each finished prefill, and queue the payload
        for a decode replica. A refused ``prefill_join`` leaves the
        head queued: the deferred-admission path, retried next
        sweep."""
        progressed = False
        for i in self._prefill_ids:
            rep = self.replicas[i]
            if not rep.alive:
                continue
            q = self._pqueues[i]
            while q:
                req = q[0]
                t_admit = time.perf_counter()
                join_kw = ({"tenant_id": req.tenant_id}
                           if req.tenant_id is not None else {})
                res = rep.engine.prefill_join(req.prompt, **join_kw)
                if res is None:
                    break
                q.popleft()
                slot, tok, _bucket = res
                progressed = True
                if req.max_new_tokens <= 1 or (
                    req.eos_id is not None and tok == req.eos_id
                ):
                    # Done at prefill: nothing to decode, nothing to
                    # stream — finish at the router.
                    rep.engine.leave(slot)
                    self.results[req.request_id] = {
                        "tokens": list(req.prompt) + [tok],
                        "generated": [tok],
                    }
                    self._ttfts.append(time.perf_counter() - req._arrival)
                    continue
                # t_export stamps AFTER the prefill: the kv_transfer
                # event's dur_s is the HANDOFF latency (export →
                # adoption), not prefill compute (review finding); the
                # admission-to-adoption total rides admit_prefilled's
                # dur_s instead.
                t_export = time.perf_counter()
                payload = rep.engine.export_kv(slot)
                rep.engine.leave(slot)
                # Journey snapshot ON the payload (ISSUE 17): in
                # process the same Request object continues the chain;
                # over a real wire the decode rank restores it from
                # exactly this key (journey.adopt_payload).
                _journey.attach_payload(payload, req)
                dst = self._choose_decode(req.tenant_id)
                self._pending[dst.replica_id].append(
                    (req, payload, t_export, t_admit, i))
        return progressed

    def _choose_decode(self, tenant_id=None) -> Replica:
        """Decode placement: most free pool blocks, then least loaded
        (pending handoffs count as load — they land next). Tenant-
        bearing handoffs only consider resident replicas —
        ``import_kv`` validates residency before adopting, so a
        non-resident pick would crash the adopt pump. Alive is checked
        FIRST so a dead-pool outage reads as what it is, not as a
        residency problem (review finding)."""
        alive = self._alive(self._decode_ids)
        if not alive:
            raise RuntimeError("no alive decode replica")
        cands = self._resident(self._expert_hosts(alive), tenant_id)
        return max(cands, key=lambda rep: (
            rep.kv_blocks_free() or 0,
            -(rep.load() + len(self._pending[rep.replica_id])),
            -rep.replica_id,
        ))

    def _pump_adopt(self) -> bool:
        """Adopt pending handoffs into decode replicas. ``import_kv``
        returning None (no slot / pool full right now) keeps the
        payload queued — requeue-on-full, FIFO per replica so the
        per-pair ordering of the TCP plane is preserved end to end."""
        progressed = False
        for i in self._decode_ids:
            rep = self.replicas[i]
            if not rep.alive:
                continue
            dq = self._pending[i]
            while dq:
                req, payload, t_export, t_admit, src = dq[0]
                res = rep.engine.import_kv(payload)
                if res is None:
                    break
                dq.popleft()
                slot, tok = res
                now = time.perf_counter()
                self.transfers += 1
                self.transfer_bytes += int(payload["nbytes"])
                self._event(
                    "kv_transfer", request=req.request_id, src=src,
                    dst=i, nbytes=int(payload["nbytes"]),
                    blocks=len(payload["blocks"]),
                    dur_s=round(now - t_export, 9),
                    **_journey.fields(req),
                )
                rep.scheduler.admit_prefilled(req, slot, tok,
                                              dur_s=now - t_admit)
                progressed = True
        return progressed

    # ------------------------------------------------------------------
    # drive

    @property
    def drained(self) -> bool:
        return (not self.work_pending()
                and all(rep.drained for rep in self.replicas.values()
                        if rep.alive))

    def work_pending(self) -> int:
        return (sum(len(q) for q in self._pqueues.values())
                + sum(len(q) for q in self._pending.values()))

    def run(self, max_steps: int = 100_000,
            max_seconds: Optional[float] = None) -> dict:
        """Drive the whole cluster until every stream drains; returns
        the merged ``{request_id: {'tokens', 'generated'}}`` dict
        (router-local finishes + every replica's results).
        ``max_seconds`` bounds the run by wall clock, stopping cleanly
        (unfinished requests stay queued/in flight); ``max_steps``
        stays the runaway guard and raises."""
        from chainermn_tpu.observability import flight as _flight

        for rep in self.replicas.values():
            if rep.alive:
                rep.scheduler.start_window()
        t0 = time.perf_counter()
        steps = 0
        try:
            while not self.drained:
                _flight.beat(steps)
                if max_seconds is not None and (
                    time.perf_counter() - t0 >= max_seconds
                ):
                    break
                progressed = False
                if self.mode == "disaggregated":
                    progressed |= self._pump_prefill()
                    progressed |= self._pump_adopt()
                for i in self._decode_ids:
                    rep = self.replicas[i]
                    if rep.alive and not rep.drained:
                        progressed |= rep.tick()
                if not progressed:
                    inflight = sum(rep.scheduler.in_flight
                                   for rep in self.replicas.values()
                                   if rep.alive)
                    if inflight == 0:
                        queued = self.work_pending() + sum(
                            rep.scheduler.pending
                            for rep in self.replicas.values()
                            if rep.alive)
                        raise RuntimeError(
                            f"cluster stalled with {queued} request(s) "
                            "unplaceable on idle replicas (slot/pool "
                            "shortage everywhere)"
                        )
                steps += 1
                if steps > max_steps:
                    raise RuntimeError(
                        f"exceeded max_steps={max_steps} with work "
                        "still in flight")
                self._publish_gauges()
        finally:
            _flight.quiesce()
        for rep in self.replicas.values():
            if rep.alive:
                rep.scheduler.close_window()
        self._wall = time.perf_counter() - t0
        return self.collect_results()

    def collect_results(self) -> dict:
        """THIS router's finished streams, wherever they landed.
        Replica schedulers are cumulative and outlive any one router
        (the warm-replica bench pattern) — filtering by the ids this
        router assigned keeps a fresh router from returning a previous
        router's streams (review finding)."""
        out = dict(self.results)
        for rep in self.replicas.values():
            for rid, res in rep.scheduler.results.items():
                if rid in self._seen_ids:
                    out[rid] = res
        return out

    def preempt_request(self, request_id: str,
                        exclude_replica: bool = True) -> int:
        """Preempt one in-flight (or mid-fill) request and RE-ROUTE it
        (ISSUE 11): the holding replica's scheduler parks the partial
        stream as resume state ON the request
        (:meth:`~chainermn_tpu.serving.scheduler.Scheduler.preempt`
        with ``requeue=False``), and the router places it again — on a
        DIFFERENT replica when ``exclude_replica`` and one is alive
        (the load-shedding migration move), else back on the source.
        Resumed requests are ALWAYS submitted straight to a
        decode-capable replica's scheduler, never a disaggregated
        prefill queue: the prefill pump joins from the ORIGINAL prompt
        and ``admit_prefilled`` re-samples TTFT, both of which would
        break the resume contract (review finding). The arrival stamp
        survives the hop (keep_arrival, the unified rule) and stream
        determinism — greedy, or counter-key sampled under the
        ``Request.seed`` travelling on the same object — makes the
        resumed stream bit-identical wherever it lands. Returns the
        new replica id."""
        src = None
        for i, rep in self.replicas.items():
            if not rep.alive:
                continue
            slot = rep.scheduler.slot_of(request_id)
            if slot is not None:
                src = (i, slot)
                break
        if src is None:
            raise ValueError(
                f"request {request_id!r} is not in flight on any "
                "alive replica")
        src_id, slot = src
        ids = [i for i in self._decode_ids if i != src_id] \
            if exclude_replica else list(self._decode_ids)
        cands = self._alive(ids) or self._alive(self._decode_ids)
        if not cands:
            raise RuntimeError("no alive decode replica to resume on")
        # Residency filter BEFORE preempting (review finding: _choose
        # treats residency as a score, not a filter — a non-resident
        # winner would refuse the submit and strand the just-preempted
        # request). Failing here leaves the stream running in place.
        tenant = getattr(self.replicas[src_id].engine,
                         "tenant_of_slot", lambda s: None)(slot)
        cands = self._resident(self._expert_hosts(cands), tenant)
        req = self.replicas[src_id].scheduler.preempt(slot, requeue=False)
        # Same scoring as _route's placement, pending prefill queues
        # included in the load tiebreak (review finding: a diverging
        # re-implementation scored migrations differently).
        extra = {i: len(self._pqueues.get(i, ()))
                 for i in self.replicas}
        rep = self._choose(cands, req, extra)
        rep.scheduler.submit(req)
        rid = rep.replica_id
        if req.session_id is not None:
            # re-pin the session so later turns follow the migration
            self._sessions[req.session_id] = rid
        self._route_counts[rid] = self._route_counts.get(rid, 0) + 1
        self._event(
            "route", request=req.request_id, replica=rid,
            policy=self.policy, mode=self.mode, sticky=False,
            requeue=True, preempted_from=src_id,
            hit_blocks=rep.prefix_hit_blocks(
                req.prompt, tenant_id=req.tenant_id),
            load=rep.load(), kv_blocks_free=rep.kv_blocks_free(),
            **({"tenant": req.tenant_id}
               if req.tenant_id is not None else {}),
            **_journey.fields(req),
        )
        self._publish_gauges()
        return rid

    # ------------------------------------------------------------------
    # replica loss

    def fail_replica(self, replica_id: int) -> list[str]:
        """Take ``replica_id`` out of rotation and re-route everything
        it held — queued requests, pending handoffs, AND in-flight
        streams (their partial output is discarded; deterministic
        streams — greedy, or counter-key sampled under the seed riding
        each Request — mean the re-run is bit-identical, so the client
        sees latency, not corruption). Returns the re-routed request
        ids. Raises when the survivors cannot cover the dead
        replica's role."""
        rep = self.replicas.get(replica_id)
        if rep is None or not rep.alive:
            raise ValueError(f"replica {replica_id} unknown or already "
                             "failed")
        # Role coverage is validated BEFORE any mutation: raising
        # halfway would discard the just-evacuated requests and leave
        # the router half-updated for a caller that catches the error
        # (review finding).
        if replica_id in self._prefill_ids and not self._alive(
            [i for i in self._prefill_ids if i != replica_id]
        ) and self.mode == "disaggregated":
            raise RuntimeError(
                "last prefill replica failed — no survivor can cover "
                "its role")
        if replica_id in self._decode_ids and not self._alive(
            [i for i in self._decode_ids if i != replica_id]
        ):
            raise RuntimeError(
                "last decode replica failed — no survivor can cover "
                "its role")
        rep.alive = False
        orphans: list[Request] = []
        orphans.extend(self._pqueues.pop(replica_id, ()))
        if replica_id in self._prefill_ids:
            self._prefill_ids.remove(replica_id)
        for entry in self._pending.pop(replica_id, ()):
            # the payload targeted the dead pool; re-prefill elsewhere
            orphans.append(entry[0])
        if replica_id in self._decode_ids:
            self._decode_ids.remove(replica_id)
        orphans.extend(rep.scheduler.evacuate())
        for sid, pinned in list(self._sessions.items()):
            if pinned == replica_id:
                del self._sessions[sid]
        orphans.sort(key=lambda r: r._arrival)
        for req in orphans:
            self._route(req, requeue=True)
        return [r.request_id for r in orphans]

    # ------------------------------------------------------------------

    def summary(self) -> dict:
        """Cluster rollup: per-replica scheduler summaries plus the
        router's own accounting — route counts, transfer count/bytes,
        cluster-wide goodput (FINISHED generated tokens of THIS
        router's requests / router wall) and TTFT percentiles over the
        live replicas' windows. Counts come from the merged results,
        not event windows: dead replicas' stale windows describe
        discarded partial streams, and warm replicas may carry other
        routers' traffic (review finding) — neither belongs in this
        router's goodput."""
        from chainermn_tpu.observability.stats import nearest_rank

        ttfts = list(self._ttfts)
        merged = self.collect_results()
        requests = len(merged)
        tokens = sum(len(r["generated"]) for r in merged.values())
        per_replica = {}
        for i, rep in self.replicas.items():
            s = rep.summary()
            s["alive"] = rep.alive
            per_replica[i] = s
            if not rep.alive:
                continue
            for ev in rep.scheduler.event_window:
                if (ev.get("kind") == "serving"
                        and ev.get("phase") == "prefill"
                        and ev.get("ttft_s") is not None):
                    ttfts.append(float(ev["ttft_s"]))
        out = {
            "mode": self.mode,
            "policy": self.policy,
            "replicas": per_replica,
            "requests": requests,
            "generated_tokens": tokens,
            "routes": dict(sorted(self._route_counts.items())),
            "kv_transfer": {"transfers": self.transfers,
                            "bytes": self.transfer_bytes},
            "ttft_ms_p50": (round(nearest_rank(ttfts, 0.5) * 1e3, 4)
                            if ttfts else None),
            "ttft_ms_p99": (round(nearest_rank(ttfts, 0.99) * 1e3, 4)
                            if ttfts else None),
        }
        if self._wall is not None:
            out["wall_s"] = round(self._wall, 4)
            if self._wall > 0:
                out["goodput_tokens_per_sec"] = round(
                    tokens / self._wall, 2)
        if self.events_dropped:
            out["events_dropped"] = self.events_dropped
        return out
