"""Structured trace/event recorder — the collective-wire and step-time
telemetry layer (ISSUE 2 tentpole; docs/observability.md).

SURVEY.md section 5 records that the reference had no observability
beyond rank-0 ``print`` gating; this module measures the thing the
framework exists to optimize: bytes and time on the collective wire,
per step, per process. Three properties are load-bearing:

- **Host-side timestamps only.** Instrumentation wraps the *eager* API
  surface (communicator calls, trainer loop phases, host-plane object
  collectives); it never enters a jitted program, so an instrumented
  step lowers to EXACTLY the same HLO — zero added device-plane
  collectives (structural test: ``tests/test_trace.py``). Durations of
  eager device-plane calls are dispatch-to-return under JAX's async
  dispatch; set ``CHAINERMN_TPU_TRACE_SYNC=1`` (or ``enable(sync=True)``)
  to block on results for true wall durations — a measurement mode, not
  the default, because the sync serialises pipelining.
- **Near-zero overhead when off.** Every instrumentation site starts
  with ``trace.active()``; disabled, that is one global read and the
  site adds no timing, no allocation, no pickling.
- **One schema, versioned.** Every event is one JSON object with
  ``schema`` (:data:`TRACE_SCHEMA`), ``kind``, ``t`` (epoch seconds),
  ``pid``, ``rank``; kinds: ``meta``, ``collective``, ``step``, ``span``,
  ``dispatch`` (autotune provenance), ``straggler``, ``profile_start`` /
  ``profile_stop``, ``wire`` / ``overlap_config`` (ISSUE 3 per-bucket
  reduction telemetry), ``serving`` (ISSUE 4 queue_wait / prefill /
  decode_step / finish phases, plus the ISSUE 11 ``preempt`` phase),
  ``speculate`` (ISSUE 5 per-tick
  drafted/accepted counts), ``prefix_cache`` (ISSUE 7 per-admission
  prompt/hit/prefilled token counts + COW copies), ``prefill_chunk``
  (ISSUE 11 per-advanced-fill-row chunk telemetry from the mixed
  step).
  ``tools/trace_report.py`` summarizes a JSONL file;
  :func:`chrome_trace` converts to the ``chrome://tracing`` / Perfetto
  format.

Enable programmatically (:func:`enable`) or by environment:
``CHAINERMN_TPU_TRACE=<path.jsonl>`` turns the recorder on at first use
in any process — which is how child processes inherit tracing without
plumbing.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Iterable, Mapping, Optional

#: Version stamped into every event. Bump on any incompatible field
#: change; consumers (tools/trace_report.py) key on it.
TRACE_SCHEMA = 1

_ENV_PATH = "CHAINERMN_TPU_TRACE"
_ENV_SYNC = "CHAINERMN_TPU_TRACE_SYNC"

#: In-memory event cap per recorder — a runaway loop must not eat the
#: host; overflow increments ``dropped`` (file writes continue; the
#: metrics plane exports the count live as ``trace_dropped_events``).
MAX_BUFFERED_EVENTS = 200_000

# The nearest-rank percentile rule, shared with the metrics histograms
# (ISSUE 6 satellite: one owner in observability/stats.py). This module
# is ALSO loaded by file path from tools/trace_report.py with no package
# context (to avoid paying a jax import in a report tool) — load stats
# the same way there.
if __package__:
    from chainermn_tpu.observability.stats import jain_index, nearest_rank
else:  # pragma: no cover - exercised via tools/trace_report.py
    import importlib.util as _ilu

    _spec = _ilu.spec_from_file_location(
        "_obs_stats",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "stats.py"),
    )
    _mod = _ilu.module_from_spec(_spec)
    _spec.loader.exec_module(_mod)
    nearest_rank = _mod.nearest_rank
    jain_index = _mod.jain_index

#: Event sinks (ISSUE 6): callables ``sink(event_dict)`` invoked for
#: every event ANY recorder emits — the metrics tap and the flight ring
#: register here, so every already-instrumented site feeds the live
#: plane with zero new call sites. Sinks fire only while a recorder is
#: active; a raising sink is dropped from that event, never propagated
#: into an instrumentation site.
_sinks: list = []


def add_sink(fn) -> None:
    """Register an event sink (idempotent)."""
    if fn not in _sinks:
        _sinks.append(fn)


def remove_sink(fn) -> None:
    try:
        _sinks.remove(fn)
    except ValueError:
        pass


def _process_rank() -> int:
    """Host-plane rank WITHOUT triggering jax backend discovery (the
    recorder must be usable in processes that never import jax — the
    bench parent — and before backend init): native-TCP env first, then
    the jax distributed client state if someone initialised it."""
    r = os.environ.get("CHAINERMN_TPU_RANK")
    if r is not None:
        try:
            return int(r)
        except ValueError:
            pass
    try:
        from jax._src import distributed

        state = distributed.global_state
        if state.client is not None:
            return int(state.process_id)
    except Exception:
        pass
    return 0


class Recorder:
    """Append-only structured event stream, optionally write-through to
    a JSONL file (append mode, line-buffered: a crash loses at most the
    current line). Thread-safe: the trainer's prefetch generator and the
    main loop may both record."""

    def __init__(
        self,
        path: Optional[str] = None,
        *,
        sync: bool = False,
        mode: str = "a",
        meta: Optional[Mapping[str, Any]] = None,
    ) -> None:
        self.path = path
        self.sync = sync
        self.events: list[dict] = []
        self.dropped = 0
        #: epoch seconds of the most recent event — the exporter's
        #: ``/healthz`` last-event-age signal.
        self.last_event_t: float = 0.0
        self._lock = threading.Lock()
        self._rank = _process_rank()
        self._file = None
        if path:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            self._file = open(path, mode, buffering=1)
        self.event(
            "meta",
            started_at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            sync=bool(sync),
            **dict(meta or {}),
        )

    # ------------------------------------------------------------------

    def event(self, kind: str, **fields: Any) -> dict:
        """Record one event; returns the event dict (callers may inspect
        it in tests). Non-JSON-serialisable field values are repr()'d
        rather than ever raising out of an instrumentation site."""
        ev = {
            "schema": TRACE_SCHEMA,
            "kind": kind,
            "t": round(time.time(), 6),
            # Monotonic sibling stamp (ISSUE 17 satellite): ``t`` is
            # epoch (comparable across processes once clock-synced but
            # steppable by NTP/admin), ``t_mono`` is perf_counter
            # (process-local, step-free) — same-process ordering in
            # the journey merger reads THIS, never the wall clock.
            "t_mono": round(time.perf_counter(), 9),
            "pid": os.getpid(),
            "rank": self._rank,
            **fields,
        }
        with self._lock:
            if len(self.events) < MAX_BUFFERED_EVENTS:
                self.events.append(ev)
            else:
                self.dropped += 1
            if self._file is not None:
                try:
                    line = json.dumps(ev)
                except (TypeError, ValueError):
                    ev = {k: (v if _jsonable(v) else repr(v))
                          for k, v in ev.items()}
                    line = json.dumps(ev)
                try:
                    self._file.write(line + "\n")
                except (OSError, ValueError):
                    # full disk / closed file must never break training
                    self._file = None
        self.last_event_t = ev["t"]
        # Sinks OUTSIDE the lock: a sink may inspect this recorder (the
        # metrics health hook reads .dropped) without deadlocking, and a
        # slow sink must not serialise other recording threads.
        for sink in tuple(_sinks):
            try:
                sink(ev)
            except Exception:
                pass
        return ev

    def collective(
        self,
        op: str,
        *,
        nbytes: Optional[int] = None,
        dur_s: Optional[float] = None,
        plane: str = "device",
        wire_dtype: Optional[str] = None,
        provenance: Optional[dict] = None,
        **extra: Any,
    ) -> dict:
        """One collective-wire counter event. ``provenance`` is the
        autotune decision record behind an ``'auto'``-resolved
        configuration (name/winner/source/key), attached so every auto
        collective in a trace names why it took the path it took."""
        fields: dict = {"op": op, "plane": plane}
        if nbytes is not None:
            fields["nbytes"] = int(nbytes)
        if dur_s is not None:
            fields["dur_s"] = round(float(dur_s), 9)
        if wire_dtype is not None:
            fields["wire_dtype"] = str(wire_dtype)
        if provenance is not None:
            fields["provenance"] = provenance
        fields.update(extra)
        return self.event("collective", **fields)

    def flush(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.flush()
                except (OSError, ValueError):
                    pass

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    if self.dropped:
                        self._file.write(json.dumps({
                            "schema": TRACE_SCHEMA, "kind": "meta",
                            "t": round(time.time(), 6),
                            "t_mono": round(time.perf_counter(), 9),
                            "pid": os.getpid(), "rank": self._rank,
                            "dropped_events": self.dropped,
                        }) + "\n")
                    self._file.close()
                except (OSError, ValueError):
                    pass
                self._file = None


def _jsonable(v: Any) -> bool:
    try:
        json.dumps(v)
        return True
    except (TypeError, ValueError):
        return False


# ----------------------------------------------------------------------
# Global recorder
# ----------------------------------------------------------------------

_active: Optional[Recorder] = None
_env_checked = False


def enable(
    path: Optional[str] = None,
    *,
    sync: Optional[bool] = None,
    mode: str = "a",
    meta: Optional[Mapping[str, Any]] = None,
) -> Recorder:
    """Install (and return) the process-global recorder. ``path=None``
    keeps events in memory only (tests). Replaces any prior recorder
    (closing its file)."""
    global _active, _env_checked
    if sync is None:
        sync = bool(os.environ.get(_ENV_SYNC))
    # Construct FIRST: if the path is unwritable this raises with the
    # previous recorder still installed and functional — never leave a
    # closed (file-less) recorder as the active one, silently buffering
    # events nobody will ever see.
    new = Recorder(path, sync=sync, mode=mode, meta=meta)
    if _active is not None:
        _active.close()
    _env_checked = True
    _active = new
    return _active


def disable() -> None:
    """Tear down the global recorder (file closed; events discarded)."""
    global _active
    if _active is not None:
        _active.close()
        _active = None


def active() -> Optional[Recorder]:
    """The global recorder, or None when tracing is off. First call
    honours ``CHAINERMN_TPU_TRACE=<path>`` — the env contract that lets
    subprocesses (bench children, the capture script's stages) inherit
    tracing."""
    global _active, _env_checked
    if _active is None and not _env_checked:
        _env_checked = True
        path = os.environ.get(_ENV_PATH)
        if path:
            try:
                enable(path)
            except OSError:
                pass  # unwritable path must not break the workload
    return _active


@contextlib.contextmanager
def span(name: str, kind: str = "span", **fields: Any):
    """Timed span, on two clocks at once: always a
    ``jax.profiler.TraceAnnotation`` (so it lands in a live profiler
    session's host plane, beside the device's ops), and with a recorder
    on also an event (recorded at exit, with ``dur_s`` and ``ok``).
    Yields a mutable dict merged into the event — callers may attach
    results discovered inside the block. With no recorder and no
    profiler session it costs the annotation's no-op."""
    # Imported here, not at the top: tools/trace_report.py loads this
    # file with no package and no jax, and never opens a span.
    from chainermn_tpu.utils.observability import annotate

    rec = active()
    with annotate(name):
        if rec is None:
            yield {}
            return
        extra: dict = {}
        t0 = time.perf_counter()
        ok = False
        try:
            yield extra
            ok = True
        finally:
            rec.event(kind, name=name,
                      dur_s=round(time.perf_counter() - t0, 9),
                      ok=ok, **{**fields, **extra})


def sync_point(x: Any) -> Any:
    """Block on ``x`` when the recorder is in sync mode (true wall
    durations for eager device-plane calls); identity otherwise."""
    rec = _active
    if rec is not None and rec.sync:
        import jax

        jax.block_until_ready(x)
    return x


def tree_nbytes(tree: Any) -> Optional[int]:
    """Total payload bytes of an array pytree (None when unknowable) —
    the byte counter behind the wire events. Never raises."""
    try:
        import jax

        total = 0
        for leaf in jax.tree.leaves(tree):
            nb = getattr(leaf, "nbytes", None)
            if nb is None:
                import numpy as np

                nb = np.asarray(leaf).nbytes
            total += int(nb)
        return total
    except Exception:
        return None


def obj_nbytes(obj: Any) -> Optional[int]:
    """Pickled size of a host-plane object payload. Only called when
    tracing is active (it costs one pickle — host-plane objects are
    metadata-sized by convention, never gradients)."""
    try:
        import pickle

        return len(pickle.dumps(obj, protocol=4))
    except Exception:
        return None


# ----------------------------------------------------------------------
# Exports
# ----------------------------------------------------------------------

def read_jsonl(path: str) -> list[dict]:
    """Parse a trace JSONL file, skipping unparseable lines (a crashed
    writer may leave a torn tail)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return out


def summarize_overlap(events: Iterable[Mapping[str, Any]]) -> Optional[dict]:
    """Comm/compute-overlap rollup from ``wire`` + ``overlap_config``
    events (the consumer side of the per-bucket wire events; one owner
    shared by ``tools/trace_report.py`` and bench).

    Two wire-event flavours feed it:

    - trace-time layout events (``reduce_tree``'s in-jit bucketed
      schedules, one event per bucket per stage; no ``dur_s``): counted
      per schedule under ``schedules`` — buckets (a bucket's first
      stage, ``stage_index`` 0, counts it and its ``overlapped`` flag)
      and the bytes its stages carry — what the compiled program
      COMMITTED to;
    - measured events (the eager ``OverlappedBucketReducer``; ``dur_s``
      = dispatch->ready, ``blocked_s`` = wait actually paid at
      collect): aggregated into comm time total vs comm time hidden
      behind compute, and the ``hidden_fraction`` between them.

    Returns None when the trace carries none (section omitted)."""
    configs: list[dict] = []
    layout: dict = {}
    n_measured = 0
    comm_s = 0.0
    blocked_s = 0.0
    for ev in events:
        kind = ev.get("kind")
        if kind == "overlap_config":
            configs.append({
                k: ev.get(k)
                for k in ("double_buffering", "staleness", "schedule",
                          "donate")
            })
        elif kind == "wire":
            dur = ev.get("dur_s")
            if dur is None:
                key = str(ev.get("schedule", "?"))
                row = layout.setdefault(
                    key, {"buckets": 0, "nbytes": 0, "overlapped": 0}
                )
                if not ev.get("stage_index"):
                    row["buckets"] += 1
                    row["overlapped"] += 1 if ev.get("overlapped") else 0
                row["nbytes"] += int(ev.get("nbytes") or 0)
            else:
                n_measured += 1
                comm_s += float(dur)
                # None (absent) falls back to dur; an explicit 0.0 is a
                # FULLY-HIDDEN bucket and must count as such.
                b = ev.get("blocked_s")
                blocked_s += float(dur if b is None else b)
    if not configs and not layout and not n_measured:
        return None
    out: dict = {}
    if configs:
        out["config"] = configs
    if layout:
        out["schedules"] = {
            k: layout[k] for k in sorted(layout)
        }
    if n_measured:
        hidden_s = max(0.0, comm_s - blocked_s)
        out["measured"] = {
            "n": n_measured,
            "comm_ms_total": round(comm_s * 1e3, 4),
            "comm_ms_blocked": round(blocked_s * 1e3, 4),
            "comm_ms_hidden": round(hidden_s * 1e3, 4),
            "hidden_fraction": (round(hidden_s / comm_s, 4)
                                if comm_s > 0 else 0.0),
        }
    return out


def summarize_serving(events: Iterable[Mapping[str, Any]]) -> Optional[dict]:
    """Serving rollup from ``serving`` (+ ``speculate``) events (ISSUE
    4/5: the consumer side of the scheduler's per-phase events; one
    owner shared by ``tools/trace_report.py`` and bench's ``serving``
    phase).

    Definitions (deterministic — the report contract pins them):

    - ``generated_tokens`` = one per prefill (its sampled first token)
      plus each ``decode_step``'s ``tokens`` field;
    - ``tokens_per_sec`` = generated tokens / (prefill + decode step
      durations) — device-busy time, not wall (queue idle gaps are the
      scheduler's property, not the engine's);
    - ``token_ms_p50``/``p99`` = nearest-rank percentiles (ceil(q*n))
      over ``decode_step`` durations — under plain decode each active
      request gains one token per step, so the step duration IS its
      per-token latency (under speculation it is the TICK latency for
      1..K+1 tokens per request — divide by ``generated_tokens /
      decode_steps`` for an amortized per-token figure);
    - ``ttft_ms_p50``/``p99`` = nearest-rank percentiles over the
      prefill events' ``ttft_s`` (submit → first token; None for
      traces predating the field; a preemption-resume's re-prefill
      carries no ``ttft_s`` and never re-enters the percentile);
    - ``tpot_ms_p50``/``p99`` (ISSUE 11 satellite) = nearest-rank
      percentiles over PER-REQUEST mean inter-token latency — the
      finish events' ``tpot_ms`` field (first token → finish over
      ``generated - 1`` intervals; preemption gaps included), falling
      back to ``(dur_s - ttft_s) / (generated - 1)`` for traces
      predating the field;
    - ``slo_attainment`` (present only when some finished request
      carried TTFT/TPOT targets, ISSUE 11) = fraction of
      target-bearing finished requests whose every stated target was
      met (the finish events' ``slo_ttft_ok``/``slo_tpot_ok``
      verdicts), with ``slo_requests`` the denominator;
    - ``preemptions`` (present only when > 0, ISSUE 11) = count of
      ``phase='preempt'`` events;
    - ``chunked_prefill`` (present only when ``prefill_chunk`` events
      exist, ISSUE 11) = chunk count and prompt tokens written through
      the mixed step's fill rows;
    - ``occupancy_mean`` = mean of ``n_active / n_slots`` over decode
      steps;
    - ``speculation`` (present only when ``speculate`` events exist) =
      drafted/accepted token totals, ``accept_rate`` = accepted /
      drafted, and ``accept_len_hist`` — accept-length counts keyed by
      stringified length (JSON-stable), the trace_report histogram;
    - ``prefix_cache`` (present only when ``prefix_cache`` events
      exist, ISSUE 7) = admission lookups/hits, ``hit_rate`` = hits /
      lookups, prompt vs prefilled vs cache-served token totals
      (``prefilled_tokens`` is the MEASURED prefill work — the bench
      acceptance reads it, not prose), ``hit_token_rate`` = hit tokens
      / prompt tokens, and total ``cow_blocks`` copied;
    - ``tenants`` (present when any prefill/finish event exists,
      ISSUE 14) = per-tenant rollup — requests, generated tokens,
      TTFT/TPOT p50/p99, SLO attainment where targets were stated —
      keyed by the events' ``tenant`` field with a ``'default'``
      fallback, so pre-tenant traces keep parsing (they roll up as one
      ``'default'`` tenant); ``tenant_fairness_jain`` = Jain's index
      over the per-tenant generated-token totals
      (:func:`~chainermn_tpu.observability.stats.jain_index` — 1.0 for
      a single tenant by construction).

    Returns None when the trace carries no serving events."""
    queue_waits: list[float] = []
    prefills: list[float] = []
    ttfts: list[float] = []
    ttft_by_req: dict = {}
    tpots: list[float] = []
    steps: list[float] = []
    occupancy: list[float] = []
    step_tokens = 0
    finishes = 0
    finish_evs: list = []
    preemptions = 0
    chunks = chunk_tokens = 0
    spec_ticks = 0
    spec_drafted = 0
    spec_accepted = 0
    accept_hist: dict = {}
    px_lookups = px_hits = 0
    px_hit_tokens = px_prompt_tokens = px_prefill_tokens = px_cow = 0
    tenant_ttfts: dict = {}
    tenant_fin: dict = {}
    for ev in events:
        kind = ev.get("kind")
        if kind == "prefill_chunk":
            chunks += 1
            chunk_tokens += int(ev.get("tokens") or 0)
            continue
        if kind == "speculate":
            spec_ticks += 1
            spec_drafted += int(ev.get("drafted") or 0)
            spec_accepted += int(ev.get("accepted") or 0)
            for a in (ev.get("accept_lens") or ()):
                k = str(int(a))
                accept_hist[k] = accept_hist.get(k, 0) + 1
            continue
        if kind == "prefix_cache":
            px_lookups += 1
            if int(ev.get("hit_blocks") or 0) > 0:
                px_hits += 1
            px_hit_tokens += int(ev.get("hit_tokens") or 0)
            px_prompt_tokens += int(ev.get("prompt_tokens") or 0)
            px_prefill_tokens += int(ev.get("prefill_tokens") or 0)
            px_cow += int(ev.get("cow_blocks") or 0)
            continue
        if kind != "serving":
            continue
        phase = ev.get("phase")
        dur = float(ev.get("dur_s") or 0.0)
        if phase == "queue_wait":
            queue_waits.append(dur)
        elif phase == "prefill":
            prefills.append(dur)
            if ev.get("ttft_s") is not None:
                ttfts.append(float(ev["ttft_s"]))
                tenant_ttfts.setdefault(
                    ev.get("tenant") or "default", []
                ).append(float(ev["ttft_s"]))
                rid = ev.get("request")
                if rid is not None and rid not in ttft_by_req:
                    ttft_by_req[rid] = float(ev["ttft_s"])
        elif phase == "decode_step":
            steps.append(dur)
            step_tokens += int(ev.get("tokens") or 0)
            n_slots = ev.get("n_slots")
            if n_slots:
                occupancy.append(float(ev.get("n_active") or 0)
                                 / float(n_slots))
        elif phase == "preempt":
            preemptions += 1
        elif phase == "finish":
            finishes += 1
            finish_evs.append(ev)
    # Per-request TPOT: the finish event's own tpot_ms when present
    # (preferred — the scheduler's first-token clock survives
    # preemption), else derived from dur - ttft over generated - 1.
    slo_total = slo_ok = 0
    for ev in finish_evs:
        tpot = ev.get("tpot_ms")
        if tpot is None:
            gen = int(ev.get("generated") or 0)
            rid = ev.get("request")
            ttft = ttft_by_req.get(rid)
            if gen > 1 and ttft is not None and ev.get("dur_s"):
                tpot = (float(ev["dur_s"]) - ttft) / (gen - 1) * 1e3
        if tpot is not None:
            tpots.append(float(tpot))
        verdicts = [ev.get(k) for k in ("slo_ttft_ok", "slo_tpot_ok")
                    if ev.get(k) is not None]
        if verdicts:
            slo_total += 1
            if all(verdicts):
                slo_ok += 1
        # Per-tenant accumulation (ISSUE 14): the 'default' fallback
        # keeps pre-tenant traces rolling up as one tenant.
        tf = tenant_fin.setdefault(
            ev.get("tenant") or "default",
            {"requests": 0, "tokens": 0, "tpots": [],
             "slo_total": 0, "slo_ok": 0},
        )
        tf["requests"] += 1
        tf["tokens"] += int(ev.get("generated") or 0)
        if tpot is not None:
            tf["tpots"].append(float(tpot))
        if verdicts:
            tf["slo_total"] += 1
            if all(verdicts):
                tf["slo_ok"] += 1
    if not (queue_waits or prefills or steps or finishes or spec_ticks
            or px_lookups or preemptions or chunks):
        return None

    pct = nearest_rank  # the shared ceil(q*n) rule (observability.stats)

    tokens = step_tokens + len(prefills)
    busy_s = sum(prefills) + sum(steps)
    out: dict = {
        "requests": finishes,
        "prefills": len(prefills),
        "generated_tokens": tokens,
        "decode_steps": len(steps),
        "queue_wait_ms_mean": (
            round(sum(queue_waits) / len(queue_waits) * 1e3, 4)
            if queue_waits else None),
        "prefill_ms_mean": (round(sum(prefills) / len(prefills) * 1e3, 4)
                            if prefills else None),
        "token_ms_p50": (round(pct(steps, 0.5) * 1e3, 4)
                         if steps else None),
        "token_ms_p99": (round(pct(steps, 0.99) * 1e3, 4)
                         if steps else None),
        "ttft_ms_p50": (round(pct(ttfts, 0.5) * 1e3, 4)
                        if ttfts else None),
        "ttft_ms_p99": (round(pct(ttfts, 0.99) * 1e3, 4)
                        if ttfts else None),
        "tpot_ms_p50": (round(pct(tpots, 0.5), 4) if tpots else None),
        "tpot_ms_p99": (round(pct(tpots, 0.99), 4) if tpots else None),
        "occupancy_mean": (round(sum(occupancy) / len(occupancy), 4)
                           if occupancy else None),
        "tokens_per_sec": (round(tokens / busy_s, 2) if busy_s > 0
                           else None),
    }
    if slo_total:
        out["slo_requests"] = slo_total
        out["slo_attainment"] = round(slo_ok / slo_total, 4)
    if preemptions:
        out["preemptions"] = preemptions
    if chunks:
        out["chunked_prefill"] = {"chunks": chunks,
                                  "chunk_tokens": chunk_tokens}
    if spec_ticks:
        out["speculation"] = {
            "ticks": spec_ticks,
            "drafted": spec_drafted,
            "accepted": spec_accepted,
            "accept_rate": (round(spec_accepted / spec_drafted, 4)
                            if spec_drafted else None),
            "accept_len_hist": {
                k: accept_hist[k]
                for k in sorted(accept_hist, key=int)
            },
        }
    if px_lookups:
        out["prefix_cache"] = {
            "lookups": px_lookups,
            "hits": px_hits,
            "hit_rate": round(px_hits / px_lookups, 4),
            "prompt_tokens": px_prompt_tokens,
            "hit_tokens": px_hit_tokens,
            "prefilled_tokens": px_prefill_tokens,
            "hit_token_rate": (round(px_hit_tokens / px_prompt_tokens, 4)
                               if px_prompt_tokens else None),
            "cow_blocks": px_cow,
        }
    if tenant_fin or tenant_ttfts:
        tenants: dict = {}
        for t in sorted(set(tenant_fin) | set(tenant_ttfts)):
            tf = tenant_fin.get(t, {"requests": 0, "tokens": 0,
                                    "tpots": [], "slo_total": 0,
                                    "slo_ok": 0})
            tts = tenant_ttfts.get(t, [])
            row: dict = {
                "requests": tf["requests"],
                "generated_tokens": tf["tokens"],
                "ttft_ms_p50": (round(pct(tts, 0.5) * 1e3, 4)
                                if tts else None),
                "ttft_ms_p99": (round(pct(tts, 0.99) * 1e3, 4)
                                if tts else None),
                "tpot_ms_p50": (round(pct(tf["tpots"], 0.5), 4)
                                if tf["tpots"] else None),
                "tpot_ms_p99": (round(pct(tf["tpots"], 0.99), 4)
                                if tf["tpots"] else None),
            }
            if tf["slo_total"]:
                row["slo_requests"] = tf["slo_total"]
                row["slo_attainment"] = round(
                    tf["slo_ok"] / tf["slo_total"], 4)
            tenants[t] = row
        out["tenants"] = tenants
        out["tenant_fairness_jain"] = round(jain_index(
            [tenants[t]["generated_tokens"] for t in tenants]), 4)
    return out


def chrome_trace(events: Iterable[Mapping[str, Any]]) -> dict:
    """Convert trace events to the Chrome trace-event format (load in
    ``chrome://tracing`` or https://ui.perfetto.dev). Events with a
    duration become complete ('X') slices; instants become 'i' marks.
    pid = process rank, tid = event kind — one track per subsystem.
    Journey-linked spans (ISSUE 17) whose ``parent`` span lives on a
    DIFFERENT rank additionally emit a flow-arrow pair (``ph: s``/``f``,
    ``bp: e``) so cross-rank handoffs render as arrows between pids."""
    out = []
    # span id -> (end ts us, rank, kind) for the flow pass; same-rank
    # parent links stay implicit (one pid track already reads in order).
    span_ix: dict = {}
    flows: list = []
    for ev in events:
        kind = ev.get("kind", "?")
        if kind == "meta":
            continue
        dur = ev.get("dur_s")
        name = ev.get("op") or ev.get("name") or kind
        ts = float(ev.get("t", 0.0)) * 1e6
        args = {k: v for k, v in ev.items()
                if k not in ("kind", "t", "t_mono", "pid", "rank",
                             "schema")}
        base = {
            "name": str(name),
            "cat": kind,
            "pid": ev.get("rank", 0),
            "tid": kind,
            "args": args,
        }
        if dur:
            # 't' stamps event END for spans recorded at exit; chrome
            # wants the start.
            start = ts - float(dur) * 1e6
            out.append({**base, "ph": "X", "ts": start,
                        "dur": float(dur) * 1e6})
        else:
            start = ts
            out.append({**base, "ph": "i", "ts": ts, "s": "p"})
        span = ev.get("span")
        if span is not None:
            span_ix[span] = (ts, ev.get("rank", 0), kind)
            parent = ev.get("parent")
            if parent is not None:
                flows.append((parent, start, ev.get("rank", 0), kind,
                              str(ev.get("journey", span))))
    for n, (parent, start, rank, kind, journey) in enumerate(flows):
        src = span_ix.get(parent)
        if src is None or src[1] == rank:
            continue  # orphan link or same-rank hop — no arrow
        p_ts, p_rank, p_kind = src
        flow = {"name": journey, "cat": "journey", "id": n + 1}
        out.append({**flow, "ph": "s", "ts": p_ts, "pid": p_rank,
                    "tid": p_kind})
        out.append({**flow, "ph": "f", "bp": "e", "ts": max(start, p_ts),
                    "pid": rank, "tid": kind})
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def write_chrome_trace(jsonl_path: str, out_path: str) -> int:
    """JSONL trace file -> Chrome trace JSON; returns the event count."""
    trace = chrome_trace(read_jsonl(jsonl_path))
    with open(out_path, "w") as f:
        json.dump(trace, f)
    return len(trace["traceEvents"])
