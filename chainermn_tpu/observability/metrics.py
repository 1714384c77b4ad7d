"""Process-local live metrics registry: Counter / Gauge / Histogram
(ISSUE 6 tentpole; docs/observability.md "Live metrics").

PR 2's trace subsystem is post-hoc — a JSONL file read after the run.
This module is the LIVE half: a registry of named series a running
process updates in place and :mod:`~chainermn_tpu.observability.exporter`
serves over HTTP while the workload runs. Two feeding paths:

- **Recorder tap** (:func:`install_tap`): one sink registered on the
  trace :class:`~chainermn_tpu.observability.trace.Recorder` forwards
  every emitted event into metric updates, so every already-
  instrumented site (``collective`` wire counters, ``step`` timelines,
  ``serving``/``speculate`` phases, ``straggler`` reports) populates
  metrics with ZERO new call sites and zero HLO change (the
  instrumentation stays host-side timestamps only — structural test in
  tests/test_metrics.py, same pattern as tests/test_trace.py).
- **Direct gauges** at host planes that have state but no events:
  scheduler queue depth / in-flight count, engine slot occupancy,
  KV-block pool free/leased, trainer step counter. Those sites guard on
  :func:`active_registry` — one global read when the plane is off, the
  trace module's overhead discipline.

Histograms use FIXED log-spaced buckets (:func:`log_buckets`), so
streaming p50/p90/p99 come from cumulative bucket counts — no samples
are retained; the quantile rule is the shared nearest-rank
``ceil(q*n)`` (:mod:`~chainermn_tpu.observability.stats`), with the
bucket UPPER BOUND reported (a conservative <= one-bucket-width
overestimate; the +Inf bucket reports ``inf``).

Like the recorder, the registry is process-local and thread-safe
(exporter scrape thread vs workload threads). No new dependencies:
stdlib only.
"""

from __future__ import annotations

import bisect
import collections
import math
import os
import re
import threading
import time
from typing import Any, Callable, Dict, Iterable, Mapping, Optional, Tuple

# Like trace.py, this module is ALSO loaded by file path from
# tools/metrics_dump.py with no package context (the tool must not pay
# for ``import chainermn_tpu`` -> jax just to format a scrape) — load
# the stdlib-only siblings the same way there.
if __package__:
    from chainermn_tpu.observability import trace as _trace
    from chainermn_tpu.observability.stats import nearest_rank_index
else:  # pragma: no cover - exercised via tools/metrics_dump.py
    import importlib.util as _ilu

    def _load_sibling(fname, modname):
        spec = _ilu.spec_from_file_location(
            modname,
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         fname),
        )
        mod = _ilu.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    _trace = _load_sibling("trace.py", "_obs_trace")
    nearest_rank_index = _load_sibling("stats.py", "_obs_stats")\
        .nearest_rank_index

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")

#: quantiles every histogram snapshot reports — the serving SLO set.
SNAPSHOT_QUANTILES = (0.5, 0.9, 0.99)


def log_buckets(lo: float = 1e-5, hi: float = 100.0,
                per_decade: int = 4) -> Tuple[float, ...]:
    """Fixed log-spaced bucket upper bounds covering ``[lo, hi]`` —
    the default latency ladder (10 us .. 100 s at 4 buckets/decade,
    ~29 bounds). Fixed by construction: every process cuts the same
    ladder, so cross-rank merges never need bucket alignment."""
    if not (0 < lo < hi) or per_decade < 1:
        raise ValueError(f"need 0 < lo < hi and per_decade >= 1, got "
                         f"lo={lo} hi={hi} per_decade={per_decade}")
    n = int(math.ceil(math.log10(hi / lo) * per_decade))
    return tuple(lo * 10 ** (i / per_decade) for i in range(n + 1))


DEFAULT_TIME_BUCKETS = log_buckets()


def _labels_key(labels: Mapping[str, str]) -> Tuple[Tuple[str, str], ...]:
    for k in labels:
        if not _LABEL_RE.match(k):
            raise ValueError(f"invalid label name {k!r}")
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _escape_label_value(v: str) -> str:
    return v.replace("\\", r"\\").replace("\n", r"\n").replace('"', r'\"')


def _render_labels(key: Tuple[Tuple[str, str], ...],
                   extra: Optional[Mapping[str, str]] = None) -> str:
    pairs = list(key)
    if extra:
        pairs = sorted(dict(list(key) + list(extra.items())).items())
    if not pairs:
        return ""
    body = ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in pairs)
    return "{" + body + "}"


class _Family:
    """One named metric family; children are keyed by label sets."""

    kind = "untyped"

    def __init__(self, name: str, help_: str, lock: threading.Lock) -> None:
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        self.name = name
        self.help = help_
        self._lock = lock
        self._children: Dict[Tuple[Tuple[str, str], ...], Any] = {}


class Counter(_Family):
    """Monotone total."""

    kind = "counter"

    def inc(self, value: float = 1.0, **labels: str) -> None:
        if value < 0:
            raise ValueError(f"counter {self.name} cannot decrease "
                             f"(inc {value})")
        key = _labels_key(labels)
        with self._lock:
            self._children[key] = self._children.get(key, 0.0) + value

    def value(self, **labels: str) -> float:
        with self._lock:
            return float(self._children.get(_labels_key(labels), 0.0))


class Gauge(_Family):
    kind = "gauge"

    def set(self, value: float, **labels: str) -> None:
        key = _labels_key(labels)
        with self._lock:
            self._children[key] = float(value)

    def inc(self, value: float = 1.0, **labels: str) -> None:
        key = _labels_key(labels)
        with self._lock:
            self._children[key] = self._children.get(key, 0.0) + value

    def value(self, **labels: str) -> Optional[float]:
        with self._lock:
            v = self._children.get(_labels_key(labels))
            return None if v is None else float(v)

    def clear(self) -> None:
        """Drop every labelled child: for a gauge whose label set is
        replaced as a whole (a stale label must not outlive its value)."""
        with self._lock:
            self._children.clear()


class Histogram(_Family):
    """Fixed-bucket streaming histogram: per child, cumulative-ready
    counts per bucket plus sum/count — p50/p90/p99 without retaining
    samples (module docstring)."""

    kind = "histogram"

    def __init__(self, name: str, help_: str, lock: threading.Lock,
                 buckets: Iterable[float] = DEFAULT_TIME_BUCKETS) -> None:
        super().__init__(name, help_, lock)
        bs = tuple(sorted(float(b) for b in buckets))
        if not bs or any(b <= 0 for b in bs) or len(set(bs)) != len(bs):
            raise ValueError(f"buckets must be positive, unique, "
                             f"non-empty; got {buckets}")
        self.buckets = bs  # upper bounds; +Inf bucket is implicit

    def _child(self, key):
        st = self._children.get(key)
        if st is None:
            st = {"counts": [0] * (len(self.buckets) + 1),
                  "sum": 0.0, "n": 0}
            self._children[key] = st
        return st

    def observe(self, value: float, **labels: str) -> None:
        value = float(value)
        idx = bisect.bisect_left(self.buckets, value)  # first ub >= value
        key = _labels_key(labels)
        with self._lock:
            st = self._child(key)
            st["counts"][idx] += 1
            st["sum"] += value
            st["n"] += 1

    def count(self, **labels: str) -> int:
        with self._lock:
            st = self._children.get(_labels_key(labels))
            return int(st["n"]) if st else 0

    def quantile(self, q: float, **labels: str) -> Optional[float]:
        """Nearest-rank quantile over the bucket counts: the bucket
        UPPER BOUND holding 1-based rank ``ceil(q*n)`` (the shared
        stats rule); ``inf`` when the rank falls in the overflow
        bucket; None with no observations."""
        with self._lock:
            st = self._children.get(_labels_key(labels))
            if not st or not st["n"]:
                return None
            rank = nearest_rank_index(st["n"], q) + 1  # 1-based
            cum = 0
            for i, c in enumerate(st["counts"]):
                cum += c
                if cum >= rank:
                    return (self.buckets[i] if i < len(self.buckets)
                            else math.inf)
        return math.inf  # unreachable; counts always sum to n


class MetricsRegistry:
    """Name -> family map with get-or-create accessors (an existing
    family is returned as-is; a kind mismatch raises — two subsystems
    silently sharing one name as different types is a bug)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Family] = {}
        self._collect_hooks: list[Callable[["MetricsRegistry"], None]] = []

    def _get_or_create(self, cls, name: str, help_: str, **kw) -> _Family:
        with self._lock:
            fam = self._metrics.get(name)
            if fam is not None:
                if not isinstance(fam, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{fam.kind}, requested {cls.kind}"
                    )
                return fam
            fam = cls(name, help_, self._lock, **kw)
            self._metrics[name] = fam
            return fam

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get_or_create(Counter, name, help_)

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help_)

    def histogram(self, name: str, help_: str = "",
                  buckets: Iterable[float] = DEFAULT_TIME_BUCKETS
                  ) -> Histogram:
        return self._get_or_create(Histogram, name, help_, buckets=buckets)

    def register_collect(self, fn: Callable[["MetricsRegistry"], None]
                         ) -> None:
        """Hook run before every snapshot/exposition — how scrape-time
        values (recorder drop counts, pool sizes) stay live without a
        per-event write. Hooks must never raise out of a scrape."""
        if fn not in self._collect_hooks:
            self._collect_hooks.append(fn)

    def _run_collect(self) -> None:
        for fn in tuple(self._collect_hooks):
            try:
                fn(self)
            except Exception:
                pass

    # ------------------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-able view of every family: counters/gauges as values,
        histograms as count/sum/cumulative buckets + the SLO quantiles.
        This is the peer-merge payload (exporter) and what the
        benchmark's loop reads its counters from."""
        self._run_collect()
        out: dict = {}
        with self._lock:
            for name, fam in sorted(self._metrics.items()):
                rows = []
                if isinstance(fam, Histogram):
                    for key, st in sorted(fam._children.items()):
                        cum, buckets = 0, []
                        for i, c in enumerate(st["counts"][:-1]):
                            cum += c
                            buckets.append([fam.buckets[i], cum])
                        buckets.append(["+Inf", st["n"]])
                        rows.append({
                            "labels": dict(key),
                            "count": st["n"],
                            "sum": round(st["sum"], 9),
                            "buckets": buckets,
                        })
                else:
                    for key, v in sorted(fam._children.items()):
                        rows.append({"labels": dict(key), "value": v})
                out[name] = {"type": fam.kind, "help": fam.help,
                             "values": rows}
        # Quantiles OUTSIDE the lock pass (quantile() re-locks). inf
        # (rank fell in the overflow bucket) becomes None: strict-JSON
        # consumers of the snapshot must not meet bare Infinity.
        # Iterate the families CAPTURED in pass 1: a family first
        # created between the passes (workload thread racing a scrape)
        # has no `out` entry yet and must not KeyError the scrape.
        for name, fam in list(self._metrics.items()):
            if isinstance(fam, Histogram) and name in out:
                for row in out[name]["values"]:
                    qs = {}
                    for q in SNAPSHOT_QUANTILES:
                        v = fam.quantile(q, **row["labels"])
                        qs[f"p{int(q * 100)}"] = (
                            v if v is None or math.isfinite(v) else None
                        )
                    row["quantiles"] = qs
        return out

    def exposition(self, extra_snapshots: Iterable[Tuple[str, dict]] = ()
                   ) -> str:
        """Prometheus text exposition (v0.0.4): ``# HELP`` / ``# TYPE``
        per family, then the sample lines; histograms expand into
        ``_bucket{le=...}`` / ``_sum`` / ``_count``. ``extra_snapshots``
        are (rank, snapshot) pairs from peer processes (exporter's
        rank-0 merge) — their series carry an added ``rank`` label."""
        return render_exposition(
            self.snapshot(), extra_snapshots=extra_snapshots
        )


def render_exposition(snapshot: Mapping[str, dict],
                      extra_snapshots: Iterable[Tuple[str, dict]] = ()
                      ) -> str:
    """Snapshot(s) -> exposition text (one owner for own + peer
    rendering, and for tools/metrics_dump.py's offline mode)."""
    merged: Dict[str, dict] = {}

    def fold(snap: Mapping[str, dict], extra_labels: dict) -> None:
        for name, fam in snap.items():
            slot = merged.setdefault(
                name, {"type": fam.get("type", "untyped"),
                       "help": fam.get("help", ""), "rows": []}
            )
            for row in fam.get("values", ()):
                labels = {**row.get("labels", {}), **extra_labels}
                slot["rows"].append({**row, "labels": labels})

    fold(snapshot, {})
    for rank, snap in extra_snapshots:
        fold(snap, {"rank": str(rank)})

    lines: list[str] = []
    for name in sorted(merged):
        fam = merged[name]
        if fam["help"]:
            lines.append(f"# HELP {name} {fam['help']}")
        lines.append(f"# TYPE {name} {fam['type']}")
        for row in fam["rows"]:
            key = _labels_key(row["labels"])
            if fam["type"] == "histogram":
                for le, cum in row["buckets"]:
                    le_s = "+Inf" if le == "+Inf" else repr(float(le))
                    lines.append(
                        f"{name}_bucket"
                        f"{_render_labels(key, {'le': le_s})} {cum}"
                    )
                lines.append(f"{name}_sum{_render_labels(key)} "
                             f"{repr(float(row['sum']))}")
                lines.append(f"{name}_count{_render_labels(key)} "
                             f"{row['count']}")
            else:
                v = row["value"]
                v_s = repr(float(v)) if not float(v).is_integer() \
                    else str(int(v))
                lines.append(f"{name}{_render_labels(key)} {v_s}")
    return "\n".join(lines) + "\n"


def parse_exposition(text: str) -> Dict[Tuple[str, Tuple[Tuple[str, str],
                                                         ...]], float]:
    """Inverse of :func:`render_exposition` for tests and the dryrun
    self-scrape: ``{(name, sorted-label-tuple): value}``. Raises on a
    malformed sample line — the exporter golden test leans on that."""
    out: dict = {}
    sample = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{([^}]*)\})? '
        r'([0-9eE+.inf-]+|NaN)$'
    )
    labelpair = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
    _UNESCAPE = re.compile(r'\\(.)')
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = sample.match(line)
        if not m:
            raise ValueError(f"malformed exposition line: {line!r}")
        name, _, labelbody, value = m.groups()
        labels = []
        if labelbody:
            matched = labelpair.findall(labelbody)
            # One pass over escapes: a sequential replace chain turns
            # the escaped form of backslash+'n' (\\n) into
            # backslash+newline — \\ must not re-expose an n to the \n
            # rule (render->parse must round-trip).
            labels = [
                (k, _UNESCAPE.sub(
                    lambda m: "\n" if m.group(1) == "n" else m.group(1), v
                ))
                for k, v in matched
            ]
        out[(name, tuple(sorted(labels)))] = float(value)
    return out


# ----------------------------------------------------------------------
# Global registry + the recorder tap
# ----------------------------------------------------------------------

_registry: Optional[MetricsRegistry] = None
_registry_lock = threading.Lock()
_tap_installed = False


def registry() -> MetricsRegistry:
    """The process-global registry, created on first use."""
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = MetricsRegistry()
        return _registry


def active_registry() -> Optional[MetricsRegistry]:
    """The global registry or None — the one-global-read guard every
    direct-gauge site starts with (the plane costs nothing until
    something creates the registry)."""
    return _registry


def reset() -> None:
    """Tear down the global registry and the tap (tests)."""
    global _registry, _tap_installed, _dropped_seen
    uninstall_tap()
    with _registry_lock:
        _registry = None
    _tap_installed = False
    _dropped_seen = None
    _reset_slo_window()
    _reset_spec_totals()


def install_tap(reg: Optional[MetricsRegistry] = None) -> MetricsRegistry:
    """Register the recorder->metrics sink (idempotent) plus the
    scrape-time recorder-health hook. Events only flow while a trace
    recorder is active; the sink itself adds no cost with tracing off
    (it is simply never called)."""
    global _registry, _tap_installed
    if reg is not None:
        with _registry_lock:
            _registry = reg
    reg = registry()
    if not _tap_installed:
        _trace.add_sink(_tap_event)
        _tap_installed = True
    reg.register_collect(_collect_recorder_health)
    reg.register_collect(_collect_slo_burn)
    reg.register_collect(_collect_spec_accept)
    return reg


def uninstall_tap() -> None:
    global _tap_installed
    _trace.remove_sink(_tap_event)
    _tap_installed = False


# (recorder-identity, last-seen dropped) — the counter accumulates
# DELTAS across recorder generations: each Recorder's `dropped` starts
# at 0, so mirroring it with a bare max() would hide a later, smaller
# recorder's drops behind an earlier recorder's total (review finding).
# The watermark read-modify-write is guarded: ThreadingHTTPServer
# scrapes concurrently, and two unsynchronized collects would both see
# the same prev and double-count the delta (review finding). Safe to
# take here — collect hooks run OUTSIDE the registry lock.
_dropped_seen: Optional[Tuple[int, int]] = None
_dropped_lock = threading.Lock()


def _collect_recorder_health(reg: MetricsRegistry) -> None:
    """Scrape-time sync of recorder-owned monotone state: the live
    ``trace_dropped_events`` counter (ISSUE 6 satellite — before this,
    ``Recorder.dropped`` surfaced only in the ``close()`` meta event;
    process-lifetime total across recorder generations) and the
    buffered-event gauge."""
    global _dropped_seen
    rec = _trace.active()
    if rec is None:
        return
    rec_id = id(rec)
    with _dropped_lock:
        # One read: drops landing between two reads would advance the
        # watermark without ever being counted.
        dropped = rec.dropped
        prev = _dropped_seen[1] if (
            _dropped_seen is not None and _dropped_seen[0] == rec_id
        ) else 0
        delta = dropped - prev
        if delta < 0:
            # dropped is monotone per recorder: a decrease means id()
            # reuse by a NEW recorder — its whole count is fresh.
            delta = dropped
        _dropped_seen = (rec_id, dropped)
    reg.counter(
        "trace_dropped_events",
        "trace events dropped by the recorder's in-memory buffer cap",
    ).inc(float(delta))  # inc(0) still exports the series on a lossless run
    reg.gauge(
        "trace_buffered_events", "events in the recorder's memory buffer"
    ).set(len(rec.events))


# ----------------------------------------------------------------------
# SLO burn rate (ISSUE 17): sliding-window violation fraction
# ----------------------------------------------------------------------
#
# ``serving_slo_violations_total`` is a counter — it can only say "how
# many ever", which makes a dashboard alert integrate-by-hand. The burn
# rate is the operational form: the fraction of target-bearing finishes
# inside the trailing window that MISSED their target, per (kind,
# tenant). 0.0 = clean, 1.0 = every request burning. Window length is
# ``CHAINERMN_TPU_SLO_WINDOW_S`` (seconds, default 60); a pair whose
# verdicts have all aged out reads 0.0 — the gauge stays exported (a
# vanished series and a healthy one must not look alike).

_SLO_WINDOW_ENV = "CHAINERMN_TPU_SLO_WINDOW_S"
_SLO_WINDOW_DEFAULT_S = 60.0

#: (monotonic stamp, kind, tenant, ok) per finish-event verdict —
#: monotonic, not epoch: a stepped wall clock must not dump or pin the
#: window.
_slo_window: collections.deque = collections.deque()
_slo_pairs_seen: set = set()
_slo_lock = threading.Lock()


def _slo_window_s() -> float:
    try:
        v = float(os.environ.get(_SLO_WINDOW_ENV, _SLO_WINDOW_DEFAULT_S))
    except ValueError:
        return _SLO_WINDOW_DEFAULT_S
    return v if v > 0 else _SLO_WINDOW_DEFAULT_S


def _record_slo_verdict(kind: str, tenant: str, ok: bool) -> None:
    with _slo_lock:
        _slo_window.append((time.monotonic(), kind, tenant, bool(ok)))
        _slo_pairs_seen.add((kind, tenant))


def slo_burn_rates(window_s: Optional[float] = None) -> dict:
    """``{kind: {tenant: burn}}`` over the trailing window — burn is
    violations/total among finishes carrying that SLO verdict. Every
    (kind, tenant) pair ever seen this process stays in the map (0.0
    once its verdicts age out). Feeds both the ``serving_slo_burn_rate``
    gauge and the exporter's ``/healthz`` body."""
    if window_s is None:
        window_s = _slo_window_s()
    cutoff = time.monotonic() - window_s
    counts: dict = {}
    with _slo_lock:
        while _slo_window and _slo_window[0][0] < cutoff:
            _slo_window.popleft()
        for _t, kind, tenant, ok in _slo_window:
            tot, bad = counts.get((kind, tenant), (0, 0))
            counts[(kind, tenant)] = (tot + 1, bad + (0 if ok else 1))
        pairs = sorted(_slo_pairs_seen)
    out: dict = {}
    for kind, tenant in pairs:
        tot, bad = counts.get((kind, tenant), (0, 0))
        out.setdefault(kind, {})[tenant] = (
            round(bad / tot, 6) if tot else 0.0)
    return out


def _reset_slo_window() -> None:
    with _slo_lock:
        _slo_window.clear()
        _slo_pairs_seen.clear()


def _collect_slo_burn(reg: MetricsRegistry) -> None:
    """Scrape-time hook: re-derive the burn gauges from the window (a
    sliding-window value must DECAY without new events — only a
    collect hook, never a per-event write, can show that)."""
    for kind, tenants in slo_burn_rates().items():
        for tenant, burn in tenants.items():
            reg.gauge(
                "serving_slo_burn_rate",
                "fraction of SLO-bearing finishes in the trailing "
                f"window (${_SLO_WINDOW_ENV}, default "
                f"{_SLO_WINDOW_DEFAULT_S:g}s) that missed their target",
            ).set(burn, kind=kind, tenant=tenant)


# ----------------------------------------------------------------------
# Speculative acceptance by sampling mode (ISSUE 18)
# ----------------------------------------------------------------------
#
# The unlabeled ``speculate_drafted_total``/``accepted_total`` counters
# predate sampled speculation and stay exactly as they were (pinned in
# tests/test_metrics.py). Now that verify ticks run in two acceptance
# regimes — exact-match greedy vs rejection-sampling sampled
# (docs/serving.md "Sampling") — the operational question is the RATE
# per regime: a sampled acceptance collapse is a drafter-mismatch
# signal that an aggregate counter would average away.

#: {mode: (drafted, accepted)} — process-lifetime totals.
_spec_totals: dict = {}
_spec_lock = threading.Lock()


def _record_spec(mode: str, drafted: float, accepted: float) -> None:
    with _spec_lock:
        tot, acc = _spec_totals.get(mode, (0.0, 0.0))
        _spec_totals[mode] = (tot + drafted, acc + accepted)


def spec_accept_rates() -> dict:
    """``{mode: rate}`` — accepted/drafted per sampling mode over the
    process lifetime. A mode that has drafted nothing reads 0.0 but
    stays in the map once seen (same vanished-vs-healthy rule as the
    burn gauges). Feeds the ``serving_spec_accept_rate`` gauge and the
    exporter's ``/healthz`` body."""
    with _spec_lock:
        return {
            mode: (round(acc / tot, 6) if tot else 0.0)
            for mode, (tot, acc) in sorted(_spec_totals.items())
        }


def _reset_spec_totals() -> None:
    with _spec_lock:
        _spec_totals.clear()


def _collect_spec_accept(reg: MetricsRegistry) -> None:
    """Scrape-time hook: derive the per-mode acceptance-rate gauge from
    the totals (a ratio is a derived value — exporting it per-event
    would snapshot whichever tick scraped last)."""
    for mode, rate in spec_accept_rates().items():
        reg.gauge(
            "serving_spec_accept_rate",
            "speculative tokens accepted / drafted by sampling mode "
            "(process lifetime)",
        ).set(rate, mode=mode)


def _tap_event(ev: Mapping[str, Any]) -> None:
    """The recorder sink: one trace event -> metric updates. Must never
    raise (the recorder swallows sink errors, but a broken tap would
    silently stop updating — keep each branch total)."""
    reg = _registry
    if reg is None:
        return
    kind = ev.get("kind")
    if kind == "collective":
        op = str(ev.get("op", "?"))
        plane = str(ev.get("plane", "device"))
        reg.counter(
            "wire_events_total", "collective-wire events by op"
        ).inc(op=op, plane=plane)
        nb = ev.get("nbytes")
        if nb is not None:
            reg.counter(
                "wire_bytes_total", "collective-wire payload bytes by op"
            ).inc(float(nb), op=op, plane=plane)
        dur = ev.get("dur_s")
        if dur is not None:
            reg.counter(
                "wire_seconds_total", "collective-wire seconds by op"
            ).inc(float(dur), op=op, plane=plane)
            reg.histogram(
                "collective_seconds", "per-collective duration"
            ).observe(float(dur), op=op, plane=plane)
    elif kind == "step":
        reg.counter("train_steps_total", "trainer iterations").inc()
        it = ev.get("iteration")
        if it is not None:
            reg.gauge("train_iteration", "last completed trainer "
                      "iteration").set(float(it))
        for phase, v in (ev.get("phases") or {}).items():
            reg.histogram(
                "step_phase_seconds", "trainer step-timeline phase seconds"
            ).observe(float(v), phase=str(phase))
    elif kind == "serving":
        phase = ev.get("phase")
        dur = float(ev.get("dur_s") or 0.0)
        if phase == "queue_wait":
            reg.histogram(
                "serving_queue_wait_seconds", "submit -> admission wait"
            ).observe(dur)
        elif phase == "prefill":
            reg.histogram(
                "serving_prefill_seconds", "bucketed prefill duration"
            ).observe(dur)
            if ev.get("ttft_s") is not None:
                reg.histogram(
                    "serving_ttft_seconds",
                    "submit -> first token (the TTFT SLO)",
                ).observe(float(ev["ttft_s"]))
                if ev.get("tenant") is not None:
                    # Per-tenant TTFT (ISSUE 14): the tenant label set
                    # is bounded by adapter-bank capacity, so the
                    # cardinality stays small by construction.
                    reg.histogram(
                        "serving_tenant_ttft_seconds",
                        "submit -> first token per tenant",
                    ).observe(float(ev["ttft_s"]),
                              tenant=str(ev["tenant"]))
            reg.counter(
                "serving_tokens_total", "generated tokens (first token "
                "per prefill + decode-step tokens)"
            ).inc()
        elif phase == "decode_step":
            reg.histogram(
                "serving_decode_step_seconds",
                "fused decode-step duration (per-token latency under "
                "plain decode; tick latency under speculation)",
            ).observe(dur)
            reg.counter("serving_decode_steps_total",
                        "fused decode steps").inc()
            toks = ev.get("tokens")
            if toks:
                reg.counter(
                    "serving_tokens_total", "generated tokens (first "
                    "token per prefill + decode-step tokens)"
                ).inc(float(toks))
        elif phase == "finish":
            reg.counter("serving_requests_total",
                        "completed serving requests").inc()
            if ev.get("tenant") is not None:
                reg.counter(
                    "serving_tenant_requests_total",
                    "completed serving requests per tenant",
                ).inc(tenant=str(ev["tenant"]))
                gen = ev.get("generated")
                if gen:
                    reg.counter(
                        "serving_tenant_tokens_total",
                        "generated tokens per tenant (from finishes)",
                    ).inc(float(gen), tenant=str(ev["tenant"]))
            # SLO verdicts (ISSUE 11): one violation count per missed
            # target kind — a request can miss both. Every verdict
            # (pass or fail) also lands in the burn-rate window
            # (ISSUE 17) — a rate needs the denominator too.
            tenant = str(ev.get("tenant") or "default")
            if ev.get("slo_ttft_ok") is False:
                reg.counter(
                    "serving_slo_violations_total",
                    "finished requests outside a stated SLO target",
                ).inc(kind="ttft")
            if ev.get("slo_tpot_ok") is False:
                reg.counter(
                    "serving_slo_violations_total",
                    "finished requests outside a stated SLO target",
                ).inc(kind="tpot")
            if ev.get("slo_ttft_ok") is not None:
                _record_slo_verdict("ttft", tenant, ev["slo_ttft_ok"])
            if ev.get("slo_tpot_ok") is not None:
                _record_slo_verdict("tpot", tenant, ev["slo_tpot_ok"])
        elif phase == "preempt":
            reg.counter(
                "serving_preemptions_total",
                "in-flight requests preempted back to the queue "
                "(SLO scheduling)",
            ).inc()
    elif kind == "prefill_chunk":
        reg.counter(
            "serving_prefill_chunks_total",
            "prompt chunks written through the mixed step",
        ).inc()
        reg.counter(
            "serving_chunk_tokens_total",
            "prompt tokens prefilled through mixed-step chunks",
        ).inc(float(ev.get("tokens") or 0))
    elif kind == "speculate":
        drafted = float(ev.get("drafted") or 0)
        accepted = float(ev.get("accepted") or 0)
        reg.counter("speculate_drafted_total",
                    "speculative tokens drafted").inc(drafted)
        reg.counter("speculate_accepted_total",
                    "speculative tokens accepted").inc(accepted)
        _record_spec(str(ev.get("mode") or "greedy"), drafted, accepted)
    elif kind == "moe_dispatch":
        # ISSUE 20: host-side mirror of one MoE dispatch observation
        # (parallel.moe.record_moe_dispatch). Counters accumulate the
        # drop/pad token flow; gauges snapshot the latest per-expert
        # load histogram and the static capacity.
        reg.counter(
            "moe_dropped_tokens_total",
            "MoE capacity-overflow token assignments (carried by the "
            "residual path, not corrupted)",
        ).inc(float(ev.get("dropped") or 0))
        reg.counter(
            "moe_padded_tokens_total",
            "empty MoE queue slots shipped over the a2a wire anyway "
            "(the static-shape tax)",
        ).inc(float(ev.get("padded") or 0))
        layer = ev.get("layer")
        labels = {"layer": str(layer)} if layer is not None else {}
        for i, v in enumerate(ev.get("expert_load") or ()):
            reg.gauge(
                "moe_expert_load",
                "kept tokens routed to each expert at the last "
                "observed dispatch",
            ).set(float(v), expert=str(i), **labels)
        if ev.get("capacity") is not None:
            reg.gauge(
                "moe_capacity",
                "per-expert token capacity of the MoE dispatch",
            ).set(float(ev["capacity"]), **labels)
    elif kind == "prefix_cache":
        reg.counter("kv_prefix_lookups_total",
                    "prefix-trie lookups at admission").inc()
        if int(ev.get("hit_blocks") or 0) > 0:
            reg.counter("kv_prefix_hits_total",
                        "admissions that adopted cached blocks").inc()
        reg.counter(
            "kv_prefix_hit_tokens_total",
            "prompt tokens served from the prefix cache (not "
            "re-prefilled)",
        ).inc(float(ev.get("hit_tokens") or 0))
        reg.counter(
            "kv_prefix_prefill_tokens_total",
            "prompt tokens actually prefilled (the unshared tails)",
        ).inc(float(ev.get("prefill_tokens") or 0))
        cow = float(ev.get("cow_blocks") or 0)
        if cow:
            reg.counter("kv_prefix_cow_blocks_total",
                        "copy-on-write block copies").inc(cow)
    elif kind == "route":
        reg.counter(
            "cluster_routes_total",
            "requests placed on a replica by the cluster router",
        ).inc(rank=str(ev.get("replica")))
        if ev.get("requeue"):
            reg.counter(
                "cluster_requeues_total",
                "requests re-routed after a deferral or replica loss",
            ).inc()
    elif kind == "kv_transfer":
        reg.counter(
            "kv_transfer_total",
            "cross-replica KV handoffs (disaggregated prefill/decode)",
        ).inc()
        reg.counter(
            "kv_transfer_bytes_total",
            "KV block bytes streamed between replicas",
        ).inc(float(ev.get("nbytes") or 0))
        reg.counter(
            "kv_transfer_blocks_total",
            "KV blocks streamed between replicas",
        ).inc(float(ev.get("blocks") or 0))
        if ev.get("dur_s") is not None:
            reg.histogram(
                "kv_transfer_seconds",
                "export -> adoption latency of one KV handoff",
            ).observe(float(ev["dur_s"]))
    elif kind == "straggler":
        reg.counter("straggler_reports_total",
                    "straggler-monitor flag reports").inc()
