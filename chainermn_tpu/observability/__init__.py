"""Structured observability: collective-wire counters, step-time
breakdown, and cross-rank straggler detection (ISSUE 2; see
docs/observability.md).

Three integrated layers, all host-side (an instrumented program lowers
to exactly the same HLO — zero added device-plane collectives):

- :mod:`~chainermn_tpu.observability.trace` — the event recorder. Wire
  counters for every communicator collective (op, payload bytes, wire
  dtype, duration, autotune provenance of any ``'auto'`` decision),
  step-timeline events from the Trainer, JSONL + Chrome-trace export.
  Enable with ``CHAINERMN_TPU_TRACE=<path.jsonl>`` or
  :func:`~chainermn_tpu.observability.trace.enable`.
- :mod:`~chainermn_tpu.observability.straggler` — cross-rank drift
  detection over :class:`ObservationAggregator` windows.
- ``tools/trace_report.py`` — per-op bytes/time tables (with roofline
  floors where device peaks are known) from an emitted JSONL.

The LIVE plane (ISSUE 6) sits beside the post-hoc trace:

- :mod:`~chainermn_tpu.observability.metrics` — process-local
  Counter/Gauge/Histogram registry, fed by a recorder *tap* (every
  traced site populates metrics with zero new call sites) plus direct
  gauges at stateful host planes; streaming SLO percentiles from fixed
  log-spaced buckets.
- :mod:`~chainermn_tpu.observability.exporter` — stdlib HTTP daemon
  serving ``/metrics`` (Prometheus text), ``/healthz``, and
  ``/trace/tail``; gated by ``CHAINERMN_TPU_METRICS_PORT``.
- :mod:`~chainermn_tpu.observability.flight` — bounded event ring,
  in-flight collective marker, trainer heartbeat, and the hang
  watchdog that turns a silent distributed stall into
  ``hang_dump_<rank>.json``.
- :mod:`~chainermn_tpu.observability.stats` — the shared nearest-rank
  percentile rule (``ceil(q*n)``) behind both the serving rollup and
  the histogram quantiles.

The DEVICE side of the train path (ISSUE 23) is named from the inside:
:mod:`~chainermn_tpu.observability.train_path` spells every
``jax.named_scope``, host span and counter the training step and its
set-up carry (docs/observability.md, "Training on the device").

The pre-existing ``jax.profiler`` wrappers stay in
:mod:`chainermn_tpu.utils.observability`; ``profile()`` records its
start/stop into this event stream as well, and ``annotate()`` is how
:func:`~chainermn_tpu.observability.trace.span` reaches the profiler.
"""

from chainermn_tpu.observability.trace import (
    TRACE_SCHEMA,
    Recorder,
    active,
    chrome_trace,
    disable,
    enable,
    read_jsonl,
    span,
    summarize_overlap,
    write_chrome_trace,
)


def __getattr__(name):
    # Lazy: straggler pulls in ObservationAggregator -> communicators,
    # while the communicators themselves import this package for the
    # trace module — eager re-export here would be a circular import.
    # The live-plane modules stay lazy for the same reason (flight and
    # metrics are imported by the communicator base / host comm).
    if name == "StragglerMonitor":
        from chainermn_tpu.observability.straggler import StragglerMonitor

        return StragglerMonitor
    if name in ("metrics", "exporter", "flight", "stats", "journey",
                "clocksync"):
        import importlib

        return importlib.import_module(
            f"chainermn_tpu.observability.{name}"
        )
    if name == "nearest_rank":
        from chainermn_tpu.observability.stats import nearest_rank

        return nearest_rank
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}"
    )

__all__ = [
    "TRACE_SCHEMA",
    "Recorder",
    "StragglerMonitor",
    "active",
    "chrome_trace",
    "clocksync",
    "disable",
    "enable",
    "exporter",
    "flight",
    "journey",
    "metrics",
    "nearest_rank",
    "read_jsonl",
    "span",
    "stats",
    "summarize_overlap",
    "write_chrome_trace",
]
