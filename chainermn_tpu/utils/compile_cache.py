"""Where compiled programs are kept between runs.

One helper for every entry point (``chip_smoke.py``, ``benchmark/run.py``,
the example CLIs), so that processes which share compiles share one cache.
"""

from __future__ import annotations

import os
import threading

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_listening = False
_listen_lock = threading.Lock()


def _count_compiles() -> None:
    """Listen, once a process, to what JAX publishes about each compile
    (``jax.monitoring``) and count it in the metrics registry under the
    names of ``observability.train_path``: seconds tracing, lowering and
    in the backend's compile-or-load, programs through the backend,
    persistent-cache hits and misses (a miss is a compile whose program
    was then written). The listeners run only when something compiles,
    never on a step's path.

    JAX times every jitted function it traces, the ``jnp`` ones inside
    a step's trace included, each span inside its caller's; tracing
    seconds count such nested spans once."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        _listening = True
    from jax import monitoring

    from chainermn_tpu.observability import train_path
    from chainermn_tpu.observability.metrics import registry

    counted: list[tuple[float, float]] = []  # disjoint, by start
    lock = threading.Lock()

    def on_span(event: str, start: float, end: float, **_) -> None:
        if event != _TRACE_EVENT:
            return
        # spans arrive as they close, the inner before the outer: an
        # outer one takes the place of those it holds
        with lock:
            held = 0.0
            while counted and counted[-1][0] >= start:
                s, e = counted.pop()
                held += e - s
            counted.append((start, end))
        registry().counter(
            train_path.JAX_TRACE_SECONDS,
            "seconds JAX spent tracing functions to jaxprs",
        ).inc(max(0.0, end - start - held))

    seconds_of = {
        _LOWER_EVENT: (train_path.JAX_LOWER_SECONDS,
                       "seconds JAX spent lowering jaxprs to MLIR modules"),
        _BACKEND_EVENT: (train_path.JAX_BACKEND_COMPILE_SECONDS,
                         "seconds in the backend's compile, or its load "
                         "from the persistent cache"),
    }
    count_of = {
        _HIT_EVENT: (train_path.COMPILE_CACHE_HITS,
                     "programs loaded from the persistent compilation cache"),
        _MISS_EVENT: (train_path.COMPILE_CACHE_MISSES,
                      "programs compiled and written to the persistent cache"),
    }

    def on_duration(event: str, seconds: float, **_) -> None:
        if event in seconds_of:
            registry().counter(*seconds_of[event]).inc(seconds)
        if event == _BACKEND_EVENT:
            registry().counter(
                train_path.PROGRAMS_COMPILED,
                "programs handed to the backend (compiled or loaded)",
            ).inc()

    def on_event(event: str, **_) -> None:
        if event in count_of:
            registry().counter(*count_of[event]).inc()

    monitoring.register_event_time_span_listener(on_span)
    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache, start counting
    compiles (:func:`_count_compiles`) and return the cache's
    directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache —
    JAX reads it itself and nothing is set in code. Otherwise the cache
    is ``<checkout>/.jax_cache``: a fixed place, never a temp name, pid
    or timestamp, so the next run finds what this one compiled."""
    _count_compiles()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
