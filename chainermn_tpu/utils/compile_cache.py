"""Where compiled programs are kept between runs.

One helper for every entry point (``chip_smoke.py``, ``benchmark/run.py``,
the example CLIs), so that processes which share compiles share one cache.
"""

from __future__ import annotations

import os
import re
import threading

from chainermn_tpu.observability import train_path
from chainermn_tpu.observability.metrics import registry

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

#: distinct ``program`` labels a process publishes; a program that comes
#: after them is filed under ``train_path.OTHER_PROGRAM``
MAX_PROGRAMS = 64

#: ``jit(<name>)`` / ``pmap(<name>)``, as JAX names a program once it is
#: lowered; tracing reports the bare ``<name>``
_WRAPPED = re.compile(r"(?:jit|pmap)\((.*)\)", re.DOTALL)

#: the counter each event adds to: seconds of a stage, and what the
#: persistent cache did inside a backend event
_SECONDS_OF = {_LOWER_EVENT: train_path.JAX_LOWER_SECONDS,
               _BACKEND_EVENT: train_path.JAX_BACKEND_COMPILE_SECONDS}
_CACHE_OF = {_HIT_EVENT: train_path.COMPILE_CACHE_HITS,
             _MISS_EVENT: train_path.COMPILE_CACHE_MISSES,
             _RETRIEVAL_EVENT: train_path.COMPILE_CACHE_RETRIEVAL_SECONDS}
_HELP = {
    train_path.JAX_TRACE_SECONDS:
        "seconds JAX spent tracing functions to jaxprs",
    train_path.JAX_LOWER_SECONDS:
        "seconds JAX spent lowering jaxprs to MLIR modules",
    train_path.JAX_BACKEND_COMPILE_SECONDS:
        "seconds in the backend's compile, or its load from the "
        "persistent cache",
    train_path.PROGRAMS_COMPILED:
        "programs handed to the backend (compiled or loaded)",
    train_path.COMPILE_CACHE_HITS:
        "programs loaded from the persistent compilation cache",
    train_path.COMPILE_CACHE_MISSES:
        "programs compiled and written to the persistent cache",
    train_path.COMPILE_CACHE_RETRIEVAL_SECONDS:
        "seconds reading and loading the programs the persistent cache "
        "held",
}

#: the kernel's view of this process: ``schedstat`` (run time, run-queue
#: wait, time slices) of its main thread, and one directory a thread
#: under ``task/`` with the same of each
_PROC = "/proc/self"


class _Held:
    """What one thread's compiles have reported and no counter holds."""

    __slots__ = ("roots", "cache")

    def __init__(self) -> None:
        # traced spans that no later span holds, disjoint and by start:
        # [start, end, fun_name, seconds of it the counters hold already]
        self.roots: list[list] = []
        # cache events since the thread's last backend event, by counter
        self.cache: dict[str, float] = {}


class _CompileCounts:
    """What JAX publishes about each compile (``jax.monitoring``),
    counted in the metrics registry by program under the names of
    ``observability.train_path``: seconds tracing, lowering and in the
    backend's compile-or-load, programs through the backend, persistent-
    cache hits, misses (a miss is a compile whose program was then
    written) and seconds loading what was hit.

    JAX times every jitted function it traces, the ``jnp`` ones inside a
    step's trace included, each span inside its caller's and closing
    before it. A traced span is therefore held back until its thread
    lowers or compiles something (it has no trace open then) or the
    registry is read; an outer span that closes meanwhile takes the
    place of those it holds. So a nested trace counts once, under the
    outermost program. The cache's events carry no name and arrive on
    the compiling thread before the backend event that closes round
    them: they are filed under that event's program."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._held: dict[int, _Held] = {}
        self._programs: set[str] = set()

    def _inc(self, name: str, value: float, fun_name: str) -> None:
        """Under the lock. One program carries one label through all
        three stages: its name without JAX's ``jit(...)``."""
        wrapped = _WRAPPED.fullmatch(fun_name)
        program = wrapped.group(1) if wrapped else fun_name
        if program not in self._programs:
            if not program or len(self._programs) >= MAX_PROGRAMS:
                program = train_path.OTHER_PROGRAM
            else:
                self._programs.add(program)
        registry().counter(name, _HELP[name]).inc(value, program=program)

    def _count_roots(self, held: _Held) -> None:
        for root in held.roots:
            start, end, fun_name, counted = root
            if end - start > counted:
                self._inc(train_path.JAX_TRACE_SECONDS,
                          end - start - counted, fun_name)
                root[3] = end - start

    # JAX calls every listener of a kind for every event of that kind: a
    # traced function calls the two below once each, thousands of times
    # for one step, so their first lines are most of the listeners' cost
    # (PERF.md has it in seconds a run)

    def on_span(self, event: str, start: float, end: float,
                fun_name: str = "", **_) -> None:
        if event != _TRACE_EVENT:
            return
        ident = threading.get_ident()
        with self._lock:
            held = self._held.get(ident)
            if held is None:
                held = self._held[ident] = _Held()
            roots = held.roots
            counted = 0.0
            while roots and roots[-1][0] >= start:
                counted += roots.pop()[3]
            roots.append([start, end, fun_name, counted])

    def on_duration(self, event: str, seconds: float, fun_name: str = "",
                    **_) -> None:
        if event == _TRACE_EVENT:
            return  # counted from its span
        if event not in _SECONDS_OF:
            return self.on_event(event, seconds)
        ident = threading.get_ident()
        with self._lock:
            held = self._held.get(ident)
            if held is not None:
                # a thread that lowers or compiles has no trace open:
                # what it traced is final and need not be kept
                self._count_roots(held)
                held.roots.clear()
            self._inc(_SECONDS_OF[event], seconds, fun_name)
            if event == _BACKEND_EVENT:
                self._inc(train_path.PROGRAMS_COMPILED, 1.0, fun_name)
                if held is not None:
                    for name, value in held.cache.items():
                        self._inc(name, value, fun_name)
                    del self._held[ident]

    def on_event(self, event: str, value: float = 1.0, **_) -> None:
        name = _CACHE_OF.get(event)
        if name is None:
            return
        ident = threading.get_ident()
        with self._lock:
            held = self._held.get(ident)
            if held is None:
                held = self._held[ident] = _Held()
            held.cache[name] = held.cache.get(name, 0.0) + value

    def collect(self, _registry=None) -> None:
        """Collect hook: the traced seconds held back reach the counters
        before they are read. A span stays where it is, with what is
        counted of it, while a trace round it may still be open."""
        with self._lock:
            for held in self._held.values():
                self._count_roots(held)


def _runqueue_wait_seconds(proc: str = _PROC) -> float | None:
    """Seconds the process's threads stood runnable and were given no
    core (the second field of each thread's ``schedstat``, ns); ``None``
    where the kernel keeps no such statistics. A thread that has exited
    takes its share with it."""
    if not os.path.exists(os.path.join(proc, "schedstat")):
        return None
    total = 0
    for thread in os.listdir(os.path.join(proc, "task")):
        try:
            with open(os.path.join(proc, "task", thread, "schedstat")) as f:
                total += int(f.read().split()[1])
        except (OSError, IndexError, ValueError):
            continue  # the thread has gone since it was listed
    return total / 1e9


def _collect_host(reg, proc: str = _PROC) -> None:
    """Collect hook: what the host gave the process and what it made it
    wait, read when the registry is and never in between."""
    times = os.times()
    for name, help_, now in (
        (train_path.PROCESS_RUNQUEUE_WAIT_SECONDS,
         "seconds the process's threads waited runnable for a core",
         _runqueue_wait_seconds(proc)),
        (train_path.PROCESS_CPU_SECONDS,
         "seconds of CPU the process used, user and system",
         times.user + times.system),
    ):
        if now is not None:
            counter = reg.counter(name, help_)
            counter.inc(max(0.0, now - counter.value()))


_counts: _CompileCounts | None = None
_listen_lock = threading.Lock()


def _count_compiles() -> None:
    """Listen, once a process, to what JAX publishes about each compile
    (:class:`_CompileCounts`), and have the registry of the moment read
    the host's clocks when it is read itself (:func:`_collect_host`).
    The listeners run only when something compiles and the hooks when
    the registry is scraped or snapshot, never on a step's path."""
    global _counts
    with _listen_lock:
        if _counts is None:
            from jax import monitoring

            _counts = _CompileCounts()
            monitoring.register_event_time_span_listener(_counts.on_span)
            monitoring.register_event_duration_secs_listener(
                _counts.on_duration)
            monitoring.register_event_listener(_counts.on_event)
    registry().register_collect(_counts.collect)
    registry().register_collect(_collect_host)


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
