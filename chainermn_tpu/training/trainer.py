"""Minimal trainer loop with rank-0 reporting and extension triggers.

The reference rode Chainer's ``Trainer``/``Updater``/``Extension`` machinery
(external to it); a standalone framework needs its own loop. Reporting
follows the reference's observability pattern exactly (SURVEY.md section 5):
**gate reporter output on rank 0** (``comm.rank == 0`` in every example
(dagger)), aggregate metrics across processes before logging.
"""

from __future__ import annotations

import sys
import time
from typing import Any, Callable, Iterable, Optional

import jax
import numpy as np

from chainermn_tpu.communicators.base import CommunicatorBase
from chainermn_tpu.observability import flight as _flight
from chainermn_tpu.observability import metrics as _metrics
from chainermn_tpu.observability import trace as _trace
from chainermn_tpu.observability import train_path

PyTree = Any


def default_collate(batch: list) -> Any:
    """list of examples -> stacked numpy pytree. Examples may be tuples
    (``(x, y)``), dicts, or plain arrays."""
    first = batch[0]
    if isinstance(first, tuple):
        return tuple(np.stack([b[i] for b in batch]) for i in range(len(first)))
    if isinstance(first, dict):
        return {k: np.stack([b[k] for b in batch]) for k in first}
    return np.stack(batch)


def host_local_batch_to_global(batch: Any, comm: CommunicatorBase, spec=None):
    """Assemble each process's host-local batch into the global sharded
    arrays a jitted step's ``in_specs`` expect. No-op on a single process.

    Default ``spec`` treats local batches as this process's data-parallel
    shard (leading dim concatenated over processes — the
    ``scatter_dataset`` norm). Pass ``P()`` for master-broadcast iterators
    where every process holds the identical batch.
    """
    if comm.host.size == 1:
        return batch
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    spec = P(comm.grad_axes) if spec is None else spec
    return multihost_utils.host_local_array_to_global_array(
        batch, comm.mesh, spec
    )


class Trainer:
    """Drive ``step_fn`` over an iterator with periodic extensions.

    Extensions are callables ``ext(trainer) -> None`` registered with an
    iteration interval — the shape of Chainer's extension protocol, enough
    to host the multi-node evaluator and checkpointer (SURVEY.md section 2.7).
    """

    def __init__(
        self,
        step_fn: Callable,
        state: Any,
        train_iter: Iterable,
        comm: CommunicatorBase,
        *,
        collate: Callable = default_collate,
        batch_spec=None,
        log_interval: int = 100,
        out=sys.stdout,
        prefetch: int = 0,
    ) -> None:
        self.step_fn = step_fn
        self.state = state
        self.train_iter = train_iter
        self.comm = comm
        self.collate = collate
        #: PartitionSpec describing what each process's local batch IS in
        #: the global batch (see :func:`host_local_batch_to_global`).
        # Master-broadcast iterators deliver the IDENTICAL batch to every
        # process; treating those as data-parallel shards would silently
        # duplicate every example, so detect and default to replicated.
        if batch_spec is None and getattr(
            train_iter, "replicated_batches", False
        ):
            from jax.sharding import PartitionSpec

            batch_spec = PartitionSpec()
        self.batch_spec = batch_spec
        self.log_interval = log_interval
        self.out = out
        #: batches kept in flight on device ahead of the step (0 = off;
        #: 2 = double buffering). See
        #: :func:`chainermn_tpu.training.prefetch.prefetch_to_device`.
        self.prefetch = prefetch
        self.iteration = 0
        #: cross-rank aggregated host metrics at the last log point —
        #: populated on EVERY rank (via :class:`ObservationAggregator`),
        #: so non-zero ranks can drive extensions off metrics; rank 0
        #: additionally pretty-prints its LOCAL metrics, unchanged.
        self.observation: dict[str, float] = {}
        self._extensions: list[tuple[int, Callable]] = []
        # Step-phase window for the observability layer: per-phase
        # second sums since the last consume_phase_window() (the
        # straggler monitor's input) + the h2d handoff slot from the
        # batch generator.
        self._phase_sums: dict[str, float] = {}
        self._phase_steps = 0
        self._h2d_pending = 0.0
        from chainermn_tpu.extensions.observation_aggregator import (
            ObservationAggregator,
        )

        self._obs_agg = ObservationAggregator(comm)

    def extend(self, extension: Callable, *, interval: int = 1) -> None:
        self._extensions.append((interval, extension))

    # ------------------------------------------------------------------

    def _log(self, msg: str) -> None:
        if self.comm.rank == 0:
            print(msg, file=self.out, flush=True)

    def _collated_batches(self, n: int):
        """Yield exactly ``n`` collated, mesh-global batches, restarting
        the epoch iterator as needed (with the empty-epoch guard)."""
        produced = 0
        it = iter(self.train_iter)
        fresh_epoch = True
        while produced < n:
            try:
                batch = next(it)
                fresh_epoch = False
            except StopIteration:
                if fresh_epoch:
                    raise RuntimeError(
                        "train iterator yielded no batches in a full epoch "
                        "(dataset shard smaller than batch size with "
                        "drop_last?) — aborting instead of spinning"
                    )
                it = iter(self.train_iter)
                fresh_epoch = True
                continue
            produced += 1
            collated = self.collate(batch)
            # Time the host→device/global-array assembly separately from
            # the pull (the step-timeline's ``h2d`` phase). ACCUMULATED,
            # not assigned: with ``prefetch`` on, one loop pull can
            # drive several assemblies (queue fill) — they all belong to
            # the step whose data interval paid for them, so the loop
            # drains the accumulator once per step.
            t_h2d = time.perf_counter()
            with _trace.span(train_path.TRAINER_H2D):
                out = host_local_batch_to_global(
                    collated, self.comm, self.batch_spec
                )
            self._h2d_pending += time.perf_counter() - t_h2d
            yield out

    def run(self, max_iterations: int) -> Any:
        try:
            return self._run_impl(max_iterations)
        finally:
            # The run is OVER — returned OR raised: stand the heartbeat
            # down so a process that lingers after training (eval,
            # checkpointing, a driver that caught the exception) is not
            # mistaken for a hang by the watchdog; its fire-once dump
            # must stay in the barrel for a real stall (review finding:
            # the raise path used to leave a stale beat).
            _flight.quiesce()

    def _run_impl(self, max_iterations: int) -> Any:
        t0 = time.perf_counter()
        # Live-telemetry front door (ISSUE 6): honour the metrics-port
        # and hang-watchdog env gates once per run. Both are no-ops
        # (one env read) when unset — and must never break training.
        try:
            from chainermn_tpu.observability import exporter as _exporter

            _exporter.maybe_start_from_env()
            _flight.maybe_start_from_env()
        except Exception:
            pass
        rec0 = _trace.active()
        if rec0 is not None:
            # Comm/compute-overlap configuration of the step driving this
            # loop (make_train_step attaches it): recorded once so the
            # trace's wire events can be read against the mode —
            # double-buffered staleness, reduction schedule, donation —
            # that produced them (tools/trace_report.py "overlap").
            info = getattr(self.step_fn, "overlap_info", None)
            if info:
                rec0.event("overlap_config", **dict(info))
        batches = self._collated_batches(max_iterations - self.iteration)
        if self.prefetch:
            import math

            from jax.sharding import NamedSharding, PartitionSpec

            from chainermn_tpu.training.prefetch import prefetch_to_device

            # Place straight to the step's batch sharding: a bare
            # device_put would commit the whole global batch to device 0
            # (prefetch-deep HBM spike there) and the step would then
            # reshard device-to-device.
            spec = (
                self.batch_spec
                if self.batch_spec is not None
                else PartitionSpec(self.comm.grad_axes)
            )
            sharding = NamedSharding(self.comm.mesh, spec)
            dim0_axes = spec[0] if len(spec) else None
            if dim0_axes is None:
                n_data = 1
            elif isinstance(dim0_axes, tuple):
                n_data = math.prod(
                    self.comm.mesh.shape[a] for a in dim0_axes
                )
            else:
                n_data = self.comm.mesh.shape[dim0_axes]

            def _place(bs):
                # Enabling prefetch must never change which batches are
                # accepted: mesh-shard only batches whose leading dims
                # divide the data axes; others keep the default placement
                # (prefetch_to_device passes jax.Arrays through).
                for b in bs:
                    fits = all(
                        leaf.shape[0] % n_data == 0
                        for leaf in jax.tree.leaves(b)
                        if getattr(leaf, "ndim", 0) >= 1
                    )
                    yield jax.device_put(b, sharding) if fits else b

            batches = prefetch_to_device(_place(batches), self.prefetch)
        it = iter(batches)
        end = object()
        while True:
            # one iteration, one step of a profile: a live jax.profiler
            # session groups the host spans and device ops below by it
            # (numbered as the ``step`` event numbers it)
            with jax.profiler.StepTraceAnnotation(
                train_path.TRAINER_STEP, step_num=self.iteration + 1
            ):
                # --- data-wait: pulling the next collated global batch
                # (collate + epoch restarts; with prefetch, also the queue
                # wait). The generator accumulates its h2d sub-spans into
                # ``_h2d_pending``; draining it here keeps the two phases
                # disjoint even when one pull runs several assemblies
                # (prefetch queue fill).
                self._h2d_pending = 0.0
                t_data = time.perf_counter()
                with _trace.span(train_path.TRAINER_DATA_WAIT):
                    collated = next(it, end)
                if collated is end:
                    break
                h2d = self._h2d_pending
                data_wait = time.perf_counter() - t_data - h2d

                # --- compute: the jitted step. Dispatch-to-return under
                # async dispatch; a sync-mode recorder blocks on the metrics
                # for true wall time (measurement mode — serialises overlap).
                t_step = time.perf_counter()
                self.state, metrics = self.step_fn(self.state, collated)
                rec = _trace.active()
                if rec is not None and rec.sync:
                    jax.block_until_ready(metrics)
                compute = time.perf_counter() - t_step
                self.iteration += 1
                # Hang-watchdog heartbeat + the direct step-counter gauge
                # (ISSUE 6): the trainer's state plane has no trace event of
                # its own until the step event below — the beat and gauge
                # stay live even with tracing off. One slot store; the gauge
                # guards on the registry existing at all.
                _flight.beat(self.iteration)
                reg = _metrics.active_registry()
                if reg is not None:
                    reg.gauge(
                        "train_iteration", "last completed trainer iteration"
                    ).set(float(self.iteration))

                log_s = 0.0
                if self.iteration % self.log_interval == 0 or self.iteration == max_iterations:
                    t_log = time.perf_counter()
                    with _trace.span(train_path.TRAINER_LOG):
                        self._log_metrics(metrics, max_iterations, t0)
                    log_s = time.perf_counter() - t_log

                # Window accumulation BEFORE extensions run, so a straggler
                # monitor firing as an extension sees this step included.
                phases = {
                    "data_wait": data_wait,
                    "h2d": h2d,
                    "compute": compute,
                    "logging": log_s,
                }
                for k, v in phases.items():
                    self._phase_sums[k] = self._phase_sums.get(k, 0.0) + v
                self._phase_steps += 1

                t_ext = time.perf_counter()
                for interval, ext in self._extensions:
                    if self.iteration % interval == 0:
                        ext(self)
                ext_s = time.perf_counter() - t_ext
                self._phase_sums["extensions"] = (
                    self._phase_sums.get("extensions", 0.0) + ext_s
                )

                if rec is not None:
                    rec.event(
                        "step", iteration=self.iteration,
                        phases={k: round(v, 6)
                                for k, v in {**phases,
                                             "extensions": ext_s}.items()},
                    )
        return self.state

    def _log_metrics(self, metrics, max_iterations: int, t0: float) -> None:
        metrics = dict(metrics)
        load = metrics.pop("moe/expert_load", None)
        if load is not None:
            # the per-expert vector of a dropless MoE loss
            # (models.lm_loss_moe) is no scalar to print: it goes to the
            # ``moe_dispatch`` trace event and the ``moe_expert_load``
            # gauges; that path has no capacity and pads nothing
            from chainermn_tpu.parallel.moe import record_moe_dispatch

            record_moe_dispatch({
                "expert_load": load, "padded": 0.0, "capacity": 0.0,
                "dropped": metrics.get("moe/dropped", 0.0)})
        host_metrics = {
            k: float(jax.device_get(v)) for k, v in metrics.items()
        }
        # Cross-rank aggregation so EVERY rank holds the global
        # metrics (one host collective per log point; all ranks
        # reach this branch at the same iteration). Rank-0's
        # pretty-print keeps its LOCAL values, unchanged.
        agg = self._obs_agg(host_metrics)
        self.observation = agg if agg is not None else dict(host_metrics)
        rate = self.iteration / (time.perf_counter() - t0)
        pretty = " ".join(f"{k}={v:.4f}" for k, v in host_metrics.items())
        self._log(
            f"iter {self.iteration}/{max_iterations} {pretty} "
            f"({rate:.1f} it/s)"
        )

    def consume_phase_window(self) -> dict[str, float]:
        """Mean seconds per step-timeline phase (data_wait / h2d /
        compute / logging / extensions) since the last call, then reset —
        the straggler monitor's per-window input. Local, no collective."""
        n = max(1, self._phase_steps)
        out = {k: v / n for k, v in self._phase_sums.items()}
        self._phase_sums = {}
        self._phase_steps = 0
        return out
