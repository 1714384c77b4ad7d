"""α–β cost model for composed reduction schedules (ISSUE 16).

PR 11's deriver enumerates ``2^k`` legal pipelines per mesh and PR 15's
slicing multiplied that by slice-count arms; brute-force measurement of
the grid stops scaling past ~3 mesh levels. This module prices every
derived pipeline (sliced variants included) with a per-LEVEL α–β model
— the HiCCL-style decomposition (arXiv:2408.05962): each mesh level ℓ
has a latency coefficient ``α_ℓ`` (ms per ring step — the per-hop
fixed cost) and a bandwidth coefficient ``β_ℓ`` (ms per wire byte),
and a stage over a merged axis group costs ``steps·α_ℓ + wire·β_ℓ``
where ℓ is the SLOWEST member level of the group (axis 0 is the
slow/DCN-most level, the repo's mesh convention — merging a fast axis
into a slow group rides the slow wire).

Stage terms (``n`` = merged group size, ``b`` = payload bytes through
the stage — the ring-algorithm arithmetic):

- ``rs`` / ``ag``: ``n-1`` steps, ``((n-1)/n)·b`` wire bytes;
- ``ar``: ``2(n-1)`` steps, ``2((n-1)/n)·b`` (reduce-scatter +
  all-gather fused);
- ``bc``: ``tree_sends(n, radix)`` steps, ``tree_sends·b`` wire (every
  sub-send moves the full buffer along the donor path);
- ``su``: free (owes the wire nothing).

A SLICED composition is priced as its software pipeline's critical
path: the skewed issue order puts stage j of slice i at tick ``i+j``,
concurrent stages within a tick overlap, so the tick costs the MAX of
its members and the pipeline costs the sum over ticks — which is
exactly why slicing can win (the slow inter-level stage hides behind
the fast one) and why the model can rank sliced arms without measuring
them.

FIT SOURCES, in trust order:

- :func:`fit_pipeline_rows` — least squares over the whole-pipeline
  medians the bench already measured (``composed_schedule_ms`` rows in
  BENCH_DETAILS.json): k levels give 2k unknowns, the 8-arm grid gives
  8 equations, overdetermined from 3 levels down. This is the offline
  path :func:`load_from_bench_details` rides.
- :func:`calibrate` — a short live probe (whole-pipeline wall clocks
  through :class:`~chainermn_tpu.parallel.reduction_schedule.
  MeasuredComposedReducer`, median of n repeats) fitted the same way,
  for a box with no bench rows yet.

NEVER TRUSTED BLIND: :func:`rank_compositions` with ``model=None``
(no rows for this mesh shape) returns mode ``exhaustive`` with
provenance ``forced:uncalibrated`` — rank on a default-initialized
model is the failure mode this module refuses by construction — and
:func:`emit_sched_search_event` puts every top-k ranking's
predicted-vs-measured error into the trace, so a model that drifts past
the measurement spread shows.
"""

from __future__ import annotations

import dataclasses
import json
import statistics
import time
from typing import Mapping, Optional, Sequence

from chainermn_tpu.parallel.composition import (
    Composition,
    CompositionError,
    DEFAULT_RADIX,
    _replay_sizes,
    canonical_axis_names,
    compact_slices,
    compile_schedule,
    effective_slices,
    slice_bounds,
    tree_sends,
)

#: The composed wire is f32 (the executor reduces f32 buffers).
WIRE_ITEMSIZE = 4

#: Provenance string for the forced-exhaustive degrade — the loud
#: spelling ISSUE 16 pins (never rank on a default-initialized model).
UNCALIBRATED = "forced:uncalibrated"


def stage_terms(
    comp: Composition,
    n_elems: int,
    world_shape: Sequence[int],
    mesh_axes: Optional[Sequence[str]] = None,
) -> list[tuple[int, int, float, float]]:
    """Per-stage model terms for ONE pipeline (unsliced rendering) of
    ``n_elems`` f32 elements: ``(tick, level, steps, wire_bytes)``
    rows, one per collective stage per slice. ``tick`` is the software-
    pipeline issue tick (``slice + stage_index``; 0.. for the unsliced
    rendering) — :func:`predict` maxes within a tick and sums across.

    ``mesh_axes`` defaults to the canonical positional tokens; pass the
    actual mesh names when pricing a bound composition."""
    shape = tuple(int(d) for d in world_shape)
    names = (tuple(mesh_axes) if mesh_axes is not None
             else canonical_axis_names(len(shape)))
    if len(names) != len(shape):
        raise CompositionError(
            f"world shape {shape} and mesh axes {names} disagree"
        )
    axis_sizes = {a: shape[i] for i, a in enumerate(names)}
    level_of = {a: i for i, a in enumerate(names)}
    comp = compact_slices(comp)
    s_eff = effective_slices(comp.slices, int(n_elems))

    def rows_for(elems: int, slice_i: int) -> list:
        out = []
        replayed, _, _ = _replay_sizes(comp.stages, elems, axis_sizes)
        for j, (st, size_in, size_out) in enumerate(replayed):
            if st.primitive == "sharded_update":
                continue
            n = 1
            for a in st.axes:
                n *= axis_sizes[a]
            level = min(level_of[a] for a in st.axes)
            if st.primitive == "broadcast":
                sends = tree_sends(n, st.radix or DEFAULT_RADIX)
                steps = sends
                wire = float(sends * size_in * WIRE_ITEMSIZE)
            elif st.primitive == "allreduce":
                steps = 2 * (n - 1)
                wire = 2.0 * (n - 1) / n * size_in * WIRE_ITEMSIZE
            elif st.primitive == "reduce_scatter":
                steps = n - 1
                wire = float(n - 1) / n * size_in * WIRE_ITEMSIZE
            else:  # allgather: the gathered (output) size rides the wire
                steps = n - 1
                wire = float(n - 1) / n * size_out * WIRE_ITEMSIZE
            out.append((slice_i + j, level, steps, wire))
        return out

    if s_eff <= 1:
        return rows_for(int(n_elems), 0)
    rows = []
    for i, (lo, hi) in enumerate(slice_bounds(int(n_elems), s_eff)):
        rows.extend(rows_for(hi - lo, i))
    return rows


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Fitted per-level α–β coefficients for one world shape.

    ``alphas[ℓ]`` is ms per ring step at level ℓ, ``betas[ℓ]`` ms per
    wire byte; ``source`` is the fit provenance
    (``"fit:bench_details"`` / ``"fit:calibration"``); ``fit_err_pct``
    the max relative error of the model on the rows it was fitted from
    (the round-trip bound the tests pin); ``fit_rows`` those rows'
    signatures."""

    world_shape: tuple[int, ...]
    alphas: tuple[float, ...]
    betas: tuple[float, ...]
    source: str
    fit_err_pct: float
    fit_rows: tuple[str, ...] = ()

    def predict(
        self,
        comp,
        payload_bytes: int,
        mesh_axes: Optional[Sequence[str]] = None,
    ) -> float:
        """Predicted ms for ``comp`` (signature string or
        :class:`Composition`) moving ``payload_bytes`` through the
        wire. Sliced compositions are priced as their software
        pipeline's critical path: concurrent stages within an issue
        tick overlap (the tick costs their max), ticks serialize."""
        names = (tuple(mesh_axes) if mesh_axes is not None
                 else canonical_axis_names(len(self.world_shape)))
        if not isinstance(comp, Composition):
            comp = compile_schedule(comp, names)
        n_elems = max(1, int(payload_bytes) // WIRE_ITEMSIZE)
        ticks: dict[int, float] = {}
        for tick, level, steps, wire in stage_terms(
                comp, n_elems, self.world_shape, names):
            cost = steps * self.alphas[level] + wire * self.betas[level]
            ticks[tick] = max(ticks.get(tick, 0.0), cost)
        return float(sum(ticks.values()))


def fit_pipeline_rows(
    rows_ms: Mapping[str, float],
    world_shape: Sequence[int],
    payload_bytes: int,
    *,
    source: str = "fit:pipeline_rows",
) -> CostModel:
    """Fit the per-level α–β coefficients from whole-pipeline medians
    (``{signature: ms}`` at one world shape and payload) by
    non-negative least squares: ``k`` levels give ``2k`` unknowns and
    the composed sweep's ``2^k`` arms give the equations —
    overdetermined from 3 levels down. Coefficients are physical
    (non-negative: a step or a byte never pays back time), enforced by
    projected re-solves on the active set, and the residual of the fit
    on its own rows is stored as ``fit_err_pct`` — the model's stated
    round-trip tolerance, which callers gate adoptions against."""
    import numpy as np

    shape = tuple(int(d) for d in world_shape)
    k = len(shape)
    sigs = sorted(rows_ms)
    if len(sigs) < 2:
        raise CompositionError(
            f"fit needs >= 2 pipeline rows, got {len(sigs)}"
        )
    names = canonical_axis_names(k)
    n_elems = max(1, int(payload_bytes) // WIRE_ITEMSIZE)
    A = np.zeros((len(sigs), 2 * k))
    b = np.array([float(rows_ms[s]) for s in sigs])
    for i, sig in enumerate(sigs):
        comp = compile_schedule(sig, names)
        for _, level, steps, wire in stage_terms(
                comp, n_elems, shape, names):
            A[i, 2 * level] += steps
            A[i, 2 * level + 1] += wire
    # Column scaling (steps are O(1), bytes O(1e6)) + a tiny ridge for
    # rank-deficient grids, then clip-and-refit on the active set so
    # the returned coefficients are non-negative without distorting
    # the free ones.
    col = np.maximum(np.abs(A).max(axis=0), 1e-12)
    As = A / col
    free = np.ones(2 * k, dtype=bool)
    x = np.zeros(2 * k)
    for _ in range(2 * k + 1):
        idx = np.where(free)[0]
        if idx.size == 0:
            break
        Af = As[:, idx]
        ridge = 1e-8 * np.eye(idx.size)
        xf = np.linalg.solve(Af.T @ Af + ridge, Af.T @ b)
        neg = xf < 0
        if not neg.any():
            x = np.zeros(2 * k)
            x[idx] = xf
            break
        free[idx[neg]] = False
    coeffs = x / col
    pred = A @ coeffs
    err = float(np.max(np.abs(pred - b) / np.maximum(np.abs(b), 1e-12)))
    return CostModel(
        world_shape=shape,
        alphas=tuple(float(coeffs[2 * i]) for i in range(k)),
        betas=tuple(float(coeffs[2 * i + 1]) for i in range(k)),
        source=source,
        fit_err_pct=round(err * 100.0, 3),
        fit_rows=tuple(sigs),
    )


def load_from_bench_details(
    path: str = "BENCH_DETAILS.json",
    *,
    world_shape: Optional[Sequence[int]] = None,
) -> Optional[CostModel]:
    """Fit from the composed-sweep rows a prior bench left on disk
    (``composed_schedule_ms`` + ``composed_world_shape`` +
    ``composed_payload_mb``). Returns ``None`` — the UNCALIBRATED
    degrade, never a default model — when the file, the rows, or the
    requested mesh shape are missing/mismatched, and ALSO when the
    rows cannot overdetermine the ``2k`` coefficients (< ``2k+1``
    rows): a prior TOP-K capture leaves only the arms it measured,
    and an interpolating fit over them would round-trip perfectly
    while extrapolating garbage to the skipped arms — the one failure
    mode the predicted-vs-measured audit cannot see (the audited arms
    ARE the fit rows). Refusing keeps the cadence honest: a top-k
    capture is followed by one exhaustive sweep that restores full
    coverage, then top-k resumes.

    No program writes these rows since ``bench.py`` left (PR 28): the
    reader and ``tests/data/bench_details_composed_cpu.json`` stay with
    the rest of this module for ROADMAP D6 to adjudicate."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    rows = data.get("composed_schedule_ms")
    shape = data.get("composed_world_shape")
    payload_mb = data.get("composed_payload_mb")
    if not isinstance(rows, dict) or not shape:
        return None
    if len(rows) < 2 * len(shape) + 1:
        return None
    if world_shape is not None and tuple(int(d) for d in shape) != tuple(
            int(d) for d in world_shape):
        return None
    try:
        return fit_pipeline_rows(
            {str(k): float(v) for k, v in rows.items()},
            tuple(int(d) for d in shape),
            int(float(payload_mb or 1.0) * (1 << 20)),
            source="fit:bench_details",
        )
    except Exception:
        return None


def calibrate(
    comm,
    *,
    payload_mb: float = 1.0,
    candidates: Optional[Sequence[str]] = None,
    repeats: int = 3,
) -> CostModel:
    """Short LIVE probe: run a calibration subset of the derived
    pipelines eagerly (whole-pipeline wall clocks through
    :class:`~chainermn_tpu.parallel.reduction_schedule.
    MeasuredComposedReducer`, median of ``repeats``) and fit the same
    per-level least squares. The default subset is every derived
    composition for the communicator's mesh — at 3 levels that is the
    8-arm grid the bench measures, so calibration and bench rows are
    directly comparable."""
    import numpy as np

    from chainermn_tpu.parallel.composition import derive_compositions
    from chainermn_tpu.parallel.reduction_schedule import (
        MeasuredComposedReducer,
    )

    axes = comm.grad_axes
    axes = axes if isinstance(axes, tuple) else (axes,)
    shape = tuple(int(comm.mesh.shape[a]) for a in axes)
    if candidates is None:
        candidates = [c.signature() for c in derive_compositions(axes)]
    n_elems = max(1, int(float(payload_mb) * (1 << 20)) // WIRE_ITEMSIZE)
    rng = np.random.RandomState(0)
    stacked = {"g": np.asarray(
        rng.randn(comm.size, n_elems), np.float32)}
    rows: dict[str, float] = {}
    for sig in candidates:
        red = MeasuredComposedReducer(comm, schedule=sig)
        red.reduce(stacked)  # warm the per-stage jit caches

        def sample(red=red):
            t0 = time.perf_counter()
            red.reduce(stacked)
            return (time.perf_counter() - t0) * 1000.0

        rows[canonical_signature(sig, len(shape))] = statistics.median(
            sample() for _ in range(max(1, repeats)))
    model = fit_pipeline_rows(
        rows, shape, n_elems * WIRE_ITEMSIZE, source="fit:calibration")
    return model


def canonical_signature(sig: str, n_axes: int) -> str:
    """A signature re-spelled over the canonical positional tokens —
    the spelling fit rows and rank orders key on."""
    from chainermn_tpu.parallel.composition import signature_for

    return signature_for(sig, n_axes)


@dataclasses.dataclass(frozen=True)
class RankResult:
    """One schedule-search ranking: ``order`` is every candidate
    best-predicted-first (deterministic: ties break on the signature
    string), ``measured`` the prefix the caller should actually time,
    ``skipped`` the rest WITH their predicted costs still in
    ``predicted_ms`` (no silent coverage loss — the bench logs them).
    ``mode`` is ``"topk"`` or ``"exhaustive"``; ``provenance`` names
    why (``cost_model:<fit source>`` or ``forced:uncalibrated``)."""

    mode: str
    provenance: str
    order: tuple[str, ...]
    predicted_ms: dict[str, float]
    measured: tuple[str, ...]
    skipped: tuple[str, ...]


def rank_compositions(
    model: Optional[CostModel],
    candidates: Sequence[str],
    payload_bytes: int,
    *,
    k: int = 3,
    mesh_axes: Optional[Sequence[str]] = None,
    mode: str = "topk",
) -> RankResult:
    """Rank ``candidates`` (signature strings) by predicted cost and
    pick the top-``k`` to measure. DEGRADES LOUDLY: ``model=None``
    (no wire rows for this mesh shape) or ``mode="exhaustive"`` marks
    every candidate measured — ``forced:uncalibrated`` provenance in
    the None case, so a ranking is never silently built on a
    default-initialized model."""
    cands = tuple(dict.fromkeys(candidates))  # stable de-dup
    if model is None or mode == "exhaustive":
        return RankResult(
            mode="exhaustive",
            provenance=(UNCALIBRATED if model is None
                        else "exhaustive:requested"),
            order=cands,
            predicted_ms={},
            measured=cands,
            skipped=(),
        )
    preds = {
        sig: model.predict(sig, payload_bytes, mesh_axes)
        for sig in cands
    }
    order = tuple(sorted(cands, key=lambda s: (preds[s], s)))
    k = max(1, int(k))
    return RankResult(
        mode="topk",
        provenance=f"cost_model:{model.source}",
        order=order,
        predicted_ms={s: round(preds[s], 4) for s in order},
        measured=order[:k],
        skipped=order[k:],
    )


def emit_sched_search_event(
    rank: RankResult,
    measured_ms: Optional[Mapping[str, float]] = None,
    *,
    spread_pct: Optional[float] = None,
) -> Optional[float]:
    """One ``sched_search`` trace event — the search's audit record
    (``docs/observability.md``): every ranked arm's predicted price,
    the measured ms for the arms actually timed, and the resulting
    :func:`model_error_pct` beside the measurement spread so
    ``tools/trace_report.py`` can print predicted-vs-measured and flag
    a model past the gate LOUDLY. No-op without an active recorder;
    returns the error either way so callers gate on it."""
    from chainermn_tpu.observability import trace as _trace

    err = model_error_pct(rank.predicted_ms, measured_ms or {})
    rec = _trace.active()
    if rec is not None:
        fields: dict = {
            "mode": rank.mode,
            "provenance": rank.provenance,
            "predicted_ms": dict(rank.predicted_ms),
            "measured": list(rank.measured),
            "skipped": list(rank.skipped),
        }
        if measured_ms:
            fields["measured_ms"] = {
                k: round(float(v), 4) for k, v in measured_ms.items()
            }
        if spread_pct is not None:
            fields["spread_pct"] = round(float(spread_pct), 3)
        if err is not None:
            fields["err_pct"] = err
        rec.event("sched_search", **fields)
    return err


def model_error_pct(
    predicted_ms: Mapping[str, float],
    measured_ms: Mapping[str, float],
) -> Optional[float]:
    """Max relative predicted-vs-measured error (percent) over the
    signatures present in BOTH maps — the audit number every top-k
    ranking's ``sched_search`` event carries. None when the maps share
    nothing."""
    errs = [
        abs(predicted_ms[s] - measured_ms[s]) / max(abs(measured_ms[s]),
                                                    1e-12)
        for s in predicted_ms if s in measured_ms
    ]
    if not errs:
        return None
    return round(max(errs) * 100.0, 3)


__all__ = [
    "CostModel",
    "RankResult",
    "UNCALIBRATED",
    "WIRE_ITEMSIZE",
    "calibrate",
    "canonical_signature",
    "emit_sched_search_event",
    "fit_pipeline_rows",
    "load_from_bench_details",
    "model_error_pct",
    "rank_compositions",
    "stage_terms",
]
