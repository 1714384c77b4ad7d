"""α–β cost-model contract (chainermn_tpu.parallel.cost_model).

ISSUE 16's schedule search is only admissible if the model is audited,
never trusted blind — so the tests pin exactly that contract:

- stage terms reproduce the ring arithmetic (ar == rs>ag by
  construction, su free, ag prices the gathered size, bc prices
  tree_sends) and sliced pricing is the software pipeline's critical
  path (max within an issue tick, sum across);
- a fit ROUND-TRIPS the rows it was fitted from within its own stated
  ``fit_err_pct`` (the tolerance callers gate adoptions against), and
  recovers a synthetic ground-truth model near-exactly;
- rank order is deterministic across runs and candidate orderings;
- the UNCALIBRATED degrade is loud: no rows for the mesh shape →
  mode ``exhaustive``, provenance ``forced:uncalibrated``, every
  candidate measured — never a ranking off a default model;
- on a recorded composed sweep's rows (tests/data/) the predicted winner
  lands inside the measured spread gate of the measured best (the
  acceptance criterion).
"""

import json
import os
import random

import pytest

from chainermn_tpu.parallel.composition import (
    canonical_axis_names,
    derive_compositions,
    tree_sends,
)
from chainermn_tpu.parallel.cost_model import (
    UNCALIBRATED,
    WIRE_ITEMSIZE,
    CostModel,
    fit_pipeline_rows,
    load_from_bench_details,
    model_error_pct,
    rank_compositions,
    stage_terms,
)
from chainermn_tpu.parallel.composition import compile_schedule

SHAPE3 = (2, 2, 2)
AXES3 = canonical_axis_names(3)
PAYLOAD = 1 << 20  # 1 MiB — the bench's composed-phase payload


def _model(alphas, betas, shape=SHAPE3, source="fit:test"):
    return CostModel(world_shape=tuple(shape), alphas=tuple(alphas),
                     betas=tuple(betas), source=source, fit_err_pct=0.0)


def _grid_sigs(shape=SHAPE3):
    axes = canonical_axis_names(len(shape))
    return [c.signature() for c in derive_compositions(axes)]


class TestStageTerms:
    def test_ar_equals_rs_ag_by_construction(self):
        """The ring arithmetic prices ar(X) and rs(X)>ag(X)
        identically — the model family cannot split them, so the rank
        tie-break (signature string) is what keeps order stable."""
        m = _model([0.1, 0.2, 0.5], [1e-6, 2e-6, 4e-6])
        assert m.predict("ar(a0+a1+a2)", PAYLOAD) == pytest.approx(
            m.predict("rs(a0+a1+a2)>ag(a0+a1+a2)", PAYLOAD))

    def test_su_is_free(self):
        m = _model([0.1, 0.2, 0.5], [1e-6, 2e-6, 4e-6])
        assert m.predict("rs(a0+a1+a2)>su>ag(a0+a1+a2)",
                         PAYLOAD) == pytest.approx(
            m.predict("rs(a0+a1+a2)>ag(a0+a1+a2)", PAYLOAD))

    def test_level_is_slowest_member(self):
        """A merged group rides its slowest member's wire: a0 is the
        slow level, so a group containing a0 prices off level 0."""
        comp = compile_schedule("rs(a2)>ar(a0+a1)>ag(a2)", AXES3)
        rows = stage_terms(comp, PAYLOAD // WIRE_ITEMSIZE, SHAPE3)
        assert [lvl for _, lvl, _, _ in rows] == [2, 0, 2]
        # only the level-0 alpha charged: ar over the merged (a0,a1)
        # pair has n=4 -> 2(n-1) = 6 steps
        slow = _model([1.0, 0.0, 0.0], [0.0, 0.0, 0.0])
        assert slow.predict("rs(a2)>ar(a0+a1)>ag(a2)",
                            PAYLOAD) == pytest.approx(6.0)

    def test_allgather_prices_output_size(self):
        """ag's wire bytes follow the GATHERED size: after rs(a0+a1+a2)
        the shard is 1/8, and ag moves (n-1)/n of the FULL buffer —
        identical wire to the rs leg, not 1/8th of it."""
        comp = compile_schedule("rs(a0+a1+a2)>ag(a0+a1+a2)", AXES3)
        rows = stage_terms(comp, PAYLOAD // WIRE_ITEMSIZE, SHAPE3)
        (_, _, _, wire_rs), (_, _, _, wire_ag) = rows
        assert wire_ag == pytest.approx(wire_rs)

    def test_bc_prices_tree_sends(self):
        m = _model([1.0, 0.0, 0.0], [0.0, 0.0, 0.0])
        # bc over all 3 axes: n=8, radix 2 -> tree_sends = 3 steps
        assert m.predict("bc(a0+a1+a2)", PAYLOAD) == pytest.approx(
            float(tree_sends(8, 2)))
        assert m.predict("bc(a0+a1+a2)@4", PAYLOAD) == pytest.approx(
            float(tree_sends(8, 4)))

    def test_sliced_is_critical_path_not_sum(self):
        """S slices of a 2-stage pipeline cost S+1 ticks, not 2S: the
        fast stage hides behind the slow one, which is exactly why the
        model can rank sliced arms without measuring them."""
        m = _model([1.0, 1.0, 1.0], [0.0, 0.0, 0.0])
        flat_sig = "rs(a2)>rs(a0+a1)>ag(a0+a1)>ag(a2)"
        flat = m.predict(flat_sig, PAYLOAD)
        sliced = m.predict(
            "rs(a2)[s0..3]>rs(a0+a1)>ag(a0+a1)>ag(a2)", PAYLOAD)
        # flat: per-stage steps [1,3,3,1] -> 8. Sliced S=4: ticks 0..6
        # cost max-of-members [1,3,3,3,3,3,1] -> 17, NOT the 32 a
        # serial rendering of 4 slices would pay.
        assert flat == pytest.approx(8.0)
        assert sliced == pytest.approx(17.0)

    def test_zigzag_prices_like_contiguous(self):
        """Zigzag changes the cut pattern, not the per-slice sizes —
        the model must price the layouts identically."""
        m = _model([0.3, 0.2, 0.1], [1e-6, 2e-6, 3e-6])
        a = m.predict("rs(a2)[s0..3]>rs(a0+a1)>ag(a0+a1)>ag(a2)", PAYLOAD)
        b = m.predict("rs(a2)[z0..3]>rs(a0+a1)>ag(a0+a1)>ag(a2)", PAYLOAD)
        assert a == pytest.approx(b)


class TestFit:
    def test_recovers_synthetic_ground_truth(self):
        """Rows generated BY a known model fit back to near-zero
        residual — the fit's sanity anchor."""
        truth = _model([0.12, 0.25, 0.56],
                       [9e-7, 9.5e-7, 1.1e-6])
        rows = {s: truth.predict(s, PAYLOAD) for s in _grid_sigs()}
        fitted = fit_pipeline_rows(rows, SHAPE3, PAYLOAD)
        assert fitted.fit_err_pct < 0.1
        for s, ms in rows.items():
            assert fitted.predict(s, PAYLOAD) == pytest.approx(
                ms, rel=1e-3)

    def test_round_trips_within_stated_tolerance(self):
        """THE contract: a fitted model reproduces the rows it was
        fitted from within its own stated fit_err_pct — noisy rows
        included."""
        truth = _model([0.12, 0.25, 0.56], [9e-7, 9.5e-7, 1.1e-6])
        rng = random.Random(7)
        rows = {s: truth.predict(s, PAYLOAD) * rng.uniform(0.85, 1.15)
                for s in _grid_sigs()}
        fitted = fit_pipeline_rows(rows, SHAPE3, PAYLOAD)
        # fit_err_pct is rounded to 3 decimals of a percent — allow
        # exactly that rounding slack, nothing more
        tol = (fitted.fit_err_pct + 1e-3) / 100.0
        for s, ms in rows.items():
            assert abs(fitted.predict(s, PAYLOAD) - ms) <= tol * abs(ms)
        assert fitted.fit_rows == tuple(sorted(rows))

    def test_coefficients_are_physical(self):
        """Non-negative α/β even on adversarial rows: a step or a byte
        never pays back time."""
        rng = random.Random(3)
        rows = {s: rng.uniform(1.0, 10.0) for s in _grid_sigs()}
        fitted = fit_pipeline_rows(rows, SHAPE3, PAYLOAD)
        assert all(a >= 0.0 for a in fitted.alphas)
        assert all(b >= 0.0 for b in fitted.betas)

    def test_refuses_underdetermined(self):
        from chainermn_tpu.parallel.composition import CompositionError

        with pytest.raises(CompositionError, match=">= 2"):
            fit_pipeline_rows({"ar(a0+a1+a2)": 3.2}, SHAPE3, PAYLOAD)


class TestRank:
    def test_deterministic_across_runs_and_orderings(self):
        m = _model([0.12, 0.25, 0.56], [9e-7, 9.5e-7, 1.1e-6])
        sigs = _grid_sigs()
        first = rank_compositions(m, sigs, PAYLOAD, k=3)
        again = rank_compositions(m, sigs, PAYLOAD, k=3)
        shuffled = list(sigs)
        random.Random(11).shuffle(shuffled)
        reordered = rank_compositions(m, shuffled, PAYLOAD, k=3)
        assert first.order == again.order == reordered.order
        assert first.predicted_ms == reordered.predicted_ms
        assert first.measured == first.order[:3]
        assert first.skipped == first.order[3:]
        assert first.mode == "topk"
        assert first.provenance == "cost_model:fit:test"
        # no silent coverage loss: every skipped arm keeps its price
        assert all(s in first.predicted_ms for s in first.skipped)

    def test_uncalibrated_degrades_loudly(self):
        """model=None → exhaustive with forced:uncalibrated — a
        ranking is never built on a default-initialized model."""
        sigs = _grid_sigs()
        r = rank_compositions(None, sigs, PAYLOAD, k=3)
        assert r.mode == "exhaustive"
        assert r.provenance == UNCALIBRATED
        assert r.measured == tuple(sigs)
        assert r.skipped == ()
        assert r.predicted_ms == {}

    def test_exhaustive_requested(self):
        m = _model([0.1, 0.2, 0.5], [1e-6, 2e-6, 4e-6])
        r = rank_compositions(m, _grid_sigs(), PAYLOAD, mode="exhaustive")
        assert r.mode == "exhaustive"
        assert r.provenance == "exhaustive:requested"
        assert r.skipped == ()


class TestBenchDetailsRows:
    """The acceptance criterion, on a recorded composed sweep (the rows
    of the last committed BENCH_DETAILS.json, kept as a fixture now
    that the file is a per-run output)."""

    DETAILS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "bench_details_composed_cpu.json")

    def _rows(self):
        with open(self.DETAILS) as f:
            data = json.load(f)
        return data, data["composed_schedule_ms"]

    def test_fit_loads_and_round_trips(self):
        data, rows = self._rows()
        model = load_from_bench_details(self.DETAILS)
        assert model is not None
        assert model.source == "fit:bench_details"
        assert model.world_shape == tuple(data["composed_world_shape"])
        payload = int(float(data.get("composed_payload_mb", 1)) * (1 << 20))
        # fit_err_pct is stored round(err*100, 3): it can understate the
        # true worst residual by half an ULP of that rounding (5e-4 pct
        # points), so allow exactly that margin on top.
        tol = (model.fit_err_pct + 5e-4) / 100.0
        for s, ms in rows.items():
            assert abs(model.predict(s, payload) - float(ms)) <= (
                tol * abs(float(ms)))

    def test_predicted_winner_inside_spread_gate(self):
        """rank_compositions reproduces the measured winner INSIDE the
        spread gate: the predicted-best arm's measured time is within
        measured-best · (1 + spread/100)."""
        data, rows = self._rows()
        model = load_from_bench_details(self.DETAILS)
        payload = int(float(data.get("composed_payload_mb", 1)) * (1 << 20))
        spread = float(data.get("composed_spread_pct", 10.0)) or 10.0
        r = rank_compositions(model, list(rows), payload, k=3)
        assert r.mode == "topk"
        best_measured = min(float(v) for v in rows.values())
        predicted_winner_measured = float(rows[r.measured[0]])
        gate = best_measured * (1.0 + spread / 100.0)
        assert predicted_winner_measured <= gate, (
            f"predicted winner {r.measured[0]} measured "
            f"{predicted_winner_measured} vs gate {gate}")
        # and the model's own audit number on these rows sits inside
        # the spread (the topk-adoption condition the seeding uses)
        err = model_error_pct(r.predicted_ms, rows)
        assert err is not None and err <= spread

    def test_shape_mismatch_returns_none(self):
        assert load_from_bench_details(
            self.DETAILS, world_shape=(4, 4)) is None

    def test_missing_file_returns_none(self, tmp_path):
        assert load_from_bench_details(str(tmp_path / "nope.json")) is None

    def test_rowless_file_returns_none(self, tmp_path):
        p = tmp_path / "empty.json"
        p.write_text(json.dumps({"device_kind": "cpu"}))
        assert load_from_bench_details(str(p)) is None

    def test_underdetermined_rows_return_none(self, tmp_path):
        """A prior TOP-K capture leaves only the arms it measured; an
        interpolating fit over < 2k+1 rows would round-trip perfectly
        while extrapolating garbage to the skipped arms — the one
        failure mode the audit cannot see. load refuses it."""
        p = tmp_path / "topk.json"
        p.write_text(json.dumps({
            "composed_schedule_ms": {
                "ar(a0+a1+a2)": 3.2,
                "rs(a0+a1+a2)>ag(a0+a1+a2)": 3.3,
                "rs(a1+a2)>ar(a0)>ag(a1+a2)": 3.6,
                "rs(a2)>ar(a0+a1)>ag(a2)": 3.9,
            },
            "composed_world_shape": [2, 2, 2],
            "composed_payload_mb": 1,
        }))
        assert load_from_bench_details(str(p)) is None


class TestModelError:
    def test_max_relative_error(self):
        err = model_error_pct({"a": 1.0, "b": 2.0}, {"a": 1.1, "b": 2.0})
        assert err == pytest.approx(100.0 / 11.0, abs=0.01)

    def test_no_overlap_is_none(self):
        assert model_error_pct({"a": 1.0}, {"b": 1.0}) is None


class TestSchedSearchTraceEvent:
    """The search's audit record on the trace plane: emit -> one
    ``sched_search`` event; summarize_overlap turns it into the
    predicted-vs-measured rows (skipped arms still priced) and the
    composition rows above gain the predicted_ms column."""

    def test_emit_and_summarize(self):
        from chainermn_tpu.observability import trace
        from chainermn_tpu.parallel.cost_model import (
            emit_sched_search_event,
        )

        model = _model([1.0] * 3, [0.0] * 3)
        sigs = _grid_sigs()
        rank = rank_compositions(model, sigs, PAYLOAD, k=2)
        rec = trace.enable(None)
        try:
            measured = {s: rank.predicted_ms[s] * 1.05
                        for s in rank.measured}
            err = emit_sched_search_event(rank, measured,
                                          spread_pct=10.0)
            # |pred - meas| / meas = 0.05/1.05
            assert err == pytest.approx(100 * 0.05 / 1.05, abs=0.01)
            evs = [e for e in rec.events
                   if e.get("kind") == "sched_search"]
            assert len(evs) == 1
            ev = evs[0]
            assert ev["mode"] == "topk"
            assert ev["provenance"] == "cost_model:fit:test"
            assert ev["err_pct"] == err
            assert ev["spread_pct"] == 10.0
            # summarizer: rows for every arm, skipped flagged, and a
            # composition row picks up the predicted column
            wire = {"kind": "wire", "composition": rank.measured[0],
                    "schedule": rank.measured[0],
                    "stage": rank.measured[0], "stage_op": "all-reduce",
                    "nbytes": 64, "stage_index": 0}
            ov = trace.summarize_overlap([wire] + rec.events)
            ss = ov["sched_search"]
            assert ss["mode"] == "topk" and ss["err_pct"] == err
            assert set(ss["rows"]) == set(sigs)
            for s in rank.skipped:
                assert ss["rows"][s]["skipped"] is True
                assert "predicted_ms" in ss["rows"][s]
            comp_row = ov["compositions"][rank.measured[0]]
            assert comp_row["predicted_ms"] == pytest.approx(
                rank.predicted_ms[rank.measured[0]], abs=1e-3)
        finally:
            trace.disable()

    def test_no_recorder_still_returns_error(self):
        from chainermn_tpu.observability import trace
        from chainermn_tpu.parallel.cost_model import (
            emit_sched_search_event,
        )

        assert trace.active() is None
        model = _model([1.0] * 3, [0.0] * 3)
        rank = rank_compositions(model, _grid_sigs(), PAYLOAD, k=2)
        err = emit_sched_search_event(
            rank, {s: rank.predicted_ms[s] for s in rank.measured})
        assert err == 0.0
