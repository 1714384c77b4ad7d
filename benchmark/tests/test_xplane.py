"""The trace reduction: on a synthetic table whose answers can be worked
by hand, and on the event table recorded from one traced run of
``gpt2m-podshare-dp4`` on the v5e (``data/``), whose numbers are pinned."""

import os

import pytest

import trace_table
import xplane

CATS = {"mm": "matmul", "flash": "mosaic", "ar": "collective",
        "ar-start.1": "collective", "ar-done.1": "collective"}


def _table():
    # two whole runs of the module, [0, 100) and [100, 200) ns; the op
    # line is sequential, as a TPU core's is
    ops = [
        ["mm", 0, 40], ["flash", 40, 10],
        ["ar", 50, 20],                          # synchronous: all exposed
        ["other", 80, 20],                       # idle 70-80
        ["ar-start.1", 100, 5], ["mm", 105, 45],
        ["ar-done.1", 160, 10],                  # in flight 100-170
        ["other", 175, 25],                      # idle 170-175
    ]
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "async": [],
        "modules": [["jit_step(1)", 0, 100], ["jit_step(1)", 100, 100]]}},
        "host_spans": [["bench.wait", 60, 30], ["bench.dispatch", 165, 20]]}


def test_reduction_by_hand():
    r = xplane.reduce(_table(), CATS, "jit_step")["/device:TPU:0"]
    assert r["steps"] == 2 and r["window_ns"] == 200
    assert r["busy_ns"] == 200 - 10 - 5  # idle 70-80 and 170-175 only
    assert r["category_ns"] == {"matmul": 85, "mosaic": 10,
                                "collective": 35, "other": 45}
    assert r["collective_flight_ns"] == 20 + 70
    # exposed: all of the synchronous one, and 100-105 and 150-170 of the
    # pair's flight (the matmul hides 105-150)
    assert r["collective_exposed_ns"] == 20 + 25
    assert r["gaps"] == [("bench.wait", 10), ("bench.dispatch", 5)]
    assert r["ops"][0] == ("mm", "matmul", 85)
    out = xplane.breakdown({"d": r}, {"mm": "jvp(LM)/block_3/ff_up/dot"})
    assert out["device_ops"][0] == ["[matmul] jvp(LM)/block*/ff_up/dot",
                                    85 / 2 / 1e9]
    assert out["idle_gaps"][0] == ["bench.wait", 10 / 1e9]


def test_a_loop_does_not_count_its_body_twice():
    t = _table()
    dev = t["devices"]["/device:TPU:0"]
    dev["ops"] += [["while.1", 105, 47]]  # spans the second "mm"
    r = xplane.reduce(t, CATS, "jit_step")["/device:TPU:0"]
    assert r["category_ns"]["matmul"] == 85
    assert r["category_ns"]["other"] == 45 + 2  # the loop's own time only


def test_the_async_line_gives_a_collective_its_flight():
    t = _table()
    dev = t["devices"]["/device:TPU:0"]
    dev["async"] = [["ar-start.1", 100, 68]]  # the runtime's own record
    r = xplane.reduce(t, CATS, "jit_step")["/device:TPU:0"]
    assert r["collective_flight_ns"] == 20 + 68
    assert r["collective_exposed_ns"] == 20 + 23


def test_first_run_is_left_out_of_a_long_trace():
    t = _table()
    dev = t["devices"]["/device:TPU:0"]
    dev["modules"] = [["jit_step(1)", i * 100, 100] for i in range(5)]
    dev["ops"] = [["mm", i * 100, 60] for i in range(5)]
    r = xplane.reduce(t, CATS, "jit_step")["/device:TPU:0"]
    assert r["steps"] == 4 and r["window_ns"] == 400
    assert r["busy_ns"] == 240


def test_nothing_to_read_gives_nothing():
    assert xplane.reduce({"devices": {}, "host_spans": []}, {}) == {}
    assert xplane.mean_over_devices({}, lambda r: 1) is None
    assert xplane.breakdown({}) == {}


DATA = os.path.join(os.path.dirname(__file__), "data")


def _recorded(cell):
    """``trace_table.cut``'s cut of one ``--trace 1`` run of ``cell`` on
    the v5e (PR 22): two whole steps on each device, with the categories
    of the step's HLO beside them."""
    table = trace_table.load(os.path.join(DATA, cell + ".table.json.gz"))
    return xplane.reduce(table, table["categories"], table["module"])


def _layer_metric(name, reduced, mosaic_calls=72):
    import spec

    ctx = {"trace": reduced, "loop": {"mosaic_calls": mosaic_calls}}
    return spec.Roots().module("layer_metrics", name).read(ctx)


def test_pinned_on_the_recorded_one_chip_trace():
    r = _recorded("gpt2m-podshare-1chip")
    dev = r["/device:TPU:0"]
    assert (dev["steps"], dev["window_ns"], dev["busy_ns"]) == (
        2, 197384255, 197304427)
    assert dev["category_ns"] == {"other": 33256117, "matmul": 112529499,
                                  "mosaic": 51518811}
    # the categories add up to the busy time: nothing counted twice
    assert sum(dev["category_ns"].values()) == pytest.approx(
        dev["busy_ns"], rel=1e-4)
    assert _layer_metric("matmul_ms", r) == pytest.approx(56.2647495)
    assert _layer_metric("flash_ms", r) == pytest.approx(25.7594055)
    assert _layer_metric("device_idle_pct", r) == pytest.approx(
        0.040442942, rel=1e-6)
    assert _layer_metric("allreduce_exposed_ms", r) == 0.0  # one chip
    assert dev["gaps"][0] == ("bench.wait", 34566)


def test_pinned_on_the_recorded_dp4_trace():
    """The four chips of ``gpt2m-podshare-dp4``: six synchronous
    all-reduces a step, none hidden behind compute."""
    r = _recorded("gpt2m-podshare-dp4")
    assert sorted(r) == [f"/device:TPU:{i}" for i in range(4)]
    dev = r["/device:TPU:0"]
    assert (dev["steps"], dev["window_ns"], dev["busy_ns"]) == (
        2, 234101409, 234012117)
    assert dev["category_ns"] == {"other": 55398057, "matmul": 102451548,
                                  "mosaic": 51539086,
                                  "collective": 24623426}
    assert dev["collective_flight_ns"] == dev["collective_exposed_ns"] \
        == 24623426
    assert sum(dev["category_ns"].values()) == pytest.approx(
        dev["busy_ns"], rel=1e-4)
    assert _layer_metric("allreduce_ms", r) == pytest.approx(12.309934625)
    assert _layer_metric("allreduce_exposed_ms", r) == pytest.approx(
        12.309934625)
    assert _layer_metric("matmul_ms", r) == pytest.approx(51.228983125)
    assert _layer_metric("flash_ms", r) == pytest.approx(25.7691625)
    assert _layer_metric("device_idle_pct", r) == pytest.approx(
        0.03886297929504845)
    assert _layer_metric("flash_ms", r, mosaic_calls=0) is None
