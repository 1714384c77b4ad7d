"""BENCHMARK.json against the contract's limits and against the files it
names: the committed one, and the one a later PR would have after adding
cells, configurations, mixes and a per-layer metric (``conftest.py``:
``added``), which is held to the same rules without an edit here."""

import os
import re

import pytest

import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

#: what ISSUE 22 asked this benchmark to hold, letter for letter; a later
#: PR adds to these and the test below still holds
ISSUE_22 = {
    "workloads": ["gpt2m-hostfill-1chip", "gpt2m-podshare-1chip",
                  "gpt2m-podshare-dp4", "resnet50-hostfill-1chip"],
    "configs": ["gpt2-medium", "resnet50"],
    "end_to_end": ["tokens_per_s", "images_per_s", "step_ms", "mfu_pct",
                   "setup_s"],
    "per_layer": ["compile_s", "input_wait_ms", "peak_hbm_gb",
                  "allreduce_ms", "allreduce_exposed_ms", "matmul_ms",
                  "flash_ms", "flash_roofline_pct", "device_idle_pct"],
}


@pytest.fixture(params=["committed", "extended"])
def case(request, added):
    """``(benchmark, roots)``."""
    if request.param == "committed":
        return spec.load_benchmark(), spec.Roots()
    roots, benchmark = added
    return benchmark, roots


def test_keys_names_and_limits(case):
    b, _ = case
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark"] and 1 <= b["run_seconds"] <= 51
    assert 1 <= len(b["command"]) <= 32
    assert 1 <= len(b["configs"]) <= 24 and 2 <= len(b["workloads"]) <= 24
    assert 1 <= len(b["end_to_end"]) <= 16
    assert 1 <= len(b["per_layer"]) <= 128
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        names = [e["name"] for e in b[group]]
        assert len(names) == len(set(names))
        for e in b[group]:
            assert set(e) == keys
            assert NAME.match(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    cells = {w["name"] for w in b["workloads"]}
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_the_committed_file_is_small():
    assert os.path.getsize(os.path.join(spec.CHECKOUT, "BENCHMARK.json")) \
        < 64 * 1024


def test_cells_pairs_chips_and_configurations(case):
    b, _ = case
    cells = b["workloads"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs)), "a pair of config and mix twice"
    assert all(w["chips"] in (1, 4) for w in cells)
    assert all(NAME.match(w["config"]) and NAME.match(w["traffic"])
               for w in cells)
    # at most a quarter of the cells, rounded down, take four chips; one
    # always may
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    configs = [c["name"] for c in b["configs"]]
    assert {w["config"] for w in cells} == set(configs)  # each is used
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith(b["paths"][0] + "/") for f in files)
    for c in b["configs"]:
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16


def test_issue_22s_names_are_all_there(case):
    b, _ = case
    for group, names in ISSUE_22.items():
        assert set(names) <= {e["name"] for e in b[group]}, group


def test_every_entry_has_its_files_and_they_agree(case):
    b, roots = case
    for w in b["workloads"]:
        cell = spec.load_cell(roots, w["name"])
        for k in ("config", "traffic", "chips", "why"):
            assert cell[k] == w[k], (w["name"], k)
        family = cell["config_spec"]["family"]
        assert roots.path("families", family + ".py")
        assert roots.path("reference", family + ".py")
        assert roots.path("loops", cell["mix"]["loop"] + ".py")
    for c in b["configs"]:
        # ``file`` counts from the checkout; a root stands for benchmark/
        on_disk = roots.json(*c["file"].split("/")[1:])
        assert on_disk["source"] == c["source"]
        assert on_disk["reduced"] == c["reduced"]
    end_to_end = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        reader = roots.module("layer_metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.BETTER,
                reader.SOURCE) == (m["layer"], m["unit"], m["moves"],
                                   m["better"], m["source"])
        assert m["moves"] in end_to_end


def test_every_cell_reports_what_the_contract_asks(case):
    b, _ = case
    for w in b["workloads"]:
        e2e = {m["name"] for m in spec.metrics_of(w["name"], b["end_to_end"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = spec.metrics_of(w["name"], b["per_layer"])
        assert layer and all(m["moves"] in e2e for m in layer)


def test_files_under_paths_are_named_from_a_names_characters(case):
    _, roots = case
    bad = []
    for root in roots.dirs:
        for d, dirs, files in os.walk(root):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            bad += [os.path.join(d, f) for f in files
                    if not re.fullmatch(r"[A-Za-z0-9_.\-]+", f)]
    assert not bad
