"""Every cell of BENCHMARK.json resolves to its files, and the file keeps the
shape and limits of the benchmark's format (names, units, sources, bounds,
a run length whose full check of 24 cells fits its time budget)."""

import json
import re

import pytest

from bench import harness

BM = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BM["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert 1 <= BM["run_seconds"] <= 51
    assert BM["paths"] == ["bench"]
    assert not any(w.startswith("/") or ".." in w for w in BM["command"])
    # a full check of 24 cells fits its time budget at this run length
    assert 1200 + (2 + 14 * 24) * (BM["run_seconds"] + 60) + 24 * 180 <= 43200
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_sources():
    names = ([c["name"] for c in BM["configs"]] + CELLS
             + [m["name"] for m in BM["end_to_end"] + BM["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in BM["end_to_end"]}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in BM["end_to_end"]}


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = harness.resolve(name)
    assert cell.chips in (1, 4)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        # each metric read in a cell moves an end-to-end metric the cell reports
        assert m["moves"] in reported
        reader = harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    assert set(cell.limits) & {"loss", "grad", "change"}
    assert callable(cell.model.param_spec) and callable(cell.model.forward)
    conf = {c["name"]: c for c in BM["configs"]}[
        {w["name"]: w for w in BM["workloads"]}[name]["config"]]
    assert conf["reduced"] == cell.config["reduced"]
    assert all(NAME.match(k) for k in conf["reduced"])


def test_every_config_is_used_and_files_are_distinct():
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}
    files = [c["file"] for c in BM["configs"]]
    assert len(files) == len(set(files))
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))
