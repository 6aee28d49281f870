"""BENCHMARK.json and the files the harness finds by name."""
import json
import os

import bench_cells  # noqa: F401  (puts the checkout on sys.path)
import pytest

from bench import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
            "per_layer"}


def test_top_level_keys_and_paths():
    assert set(BENCH) == TOP_KEYS
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    assert os.path.isfile(os.path.join(spec.ROOT, BENCH["command"][1]))
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_sources():
    spec.check_names(BENCH)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("bad", ["has space", "a,b", "a/b", "", "é", "x" * 65])
def test_bad_names_are_refused(bad):
    broken = json.loads(json.dumps(BENCH))
    broken["per_layer"][0]["name"] = bad
    with pytest.raises(spec.SpecError):
        spec.check_names(broken)


def test_a_duplicate_cell_is_refused():
    broken = json.loads(json.dumps(BENCH))
    broken["workloads"].append(dict(broken["workloads"][0]))
    with pytest.raises(spec.SpecError):
        spec.check_names(broken)


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_finds_its_files(name):
    cell = spec.cell(name)
    assert spec.driver(cell["traffic"]["kind"]).Driver
    e2e = [m["name"] for m in cell["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(spec.reader(m["name"]))
    assert cell["limits"] and all(v is not None for v in cell["limits"].values())


def test_every_config_file_is_used_once():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_unknown_workload_and_metric():
    with pytest.raises(spec.SpecError):
        spec.cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.reader("no_such_metric")
    with pytest.raises(spec.SpecError):
        spec.driver("no_such_kind")
