"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix.  The
configuration is the file its ``configs`` entry gives; the traffic mix is
``bench/traffic/<traffic>.json``, whose ``kind`` picks the driver
``bench/drivers/<kind>.py``; the limits of the cell's ``correct`` are in
``bench/workloads/<cell>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a configuration, a traffic
mix of an existing kind or a per-layer metric is adding files.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}


class SpecError(ValueError):
    pass


def load_benchmark(path: Path = ROOT / "BENCHMARK.json") -> dict:
    with open(path) as f:
        return json.load(f)


def _json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def check_names(bench: dict) -> None:
    """The naming rules: names, units, sources, uniqueness, references."""
    seen: dict[str, set] = {"configs": set(), "workloads": set(), "metrics": set()}
    for c in bench["configs"]:
        _name(c["name"], seen["configs"])
        for k in c["reduced"]:
            _name(k, set())
    for w in bench["workloads"]:
        _name(w["name"], seen["workloads"])
        _name(w["traffic"], set())
        if w["config"] not in seen["configs"]:
            raise SpecError(f"cell {w['name']} names unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            raise SpecError(f"cell {w['name']} asks for {w['chips']} chips")
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    if len(pairs) != len(bench["workloads"]):
        raise SpecError("a (config, traffic) pair appears twice")
    e2e_names = set()
    for m in bench["end_to_end"] + bench["per_layer"]:
        _name(m["name"], seen["metrics"])
        if not UNIT.match(m["unit"]):
            raise SpecError(f"metric {m['name']}: bad unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            raise SpecError(f"metric {m['name']}: better must be lower|higher")
        for cell in m.get("workloads", []):
            if cell not in seen["workloads"]:
                raise SpecError(f"metric {m['name']} names unknown cell {cell}")
    for m in bench["end_to_end"]:
        e2e_names.add(m["name"])
        if m["source"] not in SOURCES_E2E:
            raise SpecError(f"end-to-end metric {m['name']}: source {m['source']}")
    for m in bench["per_layer"]:
        if m["source"] not in SOURCES:
            raise SpecError(f"per-layer metric {m['name']}: source {m['source']}")
        if m["moves"] not in e2e_names:
            raise SpecError(f"per-layer metric {m['name']} moves unknown {m['moves']}")


def _name(name: str, seen: set) -> None:
    if not isinstance(name, str) or not NAME.match(name):
        raise SpecError(f"bad name {name!r}")
    if name in seen:
        raise SpecError(f"name {name!r} used twice")
    seen.add(name)


def cell(name: str, bench: dict | None = None) -> dict:
    """Everything one cell runs with: its entry, configuration, traffic,
    limits and the metrics it reports."""
    bench = bench or load_benchmark()
    check_names(bench)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; one of {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return {
        "name": name,
        "chips": w["chips"],
        "config": _json(ROOT / conf["file"]),
        "traffic": _json(BENCH / "traffic" / f"{w['traffic']}.json"),
        "limits": _json(BENCH / "workloads" / f"{name}.json")["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if _reports(m, name)],
        "per_layer": per_layer_for(name, bench),
    }


def _reports(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def per_layer_for(cell_name: str, bench: dict) -> list[dict]:
    """Per-layer metrics of a cell: those that list it, and those without a
    list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"] if _reports(m, cell_name)}

    def applies(m: dict) -> bool:
        if "workloads" in m:
            return cell_name in m["workloads"]
        return m["moves"] in e2e

    return [m for m in bench["per_layer"] if applies(m)]


def driver(kind: str):
    """The module that drives a traffic kind: ``bench/drivers/<kind>.py``."""
    if not NAME.match(kind) or not (BENCH / "drivers" / f"{kind}.py").is_file():
        raise SpecError(f"no driver for traffic kind {kind!r}")
    return importlib.import_module(f"bench.drivers.{kind}")


def reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SpecError(f"no reader for per-layer metric {metric!r}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
