"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A cell is an entry of ``workloads``; its configuration is the ``file`` of the
entry of ``configs`` it names, its traffic ``traffic/<name>.json``, its driver
``drivers/<config["driver"]>.py`` and each per-layer metric
``layer_metrics/<name>.py``. No table in this package lists any of them.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmarks/chip
ROOT = os.path.dirname(os.path.dirname(HERE))  # the checkout


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under benchmarks/chip as a module; a metric's name may hold dots."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind}/{name}.py under {HERE}")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration file, with its name under "name"
    traffic: dict
    end_to_end: list  # the manifest entries of the metrics this cell reports
    per_layer: list
    run_seconds: int


def _reported_by(metrics: list, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def load_cell(name: str) -> Cell:
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r}; it has {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(ROOT, configs[entry["config"]]["file"]))
    config["name"] = entry["config"]
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=config,
        traffic=load_json(os.path.join(HERE, "traffic", entry["traffic"] + ".json")),
        end_to_end=_reported_by(manifest["end_to_end"], name),
        per_layer=_reported_by(manifest["per_layer"], name),
        run_seconds=int(manifest["run_seconds"]),
    )


def peaks_for(device_kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; a kind that is not there is an error, not a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise SystemExit(f"peaks.json has no entry for device kind {device_kind!r}: add one with its source")
    return table[device_kind]
