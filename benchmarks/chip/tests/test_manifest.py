"""BENCHMARK.json against the characters and limits the driver allows, and against the files it names."""
import json
import os
import re
import subprocess

import pytest
from conftest import CHIP, ROOT

from harness import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
MANIFEST = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmarks/chip"]
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert all(LINE.match(word) for word in MANIFEST["command"]) and len(MANIFEST["command"]) <= 32


@pytest.mark.parametrize("entry", METRICS + MANIFEST["configs"] + MANIFEST["workloads"], ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic", "moves"):
        assert key not in entry or NAME.match(entry[key])
    assert "unit" not in entry or UNIT.match(entry["unit"])
    for key in ("why", "layer", "source"):
        assert key not in entry or LINE.match(entry[key]), (key, len(entry[key]))
    assert all(NAME.match(k) for k in entry.get("reduced", [])) and len(entry.get("reduced", [])) <= 16


def test_names_are_unique_and_entries_hold_just_their_keys():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"} and w["chips"] in (1, 4)
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.1
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert all(m["better"] in ("lower", "higher") for m in METRICS)


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(cell):
    c = manifest.load_cell(cell)
    assert c.config["source"] == next(x["source"] for x in MANIFEST["configs"] if x["name"] == c.config["name"])
    assert c.config["layout"]["chips"] == c.chips
    driver = manifest.load_module("drivers", c.config["driver"])
    for fn in ("build", "check", "work", *c.traffic["calls"]):
        assert callable(getattr(driver, fn))
    assert set(driver.work(c.config)) >= {"flops", "bytes", "kernels"}
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in reported
        reader = manifest.load_module("layer_metrics", m["name"])
        assert (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES) == (m["name"], m["unit"], m["layer"], m["moves"])


def test_every_file_under_paths_is_named_from_a_names_characters():
    listed = subprocess.run(["git", "ls-files", "--cached", "--others", "--exclude-standard", "benchmarks/chip"],
                            cwd=ROOT, capture_output=True, text=True, check=True).stdout.split()
    assert listed and all(re.match(r"^[A-Za-z0-9_.\-/]+$", f) for f in listed)


def test_harness_and_command_name_no_cell_configuration_driver_kernel_or_layer_metric():
    banned = re.compile(r"kmeans|cdist|groupby|susy|h2o|lloyd|roofline|kernels_declined", re.I)
    files = [os.path.join(CHIP, "run.py")] + [os.path.join(CHIP, "harness", f) for f in os.listdir(os.path.join(CHIP, "harness"))
                                              if f.endswith(".py")]
    for path in files:
        with open(path, encoding="utf-8") as fh:
            hits = [ln for ln in fh if banned.search(ln)]
        assert not hits, (path, hits)


def test_peaks_name_their_source():
    for kind, row in manifest.load_json(os.path.join(CHIP, "peaks.json")).items():
        assert row["flops_per_s"] > 0 and row["hbm_bytes_per_s"] > 0 and row["source"]
    with pytest.raises(SystemExit):
        manifest.peaks_for("a chip nobody listed")
