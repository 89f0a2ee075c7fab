"""BENCHMARK.json is whole: every name it gives has its file, and every
file the harness looks up by name is there."""
import json
import re
from pathlib import Path

import pytest

from chip import harness

ROOT = Path(__file__).resolve().parents[3]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CHIP = ROOT / "benchmarks" / "chip"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert all(0.01 <= m["bound"] <= 0.25 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = harness.find_cell(cell, BENCH)
    assert c.config["ranks"] % c.chips == 0
    assert c.config["chips"] == c.chips
    kind = harness.kind_module(c.traffic)
    assert all(hasattr(kind, f) for f in ("setup", "window", "release",
                                          "check"))
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(config):
    data = json.loads((ROOT / config["file"]).read_text())
    assert data["name"] == config["name"]
    assert data["reduced"] == config["reduced"]
    assert (CHIP / data["reference"]).is_file()
    from repro.configs.dvnr import DVNRConfig
    DVNRConfig(**data["model"])
