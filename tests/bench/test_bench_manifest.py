"""BENCHMARK.json against the benchmark's contract, and every file it
names found where the harness looks."""
from __future__ import annotations

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]

MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
ALL_METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51
    for p in MAN["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p


@pytest.mark.parametrize("name", [m["name"] for m in ALL_METRICS]
                         + [w["name"] for w in MAN["workloads"]]
                         + [c["name"] for c in MAN["configs"]])
def test_names(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert set(metric) - {"workloads"} <= {
        "name", "unit", "better", "bound", "source", "layer", "moves"}


def test_unique_names():
    for group in (ALL_METRICS, MAN["workloads"], MAN["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


def test_end_to_end_bounds():
    e2e = {m["name"]: m for m in MAN["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda m: m["name"])
def test_moves_target_reported_by_each_listed_cell(metric):
    target = next(m for m in MAN["end_to_end"] if m["name"] == metric["moves"])
    for cell in metric["workloads"]:
        assert cell in target.get("workloads", [cell])
    assert (ROOT / "bench" / "layer_metrics" / f"{metric['name']}.py").is_file()


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda w: w["name"])
def test_cell_reports_enough(cell):
    e2e = [m for m in MAN["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    assert len(e2e) >= 2
    assert any(cell["name"] in m["workloads"] for m in MAN["per_layer"])
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    assert (ROOT / "bench" / "entries" / f"{traffic['entry']}.py").is_file()
    assert (ROOT / "bench" / "gen" / f"{traffic['generator']}.py").is_file()
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200


def test_every_config_has_a_cell_and_a_file():
    used = {w["config"] for w in MAN["workloads"]}
    for c in MAN["configs"]:
        assert c["name"] in used
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert set(c["reduced"]) <= set(data), c["reduced"]


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, len(MAN["workloads"]) // 2)


def test_pairs_of_config_and_traffic_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_manifest_is_small():
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
