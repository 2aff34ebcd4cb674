"""The manifest ``BENCHMARK.json`` against the files the harness finds by
name, and the rules its entries keep. Runs on the CPU; imports no
program code."""

from __future__ import annotations

import json
import re
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def reports(metric: dict) -> set[str]:
    """The cells that report a metric."""
    return set(metric.get("workloads", CELLS))


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_finds_its_files(cell):
    w = CELLS[cell]
    assert (HERE / "configs" / f"{w['config']}.json").is_file()
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    assert (HERE / "kinds" / f"{mix['kind']}.py").is_file()
    assert w["config"] in {c["name"] for c in BENCH["configs"]}
    assert w["chips"] == 1
    assert 1 <= len(w["why"]) <= 200


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_reports_setup_another_end_to_end_and_a_per_layer_metric(cell):
    e2e = {m["name"] for m in BENCH["end_to_end"] if cell in reports(m)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in reports(m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_moves_what_its_cells_report(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    assert m["workloads"], "each per-layer metric lists its cells"
    assert set(m["workloads"]) <= set(CELLS)
    moved = E2E[m["moves"]]
    assert set(m["workloads"]) <= reports(moved)
    assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_names_units_and_readers(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    assert NAME.match(m["name"])
    assert UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert (HERE / "metrics" / f"{metric}.py").is_file()
    if metric in E2E:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")


def test_names_are_unique_and_well_formed():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_config_file_matches_manifest(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    data = json.loads((ROOT / entry["file"]).read_text())
    assert entry["file"] == f"chipbench/configs/{config}.json"
    assert data["name"] == config
    assert data["source"] == entry["source"]
    assert data["reduced"] == entry["reduced"]
    assert set(data["reduced"]) <= set(data["published"])
    assert all(NAME.match(k) for k in entry["reduced"])
    assert any(w["config"] == config for w in BENCH["workloads"])
    assert set(data["limits"]) >= {"mismatches", "rate_gap", "ws_residual", "ws_h_gap"}


def test_run_seconds_fits_the_check_with_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_unknown_device_kind_is_an_error():
    from chipbench.harness import peaks_of

    peaks = json.loads((HERE / "peaks.json").read_text())
    assert peaks_of("TPU v5 lite", peaks)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit, match="not in peaks.json"):
        peaks_of("TPU v9 imaginary", peaks)
