"""The reduction from a profiler trace to busy time, idle share,
per-executable device time and the breakdown, checked on a hand-made
trace with known answers and on a trace recorded on a TPU v5e (three
working-set solves of the ``table1_j3`` configuration, traced by the
harness's own spans). Runs on the CPU; importing the reduction loads no
JAX and no TPU library."""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import trace_reduce

HERE = Path(__file__).resolve().parent
RECORDED = HERE / "testdata" / "table1_admit_v5e.xplane.pb"


def plane(name, **lines):
    return {"name": name, "lines": [{"name": k, "events": v} for k, v in lines.items()]}


def synthetic():
    host = plane(
        "/host:CPU",
        python3=[
            ("chipbench.window", 0.0, 100.0),
            ("chipbench.between", 0.0, 12.0),
            ("chipbench.call", 12.0, 50.0),
            ("chipbench.between", 62.0, 38.0),
        ],
    )
    ops = [("fusion.1", 10.0, 20.0), ("fusion.2", 20.0, 20.0), ("while.3", 60.0, 10.0),
           ("fusion.1", 95.0, 10.0)]
    modules = [("jit_solve(12)", 10.0, 30.0), ("jit_small(4)", 60.0, 10.0),
               ("jit_solve(12)", 95.0, 10.0)]
    dev = plane("/device:TPU:0", **{"XLA Ops": ops, "XLA Modules": modules})
    return [host, dev]


def test_busy_idle_modules_and_gaps_of_a_known_trace():
    got = trace_reduce.reduce(synthetic())
    # ops cover [10, 40] + [60, 70] + [95, 100] of the window [0, 100]
    assert got["window_s"] == pytest.approx(100e-9)
    assert got["busy_s"] == pytest.approx(45e-9)
    assert got["idle_share"] == pytest.approx(0.55)
    assert got["modules_s"] == pytest.approx({"jit_solve": 35e-9, "jit_small": 10e-9})
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(25e-9)]
    assert [g[0] for g in got["idle_gaps"]] == ["between", "call", "between"]
    assert [g[1] for g in got["idle_gaps"]] == pytest.approx([25e-9, 20e-9, 10e-9])


def test_nothing_to_read_gives_none():
    host, dev = synthetic()
    assert trace_reduce.reduce([dev]) is None  # no window span
    empty = plane("/device:TPU:0", **{"XLA Ops": []})
    assert trace_reduce.reduce([host, empty]) is None  # no device op


def test_recorded_tpu_trace():
    got = trace_reduce.reduce(trace_reduce.load(str(RECORDED)))
    assert got is not None
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["idle_share"] == pytest.approx(1 - got["busy_s"] / got["window_s"])
    assert got["modules_s"] and max(got["modules_s"].values()) <= got["window_s"]
    assert 1 <= len(got["device_ops"]) <= trace_reduce.TOP
    secs = [s for _, s in got["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert len(got["idle_gaps"]) <= trace_reduce.TOP
    assert {g[0] for g in got["idle_gaps"]} <= {"call", "between", "outside_harness_spans"}
    assert sum(s for _, s in got["idle_gaps"]) <= got["window_s"] - got["busy_s"] + 1e-9


def test_import_loads_no_jax():
    code = "import sys; import chipbench.trace_reduce; print('jax' in sys.modules)"
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=HERE.parent, capture_output=True, text=True,
        check=True,
    )
    assert out.stdout.strip() == "False"
