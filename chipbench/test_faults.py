"""The comparison that decides ``correct``, shown to fail.

Each test drives a whole run of a cell (set-up, window, comparison) at a
size the CPU holds, with the harness's look for a chip skipped and the
timed path broken underneath in one way, and requires ``correct`` to
come out false; the sound run beside them requires it true. The faults,
for each kind of cell:

- ``state_unchanged``: the drive loop returns its state unchanged / the
  solver's outer iteration never moves;
- ``half_batch``: half of the lanes left out and the mean taken over the
  rest / half of each chunk's requests dropped / half of the catalogue
  left out of the rates the solver sees;
- ``answer_altered``: one counter of the Report / one characteristic
  time altered where it is produced.

A single chip has no exchange between chips to leave out.

``test_control_fails`` runs the control (``control.py``) at the same
sizes: the reference in the program's place one precision step down.
"""

from __future__ import annotations

import dataclasses
import json
import time

import numpy as np
import pytest

from chipbench import control, generator, harness

SMALL = {
    "fig2_j9": {
        "scenario": {
            "workload": {"n_objects": 3000},
            "system": {
                "allocations": [10, 10, 10, 20, 20, 20, 70, 70, 70],
                "physical_capacity": 300,
            },
            "n_requests": 6000,
            "warmup": 600,
        },
        "allocation_classes": {
            "classes": [
                {"positions": [0, 1, 2], "choices": [5, 10, 20]},
                {"positions": [3, 4, 5], "choices": [10, 20, 40]},
                {"positions": [6, 7, 8], "choices": [35, 70, 140]},
            ],
        },
    },
    "table1_j3": {"scenario": {"n_requests": 6000}},
}
CELLS = [w["name"] for w in json.loads((harness.ROOT / "BENCHMARK.json").read_text())["workloads"]]
SEED = 2_718_281_828_459


def small_config(cell: str) -> dict:
    config = generator.load("configs", cell.split(".")[0])
    return generator.merged(config, SMALL[config["name"]])


def run_small(cell: str, monkeypatch) -> dict:
    monkeypatch.setattr(harness, "use_cache", lambda: "not used in tests")
    return harness.run_cell(
        cell, SEED, 0.0, False, time.perf_counter(),
        config=small_config(cell), require_chip=False,
    )


def _mc_state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.core import fastsim_jax

    run = fastsim_jax._RunnerBase._run

    def frozen(self, P, O):
        kept = jax.tree_util.tree_map(np.asarray, self.st)
        run(self, P, O)
        self.st = jax.tree_util.tree_map(jnp.asarray, kept)

    monkeypatch.setattr(fastsim_jax._RunnerBase, "_run", frozen)


def _mc_half_batch(monkeypatch, lanes: bool):
    from repro.core import fastsim_jax
    from repro.scenario import runner

    if lanes:
        report = runner._ensemble_report
        monkeypatch.setattr(
            runner,
            "_ensemble_report",
            lambda sc, results, *a: report(sc, results[: len(results) // 2], *a),
        )
    else:
        feed = fastsim_jax.XLAChunkRunner.feed

        def half(self, proxies, objects):
            m = len(proxies) // 2
            feed(self, proxies[:m], objects[:m])

        monkeypatch.setattr(fastsim_jax.XLAChunkRunner, "feed", half)


def _mc_answer_altered(monkeypatch):
    from repro.core import fastsim

    assemble = fastsim._assemble

    def altered(*a, **k):
        res = assemble(*a, **k)
        return dataclasses.replace(res, n_miss=res.n_miss + 1)

    monkeypatch.setattr(fastsim, "_assemble", altered)


def _ws_state_unchanged(monkeypatch):
    import functools

    from repro.core import workingset

    solve = workingset._solve_jax
    monkeypatch.setattr(
        workingset, "_solve_jax", functools.partial(_no_outer_steps, solve)
    )
    workingset._jitted_solver.cache_clear()


def _no_outer_steps(solve, lam, lengths, b, **kw):
    return solve(lam, lengths, b, **{**kw, "n_outer": 0})


def _ws_half_batch(monkeypatch):
    from repro.scenario import runner

    rates = runner._rates_for

    def half(sc):
        lam = np.array(rates(sc))
        lam[:, lam.shape[1] // 2:] = 0.0
        return lam / lam.sum(axis=1, keepdims=True)

    monkeypatch.setattr(runner, "_rates_for", half)


def _ws_answer_altered(monkeypatch):
    from repro.scenario import runner

    solve = runner.solve_workingset

    def altered(*a, **k):
        sol = solve(*a, **k)
        sol.t = sol.t.copy()
        sol.t[0] *= 1.01
        return sol

    monkeypatch.setattr(runner, "solve_workingset", altered)


FAULTS = {
    "state_unchanged": lambda cell, mp: (
        _ws_state_unchanged(mp) if "admit" in cell else _mc_state_unchanged(mp)
    ),
    "half_batch": lambda cell, mp: (
        _ws_half_batch(mp) if "admit" in cell else _mc_half_batch(mp, "lanes" in cell)
    ),
    "answer_altered": lambda cell, mp: (
        _ws_answer_altered(mp) if "admit" in cell else _mc_answer_altered(mp)
    ),
}


@pytest.fixture(autouse=True)
def _fresh_solver():
    from repro.core import workingset

    yield
    workingset._jitted_solver.cache_clear()


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, monkeypatch):
    result = run_small(cell, monkeypatch)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_makes_run_incorrect(cell, fault, monkeypatch):
    FAULTS[fault](cell, monkeypatch)
    result = run_small(cell, monkeypatch)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails(cell):
    config = small_config(cell)
    mix = generator.load("traffic", cell.split(".")[1])
    got = control.readings(config, mix, SEED)
    assert any(v > config["limits"][k] for k, v in got.items()), got
