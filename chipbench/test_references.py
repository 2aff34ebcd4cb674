"""The plain references against the program at small sizes on the CPU:
the Monte-Carlo reference equals the host C engine event for event (the
XLA driver is held to it by the sound runs of ``test_faults.py``), and
the reference solver finds the program's characteristic times."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench import generator
from chipbench import reference_mc as ref_mc
from chipbench import reference_ws as ref_ws
from chipbench.kinds import monte_carlo


MC_MIX = {"kind": "monte_carlo",
          "scenario": {"estimator": {"kind": "monte_carlo", "replications": 1}}}

# Specifications the reference models beyond the cells' own, laid over
# the small Table-I configuration: RRE slack thresholds with the ripple
# histogram from the first request, object lengths that fall with
# popularity or are drawn, proxies of unequal rates, and no ghosts.
VARIANTS = {
    "unit": {},
    "slack": {"system": {"slack_frac": 0.25, "physical_capacity": None}, "ripple_from": 0},
    "zipf_lengths": {"workload": {"lengths": {"kind": "zipf", "max_len": 8, "beta": 0.5}}},
    "lognormal_lengths": {
        "workload": {"lengths": {"kind": "lognormal", "mu": 0.5, "sigma": 1.0, "max_len": 40}}
    },
    "proxy_rates": {"workload": {"proxy_rates": [2.0, 1.0, 0.5]}},
    "no_ghosts": {"system": {"ghost_retention": False}},
}


def small_table1(over: dict) -> dict:
    config = generator.load("configs", "table1_j3")
    config["scenario"] = generator.merged(
        config["scenario"], {"n_requests": 30_000, "system": {"backend": "c"}, **over}
    )
    return config


@pytest.mark.parametrize("variant", sorted(VARIANTS))
@pytest.mark.parametrize("seed", [3, 2**33 + 5])
def test_monte_carlo_reference_equals_the_c_engine(seed, variant):
    from repro.core import fastsim_c

    if not fastsim_c.available():
        pytest.skip("no C compiler for the host engine")
    config = small_table1(VARIANTS[variant])
    call = generator.Traffic(config, MC_MIX, seed).next_call()
    assert monte_carlo.unsupported(call.spec) is None
    got = monte_carlo.compare(call.spec, call.scenario.run())
    assert got["mismatches"] == 0, got["failed_fields"]
    assert got["rate_gap"] == 0.0


@pytest.mark.parametrize(
    "over",
    [
        {"system": {"variant": "slru"}},
        {"system": {"batch_interval": 64}},
        {"workload": {"kind": "shot_noise"}},
    ],
)
def test_reference_refuses_what_it_does_not_model(over):
    spec = generator.merged(small_table1({})["scenario"], over)
    assert monte_carlo.unsupported(spec)


def test_trace_draw_matches_the_workload():
    config = generator.load("configs", "fig2_j9")
    call = generator.Traffic(config, MC_MIX, 9).next_call()
    trace_seed, _ = ref_mc.derive_seeds(call.spec["seed"])
    wl = call.spec["workload"]
    lam = ref_mc.zipf_rates(wl["n_objects"], wl["alphas"])
    assert np.array_equal(lam, call.scenario.workload.rates())
    P, O = ref_mc.sample_trace(lam, 5000, trace_seed)
    chunk = next(call.scenario.workload.iter_chunks(5000, trace_seed, chunk_size=5000))
    assert np.array_equal(P, chunk.proxies) and np.array_equal(O, chunk.objects)


@pytest.mark.parametrize("b", [(64, 64, 64), (8, 64, 8), (64, 8, 8)])
def test_reference_solver_finds_the_programs_times(b):
    from repro.core.workingset import solve_workingset

    lam = ref_mc.zipf_rates(1000, (0.75, 0.5, 1.0))
    lengths = np.ones(1000)
    t, h, _ = ref_ws.solve(lam, lengths, np.asarray(b, float), np.float32)
    sol = solve_workingset(lam, lengths, np.asarray(b, float))
    np.testing.assert_allclose(t, sol.t, rtol=1e-5)
    for times, occ in ((t, h), (sol.t, sol.h)):
        got = ref_ws.check(lam, lengths, b, times, occ)
        assert got["ws_residual"] < 1e-5 and got["ws_h_gap"] < 1e-6


def test_poisson_binomial_expectation_by_enumeration():
    rng = np.random.default_rng(0)
    h = rng.random((4, 7))
    h[0, 0], h[1, 1] = 0.0, 1.0
    got = ref_ws.expect_inv_one_plus_others(h)
    for i in range(4):
        others = [j for j in range(4) if j != i]
        want = np.zeros(7)
        for bits in range(2 ** len(others)):
            z = [(bits >> n) & 1 for n in range(len(others))]
            p = np.prod([h[j] if zj else 1 - h[j] for j, zj in zip(others, z)], axis=0)
            want += p / (1 + sum(z))
        np.testing.assert_allclose(got[i], want, rtol=1e-12)
