"""Tests for the declarative scenario layer (repro.scenario).

Covers the acceptance contract of the unified API:

* scenario JSON round-trip: serialize -> load -> rerun yields an
  identical seeded Report;
* estimator-vs-simulator agreement on the Table-I preset;
* the reference backend, the pooled variant, the non-stationary
  shot-noise workload, trace replay with empirical rates, object-size
  distributions, and the chunked/streaming trace sampler;
* one Report shape from every Monte-Carlo path.
"""

import dataclasses
import json

import numpy as np
import pytest

from repro.core import rate_matrix, sample_trace, sample_trace_chunks
from repro.scenario import (
    Estimator,
    FaultSpec,
    LengthSpec,
    Report,
    Scenario,
    System,
    Workload,
    get_preset,
    list_presets,
)


def small_scenario(**kw) -> Scenario:
    defaults = dict(
        name="small",
        workload=Workload(n_objects=200, alphas=(0.7, 1.0)),
        system=System(allocations=(12, 12), physical_capacity=120),
        estimator=Estimator("monte_carlo"),
        n_requests=30_000,
        seed=3,
    )
    defaults.update(kw)
    return Scenario(**defaults)


# ---------------------------------------------------------------------------
# Round-trip + determinism
# ---------------------------------------------------------------------------
def test_json_round_trip_identical_report(tmp_path):
    sc = small_scenario(
        system=System(
            allocations=(12, 12),
            physical_capacity=140,
            slack_frac=0.25,
            batch_interval=100,
        ),
        ripple_from=0,
    )
    rep1 = sc.run()

    path = sc.save(tmp_path / "sc.json")
    loaded = Scenario.load(path)
    assert loaded == sc
    rep2 = loaded.run()
    assert rep1.same_estimates(rep2)
    np.testing.assert_array_equal(rep1.hit_prob, rep2.hit_prob)
    assert rep1.ripple == rep2.ripple

    # The Report itself survives the artifact JSON format.
    rep3 = Report.from_dict(json.loads(json.dumps(rep1.to_dict())))
    assert rep1.same_estimates(rep3)


def test_all_presets_serializable_and_scalable():
    names = list_presets()
    assert {
        "table1", "table2_ws", "table3_noshare", "fig2_ripple",
        "rre", "slru", "j2_bounds", "shot_noise", "quickstart",
        "admission_overbooking",
    } <= set(names)
    for name in names:
        sc = get_preset(name)
        assert sc.description
        clone = Scenario.from_json(sc.to_json())
        assert clone == sc
        small = sc.scaled(requests=0.001, catalogue=0.5)
        assert small.n_requests <= max(sc.n_requests, 1)
        Scenario.from_json(small.to_json())  # still serializable


def test_scaled_preserves_shape():
    sc = get_preset("fig2_ripple").scaled(requests=0.01, catalogue=0.01)
    assert sc.workload.n_objects == 10_000
    assert sc.system.allocations == (10, 10, 10, 20, 20, 20, 70, 70, 70)
    assert sc.n_requests == 30_000
    assert sc.system.capacity() == sum(sc.system.allocations)


# ---------------------------------------------------------------------------
# Estimator agreement (Table-I preset): the acceptance criterion
# ---------------------------------------------------------------------------
def test_estimators_agree_on_table1_preset():
    sc = get_preset("table1", b=(64, 64, 8)).scaled(requests=0.015)
    sim = sc.run()
    ws = sc.with_estimator("working_set").run()
    assert sim.estimator == "monte_carlo"
    assert ws.estimator == "working_set" and ws.converged
    # Paper Tables I vs II agree to a few percent; at 150k requests the
    # trajectory noise adds a little on top.
    rel = np.abs(ws.hit_rate - sim.hit_rate) / np.maximum(sim.hit_rate, 1e-9)
    assert np.max(rel) < 0.1, rel
    assert abs(ws.overall_hit_rate - sim.overall_hit_rate) < 0.02
    # Same Report surface from both paths.
    assert sim.hit_prob.shape == ws.hit_prob.shape == (3, 1000)
    assert sim.ripple is not None and ws.ripple is None


# ---------------------------------------------------------------------------
# Backends and variants
# ---------------------------------------------------------------------------
def test_reference_backend_matches_fastsim():
    fast = small_scenario().run()
    ref = small_scenario(
        system=System(
            allocations=(12, 12), physical_capacity=120, backend="reference"
        )
    ).run()
    np.testing.assert_array_equal(ref.hit_prob, fast.hit_prob)
    np.testing.assert_array_equal(ref.realized_hit_rate, fast.realized_hit_rate)
    assert ref.ripple == fast.ripple


def test_pooled_variant():
    sc = small_scenario(system=System(variant="pooled", allocations=(12, 12)))
    rep = sc.run()
    # One collective cache: every proxy sees the same per-object hit prob.
    np.testing.assert_array_equal(rep.hit_prob[0], rep.hit_prob[1])
    ws = sc.with_estimator("working_set").run()
    rel = np.abs(ws.hit_rate - rep.hit_rate) / np.maximum(rep.hit_rate, 1e-9)
    assert np.max(rel) < 0.15
    # Pooling dominates static partitioning of the same total capacity.
    ns = small_scenario(
        system=System(variant="noshare", allocations=(12, 12))
    ).run()
    assert np.all(rep.hit_rate >= ns.hit_rate - 0.02)


def test_slru_variant_and_ws_rejection():
    sc = small_scenario(
        system=System(variant="slru", allocations=(12, 12), physical_capacity=120)
    )
    rep = sc.run()
    assert rep.ripple is not None
    with pytest.raises(ValueError, match="S-LRU"):
        sc.with_estimator("working_set").run()


def test_proxy_count_mismatch_rejected():
    with pytest.raises(ValueError, match="proxies"):
        small_scenario(system=System(allocations=(12, 12, 12)))


# ---------------------------------------------------------------------------
# Workload axis
# ---------------------------------------------------------------------------
def test_shot_noise_workload_runs_and_churns():
    wl = Workload(
        kind="shot_noise",
        n_objects=300,
        alphas=(0.8, 1.0),
        phase_requests=5_000,
        phase_shift=30,
    )
    sc = small_scenario(workload=wl, n_requests=40_000)
    rep = sc.run()
    stat = small_scenario(
        workload=Workload(n_objects=300, alphas=(0.8, 1.0)), n_requests=40_000
    ).run()
    # Churn spreads popularity over more objects -> strictly harder for a
    # small cache than the stationary IRM with identical Zipf profile.
    assert rep.overall_hit_rate < stat.overall_hit_rate
    # The analytic estimator runs on the time-average rate matrix.
    ws = sc.with_estimator("working_set").run()
    assert ws.converged
    # mean_rates is a proper mixture: rows still sum to the proxy rates.
    lam = wl.mean_rates(40_000)
    np.testing.assert_allclose(lam.sum(axis=1), wl.rates().sum(axis=1))


def test_trace_replay_and_empirical_rates():
    lam = rate_matrix(150, [0.9, 1.1])
    t = sample_trace(lam, 8_000, seed=11)
    wl = Workload(
        kind="trace",
        n_objects=150,
        trace_proxies=tuple(int(x) for x in t.proxies),
        trace_objects=tuple(int(x) for x in t.objects),
    )
    sc = Scenario(
        name="replay",
        workload=wl,
        system=System(allocations=(10, 10), physical_capacity=100),
        n_requests=0,  # 0 = full trace
        warmup=800,
    )
    rep = sc.run()
    assert rep.n_requests == 8_000
    ws = sc.with_estimator("working_set").run()
    assert ws.hit_prob.shape == (2, 150)
    # Round trip keeps the embedded trace.
    rep2 = Scenario.from_json(sc.to_json()).run()
    assert rep.same_estimates(rep2)


def test_length_specs():
    for spec in (
        LengthSpec("unit"),
        LengthSpec("fixed", value=3),
        LengthSpec("zipf", beta=0.7, max_len=6),
        LengthSpec("lognormal", sigma=0.8, max_len=9),
    ):
        l = spec.materialize(100, seed=5)
        assert l.shape == (100,) and l.dtype == np.int64
        assert (l >= 1).all() and (l <= max(spec.max_len, spec.value, 1)).all()
        np.testing.assert_array_equal(l, spec.materialize(100, seed=5))
    with pytest.raises(ValueError):
        LengthSpec("nope")
    # Non-unit lengths flow through the whole pipeline.
    sc = small_scenario(
        workload=Workload(
            n_objects=200,
            alphas=(0.7, 1.0),
            lengths=LengthSpec("zipf", beta=0.5, max_len=4),
        ),
        system=System(allocations=(30, 30)),
    )
    rep = sc.run()
    ws = sc.with_estimator("working_set").run()
    assert 0 < rep.overall_hit_rate < 1 and ws.converged


def test_chunked_sampling_equals_one_shot():
    lam = rate_matrix(300, [0.7, 1.0, 1.3])
    one = sample_trace(lam, 25_000, seed=9)
    parts = list(sample_trace_chunks(lam, 25_000, chunk_size=4_000, seed=9))
    assert len(parts) == 7 and len(parts[-1]) == 1_000
    np.testing.assert_array_equal(
        one.proxies, np.concatenate([p.proxies for p in parts])
    )
    np.testing.assert_array_equal(
        one.objects, np.concatenate([p.objects for p in parts])
    )
    # Workload.iter_chunks applies the same shot-noise rotation as sample.
    wl = Workload(
        kind="shot_noise",
        n_objects=300,
        alphas=(0.7, 1.0, 1.3),
        phase_requests=3_000,
        phase_shift=17,
    )
    full = wl.sample(10_000, seed=4)
    chunks = list(wl.iter_chunks(10_000, seed=4, chunk_size=1_500))
    np.testing.assert_array_equal(
        full.objects, np.concatenate([c.objects for c in chunks])
    )


# ---------------------------------------------------------------------------
# One Report builder for every Monte-Carlo path
# ---------------------------------------------------------------------------
MC_PATHS = {
    "oneshot_single": dict(streaming=False),
    "streamed_single": dict(streaming=True),
    "sequential_ensemble": dict(streaming=False, replications=2),
    "batched_xla_ensemble": dict(streaming=False, replications=2, backend="xla"),
    "streamed_cluster": dict(streaming=True, nodes=3),
}


def _mc_path_scenario(streaming, replications=1, backend="c", nodes=1) -> Scenario:
    faults = FaultSpec(events=((0.4, "fail", 1), (0.6, "recover", 1))) if nodes > 1 else None
    return small_scenario(
        workload=Workload(n_objects=300, alphas=(0.7, 0.9, 1.1)),
        system=System(allocations=(16, 16, 16), physical_capacity=200,
                      backend=backend, nodes=nodes, faults=faults),
        estimator=Estimator("monte_carlo", streaming=streaming,
                            replications=replications, chunk_size=2_000),
        n_requests=6_000,
        warmup=600,
    )


def _filled_fields(report) -> set:
    return {k for k, v in report.to_dict().items() if v is not None} - {"ensemble"}


@pytest.mark.parametrize("path", sorted(MC_PATHS))
def test_every_monte_carlo_path_reports_the_same_fields(path):
    """Single runs, one-shot or streamed, ensembles, sequential or in one
    XLA program, and clusters fill the same Report fields, extras and
    ripple statistics; a streamed run names its chunk size, an ensemble
    adds its per-replica payload and a cluster its telemetry."""
    kw = MC_PATHS[path]
    rep = _mc_path_scenario(**kw).run()
    base = _mc_path_scenario(streaming=False).run()
    assert _filled_fields(rep) == _filled_fields(base)
    assert set(rep.ripple) == set(base.ripple)
    assert set(rep.extras) == (
        {"n_hit_list", "n_hit_cache", "n_miss", "streaming"}
        | ({"chunk_size"} if kw["streaming"] else set())
        | ({"cluster"} if kw.get("nodes", 1) > 1 else set())
    )
    assert rep.extras["streaming"] is kw["streaming"]
    assert (rep.ensemble is not None) == (kw.get("replications", 1) > 1)
    assert rep.hit_prob_is_sparse == kw["streaming"]
