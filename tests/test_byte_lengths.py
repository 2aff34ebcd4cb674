"""Byte-sized objects on the XLA drive loops.

Memcached items are sized in bytes, so at J=9 (``M = lcm(1..9) =
2520``) the scaled budgets ``b_hat * M`` pass 2**31 and the drive loops
carry their virtual lengths in int64. Both drivers, single-lane and
batched, must still equal the executable spec (``core/shared_lru``
through ``backend="reference"``) event for event: occupancy ticks,
counters, the evictions-per-set histogram and the final virtual
lengths, whole-trace and streamed, with and without ghost retention and
an RRE slack. Lane r of the batched driver stays bit-identical to the
single-lane driver on its trace. ``_xla_applicable`` admits these sizes
and still refuses what no integer type it uses can hold. The ``item``
length kind draws key plus value bytes by the two laws' quantile
functions. The front door counts each Report's eviction work under a
profiler session, on every Monte-Carlo path, the cluster's included.
"""

import dataclasses
import json
import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import spans  # noqa: E402
from repro.core import fastsim_jax  # noqa: E402
from repro.core.fastsim import SimParams, _xla_applicable, simulate_trace  # noqa: E402
from repro.core.irm import rate_matrix, sample_trace  # noqa: E402
from repro.scenario import Estimator, LengthSpec, Scenario, System, Workload  # noqa: E402

J = 9
N = 500
ALPHAS = tuple(0.5 + 0.5 * j for j in range(J))
# Heavy-tailed item bytes up to 2**16 (some clipped there), and budgets
# in the paper's 1:2:6 classes whose scaled form passes 2**31.
ITEMS = LengthSpec(kind="item", max_len=2**16, value_gpd=(15.0, 4000.0, 0.6))
BUDGETS = (150_000,) * 3 + (300_000,) * 3 + (900_000,) * 3
CAPACITY = sum(BUDGETS) + 200_000
M = math.lcm(*range(1, J + 1))


def scenario(backend, *, streaming, ghost, slack, replications=1):
    return Scenario(
        name="bytes",
        workload=Workload(n_objects=N, alphas=ALPHAS, lengths=ITEMS),
        system=System(
            allocations=BUDGETS,
            physical_capacity=CAPACITY,
            ghost_retention=ghost,
            slack_frac=slack,
            backend=backend,
        ),
        estimator=Estimator(
            "monte_carlo",
            streaming=streaming,
            chunk_size=1_500,
            replications=replications,
        ),
        n_requests=4_000,
        warmup=400,
        seed=11,
    )


def test_sizes_pass_the_int32_envelope():
    lengths = ITEMS.materialize(N, 11)
    assert lengths.max() == 2**16
    assert max(BUDGETS) * M > 2**31
    assert fastsim_jax.vlen_dtype(J, 2**16, max(BUDGETS)) == jnp.int64


@pytest.mark.parametrize("replications", [1, 2], ids=["one_lane", "lanes"])
@pytest.mark.parametrize("slack", [0.0, 0.25], ids=["b", "b_hat"])
@pytest.mark.parametrize("ghost", [True, False], ids=["ghosts", "no_ghosts"])
@pytest.mark.parametrize("streaming", [False, True], ids=["whole", "streamed"])
def test_xla_equals_reference_spec(streaming, ghost, slack, replications):
    kw = dict(ghost=ghost, slack=slack, replications=replications)
    ref = scenario("reference", streaming=False, **kw).run()  # whole trace only
    got = scenario("xla", streaming=streaming, **kw).run()
    assert got.backend == "xla"
    if replications > 1:
        assert got.ensemble["batched"] is True
    assert np.array_equal(got.dense_hit_prob(), ref.dense_hit_prob())
    assert np.array_equal(got.realized_hit_rate, ref.realized_hit_rate, equal_nan=True)
    assert got.ripple == ref.ripple
    assert np.array_equal(got.final_vlen, ref.final_vlen)
    for key in ("n_hit_list", "n_hit_cache", "n_miss"):
        assert got.extras[key] == ref.extras[key]
    assert got.n_requests == ref.n_requests
    # sparse (streamed) and dense hit-rate sums differ in order only
    np.testing.assert_allclose(got.hit_rate, ref.hit_rate, rtol=1e-12)
    # the mechanism is exercised: sets that evict several objects
    assert sum(c for k, c in ref.ripple["evictions_per_set"].items() if int(k) >= 3)


@pytest.mark.parametrize(
    "kw",
    [dict(), dict(ghost_retention=False), dict(ripple_allocations=tuple(b + b // 4 for b in BUDGETS))],
    ids=["ghosts", "no_ghosts", "b_hat"],
)
def test_every_lane_equals_the_single_lane_driver(kw):
    p = SimParams(allocations=BUDGETS, physical_capacity=CAPACITY, **kw)
    lengths = ITEMS.materialize(N, 5)
    lam = rate_matrix(N, list(ALPHAS))
    traces = [sample_trace(lam, 3_000, seed=40 + r) for r in range(3)]
    lanes = fastsim_jax.simulate_ensemble(p, traces, N, lengths=lengths, warmup=300)
    for lane, trace in zip(lanes, traces):
        single = simulate_trace(p, trace, N, lengths=lengths, warmup=300, engine="xla")
        assert np.array_equal(lane.dense_occupancy(), single.dense_occupancy())
        assert np.array_equal(lane.final_vlen, single.final_vlen)
        assert np.array_equal(lane.evictions_per_set, single.evictions_per_set)
        assert np.array_equal(lane.hits_by_proxy, single.hits_by_proxy)
        assert (lane.n_miss, lane.n_primary, lane.n_ripple) == (
            single.n_miss, single.n_primary, single.n_ripple,
        )


ETC = dict(alloc=(380_000,) * 3 + (760_000,) * 3 + (2_660_000,) * 3, B=11_400_000)


@pytest.mark.parametrize(
    "J_, max_len, alloc, B, n, ok",
    [
        (9, 2**20, ETC["alloc"], ETC["B"], 250_000, True),  # the ETC store
        (9, 2**16, BUDGETS, CAPACITY, 4_000, True),
        (9, 2**20, ETC["alloc"], 2**31 - 2**20 - 1, 250_000, True),
        (9, 2**20, ETC["alloc"], 2**31, 250_000, False),  # occupancy past int32
        (9, 1, ETC["alloc"], ETC["B"], 2**31, False),  # request index
        (40, 2**20, (2**20,) * 40, 40 * 2**20, 1_000, False),  # vlen past int64
    ],
    ids=["etc", "tests", "B_max", "B_2_31", "n_2_31", "J40"],
)
def test_xla_envelope(J_, max_len, alloc, B, n, ok):
    p = SimParams(allocations=alloc, physical_capacity=B)
    lengths = np.array([1, max_len], dtype=np.int64)
    assert _xla_applicable(n, 2, lengths, p) is ok


@pytest.mark.parametrize(
    "J_, max_len, bhat, dtype",
    [(9, 1, 7_000, jnp.int32), (3, 1, 64, jnp.int32), (9, 2**20, 2_660_000, jnp.int64),
     (40, 2**20, 2**20, None)],
    ids=["fig2_units", "table1", "etc_bytes", "J40"],
)
def test_virtual_length_type_follows_the_sizes(J_, max_len, bhat, dtype):
    assert fastsim_jax.vlen_dtype(J_, max_len, bhat) == dtype


def test_unrepresentable_store_is_refused_at_the_front_door():
    sc = scenario("xla", streaming=False, ghost=True, slack=0.0)
    big = Scenario.from_dict(
        {**sc.to_dict(), "system": {**sc.system.to_dict(), "physical_capacity": 2**31}}
    )
    with pytest.raises(RuntimeError, match="not applicable"):
        big.run()


@pytest.mark.parametrize("seed", [0, 5, 2**40 + 3])
@pytest.mark.parametrize("max_len", [2**20, 600])
def test_item_lengths_are_the_two_laws_quantiles(seed, max_len):
    spec = LengthSpec(kind="item", max_len=max_len)
    n = 20_000
    u = np.random.default_rng(seed ^ 0x5EED1E).random(2 * n)
    got = spec.materialize(n, seed)
    for k in (0, 1, 17, n // 2, n - 1):
        uk, uv = float(u[k]), float(u[n + k])
        key = 30.7984 + 8.20449 * ((-math.log(uk)) ** -0.078688 - 1) / 0.078688
        value = 15.0 + 214.476 * ((1 - uv) ** -0.348238 - 1) / 0.348238
        assert got[k] == min(max(round(key) + round(value), 1), max_len)
    assert got.dtype == np.int64 and got.min() >= 1 and got.max() <= max_len
    if max_len == 2**20:  # the ETC pool's shape: ~380 B mean, ~6.5% over 1 kB
        assert 360 < got.mean() < 400
        assert 0.055 < (got > 1024).mean() < 0.075


def test_item_length_spec_json_round_trip_and_checks():
    wl = Workload(n_objects=10, lengths=LengthSpec(kind="item", max_len=2**20,
                                                   value_gpd=(15, 214.476, 0.348238)))
    back = Workload.from_dict(json.loads(json.dumps(wl.to_dict())))
    assert back == wl and back.lengths.value_gpd == (15.0, 214.476, 0.348238)
    assert np.array_equal(back.object_lengths(3), wl.object_lengths(3))
    for bad in (dict(key_gev=(1.0, 0.0, 0.1)), dict(value_gpd=(1.0, 1.0, 0.0)),
                dict(value_gpd=(1.0, 2.0))):
        with pytest.raises(ValueError):
            LengthSpec(kind="item", **bad)


@pytest.mark.parametrize("path", ["one_lane", "lanes", "cluster"])
def test_eviction_counters_per_report(path, monkeypatch, tmp_path):
    monkeypatch.setattr(spans, "_RING", spans.Ring(spans.CAPACITY))
    replications = 2 if path == "lanes" else 1
    sc = scenario("xla", streaming=True, ghost=True, slack=0.0, replications=replications)
    if path == "cluster":  # three nodes on the host engine behind the ring
        sc = dataclasses.replace(sc, system=dataclasses.replace(sc.system, backend="auto", nodes=3))
    with jax.profiler.trace(str(tmp_path)):
        rep = sc.run()
    counts = {e.name: e for e in spans.events() if e.value is not None}
    rip = rep.ripple
    assert counts["mc.evictions"].value == rip["n_primary"] + rip["n_ripple"]
    assert counts["mc.sets"].value == rip["n_sets_recorded"] - rip["evictions_per_set"].get("0", 0)
    assert counts["mc.sets"].value > 0
    parents = {e.span_id: e.name for e in spans.events() if e.value is None}
    assert parents[counts["mc.sets"].parent_id] == "mc.collect"
    sc.run()  # recording off: nothing more is counted
    assert sum(e.value is not None for e in spans.events()) == 2
