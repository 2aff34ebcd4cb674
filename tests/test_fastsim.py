"""Differential equivalence tests: the array engine vs the reference spec.

``SharedLRUCache`` (OrderedDict reference, kept as the executable spec)
and the ``fastsim`` backends (per-op Python, inlined Python loop, C, XLA)
must agree *event for event*: same get/set outcomes, same eviction
sequences (victim, list, ripple/physical flags), same exact scaled
virtual lengths, same ghost order, and bit-identical residence-time
occupancy integers. Randomized traces (plain numpy RNG — no hypothesis
dependency) sweep J, object lengths, ghost retention, RRE thresholds,
and in-place length updates.
"""

import numpy as np
import pytest

from repro.core import (
    FastSegmentedSharedLRU,
    FastSharedLRU,
    GetResult,
    NotSharedSystem,
    SegmentedSharedLRUCache,
    SharedLRUCache,
    SimParams,
    rate_matrix,
    sample_trace,
    simulate_trace,
)
from repro.core import fastsim_c
from repro.core.metrics import OccupancyRecorder


def _events(stats):
    return [(e.proxy, e.key, e.ripple, e.physical) for e in stats.evictions]


def _random_config(rng, max_j=4, n_objects=40):
    J = int(rng.integers(1, max_j + 1))
    allocs = rng.integers(2, 10, size=J).tolist()
    slack = int(rng.integers(0, 4))
    bhat = [a + slack for a in allocs]
    B = sum(bhat) + int(rng.integers(0, 30))
    ghost = bool(rng.integers(0, 2))
    lens = rng.integers(1, 4, size=n_objects).tolist()
    return J, allocs, bhat, B, ghost, lens


def test_differential_event_for_event():
    """Random op streams: outcomes, eviction sequences, vlen, ghosts."""
    rng = np.random.default_rng(0)
    N = 40
    for trial in range(12):
        J, allocs, bhat, B, ghost, lens = _random_config(rng, n_objects=N)
        ref = SharedLRUCache(
            allocs, B, ghost_retention=ghost, ripple_allocations=bhat
        )
        fast = FastSharedLRU(
            N, allocs, B, ghost_retention=ghost, ripple_allocations=bhat
        )
        for step in range(350):
            i = int(rng.integers(0, J))
            k = int(rng.integers(0, N))
            if rng.random() < 0.1:
                # in-place length update via set (resident or not)
                l = int(rng.integers(1, 4))
                st = ref.set(i, k, l)
                res2, ev2 = fast.set(i, k, l)
            else:
                st = ref.get(i, k)
                res2, ev2 = fast.get(i, k)
                if st.result is GetResult.MISS:
                    st = ref.set(i, k, lens[k])
                    res2, ev2 = fast.set(i, k, lens[k])
            assert st.result is res2, (trial, step)
            assert _events(st) == ev2, (trial, step)
            assert ref.vlen_scaled == fast.vlen_scaled, (trial, step)
            if step % 29 == 0:
                fast.check_invariants()
        for j in range(J):
            assert ref.list_keys(j) == fast.list_keys(j)
        assert list(ref.ghosts.keys()) == fast.ghost_keys()
        assert ref.phys_used == fast.phys_used
        assert set(k for k, l in ref.length.items()) == {
            k for k in range(N) if fast.in_physical(k)
        }
        ref.check_invariants()
        fast.check_invariants()


def test_differential_enforce_batch_mode():
    """RRE delayed-batch trims agree with the reference ``enforce``."""
    rng = np.random.default_rng(7)
    N = 30
    allocs, bhat = [4, 6, 5], [6, 8, 7]
    ref = SharedLRUCache(allocs, sum(bhat) + 10, ripple_allocations=bhat)
    fast = FastSharedLRU(N, allocs, sum(bhat) + 10, ripple_allocations=bhat)
    for step in range(300):
        i = int(rng.integers(0, 3))
        k = int(rng.integers(0, N))
        st = ref.get(i, k)
        res2, _ = fast.get(i, k)
        if st.result is GetResult.MISS:
            ref.set(i, k, 1)
            fast.set(i, k, 1)
        if step % 40 == 0:
            ev1 = [(e.proxy, e.key) for e in ref.enforce()]
            ev2 = [(p, key) for p, key, _, _ in fast.enforce()]
            assert ev1 == ev2, step
    ref.check_invariants()
    fast.check_invariants()


def test_slru_differential_event_for_event():
    rng = np.random.default_rng(1)
    N = 40
    for trial in range(8):
        J = int(rng.integers(2, 4))
        allocs = rng.integers(3, 12, size=J).tolist()
        B = sum(allocs) + 20
        ref = SegmentedSharedLRUCache(allocs, B)
        fast = FastSegmentedSharedLRU(N, allocs, B)
        for step in range(500):
            i = int(rng.integers(0, J))
            k = int(rng.integers(0, N))
            st = ref.get(i, k)
            res2, ev2 = fast.get(i, k)
            if st.result is GetResult.MISS:
                st = ref.set(i, k, 1)
                res2, ev2 = fast.set(i, k, 1)
            assert st.result is res2, (trial, step)
            assert _events(st) == ev2, (trial, step)
            assert ref.vlen_scaled == fast.vlen_scaled
        for j in range(J):
            assert ref.list_keys(j) == fast.list_keys(j)
            for k in ref.list_keys(j):
                assert ref.segment_of(j, k) == fast.segment_of(j, k)
        ref.check_invariants()
        fast.check_invariants()


# ---------------------------------------------------------------------------
# Whole-trace drivers vs the reference drive loop
# ---------------------------------------------------------------------------
def _reference_occupancy(cache_cls, b, B, trace, n_objects, warmup, **kw):
    cache = cache_cls(list(b), physical_capacity=B, **kw)
    rec = OccupancyRecorder(len(b), n_objects).attach_to(cache)
    P, O = trace.proxies.tolist(), trace.objects.tolist()
    for idx in range(len(P)):
        rec.now = idx
        if idx == warmup:
            rec.reset_window()
        i, k = P[idx], O[idx]
        if cache.get(i, k).result is GetResult.MISS:
            cache.set(i, k, 1)
    rec.now = len(P)
    rec.finalize()
    return cache, rec.occupancy()


@pytest.fixture(scope="module")
def small_trace():
    lam = rate_matrix(300, [0.75, 0.5, 1.0])
    return sample_trace(lam, 60_000, seed=11), 300


def test_flat_loop_matches_reference_occupancy_exactly(small_trace):
    trace, N = small_trace
    warmup = 5_000
    cache, occ_ref = _reference_occupancy(
        SharedLRUCache, (8, 8, 8), 300, trace, N, warmup
    )
    res = simulate_trace(
        SimParams(allocations=(8, 8, 8), physical_capacity=300),
        trace,
        N,
        warmup=warmup,
        engine="flat",
    )
    assert np.array_equal(occ_ref, res.occupancy)
    assert cache.n_hit_list == res.n_hit_list
    assert cache.n_hit_cache == res.n_hit_cache
    assert cache.n_miss == res.n_miss


def test_generic_loop_equals_flat_loop(small_trace):
    trace, N = small_trace
    p = SimParams(allocations=(8, 16, 8), physical_capacity=300)
    a = simulate_trace(p, trace, N, warmup=4_000, engine="flat")
    b = simulate_trace(p, trace, N, warmup=4_000, engine="generic")
    assert np.array_equal(a.occupancy, b.occupancy)
    assert np.array_equal(a.evictions_per_set, b.evictions_per_set)
    assert np.array_equal(a.hits_by_proxy, b.hits_by_proxy)
    assert a.n_ripple == b.n_ripple and a.n_primary == b.n_primary


@pytest.mark.skipif(not fastsim_c.available(), reason="no C compiler")
@pytest.mark.parametrize(
    "kw",
    [
        dict(),
        dict(ghost_retention=False),
        dict(ripple_allocations=(12, 20, 12)),
        dict(ripple_allocations=(10, 18, 10), batch_interval=50),
    ],
)
def test_c_backend_equals_python_flat(small_trace, kw):
    trace, N = small_trace
    p = SimParams(allocations=(8, 16, 8), physical_capacity=300, **kw)
    a = simulate_trace(p, trace, N, warmup=4_000, engine="c")
    b = simulate_trace(p, trace, N, warmup=4_000, engine="flat")
    assert np.array_equal(a.occupancy, b.occupancy)
    assert np.array_equal(a.evictions_per_set, b.evictions_per_set)
    assert np.array_equal(a.hits_by_proxy, b.hits_by_proxy)
    assert np.array_equal(a.reqs_by_proxy, b.reqs_by_proxy)
    assert np.array_equal(a.final_vlen, b.final_vlen)
    assert a.n_hit_list == b.n_hit_list and a.n_miss == b.n_miss
    assert a.n_ripple == b.n_ripple and a.n_primary == b.n_primary
    assert a.n_batch_evictions == b.n_batch_evictions


def test_xla_backend_equals_python_flat():
    jax = pytest.importorskip("jax")
    del jax
    lam = rate_matrix(200, [0.8, 1.0])
    trace = sample_trace(lam, 20_000, seed=3)
    p = SimParams(allocations=(8, 8), physical_capacity=200)
    a = simulate_trace(p, trace, 200, warmup=2_000, engine="xla")
    b = simulate_trace(p, trace, 200, warmup=2_000, engine="flat")
    assert np.array_equal(a.occupancy, b.occupancy)
    assert np.array_equal(a.evictions_per_set, b.evictions_per_set)
    assert a.n_hit_list == b.n_hit_list and a.n_miss == b.n_miss


def test_xla_driver_build_error_is_not_masked(monkeypatch):
    """A failure to build the XLA driver (e.g. a device allocation
    error) reaches the caller as the cause of the error it gets."""
    from repro.core import fastsim_jax

    def fail(*args, **kwargs):
        raise MemoryError("RESOURCE_EXHAUSTED: device allocation failed")

    monkeypatch.setattr(fastsim_jax, "XLAChunkRunner", fail)
    lam = rate_matrix(200, [0.8, 1.0])
    trace = sample_trace(lam, 1_000, seed=3)
    p = SimParams(allocations=(8, 8), physical_capacity=200)
    with pytest.raises(RuntimeError, match="could not be built") as err:
        simulate_trace(p, trace, 200, engine="xla")
    assert isinstance(err.value.__cause__, MemoryError)


def test_noshare_variant_matches_reference_baseline(small_trace):
    trace, N = small_trace
    warmup = 5_000
    ns = NotSharedSystem([16, 24, 8])
    rec = OccupancyRecorder(3, N)
    P, O = trace.proxies.tolist(), trace.objects.tolist()
    for idx in range(len(P)):
        rec.now = idx
        if idx == warmup:
            rec.reset_window()
        i, k = P[idx], O[idx]
        st = ns.get_autofetch(i, k, 1)
        if st.result is GetResult.MISS:
            rec.hook("attach", i, k)
        for ev in st.evictions:
            rec.hook("detach", ev.proxy, ev.key)
    rec.now = len(P)
    rec.finalize()
    occ_ref = rec.occupancy()

    for engine in ["flat"] + (["c"] if fastsim_c.available() else []):
        res = simulate_trace(
            SimParams(allocations=(16, 24, 8), variant="noshare"),
            trace,
            N,
            warmup=warmup,
            engine=engine,
        )
        assert np.array_equal(occ_ref, res.occupancy), engine


def test_slru_batch_driver_matches_reference_hit_rates(small_trace):
    trace, N = small_trace
    warmup = 6_000
    res = simulate_trace(
        SimParams(allocations=(32, 32, 32), physical_capacity=300, variant="slru"),
        trace,
        N,
        warmup=warmup,
    )
    ref = SegmentedSharedLRUCache([32, 32, 32], physical_capacity=300)
    hits = np.zeros(3)
    reqs = np.zeros(3)
    P, O = trace.proxies.tolist(), trace.objects.tolist()
    for idx in range(len(P)):
        i, k = P[idx], O[idx]
        st = ref.get(i, k)
        if st.result is GetResult.MISS:
            ref.set(i, k, 1)
        if idx >= warmup:
            reqs[i] += 1
            hits[i] += st.result is GetResult.HIT_LIST
    assert np.array_equal(hits, res.hits_by_proxy)
    assert np.array_equal(reqs, res.reqs_by_proxy)


# ---------------------------------------------------------------------------
# Structural checks and guards on the array engine itself
# ---------------------------------------------------------------------------
def test_engine_arrays_and_introspection():
    eng = FastSharedLRU(10, [3, 3], physical_capacity=10)
    eng.set(0, 4, 2)
    eng.get(1, 4)
    arrs = eng.arrays()
    assert arrs["prev"].shape == (2, 10) and arrs["prev"].dtype == np.int64
    assert arrs["holders"][4] == 0b11
    assert eng.share_of(4) == pytest.approx(1.0)
    assert eng.vlen(0) == pytest.approx(1.0)
    assert eng.list_keys(0) == [4]
    eng.check_invariants()


def test_engine_parameter_guards():
    with pytest.raises(ValueError):
        FastSharedLRU(10, [])
    with pytest.raises(ValueError):
        FastSharedLRU(10, [4, 4], physical_capacity=4)
    with pytest.raises(ValueError):
        FastSharedLRU(10, [4, 4], ripple_allocations=[3, 4])
    with pytest.raises(ValueError):
        FastSharedLRU(10, [4], physical_capacity=8).set(0, 3, 0)
    with pytest.raises(ValueError):
        SimParams(allocations=(4,), variant="nope").make_engine(10)


def test_simresult_derived_stats(small_trace):
    trace, N = small_trace
    res = simulate_trace(
        SimParams(allocations=(8, 8, 8), physical_capacity=300),
        trace,
        N,
        warmup=5_000,
    )
    assert res.requests_per_sec > 0
    assert 0.0 <= res.frac_multi_eviction <= 1.0
    assert res.mean_evictions >= 0.0
    hist = res.histogram()
    assert sum(hist.values()) == res.n_sets_recorded
    assert np.all(res.hit_rate_by_proxy >= 0) and np.all(
        res.hit_rate_by_proxy <= 1
    )
    # PASTA sanity: occupancy of rank-1 should exceed rank-1000 tail
    assert res.occupancy[:, 0].min() > res.occupancy[:, -1].max()
