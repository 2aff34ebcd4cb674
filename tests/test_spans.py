"""The program's own spans and counters (``repro.spans``).

Under a profiler session, a streamed Monte-Carlo scenario, a two-lane
XLA ensemble and a working-set solve record every span of their host
paths, nested under one ``scenario.run`` root per call; the solver
counts its residual evaluations and the useful ones, and its one
transfer each way. With no session,
nothing is recorded. A small ring counts what it overwrote. The three
jitted programs carry fixed names into their modules.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro import spans
from repro.core import fastsim_jax, workingset
from repro.scenario import Estimator, Scenario, System, Workload

MC_SPANS = {"mc.trace", "mc.feed", "mc.compile", "mc.drive", "mc.collect"}
WS_SPANS = {"ws.prepare", "ws.solve", "ws.collect"}
WS_COUNTERS = {"ws.residual_evals", "ws.residual_evals_useful", "ws.fetches",
               "ws.transfers"}


@pytest.fixture
def ring(monkeypatch):
    """A fresh ring, and a fresh executable cache so compiles happen in
    the session."""
    fresh = spans.Ring(spans.CAPACITY)
    monkeypatch.setattr(spans, "_RING", fresh)
    monkeypatch.setattr(fastsim_jax, "_AOT_CACHE", {})
    return fresh


def scenarios():
    # warm-up on a chunk boundary: one chunk shape, one compile per driver
    wl = Workload(alphas=(0.75, 0.5, 1.0), n_objects=97)
    xla = System(allocations=(8, 8, 8), physical_capacity=97, backend="xla")
    return {
        "stream": Scenario(
            name="stream", workload=wl, system=xla, n_requests=800, warmup=400,
            estimator=Estimator("monte_carlo", streaming=True, chunk_size=400),
        ),
        "lanes": Scenario(
            name="lanes", workload=wl, system=xla, n_requests=400, warmup=0,
            estimator=Estimator("monte_carlo", streaming=False, replications=2),
        ),
        "ws": Scenario(
            name="ws", workload=wl, n_requests=1000,
            system=System(allocations=(8, 8, 8), physical_capacity=97),
            estimator=Estimator("working_set"),
        ),
    }


def traced(tmp_path, *scs):
    with jax.profiler.trace(str(tmp_path)):
        return [sc.run() for sc in scs]


def by_call(events):
    roots = [e for e in events if e.name == "scenario.run"]
    return {r.span_id: (r, [e for e in events if e.call_id == r.span_id and e is not r])
            for r in roots}


def test_each_call_records_its_spans_nested_under_one_root(ring, tmp_path):
    scs = scenarios()
    reports = traced(tmp_path, scs["stream"], scs["lanes"], scs["ws"])
    calls = by_call(spans.events())
    assert len(calls) == 3 and spans.dropped() == 0
    names = []
    for root, under in calls.values():
        assert root.parent_id is None and root.call_id == root.span_id
        ids = {root.span_id: root} | {e.span_id: e for e in under if e.value is None}
        for e in under:
            parent = ids[e.parent_id]
            assert parent.t0 <= e.t0 <= e.t1 <= parent.t1
        names.append({e.name for e in under})
    assert names[0] >= MC_SPANS and names[1] >= MC_SPANS
    assert names[2] == WS_SPANS | WS_COUNTERS
    # the drive spans are the Report's own drive-loop clock
    for (_, under), rep in zip(list(calls.values())[:2], reports):
        drive = sum(e.t1 - e.t0 for e in under if e.name == "mc.drive")
        assert drive == pytest.approx(rep.elapsed_s, rel=1e-9)
    # one mc.trace per lane of the ensemble, one per chunk (and the
    # stream's end) of the streamed call
    assert sum(e.name == "mc.trace" for e in list(calls.values())[1][1]) == 2
    assert sum(e.name == "mc.trace" for e in list(calls.values())[0][1]) == 3


def test_residual_evaluation_counters(ring, tmp_path):
    (rep,) = traced(tmp_path, scenarios()["ws"])
    counts = {e.name: e.value for e in spans.events() if e.value is not None}
    est = Estimator("working_set")
    evals = (rep.extras["iterations"] + 1) * (workingset.GROW_STEPS + est.n_bisect)
    assert counts["ws.residual_evals"] == evals
    assert 1 <= counts["ws.residual_evals_useful"] <= evals


def test_one_device_to_host_fetch_per_solve(ring, tmp_path):
    sc = scenarios()["ws"]
    traced(tmp_path, sc, sc)
    events = spans.events()
    fetches = [e for e in events if e.name == "ws.fetches"]
    assert [e.value for e in fetches] == [1, 1]
    parents = {e.span_id: e.name for e in events if e.value is None}
    assert all(parents[e.parent_id] == "ws.collect" for e in fetches)
    sc.run()  # recording off: nothing more is counted
    assert spans.events() == events


def test_one_host_to_device_transfer_per_solve(ring, tmp_path):
    sc = scenarios()["ws"]
    traced(tmp_path, sc, sc)
    events = spans.events()
    transfers = [e for e in events if e.name == "ws.transfers"]
    assert [e.value for e in transfers] == [1, 1]
    parents = {e.span_id: e.name for e in events if e.value is None}
    assert all(parents[e.parent_id] == "ws.prepare" for e in transfers)
    sc.run()  # recording off: nothing more is counted
    assert spans.events() == events


def test_a_solve_at_a_fresh_shape_compiles_only_the_solver(caplog):
    import logging

    N = 211  # a shape no other test solves at
    lam = np.full((3, N), 1.0 / N)
    with caplog.at_level(logging.WARNING), jax.log_compiles(True):
        workingset.solve_workingset(lam, np.ones(N), np.full(3, 8.0),
                                    n_outer=5, n_bisect=10)
    compiled = [r.getMessage().split(" ", 2)[1] for r in caplog.records
                if r.getMessage().startswith("Compiling ")]
    assert compiled == ["jit(ws_solve)"]


def test_nothing_is_recorded_without_a_profiler_session(ring):
    assert not spans.recording()
    assert spans.span("x") is spans.span("y")  # the shared no-op
    for sc in scenarios().values():
        sc.run()
    spans.count("c", 1)
    with spans.timed("t") as t:
        pass
    assert t.seconds >= 0
    assert spans.events() == [] and spans.dropped() == 0


def test_a_full_ring_counts_what_it_overwrote(monkeypatch, tmp_path):
    small = spans.Ring(4)
    monkeypatch.setattr(spans, "_RING", small)
    with jax.profiler.trace(str(tmp_path)):
        for i in range(6):
            with spans.span(f"s{i}"):
                spans.count("n", i)
    assert spans.dropped() == 8
    got = spans.events()
    assert [e.name for e in got] == ["n", "s4", "n", "s5"]
    assert got[-1].t0 >= got[0].t0


def _lowered_name(fn, *args):
    text = fn.lower(*args).as_text()
    return text.split("module @", 1)[1].split(" ", 1)[0]


@pytest.mark.parametrize("name", ["jit_mc_drive", "jit_mc_drive_lanes", "jit_ws_solve"])
def test_executables_carry_fixed_names(name):
    import jax.numpy as jnp

    J, N, m = 3, 50, 16
    if name == "jit_ws_solve":
        fn = workingset._jitted_solver("L1", 8, 5, 10, 0.7, 1e-7)
        args = (jnp.ones((J * N + N + J,)), J, N)
    else:
        lanes = name.endswith("lanes")
        fn = (fastsim_jax._batched_fn if lanes else fastsim_jax._single_fn)(True, N)
        lane = (2,) if lanes else ()
        i32 = jnp.int32
        args = (
            fastsim_jax._init_state(J, N, batch=2 if lanes else None),
            jnp.zeros(lane + (m,), i32), jnp.zeros(lane + (m,), i32), i32(0),
            jnp.ones((N,), i32), jnp.ones((J,), i32), jnp.ones((J,), i32),
            jnp.ones((J + 2,), i32), i32(10), i32(0), i32(0),
        )
    assert _lowered_name(fn, *args) == name


def test_off_span_allocates_nothing():
    import tracemalloc

    spans.span("warm")
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot()
        for _ in range(1000):
            with spans.span("off"):
                pass
        after = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    grown = sum(s.size_diff for s in after.compare_to(before, "filename")
                if s.traceback[0].filename == spans.__file__)
    assert grown == 0
