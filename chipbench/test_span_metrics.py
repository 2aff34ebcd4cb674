"""The readers of the program's spans and counters, checked on synthetic
events with known answers: window selection, sums per million
lane-requests and per call, self time against overlapping children,
and nothing read (``None``) from a truncated ring, a window without
calls of the reader's kind, or a program that records no spans. Runs on
the CPU."""

from __future__ import annotations

import sys

import pytest

from chipbench.harness import Run, load_metric
from repro import spans

Event = spans.Event

MC_READERS = ["tracegen_s_per_mreq.mc", "feed_s_per_mreq.mc", "collect_s_per_mreq.mc",
              "unspanned_share.mc"]
WS_READERS = ["ws_prepare_ms.admit", "ws_solve_wall_ms.admit", "ws_collect_ms.admit",
              "unspanned_share.admit", "ws_useful_evals.admit"]


def mc_call(cid, t0):
    """A Monte-Carlo call of 10 s at ``t0``: children cover 1-3 (trace),
    2-4 (feed, overlapping the trace), 4-8 (drive) and 8.5-9.5
    (collect), so 2 s are its own (0-1, 8-8.5, 9.5-10)."""
    ev = [Event("scenario.run", cid, None, cid, t0, t0 + 10)]
    for k, (name, a, b) in enumerate([("mc.trace", 1, 3), ("mc.feed", 2, 4),
                                      ("mc.drive", 4, 8), ("mc.collect", 8.5, 9.5)]):
        ev.append(Event(name, cid + 1 + k, cid, cid, t0 + a, t0 + b))
    # a grandchild inside the drive span adds nothing to the union
    ev.append(Event("mc.compile", cid + 9, cid + 3, cid, t0 + 4, t0 + 5))
    return ev


def ws_call(cid, t0, useful):
    """A working-set call of 8 ms: prepare 0-1 and 1-2, solve 2-6, collect
    6-7 with the two counters under it; 1 ms its own (7-8)."""
    ms = 1e-3
    ev = [Event("scenario.run", cid, None, cid, t0, t0 + 8 * ms)]
    for k, (name, a, b) in enumerate([("ws.prepare", 0, 1), ("ws.prepare", 1, 2),
                                      ("ws.solve", 2, 6), ("ws.collect", 6, 7)]):
        ev.append(Event(name, cid + 1 + k, cid, cid, t0 + a * ms, t0 + b * ms))
    ev.append(Event("ws.residual_evals", None, cid + 4, cid, t0 + 6.5 * ms,
                    t0 + 6.5 * ms, 1540))
    ev.append(Event("ws.residual_evals_useful", None, cid + 4, cid, t0 + 6.5 * ms,
                    t0 + 6.5 * ms, useful))
    return ev


def fill(monkeypatch, events, capacity=1 << 10):
    ring = spans.Ring(capacity)
    for e in sorted(events, key=lambda e: e.t1):
        ring.append(e)
    monkeypatch.setattr(spans, "_RING", ring)
    return ring


def mc_run():
    return Run(records=[{"kind": "monte_carlo", "lane_requests": 1_000_000}] * 2,
               window_start=100.0, window_end=130.0)


def ws_run():
    return Run(records=[{"kind": "working_set", "iterations": 9}] * 2,
               window_start=100.0, window_end=101.0)


def mc_events():
    # one call before the window (set-up), two inside it
    return mc_call(1, 50.0) + mc_call(100, 101.0) + mc_call(200, 115.0)


def ws_events():
    return ws_call(1, 99.0, 1540) + ws_call(100, 100.1, 300) + ws_call(200, 100.5, 162)


@pytest.mark.parametrize("name, want", [
    ("tracegen_s_per_mreq.mc", 2.0),  # 2 calls x 2 s over 2 Mreq
    ("feed_s_per_mreq.mc", 2.0),
    ("collect_s_per_mreq.mc", 1.0),
    ("unspanned_share.mc", 100.0 * 4 / 12),  # 2 x 2 s own over 2 x (10 - 4) s
])
def test_mc_readers(monkeypatch, name, want):
    fill(monkeypatch, mc_events())
    assert load_metric(name)(mc_run()) == pytest.approx(want)


@pytest.mark.parametrize("name, want", [
    ("ws_prepare_ms.admit", 2.0),
    ("ws_solve_wall_ms.admit", 4.0),
    ("ws_collect_ms.admit", 1.0),
    ("unspanned_share.admit", 100.0 / 8),
    ("ws_useful_evals.admit", 100.0 * (300 + 162) / (2 * 1540)),
])
def test_ws_readers(monkeypatch, name, want):
    fill(monkeypatch, ws_events())
    assert load_metric(name)(ws_run()) == pytest.approx(want)


@pytest.mark.parametrize("name", MC_READERS + WS_READERS)
def test_truncated_ring_reads_nothing(monkeypatch, name):
    # the window's calls alone, the oldest two events overwritten
    events = mc_events()[6:] if name in MC_READERS else ws_events()[7:]
    run = mc_run() if name in MC_READERS else ws_run()
    ring = fill(monkeypatch, events, capacity=len(events) - 2)
    assert ring.dropped() == 2
    assert load_metric(name)(run) is None


@pytest.mark.parametrize("name", MC_READERS + WS_READERS)
def test_window_without_calls_of_its_kind_reads_nothing(monkeypatch, name):
    mc = name in MC_READERS
    fill(monkeypatch, ws_events() if mc else mc_events())
    assert load_metric(name)(mc_run() if mc else ws_run()) is None
    fill(monkeypatch, [])
    assert load_metric(name)(mc_run() if mc else ws_run()) is None


@pytest.mark.parametrize("name", MC_READERS + WS_READERS)
def test_program_without_spans_reads_nothing(monkeypatch, name):
    monkeypatch.delattr(sys.modules["repro"], "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)  # import fails
    assert load_metric(name)(mc_run() if name in MC_READERS else ws_run()) is None


def test_ring_that_dropped_only_older_events_still_reads(monkeypatch):
    old = [Event("setup", 900 + i, None, 900 + i, 10.0 + i, 10.5 + i) for i in range(4)]
    ring = fill(monkeypatch, old + ws_events()[7:], capacity=len(ws_events()) - 7 + 1)
    assert ring.dropped() == 3 and spans.events()[0].t0 < 100.0
    assert load_metric("ws_solve_wall_ms.admit")(ws_run()) == pytest.approx(4.0)
