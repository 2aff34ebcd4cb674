"""What the metric readers take from the program's own spans and
counters (``repro.spans``): the events of the window's
``Scenario.run()`` calls.

A call is a root ``scenario.run`` span that lies inside the window; its
span id is the ``call_id`` of every span and counter under it. Nothing
is read (``None``) where the program records no spans (a program
without ``repro.spans``), where the ring holds no call of the window,
or where the ring overwrote events and its oldest one is newer than the
window's start, so that part of the window may be gone.
"""

from __future__ import annotations

ROOT = "scenario.run"


def calls(run) -> list[tuple] | None:
    """[(root span, [events under it])] of the window's calls."""
    try:
        from repro import spans
    except ImportError:
        return None
    events = spans.events()
    if spans.dropped() and (not events or events[0].t0 > run.window_start):
        return None
    roots = {
        e.span_id: (e, [])
        for e in events
        if e.name == ROOT and e.parent_id is None
        and run.window_start <= e.t0 and e.t1 <= run.window_end
    }
    for e in events:
        if e.call_id in roots and e.span_id != e.call_id:
            roots[e.call_id][1].append(e)
    return list(roots.values()) or None


def having(found: list[tuple] | None, name: str) -> list[tuple] | None:
    """The calls that hold at least one event named ``name``."""
    if found is None:
        return None
    return [c for c in found if any(e.name == name for e in c[1])] or None


def seconds(found: list[tuple], name: str) -> float | None:
    """Summed duration of the spans named ``name``; None where there are
    none."""
    got = [e.t1 - e.t0 for _, under in found for e in under
           if e.name == name and e.value is None]
    return sum(got) if got else None


def total(found: list[tuple], name: str) -> int | None:
    """Summed value of the counter ``name``; None where it was never
    counted."""
    got = [e.value for _, under in found for e in under
           if e.name == name and e.value is not None]
    return sum(got) if got else None


def self_seconds(root, under: list) -> float:
    """The root's duration less the union of its direct children."""
    spans = sorted(
        (max(e.t0, root.t0), min(e.t1, root.t1))
        for e in under
        if e.parent_id == root.span_id and e.value is None
    )
    covered, end = 0.0, root.t0
    for s, e in spans:
        s = max(s, end)
        if e > s:
            covered += e - s
            end = e
    return (root.t1 - root.t0) - covered


def mc_seconds_per_mreq(run, name: str) -> float | None:
    """Summed ``name`` spans of the window's Monte-Carlo calls per
    million simulated lane-requests."""
    found = having(calls(run), "mc.drive")
    mreq = sum(r["lane_requests"] for r in run.records if r["kind"] == "monte_carlo") / 1e6
    secs = None if found is None else seconds(found, name)
    if secs is None or not mreq:
        return None
    return secs / mreq


def ws_ms_per_call(run, name: str) -> float | None:
    """Mean ``name`` milliseconds per working-set call of the window."""
    found = having(calls(run), "ws.solve")
    secs = None if found is None else seconds(found, name)
    if secs is None:
        return None
    return 1000.0 * secs / len(found)
