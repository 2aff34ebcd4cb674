"""The program's spans and the device's events share one clock, shown on
traces recorded on a TPU v5e with the spans on: a 2 s window of
``table1_j3.admit_ws`` calls and one ``table1_j3.mc_lanes`` call, each
driven by the harness's own window loop under ``--trace 1``'s profiler
options (executables traced, not their ops).

Inside the harness's ``chipbench.call`` spans, at least 90% of the
time the device idles lies under a program span other than
``scenario.run``, and each execution of the solver and of the drive
loop lies inside the ``ws.solve`` or ``mc.drive`` span that waits for
it, to within 1 ms. The traces keep the device planes and the host
thread that holds the spans; the runtime's other host threads, and
metadata no kept event refers to, were dropped to keep each file small.
Runs on the CPU."""

from __future__ import annotations

from pathlib import Path

import pytest

from chipbench import trace_reduce

HERE = Path(__file__).resolve().parent
RECORDED = {
    "admit": (HERE / "testdata" / "table1_admit_spans_v5e.xplane.pb", "jit_ws_solve", "ws.solve"),
    "lanes": (HERE / "testdata" / "table1_lanes_spans_v5e.xplane.pb", "jit_mc_drive_lanes",
              "mc.drive"),
}
PROGRAM = ("scenario.run", "mc.", "ws.")
SKEW_NS = 1e6  # 1 ms


def host_events(planes, prefixes):
    return [
        (name, s, s + d)
        for p in planes if not trace_reduce.DEVICE_PLANE.match(p["name"])
        for line in p["lines"] for name, s, d in line["events"]
        if name.startswith(prefixes)
    ]


def module_events(planes):
    return [
        (trace_reduce._module_name(name), s, s + d)
        for p in planes if p["name"] == "/device:TPU:0"
        for line in p["lines"] if line["name"] == trace_reduce.MODULES_LINE
        for name, s, d in line["events"]
    ]


def length(intervals):
    return sum(e - s for s, e in trace_reduce._union(intervals))


def overlap(a, b):
    """Length of the intersection of two interval sets."""
    ua, ub = trace_reduce._union(a), trace_reduce._union(b)
    return sum(max(0.0, min(e1, e2) - max(s1, s2)) for s1, e1 in ua for s2, e2 in ub)


@pytest.fixture(scope="module", params=sorted(RECORDED))
def recorded(request):
    path, module, span = RECORDED[request.param]
    return trace_reduce.load(str(path)), module, span


def test_device_idle_in_calls_lies_under_program_spans(recorded):
    planes, _, _ = recorded
    calls = [(s, e) for _, s, e in host_events(planes, ("chipbench.call",))]
    busy = trace_reduce._union([(s, e) for _, s, e in module_events(planes)])
    idle = []
    for lo, hi in calls:
        prev = lo
        for s, e in trace_reduce._clip(busy, lo, hi) + [(hi, hi)]:
            if s > prev:
                idle.append((prev, s))
            prev = max(prev, e)
    inner = [(s, e) for name, s, e in host_events(planes, PROGRAM) if name != "scenario.run"]
    assert calls and idle and inner
    assert overlap(idle, inner) >= 0.9 * length(idle)


def test_each_execution_lies_in_the_span_that_waits_for_it(recorded):
    # The device's clock is mapped onto the host's to within a fraction
    # of a millisecond: solver executions read up to ~0.8 ms before the
    # ws.solve span that dispatched them opens.
    planes, module, span = recorded
    device = sorted((s, e) for name, s, e in module_events(planes) if name == module)
    waits = sorted((s, e) for name, s, e in host_events(planes, (span,)) if name == span)
    assert device and len(device) == len(waits)
    for (ds, de), (ws, we) in zip(device, waits):
        assert ws - SKEW_NS <= ds < de <= we


def test_every_call_holds_one_scenario_run(recorded):
    planes, _, _ = recorded
    calls = host_events(planes, ("chipbench.call",))
    roots = host_events(planes, ("scenario.run",))
    assert len(roots) == len(calls)
    for (_, cs, ce), (_, rs, re) in zip(sorted(calls, key=lambda c: c[1]),
                                        sorted(roots, key=lambda r: r[1])):
        assert cs <= rs <= re <= ce
