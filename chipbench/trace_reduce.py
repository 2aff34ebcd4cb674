"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read into plain data (``load``): planes, their lines, and
events as (name, start_ns, duration_ns). ``reduce`` then works on that
data alone, so the test can check it on a recorded trace without a
chip:

- the traced window is the host span the harness names
  ``chipbench.window``;
- busy time is the union of the intervals of the device's ops (the
  ``XLA Ops`` line of each ``/device:TPU:<n>`` plane, or its ``XLA
  Modules`` line where the run traced executables only), clipped to the
  window and averaged over the devices; the idle share is 1 minus busy
  over the window;
- an executable's device time is the sum of its events on the
  ``XLA Modules`` line, named without the ``(<id>)`` suffix the runtime
  appends;
- the breakdown lists the ops (or executables) that took most device
  time, and the longest idle gaps of the first device, each named by the
  harness span (``chipbench.call``, ``chipbench.between``) that covers
  most of it.
"""

from __future__ import annotations

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK = "chipbench."
WINDOW = "chipbench.window"
TOP = 10


def load(path: str) -> list[dict]:
    """An ``.xplane.pb`` file as [{"name", "lines": [{"name", "events":
    [(name, start_ns, duration_ns), ...]}]}]."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        {
            "name": plane.name,
            "lines": [
                {
                    "name": line.name,
                    "events": [
                        (ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in line.events
                    ],
                }
                for line in plane.lines
            ],
        }
        for plane in data.planes
    ]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def host_spans(planes) -> list[tuple[str, float, float]]:
    """The harness's own spans, from any non-device plane."""
    out = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(MARK):
                    out.append((name, start, start + dur))
    return out


def reduce(planes) -> dict | None:
    """Device numbers of the traced window, or None where the trace has
    no window span or no device op inside it."""
    spans = host_spans(planes)
    windows = [(s, e) for name, s, e in spans if name == WINDOW]
    if not windows:
        return None
    lo, hi = windows[0]
    busy, ops, modules = [], defaultdict(float), defaultdict(float)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    first_union = None
    for plane in sorted(devices, key=lambda p: p["name"]):
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE, [])
        intervals = _clip([(s, s + d) for _, s, d in op_events], lo, hi)
        union = _union(intervals)
        if not union:
            continue
        if first_union is None:
            first_union = union
        busy.append(sum(e - s for s, e in union))
        for name, s, d in op_events:
            for cs, ce in _clip([(s, s + d)], lo, hi):
                ops[_module_name(name)] += (ce - cs) / len(devices)
        for name, s, d in lines.get(MODULES_LINE, []):
            for cs, ce in _clip([(s, s + d)], lo, hi):
                modules[_module_name(name)] += (ce - cs) / len(devices)
    if not busy:
        return None
    window_ns = hi - lo
    gaps, prev = [], lo
    for s, e in first_union + [[hi, hi]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    labelled = [
        (_label(spans, s, e), (e - s) / 1e9)
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    ]
    busy_ns = sum(busy) / len(busy)
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "modules_s": {k: v / 1e9 for k, v in modules.items()},
        "device_ops": [
            [name, secs / 1e9]
            for name, secs in sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
        ],
        "idle_gaps": [[label, secs] for label, secs in labelled],
    }


def _label(spans, s, e) -> str:
    """The harness span (other than the window) that covers most of the
    gap [s, e]."""
    best, best_cover = "outside_harness_spans", 0.0
    for name, hs, he in spans:
        if name == WINDOW:
            continue
        cover = min(e, he) - max(s, hs)
        if cover > best_cover:
            best, best_cover = name[len(MARK):], cover
    return best
