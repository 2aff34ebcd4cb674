"""Monte-Carlo calls over memcached item sizes, held to the
configuration's exact guarantee.

The same comparison as ``monte_carlo``'s, with the object lengths drawn
by ``reference_items`` from the call's seed and handed to
``reference_mc``'s event loop as explicit lengths: every event-level
field of the Report must equal the reference's (``mismatches``), and
the hit rates' float sums agree within ``rate_gap``. The control
divides the occupancy ticks in float32.
"""

from __future__ import annotations

import dataclasses

from chipbench import reference_items
from chipbench import reference_mc as ref_mc
from chipbench.kinds import monte_carlo

record = monte_carlo.record


def unsupported(spec: dict) -> str | None:
    kind = spec["workload"].get("lengths", {}).get("kind", "unit")
    if kind != "item":
        return f"length kind {kind!r} (this kind draws item sizes)"
    return ref_mc.unsupported(spec)


def with_items(spec: dict) -> dict:
    """The specification with its item sizes drawn by the reference and
    written out as explicit lengths."""
    wl = spec["workload"]
    _, length_seed = ref_mc.derive_seeds(spec.get("seed", 0))
    sizes = reference_items.item_lengths(wl["lengths"], wl["n_objects"], length_seed)
    return {**spec, "workload": {**wl, "lengths": {"kind": "explicit", "values": sizes}}}


def _drawn(call):
    return dataclasses.replace(call, spec=with_items(call.spec))


def check(config: dict, calls: list) -> dict:
    return monte_carlo.check(config, [(_drawn(call), rep) for call, rep in calls])


def control(config: dict, call) -> dict:
    return monte_carlo.control(config, _drawn(call))


def compare(spec: dict, report) -> dict:
    return monte_carlo.compare(with_items(spec), report)
