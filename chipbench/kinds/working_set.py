"""Working-set calls, held to float32 on the device against a float64
reference (``reference_ws.check``): the residual of eq. (8) and the gap
of h, both in float64 at the solve's own t. A solve is judged by what
it says: one that stops at its iteration cap with an accurate t is late
(its time counts), not wrong.

The control solves eq. (8) in bfloat16 with the reference solver.
"""

from __future__ import annotations

import numpy as np

from chipbench import reference_mc as ref_mc
from chipbench import reference_ws as ref_ws

# The bfloat16 solve stops when its own step no longer moves t; these
# caps only bound it (bfloat16 resolves t to 8 bits after a few steps).
BF16_OUTER = 60
BF16_BISECT = 40


def unsupported(spec: dict) -> str | None:
    if spec.get("estimator", {}).get("attribution", "L1") != "L1":
        return "attributions other than the exact L1"
    return ref_mc.unsupported(spec)


def record(report) -> dict:
    return {"kind": report.estimator, "iterations": int(report.extras["iterations"])}


def problem(spec: dict):
    """(rates, lengths, allocations) of a call, from its specification."""
    wl = spec["workload"]
    lam = ref_mc.zipf_rates(wl["n_objects"], wl["alphas"], wl.get("proxy_rates"))
    _, length_seed = ref_mc.derive_seeds(spec.get("seed", 0))
    lengths = ref_mc.object_lengths(wl.get("lengths"), wl["n_objects"], length_seed)
    return lam, lengths.astype(np.float64), np.asarray(spec["system"]["allocations"], float)


def check(config: dict, calls: list) -> dict:
    """The widest residual and h gap over every call."""
    totals = {"ws_residual": 0.0, "ws_h_gap": 0.0}
    for call, report in calls:
        t = np.asarray(report.extras["characteristic_times"], np.float64)
        got = ref_ws.check(*problem(call.spec), t, report.hit_prob)
        totals = {k: max(v, got[k]) for k, v in totals.items()}
    return totals


def control(config: dict, call) -> dict:
    import jax.numpy as jnp

    lam, lengths, b = problem(call.spec)
    t, h, _ = ref_ws.solve(
        lam, lengths, b, jnp.bfloat16, n_outer=BF16_OUTER, n_bisect=BF16_BISECT
    )
    return ref_ws.check(lam, lengths, b, t, h)
