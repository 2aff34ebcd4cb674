"""Monte-Carlo calls, held to the configuration's exact guarantee.

The plain reference (``reference_mc``) redraws each lane's trace from
the call's scenario seed and simulates it, and every event-level field
of the Report must equal the reference's: occupancy ticks over the
window (as the touched objects and their time-average occupancies),
hit and miss counters, realized per-proxy hit rates, the
evictions-per-set histogram and its summaries, and the final virtual
list lengths. The count of fields that differ is ``mismatches``. The
demand-weighted hit rates are float sums whose order is not part of the
guarantee; their widest relative gap is ``rate_gap``.

The control divides the occupancy ticks in float32 instead of float64
and is compared with the float64 reference on the same traces.
"""

from __future__ import annotations

import sys
from types import SimpleNamespace

import numpy as np

from chipbench import reference_mc as ref_mc

unsupported = ref_mc.unsupported


def record(report) -> dict:
    return {
        "kind": report.estimator,
        "lane_requests": int(report.n_requests),
        "drive_s": float(report.elapsed_s),
    }


def check(config: dict, calls: list) -> dict:
    """Mismatches summed and the widest rate gap over every call."""
    totals = {"mismatches": 0, "rate_gap": 0.0}
    for call, report in calls:
        got = compare(call.spec, report)
        totals["mismatches"] += got["mismatches"]
        totals["rate_gap"] = max(totals["rate_gap"], got["rate_gap"])
        if got["failed_fields"]:
            print(f"call seed {call.spec['seed']}: fields that differ: "
                  f"{got['failed_fields']}", file=sys.stderr, flush=True)
    return totals


def control(config: dict, call) -> dict:
    got = compare(call.spec, reference_report(call.spec, np.float32))
    got.pop("failed_fields")
    return got


def compare(spec: dict, report) -> dict:
    ref, lanes = ref_mc.lanes(spec)
    if len(lanes) == 1:
        return _single(ref, report, lanes[0])
    return _ensemble(ref, report, lanes)


def _rel_gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return float("inf")
    both_nan = np.isnan(got) & np.isnan(want)
    scale = np.maximum(np.abs(want), 1e-300)
    gap = np.where(both_nan, 0.0, np.abs(got - want) / scale)
    return float(np.nan_to_num(gap, nan=np.inf).max(initial=0.0))


def _verdict(fields: dict, gap: float) -> dict:
    return {
        "mismatches": sum(not ok for ok in fields.values()),
        "rate_gap": gap,
        "failed_fields": sorted(k for k, ok in fields.items() if not ok),
    }


def _counters(report, lanes) -> dict:
    return {
        key: report.extras.get(key) == sum(o[key] for o in lanes)
        for key in ("n_hit_list", "n_hit_cache", "n_miss")
    }


def _single(ref, report, out) -> dict:
    lam = ref["lam"]
    sparse = hasattr(report.hit_prob, "indices")
    if sparse:
        occupancy = {
            "occupancy_objects": np.array_equal(report.hit_prob.indices, out["keys"]),
            "occupancy_values": np.array_equal(report.hit_prob.values, out["values"]),
        }
    else:
        dense = np.zeros(lam.shape, dtype=np.float64)
        dense[:, out["keys"]] = out["values"]
        occupancy = {"occupancy_values": np.array_equal(report.hit_prob, dense)}
    per, overall = ref_mc.hit_rates(lam, out["keys"], out["values"], not sparse)
    fields = {
        **occupancy,
        "n_requests": report.n_requests == ref["n"],
        "warmup": report.warmup == ref["warmup"],
        "realized_hit_rate": np.array_equal(
            report.realized_hit_rate, ref_mc.realized(out), equal_nan=True
        ),
        "ripple": report.ripple == ref_mc.lanes_ripple([out]),
        "final_vlen": np.array_equal(report.final_vlen, out["final_vlen"]),
        **_counters(report, [out]),
    }
    gap = max(_rel_gap(report.hit_rate, per), _rel_gap(report.overall_hit_rate, overall))
    return _verdict(fields, gap)


def _ensemble(ref, report, lanes) -> dict:
    ens = report.ensemble or {}
    lam = ref["lam"]
    R = len(lanes)
    dense = np.zeros((R, *lam.shape), dtype=np.float64)
    rates = []
    for r, out in enumerate(lanes):
        dense[r][:, out["keys"]] = out["values"]
        rates.append(ref_mc.hit_rates(lam, out["keys"], out["values"], True))
    fields = {
        "replications": ens.get("replications") == R,
        "occupancy_values": "hit_prob" in ens and np.array_equal(ens["hit_prob"], dense),
        "n_requests": report.n_requests == ref["n"] * R,
        "warmup": report.warmup == ref["warmup"],
        "realized_hit_rate": "realized_hit_rate" in ens
        and np.array_equal(
            ens["realized_hit_rate"],
            np.stack([ref_mc.realized(o) for o in lanes]),
            equal_nan=True,
        ),
        "ripple": report.ripple == ref_mc.lanes_ripple(lanes),
        "final_vlen": np.array_equal(
            report.final_vlen, np.stack([o["final_vlen"] for o in lanes]).mean(axis=0)
        ),
        **_counters(report, lanes),
    }
    gap = np.inf
    if "hit_rate" in ens and "overall_hit_rate" in ens:
        gap = max(
            _rel_gap(ens["hit_rate"], np.stack([p for p, _ in rates])),
            _rel_gap(ens["overall_hit_rate"], [o for _, o in rates]),
        )
    return _verdict(fields, gap)


def reference_report(spec: dict, occupancy_dtype) -> SimpleNamespace:
    """What the Report of a call holds, made by the reference with its
    occupancy divided in ``occupancy_dtype``; sparse where one lane
    streams, as the program's Report is."""
    ref, lanes = ref_mc.lanes(spec, occupancy_dtype)
    lam = ref["lam"]
    dense = np.zeros((len(lanes), *lam.shape))
    for r, out in enumerate(lanes):
        dense[r][:, out["keys"]] = out["values"]
    rates = [ref_mc.hit_rates(lam, o["keys"], o["values"], True) for o in lanes]
    streaming = spec.get("estimator", {}).get("streaming")
    first = lanes[0]
    hit_prob = (
        SimpleNamespace(indices=first["keys"], values=first["values"])
        if streaming and len(lanes) == 1
        else dense.mean(axis=0)
    )
    realized = np.stack([ref_mc.realized(o) for o in lanes])
    return SimpleNamespace(
        hit_prob=hit_prob,
        hit_rate=np.mean([p for p, _ in rates], axis=0),
        overall_hit_rate=float(np.mean([o for _, o in rates])),
        n_requests=ref["n"] * len(lanes),
        warmup=ref["warmup"],
        realized_hit_rate=realized.mean(axis=0),
        ripple=ref_mc.lanes_ripple(lanes),
        final_vlen=np.stack([o["final_vlen"] for o in lanes]).mean(axis=0),
        extras={k: sum(o[k] for o in lanes) for k in ("n_hit_list", "n_hit_cache", "n_miss")},
        ensemble={
            "replications": len(lanes),
            "hit_prob": dense,
            "hit_rate": np.stack([p for p, _ in rates]),
            "overall_hit_rate": np.asarray([o for _, o in rates]),
            "realized_hit_rate": realized,
        },
    )
