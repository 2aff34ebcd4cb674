"""Plain reference of the shared-LRU Monte-Carlo estimate.

Written from the paper's description of the system (arXiv:1905.07641,
Section III) and from nothing of the program under test: J proxies each
keep an LRU list over one physical store; an object held by the lists
P(k) is charged ``l_k / |P(k)|`` to each of them; a list over its
allocation evicts from its LRU end, and the evicted list's share moves
to the remaining holders, which may push them over their own limit in
turn (ripple evictions). An object no list holds stays resident as a
ghost until room is needed. Hit probabilities are time-average
occupancies (PASTA), counted in requests.

Charges are kept as integers scaled by lcm(1..J), so the arithmetic is
exact. The per-list order is an ``OrderedDict`` (last item = most
recently used); the ghosts are one more ``OrderedDict`` in eviction
order.

The request trace and the object lengths are drawn here too, from the
scenario seed: the seed's two ``SeedSequence`` children, the lanes'
seeds keyed on ``(trace_seed, r, 0xE25B)``, one inverse-CDF draw over
the flattened (proxy, object) rate cells, and the length populations
as the scenario's ``LengthSpec`` states them. That draw is part of the
exact guarantee (a seed names one trace), so a program whose stream
differs, however sound its statistics, reads not correct until the
benchmark's draw is changed with it.

``reference_of(spec)`` reads all of it from the scenario specification
in its JSON form, with the program's documented defaults for what the
specification leaves out; ``unsupported(spec)`` names what this
reference does not model (another workload kind or system variant,
delayed batch eviction, a cluster), and the check refuses such a cell
before its window.
"""

from __future__ import annotations

import math
from collections import OrderedDict

import numpy as np

HIST_BUCKETS = 1024


# ---------------------------------------------------------------------------
# Seeds, rates and the trace
# ---------------------------------------------------------------------------
def derive_seeds(seed: int) -> tuple[int, int]:
    """(trace_seed, length_seed): two SeedSequence children of the
    scenario seed."""
    children = np.random.SeedSequence(int(seed)).spawn(2)
    return tuple(int(c.generate_state(1)[0]) for c in children)


def ensemble_seeds(trace_seed: int, replications: int) -> list[int]:
    """Lane 0 keeps the scenario's trace seed; lane r >= 1 draws the
    SeedSequence keyed on (trace_seed, r, 0xE25B)."""
    out = [int(trace_seed)]
    for r in range(1, int(replications)):
        ss = np.random.SeedSequence([int(trace_seed), int(r), 0xE25B])
        out.append(int(ss.generate_state(1)[0]))
    return out


def zipf_rates(n_objects: int, alphas, proxy_rates=None) -> np.ndarray:
    """(J, N) rates: proxy i requests rank k with probability
    proportional to k^-alpha_i, each proxy's total rate 1 or its
    ``proxy_rates`` entry."""
    ranks = np.arange(1, n_objects + 1, dtype=np.float64)
    rows = []
    for a in alphas:
        w = ranks ** (-float(a))
        rows.append(w / w.sum())
    totals = np.ones(len(alphas)) if proxy_rates is None else proxy_rates
    return np.stack(rows) * np.asarray(totals, dtype=np.float64)[:, None]


def object_lengths(spec: dict | None, n_objects: int, length_seed: int) -> np.ndarray:
    """(N,) lengths of a ``LengthSpec`` in its JSON form: ``unit`` 1;
    ``fixed`` ``value``; ``zipf`` round(max_len * k^-beta) for rank k;
    ``lognormal`` round(exp(N(mu, sigma))) drawn from ``length_seed ^
    0x5EED1E``; ``explicit`` ``values``. Each clipped to [1, max_len]
    where the kind has a ``max_len``."""
    spec = {"kind": "unit", "value": 1, "beta": 0.5, "max_len": 8, "mu": 0.0,
            "sigma": 0.5, **(spec or {})}
    kind = spec["kind"]
    if kind == "unit":
        return np.ones(n_objects, dtype=np.int64)
    if kind == "fixed":
        return np.full(n_objects, int(spec["value"]), dtype=np.int64)
    if kind == "zipf":
        ranks = np.arange(1, n_objects + 1, dtype=np.float64)
        raw = np.round(spec["max_len"] * ranks ** (-spec["beta"]))
    elif kind == "lognormal":
        rng = np.random.default_rng(length_seed ^ 0x5EED1E)
        raw = np.round(np.exp(rng.normal(spec["mu"], spec["sigma"], n_objects)))
    else:
        return np.asarray(spec["values"], dtype=np.int64)
    return np.clip(raw, 1, spec["max_len"]).astype(np.int64)


def default_warmup(n: int, allocations) -> int:
    """The warm-up a scenario that names none gets: the Table-I rule,
    max(n / 15, 10 * sum of the allocations)."""
    return max(n // 15, 10 * sum(int(b) for b in allocations))


def unsupported(spec: dict) -> str | None:
    """What of the specification this reference does not model, or
    None."""
    wl, sy = spec["workload"], spec["system"]
    if wl.get("kind", "irm") != "irm":
        return f"workload kind {wl.get('kind')!r}"
    if wl.get("phase_requests", 0):
        return "phased workloads"
    if sy.get("variant", "lru") != "lru":
        return f"system variant {sy.get('variant')!r}"
    if sy.get("batch_interval", 0):
        return "delayed batch eviction"
    if sy.get("nodes", 1) != 1 or sy.get("faults") or sy.get("admission"):
        return "clusters and admission control"
    return None


def reference_of(spec: dict) -> dict:
    """Everything a lane needs, read from the scenario specification:
    ``lam``, ``lengths``, ``n``, ``warmup``, ``trace_seeds`` (one per
    lane) and the ``simulate`` keywords."""
    wl, sy, est = spec["workload"], spec["system"], spec.get("estimator", {})
    lam = zipf_rates(wl["n_objects"], wl["alphas"], wl.get("proxy_rates"))
    trace_seed, length_seed = derive_seeds(spec.get("seed", 0))
    b = [int(x) for x in sy["allocations"]]
    bhat = sy.get("ripple_allocations")
    if bhat is None and sy.get("slack_frac", 0.0) > 0:
        bhat = [int(np.ceil(x * (1.0 + sy["slack_frac"]))) for x in b]
    capacity = sy.get("physical_capacity")
    if capacity is None:
        capacity = sum(bhat or b)
    n = int(spec["n_requests"])
    warmup = spec.get("warmup")
    warmup = min(int(default_warmup(n, b) if warmup is None else warmup), n)
    ripple_from = spec.get("ripple_from")
    return {
        "lam": lam,
        "n": n,
        "warmup": warmup,
        "trace_seeds": ensemble_seeds(trace_seed, est.get("replications", 1)),
        "simulate": {
            "allocations": b,
            "capacity": int(capacity),
            "ripple_allocations": bhat,
            "ghost_retention": sy.get("ghost_retention", True),
            "warmup": warmup,
            "ripple_from": warmup if ripple_from is None else int(ripple_from),
            "lengths": object_lengths(wl.get("lengths"), wl["n_objects"], length_seed),
        },
    }


def lanes(spec: dict, occupancy_dtype=np.float64) -> tuple[dict, list[dict]]:
    """The reference's run of every lane of a call: (``reference_of``,
    one ``simulate`` output per lane)."""
    ref = reference_of(spec)
    out = []
    for seed in ref["trace_seeds"]:
        P, O = sample_trace(ref["lam"], ref["n"], seed)
        out.append(simulate(P, O, **ref["simulate"], occupancy_dtype=occupancy_dtype))
    return ref, out


def sample_trace(lam: np.ndarray, n: int, trace_seed: int):
    """n (proxy, object) requests: one uniform per request, looked up in
    the CDF of the flattened rate cells."""
    J, N = lam.shape
    cdf = np.cumsum(lam.ravel())
    cdf /= cdf[-1]
    cdf[-1] = 1.0
    rng = np.random.default_rng(trace_seed)
    idx = np.searchsorted(cdf, rng.random(n), side="right")
    np.clip(idx, 0, J * N - 1, out=idx)
    return (idx // N).astype(np.int64), (idx % N).astype(np.int64)


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------
def simulate(
    proxies,
    objects,
    *,
    allocations,
    capacity: int,
    ripple_allocations=None,
    ghost_retention: bool = True,
    warmup: int = 0,
    ripple_from: int | None = None,
    lengths=None,
    occupancy_dtype=np.float64,
) -> dict:
    """Drive one trace through the shared LRU lists.

    Each request (i, k) is an MCD ``get``; on a miss the client fetches
    and ``set``s k. A list hit moves k to the MRU end of list i. Any
    other request attaches k to list i, re-apportioning its charge, and
    then evicts while some list is over its limit: the trigger list i
    against its allocation, every other list against its ripple
    threshold; the list furthest over goes first (the lowest index on a
    tie). Object k has length ``lengths[k]`` (1 where none are given).
    Only sets (misses) enter the evictions-per-set histogram, from
    request ``ripple_from`` on (``warmup`` where it is None); the
    occupancy and the per-proxy request counts start afresh at
    ``warmup``.

    ``occupancy_dtype`` is the precision of the final occupancy division
    (float64 as specified; a lower one is the control's).
    """
    P = np.asarray(proxies).tolist()
    O = np.asarray(objects).tolist()
    n = len(P)
    J = len(allocations)
    size = None if lengths is None else np.asarray(lengths).tolist()
    rip_from = warmup if ripple_from is None else ripple_from
    M = math.lcm(*range(1, J + 1))
    share = [0] + [M // p for p in range(1, J + 1)]
    b = [int(x) * M for x in allocations]
    bhat = [int(x) * M for x in (ripple_allocations or allocations)]

    lists = [OrderedDict() for _ in range(J)]
    holders: dict[int, set] = {}
    resident: dict[int, int] = {}  # object -> length while cached
    ghosts: OrderedDict = OrderedDict()
    phys = 0
    vlen = [0] * J
    since: dict[tuple, int] = {}
    tot: dict[tuple, int] = {}
    t_start = 0
    n_hit_list = n_hit_cache = n_miss = 0
    hits_p = [0] * J
    reqs_p = [0] * J
    hist = [0] * HIST_BUCKETS
    n_sets = n_prim = n_rip_total = 0

    def drop_ghost() -> None:
        nonlocal phys
        g, _ = ghosts.popitem(last=False)
        phys -= resident.pop(g)

    for idx in range(n):
        if idx == warmup:
            tot = dict.fromkeys(tot, 0)
            t_start = idx
        i = P[idx]
        k = O[idx]
        post = idx >= warmup
        lst = lists[i]
        if k in lst:
            n_hit_list += 1
            lst.move_to_end(k)
            if post:
                hits_p[i] += 1
                reqs_p[i] += 1
            continue

        if k in resident:
            n_hit_cache += 1
            l = resident[k]
            hs = holders.get(k)
            if hs:
                p = len(hs)
                for j in hs:
                    vlen[j] += l * (share[p + 1] - share[p])
                hs.add(i)
                vlen[i] += l * share[p + 1]
            else:
                del ghosts[k]
                holders[k] = {i}
                vlen[i] += l * M
            is_set = False
        else:
            n_miss += 1
            l = 1 if size is None else size[k]
            while phys + l > capacity and ghosts:
                drop_ghost()
            resident[k] = l
            phys += l
            holders[k] = {i}
            vlen[i] += l * M
            is_set = True

        lst[k] = None
        since[(i, k)] = idx

        n_ev = n_rip = 0
        while True:
            worst, worst_over = -1, 0
            for j in range(J):
                over = vlen[j] - (b[j] if j == i else bhat[j])
                if over > worst_over:
                    worst, worst_over = j, over
            if worst < 0:
                break
            v, _ = lists[worst].popitem(last=False)
            s = since.pop((worst, v))
            tot[(worst, v)] = tot.get((worst, v), 0) + idx - max(s, t_start)
            hs = holders[v]
            lv = resident[v]
            p = len(hs)
            hs.discard(worst)
            vlen[worst] -= lv * share[p]
            if hs:
                for j in hs:
                    vlen[j] += lv * (share[p - 1] - share[p])
            else:
                del holders[v]
                if ghost_retention:
                    ghosts[v] = None
                else:
                    phys -= resident.pop(v)
            n_ev += 1
            if worst != i:
                n_rip += 1

        if is_set:
            while phys > capacity and ghosts:
                drop_ghost()
            if idx >= rip_from:
                n_sets += 1
                hist[min(n_ev, HIST_BUCKETS - 1)] += 1
                n_rip_total += n_rip
                n_prim += n_ev - n_rip
        if post:
            reqs_p[i] += 1

    for (i, k), s in since.items():
        tot[(i, k)] = tot.get((i, k), 0) + n - max(s, t_start)
    horizon = max(n - t_start, 1)
    keys = sorted({k for (i, k), t in tot.items() if t})
    col = {k: c for c, k in enumerate(keys)}
    ticks = np.zeros((J, len(keys)), dtype=np.int64)
    for (i, k), t in tot.items():
        if t:
            ticks[i, col[k]] = t
    last = max((c for c, x in enumerate(hist) if x), default=0)
    if occupancy_dtype == np.float64:
        values = ticks / horizon
    else:
        values = (
            ticks.astype(occupancy_dtype) / occupancy_dtype(horizon)
        ).astype(np.float64)
    return {
        "keys": np.asarray(keys, dtype=np.int64),
        "values": values,
        "n_hit_list": n_hit_list,
        "n_hit_cache": n_hit_cache,
        "n_miss": n_miss,
        "hits_p": np.asarray(hits_p, dtype=np.int64),
        "reqs_p": np.asarray(reqs_p, dtype=np.int64),
        "hist": np.asarray(hist[: last + 1], dtype=np.int64),
        "n_sets": n_sets,
        "n_prim": n_prim,
        "n_rip": n_rip_total,
        "final_vlen": np.asarray(vlen, dtype=np.int64) / M,
    }


# ---------------------------------------------------------------------------
# Report-level estimates
# ---------------------------------------------------------------------------
def hit_rates(lam: np.ndarray, keys, values, dense: bool):
    """Demand-weighted per-proxy hit rate and the overall rate from the
    occupancy, summed over the dense (J, N) matrix or over the touched
    columns only, as the Report's representation holds it."""
    totals = lam.sum(axis=1)
    w = lam / np.maximum(totals, 1e-300)[:, None]
    shares = totals / max(totals.sum(), 1e-300)
    if dense:
        occ = np.zeros(lam.shape, dtype=np.float64)
        occ[:, keys] = values
        per = (w * occ).sum(axis=1)
    else:
        per = (w[:, keys] * values).sum(axis=1)
    return per, float((shares * per).sum())


def realized(out: dict) -> np.ndarray:
    reqs = out["reqs_p"]
    with np.errstate(invalid="ignore"):
        return np.where(reqs > 0, out["hits_p"] / np.maximum(reqs, 1), np.nan)


def lanes_ripple(lanes: list) -> dict:
    """The ripple summary of several lanes: histograms and counts
    summed."""
    hist = np.zeros(max(len(o["hist"]) for o in lanes), dtype=np.int64)
    for o in lanes:
        hist[: len(o["hist"])] += o["hist"]
    return ripple_summary(
        hist,
        sum(o["n_sets"] for o in lanes),
        sum(o["n_prim"] for o in lanes),
        sum(o["n_rip"] for o in lanes),
    )


def ripple_summary(hist: np.ndarray, n_sets: int, n_prim: int, n_rip: int):
    ks = np.arange(len(hist))
    return {
        "evictions_per_set": {str(k): int(c) for k, c in enumerate(hist) if c},
        "n_sets_recorded": int(n_sets),
        "n_primary": int(n_prim),
        "n_ripple": int(n_rip),
        "n_batch_evictions": 0,
        "frac_multi_eviction": float(hist[2:].sum() / n_sets if n_sets else 0.0),
        "mean_evictions": float((ks * hist).sum() / n_sets if n_sets else 0.0),
    }
