"""XLA-compiled whole-trace driver for the shared-LRU array engine.

The same struct-of-arrays state as :class:`~repro.core.fastsim.
FastSharedLRU` — intrusive doubly-linked lists in flat int32 vectors,
holder indicator matrix, exact lcm-scaled virtual lengths, ghost list,
inline residence-time (PASTA) occupancy — compiled to native code by
XLA.

Branchless predicated step (single-replica driver)
--------------------------------------------------
The per-request program has **no divergent control flow**: the
hit / attach / miss branches are folded into one straight-line sequence
of predicated scatter updates (``vec.at[idx].set(where(pred, new,
old))``), and the eviction / ghost loops are ``lax.while_loop``s whose
conditions carry the branch predicate, with **minimal carries** — each
loop threads only the arrays it mutates, because XLA's copy insertion
materializes every buffer a nested loop carries, per request. The
occupancy-window reset at ``warmup`` happens *between* compiled calls
(the runners split chunks at the boundary), keeping the step
straight-line. The carried state dict is **donated** to the compiled
executable, so chunk-to-chunk feeding updates buffers in place.

Batched multi-replica ensembles
-------------------------------
:class:`BatchedXLARunner` / :func:`simulate_ensemble` run R independent
replicas inside ONE compiled program: per-lane request traces
(independent ``SeedSequence`` substreams upstream), shared workload
constants, optionally per-lane ``(b, b_hat)`` sweep points. The kernel
(:func:`_drive_batched_impl`) is written directly in batched form — a
single ``lax.while_loop`` of per-lane predicated micro-ops in which
every lane advances through its own trace at its own pace — rather than
``jax.vmap`` of the single-lane step, whose while-loop batching rule
would select-copy the whole state per eviction. Lane r is bit-identical
to the single-run driver on trace r (asserted by
``tests/test_ensemble.py``), so every Monte-Carlo estimate gains a
cross-replica confidence band from one compile + one dispatch.

On CPU the batched win is bounded: XLA CPU scatters pay a per-lane
per-update cost, so aggregate ensemble throughput lands near (not far
above) R sequential runs — ``bench_simthroughput`` records the measured
ratio honestly. The formulation targets accelerator backends, where
lane updates vectorize and the batch amortizes dispatch; on CPU its
practical payoff is single-program ensembles with compile time paid
once instead of per replica.

Compilation is always performed *outside* the timed region: each new
(shape, flags) pair is lowered and compiled once via the AOT API and the
resulting executable is reused for every subsequent same-shape ``feed``,
so ``elapsed`` provably excludes compile time.

All arithmetic is integer (exact). Indices, counters, object lengths
and the physical occupancy are int32: ``n_requests < 2**31``, ``J * N <
2**31`` and ``max(B, sum b_hat) + max_length < 2**31``. The virtual list
lengths, the thresholds ``b * M`` and ``b_hat * M`` and each holder's
charge ``l * (M // |P|)`` (``M = lcm(1..J)``) are bounded by
``fastsim.vlen_bound`` = ``(J * max(b_hat) + max_length) * M``: where
that is below 2**31 they are int32, else int64, built and traced with
64-bit types enabled for the drive loop alone (:func:`vlen_dtype`). Unit
objects at the paper's Section VI-C scale stay int32 (bound ~1.6e8 at
J=9); byte-sized memcached items (lengths up to 2**20, ``B < 2**31``)
need int64 (bound ~5e13 at J=9). Equivalence with the pure-Python
engines (and hence with the reference spec) is asserted by
``tests/test_fastsim.py`` / ``tests/test_streaming.py`` /
``tests/test_ensemble.py`` / ``tests/test_byte_lengths.py`` as exact
equality of occupancy integers, counters, virtual lengths, and ripple
histograms.

Supports the flat shared-LRU variant with ghost retention on/off and RRE
slack thresholds (``b_hat``); the S-LRU, not-shared, and delayed-batch
variants run on the pure-Python loops (see ``fastsim.simulate_trace``).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro import spans

from .fastsim import HIST_BUCKETS, vlen_bound

# Evictions-per-set histogram buckets — the single shared constant from
# fastsim (all backends clamp into the same last bucket, keeping
# histograms bit-identical). HIST_MAX is kept as the module-local alias
# the kernel code reads.
HIST_MAX = HIST_BUCKETS


def _upd(vec, idx, val, pred):
    """Predicated 1-D scatter: vec[idx] = val if pred (no-op otherwise)."""
    safe = jnp.maximum(idx, 0)
    return vec.at[safe].set(jnp.where(pred, val, vec[safe]))


def vlen_dtype(J: int, max_length: int, max_bhat: int):
    """The virtual lengths' integer type: int32 where
    :func:`~repro.core.fastsim.vlen_bound` fits it, else int64 (None
    past int64)."""
    bound = vlen_bound(J, max_length, max_bhat)
    if bound < 2**31:
        return jnp.int32
    return jnp.int64 if bound < 2**63 else None


def _wide(vdt):
    """The context that builds the state and constants of a driver whose
    virtual lengths are ``vdt``, and traces it: 64-bit types on for
    int64, nothing otherwise. The compiled executable runs outside it."""
    if vdt == jnp.int64:
        return jax.enable_x64(True)
    return contextlib.nullcontext()


def _init_state(
    J: int, N: int, batch: Optional[int] = None, vdt=jnp.int32
) -> Dict[str, jnp.ndarray]:
    """Fresh carried state for :func:`_drive` (one cold engine).

    ``batch=R`` prepends a replica axis to every leaf — R independent
    cold engines for the vmapped ensemble driver. ``vlen`` is ``vdt``
    (build int64 state under :func:`_wide`), every other leaf int32.
    """
    I32 = jnp.int32
    pre = () if batch is None else (int(batch),)

    def full(shape, val):
        return jnp.full(pre + shape, val, I32)

    def scalar(val):
        return jnp.full(pre, val, I32) if pre else jnp.int32(val)

    return {
        "nxt": full((J * N,), -1),
        "prv": full((J * N,), -1),
        "head": full((J,), -1),
        "tail": full((J,), -1),
        "hold": full((J * N,), 0),
        "hcnt": full((N,), 0),
        "length": full((N,), 0),
        "vlen": jnp.zeros(pre + (J,), vdt),
        "phys": scalar(0),
        "gnxt": full((N,), -1),
        "gprv": full((N,), -1),
        "ghead": scalar(-1),
        "gtail": scalar(-1),
        "isghost": full((N,), 0),
        "res_since": full((J * N,), -1),
        "tot_time": full((J * N,), 0),
        "t_start": scalar(0),
        "n_hit_list": scalar(0),
        "n_hit_cache": scalar(0),
        "n_miss": scalar(0),
        "hits_p": full((J,), 0),
        "reqs_p": full((J,), 0),
        "hist": full((HIST_MAX,), 0),
        "n_sets": scalar(0),
        "n_prim": scalar(0),
        "n_rip": scalar(0),
    }


def _drive_impl(
    st,  # carried state dict (see _init_state)
    P,  # (n,) int32 proxies of this chunk
    O,  # (n,) int32 objects of this chunk
    idx0,  # () int32 absolute index of the chunk's first request
    lengths,  # (N,) int32
    b_scaled,  # (J,) int32 or int64: the virtual lengths' type
    bhat_scaled,  # (J,) same type
    share_arr,  # (J+2,) same type: [0, M//1, ..., M//J, 0]
    B,  # () int32
    warmup,  # () int32
    ripple_from,  # () int32
    *,
    ghost_retention: bool,
    n_objects: int,
):
    """One chunk of requests through the branchless predicated step.

    The occupancy-window reset at ``warmup`` is NOT part of the step:
    the runners split the chunk at the warmup boundary and reset
    ``tot_time`` / ``t_start`` between calls, so the per-request program
    stays straight-line.
    """
    n = P.shape[0]
    J = b_scaled.shape[0]
    N = n_objects
    I32 = jnp.int32
    rowbase = jnp.arange(J, dtype=I32) * N  # for holder-column gathers
    proxy_ids = jnp.arange(J, dtype=I32)

    # Inner loops carry ONLY the arrays they mutate: threading the whole
    # state dict through a lax.while_loop makes XLA's copy insertion
    # materialize every big buffer around each loop — per request. The
    # minimal carries below are what makes the step cheap on CPU.
    GHOST_KEYS = ("ghead", "gtail", "gprv", "isghost", "phys", "length")
    EV_KEYS = (
        "nxt", "prv", "head", "tail", "hold", "hcnt", "vlen",
        "res_since", "tot_time",
    ) + (
        ("ghead", "gtail", "gnxt", "gprv", "isghost")
        if ghost_retention
        else ("length", "phys")
    )

    def ghost_evict_head(gs, gnxt):
        g = gs["ghead"]
        gn = gnxt[g]
        gs = dict(gs)
        gs["ghead"] = gn
        gs["gtail"] = jnp.where(gn == -1, -1, gs["gtail"])
        gs["gprv"] = _upd(gs["gprv"], gn, -1, gn != -1)
        gs["isghost"] = gs["isghost"].at[g].set(0)
        gs["phys"] = gs["phys"] - gs["length"][g]
        gs["length"] = gs["length"].at[g].set(0)
        return gs

    def ghost_loop(st, need_room):
        """Evict ghosts while ``need_room(phys)`` holds (minimal carry;
        ``gnxt`` is read-only inside and captured by closure)."""
        gnxt = st["gnxt"]
        gs = {k: st[k] for k in GHOST_KEYS}
        gs = lax.while_loop(
            lambda s: need_room(s["phys"]) & (s["ghead"] != -1),
            lambda s: ghost_evict_head(s, gnxt),
            gs,
        )
        st = dict(st)
        st.update(gs)
        return st

    def step(local, st):
        st = dict(st)
        local = jnp.int32(local)  # the loop's counter is int64 under x64
        idx = idx0 + local
        i = P[local]
        k = O[local]
        base = i * N
        ik = base + k
        post = idx >= warmup

        # ---- branch predicates (all updates below are predicated) ----
        held = st["hold"][ik] == 1
        resident = st["length"][k] > 0
        hit = held
        hitc = (~held) & resident
        miss = (~held) & (~resident)
        att = ~held  # both cache-hit and miss attach k to list i

        st["n_hit_list"] = st["n_hit_list"] + hit.astype(I32)
        st["n_hit_cache"] = st["n_hit_cache"] + hitc.astype(I32)
        st["n_miss"] = st["n_miss"] + miss.astype(I32)
        st["hits_p"] = st["hits_p"].at[i].add((hit & post).astype(I32))

        # ---- miss: make physical room among ghosts, become resident --
        l_new = lengths[k]
        st = ghost_loop(st, lambda phys: miss & (phys + l_new > B))
        st["length"] = _upd(st["length"], k, l_new, miss)
        st["phys"] = st["phys"] + jnp.where(miss, l_new, 0)

        # ---- attach bookkeeping (share re-apportionment, eq. (5)) ----
        l = st["length"][k]  # miss: l_new; cache hit: resident length
        p_old = st["hcnt"][k]  # 0 for a miss
        delta = l * (share_arr[p_old + 1] - share_arr[p_old])
        holdcol = st["hold"][rowbase + k]  # (J,) — i's bit still 0
        st["vlen"] = st["vlen"] + jnp.where(att, delta, 0) * holdcol
        st["vlen"] = st["vlen"].at[i].add(
            jnp.where(att, l * share_arr[p_old + 1], 0)
        )
        # resurrected ghost: unlink from the ghost list
        res = att & (p_old == 0) & (st["isghost"][k] == 1)
        gp = st["gprv"][k]
        gn = st["gnxt"][k]
        st["ghead"] = jnp.where(res & (gp == -1), gn, st["ghead"])
        st["gnxt"] = _upd(st["gnxt"], gp, gn, res & (gp != -1))
        st["gtail"] = jnp.where(res & (gn == -1), gp, st["gtail"])
        st["gprv"] = _upd(st["gprv"], gn, gp, res & (gn != -1))
        st["isghost"] = _upd(st["isghost"], k, 0, res)
        st["hold"] = _upd(st["hold"], ik, 1, att)
        st["hcnt"] = st["hcnt"].at[k].add(att.astype(I32))

        # ---- list hit: unlink k from its current position ------------
        not_head = st["head"][i] != k
        rem = hit & not_head
        p = st["prv"][ik]
        nx = st["nxt"][ik]
        st["tail"] = st["tail"].at[i].set(
            jnp.where(rem & (p == -1), nx, st["tail"][i])
        )
        st["nxt"] = _upd(st["nxt"], base + p, nx, rem & (p != -1))
        st["prv"] = _upd(st["prv"], base + nx, p, rem)  # nx != -1: not head

        # ---- insert k at the head of list i (hit-not-head or attach) -
        h = st["head"][i]
        mv = rem | att
        st["tail"] = st["tail"].at[i].set(
            jnp.where(att & (h == -1), k, st["tail"][i])
        )
        st["nxt"] = _upd(st["nxt"], base + h, k, mv & (h != -1))
        st["prv"] = _upd(st["prv"], ik, h, mv)
        st["nxt"] = _upd(st["nxt"], ik, -1, mv)
        st["head"] = st["head"].at[i].set(k)
        st["res_since"] = _upd(st["res_since"], ik, idx, att)

        # ---- eviction loop (RRE thresholds; trigger = i) -------------
        lim = jnp.where(proxy_ids == i, b_scaled, bhat_scaled)
        t_start = st["t_start"]  # read-only inside the loop
        length_ro = st["length"]  # ghost mode: evictions never mutate it

        def ev_cond(carry):
            s, _, _ = carry
            return att & (jnp.max(s["vlen"] - lim) > 0)

        def ev_body(carry):
            s, n_ev, n_rip = carry
            s = dict(s)
            worst = jnp.argmax(s["vlen"] - lim).astype(I32)
            wbase = worst * N
            v = s["tail"][worst]
            wv = wbase + v
            # unlink the tail victim (prv[wv] == -1 by definition)
            nv = s["nxt"][wv]
            s["tail"] = s["tail"].at[worst].set(nv)
            s["head"] = (
                s["head"].at[worst].set(
                    jnp.where(nv == -1, -1, s["head"][worst])
                )
            )
            s["prv"] = _upd(s["prv"], wbase + nv, -1, nv != -1)
            # occupancy detach
            since = s["res_since"][wv]
            add = idx - jnp.maximum(since, t_start)
            s["tot_time"] = _upd(
                s["tot_time"], wv, s["tot_time"][wv] + add, since >= 0
            )
            s["res_since"] = s["res_since"].at[wv].set(-1)
            # share re-apportionment
            vl = (length_ro if ghost_retention else s["length"])[v]
            vp_old = s["hcnt"][v]
            s["vlen"] = s["vlen"].at[worst].add(-vl * share_arr[vp_old])
            s["hold"] = s["hold"].at[wv].set(0)
            s["hcnt"] = s["hcnt"].at[v].add(-1)
            vholdcol = s["hold"][rowbase + v]  # remaining holders
            vdelta = vl * (share_arr[vp_old - 1] - share_arr[vp_old])
            s["vlen"] = s["vlen"] + vdelta * vholdcol  # inflation
            cons = vp_old == 1
            if ghost_retention:
                gt = s["gtail"]
                s["ghead"] = jnp.where(cons & (gt == -1), v, s["ghead"])
                s["gnxt"] = _upd(s["gnxt"], gt, v, cons & (gt != -1))
                s["gprv"] = _upd(s["gprv"], v, gt, cons)
                s["gnxt"] = _upd(s["gnxt"], v, -1, cons)
                s["gtail"] = jnp.where(cons, v, s["gtail"])
                s["isghost"] = _upd(s["isghost"], v, 1, cons)
            else:
                s["phys"] = s["phys"] - jnp.where(cons, vl, 0)
                s["length"] = _upd(s["length"], v, 0, cons)
            return s, n_ev + 1, n_rip + (worst != i).astype(I32)

        sub = {key: st[key] for key in EV_KEYS}
        sub, n_ev, n_rip = lax.while_loop(
            ev_cond, ev_body, (sub, jnp.int32(0), jnp.int32(0))
        )
        st.update(sub)

        # ---- miss: reconcile transient physical overshoot ------------
        st = ghost_loop(st, lambda phys: miss & (phys > B))
        rec = miss & (idx >= ripple_from)
        one = rec.astype(I32)
        st["n_sets"] = st["n_sets"] + one
        st["hist"] = st["hist"].at[jnp.minimum(n_ev, HIST_MAX - 1)].add(one)
        st["n_rip"] = st["n_rip"] + jnp.where(rec, n_rip, 0)
        st["n_prim"] = st["n_prim"] + jnp.where(rec, n_ev - n_rip, 0)
        st["reqs_p"] = st["reqs_p"].at[i].add(post.astype(I32))
        return st

    return lax.fori_loop(0, n, step, st)


@functools.lru_cache(maxsize=None)
def _single_fn(ghost_retention: bool, n_objects: int):
    """Jitted single-replica driver (state donated) for one flag set,
    named ``jit_mc_drive`` in a device trace."""

    def mc_drive(*args):
        return _drive_impl(
            *args, ghost_retention=ghost_retention, n_objects=n_objects
        )

    return jax.jit(mc_drive, donate_argnums=(0,))


def _drive_batched_impl(
    st,  # carried state dict with a leading replica axis R
    P,  # (R, n) int32 proxies, one trace per lane
    O,  # (R, n) int32 objects
    idx0,  # () int32 absolute index of the chunk's first request
    lengths,  # (N,) int32 (shared across lanes)
    b_scaled,  # (J,) or (R, J) vlen type — per-lane rows = sweep points
    bhat_scaled,  # (J,) or (R, J) vlen type
    share_arr,  # (J+2,) vlen type
    B,  # () int32
    warmup,  # () int32
    ripple_from,  # () int32
    *,
    ghost_retention: bool,
    n_objects: int,
):
    """R independent replicas through one compiled micro-op loop.

    Identical per-lane semantics to :func:`_drive_impl`, but the nested
    request-loop / eviction-loop / ghost-loop structure is flattened
    into ONE ``lax.while_loop`` of predicated micro-ops: each iteration
    advances every lane by one action — a head-ghost eviction, the
    hit/attach/miss request body (plus its first eviction), one more
    ripple eviction, or a reconcile eviction — tracked by a per-lane
    ``(cursor, phase)`` pair. Lanes progress through their own traces
    independently (a lane rippling evictions never stalls the others),
    and no inner ``lax.while_loop`` remains: nested loops make XLA's
    copy insertion materialize the big carry buffers around every
    request, which is the dominant cost of a lockstep formulation on
    CPU. Updates are single predicated scatters (``mode="drop"`` with
    the predicate encoded as an out-of-bounds index) or dense one-hot
    selects for the J-wide arrays, so per-op overhead is paid once per
    R lanes.

    The per-lane mutation sequence (ghost evictions, attach, evictions,
    reconcile, stats) is exactly the single-lane order, so lane r fed
    trace r is bit-identical to :func:`_drive_impl` on that trace.
    """
    R, m = P.shape
    J = share_arr.shape[0] - 2
    N = n_objects
    I32 = jnp.int32
    LN = jnp.arange(R, dtype=I32)
    rowbase = jnp.arange(J, dtype=I32) * N
    proxy_ids = jnp.arange(J, dtype=I32)
    b_b = jnp.broadcast_to(b_scaled, (R, J))
    bh_b = jnp.broadcast_to(bhat_scaled, (R, J))
    ones_l = jnp.ones((R,), I32)

    def g1(vec, idx):
        """Per-lane gather: vec[(R, M)][lane, idx[lane]] -> (R,)."""
        return vec[LN, jnp.maximum(idx, 0)]

    def s1(vec, idx, val, pred):
        """Per-lane predicated scatter: vec[lane, idx] = val if pred.

        The predicate is encoded as an out-of-bounds column index
        (``mode="drop"`` discards it), so the update is one scatter op
        instead of gather + select + scatter. ``idx`` must be a valid
        in-bounds index whenever ``pred`` holds (the engine's structural
        invariants guarantee it, exactly as in the single-lane driver).
        """
        oob = vec.shape[1]
        tgt = jnp.where(pred, idx, oob)
        return vec.at[LN, tgt].set(val, mode="drop")

    def a1(vec, idx, val, pred):
        """Per-lane predicated scatter-add (same drop-mode trick)."""
        oob = vec.shape[1]
        tgt = jnp.where(pred, idx, oob)
        return vec.at[LN, tgt].add(val, mode="drop")

    def _bval(val, dtype):
        val = jnp.asarray(val, dtype)
        return jnp.broadcast_to(
            val[:, None] if val.ndim == 1 else val, (R, J)
        )

    def gJ(vec, col):
        """Per-lane gather from a (R, J) array via dense one-hot sum."""
        return jnp.where(proxy_ids[None, :] == col[:, None], vec, 0).sum(
            axis=1, dtype=I32
        )

    def sJ(vec, col, val, pred):
        """Per-lane predicated write into a (R, J) array, dense form."""
        pred = jnp.broadcast_to(pred, (R,))
        mask = (proxy_ids[None, :] == col[:, None]) & pred[:, None]
        return jnp.where(mask, _bval(val, vec.dtype), vec)

    def aJ(vec, col, val, pred):
        """Per-lane predicated add into a (R, J) array, dense form."""
        pred = jnp.broadcast_to(pred, (R,))
        mask = (proxy_ids[None, :] == col[:, None]) & pred[:, None]
        return vec + jnp.where(mask, _bval(val, vec.dtype), 0)

    def body(carry):
        st, cur, phase, wasmiss, nev, nrip = carry
        st = dict(st)
        curc = jnp.minimum(cur, m - 1)
        i = P[LN, curc]
        k = O[LN, curc]
        base = i * N
        ik = base + k
        idx = idx0 + cur  # (R,) absolute index of each lane's request
        post = idx >= warmup
        inflight = cur < m

        # ---- classify lanes sitting at a request boundary ------------
        held = g1(st["hold"], ik) == 1
        resident = g1(st["length"], k) > 0
        missp = (~held) & (~resident)
        l_new = lengths[k]
        p0 = inflight & (phase == 0)
        p1 = inflight & (phase == 1)
        p2 = inflight & (phase == 2)
        ghosts = st["ghead"] != -1
        need_pre = p0 & missp & (st["phys"] + l_new > B) & ghosts
        need_rec = p2 & (wasmiss == 1) & (st["phys"] > B) & ghosts
        gact = need_pre | need_rec

        # ---- ghost-evict action (one head ghost per active lane) -----
        g = st["ghead"]
        gn = g1(st["gnxt"], g)
        st["ghead"] = jnp.where(gact, gn, st["ghead"])
        st["gtail"] = jnp.where(gact & (gn == -1), -1, st["gtail"])
        st["gprv"] = s1(st["gprv"], gn, -1, gact & (gn != -1))
        st["isghost"] = s1(st["isghost"], g, 0, gact)
        glen = g1(st["length"], g)
        st["phys"] = st["phys"] - jnp.where(gact, glen, 0)
        st["length"] = s1(st["length"], g, 0, gact)

        # ---- request action (lanes whose physical room suffices) -----
        still_pre = (
            need_pre & (st["phys"] + l_new > B) & (st["ghead"] != -1)
        )
        doreq = p0 & ~still_pre
        hit = doreq & held
        hitc = doreq & (~held) & resident
        missnow = doreq & missp
        att = doreq & (~held)

        st["n_hit_list"] = st["n_hit_list"] + hit.astype(I32)
        st["n_hit_cache"] = st["n_hit_cache"] + hitc.astype(I32)
        st["n_miss"] = st["n_miss"] + missnow.astype(I32)
        st["hits_p"] = aJ(st["hits_p"], i, ones_l, hit & post)

        # miss: become resident (room was made above / in prior rounds)
        st["length"] = s1(st["length"], k, l_new, missnow)
        st["phys"] = st["phys"] + jnp.where(missnow, l_new, 0)

        # attach bookkeeping (share re-apportionment, eq. (5))
        l = g1(st["length"], k)
        p_old = g1(st["hcnt"], k)
        delta = l * (share_arr[p_old + 1] - share_arr[p_old])
        holdcol = st["hold"][LN[:, None], rowbase[None, :] + k[:, None]]
        st["vlen"] = st["vlen"] + jnp.where(att, delta, 0)[:, None] * holdcol
        st["vlen"] = aJ(st["vlen"], i, l * share_arr[p_old + 1], att)
        res = att & (p_old == 0) & (g1(st["isghost"], k) == 1)
        gp = g1(st["gprv"], k)
        gn2 = g1(st["gnxt"], k)
        st["ghead"] = jnp.where(res & (gp == -1), gn2, st["ghead"])
        st["gnxt"] = s1(st["gnxt"], gp, gn2, res & (gp != -1))
        st["gtail"] = jnp.where(res & (gn2 == -1), gp, st["gtail"])
        st["gprv"] = s1(st["gprv"], gn2, gp, res & (gn2 != -1))
        st["isghost"] = s1(st["isghost"], k, 0, res)
        st["hold"] = s1(st["hold"], ik, 1, att)
        st["hcnt"] = a1(st["hcnt"], k, 1, att)

        # list hit: unlink k from its current position
        headi = gJ(st["head"], i)
        not_head = headi != k
        rem = hit & not_head
        p = g1(st["prv"], ik)
        nx = g1(st["nxt"], ik)
        st["tail"] = sJ(st["tail"], i, nx, rem & (p == -1))
        st["nxt"] = s1(st["nxt"], base + p, nx, rem & (p != -1))
        st["prv"] = s1(st["prv"], base + nx, p, rem)  # nx != -1: not head

        # insert k at the head of list i (hit-not-head or attach)
        mv = rem | att
        st["tail"] = sJ(st["tail"], i, k, att & (headi == -1))
        st["nxt"] = s1(st["nxt"], base + headi, k, mv & (headi != -1))
        st["prv"] = s1(st["prv"], ik, headi, mv)
        st["nxt"] = s1(st["nxt"], ik, -1, mv)
        st["head"] = sJ(st["head"], i, k, doreq)
        st["res_since"] = s1(st["res_since"], ik, idx, att)

        # request-boundary resets of the per-request registers
        wasmiss = jnp.where(doreq, missnow.astype(I32), wasmiss)
        nev = jnp.where(doreq, 0, nev)
        nrip = jnp.where(doreq, 0, nrip)

        # ---- one eviction for over-limit lanes (RRE thresholds) ------
        lim = jnp.where(proxy_ids[None, :] == i[:, None], b_b, bh_b)
        eligible = att | p1
        evact = eligible & (jnp.max(st["vlen"] - lim, axis=1) > 0)
        worst = jnp.argmax(st["vlen"] - lim, axis=1).astype(I32)
        wbase = worst * N
        v = gJ(st["tail"], worst)
        wv = wbase + v
        nv = g1(st["nxt"], wv)
        st["tail"] = sJ(st["tail"], worst, nv, evact)
        st["head"] = sJ(
            st["head"], worst, jnp.full((R,), -1, I32), evact & (nv == -1)
        )
        st["prv"] = s1(st["prv"], wbase + nv, -1, evact & (nv != -1))
        since = g1(st["res_since"], wv)
        add = idx - jnp.maximum(since, st["t_start"])
        st["tot_time"] = a1(st["tot_time"], wv, add, evact & (since >= 0))
        st["res_since"] = s1(st["res_since"], wv, -1, evact)
        vl = g1(st["length"], v)
        vp_old = g1(st["hcnt"], v)
        st["vlen"] = aJ(st["vlen"], worst, -vl * share_arr[vp_old], evact)
        st["hold"] = s1(st["hold"], wv, 0, evact)
        st["hcnt"] = a1(st["hcnt"], v, -1, evact)
        vholdcol = st["hold"][LN[:, None], rowbase[None, :] + v[:, None]]
        vdelta = vl * (share_arr[vp_old - 1] - share_arr[vp_old])
        st["vlen"] = (
            st["vlen"] + jnp.where(evact, vdelta, 0)[:, None] * vholdcol
        )
        cons = evact & (vp_old == 1)
        if ghost_retention:
            gt = st["gtail"]
            st["ghead"] = jnp.where(cons & (gt == -1), v, st["ghead"])
            st["gnxt"] = s1(st["gnxt"], gt, v, cons & (gt != -1))
            st["gprv"] = s1(st["gprv"], v, gt, cons)
            st["gnxt"] = s1(st["gnxt"], v, -1, cons)
            st["gtail"] = jnp.where(cons, v, st["gtail"])
            st["isghost"] = s1(st["isghost"], v, 1, cons)
        else:
            st["phys"] = st["phys"] - jnp.where(cons, vl, 0)
            st["length"] = s1(st["length"], v, 0, cons)
        nev = nev + evact.astype(I32)
        nrip = nrip + (evact & (worst != i)).astype(I32)

        # ---- transitions + request completion ------------------------
        over2 = jnp.max(st["vlen"] - lim, axis=1) > 0
        evicting = eligible & over2
        past_ev = hit | (eligible & ~over2) | p2
        rec_need = (
            past_ev
            & (wasmiss == 1)
            & (st["phys"] > B)
            & (st["ghead"] != -1)
        )
        done = past_ev & ~rec_need
        recs = done & (wasmiss == 1) & (idx >= ripple_from)
        st["n_sets"] = st["n_sets"] + recs.astype(I32)
        st["hist"] = a1(st["hist"], jnp.minimum(nev, HIST_MAX - 1), 1, recs)
        st["n_rip"] = st["n_rip"] + jnp.where(recs, nrip, 0)
        st["n_prim"] = st["n_prim"] + jnp.where(recs, nev - nrip, 0)
        st["reqs_p"] = aJ(st["reqs_p"], i, ones_l, done & post)
        cur = cur + done.astype(I32)
        phase = jnp.where(
            done,
            0,
            jnp.where(evicting, 1, jnp.where(rec_need, 2, phase)),
        )
        return st, cur, phase, wasmiss, nev, nrip

    def body_unrolled(carry):
        # Amortize the per-iteration carry materialization (XLA copies a
        # handful of carry buffers on entry to the loop body) over
        # several micro-ops; lanes with nothing to do no-op harmlessly.
        for _ in range(_UNROLL):
            carry = body(carry)
        return carry

    zero = jnp.zeros((R,), I32)
    carry = (st, zero, zero, zero, zero, zero)
    st, *_ = lax.while_loop(
        lambda c: jnp.any(c[1] < m), body_unrolled, carry
    )
    return st


# Micro-ops per compiled loop iteration (see body_unrolled above).
_UNROLL = 2


@functools.lru_cache(maxsize=None)
def _batched_fn(ghost_retention: bool, n_objects: int):
    """Jitted R-replica ensemble driver (state donated), named
    ``jit_mc_drive_lanes`` in a device trace."""

    def mc_drive_lanes(*args):
        return _drive_batched_impl(
            *args, ghost_retention=ghost_retention, n_objects=n_objects
        )

    return jax.jit(mc_drive_lanes, donate_argnums=(0,))


# Global AOT executable cache. A compiled driver depends only on the
# static flags and argument *shapes* (allocations, thresholds, lengths
# are runtime operands), so executables are shared across runner
# instances — eight sequential single-replica runs compile once, not
# eight times. Keyed on (driver kind, flags, J, N, const shapes, chunk
# length).
_AOT_CACHE: Dict[tuple, object] = {}


class _RunnerBase:
    """Shared chunk-feeding machinery: per-shape AOT compilation cache,
    warmup-boundary chunk splitting, timed execution, output assembly."""

    def __init__(
        self,
        params,
        n_objects: int,
        lengths,
        warmup: int,
        ripple_from: int,
        scale: int,
        b=None,
        b_hat=None,
    ) -> None:
        """``b`` / ``b_hat`` override the allocations with ``(J,)`` or
        per-lane ``(R, J)`` rows (raw units)."""
        J = len(params.allocations)
        self.J = J
        self.N = int(n_objects)
        if b is None:
            b = params.allocations
        if b_hat is None:
            b_hat = (
                params.ripple_allocations
                if params.ripple_allocations is not None
                else b
            )
        b = np.asarray(b, dtype=np.int64)
        b_hat = np.asarray(b_hat, dtype=np.int64)
        B = (
            params.physical_capacity
            if params.physical_capacity is not None
            else int(np.asarray(params.allocations).sum())
        )
        lengths = np.asarray(lengths)
        self.vdt = vlen_dtype(J, int(lengths.max()), int(b_hat.max()))
        if self.vdt is None:
            raise ValueError("virtual lengths past int64: J or sizes too large")
        share = [0] + [scale // p for p in range(1, J + 1)] + [0]
        self.ghost_retention = bool(params.ghost_retention)
        self.warmup = int(warmup)
        with _wide(self.vdt):
            self.consts = (
                jnp.asarray(lengths, jnp.int32),
                jnp.asarray(b * scale, self.vdt),
                jnp.asarray(b_hat * scale, self.vdt),
                jnp.asarray(share, self.vdt),
                jnp.int32(B),
                jnp.int32(warmup),
                jnp.int32(ripple_from),
            )
        self._compiled: Dict[int, object] = {}
        self.n_compiles = 0
        self.idx = 0
        self.elapsed = 0.0

    # -- subclass hooks -------------------------------------------------
    def _fn(self):  # the jitted driver to lower/compile
        raise NotImplementedError

    def _reset_window(self) -> None:
        """Occupancy-window reset at the warmup boundary (outside the
        compiled step — the runners split chunks here instead of
        predicating a whole-vector zeroing into the per-request
        program)."""
        self.st = dict(self.st)
        self.st["tot_time"] = jnp.zeros_like(self.st["tot_time"])
        self.st["t_start"] = jnp.full_like(self.st["t_start"], self.warmup)

    def _key_extra(self) -> tuple:
        return ()

    def _cache_key(self, m: int) -> tuple:
        return (
            type(self).__name__,
            self.ghost_retention,
            self.N,
            self.J,
            tuple((tuple(c.shape), c.dtype.name) for c in self.consts),
            m,
        ) + self._key_extra()

    def _run(self, P: jnp.ndarray, O: jnp.ndarray) -> None:
        """Execute one compiled chunk (compiling outside the timed
        region on first sight of this chunk shape)."""
        m = int(P.shape[-1])
        args = (self.st, P, O, jnp.int32(self.idx)) + self.consts
        ex = self._compiled.get(m)
        if ex is None:
            key = self._cache_key(m)
            ex = _AOT_CACHE.get(key)
            if ex is None:
                # AOT: lower + compile once per (flags, shapes), reuse
                # the executable for every later same-shape feed — the
                # warm-up is the real compiled object, not a hint to a
                # version-dependent jit cache.
                with spans.span("mc.compile"), _wide(self.vdt):
                    ex = self._fn().lower(*args).compile()
                _AOT_CACHE[key] = ex
                self.n_compiles += 1
            self._compiled[m] = ex
        with spans.timed("mc.drive") as drive:
            st = ex(*args)
            for leaf in jax.tree_util.tree_leaves(st):
                leaf.block_until_ready()
        self.elapsed += drive.seconds
        self.st = st
        self.idx += m

    def _feed_arrays(self, P: jnp.ndarray, O: jnp.ndarray) -> None:
        m = int(P.shape[-1])
        w = self.warmup
        if self.idx <= w < self.idx + m:
            cut = w - self.idx
            if cut > 0:
                self._run(P[..., :cut], O[..., :cut])
            self._reset_window()
            if cut < m:
                self._run(P[..., cut:], O[..., cut:])
        else:
            self._run(P, O)

    @staticmethod
    def _finish_one(st: Dict[str, np.ndarray], n_total: int) -> Dict:
        t_start = int(st["t_start"])
        res = st["res_since"].astype(np.int64)
        tot = st["tot_time"].astype(np.int64)
        open_m = res >= 0
        tot[open_m] += n_total - np.maximum(res[open_m], t_start)
        return {
            "tot_time": tot,
            "horizon": max(n_total - t_start, 1),
            "vlen": st["vlen"],
            "n_hit_list": int(st["n_hit_list"]),
            "n_hit_cache": int(st["n_hit_cache"]),
            "n_miss": int(st["n_miss"]),
            "hits_p": st["hits_p"],
            "reqs_p": st["reqs_p"],
            "hist": st["hist"],
            "n_sets": int(st["n_sets"]),
            "n_prim": int(st["n_prim"]),
            "n_rip": int(st["n_rip"]),
        }


class XLAChunkRunner(_RunnerBase):
    """Chunk-fed XLA driver: state carried across compiled calls.

    Same ``feed`` / ``finish`` / ``elapsed`` interface as the C and
    Python chunk drivers in :mod:`repro.core.fastsim` /
    :mod:`repro.core.fastsim_c`. Each new chunk shape is lowered and
    compiled exactly once via the AOT API *outside* the timed region and
    the compiled executable is stored (``_compiled``) and reused, so
    ``elapsed`` measures steady-state execution only. The carried state
    is donated: feeding updates the engine buffers in place.
    """

    def __init__(
        self,
        params,
        n_objects: int,
        lengths,
        warmup: int,
        ripple_from: int,
        scale: int,
    ) -> None:
        super().__init__(params, n_objects, lengths, warmup, ripple_from, scale)
        with _wide(self.vdt):
            self.st = _init_state(self.J, self.N, vdt=self.vdt)

    def _fn(self):
        return _single_fn(self.ghost_retention, self.N)

    def feed(self, proxies, objects) -> None:
        with spans.span("mc.feed"):
            P = jnp.asarray(np.asarray(proxies), jnp.int32)
            O = jnp.asarray(np.asarray(objects), jnp.int32)
        self._feed_arrays(P, O)

    def finish(self, n_total: int) -> Dict[str, np.ndarray]:
        st = {k: np.asarray(v) for k, v in self.st.items()}
        return self._finish_one(st, n_total)


class BatchedXLARunner(_RunnerBase):
    """R-replica ensemble driver: one batched compiled program.

    ``feed`` takes stacked ``(R, m)`` proxy/object chunks — replica r's
    trace in lane r — and advances R independent engines in one
    micro-op loop (lanes progress through their traces at their own
    pace; see :func:`_drive_batched_impl`). Lane 0 is bit-identical to
    :class:`XLAChunkRunner` fed the same trace (same per-lane update
    sequence, same exact integer arithmetic). With ``b_sweep`` / ``bhat_sweep``
    each lane additionally gets its own eviction thresholds (stacked
    ``(b, b_hat)`` sweep points).

    ``finish`` returns one output dict per replica (the same keys as
    :meth:`XLAChunkRunner.finish`).
    """

    def __init__(
        self,
        params,
        n_objects: int,
        lengths,
        warmup: int,
        ripple_from: int,
        scale: int,
        replications: int,
        *,
        b_sweep=None,
        bhat_sweep=None,
    ) -> None:
        if replications < 1:
            raise ValueError("replications must be >= 1")
        self.R = int(replications)
        J = len(params.allocations)
        self.sweep = b_sweep is not None or bhat_sweep is not None
        if self.sweep:
            # Per-lane (b, b_hat) sweep points, in raw allocation units.
            b_raw = np.asarray(params.allocations, dtype=np.int64)
            bh_raw = (
                np.asarray(params.ripple_allocations, dtype=np.int64)
                if params.ripple_allocations is not None
                else b_raw
            )
            b_sweep = (
                np.tile(b_raw, (self.R, 1))
                if b_sweep is None
                else np.asarray(b_sweep, dtype=np.int64)
            )
            bhat_sweep = (
                np.tile(bh_raw, (self.R, 1))
                if bhat_sweep is None
                else np.asarray(bhat_sweep, dtype=np.int64)
            )
            if b_sweep.shape != (self.R, J) or bhat_sweep.shape != (
                self.R,
                J,
            ):
                raise ValueError("sweep arrays must have shape (R, J)")
            if np.any(bhat_sweep < b_sweep):
                raise ValueError("sweep points must satisfy b_hat >= b")
        super().__init__(
            params, n_objects, lengths, warmup, ripple_from, scale,
            b=b_sweep, b_hat=bhat_sweep,
        )
        with _wide(self.vdt):
            self.st = _init_state(self.J, self.N, batch=self.R, vdt=self.vdt)

    def _key_extra(self) -> tuple:
        return (self.R,)

    def _fn(self):
        # Sweep vs shared thresholds is a shape difference ((R, J) vs
        # (J,) consts) — the same program handles both via broadcast.
        return _batched_fn(self.ghost_retention, self.N)

    def feed(self, proxies, objects) -> None:
        with spans.span("mc.feed"):
            P = jnp.asarray(np.asarray(proxies), jnp.int32)
            O = jnp.asarray(np.asarray(objects), jnp.int32)
        if P.ndim != 2 or P.shape[0] != self.R:
            raise ValueError(
                f"ensemble feed expects stacked (R={self.R}, m) chunks, "
                f"got shape {tuple(P.shape)}"
            )
        self._feed_arrays(P, O)

    def finish(self, n_total: int) -> List[Dict[str, np.ndarray]]:
        st = {k: np.asarray(v) for k, v in self.st.items()}
        return [
            self._finish_one({k: v[r] for k, v in st.items()}, n_total)
            for r in range(self.R)
        ]


def simulate_ensemble(
    params,
    traces: Sequence,
    n_objects: int,
    n_requests: Optional[int] = None,
    *,
    lengths=None,
    warmup: Optional[int] = None,
    ripple_from: Optional[int] = None,
    sparse: bool = False,
) -> List:
    """Drive R independent replicas through one batched XLA program.

    ``traces`` is a sequence of R equal-length
    :class:`~repro.core.irm.IRMTrace` objects (one per replica), or a
    sequence of R chunk *iterables* (e.g. ``Workload.iter_chunks`` per
    replica seed) that are consumed in lockstep — pass ``n_requests``
    explicitly in the streamed case. Returns one
    :class:`~repro.core.fastsim.SimResult` per replica; replica 0 is
    bit-identical to ``simulate_trace(..., engine="xla")`` on the same
    trace. Each result's ``elapsed_s`` is the wall clock of the whole
    batch, so aggregate ensemble throughput is
    ``sum(r.requests_per_sec for r in results)``.
    """
    from .fastsim import (
        _assemble,
        _validate_params,
        _xla_applicable,
        default_warmup,
    )
    from .shared_lru import _lcm_1_to

    _validate_params(params)
    if params.variant != "lru":
        raise ValueError(
            "simulate_ensemble drives the flat shared-LRU variant only"
        )
    if params.batch_interval:
        raise ValueError("the XLA driver does not support batch_interval")
    R = len(traces)
    if R < 1:
        raise ValueError("need at least one replica trace")
    N = int(n_objects)
    streamed = not hasattr(traces[0], "proxies")
    if n_requests is None:
        if streamed:
            raise ValueError("streamed ensembles need an explicit n_requests")
        n_requests = len(traces[0])
    n = int(n_requests)
    if not streamed and any(len(t) != n for t in traces):
        raise ValueError("all replica traces must have the same length")
    if lengths is None:
        lengths_a = np.ones(N, dtype=np.int64)
    else:
        lengths_a = np.ascontiguousarray(np.asarray(lengths), dtype=np.int64)
    if warmup is None:
        warmup = default_warmup(n, params.allocations)
    warmup = min(warmup, n)
    if ripple_from is None:
        ripple_from = warmup
    if not _xla_applicable(n, N, lengths_a, params):
        raise ValueError(
            "workload exceeds the XLA driver's exact integer range"
        )
    J = len(params.allocations)
    scale = _lcm_1_to(J)
    # constants and fresh engine state onto the device
    with spans.span("mc.feed"):
        runner = BatchedXLARunner(
            params, N, lengths_a, warmup, ripple_from, scale, R
        )
    if streamed:
        consumed = 0
        for group in zip(*traces):
            m = len(group[0].proxies)
            if any(len(c.proxies) != m for c in group):
                raise ValueError(
                    "replica chunk streams must yield equal-length chunks"
                )
            with spans.span("mc.feed"):
                P = np.stack([np.asarray(c.proxies) for c in group])
                O = np.stack([np.asarray(c.objects) for c in group])
            runner.feed(P, O)
            consumed += m
        if consumed != n:
            raise ValueError(
                f"chunk streams supplied {consumed} requests but "
                f"n_requests={n}"
            )
    else:
        with spans.span("mc.feed"):
            P = np.stack([np.asarray(t.proxies) for t in traces])
            O = np.stack([np.asarray(t.objects) for t in traces])
        runner.feed(P, O)
    with spans.span("mc.collect"):
        outs = runner.finish(n)
        return [
            _assemble(
                out, runner.elapsed, n, warmup, J, N, scale, "xla", sparse
            )
            for out in outs
        ]
