"""Plain reference of the working-set fixed point (paper Section IV).

The characteristic times ``t_i`` of the J LRU lists solve eq. (8),

    b_i = sum_k h_{i,k} L_{i,k},    h_{i,k} = 1 - exp(-lambda_{i,k} t_i),

with the exact L1 attribution of eq. (5): ``L_{i,k} = l_k E[1/(1+S)]``,
S the number of other lists that hold k, a sum of independent
Bernoulli(h_{j,k}), j != i. Here E[1/(1+S)] is taken from the
distribution of S itself (a Poisson-binomial recursion over the other
lists), not from a quadrature.

``check`` judges a solve by what it says: it recomputes ``h`` from the
solve's ``t`` in float64 and the residual of eq. (8) there, so it needs
one evaluation and no solve of its own. ``solve`` is a straightforward
solver (damped Jacobi over the lists, bisection per list) written with
``jax.numpy`` at a chosen dtype; the tests compare it with the program
at small sizes, and in a lower precision it is the control.
"""

from __future__ import annotations

import numpy as np


def expect_inv_one_plus_others(h, xp=np):
    """(J, N): E[1/(1+S_{-i,k})] with S_{-i,k} = sum_{j != i} Z_{j,k},
    Z_{j,k} ~ Bernoulli(h[j, k]) independent. ``dist[s]`` is P(S = s)
    over the lists folded in so far."""
    J = h.shape[0]
    rows = []
    for i in range(J):
        dist = [xp.ones_like(h[0])]
        for j in range(J):
            if j == i:
                continue
            hj = h[j]
            qj = 1 - hj
            dist = (
                [dist[0] * qj]
                + [dist[s] * qj + dist[s - 1] * hj for s in range(1, len(dist))]
                + [dist[-1] * hj]
            )
        acc = dist[0]
        for s in range(1, len(dist)):
            acc = acc + dist[s] / (1 + s)
        rows.append(acc)
    return xp.stack(rows)


def occupancy(lam, t, xp=np):
    """Eq. (3): h_{i,k} = 1 - exp(-lambda_{i,k} t_i)."""
    return -xp.expm1(-lam * t[:, None])


def check(lam, lengths, b, t, h) -> dict:
    """How far a solve's ``t`` and ``h`` lie from eq. (8) and eq. (3) in
    float64: the largest residual of eq. (8) relative to b_i, and the
    widest gap between the solve's h and float64 h at its own t."""
    lam = np.asarray(lam, np.float64)
    t = np.asarray(t, np.float64)
    b = np.asarray(b, np.float64)
    h64 = occupancy(lam, t)
    L = np.asarray(lengths, np.float64)[None, :] * expect_inv_one_plus_others(h64)
    res = np.abs(b - (h64 * L).sum(axis=1)) / b
    gap = np.abs(np.asarray(h, np.float64) - h64)
    return {"ws_residual": float(res.max()), "ws_h_gap": float(gap.max())}


def solve(lam, lengths, b, dtype, *, n_outer=200, n_bisect=90, damping=0.7, tol=1e-7):
    """Solve eq. (8) with every array in ``dtype``: start from the
    unshared times, then per outer step freeze the others' h, find each
    list's own t by bisection on its monotone residual, and move a
    damped step toward it, until the relative step is below ``tol``.
    Returns (t, h, outer steps) as float64 numpy."""
    import jax
    import jax.numpy as jnp

    lam = jnp.asarray(lam, dtype)
    lengths = jnp.asarray(lengths, dtype)
    b = jnp.asarray(b, dtype)
    J = lam.shape[0]

    def own_t(att):
        def g(t):
            return (occupancy(lam, t, jnp) * att).sum(axis=1) - b

        hi = jnp.full((J,), 1e-2, dtype)
        hi = jax.lax.fori_loop(0, 64, lambda _, x: jnp.where(g(x) < 0, 4 * x, x), hi)

        def halve(_, lohi):
            lo, hi = lohi
            mid = (lo + hi) / 2
            low = g(mid) < 0
            return jnp.where(low, mid, lo), jnp.where(low, hi, mid)

        lo, hi = jax.lax.fori_loop(0, n_bisect, halve, (jnp.zeros_like(hi), hi))
        return (lo + hi) / 2

    def step(state):
        t, it, _ = state
        h = occupancy(lam, t, jnp)
        att = lengths[None, :] * expect_inv_one_plus_others(h, jnp)
        t_next = (1 - damping) * t + damping * own_t(att)
        delta = jnp.max(jnp.abs(t_next - t) / jnp.maximum(t, 1e-12))
        return t_next, it + 1, delta.astype(jnp.float32)

    def go():
        t0 = own_t(jnp.broadcast_to(lengths[None, :], lam.shape))
        state = (t0, 0, jnp.float32(jnp.inf))
        return jax.lax.while_loop(
            lambda s: (s[1] < n_outer) & (s[2] > tol), step, state
        )

    t, it, _ = jax.jit(go)()
    t64 = np.asarray(t, np.float64)
    h = np.asarray(occupancy(lam, t, jnp), np.float64)
    return t64, h, int(it)
