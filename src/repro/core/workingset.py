r"""Working-set approximation for shared-object caches (paper Section IV).

Solves the J-dimensional fixed point (paper eq. (8))

    b_i = sum_k (1 - e^{-lambda_{i,k} t_i}) * L_{i,k},   i = 1..J

for the characteristic ("mean eviction") times ``t_i``, where ``L_{i,k}``
is the mean length of object ``k`` attributed to LRU-list ``i``:

* ``L1``   (paper eq. (5)):  l_k * E[ 1 / (1 + sum_{j!=i} Z_{j,k}) ] with
  independent Bernoulli(h_{j,k}) occupancies Z. Computed **exactly**: for
  S = sum of independent Bernoullis,

      E[1/(1+S)] = \int_0^1 E[x^S] dx = \int_0^1 prod_j (1 - h_j (1-x)) dx,

  a polynomial of degree J-1 integrated exactly by Gauss-Legendre
  quadrature with >= ceil(J/2) nodes.
* ``Lstar`` (eq. (14)): l_k / (1 + sum_{j!=i} h_{j,k})   (Jensen bound).
* ``L2``   (eq. (15)): l_k * h_{i,k} / (h_{i,k} + sum_{j!=i} h_{j,k}).
* ``full``: L = l_k — the classical (not-shared) Denning-Schwartz
  working-set approximation, used for the Table III baseline and for the
  SLA mapping b* <-> t* in the admission controller.

Empirically (paper Section V): L1 is accurate for J >= 3; for J = 2 it
underestimates hit probabilities (~30%) and L2 overestimates, giving
lower/upper bounds.

Solver: damped Jacobi outer iteration; inner step is a vectorized
bisection per proxy (the per-proxy residual is monotone increasing in
t_i for every attribution model — see Prop. 4.2's concavity argument).
Everything is jit-compiled JAX; `numpy` reference implementations used by
the property tests live in ``tests/test_workingset.py``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

ATTRIBUTIONS = ("L1", "Lstar", "L2", "full")


def hit_probabilities(lam: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """h_{i,k} = 1 - exp(-lambda_{i,k} * t_i)  (paper eq. (3))."""
    return -jnp.expm1(-lam * t[:, None])


def _leggauss01(n_nodes: int) -> Tuple[np.ndarray, np.ndarray]:
    """Gauss-Legendre nodes/weights on [0, 1]."""
    x, w = np.polynomial.legendre.leggauss(n_nodes)
    return (x + 1.0) / 2.0, w / 2.0


def expected_inverse_one_plus(h_others: jnp.ndarray, n_quad: int) -> jnp.ndarray:
    """E[1/(1 + sum_j Z_j)] for independent Z_j ~ Bernoulli(h_others[j]).

    ``h_others``: (..., J-1) stacked success probabilities; returns (...).
    Exact for polynomial degree J-1 <= 2*n_quad - 1.
    """
    x, w = _leggauss01(n_quad)
    x = jnp.asarray(x, h_others.dtype)
    w = jnp.asarray(w, h_others.dtype)
    # terms: (..., J-1, Q) -> product over J-1 -> weighted sum over Q.
    terms = 1.0 - h_others[..., None] * (1.0 - x)
    # A guard: the TPU's default f32 dot rounds its inputs to bf16. It
    # only matters where XLA emits a dot for this contraction; the
    # solver's TPU executables today hold none.
    return jnp.matmul(
        jnp.prod(terms, axis=-2), w, precision=jax.lax.Precision.HIGHEST
    )


def _l1_matrix(h: jnp.ndarray, n_quad: int) -> jnp.ndarray:
    """(J, N) matrix E_i,k = E[1/(1+sum_{j!=i} Z_{j,k})], leave-one-out.

    Uses the full product divided by the left-out factor; every factor
    ``1 - h (1-x)`` is >= x > 0 at interior quadrature nodes, so the
    division is always safe.
    """
    x, w = _leggauss01(n_quad)
    x = jnp.asarray(x, h.dtype)            # (Q,)
    w = jnp.asarray(w, h.dtype)

    def one_node(xq, wq):
        terms = 1.0 - h * (1.0 - xq)       # (J, N), strictly positive
        full = jnp.prod(terms, axis=0)     # (N,)
        return wq * full[None, :] / terms  # (J, N) leave-one-out integrand

    contribs = jax.vmap(one_node)(x, w)    # (Q, J, N)
    return contribs.sum(axis=0)


def _others_sum(h: jnp.ndarray) -> jnp.ndarray:
    """s_{i,k} = sum_{j != i} h_{j,k}."""
    return h.sum(axis=0, keepdims=True) - h


def attribution_matrix(
    h: jnp.ndarray,
    lengths: jnp.ndarray,
    kind: str,
    n_quad: int,
) -> jnp.ndarray:
    """L_{i,k} per the selected model, given occupancy probabilities h."""
    if kind == "L1":
        return lengths[None, :] * _l1_matrix(h, n_quad)
    if kind == "Lstar":
        return lengths[None, :] / (1.0 + _others_sum(h))
    if kind == "L2":
        s = _others_sum(h)
        denom = h + s
        frac = jnp.where(denom > 0, h / jnp.where(denom > 0, denom, 1.0), 1.0)
        return lengths[None, :] * frac
    if kind == "full":
        return jnp.broadcast_to(lengths[None, :], h.shape)
    raise ValueError(f"unknown attribution {kind!r}; options: {ATTRIBUTIONS}")


@dataclass
class WorkingSetSolution:
    """Solution of eq. (8): characteristic times + derived quantities."""

    t: np.ndarray          # (J,) characteristic times
    h: np.ndarray          # (J, N) hit probabilities, eq. (3)
    L: np.ndarray          # (J, N) attributed lengths at the solution
    residual: np.ndarray   # (J,) b_i - sum_k h L   (should be ~0)
    iterations: int
    converged: bool

    @property
    def hit_rate(self) -> np.ndarray:
        """Per-proxy request-weighted hit rate: sum_k lambda_norm * h."""
        return self._hit_rate

    def with_rates(self, lam: np.ndarray) -> "WorkingSetSolution":
        lam = np.asarray(lam)
        w = lam / np.maximum(lam.sum(axis=1, keepdims=True), 1e-300)
        self._hit_rate = (w * self.h).sum(axis=1)
        return self


def _solve_jax(
    lam: jnp.ndarray,
    lengths: jnp.ndarray,
    b: jnp.ndarray,
    kind: str,
    n_quad: int,
    n_outer: int,
    n_bisect: int,
    damping: float,
    tol: float,
):
    """Damped Jacobi outer loop + vectorized inner bisection. jit-able."""
    J, N = lam.shape

    def residual_all(t_cand: jnp.ndarray, h_frozen: jnp.ndarray) -> jnp.ndarray:
        """g_i(t_cand_i): eq. (8) residual with *other* proxies frozen.

        For L1/Lstar, L_{i,k} depends only on others' h -> frozen during
        the inner solve. For L2 it also depends on own h, which we
        recompute from the candidate t. ``full`` ignores h entirely.
        """
        h_own = hit_probabilities(lam, t_cand)
        if kind == "L2":
            s = _others_sum(h_frozen)
            denom = h_own + s
            frac = jnp.where(denom > 0, h_own / jnp.where(denom > 0, denom, 1.0), 1.0)
            L = lengths[None, :] * frac
        elif kind == "L1":
            L = lengths[None, :] * _l1_matrix(h_frozen, n_quad)
        elif kind == "Lstar":
            L = lengths[None, :] / (1.0 + _others_sum(h_frozen))
        else:  # full
            L = lengths[None, :]
        return (h_own * L).sum(axis=1) - b

    def inner_solve(h_frozen: jnp.ndarray) -> jnp.ndarray:
        # Bracket: grow hi until residual positive (or cap).
        hi0 = jnp.full((J,), 1e-2, lam.dtype)

        def grow(_, hi):
            g = residual_all(hi, h_frozen)
            return jnp.where(g < 0, hi * 4.0, hi)

        hi = jax.lax.fori_loop(0, 64, grow, hi0)
        lo = jnp.zeros((J,), lam.dtype)

        def bisect(_, lohi):
            lo, hi = lohi
            mid = 0.5 * (lo + hi)
            g = residual_all(mid, h_frozen)
            lo = jnp.where(g < 0, mid, lo)
            hi = jnp.where(g < 0, hi, mid)
            return lo, hi

        lo, hi = jax.lax.fori_loop(0, n_bisect, bisect, (lo, hi))
        return 0.5 * (lo + hi)

    def outer(state):
        t, it, _ = state
        h_frozen = hit_probabilities(lam, t)
        t_new = inner_solve(h_frozen)
        t_next = (1.0 - damping) * t + damping * t_new
        delta = jnp.max(jnp.abs(t_next - t) / jnp.maximum(t, 1e-12))
        return t_next, it + 1, delta

    def cond(state):
        _, it, delta = state
        return jnp.logical_and(it < n_outer, delta > tol)

    t0 = inner_solve(jnp.zeros((J, N), lam.dtype))  # not-shared warm start
    t, iters, delta = jax.lax.while_loop(cond, outer, (t0, 0, jnp.inf))
    h = hit_probabilities(lam, t)
    L = attribution_matrix(h, lengths, kind, n_quad)
    res = b - (h * L).sum(axis=1)
    return t, h, L, res, iters, delta


@functools.lru_cache(maxsize=None)
def _jitted_solver(
    kind: str,
    n_quad: int,
    n_outer: int,
    n_bisect: int,
    damping: float,
    tol: float,
    batched: bool,
):
    """One jit-compiled solver per hyperparameter set (cached).

    Previously every ``solve_workingset`` call wrapped a fresh
    ``functools.partial`` in ``jax.jit``, so the Table-II sweep paid 8
    compilations for 8 identical-shape solves. The cache reuses the
    executable; ``batched=True`` additionally ``vmap``s over a batch of
    allocation vectors so a whole ``b``-grid is one compiled call.
    """
    fn = functools.partial(
        _solve_jax,
        kind=kind,
        n_quad=n_quad,
        n_outer=n_outer,
        n_bisect=n_bisect,
        damping=damping,
        tol=tol,
    )
    if batched:
        fn = jax.vmap(fn, in_axes=(None, None, 0))
    return jax.jit(fn)


def _check_inputs(lam, lengths, b, attribution):
    J, N = lam.shape
    if lengths.shape != (N,) or b.shape[-1] != J:
        raise ValueError("shape mismatch between lam, lengths, b")
    if attribution not in ATTRIBUTIONS:
        raise ValueError(f"unknown attribution {attribution!r}")
    if attribution != "full" and np.any(b >= lengths.sum() / J):
        raise ValueError(
            "paper eq. (9) violated: some b_i >= sum(lengths)/J — the "
            "shared working-set fixed point need not exist"
        )
    if attribution == "full" and np.any(b >= lengths.sum()):
        raise ValueError("b_i >= total catalogue size: cache never evicts")


def solve_workingset(
    lam,
    lengths,
    b,
    attribution: str = "L1",
    *,
    n_quad: int | None = None,
    n_outer: int = 200,
    n_bisect: int = 90,
    damping: float = 0.7,
    tol: float = 1e-7,
) -> WorkingSetSolution:
    """Solve eq. (8) for the characteristic times of every LRU-list.

    Parameters mirror the paper: ``lam`` (J, N) request rates, ``lengths``
    (N,) object lengths, ``b`` (J,) virtual allocations satisfying eq. (9)
    ``b_i < sum_k l_k / J`` (checked). ``attribution`` picks L1 / Lstar /
    L2 / full.
    """
    lam = np.asarray(lam, dtype=np.float64)
    lengths = np.asarray(lengths, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    J, N = lam.shape
    if b.shape != (J,):
        raise ValueError("shape mismatch between lam, lengths, b")
    _check_inputs(lam, lengths, b, attribution)

    if n_quad is None:
        n_quad = max(8, (J + 1) // 2 + 1)

    dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    fn = _jitted_solver(
        attribution, n_quad, n_outer, n_bisect, damping, tol, False
    )
    t, h, L, res, iters, delta = fn(
        jnp.asarray(lam, dtype), jnp.asarray(lengths, dtype), jnp.asarray(b, dtype)
    )
    sol = WorkingSetSolution(
        t=np.asarray(t, np.float64),
        h=np.asarray(h, np.float64),
        L=np.asarray(L, np.float64),
        residual=np.asarray(res, np.float64),
        iterations=int(iters),
        converged=bool(delta <= tol),
    )
    return sol.with_rates(lam)


def solve_workingset_unshared(lam, lengths, b, **kw) -> WorkingSetSolution:
    """Classical Denning-Schwartz (no sharing): eq. (2)-(3)."""
    return solve_workingset(lam, lengths, b, attribution="full", **kw)


def virtual_footprint(
    h,
    lengths,
    attribution: str = "L1",
    n_quad: int | None = None,
) -> np.ndarray:
    """Per-proxy memory footprint ``sum_k h_{i,k} L_{i,k}(h)`` (eq. (4)).

    Evaluates the attributed-length matrix at the given occupancy
    probabilities ``h`` (J, N) and contracts it against ``h`` — the
    virtual allocation each proxy consumes under sharing. Evaluated at
    ``h* = h(t*)`` of the *unshared* working set at the SLA allocation
    ``b*``, this is exactly the minimal SLA-preserving virtual allocation
    of eq. (10); the admission controller
    (:mod:`repro.core.admission`) uses it at every refresh.
    """
    h = np.asarray(h, dtype=np.float64)
    lengths = np.asarray(lengths, dtype=np.float64)
    if attribution not in ATTRIBUTIONS:
        raise ValueError(
            f"unknown attribution {attribution!r}; options: {ATTRIBUTIONS}"
        )
    J = h.shape[0]
    if n_quad is None:
        n_quad = max(8, (J + 1) // 2 + 1)
    L = np.asarray(
        attribution_matrix(jnp.asarray(h), jnp.asarray(lengths), attribution, n_quad)
    )
    return (h * L).sum(axis=1)


def solve_workingset_batch(
    lam,
    lengths,
    b_batch,
    attribution: str = "L1",
    *,
    n_quad: int | None = None,
    n_outer: int = 200,
    n_bisect: int = 90,
    damping: float = 0.7,
    tol: float = 1e-7,
) -> list:
    """Solve eq. (8) for a whole batch of allocation vectors at once.

    ``b_batch``: (K, J) — e.g. the 8 Table-II ``b``-combinations. One
    ``jax.vmap``-ed jit call replaces K sequential solves (and K
    recompilations under the old per-call jit), so the Table-II sweep
    compiles once and solves the grid in a single XLA execution. The
    batched while-loop iterates until the *slowest* combo converges;
    per-combo ``converged`` is still reported from its final delta.

    Returns a list of K :class:`WorkingSetSolution`.
    """
    lam = np.asarray(lam, dtype=np.float64)
    lengths = np.asarray(lengths, dtype=np.float64)
    b_batch = np.atleast_2d(np.asarray(b_batch, dtype=np.float64))
    J, N = lam.shape
    if b_batch.shape[1] != J:
        raise ValueError("b_batch must be (K, J)")
    for b in b_batch:
        _check_inputs(lam, lengths, b, attribution)

    if n_quad is None:
        n_quad = max(8, (J + 1) // 2 + 1)

    dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    fn = _jitted_solver(attribution, n_quad, n_outer, n_bisect, damping, tol, True)
    t, h, L, res, iters, delta = fn(
        jnp.asarray(lam, dtype),
        jnp.asarray(lengths, dtype),
        jnp.asarray(b_batch, dtype),
    )
    t, h, L, res = (np.asarray(x, np.float64) for x in (t, h, L, res))
    iters, delta = np.asarray(iters), np.asarray(delta)
    out = []
    for k in range(b_batch.shape[0]):
        sol = WorkingSetSolution(
            t=t[k],
            h=h[k],
            L=L[k],
            residual=res[k],
            iterations=int(iters[k]),
            converged=bool(delta[k] <= tol),
        )
        out.append(sol.with_rates(lam))
    return out
