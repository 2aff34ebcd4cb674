"""Working-set solver: numpy cross-checks, bounds, and structure.

Only the final randomized sweep needs hypothesis; the module (including
the numpy reference implementation ``_numpy_residual``) stays importable
and the deterministic tests run without it.
"""

import functools
import itertools

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # pragma: no cover - optional dependency
    given = settings = st = None

from repro.core import workingset
from repro.core import (
    attribution_matrix,
    expected_inverse_one_plus,
    rate_matrix,
    solve_workingset,
    solve_workingset_unshared,
)

import jax
import jax.numpy as jnp


def test_expected_inverse_exact_vs_monte_carlo():
    rng = np.random.default_rng(0)
    h = rng.uniform(0, 1, size=5)
    exact = float(expected_inverse_one_plus(jnp.asarray(h), n_quad=8))
    zs = rng.random((200_000, 5)) < h
    mc = np.mean(1.0 / (1.0 + zs.sum(axis=1)))
    assert exact == pytest.approx(mc, rel=5e-3)


def test_expected_inverse_closed_form_j2():
    # paper: E[1/(1+Z)] = 1 - h/2 for a single Bernoulli(h)
    for h in (0.0, 0.3, 0.99, 1.0):
        got = float(expected_inverse_one_plus(jnp.asarray([h]), n_quad=8))
        assert got == pytest.approx(1 - h / 2, abs=1e-6)


def test_attribution_ordering_eq14_eq15():
    """Paper eqs (14)-(15): L1 >= L* >= L2 elementwise."""
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.uniform(0.05, 0.95, size=(4, 50)))
    lens = jnp.ones(50)
    L1 = np.asarray(attribution_matrix(h, lens, "L1", 8))
    Ls = np.asarray(attribution_matrix(h, lens, "Lstar", 8))
    L2 = np.asarray(attribution_matrix(h, lens, "L2", 8))
    assert np.all(L1 >= Ls - 1e-6)
    assert np.all(Ls >= L2 - 1e-6)
    assert np.all(L1 <= 1.0 + 1e-6)  # never exceeds the full length


def _numpy_residual(lam, lengths, b, t, n_quad=8):
    """Independent numpy implementation of eq. (8) with L1."""
    h = 1.0 - np.exp(-lam * t[:, None])
    x, w = np.polynomial.legendre.leggauss(n_quad)
    x = (x + 1) / 2
    w = w / 2
    J, N = h.shape
    res = np.empty(J)
    for i in range(J):
        others = np.delete(h, i, axis=0)              # (J-1, N)
        terms = 1.0 - others[None] * (1.0 - x[:, None, None])
        e = (terms.prod(axis=1) * w[:, None]).sum(axis=0)
        res[i] = b[i] - (h[i] * lengths * e).sum()
    return res


def test_solver_satisfies_eq8_vs_numpy():
    lam = rate_matrix(400, [0.8, 0.6, 1.1])
    lengths = np.ones(400)
    b = np.array([10.0, 20.0, 6.0])
    sol = solve_workingset(lam, lengths, b, attribution="L1")
    assert sol.converged
    res = _numpy_residual(lam, lengths, b, sol.t)
    assert np.max(np.abs(res)) < 1e-2 * b.max()


def test_unshared_matches_classical_denning_schwartz():
    lam = rate_matrix(300, [1.0])
    lengths = np.ones(300)
    sol = solve_workingset_unshared(lam, lengths, np.array([12.0]))
    # b = sum h must hold exactly
    assert sol.h[0].sum() == pytest.approx(12.0, rel=1e-4)
    # monotone in rank
    assert np.all(np.diff(sol.h[0]) <= 1e-9)


def test_sharing_raises_hit_probs_vs_unshared():
    """Prop 3.1 at the approximation level."""
    lam = rate_matrix(400, [0.8, 0.9])
    lengths = np.ones(400)
    b = np.array([15.0, 15.0])
    shared = solve_workingset(lam, lengths, b, attribution="L1")
    unshared = solve_workingset_unshared(lam, lengths, b)
    assert np.all(shared.h >= unshared.h - 1e-6)


def test_monotone_in_allocation():
    lam = rate_matrix(300, [0.7, 0.9])
    lengths = np.ones(300)
    small = solve_workingset(lam, lengths, np.array([8.0, 8.0]))
    big = solve_workingset(lam, lengths, np.array([16.0, 8.0]))
    assert np.all(big.h[0] >= small.h[0] - 1e-6)


def test_eq9_guard():
    lam = rate_matrix(100, [1.0, 1.0])
    with pytest.raises(ValueError):
        solve_workingset(lam, np.ones(100), np.array([60.0, 10.0]))


TABLE2_N = 1000
TABLE2_ALPHAS = (0.75, 0.5, 1.0)
FIG2_ALPHAS = tuple(0.5 + 0.5 * i for i in range(9))
FIG2_N_REDUCED = 5000  # the paper's 10^6 objects, cut 200-fold with b


@pytest.mark.parametrize(
    "alphas, n, b",
    [(TABLE2_ALPHAS, TABLE2_N, b) for b in itertools.product((8.0, 64.0), repeat=3)]
    + [(FIG2_ALPHAS, FIG2_N_REDUCED, (5.0,) * 3 + (10.0,) * 3 + (35.0,) * 3)],
)
def test_one_fetch_matches_the_outputs_one_by_one(alphas, n, b):
    """The packed single fetch and the host-side stop test give the
    solution the solver's outputs give when each is taken to the host
    alone."""
    lam = rate_matrix(n, list(alphas))
    lengths = np.ones(n)
    b = np.asarray(b)
    sol = solve_workingset(lam, lengths, b, attribution="L1")
    tol, n_quad = 1e-7, max(8, (len(alphas) + 1) // 2 + 1)
    fn = jax.jit(
        functools.partial(
            workingset._solve_jax, kind="L1", n_quad=n_quad, n_outer=200,
            n_bisect=90, damping=0.7, tol=tol,
        )
    )
    dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    t, h, L, res, iters, delta, _ = fn(
        *(jnp.asarray(x, dtype) for x in (lam, lengths, b))
    )
    for got, want in ((sol.t, t), (sol.h, h), (sol.L, L), (sol.residual, res)):
        assert got.dtype == np.float64
        assert np.array_equal(got, np.asarray(want, np.float64))
    assert sol.iterations == int(iters)
    assert sol.converged is bool(delta <= tol)


def _j9_lengths(n):
    """J=9 at Fig 2's exponents over ``n`` objects of lengths 1..4."""
    lengths = np.random.default_rng(9).integers(1, 5, size=n).astype(float)
    return rate_matrix(n, list(FIG2_ALPHAS)), lengths


PACKED_CASES = {
    **{f"table2_single_{i}": ("table2", b, False)
       for i, b in enumerate(itertools.product((8.0, 64.0), repeat=3))},
    "j9_lengths_single": ("j9", (12.0,) * 3 + (25.0,) * 3 + (90.0,) * 3, False),
    "table2_x64_single": ("table2", (8.0, 64.0, 8.0), True),
}


@pytest.mark.parametrize("case", sorted(PACKED_CASES))
def test_packed_inputs_match_three_arrays(case):
    """Inputs moved as one vector cast on the host give, bit for bit,
    the answers of the solver run on three arrays each moved and cast
    on the device with ``jnp.asarray``."""
    system, b, x64 = PACKED_CASES[case]
    if system == "table2":
        lam, lengths = rate_matrix(TABLE2_N, list(TABLE2_ALPHAS)), np.ones(TABLE2_N)
    else:
        lam, lengths = _j9_lengths(2000)
    b = np.asarray(b)
    tol, n_quad = 1e-7, max(8, (lam.shape[0] + 1) // 2 + 1)
    with jax.enable_x64(x64):
        sol = solve_workingset(lam, lengths, b, attribution="L1")
        solve = functools.partial(
            workingset._solve_jax, kind="L1", n_quad=n_quad, n_outer=200,
            n_bisect=90, damping=0.7, tol=tol,
        )
        dtype = jnp.float64 if x64 else jnp.float32
        t, h, L, res, iters, delta, _ = jax.jit(solve)(
            *(jnp.asarray(x, dtype) for x in (lam, lengths, b))
        )
        want_converged = bool(delta <= tol)
    assert t.dtype == dtype
    for got, want in ((sol.t, t), (sol.h, h), (sol.L, L), (sol.residual, res)):
        assert got.dtype == np.float64
        assert np.array_equal(got, np.asarray(want, np.float64))
    assert sol.iterations == int(iters)
    assert sol.converged is want_converged


@pytest.mark.parametrize("step", [-1, 0, 1])
def test_host_stop_test_matches_the_device_comparison(step):
    """At float32(tol) and at its two float32 neighbours."""
    tol = 1e-7
    delta = np.float32(tol)
    for _ in range(abs(step)):
        delta = np.nextafter(delta, np.float32(step * np.inf), dtype=np.float32)
    assert delta.dtype == np.float32
    want = bool(jnp.asarray(delta) <= tol)
    assert bool(workingset._converged(delta, tol)) is want
    assert want is (step <= 0)


def _solver_residuals_random(J, alpha0, seed):
    rng = np.random.default_rng(seed)
    alphas = alpha0 + rng.uniform(-0.2, 0.2, size=J)
    lam = rate_matrix(200, alphas.tolist())
    lengths = rng.integers(1, 4, size=200).astype(float)
    b = rng.uniform(4, lengths.sum() / J * 0.8, size=J)
    sol = solve_workingset(lam, lengths, b, attribution="L1")
    assert np.max(np.abs(sol.residual)) < 2e-2 * b.max()
    assert np.all(sol.h >= -1e-9) and np.all(sol.h <= 1 + 1e-6)


if st is not None:

    @settings(max_examples=15, deadline=None)
    @given(
        st.integers(2, 5),
        st.floats(0.4, 1.4),
        st.integers(0, 10_000),
    )
    def test_solver_residuals_random(J, alpha0, seed):
        _solver_residuals_random(J, alpha0, seed)

else:

    def test_solver_residuals_random():
        """Single-seed fallback when hypothesis is unavailable."""
        _solver_residuals_random(3, 0.9, 1234)
