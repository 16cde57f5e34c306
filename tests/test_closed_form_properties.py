"""Closed-form property suite (ISSUE 7).

Hypothesis-driven invariants of the paper's closed forms — the analytic
facts the fleet solver's correctness rests on, pinned independently of
any engine trajectory:

* PER is monotone non-increasing in SINR (Lemma 1's waterfall model):
  scaling p h up, or the bandwidth-noise product down, cannot raise q;
* the uplink rate is monotone increasing and concave in bandwidth
  (Eq. 3 — what makes the Eq.-(21) inversion single-rooted and the
  Newton iterate monotone);
* the Newton bandwidth inversion round-trips: R^u(B*(r)) == r for every
  feasible target, on both the numpy and the jax array path;
* Algorithm 1's reported ``TradeoffSolution.residual`` is within the
  ``SolverConfig`` tolerance on random feasible cells — converged means
  converged, and the warning fires otherwise.
"""

import warnings

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # offline image: deterministic fallback driver
    from _hypothesis_compat import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import closed_form as CF
from repro.core import tradeoff as T
from repro.core import wireless as W
from repro.fleet import SolverConfig

from conftest import make_problem

SETTINGS = dict(max_examples=25, deadline=None)
N0 = W.dbm_to_watt(-174.0)


# ---------------------------------------------------------------------------
# PER monotone non-increasing in SINR
# ---------------------------------------------------------------------------

@given(st.floats(1e3, 1e7), st.floats(0.01, 1.0), st.floats(1e-12, 1e-8),
       st.floats(1.001, 100.0))
@settings(**SETTINGS)
def test_per_monotone_in_sinr(bw, p, h, scale):
    """Raising SINR (scale up p h at fixed B N0) cannot raise the PER."""
    cfg = W.WirelessConfig()
    q_lo = CF.packet_error_rate(bw, p, h, N0, cfg.waterfall_m0)
    q_hi = CF.packet_error_rate(bw, p * scale, h, N0, cfg.waterfall_m0)
    assert 0.0 <= q_hi <= q_lo < 1.0
    # equivalent SINR raise via the bandwidth-noise product going down
    q_hi_b = CF.packet_error_rate(bw / scale, p, h, N0, cfg.waterfall_m0)
    assert q_hi_b <= q_lo


@given(st.floats(1e3, 1e7), st.floats(0.01, 1.0), st.floats(1e-12, 1e-8),
       st.floats(0.0, 1e-18))
@settings(**SETTINGS)
def test_per_nondecreasing_in_interference(bw, p, h, i_psd):
    """Interference PSD lowers SINR, so it cannot lower the PER."""
    cfg = W.WirelessConfig()
    q0 = CF.packet_error_rate(bw, p, h, N0, cfg.waterfall_m0)
    qi = CF.packet_error_rate(bw, p, h, N0, cfg.waterfall_m0,
                              interference_psd=i_psd)
    assert qi >= q0


# ---------------------------------------------------------------------------
# uplink rate monotone + concave in bandwidth
# ---------------------------------------------------------------------------

@given(st.floats(1e2, 1e6), st.floats(1.001, 50.0), st.floats(0.01, 1.0),
       st.floats(1e-12, 1e-8))
@settings(**SETTINGS)
def test_rate_monotone_in_bandwidth(b1, factor, p, h):
    b2 = b1 * factor
    r1 = CF.uplink_rate(np.array([b1]), p, h, N0)[0]
    r2 = CF.uplink_rate(np.array([b2]), p, h, N0)[0]
    assert 0.0 < r1 < r2


@given(st.floats(1e2, 1e6), st.floats(1e2, 1e6), st.floats(0.01, 1.0),
       st.floats(1e-12, 1e-8))
@settings(**SETTINGS)
def test_rate_concave_in_bandwidth(b1, b2, p, h):
    """Midpoint concavity: r((b1+b2)/2) >= (r(b1)+r(b2))/2."""
    mid = 0.5 * (b1 + b2)
    r = lambda b: CF.uplink_rate(np.array([b]), p, h, N0)[0]
    assert r(mid) >= 0.5 * (r(b1) + r(b2)) * (1.0 - 1e-12)


def test_rate_zero_bandwidth_is_zero():
    assert CF.uplink_rate(np.array([0.0]), 0.2, 1e-10, N0)[0] == 0.0


# ---------------------------------------------------------------------------
# Newton inversion round-trip
# ---------------------------------------------------------------------------

@given(st.floats(0.01, 0.95), st.floats(0.01, 1.0), st.floats(1e-12, 1e-8))
@settings(**SETTINGS)
def test_newton_round_trips_rate(frac, p, h):
    """rate(b(r)) == r at every feasible fraction of the capacity ceiling
    p h / (N0 ln 2), including just below it where the root diverges."""
    ceiling = p * h / (N0 * np.log(2.0))
    target = frac * ceiling
    bw = CF.min_bandwidth_for_rates(np.array([target]), np.array([p]),
                                    np.array([h]), N0)[0]
    assert np.isfinite(bw) and bw > 0.0
    r = CF.uplink_rate(np.array([bw]), p, h, N0)[0]
    assert r == pytest.approx(target, rel=1e-6)


@given(st.floats(1.0, 10.0), st.floats(0.01, 1.0), st.floats(1e-12, 1e-8))
@settings(**SETTINGS)
def test_newton_infeasible_above_ceiling(factor, p, h):
    ceiling = p * h / (N0 * np.log(2.0))
    bw = CF.min_bandwidth_for_rates(np.array([factor * ceiling]),
                                    np.array([p]), np.array([h]), N0)[0]
    assert np.isinf(bw)


def test_newton_round_trips_on_jax_path():
    """The xp=jnp lane (what vmapped fleet cells trace) agrees with numpy
    and round-trips to the same tolerance under x64."""
    import jax
    with jax.enable_x64(True):
        p, h = 0.2, 1e-10
        ceiling = p * h / (N0 * np.log(2.0))
        targets = np.array([0.05, 0.5, 0.9]) * ceiling
        bw_np = CF.min_bandwidth_for_rates(targets, np.full(3, p),
                                           np.full(3, h), N0)
        bw_jx = np.asarray(CF.min_bandwidth_for_rates(
            jnp.asarray(targets), jnp.full(3, p), jnp.full(3, h), N0,
            xp=jnp))
        np.testing.assert_allclose(bw_jx, bw_np, rtol=1e-9)
        r = CF.uplink_rate(bw_jx, p, h, N0)
        np.testing.assert_allclose(r, targets, rtol=1e-6)


# ---------------------------------------------------------------------------
# Algorithm 1 residual within tolerance
# ---------------------------------------------------------------------------

@given(st.integers(0, 40), st.sampled_from([1e-4, 4e-4, 1e-3]))
@settings(**SETTINGS)
def test_residual_within_solver_tolerance(seed, lam):
    """On feasible cells the alternation converges: the reported residual
    is at most the SolverConfig tolerance and no warning fires."""
    rtol = SolverConfig().rtol
    prob = make_problem(seed=seed, weight=lam)
    with warnings.catch_warnings():
        warnings.simplefilter("error", T.SolverConvergenceWarning)
        sol = T.solve_alternating(prob, rtol=rtol)
    assert sol.feasible
    assert 0.0 <= sol.residual <= rtol
    assert sol.iterations <= 50


@given(st.integers(0, 20))
@settings(max_examples=10, deadline=None)
def test_residual_reported_matches_recompute(seed):
    """The stored residual is the actual last cost delta: re-running one
    more alternation from the solution moves the inner cost by at most
    the tolerance."""
    prob = make_problem(seed=seed)
    sol = T.solve_alternating(prob)
    deadline, rho = T.solve_pruning(prob, sol.bandwidth)
    bw = T.solve_bandwidth(prob, rho, deadline)
    c0 = prob.inner_cost(sol.deadline, sol.bandwidth, sol.prune)
    c1 = prob.inner_cost(deadline, bw, rho)
    assert abs(c1 - c0) / max(abs(c0), 1.0) <= 10.0 * SolverConfig().rtol


def test_residual_surfaces_on_iteration_cap():
    """Starving the alternation of iterations must warn and report the
    (larger) residual instead of silently claiming convergence."""
    prob = make_problem(seed=3)
    with warnings.catch_warnings():
        warnings.simplefilter("error", T.SolverConvergenceWarning)
        with pytest.raises(T.SolverConvergenceWarning):
            T.solve_alternating(prob, max_iters=1, rtol=1e-14)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        sol = T.solve_alternating(prob, max_iters=1, rtol=1e-14)
    assert any(issubclass(w.category, T.SolverConvergenceWarning)
               for w in rec)
    assert sol.residual > 1e-14
