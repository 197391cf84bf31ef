"""The bisection's feasibility oracle against the HiGHS LP it replaced.

``bisection_lp._feasible_powers`` answers each feasibility test of
Algorithm 1 with the least fixed point ``p* = (I - M)^{-1} c`` of
constraint (14c) (DESIGN.md §7).  ``_lp_feasible_powers`` below is the
min-sum-power LP that the controller used to solve with
``scipy.optimize.linprog`` (HiGHS), kept here as the independent oracle.
"""
import dataclasses

import numpy as np
import pytest
from scipy.optimize import linprog

from repro import obs
from repro.core.channel import CFmMIMOConfig, make_channel
from repro.core.power import BisectionLPPowerControl, bisection_lp
from repro.core.power.bisection_lp import _feasible_powers, eta_upper_bound
from repro.sim.engine import _subchannel

SEEDS = (0, 1, 2)


def _lp_feasible_powers(chan, theta):
    """LP feasibility of (14c) for fixed theta, min sum(p); p or None."""
    K = theta.shape[0]
    # rows normalized by th_j * I_M_j (RHS = -1): the raw coefficients
    # are O(1e-12), far below HiGHS's absolute feasibility tolerance
    A_ub = theta[:, None] * chan.B_tilde.copy()
    A_ub[np.arange(K), np.arange(K)] = -(chan.A_bar - theta * chan.B_bar)
    scale = theta * chan.I_M
    if np.any(scale <= 0) or not np.all(np.isfinite(A_ub)):
        return None
    res = linprog(c=np.ones(K), A_ub=A_ub / scale[:, None],
                  b_ub=-np.ones(K), bounds=[(0.0, 1.0)] * K,
                  method="highs")
    return res.x if res.status == 0 else None


def _payload(K, seed):
    """Per-user payload bits around the Table III point (d * b ~ 1.8e6)."""
    rng = np.random.default_rng(1000 + seed)
    return rng.uniform(2e5, 4e6, K)


def _theta0(chan, bits):
    """theta at the bisection's upper bound eta (every user at full
    power, no interference): infeasible once K > 1."""
    expo = eta_upper_bound(chan, bits) * bits / chan.cfg.pre_log
    return np.power(2.0, expo) - 1.0


def _lp_boundary(chan, theta0):
    """The scale s* of theta0 at which the LP turns infeasible, to 1e-10
    relative (feasibility is monotone in s: raising any theta_j only
    tightens (14c))."""
    lo, hi = 0.0, 1.0
    assert _lp_feasible_powers(chan, hi * theta0) is None
    while hi - lo > 1e-10 * hi:
        mid = 0.5 * (lo + hi)
        if _lp_feasible_powers(chan, mid * theta0) is None:
            hi = mid
        else:
            lo = mid
    return 0.5 * (lo + hi)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("K", [5, 20, 40])
def test_oracle_decisions_and_powers_match_the_lp(K, seed):
    chan = make_channel(CFmMIMOConfig(K=K), seed=seed)
    theta0 = _theta0(chan, _payload(K, seed))
    s_star = _lp_boundary(chan, theta0)
    near = [s_star * (1.0 + sign * d) for d in (1e-5, 1e-3, 1e-1, 0.5)
            for sign in (-1.0, 1.0)]
    scales = np.concatenate([np.geomspace(1e-3, 10.0, 15) * s_star, near])
    decisions = []
    for s in scales:
        if abs(s / s_star - 1.0) <= 1e-6:      # HiGHS's tolerance band
            continue
        theta = s * theta0
        p_lp = _lp_feasible_powers(chan, theta)
        p = _feasible_powers(chan, theta)
        assert (p is None) == (p_lp is None), (s / s_star, p_lp is None)
        decisions.append(p is not None)
        if p is not None:
            np.testing.assert_allclose(p, p_lp, rtol=1e-7, atol=0.0)
            assert np.all(p > 0) and np.all(p <= 1.0)
    # the scan crosses the boundary, and each side holds several points
    assert 3 <= sum(decisions) <= len(decisions) - 3


def _counted(monkeypatch, oracle):
    calls = []

    def wrapped(chan, theta):
        calls.append(1)
        return oracle(chan, theta)

    monkeypatch.setattr(bisection_lp, "_feasible_powers", wrapped)
    return calls


def _solve_with(monkeypatch, oracle, chan, bits):
    """The controller's bisection with ``oracle`` as its feasibility
    test: the solution, the oracle's calls, and ``phy.lp_solves``."""
    calls = _counted(monkeypatch, oracle)
    with obs.session() as sess:
        sol = BisectionLPPowerControl().solve(chan, bits)
    return sol, len(calls), sess.counters["phy.lp_solves"]


@pytest.mark.parametrize("churn", [False, True], ids=["full", "subchannel"])
@pytest.mark.parametrize("seed", SEEDS)
def test_bisection_matches_a_bisection_on_the_lp(monkeypatch, seed, churn):
    K = 40
    chan = make_channel(CFmMIMOConfig(K=K), seed=seed)
    bits = _payload(K, seed)
    if churn:
        # as the engine's host path solves a round with absent users
        active = np.random.default_rng(seed).random(K) < 0.7
        idx = np.flatnonzero(active)
        chan, bits = _subchannel(chan, idx), bits[idx]
    sol, n, count = _solve_with(monkeypatch, _feasible_powers, chan, bits)
    ref, n_ref, count_ref = _solve_with(monkeypatch, _lp_feasible_powers,
                                        chan, bits)
    assert sol.info["bisection_iters"] == ref.info["bisection_iters"]
    assert sol.info["eta"] == ref.info["eta"] > 0.0
    assert n == n_ref == count == count_ref == sol.info["bisection_iters"]
    np.testing.assert_allclose(sol.latencies, ref.latencies,
                               rtol=1e-9, atol=0.0)
    np.testing.assert_allclose(sol.p, ref.p, rtol=1e-7, atol=0.0)


def _two_users(A, B, Bt, I_M):
    """A two-user realization with the given eq. (5) bundle."""
    chan = make_channel(CFmMIMOConfig(K=2), seed=0)
    return dataclasses.replace(
        chan, A_bar=np.asarray(A, np.float64),
        B_bar=np.asarray(B, np.float64),
        B_tilde=np.array([[0.0, Bt], [Bt, 0.0]]),
        I_M=np.asarray(I_M, np.float64))


@pytest.mark.parametrize("shift", [0.0, 1e-3], ids=["at", "past"])
def test_unattainable_target_is_infeasible(shift):
    """A_bar_j - theta_j B_bar_j <= 0: no power reaches user j's target."""
    chan = make_channel(CFmMIMOConfig(K=20), seed=3)
    theta = np.full(20, 1e-3)
    theta[7] = chan.A_bar[7] / chan.B_bar[7] * (1.0 + shift)
    assert chan.A_bar[7] - theta[7] * chan.B_bar[7] <= 0.0
    assert _feasible_powers(chan, theta) is None
    assert _lp_feasible_powers(chan, theta) is None
    # the same theta with user 7's target lowered is feasible
    theta[7] = 1e-3
    assert _feasible_powers(chan, theta) is not None


@pytest.mark.parametrize("coupling", [1.0, 1.0 - 1e-12, 1.0 + 1e-12],
                         ids=["singular", "rho-below-1", "rho-above-1"])
def test_singular_or_near_singular_system_is_infeasible(coupling):
    """theta = 1, A_bar = 1, B_bar = 0: M = [[0, c], [c, 0]], rho(M) = c.
    At c = 1, I - M is singular; just below, p* is ~1e9 (outside the
    box); just above, p* turns negative."""
    chan = _two_users([1.0, 1.0], [0.0, 0.0], coupling, [1e-3, 1e-3])
    theta = np.ones(2)
    assert _feasible_powers(chan, theta) is None
    assert _lp_feasible_powers(chan, theta) is None
    # well inside rho(M) < 1 the same system is feasible for both
    weak = _two_users([1.0, 1.0], [0.0, 0.0], 0.5, [1e-3, 1e-3])
    np.testing.assert_allclose(_feasible_powers(weak, theta), [2e-3, 2e-3],
                               rtol=1e-12)
    assert _lp_feasible_powers(weak, theta) is not None


def test_overflow_guard_skips_the_oracle(monkeypatch):
    """A midpoint whose theta would pass 2^500 counts as infeasible
    without a feasibility test.  With no interference and SINR 1e305 at
    full power every theta below 2^500 is feasible, so the guard alone
    bounds eta: the bisection closes on exponent 500 from below."""
    chan = _two_users([1.0, 1.0], [0.0, 0.0], 0.0, [1e-305, 1e-305])
    bits = np.array([1.0, 2.0])
    hi = eta_upper_bound(chan, bits)
    eta_guard = 500.0 * chan.cfg.pre_log / bits.max()
    assert 0.5 * hi > eta_guard                  # the first midpoint
    thetas = []

    def recorded(c, theta):
        thetas.append(theta)
        return _feasible_powers(c, theta)

    calls = _counted(monkeypatch, recorded)
    with obs.session() as sess:
        sol = BisectionLPPowerControl().solve(chan, bits)
    iters = sol.info["bisection_iters"]
    assert len(calls) == sess.counters["phy.lp_solves"] < iters
    assert all(np.max(t) < 2.0 ** 500 for t in thetas)
    eta = sol.info["eta"]
    assert eta_guard - 1e-4 * hi <= eta <= eta_guard
    assert np.all(np.isfinite(sol.latencies))
