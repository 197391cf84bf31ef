"""The paper's power control: bisection over eta + feasibility (§III).

Problem (14): maximize eta s.t.  0 <= p <= 1  and for every user j

    (A_bar_j - theta_j B_bar_j) p_j - theta_j sum_{j'!=j} Btilde_j^{j'} p_j'
        >= theta_j I_M^j,          theta_j = 2^(eta b_j / B_tau) - 1.

For fixed eta the constraints are linear in p, so feasibility is an LP;
bisection over eta converges to the global optimum within eps_B
(Algorithm 1, lines 13-23).  We recover the power vector of the last
feasible eta.  The LP has a closed answer (DESIGN.md §7): with every
target attainable (A_bar_j - theta_j B_bar_j > 0), constraint (14c)
reads p >= M p + c with M >= 0, c > 0, a set that is non-empty iff
rho(M) < 1; its componentwise-least element p* = (I - M)^{-1} c is the
min-sum-power point the LP would return.  So each feasibility test is
one K x K float64 linear solve, feasible iff p* is finite, positive and
inside the box: the oracle of the batched JAX path (repro.phy.solvers)
too.  The HiGHS LP stays as the independent check, in
tests/test_power_oracle.py and bench/reference.py.
"""
from __future__ import annotations

import numpy as np

from repro import obs

from ..channel.cfmmimo import ChannelRealization
from .base import PowerController, PowerSolution


def _feasible_powers(chan: ChannelRealization, theta: np.ndarray):
    """Feasibility of (14c) for fixed theta: the least fixed point of
    p = M p + c if it lies in the box, else None."""
    denom = chan.A_bar - theta * chan.B_bar
    if np.any(denom <= 0):          # target unattainable at any power
        return None
    row = theta / denom
    M = row[:, None] * chan.B_tilde
    np.fill_diagonal(M, 0.0)        # j' != j: the diagonal is unused
    c = row * chan.I_M
    try:
        p = np.linalg.solve(np.eye(theta.shape[0]) - M, c)
    except np.linalg.LinAlgError:
        return None
    ok = np.all(np.isfinite(p)) and np.all(p > 0) and np.all(p <= 1.0)
    return p if ok else None


def eta_upper_bound(chan: ChannelRealization, bits: np.ndarray) -> float:
    """Upper bound on min_j rate-per-bit: every user at full power with
    zero interference — the min over users bounds the achievable min."""
    sinr_max = chan.A_bar / (chan.B_bar + chan.I_M)
    rates = chan.cfg.pre_log * np.log2(1.0 + sinr_max)
    return float(np.min(rates / np.asarray(bits, np.float64)))


class BisectionLPPowerControl(PowerController):
    """Algorithm 1's min-max-latency power control (our scheme)."""

    name = "bisection-lp"

    def __init__(self, eps_rel: float = 1e-4, max_iters: int = 60):
        self.eps_rel = float(eps_rel)
        self.max_iters = int(max_iters)

    def solve(self, chan: ChannelRealization, bits: np.ndarray
              ) -> PowerSolution:
        """Bisect eta; counts its feasibility tests, one linear solve
        per midpoint past the 2^500 guard, in ``phy.lp_solves``."""
        bits = np.asarray(bits, np.float64)
        B_tau = chan.cfg.pre_log
        lo, hi = 0.0, eta_upper_bound(chan, bits)
        eps = self.eps_rel * hi
        best_p, best_eta, iters, lps = np.ones(chan.cfg.K), 0.0, 0, 0
        while hi - lo > eps and iters < self.max_iters:
            iters += 1
            mid = 0.5 * (lo + hi)
            expo = mid * bits / B_tau
            if np.max(expo) > 500.0:      # 2^500: numerically infeasible
                hi = mid
                continue
            theta = np.power(2.0, expo) - 1.0
            lps += 1
            p = _feasible_powers(chan, theta)
            if p is not None:
                lo, best_p, best_eta = mid, p, mid
            else:
                hi = mid
        obs.counter("phy.lp_solves", lps)
        return self._finish(chan, bits, best_p, eta=best_eta,
                            bisection_iters=iters)
