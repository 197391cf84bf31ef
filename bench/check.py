"""The comparison that decides ``correct``.

``compare`` reduces what the timed path produced in its checked rounds
(``got``) and what the plain reference produced from the same inputs
(``want``) to a few numbers; ``verdict`` holds those that
``limits/<workload>.json`` names against their limits.

The reference is teacher-forced: its round ``t`` starts from the
program's own model after round ``t - 1`` (``starts``), with the same
minibatches, so that each round is compared one round deep.  (Run free,
the quantizer's threshold would turn round-off in round 1 into
different high-resolution sets in rounds 2 and 3, and the gap would
swing from seed to seed.)  A round that restarts from ``w_0``, or that
does not carry the model to the next, reads as a wrong update there.

Each number is the worst over the checked rounds and over leaves or
users:

* ``update_gap`` — the round's model update ``w_t - w_{t-1}``: per
  leaf, the gap between the program's norm and the reference's, over
  the larger of the reference leaf's norm and the median leaf's;
* ``update_diff`` — the round's update, the norm of the difference per
  leaf over the same denominator;
* ``bits_gap`` — each user's payload bits, relative to the reference's;
* ``flips`` — how many high-resolution decisions of a round differ from
  the reference's, summed over the users: each moves a user's payload by
  ``b - 1`` bits.  A count, so one element that lands on the threshold
  by round-off reads 1 and not a share of the update;
* ``flips_rest`` — the same sum without the user who reads the most.
  One user's local training can take a ReLU or max-pool decision the
  other way on round-off, which moves that user's whole delta by a
  fraction of a percent and its threshold with it: hundreds of flips in
  that one user, where a wrong step or a lower precision moves every
  user;
* ``latency_gap`` — each user's uplink latency from the round's power
  solve, relative to the reference's (cells with a channel only).

In each round, leaves whose reference update is under a thousandth of
the median leaf's are left out of the model numbers: they move by
round-off alone.  A model is any parameter pytree; its leaves are
matched by key path (``['conv_w']``, ``['out']['w']``).
"""
import math
from typing import Dict, List

import numpy as np


def starts(got: dict, params0: dict) -> List[dict]:
    """The model each checked round starts from: ``w_0``, then the
    program's own model after the round before."""
    return [params0] + list(got["params"][:-1])


def leaves(params) -> Dict[str, np.ndarray]:
    """A parameter pytree's leaves by key path."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return {jax.tree_util.keystr(path): v for path, v in flat}


def _f64(a) -> np.ndarray:
    return np.asarray(a, np.float64)


def _worst(a: float, b: float) -> float:
    """The larger of two readings; NaN if either is (``max`` would
    drop it, and a NaN has to read as not correct)."""
    return float(np.maximum(a, b))


def _update_numbers(got_w, want_w, base):
    """(worst gap of norms, worst norm of difference) over the moving
    leaves of the updates ``got_w - base`` and ``want_w - base``."""
    g = {k: _f64(got_w[k]) - _f64(base[k]) for k in base}
    w = {k: _f64(want_w[k]) - _f64(base[k]) for k in base}
    wn = {k: float(np.linalg.norm(v)) for k, v in w.items()}
    med = float(np.median(list(wn.values())))
    gap = diff = 0.0
    for k in base:
        if wn[k] < 1e-3 * med:
            continue
        den = max(wn[k], med)
        gap = _worst(gap, abs(float(np.linalg.norm(g[k])) - wn[k]) / den)
        diff = _worst(diff, float(np.linalg.norm(g[k] - w[k])) / den)
    return gap, diff


def _rel(got, want) -> float:
    g, w = _f64(got), _f64(want)
    return float(np.max(np.abs(g - w) / np.abs(w)))


def compare(got: dict, want: dict, params0: dict, b: int
            ) -> Dict[str, float]:
    gp, wp = got["params"], want["params"]
    if len(gp) != len(wp) or not gp:
        return {"update_diff": math.nan}
    out = {"update_gap": 0.0, "update_diff": 0.0, "bits_gap": 0.0,
           "flips": 0.0, "flips_rest": 0.0}
    if want["latency"]:
        out["latency_gap"] = 0.0
    for t, base in enumerate(starts(got, params0)):
        gap, diff = _update_numbers(leaves(gp[t]), leaves(wp[t]),
                                    leaves(base))
        gb, wb = got["bits"][t], want["bits"][t]
        per_user = np.round(np.abs(_f64(gb) - _f64(wb)) / (b - 1))
        now = {"update_gap": gap, "update_diff": diff,
               "bits_gap": _rel(gb, wb), "flips": float(np.sum(per_user)),
               "flips_rest": float(np.sum(per_user) - np.max(per_user))}
        if want["latency"]:
            now["latency_gap"] = _rel(got["latency"][t],
                                      want["latency"][t])
        out = {k: _worst(v, now[k]) for k, v in out.items()}
    return out


def against_reference(ref, inputs, sels, got: dict, b: int
                      ) -> Dict[str, float]:
    """``compare`` of ``got`` with the reference rounds of ``ref``
    (a ``reference.Reference``), teacher-forced along ``got``."""
    want = ref.run(inputs, sels, starts=starts(got, inputs.params0))
    return compare(got, want, inputs.params0, b)


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}) over the numbers that
    ``limits`` names; a number missing or not finite is not correct."""
    checks, ok = {}, True
    for name, limit in limits.items():
        if name.startswith("_"):
            continue
        v = numbers.get(name, math.nan)
        checks[name] = {"value": v, "limit": limit}
        ok = ok and math.isfinite(v) and v <= limit
    return ok, checks
