"""Plain reference of one FL round of the paper's scheme.

Written from the paper (arXiv 2412.10878, Algorithm 1, eqs. 2-8 and
12-14) and its Table I network, and importing nothing of the program:

* L local AdaGrad steps per user from the round's global model (eq. 2),
  on the loss of the configuration's model module (``models/<model>.py``,
  given to ``Reference``: the model's part of the reference), over any
  parameter pytree;
* adaptive mixed-resolution quantization of each user's delta (eqs.
  6-8) with its payload bits ``d (b s + 1 - s) + 32``;
* the rho-weighted sum of the reconstructions added to the model (eq. 3),
  folded ``FOLD_BLOCK`` users at a time in user order, as the model
  module says (all K in one sum where it says None), so that a model
  whose ``[K, d]`` stack of deltas does not fit needs no edit here;
* the cell-free MIMO coefficients of eq. 5 from the AP and user
  positions, and the min-max-latency power control: bisection over the
  rate-per-bit target with an LP feasibility test (eq. 14), giving each
  user's uplink latency ``bits / rate`` (eq. 12).

Everything is float32.  ``precision="highest"`` computes every matrix
product (the model module routes each through ``mm``, the CNN's
convolution as a product over the image's patches) at
``Precision.HIGHEST``: the reference.  ``precision="high"`` is the
control, the nearest precision below the configuration's float32 at
``highest``: each product in three bf16 passes with float32
accumulation, forward and backward.

``fault`` plants one of the faults that the comparison must catch, in
the reference put in the program's place: ``"frozen"`` (the model is
never updated), ``"half_users"`` (the second half of the users is left
out and the weights renormalized over the rest), ``"no_exchange"``
(only the first ``1/shards`` of the users is folded, with their own
weights, as a device that never received the others' planes would) and
``"full_power"`` (the power solve is skipped and every user sends at
full power).
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


# -------------------------------------------------------- precision
def matmul(precision: str):
    """A float32 matrix product, forward and backward, at ``precision``:
    ``"highest"`` (``Precision.HIGHEST``) or ``"high"`` (three bf16
    passes).  On a TPU ``"high"`` is the MXU's own ``Precision.HIGH``;
    elsewhere, where a float32 product is exact at any precision, it is
    spelled out as ``a_hi b_hi + a_hi b_lo + a_lo b_hi`` with float32
    accumulation.  (On a TPU that spelling is not three passes: XLA may
    drop the float32 -> bf16 -> float32 round trip that makes the
    halves.)"""
    import jax
    import jax.numpy as jnp

    P = jax.lax.Precision
    if precision == "highest" or (precision == "high"
                                  and jax.default_backend() == "tpu"):
        prec = P.HIGHEST if precision == "highest" else P.HIGH

        def mm(a, b):
            return jnp.dot(a, b, precision=prec)
    elif precision == "high":
        def split(a):
            hi = a.astype(jnp.bfloat16)
            return hi, (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)

        def mm(a, b):
            (ah, al), (bh, bl) = split(a), split(b)

            def dot(x, y):
                return jnp.dot(x, y, preferred_element_type=jnp.float32)

            return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))
    else:
        raise ValueError(f"unknown precision {precision!r}")

    @jax.custom_vjp
    def f(a, b):
        return mm(a, b)

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return mm(g, b.T), mm(a.T, g)

    f.defvjp(fwd, bwd)
    return f


def _local_fn(loss, alpha: float, precision: str):
    """jit(params, xs [B, L, b, ...], ys [B, L, ...]) -> deltas [B, d]:
    each user's L AdaGrad steps from the round's model (eq. 2), the
    delta flattened leaf by leaf in the pytree's order."""
    import jax
    import jax.numpy as jnp

    mm = matmul(precision)
    tmap = jax.tree_util.tree_map

    def one_user(p, xs, ys):
        g0 = tmap(jnp.zeros_like, p)

        def step(carry, batch):
            w, acc = carry
            grads = jax.grad(loss)(w, batch[0], batch[1], mm)
            acc = tmap(lambda a, g: a + g * g, acc, grads)
            w = tmap(lambda v, a, g: v - alpha / jnp.sqrt(a + 1e-8) * g,
                     w, acc, grads)
            return (w, acc), None

        (w, _), _ = jax.lax.scan(step, (p, g0), (xs, ys))
        leaves = jax.tree_util.tree_leaves
        return jnp.concatenate([jnp.ravel(a - b)
                                for a, b in zip(leaves(w), leaves(p))])

    return jax.jit(jax.vmap(one_user, in_axes=(None, 0, 0)))


# --------------------------------------------------------- quantizer
def quantize(delta, lam: float, b: int):
    """eqs. 6-8 on one user's flat delta: (recon, bits)."""
    import jax.numpy as jnp

    a = jnp.abs(delta)
    inf = jnp.max(a)
    hi = a / jnp.where(inf > 0, inf, 1) >= lam
    dbar = jnp.sum(hi)
    dwq = jnp.min(jnp.where(hi, a, jnp.inf))
    dwq = jnp.where(jnp.isfinite(dwq), dwq, 0).astype(delta.dtype)
    step = (inf - dwq) / (2 ** b - 1)
    code = jnp.round((a - dwq) / jnp.where(step > 0, step, 1))
    mag = jnp.where(hi, dwq + code * step, dwq / 2)
    recon = jnp.where(delta > 0, mag, -mag)
    recon = jnp.where(inf > 0, recon, 0).astype(delta.dtype)
    d = delta.size
    s = dbar / d
    bits = jnp.where(inf > 0, d * (b * s + 1.0 - s) + 32.0, d + 32.0)
    return recon, bits


# ----------------------------------------------------------- channel
def channel_coefficients(net: dict, ap: np.ndarray, users: np.ndarray):
    """eq. 5's (A, B, Btilde, I) for MR combining, pilots assigned
    greedily past the first tau_p users (float64)."""
    side = net["area_m"]
    diff = np.abs(ap[:, None, :] - users[None, :, :])
    diff = np.minimum(diff, side - diff)
    dist = np.maximum(np.sqrt((diff ** 2).sum(-1)), 1.0)        # [M, K]
    beta = 10 ** ((net["ref_pathloss_db"]
                   - 10 * net["pathloss_exp"] * np.log10(dist)) / 10)
    M, K = beta.shape
    tau_p = net["tau_p"]
    pilot = np.zeros(K, np.int64)
    for j in range(K):
        if j < tau_p:
            pilot[j] = j
            continue
        m = int(np.argmax(beta[:, j]))
        pilot[j] = int(np.argmin([beta[m, :j][pilot[:j] == t].sum()
                                  for t in range(tau_p)]))
    co = (pilot[:, None] == pilot[None, :]).astype(np.float64)
    sigma2 = 10 ** (net["noise_dbm"] / 10) / 1000.0
    pp = tau_p * net["p_max_w"]
    gamma = pp * beta ** 2 / (pp * (beta @ co) + sigma2)
    N = float(net["N"])
    A = (N * gamma.sum(0)) ** 2
    B = N * (gamma * beta).sum(0)
    I = N * sigma2 * gamma.sum(0) / net["p_max_w"]
    Bt = N * gamma.T @ beta
    ratio = N * (gamma / beta).T @ beta
    Bt = Bt + co * ratio ** 2
    np.fill_diagonal(Bt, 0.0)
    return A, B, Bt, I


def _rates(coef, pre_log, p):
    A, B, Bt, I = coef
    sinr = A * p / (B * p + Bt @ p + I)
    return pre_log * np.log2(1.0 + sinr)


def solve_power(net: dict, coef, bits: np.ndarray, eps_rel: float,
                max_iters: int) -> np.ndarray:
    """Bisection over eta with an LP feasibility test at each midpoint
    (min total power, HiGHS); the powers of the last feasible eta."""
    from scipy.optimize import linprog

    A, B, Bt, I = coef
    K = len(A)
    pre = net["bandwidth_hz"] * (1.0 - net["tau_p"] / net["tau_c"])
    lo, hi = 0.0, float(np.min(pre * np.log2(1 + A / (B + I)) / bits))
    eps = eps_rel * hi
    best = np.ones(K)
    for _ in range(max_iters):
        if hi - lo <= eps:
            break
        mid = 0.5 * (lo + hi)
        expo = mid * bits / pre
        if np.max(expo) > 500.0:
            hi = mid
            continue
        th = 2.0 ** expo - 1.0
        rows = th[:, None] * Bt
        rows[np.arange(K), np.arange(K)] = -(A - th * B)
        rows = rows / (th * I)[:, None]
        res = linprog(np.ones(K), A_ub=rows, b_ub=-np.ones(K),
                      bounds=[(0.0, 1.0)] * K, method="highs")
        if res.status == 0:
            lo, best = mid, res.x
        else:
            hi = mid
    return np.clip(best, 0.0, 1.0)


def latencies(net: dict, coef, bits: np.ndarray, p: np.ndarray):
    pre = net["bandwidth_hz"] * (1.0 - net["tau_p"] / net["tau_c"])
    return bits / np.maximum(_rates(coef, pre, p), 1e-9)


# ------------------------------------------------------------ rounds
class Reference:
    """The reference rounds of one configuration's model (its model
    module, ``model``) at one precision; its compiled functions are
    kept for every seed it is run on."""

    def __init__(self, cfg: dict, model, precision: str = "highest"):
        import jax
        import jax.numpy as jnp

        self.cfg = cfg
        self.fold_block = model.FOLD_BLOCK
        self.local = _local_fn(model.loss, cfg["lr"], precision)
        lam, b = cfg["lambda_"], cfg["b"]
        self.quantize = jax.jit(jax.vmap(lambda d: quantize(d, lam, b)))
        # the weighted sum in user order, elementwise in float32
        self.aggregate = jax.jit(lambda w, r: jnp.sum(w[:, None] * r,
                                                      axis=0))

    def fold(self, p, inputs, sel: np.ndarray, rho: np.ndarray):
        """One round's users from the model ``p``: (the rho-weighted sum
        of their reconstructions, each user's payload bits), the users
        trained, quantized and summed the model module's ``FOLD_BLOCK``
        at a time, the blocks' sums added in user order."""
        import jax.numpy as jnp

        K = len(sel)
        block = self.fold_block or K
        agg, bits = None, []
        for i in range(0, K, block):
            j = min(i + block, K)
            delta = self.local(p, jnp.asarray(inputs.x[sel[i:j]]),
                               jnp.asarray(inputs.y[sel[i:j]]))
            recon, b = self.quantize(delta)
            part = self.aggregate(jnp.asarray(rho[i:j], jnp.float32), recon)
            agg = part if agg is None else agg + part
            bits.append(np.asarray(b, np.float64))
        return agg, np.concatenate(bits)

    def run(self, inputs, sels: List[np.ndarray],
            starts: Optional[List] = None,
            fault: Optional[str] = None, shards_over: int = 1
            ) -> Dict[str, list]:
        """The first ``len(sels)`` rounds from ``inputs.params0``: per
        round the host copy of the model after it, each user's payload
        bits and, with a channel, each user's uplink latency.  With
        ``starts``, round ``t`` starts from the model ``starts[t]``
        instead of the one its own round ``t - 1`` made."""
        import jax
        import jax.numpy as jnp

        tree = jax.tree_util
        cfg = self.cfg
        sizes = np.array([len(s) for s in inputs.shards], np.float64)
        rho = sizes / sizes.sum()
        K = len(rho)
        if fault == "half_users":
            rho = np.where(np.arange(K) < K - K // 2, rho, 0.0)
            rho = rho / rho.sum()
        elif fault == "no_exchange":
            rho = np.where(np.arange(K) < K // shards_over, rho, 0.0)
        treedef = tree.tree_structure(inputs.params0)
        shapes = [np.shape(v) for v in tree.tree_leaves(inputs.params0)]
        cuts = np.cumsum([int(np.prod(s)) for s in shapes])[:-1]
        coef = None
        if cfg.get("M") is not None:
            coef = channel_coefficients(cfg, inputs.ap_pos,
                                        inputs.user_pos)

        def f32(params):
            return tree.tree_map(lambda v: jnp.asarray(v, jnp.float32),
                                 params)

        p = f32(inputs.params0)
        out = {"params": [], "bits": [], "latency": []}
        for t, sel in enumerate(sels):
            if starts is not None:
                p = f32(starts[t])
            agg, bits = self.fold(p, inputs, sel, rho)
            if fault != "frozen":
                parts = jnp.split(agg, cuts)
                p = tree.tree_unflatten(treedef, [
                    v + part.reshape(shp) for v, part, shp
                    in zip(tree.tree_leaves(p), parts, shapes)])
            bits = np.maximum(bits, 1.0)
            out["params"].append(tree.tree_map(
                lambda v: np.asarray(v, np.float32), jax.device_get(p)))
            out["bits"].append(bits)
            if coef is not None:
                pw = (np.ones(K) if fault == "full_power" else
                      solve_power(cfg, coef, bits, cfg["eps_rel"],
                                  cfg["max_iters"]))
                out["latency"].append(latencies(cfg, coef, bits, pw))
        return out
