"""The one generator of the benchmark's inputs, driven by ``--seed``.

Everything the program under test receives is made here from the seed
and from the cell's configuration and traffic files: the user
partition, the positions of the access points and users and the seed
of the minibatch stream, and, through the configuration's model module
(``models/<model>.py``), the data and the initial parameters, each
from a sub-seed of its own.  The plain reference (``reference.py``)
gets the same inputs, so the two runs share no state that the program
made.

Every seed gives the same amount of work: the same shapes, the same
number of users, every shard at least one minibatch long, and one
fixed drawing of the AP and user positions (the configuration's
``geometry_seed``), so the per-round step and the power solve's
problem have the same size whatever the seed.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any, List, Optional

import numpy as np


@dataclasses.dataclass
class Inputs:
    x: np.ndarray                 # [n_train + n_test, ...] samples
    y: np.ndarray                 # [n_train + n_test, ...] targets
    n_train: int
    shards: List[np.ndarray]      # K sorted index arrays into x[:n_train]
    ap_pos: Optional[np.ndarray]  # [M, 2] metres, None without a channel
    user_pos: Optional[np.ndarray]  # [K, 2]
    params0: Any                  # initial parameter pytree, host copy
    mb_seed: int                  # seed of the minibatch stream


def sub_seeds(seed: int) -> np.ndarray:
    """Independent 31-bit seeds for the data, partition, channel,
    parameter and minibatch streams.  Accepts any non-negative int."""
    ss = np.random.SeedSequence(int(seed))
    return ss.generate_state(5, np.uint32) >> 1


def partition(y: np.ndarray, K: int, kind: str, alpha: float,
              min_per_user: int, seed: int) -> List[np.ndarray]:
    """Disjoint user shards: IID, or Dirichlet(alpha) label skew redrawn
    until every user holds ``min_per_user`` samples."""
    rng = np.random.default_rng(seed)
    if kind == "iid":
        return [np.sort(s) for s in np.array_split(rng.permutation(len(y)),
                                                   K)]
    if kind != "dirichlet":
        raise KeyError(f"unknown partition {kind!r}")
    classes = np.unique(y)
    while True:
        shards = [[] for _ in range(K)]
        for c in classes:
            idx = np.flatnonzero(y == c)
            rng.shuffle(idx)
            cuts = (np.cumsum(rng.dirichlet(np.full(K, alpha)))
                    * len(idx)).astype(int)[:-1]
            for u, part in enumerate(np.split(idx, cuts)):
                shards[u].extend(part.tolist())
        if min(len(s) for s in shards) >= min_per_user:
            return [np.sort(np.asarray(s, np.int64)) for s in shards]


def positions(M: int, K: int, area_m: float, seed: int):
    """Access points on a jittered square grid and users uniform over
    the wrap-around square (Table I's deployment)."""
    rng = np.random.default_rng(seed)
    g = int(np.ceil(np.sqrt(M)))
    gx, gy = np.meshgrid(np.arange(g), np.arange(g))
    pts = np.stack([gx.ravel(), gy.ravel()], -1)[:M] + 0.5
    ap = np.mod(pts * (area_m / g) + rng.uniform(-20, 20, (M, 2)), area_m)
    users = rng.uniform(0, area_m, (K, 2))
    return ap, users


def model(cfg: dict):
    """The configuration's model module, ``models/<model>.py`` with
    ``-`` read as ``_`` (see ``models/__init__.py``)."""
    return importlib.import_module(
        "models." + cfg["model"].replace("-", "_"))


def make_inputs(cfg: dict, seed: int) -> Inputs:
    s_data, s_part, _, s_init, s_mb = (int(v) for v in sub_seeds(seed))
    mod = model(cfg)
    n_train = cfg["n_train"]
    x, y, strata = mod.make_data(cfg, s_data)
    shards = partition(strata[:n_train], cfg["K"], cfg["partition"],
                       cfg.get("dirichlet_alpha", 0.0), cfg["batch_size"],
                       s_part)
    ap = users = None
    if cfg.get("M") is not None:
        # one drawing of the deployment for every run: its geometry sets
        # how hard each round's power solve is
        ap, users = positions(cfg["M"], cfg["K"], cfg["area_m"],
                              cfg["geometry_seed"])
    import jax
    params0 = jax.device_get(mod.init_params(cfg, s_init))
    return Inputs(x=x, y=y, n_train=n_train, shards=shards, ap_pos=ap,
                  user_pos=users, params0=params0, mb_seed=s_mb)


def minibatch_indices(shards: List[np.ndarray], L: int, take: int,
                      mb_seed: int, rounds: int) -> List[np.ndarray]:
    """The [K, L, take] sample indices of each of the first ``rounds``
    rounds: one ``default_rng(mb_seed)`` stream drawn user by user,
    step by step, without replacement inside a minibatch — the draw
    order of the paper's Algorithm 1."""
    rng = np.random.default_rng(mb_seed)
    return [np.stack([np.stack([rng.choice(s, take, replace=False)
                                for _ in range(L)]) for s in shards])
            for _ in range(rounds)]
