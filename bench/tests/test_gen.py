"""The generator: the same seed gives the same inputs, and every seed
gives the same amount of work."""
import json

import numpy as np
import pytest

import gen
from conftest import BENCH, TINY
from models import paper_cnn


def cfg(name, **over):
    c = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    c.update(TINY, **over)
    return c


@pytest.mark.parametrize("name", ["cnn-cifar10-table3",
                                  "cnn-fmnist-table2"])
def test_same_seed_same_inputs(name):
    a, b = (gen.make_inputs(cfg(name), 2**31 + 11) for _ in range(2))
    assert np.array_equal(a.x, b.x) and np.array_equal(a.y, b.y)
    assert all(np.array_equal(s, t) for s, t in zip(a.shards, b.shards))
    assert all(np.array_equal(a.params0[k], b.params0[k])
               for k in a.params0)
    assert a.mb_seed == b.mb_seed
    if a.ap_pos is not None:
        assert np.array_equal(a.ap_pos, b.ap_pos)
    c = gen.make_inputs(cfg(name), 2**31 + 12)
    assert not np.array_equal(a.x, c.x)


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 3, 2**33 + 5])
def test_every_seed_same_work(seed):
    c = cfg("cnn-cifar10-table3", K=40, n_train=8000, n_test=16)
    inp = gen.make_inputs(c, seed)
    assert len(inp.shards) == 40
    assert min(len(s) for s in inp.shards) >= c["batch_size"]
    allidx = np.concatenate(inp.shards)
    assert len(np.unique(allidx)) == len(allidx) == c["n_train"]
    assert inp.x.shape == (8016, 32, 32, 3) and inp.x.dtype == np.float32
    assert 0.0 <= inp.x.min() and inp.x.max() <= 1.0
    shapes = {k: v.shape for k, v in inp.params0.items()}
    assert shapes == paper_cnn.cnn_shapes(c)


def test_minibatches_follow_algorithm_1_draw_order():
    shards = [np.arange(0, 50), np.arange(50, 120)]
    sels = gen.minibatch_indices(shards, L=2, take=8, mb_seed=5, rounds=2)
    rng = np.random.default_rng(5)
    for sel in sels:
        assert sel.shape == (2, 2, 8)
        for u, s in enumerate(shards):
            for step in range(2):
                assert np.array_equal(sel[u, step],
                                      rng.choice(s, 8, replace=False))
