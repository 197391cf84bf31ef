"""The device-resident training set: ``VectorizedFLEngine._minibatches``
gathers each round's minibatches on the device from one copy of the
training set, and sends only the indices.

* the gathered arrays equal the host gather ``dataset.x[sel]`` (as
  ``jnp.asarray`` converts it) bit for bit, for the sync, replicated
  and clustered index shapes, and fused rounds on them equal rounds on
  the host gather;
* engines on one ``ImageDataset`` share one device copy
  (``engine.resident_bytes`` counts it once) and the cache does not
  keep the host arrays alive;
* ``engine.h2d_bytes`` counts the indices and per-user vectors only;
* under a user-axis mesh the gathered minibatches carry the step's
  user sharding (four virtual devices, in a subprocess).
"""
import dataclasses
import gc
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.paper_cnn import PaperCNNConfig
from repro.core.quantize import MixedResolutionQuantizer
from repro.data import make_image_classification, partition_iid
from repro.fl import FLConfig
from repro.kernels import WirePath
from repro.sim import EngineConfig, VectorizedFLEngine
from repro.sim import engine as engine_mod

K, L, B = 4, 2, 8


def _problem(n_train=200, seed=0):
    full = make_image_classification(n_samples=n_train + 40, hw=8,
                                     channels=3, n_classes=4, seed=seed)
    train = dataclasses.replace(full, x=full.x[:n_train],
                                y=full.y[:n_train])
    test = dataclasses.replace(full, x=full.x[n_train:],
                               y=full.y[n_train:])
    return train, test


def _engine(train, test, engine=None, T=3):
    cnn = PaperCNNConfig(input_hw=8, channels=3, n_classes=4,
                         conv_filters=4, dense_units=16)
    fl = FLConfig(L=L, T=T, batch_size=B, alpha=0.02, eval_every=T,
                  seed=0)
    return VectorizedFLEngine(
        train, test, partition_iid(train, K, seed=0), cnn,
        MixedResolutionQuantizer(lambda_=0.2, b=8), None, None, fl,
        engine=engine or EngineConfig(fused=True))


def _host_minibatches(self, sel, *vectors, user_sharded=False):
    """The host gather the engine used before the training set moved
    to the device: ``dataset.x[sel]`` copied, then transferred."""
    return (jnp.asarray(self.dataset.x[sel]),
            jnp.asarray(self.dataset.y[sel]),
            *(jnp.asarray(v, jnp.float32) for v in vectors))


def _draw(rng, eng, lead=()):
    return rng.integers(0, len(eng.dataset), lead + (K, L, B))


@pytest.mark.parametrize("shape", ["sync", "replicated", "clustered"])
def test_minibatches_equal_host_gather(shape):
    train, test = _problem()
    eng = _engine(train, test)
    rng = np.random.default_rng(1)
    if shape == "sync":
        sel = _draw(rng, eng)                    # [K, L, b]
    elif shape == "replicated":
        sel = _draw(rng, eng, (3,))              # [R, K, L, b]
    else:
        sel = _draw(rng, eng)[np.array([1, 2])]  # sel[g]
    w = rng.random(sel.shape[-3])
    xs, ys, w_dev = eng._minibatches(sel, w)
    hx, hy, hw = _host_minibatches(eng, sel, w)
    for got, want in ((xs, hx), (ys, hy), (w_dev, hw)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert ys.dtype == jnp.int32 and xs.dtype == jnp.float32
    # uncommitted on the default device, as jnp.asarray leaves it
    assert not xs.committed and not ys.committed


def test_fused_packed_rounds_match_host_gather(monkeypatch):
    """Three fused packed-plane rounds on the resident gather give the
    params and bits of three rounds on the host gather, bit for bit."""
    train, test = _problem()
    ecfg = EngineConfig(wire=WirePath(plane="packed"))
    resident = _engine(train, test, ecfg).run()
    monkeypatch.setattr(VectorizedFLEngine, "_minibatches",
                        _host_minibatches)
    host = _engine(train, test, ecfg).run()
    assert len(resident.logs) == len(host.logs) == 3
    for a, b in zip(resident.logs, host.logs):
        np.testing.assert_array_equal(a.bits_per_user, b.bits_per_user)
        assert a.mean_s == b.mean_s
    for a, b in zip(jax.tree_util.tree_leaves(resident.params),
                    jax.tree_util.tree_leaves(host.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engines_share_one_device_copy():
    train, test = _problem(seed=3)
    with obs.session() as sess:
        a = _engine(train, test)
        b = _engine(train, test,
                    EngineConfig(wire=WirePath(plane="packed")))
        for eng in (a, b):
            state = eng.start_run()
            eng.train_round(state, 1)
    x_dev, y_dev = engine_mod._resident_set(train)
    assert engine_mod._resident_set(train)[0] is x_dev
    # one row per sample, padded to whole 128-lane tiles: 8x8x3 -> 256
    assert x_dev.shape == (len(train), 256) and y_dev.dtype == jnp.int32
    assert sess.counters["engine.resident_bytes"] == \
        x_dev.nbytes + y_dev.nbytes == len(train) * (256 + 1) * 4


def test_resident_copy_goes_with_the_host_array():
    """The cache holds no reference to the host arrays: freeing the
    dataset drops its device copy."""
    train, _ = _problem(seed=4)
    engine_mod._resident_set(train)
    key = (id(train.x), id(train.y), None)
    assert key in engine_mod._RESIDENT
    del train
    gc.collect()
    assert key not in engine_mod._RESIDENT


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "exact"])
def test_h2d_bytes_are_the_indices_and_vectors(fused):
    train, test = _problem()
    eng = _engine(train, test, EngineConfig(fused=fused))
    state = eng.start_run()
    with obs.session() as sess:
        for t in (1, 2):
            eng.train_round(state, t)
    # int32 indices, and the fused step's weights and activity mask
    per_round = 4 * K * L * B + (2 * 4 * K if fused else 0)
    assert sess.counters["engine.h2d_bytes"] == 2 * per_round
    assert per_round < train.x[:K * L * B].nbytes / 100


@pytest.mark.timeout(600)
def test_user_sharded_gather_on_four_devices():
    script = os.path.join(os.path.dirname(__file__),
                          "resident_mesh_checks.py")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    res = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, timeout=580)
    sys.stdout.write(res.stdout)
    sys.stderr.write(res.stderr[-4000:])
    assert res.returncode == 0, "resident mesh checks failed"
    assert "RESIDENT MESH CHECKS PASSED" in res.stdout
