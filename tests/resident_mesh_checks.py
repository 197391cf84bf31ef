"""The resident gather under a user-axis mesh, on four virtual CPU
devices.

Run as a SCRIPT in its own process (tests/test_resident_data.py drives
it): the XLA device-count flag must be set before jax initializes, and
the main pytest process must keep seeing 1 device.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.paper_cnn import PaperCNNConfig  # noqa: E402
from repro.core.quantize import MixedResolutionQuantizer  # noqa: E402
from repro.data import make_image_classification, partition_iid  # noqa: E402
from repro.fl import FLConfig  # noqa: E402
from repro.kernels import WirePath  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.sim import EngineConfig, VectorizedFLEngine  # noqa: E402
from repro.sim import engine as engine_mod  # noqa: E402

K, L, B = 8, 2, 8


def main():
    assert len(jax.devices()) == 4, jax.devices()
    full = make_image_classification(n_samples=240, hw=8, channels=3,
                                     n_classes=4, seed=0)
    train = dataclasses.replace(full, x=full.x[:200], y=full.y[:200])
    test = dataclasses.replace(full, x=full.x[200:], y=full.y[200:])
    cnn = PaperCNNConfig(input_hw=8, channels=3, n_classes=4,
                         conv_filters=4, dense_units=16)
    fl = FLConfig(L=L, T=2, batch_size=B, alpha=0.02, eval_every=2,
                  seed=0)
    shards = partition_iid(train, K, seed=0)
    q = MixedResolutionQuantizer(lambda_=0.2, b=8)
    mesh = make_mesh((4, 1), ("data", "model"))
    rng = np.random.default_rng(0)
    sel = rng.integers(0, len(train), (K, L, B))
    w = rng.random(K)

    params = {}
    for label, ecfg in (
            ("packed", EngineConfig(wire=WirePath(plane="packed"),
                                    mesh=mesh)),
            ("exact", EngineConfig(mesh=mesh)),
            ("plain", EngineConfig(wire=WirePath(plane="packed")))):
        eng = VectorizedFLEngine(train, test, shards, cnn, q, None, None,
                                 fl, engine=ecfg)
        sharded = label != "plain"
        assert (eng._user_sharding is not None) == sharded, label
        xs, ys, w_dev = eng._minibatches(sel, w, user_sharded=True)
        np.testing.assert_array_equal(np.asarray(xs), train.x[sel])
        np.testing.assert_array_equal(np.asarray(ys),
                                      np.asarray(jnp.asarray(train.y[sel])))
        if sharded:
            us = eng._user_sharding
            for a in (xs, ys):
                assert a.sharding.is_equivalent_to(us, a.ndim), \
                    (label, a.sharding)
                # each device holds its own users' rows only
                assert {s.data.shape[0] for s in a.addressable_shards} \
                    == {K // 4}
            # the set is replicated over the mesh, not on one device
            x_res, _ = engine_mod._resident_set(train, eng._repl_sharding)
            assert x_res.sharding.is_fully_replicated
            assert len(x_res.sharding.device_set) == 4
        else:
            assert not xs.committed
        # paths that do not declare the user sharding stay uncommitted
        xs_r, _ = eng._minibatches(sel[None])
        assert not xs_r.committed
        params[label] = eng.run().params
        print(f"{label}: ok")
    # the sharded packed step on the resident gather equals one device
    for a, b in zip(jax.tree_util.tree_leaves(params["packed"]),
                    jax.tree_util.tree_leaves(params["plain"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    print("RESIDENT MESH CHECKS PASSED")


if __name__ == "__main__":
    main()
