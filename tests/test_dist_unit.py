"""Single-process repro.dist coverage: compressor kinds, microbatching,
sharding specs, the engine's user-axis mesh and the MoE shard_map compat
path — everything here runs on the main process's single device (the
8-fake-device checks live in dist_checks.py / test_dist.py)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.quantize.static_budget import (static_budget_roundtrip,
                                               wire_bits)
from repro.dist import (CompressorConfig, aggregate_delta, budget_k,
                        microbatch, mixed_recon, payload_bits, shard_map)
from repro.launch.mesh import make_mesh


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


def _tree(rng, G=2):
    return {"a": jnp.asarray(rng.standard_normal((G, 300)), jnp.float32),
            "b": jnp.asarray(rng.standard_normal((G, 7, 11)), jnp.float32)}


# ----------------------------------------------------------- compressor
def test_aggregate_none_is_exact_fp32_mean():
    rng = np.random.default_rng(0)
    tree = _tree(rng)
    agg, info = aggregate_delta(tree, None, (), CompressorConfig("none"))
    np.testing.assert_array_equal(np.asarray(agg["a"]),
                                  np.asarray(tree["a"]).mean(0))
    np.testing.assert_array_equal(np.asarray(agg["b"]),
                                  np.asarray(tree["b"]).mean(0))
    d = 300 + 7 * 11
    assert info["wire_bits_per_replica"] == 32 * d
    assert agg["b"].shape == (7, 11)


def test_aggregate_mixed_error_bound_and_bits():
    rng = np.random.default_rng(1)
    G, d = 4, 2048
    x = rng.standard_normal((G, d)).astype(np.float32)
    comp = CompressorConfig("mixed", s_budget=0.05, bits=8,
                            exact_topk=True)
    agg, info = aggregate_delta({"w": jnp.asarray(x)}, None, (), comp)
    out = np.asarray(agg["w"])
    true = x.mean(0)
    # every replica's contribution errs by at most ~dw_q (low-res half
    # + grid step); dw_q <= inf-norm, so the mean errs below inf-norm
    assert np.abs(out - true).max() <= np.abs(x).max()
    assert np.corrcoef(out, true)[0, 1] > 0.5
    k = budget_k(d, comp.s_budget)
    assert info["wire_bits_per_replica"] == wire_bits(d, k, comp.bits)
    assert info["wire_bits_per_replica"] < 0.2 * 32 * d


def test_mixed_recon_matches_static_budget_roundtrip():
    """The threshold-based batched roundtrip equals the index-based
    static_budget encode+decode (no rank-k magnitude ties here)."""
    rng = np.random.default_rng(2)
    G, d = 3, 512
    x = rng.standard_normal((G, d)).astype(np.float32)
    comp = CompressorConfig("mixed", s_budget=0.04, bits=4,
                            exact_topk=True)
    recon, dw_q = mixed_recon(jnp.asarray(x), comp)
    k = budget_k(d, comp.s_budget)
    for g in range(G):
        ref = static_budget_roundtrip(jnp.asarray(x[g]), k, comp.bits)
        np.testing.assert_allclose(np.asarray(recon[g]), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)
        assert float(dw_q[g]) == float(np.sort(np.abs(x[g]))[-k])


def test_aggregate_manual_mode_matches_stacked():
    """Manual (shard_map) aggregation over a size-1 data axis equals
    the stacked G=1 aggregation — same wire arithmetic, different
    collective convention."""
    rng = np.random.default_rng(3)
    d = 640
    x = rng.standard_normal(d).astype(np.float32)
    mesh = _mesh11()
    for comp in (CompressorConfig("none"),
                 CompressorConfig("mixed", s_budget=0.03, bits=8,
                                  exact_topk=True)):
        def body(v, comp=comp):
            out, _ = aggregate_delta({"w": v}, {"w": P()}, ("data",),
                                     comp)
            return out["w"]
        run = shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                        check_vma=False)
        out = np.asarray(jax.jit(run)(jnp.asarray(x)))
        ref, _ = aggregate_delta({"w": jnp.asarray(x[None])}, None, (),
                                 comp)
        np.testing.assert_allclose(out, np.asarray(ref["w"]), rtol=1e-6,
                                   atol=1e-6)


def test_compressor_config_validation():
    with pytest.raises(ValueError):
        CompressorConfig(kind="topk").validate()
    with pytest.raises(ValueError):
        CompressorConfig(kind="mixed", bits=5).validate()
    with pytest.raises(ValueError):
        CompressorConfig(kind="mixed", s_budget=0.0).validate()
    assert payload_bits(100, CompressorConfig("none")) == 3200


# ----------------------------------------------------------- microbatch
def test_microbatch_shapes_and_errors():
    batch = {"tokens": jnp.arange(24).reshape(6, 4)}
    mb = microbatch(batch, 3)
    assert mb["tokens"].shape == (3, 2, 4)
    np.testing.assert_array_equal(np.asarray(mb["tokens"][0]),
                                  np.arange(8).reshape(2, 4))
    with pytest.raises(ValueError):
        microbatch(batch, 4)
    with pytest.raises(ValueError):
        microbatch(batch, 0)


# ------------------------------------------------------------- sharding
def test_param_specs_divisibility_guard():
    from repro.configs import get_config
    from repro.dist import param_shardings, param_specs
    from repro.models import init_model

    cfg = get_config("granite-3-8b").reduced()
    params = jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg))
    mesh = _mesh11()
    specs = param_specs(params, cfg, mesh)
    # model axis of size 1 -> everything replicated
    flat = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda s: isinstance(s, P))
    assert all(all(e is None for e in s) for s in flat)
    ns = param_shardings(params, cfg, mesh)
    assert all(isinstance(s, NamedSharding)
               for s in jax.tree_util.tree_leaves(ns))


# ------------------------------------------------- engine mesh sharding
def test_engine_user_axis_mesh_matches_unsharded():
    from repro.core.quantize import MixedResolutionQuantizer
    from repro.data import make_image_classification, partition_iid
    from repro.fl.loop import FLConfig, run_fl
    from repro.sim import EngineConfig
    from repro.sim.engine import VectorizedFLEngine

    data = make_image_classification(n_samples=240, hw=8, channels=1,
                                     n_classes=4, seed=0)
    train = dataclasses.replace(data, x=data.x[:200], y=data.y[:200])
    test = dataclasses.replace(data, x=data.x[200:], y=data.y[200:])
    shards = partition_iid(train, 4, seed=0)
    from repro.configs.paper_cnn import PaperCNNConfig
    cnn = PaperCNNConfig(input_hw=8, channels=1, n_classes=4,
                         conv_filters=4, dense_units=16)
    fl = FLConfig(L=2, T=2, batch_size=16, eval_every=2, seed=0)
    q = MixedResolutionQuantizer(lambda_=0.2, b=8)

    results = {}
    for label, ecfg in (
            ("plain", EngineConfig(fused=True)),
            ("mesh", EngineConfig(fused=True, mesh=_mesh11()))):
        eng = VectorizedFLEngine(train, test, shards, cnn, q, None,
                                 None, fl, engine=ecfg)
        results[label] = eng.run()
    a = jax.tree_util.tree_leaves(results["plain"].params)
    b = jax.tree_util.tree_leaves(results["mesh"].params)
    for la, lb in zip(a, b):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6, atol=1e-6)
    # run_fl forwards the engine config
    res = run_fl(train, test, shards, cnn, q, None, None, fl,
                 engine=EngineConfig(fused=True, mesh=_mesh11()))
    assert res.rounds_completed == 2


def test_engine_mesh_without_data_axis_warns_and_disables():
    from repro.core.quantize import MixedResolutionQuantizer
    from repro.data import make_image_classification, partition_iid
    from repro.fl.loop import FLConfig
    from repro.sim import EngineConfig
    from repro.sim.engine import VectorizedFLEngine
    from repro.configs.paper_cnn import PaperCNNConfig

    data = make_image_classification(n_samples=80, hw=8, channels=1,
                                     n_classes=2, seed=1)
    shards = partition_iid(data, 2, seed=0)
    cnn = PaperCNNConfig(input_hw=8, channels=1, n_classes=2,
                         conv_filters=4, dense_units=8)
    fl = FLConfig(L=1, T=1, batch_size=8, seed=0)
    mesh = make_mesh((1, 1), ("pod", "model"))  # no "data" axis
    with pytest.warns(UserWarning, match="no 'data' axis"):
        eng = VectorizedFLEngine(data, data, shards, cnn,
                                 MixedResolutionQuantizer(0.2, 8), None,
                                 None, fl,
                                 engine=EngineConfig(fused=True,
                                                     mesh=mesh))
    assert eng._user_sharding is None


# ----------------------------------------------------- MoE compat paths
def _moe_cfg():
    from repro.configs import get_config
    return get_config("qwen2-moe-a2.7b").reduced()


def test_moe_shard_map_paths_run_on_one_device_mesh():
    """The expert-parallel shard_map paths (replicated + a2a) must run
    on this jax version through the compat wrapper."""
    from repro.models.moe import init_moe, moe_apply
    from repro.models.sharding_ctx import logical_axis_rules

    cfg = _moe_cfg()
    params = init_moe(jax.random.PRNGKey(0), cfg)
    mesh = _mesh11()
    rng = np.random.default_rng(0)

    # replicated path: no batch rule, single-token sequence
    x1 = jnp.asarray(rng.standard_normal((2, 1, cfg.d_model)),
                     jnp.float32)
    with logical_axis_rules(mesh, {"expert": "model"}):
        y1, aux1 = jax.jit(lambda p, v: moe_apply(p, v, cfg))(params, x1)
    assert y1.shape == x1.shape and np.isfinite(np.asarray(y1)).all()

    # a2a path: batch rule set, multi-token sequence
    x2 = jnp.asarray(rng.standard_normal((2, 8, cfg.d_model)),
                     jnp.float32)
    with logical_axis_rules(mesh, {"expert": "model", "batch": "data"}):
        y2, aux2 = jax.jit(lambda p, v: moe_apply(p, v, cfg))(params, x2)
    assert y2.shape == x2.shape and np.isfinite(np.asarray(y2)).all()
    assert float(aux1) > 0 and float(aux2) > 0
