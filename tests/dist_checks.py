"""Distributed-runtime correctness checks on an 8-fake-device mesh.

Run as a SCRIPT in its own process (tests/test_dist.py drives it):
the XLA device-count flag must be set before jax initializes, and the
main pytest process must keep seeing 1 device.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import dataclasses  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.dist import (CompressorConfig, TrainHParams,  # noqa: E402
                        aggregate_delta, batch_shardings,
                        build_decode_step, build_prefill_step,
                        build_train_step, decode_cache_shape,
                        decode_shardings, microbatch, param_shardings,
                        param_specs, shard_map, train_input_shardings)
from repro.launch.inputs import input_specs  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import init_model  # noqa: E402
from repro.models.config import InputShape  # noqa: E402


def small_mesh():
    return make_mesh((2, 4), ("data", "model"))


def check_aggregation_exact_mean():
    """compressor=none must equal the fp32 mean across replicas."""
    mesh = small_mesh()
    x = jnp.arange(2 * 256, dtype=jnp.float32).reshape(2, 256)
    spec = P("data", "model")

    def agg(v):
        return shard_map(
            lambda vl: jax.lax.pmean(vl, ("data",)),
            mesh=mesh, in_specs=spec, out_specs=spec,
            check_vma=False)(v)

    out = jax.jit(agg, in_shardings=NamedSharding(mesh, spec))(x)
    np.testing.assert_allclose(np.asarray(out),
                               np.tile(np.asarray(x).mean(0), (2, 1)))
    print("ok: exact mean baseline")


def check_quantized_aggregation():
    """Quantized aggregate ~ true mean; error within the static-budget
    Lemma-1 bound per replica contribution.  Fully manual over both
    mesh axes: every model shard quantizes its local slice (per-shard
    top-k + packed sign plane + all_gather over data) independently —
    the TPU-native layout of the wire format."""
    mesh = small_mesh()
    rng = np.random.default_rng(0)
    G = 2                                     # data axis = replicas
    d = 4096
    # replica-varying deltas: dim0 sharded over data
    deltas = rng.standard_normal((G, d)).astype(np.float32)
    spikes = rng.choice(d, 40, replace=False)
    deltas[:, spikes] *= 30.0
    x = jnp.asarray(deltas)
    spec_full = P("data", "model")            # replica dim x sharded dim
    comp = CompressorConfig(kind="mixed", s_budget=0.02, bits=8,
                            exact_topk=True)

    def run(v):
        def body(vl):
            # vl: [1, d / model] — this model shard's local slice
            leaf = vl[0]
            out, _ = aggregate_delta(
                {"w": leaf}, {"w": P("model")}, ("data",), comp)
            return out["w"][None]
        return shard_map(body, mesh=mesh, in_specs=spec_full,
                         out_specs=spec_full, check_vma=False)(v)

    out = jax.jit(run, in_shardings=NamedSharding(mesh, spec_full))(x)
    out = np.asarray(out)
    true_mean = deltas.mean(0)
    # every replica row holds the same aggregate
    np.testing.assert_allclose(out[0], out[1], rtol=1e-6)
    # error bounded: per-shard inf-norm * crude bound
    err = np.abs(out[0] - true_mean)
    bound = np.abs(deltas).max() * 0.6
    assert err.max() <= bound, (err.max(), bound)
    # correlation with the true mean must be strong
    c = np.corrcoef(out[0], true_mean)[0, 1]
    assert c > 0.55, c
    print(f"ok: quantized aggregation (corr={c:.3f})")


def check_train_step_runs():
    """Reduced arch, real values, 2 rounds on the 2x4 mesh: loss drops
    or at least stays finite; params stay replica-consistent."""
    mesh = small_mesh()
    cfg = dataclasses.replace(get_config("granite-3-8b").reduced(),
                              ssm_chunk=16)
    shape = InputShape("t", seq_len=32, global_batch=4, kind="train")
    hp = TrainHParams(L_local=2, alpha=0.01,
                      compressor=CompressorConfig(
                          s_budget=0.05, bits=8, exact_topk=True),
                      remat=False)
    params = init_model(jax.random.PRNGKey(0), cfg)
    step = build_train_step(cfg, mesh, shape, hp)
    batch = input_specs(cfg, shape, abstract=False, seed=0)
    batch = microbatch(batch, hp.L_local)
    ps, bs = train_input_shardings(cfg, mesh, shape, params, batch)
    jstep = jax.jit(step, in_shardings=(ps, bs))
    p1, m1 = jstep(params, batch)
    p2, m2 = jstep(p1, batch)
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"]) * 1.5
    assert int(m1["wire_bits_per_replica"]) > 0
    leaves = jax.tree_util.tree_leaves(p2)
    assert all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32))))
               for l in leaves)
    print(f"ok: train step (loss {float(m1['loss']):.3f} -> "
          f"{float(m2['loss']):.3f})")


def check_classic_vs_quantized_bits():
    mesh = small_mesh()
    cfg = get_config("granite-3-8b").reduced()
    shape = InputShape("t", seq_len=32, global_batch=4, kind="train")
    params = init_model(jax.random.PRNGKey(0), cfg)
    batch = microbatch(input_specs(cfg, shape, abstract=False), 1)
    outs = {}
    for kind in ("none", "mixed"):
        hp = TrainHParams(compressor=CompressorConfig(
            kind=kind, s_budget=0.01, bits=4, exact_topk=True),
            remat=False)
        step = build_train_step(cfg, mesh, shape, hp)
        ps, bs = train_input_shardings(cfg, mesh, shape, params, batch)
        _, m = jax.jit(step, in_shardings=(ps, bs))(params, batch)
        outs[kind] = int(m["wire_bits_per_replica"])
    assert outs["mixed"] < 0.1 * outs["none"], outs
    print(f"ok: wire bits mixed/classic = "
          f"{outs['mixed'] / outs['none']:.4f}")


def check_moe_train_step():
    mesh = small_mesh()
    cfg = dataclasses.replace(get_config("qwen2-moe-a2.7b").reduced(),
                              ssm_chunk=16)
    shape = InputShape("t", seq_len=32, global_batch=4, kind="train")
    hp = TrainHParams(compressor=CompressorConfig(
        s_budget=0.05, bits=8, exact_topk=True), remat=False)
    params = init_model(jax.random.PRNGKey(1), cfg)
    step = build_train_step(cfg, mesh, shape, hp)
    batch = microbatch(input_specs(cfg, shape, abstract=False), 1)
    ps, bs = train_input_shardings(cfg, mesh, shape, params, batch)
    p1, m1 = jax.jit(step, in_shardings=(ps, bs))(params, batch)
    assert np.isfinite(float(m1["loss"]))
    print(f"ok: MoE train step (loss {float(m1['loss']):.3f})")


def check_prefill_step():
    """Prefill forward on the 2x4 mesh: dense (sequence-parallel
    residual over the model axis) and MoE (expert-parallel all_to_all
    dispatch — serve rules map the expert axis onto 'model')."""
    mesh = small_mesh()
    for arch in ("granite-3-8b", "qwen2-moe-a2.7b"):
        cfg = dataclasses.replace(get_config(arch).reduced(),
                                  ssm_chunk=16)
        shape = InputShape("p", seq_len=64, global_batch=4,
                           kind="prefill")
        params = init_model(jax.random.PRNGKey(0), cfg)
        specs = param_specs(params, cfg, mesh)
        flat_specs = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P))
        assert any("model" in s for s in flat_specs)
        step = build_prefill_step(cfg, mesh, shape)
        batch = input_specs(cfg, shape, abstract=False, seed=0)
        ps = param_shardings(params, cfg, mesh)
        bs = batch_shardings(batch, mesh, shape)
        logits = jax.jit(step, in_shardings=(ps, bs))(params, batch)
        assert logits.shape == (4, 64, cfg.vocab_padded), logits.shape
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
        print(f"ok: prefill step {arch}")


def check_decode_step():
    mesh = small_mesh()
    for arch in ("granite-3-8b", "rwkv6-7b", "zamba2-7b"):
        cfg = dataclasses.replace(get_config(arch).reduced(), ssm_chunk=16)
        shape = InputShape("d", seq_len=64, global_batch=4, kind="decode")
        params = init_model(jax.random.PRNGKey(0), cfg)
        serve = build_decode_step(cfg, mesh, shape)
        cache_shape = decode_cache_shape(cfg, shape)
        cache = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), cache_shape)
        ps, cs, ts, isd = decode_shardings(cfg, mesh, shape, params)
        jserve = jax.jit(serve, in_shardings=(ps, cs, ts, isd),
                         out_shardings=(None, cs))
        tokens = jnp.ones((4, 1), jnp.int32)
        logits, new_cache = jserve(params, cache, tokens,
                                   jnp.asarray(5, jnp.int32))
        assert logits.shape == (4, 1, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
        print(f"ok: decode step {arch}")


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.device_count()
    check_aggregation_exact_mean()
    check_quantized_aggregation()
    check_train_step_runs()
    check_classic_vs_quantized_bits()
    check_moe_train_step()
    check_prefill_step()
    check_decode_step()
    print("ALL DIST CHECKS PASSED")
