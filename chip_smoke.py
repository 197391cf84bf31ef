#!/usr/bin/env python3
"""Chip smoke: the paper's federated round at full width on a TPU.

    python chip_smoke.py              # one chip: kernel + main-path phases
    python chip_smoke.py --chips 4    # four chips: the sharded paths only

One chip (no option):

* kernel — 40 users' deltas at the CIFAR-10 paper CNN's width
  (d = 462 410, viewed as [40, 3840, 128]) are encoded by the Pallas
  kernels and by the jnp reference lowering on the same chip: header
  lanes 0-3 and the packed planes must be bit-exact.  The fused
  dequant-reduce, with and without a carried accumulator, must match
  the reference to f32 roundoff.
* main path — the ``paper-table3`` cell (K=40, M=16 APs x N=4
  antennas, Dirichlet shards, cifar10-syn 32x32x3, batch 32, L=5) with
  T=3 and the packed wire plane, mixed-resolution (lambda=0.2, b=10)
  and bisection-LP power control, through ``run_cell`` (kernel
  lowering) and through an engine on the reference lowering: payload
  bits identical, params to f32 roundoff, metrics finite.  The same
  cell runs through ``run_grid(..., phy_batched=True)`` (power solved on
  the device; max_p <= 1).  The compiled fused step must hold a Pallas
  kernel (``tpu_custom_call``).

Four chips (``--chips 4``):

* the same cell with its 40 users sharded 10 per chip over a (4, 1)
  ("data", "model") mesh, against the unsharded run on device 0;
* ``repro.dist.aggregate_flat_manual`` on the packed plane with the
  gather and the ring reduce over a 4-way data axis, d = 462 410 per
  shard, against the jnp reference of the same compressor and the fp32
  mean (``kind="none"``).

Everything runs in this one process.  Timings printed on the way are
smoke timings, not benchmark numbers.  The last line of stdout is one
JSON object, ``{"ok": true, "device": {...}}``; without a TPU, or when
any phase fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SCENARIO = "paper-table3"
QUANT = ("mixed-resolution", {"lambda_": 0.2, "b": 10})
POWER = "bisection-lp"
LAM, B = QUANT[1]["lambda_"], QUANT[1]["b"]
D_CIFAR10 = 462_410            # params of the CIFAR-10 paper CNN
USERS = 40
EPS = float(np.finfo(np.float32).eps)


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU chips, "
                 f"found {len(devs)}")
    return devs


def timed(fn, *args):
    """(result, seconds) of one call, waited for on the device."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def check_roundoff(what: str, got, want, scale: float, terms: int):
    """|got - want| within ``terms`` f32 roundings of magnitude
    ``scale`` (4 ulps each)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    diff = float(np.max(np.abs(got - want))) if got.size else 0.0
    tol = 4.0 * terms * EPS * scale
    log(f"  {what}: max |diff| = {diff!r} (f32 roundoff bound {tol!r})")
    if not diff <= tol:
        raise AssertionError(f"{what}: max |diff| {diff!r} > {tol!r}")


def check_bits_equal(what: str, got, want):
    got, want = np.asarray(got), np.asarray(want)
    as_bits = np.dtype(f"u{got.dtype.itemsize}")
    bad = int(np.sum(got.view(as_bits) != want.view(as_bits)))
    log(f"  {what}: {bad} of {got.size} words differ")
    if bad:
        raise AssertionError(f"{what}: {bad} words differ")


def has_kernel(compiled_text: str, what: str) -> None:
    if "tpu_custom_call" not in compiled_text:
        raise AssertionError(f"{what}: no tpu_custom_call in the compiled "
                             "program; the Pallas kernels did not run")
    log(f"  {what}: compiled program holds tpu_custom_call")


# ------------------------------------------------------------ kernel
def kernel_phase(users: int = USERS, d: int = D_CIFAR10, seed: int = 0):
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.kernels.mixed_res import H_INF

    log(f"[kernel] U={users} d={d} lambda={LAM} b={B}")
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    spikes = jax.random.uniform(k[1], (users, d)) < 1 / 64
    x = jax.random.normal(k[0], (users, d), jnp.float32) \
        * jnp.where(spikes, 50.0, 1.0)
    w = jax.random.dirichlet(k[2], jnp.ones(users)).astype(jnp.float32)
    acc = 1e-2 * jax.random.normal(k[3], (d,), jnp.float32)

    def encode(kern):
        return jax.jit(lambda x: ops.mixed_res_encode(
            x, LAM, B, use_kernel=kern, interpret=False))

    enc_k, enc_r = encode(True), encode(False)
    wire_k, first = timed(enc_k, x)
    _, steady = timed(enc_k, x)
    log(f"  smoke timing: encode first call {first!r} s, "
        f"steady {steady!r} s")
    has_kernel(enc_k.lower(x).compile().as_text(), "encode")
    wire_r = enc_r(x)
    check_bits_equal("header lanes 0-3", wire_k.head[:, :4],
                     wire_r.head[:, :4])
    for plane in ("signs", "hi", "codes"):
        check_bits_equal(f"{plane} plane", getattr(wire_k, plane),
                         getattr(wire_r, plane))

    scale = float(jnp.sum(w * wire_k.head[:, H_INF]))
    for with_acc in (False, True):
        def reduce(kern):
            return jax.jit(lambda wire, w, a: ops.mixed_res_wire_reduce(
                wire, w, B, d, acc=a if with_acc else None,
                use_kernel=kern, interpret=False))

        red_k = reduce(True)
        out_k, first = timed(red_k, wire_k, w, acc)
        _, steady = timed(red_k, wire_k, w, acc)
        tag = "dequant-reduce" + (" +acc" if with_acc else "")
        log(f"  smoke timing: {tag} first call {first!r} s, "
            f"steady {steady!r} s")
        has_kernel(red_k.lower(wire_k, w, acc).compile().as_text(), tag)
        out_r = reduce(False)(wire_k, w, acc)
        extra = float(jnp.max(jnp.abs(acc))) if with_acc else 0.0
        check_roundoff(tag, out_k, out_r, scale + extra, users + 1)


# --------------------------------------------------------- main path
def paper_cell():
    from repro.sim import get_scenario

    return dataclasses.replace(get_scenario(SCENARIO), T=3,
                               aggregation="wire")


def build_engine(scn, wire=None):
    """The engine ``repro.sim.sweep`` builds for ``scn``, with the wire
    path optionally replaced (the Scenario has no lowering field)."""
    from repro.core.power import make_power_controller
    from repro.core.quantize import make_quantizer
    from repro.fl.loop import FLConfig
    from repro.sim.engine import VectorizedFLEngine
    from repro.sim.scenarios import build_problem

    train, test, shards, model, chan = build_problem(scn)
    fl = FLConfig(L=scn.L, T=scn.T, batch_size=scn.batch_size,
                  alpha=scn.lr, eval_every=scn.effective_eval_every,
                  latency_budget_s=scn.latency_budget_s, seed=scn.seed)
    ecfg = scn.engine_config()
    if wire is not None:
        ecfg = dataclasses.replace(ecfg, wire=wire)
    return VectorizedFLEngine(train, test, shards, model,
                              make_quantizer(QUANT[0], **QUANT[1]),
                              make_power_controller(POWER), chan, fl,
                              engine=ecfg)


def fused_step_text(eng) -> str:
    """Compiled text of the engine's fused round step at its round
    shapes."""
    import jax
    import jax.numpy as jnp

    sds = lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.asarray(a).dtype)
    x0 = jnp.asarray(eng.dataset.x[:1])
    y0 = jnp.asarray(eng.dataset.y[:1])
    lead = (eng.K, eng.fl.L, eng.take)
    args = (jax.tree_util.tree_map(sds, eng.params),
            jax.tree_util.tree_map(sds, eng.qstate),
            jax.ShapeDtypeStruct(lead + x0.shape[1:], x0.dtype),
            jax.ShapeDtypeStruct(lead + y0.shape[1:], y0.dtype),
            jax.ShapeDtypeStruct((eng.K,), jnp.float32),
            jax.ShapeDtypeStruct((eng.K,), jnp.float32))
    return eng._fused_step.lower(*args).compile().as_text()


def flat_params(params) -> np.ndarray:
    from repro.core.quantize.base import flatten_pytree

    return np.asarray(flatten_pytree(params)[0])


def compare_runs(what: str, got_logs, got_params, want_logs, want_params,
                 users: int):
    if len(got_logs) != len(want_logs):
        raise AssertionError(f"{what}: {len(got_logs)} rounds vs "
                             f"{len(want_logs)}")
    for a, b in zip(got_logs, want_logs):
        check_bits_equal(f"{what} round {a.round} payload bits",
                         np.asarray(a.bits_per_user, np.float64),
                         np.asarray(b.bits_per_user, np.float64))
    pk, pr = flat_params(got_params), flat_params(want_params)
    check_roundoff(f"{what} final params", pk, pr,
                   float(np.max(np.abs(pr))), users * len(got_logs))


def check_finite(what: str, summary: dict, keys):
    for key in keys:
        v = summary[key]
        log(f"  {what} {key} = {v!r}")
        if not np.isfinite(v):
            raise AssertionError(f"{what}: {key} = {v!r} is not finite")


def main_path_phase():
    from repro.kernels import WirePath
    from repro.sim import run_cell, run_grid

    scn = paper_cell()
    log(f"[main path] {scn.name}: K={scn.K} M={scn.M} N={scn.N} "
        f"{scn.dataset} n_train={scn.n_train} batch={scn.batch_size} "
        f"L={scn.L} T={scn.T} plane=packed")
    t0 = time.perf_counter()
    res = run_cell(scn, QUANT, POWER, quick=False)
    log(f"  smoke timing: run_cell (kernel lowering, compile included) "
        f"{time.perf_counter() - t0!r} s")
    check_finite("run_cell", res.summary,
                 ("final_acc", "mean_uplink_s", "total_latency_s",
                  "mean_straggler_gap_s"))

    eng = build_engine(scn)
    log(f"  d={eng.d}")
    if eng.d != D_CIFAR10:
        raise AssertionError(f"paper CNN has d={eng.d}, not {D_CIFAR10}")
    has_kernel(fused_step_text(eng), "fused round step")

    ref = build_engine(scn, WirePath(plane="packed", lowering="reference"))
    t0 = time.perf_counter()
    ref_res = ref.run()
    log(f"  smoke timing: reference-lowering run (compile included) "
        f"{time.perf_counter() - t0!r} s")
    compare_runs("kernel vs reference", res.result.logs,
                 res.result.params, ref_res.logs, ref_res.params, scn.K)

    t0 = time.perf_counter()
    grid = run_grid([scn], {"mixed": QUANT}, {"ours": POWER}, quick=False,
                    phy_batched=True)
    log(f"  smoke timing: run_grid phy_batched (compile included) "
        f"{time.perf_counter() - t0!r} s")
    summ = grid[0].summary
    check_finite("run_grid phy_batched", summ,
                 ("final_acc", "mean_uplink_s", "total_latency_s",
                  "max_p"))
    if not summ["max_p"] <= 1.0:
        raise AssertionError(f"max_p = {summ['max_p']!r} > 1")


# --------------------------------------------------------- four chips
def mesh_phase():
    from repro.launch.mesh import make_mesh
    from repro.sim import run_cell

    scn = paper_cell()
    mesh = make_mesh((4, 1), ("data", "model"))
    log(f"[mesh] {scn.name} T={scn.T}: {scn.K} users over {mesh.shape}")
    t0 = time.perf_counter()
    sharded = run_cell(scn, QUANT, POWER, quick=False, mesh=mesh)
    log(f"  smoke timing: sharded run_cell {time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    single = run_cell(scn, QUANT, POWER, quick=False)
    log(f"  smoke timing: device-0 run_cell {time.perf_counter() - t0!r} s")
    check_finite("sharded", sharded.summary,
                 ("final_acc", "mean_uplink_s", "total_latency_s"))
    compare_runs("sharded vs device 0", sharded.result.logs,
                 sharded.result.params, single.result.logs,
                 single.result.params, scn.K)


def dist_phase(d: int = D_CIFAR10, seed: int = 0):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.dist import (CompressorConfig, aggregate_flat_manual,
                            mixed_recon, shard_map)
    from repro.kernels import WirePath
    from repro.launch.mesh import make_mesh

    G = 4
    mesh = make_mesh((G,), ("data",))
    log(f"[dist] aggregate_flat_manual over {mesh.shape}, d={d} per shard")
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    spikes = jax.random.uniform(k[1], (G, d)) < 1 / 64
    x = jax.random.normal(k[0], (G, d), jnp.float32) \
        * jnp.where(spikes, 50.0, 1.0)

    def aggregate(comp):
        f = shard_map(
            lambda xs: aggregate_flat_manual(xs[0], comp, ("data",),
                                             {"data": G})[None],
            mesh, in_specs=P("data"), out_specs=P("data"))
        return jax.jit(f)

    fp32 = np.asarray(aggregate(CompressorConfig("none"))(x), np.float64)
    mean = np.mean(np.asarray(x, np.float64), axis=0)
    xmax = float(jnp.max(jnp.abs(x)))
    check_roundoff("kind=none vs fp32 mean", fp32, mean[None], xmax, G)
    for reduce in ("gather", "ring"):
        comp = CompressorConfig("mixed", exact_topk=True,
                                wire=WirePath(plane="packed",
                                              reduce=reduce))
        fn = aggregate(comp)
        out, first = timed(fn, x)
        _, steady = timed(fn, x)
        log(f"  smoke timing: {reduce} first call {first!r} s, "
            f"steady {steady!r} s")
        has_kernel(fn.lower(x).compile().as_text(), reduce)
        recon, _ = mixed_recon(x, comp)
        want = np.mean(np.asarray(recon, np.float64), axis=0)
        out = np.asarray(out, np.float64)
        check_roundoff(f"{reduce} vs compressor reference", out,
                       want[None], xmax, G + 1)
        # distance to the fp32 mean is the compressor's own error
        err = np.max(np.abs(out - fp32), axis=1)
        ref_err = float(np.max(np.abs(want - mean)))
        log(f"  {reduce}: max |agg - fp32 mean| per shard {err.tolist()!r}"
            f", compressor reference {ref_err!r}")
        check_roundoff(f"{reduce} error vs reference error", err,
                       np.full(G, ref_err), xmax, G + 1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded paths on four chips")
    args = ap.parse_args()
    devs = require_tpu(args.chips)
    from repro.compile_cache import enable_compile_cache

    log(f"chip_smoke: {len(devs)} x {devs[0].device_kind}, compile cache "
        f"{enable_compile_cache()}")
    phases = ((mesh_phase, dist_phase) if args.chips == 4
              else (kernel_phase, main_path_phase))
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        log(f"{phase.__name__}: PASS (smoke timing "
            f"{time.perf_counter() - t0!r} s)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
