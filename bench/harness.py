"""One run of one benchmark cell: set-up, the measured window, the
traced window and the comparison with the plain reference.

The window drives the engine's public round API — ``start_run``, then
``train_round`` -> ``solve_uplink_host`` -> ``finish_round`` round after
round — on an engine built as ``repro.sim.sweep.run_cell`` builds it,
from inputs that ``gen.py`` draws from the seed.  Set-up drives the same
engine object through its first ``CHECKED_ROUNDS`` rounds (which also
compiles the round step) and keeps a host copy of what each produced;
the window then goes on from there.  Once the window has closed and the
program's state is freed, ``reference.py`` follows the same rounds,
each from the program's model before it, and ``check.py`` compares.

Nothing here knows the model: the configuration's ``model`` names a
module, ``models/<model>.py`` (``gen.model``), that supplies the data,
the initial parameters, the engine's datasets and ``ModelSpec``, and
the reference's loss.  The traced run (``--trace 1``) also opens a
``repro.obs`` session around its rounds and hands the program's phase
spans and counter totals to the per-layer readers.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHECKED_ROUNDS = 3
# traffic keys the harness reads itself; every other key is a Scenario
# field (or "engine": EngineConfig fields)
HARNESS_KEYS = ("mesh", "traced_rounds", "note", "engine")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------ files
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, spec: Optional[dict] = None):
    """(cell entry, configuration, traffic, limits, spec) by name."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    confs = {c["name"]: c for c in spec["configs"]}
    cfg = load_json(ROOT / confs[cell["config"]]["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{workload}.json")
    return cell, cfg, traffic, limits, spec


def per_layer_metrics(spec: dict, workload: str) -> List[dict]:
    return [m for m in spec["per_layer"]
            if workload in m.get("workloads", [workload])]


def end_to_end_metrics(spec: dict, workload: str) -> List[dict]:
    return [m for m in spec["end_to_end"]
            if workload in m.get("workloads", [workload])]


# ----------------------------------------------------------- device
def require_devices(chips: int):
    """The first ``chips`` TPU devices; exits non-zero, printing no
    result, when JAX finds no TPU or too few."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform "
                         f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} TPU chips, "
                         f"found {len(devs)}")
    return devs[:chips]


def configure_jax(cfg: dict) -> str:
    """JAX's persistent compilation cache at a fixed directory inside
    the checkout, whatever the environment says, so that only a cell's
    first run in a checkout compiles; and the configuration's precision
    of float32 matrix products (JAX's default on a TPU is one bf16
    pass).  Returns the cache directory."""
    import jax

    jax.config.update("jax_default_matmul_precision",
                      cfg["matmul_precision"])
    path = ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(path)


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


# ----------------------------------------------------------- engine
def build_engine(cfg: dict, traffic: dict, inputs):
    """The engine ``run_cell`` builds for this configuration, on the
    seed's inputs: the model module's datasets and ModelSpec, the
    scenario's EngineConfig (with the traffic's plane and mesh),
    FLConfig, quantizer and power controller."""
    import gen
    from repro.core.channel import CFmMIMOConfig, make_channel
    from repro.core.power import make_power_controller
    from repro.core.quantize import make_quantizer
    from repro.fl.loop import FLConfig
    from repro.sim.engine import VectorizedFLEngine
    from repro.sim.scenarios import Scenario

    train, test, model = gen.model(cfg).engine_inputs(cfg, inputs)
    chan = None
    if cfg.get("M") is not None:
        net = CFmMIMOConfig(
            M=cfg["M"], N=cfg["N"], K=cfg["K"],
            bandwidth_hz=cfg["bandwidth_hz"], area_m=cfg["area_m"],
            pathloss_exp=cfg["pathloss_exp"], tau_c=cfg["tau_c"],
            tau_p=cfg["tau_p"], p_max_w=cfg["p_max_w"],
            noise_dbm=cfg["noise_dbm"],
            ref_pathloss_db=cfg["ref_pathloss_db"])
        chan = make_channel(net, ap_positions=inputs.ap_pos,
                            user_positions=inputs.user_pos)
    # the traffic's other keys are Scenario fields (the plane, churn,
    # cohorts, async rounds) and, under "engine", EngineConfig fields
    extra = {k: v for k, v in traffic.items() if k not in HARNESS_KEYS}
    unknown = set(extra) - {f.name for f in dataclasses.fields(Scenario)}
    if unknown:
        raise KeyError(f"traffic keys {sorted(unknown)} are neither "
                       "harness keys nor Scenario fields")
    scn = Scenario(name=cfg["name"], description=cfg["deployment"],
                   K=cfg["K"], L=cfg["L"], T=cfg["T"],
                   batch_size=cfg["batch_size"], lr=cfg["lr"],
                   eval_every=cfg["eval_every"],
                   latency_budget_s=cfg["latency_budget_s"],
                   M=cfg.get("M"), seed=inputs.mb_seed, **extra)
    fl = FLConfig(L=scn.L, T=scn.T, batch_size=scn.batch_size,
                  alpha=scn.lr, eval_every=scn.effective_eval_every,
                  latency_budget_s=scn.latency_budget_s, seed=scn.seed)
    ecfg = dataclasses.replace(scn.engine_config(),
                               **traffic.get("engine", {}))
    if traffic.get("mesh"):
        from repro.launch.mesh import make_mesh

        ecfg = dataclasses.replace(
            ecfg, mesh=make_mesh(tuple(traffic["mesh"]), ("data", "model")))
    quant = make_quantizer(cfg["quantizer"], lambda_=cfg["lambda_"],
                           b=cfg["b"])
    power = None
    if chan is not None:
        power = make_power_controller(cfg["power"], eps_rel=cfg["eps_rel"],
                                      max_iters=cfg["max_iters"])
    return VectorizedFLEngine(train, test, inputs.shards, model, quant,
                              power, chan, fl, engine=ecfg)


def one_round(eng, state, t: int, spans: Optional[list] = None):
    """One round through the public API, each layer under a span of
    the harness's own: appended to ``spans`` as ``(name, start_ns,
    end_ns)`` on the host's wall clock, which the profiler's trace
    shares."""
    t0 = time.time_ns()
    work = eng.train_round(state, t)
    t1 = time.time_ns()
    sol = eng.solve_uplink_host(state.chan, work.bits_np, work.active)
    t2 = time.time_ns()
    eng.finish_round(state, work, sol.straggler_s,
                     per_user_s=sol.latencies)
    if spans is not None:
        spans.extend((("round.train", t0, t1), ("round.solve", t1, t2),
                      ("round.finish", t2, time.time_ns())))
    return work, sol


def checked_rounds(eng):
    """Rounds 1 to ``CHECKED_ROUNDS`` of a new run on ``eng``, through
    the window's own calls: the run's state and, per round, a host copy
    of the model after it, each user's payload bits and uplink
    latency."""
    import jax

    state = eng.start_run()
    got = {"params": [], "bits": [], "latency": []}
    for t in range(1, CHECKED_ROUNDS + 1):
        _, sol = one_round(eng, state, t)
        got["params"].append(jax.device_get(state.params))
        got["bits"].append(np.asarray(state.logs[-1].bits_per_user))
        got["latency"].append(np.asarray(sol.latencies, np.float64))
    return state, got


# -------------------------------------------------------------- run
@dataclasses.dataclass
class Options:
    workload: str
    seed: int
    seconds: float
    trace: bool
    require_chip: bool = True
    # test hook: called with the built engine, before the first round,
    # to plant a fault in the timed path
    patch: Optional[Callable] = None
    spec: Optional[dict] = None


def run(opts: Options, t_start: float) -> dict:
    cell, cfg, traffic, limits, spec = load_cell(opts.workload, opts.spec)
    if opts.require_chip:
        devs = require_devices(cell["chips"])
    else:
        import jax

        devs = jax.devices()[:cell["chips"]]
    import jax

    sys.path.insert(0, str(ROOT / "src"))
    cache = configure_jax(cfg)
    log(f"bench: {opts.workload} seed={opts.seed} on {len(devs)} x "
        f"{devs[0].device_kind}, compile cache {cache}")

    import gen

    inputs = gen.make_inputs(cfg, opts.seed)
    log(f"bench: inputs at {time.perf_counter() - t_start:.3f} s")
    eng = build_engine(cfg, traffic, inputs)
    log(f"bench: engine at {time.perf_counter() - t_start:.3f} s")
    if opts.patch is not None:
        opts.patch(eng)
    state, got = checked_rounds(eng)
    log(f"bench: {CHECKED_ROUNDS} checked rounds at "
        f"{time.perf_counter() - t_start:.3f} s")
    # the evaluating rounds' eager batches (512 and the tail)
    eng.model_spec.accuracy(state.params, state.test_x, state.test_y)
    jax.block_until_ready(state.params)
    setup_s = time.perf_counter() - t_start
    log(f"bench: set-up {setup_s:.3f} s (d={eng.d}, K={eng.K})")

    t = CHECKED_ROUNDS
    result: Dict = {}
    if opts.trace:
        from repro import obs

        n_traced = traffic["traced_rounds"]
        spans: list = []
        # the program's spans and counters; no in-jit taps, so the step
        # already compiled runs as it is
        with obs.session(jit_stream=False) as sess, \
                tempfile.TemporaryDirectory() as tdir:
            # device activity only: the host tracer records every chunk
            # of the host's relayout of the minibatches and triples the
            # round's time; the harness keeps its own spans instead
            popts = jax.profiler.ProfileOptions()
            popts.python_tracer_level = 0
            popts.host_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=popts)
            w0 = time.perf_counter()
            for _ in range(n_traced):
                t += 1
                one_round(eng, state, t, spans)
            jax.block_until_ready(state.params)
            w1 = time.perf_counter()
            jax.profiler.stop_trace()
            import xplane

            red = xplane.reduce(xplane.find(tdir), len(devs), spans)
        phases = [e for e in sess.events if e["kind"] == "phase"]
        attempted = n_traced
        result["trace"] = red
        log(f"bench: traced {n_traced} rounds in {w1 - w0:.3f} s; "
            f"{len(phases)} program spans, "
            f"{sum(e['name'] == 'jax.compile' for e in phases)} of them "
            "jax.compile")
    else:
        times: List[float] = []
        w0 = time.perf_counter()
        deadline = w0 + opts.seconds
        while True:
            r0 = time.perf_counter()
            t += 1
            one_round(eng, state, t)
            now = time.perf_counter()
            if now >= deadline:
                jax.block_until_ready(state.params)
                now = time.perf_counter()
                times.append(now - r0)
                break
            times.append(now - r0)
        window_s = now - w0
        attempted = len(times)
        result["round_s"] = window_s / attempted
        result["round_p95_s"] = float(np.percentile(times, 95))
        result["window_s"] = window_s
    result["setup_s"] = setup_s
    device = device_info(devs)
    if opts.trace:
        device["busy_s"] = result["trace"]["busy_s"]
        device["window_s"] = result["trace"]["window_s"]
    d, K = eng.d, eng.K
    # free the program's state before the reference runs
    del eng, state
    gc.collect()

    import check
    import reference

    sels = gen.minibatch_indices(inputs.shards, cfg["L"],
                                 cfg["batch_size"], inputs.mb_seed,
                                 CHECKED_ROUNDS)
    r0 = time.perf_counter()
    numbers = check.against_reference(
        reference.Reference(cfg, gen.model(cfg)), inputs, sels, got,
        cfg["b"])
    correct, checks = check.verdict(numbers, limits)
    log(f"bench: reference {time.perf_counter() - r0:.3f} s")

    if opts.trace:
        ctx = {"cfg": cfg, "traffic": traffic, "cell": cell, "d": d,
               "K": K, "chips": len(devs), "kind": devs[0].device_kind,
               "rounds": attempted, "trace": result["trace"],
               "spans": phases, "counters": sess.counters}
        metrics = {}
        for m in per_layer_metrics(spec, opts.workload):
            mod = importlib.import_module(f"metrics.{m['name']}")
            v = mod.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out = {"correct": correct, "attempted": attempted, "failed": 0,
               "metrics": metrics, "device": device,
               "breakdown": result["trace"]["breakdown"]}
    else:
        metrics = {m["name"]: {"value": result[m["name"]],
                               "unit": m["unit"]}
                   for m in end_to_end_metrics(spec, opts.workload)}
        out = {"correct": correct, "attempted": attempted, "failed": 0,
               "metrics": metrics, "device": device}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return out
