"""repro.obs — jit-safe telemetry (DESIGN.md §10).

The contract the ISSUE pins:

* JSONL event schema: every event is one flat JSON object with the
  ``ts``/``kind``/``name`` envelope plus context tags, and the file
  round-trips through ``repro.obs.report``;
* recompile detector: a probed step function's wrapper body runs once
  per jit cache entry — forcing a retrace is counted, and crossing the
  session's storm threshold flags (and warns about) a retrace storm;
* sim-engine smoke: a tiny batched-driver grid under a session emits
  per-round ``engine.round``/``phy.solve``/``engine.jit_round`` events
  whose values match the returned round logs;
* zero-overhead when disabled: without an active session, ``jit_tap``
  stages NOTHING (no callback in the jaxpr — the compiled program is
  bit-identical to uninstrumented code) and a full grid run returns
  bit-identical round outputs whether or not a session was active.
"""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs.report import (counter_totals, load_events,
                              per_round_table, phase_breakdown,
                              render_report, retrace_summary,
                              wire_summary)
from repro.sim import get_scenario, run_grid_batched

pytestmark = pytest.mark.skipif(
    bool(jax.config.jax_enable_x64),
    reason="engine trains in float32; x64 leg covers solver parity")

QUANTIZERS = {"mixed": ("mixed-resolution", {"lambda_": 0.2, "b": 4})}
POWERS = {"ours": "bisection-lp"}


def _tiny(name, **overrides):
    fields = dict(K=4, T=4, n_train=240, n_test=60, batch_size=8, L=1,
                  name=f"{name}-tiny")
    fields.update(overrides)
    return dataclasses.replace(get_scenario(name), **fields)


# ------------------------------------------------------- event schema
def test_jsonl_event_schema_golden(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    with obs.session(jsonl=path) as sess:
        obs.record("unit.event", x=1, y=2.5, label="a")
        obs.counter("unit.count", 3)
        obs.counter("unit.count")
        with obs.context(scenario="s1", round=7):
            obs.record("unit.tagged", z=np.float32(0.5))
        with obs.scope("unit.phase"):
            pass
    mem = sess.events            # memory sink survives session close

    lines = [json.loads(ln) for ln in open(path) if ln.strip()]
    assert lines == mem                    # both sinks see every event
    by_name = {e["name"]: e for e in lines}

    # envelope: ts/kind/name on every event, session start/end framing
    for e in lines:
        assert isinstance(e["ts"], float)
        assert e["kind"] in ("event", "phase", "jit", "counter",
                             "retrace", "session")
        assert isinstance(e["name"], str)
    assert lines[0] == by_name["start"] and lines[0]["kind"] == "session"
    assert lines[-1] == by_name["end"] and lines[-1]["kind"] == "session"

    ev = by_name["unit.event"]
    assert (ev["kind"], ev["x"], ev["y"], ev["label"]) \
        == ("event", 1, 2.5, "a")
    # context tags ride on every event inside the block
    assert by_name["unit.tagged"]["scenario"] == "s1"
    assert by_name["unit.tagged"]["round"] == 7
    assert by_name["unit.tagged"]["z"] == 0.5
    # counters flush once per name at close, accumulated
    assert by_name["unit.count"]["kind"] == "counter"
    assert by_name["unit.count"]["total"] == 4.0
    assert by_name["unit.phase"]["kind"] == "phase"
    assert by_name["unit.phase"]["dur_s"] >= 0.0
    assert load_events(path) == lines      # report loader round-trips


def test_phase_spans_on_the_wall_clock_with_parents():
    before = time.time_ns()
    with obs.session() as sess:
        with obs.scope("outer"):
            with obs.scope("inner"):
                with obs.scope("innermost"):
                    pass
            with obs.scope("sibling"):
                pass
    after = time.time_ns()
    ph = {e["name"]: e for e in sess.events if e["kind"] == "phase"}
    assert [e["name"] for e in sess.events if e["kind"] == "phase"] \
        == ["innermost", "inner", "sibling", "outer"]    # at exit
    assert ph["outer"]["parent"] is None
    assert ph["inner"]["parent"] == ph["sibling"]["parent"] == "outer"
    assert ph["innermost"]["parent"] == "inner"
    for e in ph.values():
        assert before <= e["t0_ns"] <= e["t1_ns"] <= after
        assert e["dur_s"] <= (e["t1_ns"] - e["t0_ns"]) * 1e-9 + 1e-6
    for child, parent in (("inner", "outer"), ("innermost", "inner"),
                          ("sibling", "outer")):
        assert ph[parent]["t0_ns"] <= ph[child]["t0_ns"]
        assert ph[child]["t1_ns"] <= ph[parent]["t1_ns"]
    assert ph["inner"]["t1_ns"] <= ph["sibling"]["t0_ns"]
    assert sess.stack == []


def test_compile_is_a_span_only_inside_a_session():
    def compiles(fn_name, sess):
        return [e for e in sess.events if e["kind"] == "phase"
                and e["name"] == "jax.compile"
                and fn_name in e["fun_name"]]

    def obs_compile_probe_a(x):
        return x * 3.0 + 17.25

    def obs_compile_probe_b(x):
        return x * 5.0 - 17.25

    x = np.arange(57, dtype=np.float32)
    with obs.session() as sess:
        before = time.time_ns()
        with obs.scope("outer"):
            jax.jit(obs_compile_probe_a)(x).block_until_ready()
        spans = compiles("obs_compile_probe_a", sess)
        assert len(spans) == 1
        (span,) = spans
        assert span["parent"] == "outer"
        assert before <= span["t0_ns"] <= span["t1_ns"] <= time.time_ns()
        assert span["dur_s"] > 0
    n_events = len(sess.events)
    jax.jit(obs_compile_probe_b)(x).block_until_ready()
    assert len(sess.events) == n_events
    assert not compiles("obs_compile_probe_b", sess)


def test_scalarization_of_array_payloads():
    with obs.session() as sess:
        obs.record("arrays", small=np.arange(3), big=np.zeros(1000),
                   zero_d=np.float64(2.0))
        e = sess.events[-1]
    assert e["small"] == [0, 1, 2]
    assert e["zero_d"] == 2.0
    assert e["big"] == {"min": 0.0, "max": 0.0, "mean": 0.0,
                        "size": 1000}


def test_single_active_session_enforced():
    with obs.session():
        with pytest.raises(RuntimeError, match="already active"):
            with obs.session():
                pass
    assert not obs.enabled()               # cleared even after nesting


# -------------------------------------------------- recompile detector
def test_retrace_probe_counts_jit_cache_misses():
    obs.reset_retrace_counts()
    f = jax.jit(obs.retrace_probe("t.f", lambda x: x * 2))
    f(jnp.ones(3))
    f(jnp.ones(3))                         # cache hit: no wrapper run
    f(jnp.ones(4))                         # shape change: retrace
    assert obs.retrace_counts()["t.f"] == 2


def test_retrace_storm_flagged_and_warned():
    obs.reset_retrace_counts()
    with obs.session(retrace_storm=3) as sess:
        g = jax.jit(obs.retrace_probe("t.storm", lambda x: x + 1))
        g(jnp.ones(1))
        g(jnp.ones(2))
        with pytest.warns(UserWarning, match="retrace storm"):
            g(jnp.ones(3))
        events = [e for e in sess.events if e["kind"] == "retrace"
                  and e["name"] == "t.storm"]
    assert [e["count"] for e in events] == [1, 2, 3]
    assert [e["storm"] for e in events] == [False, False, True]
    assert sess.retraces["t.storm"] == 3
    assert retrace_summary(events)[0]["storm"]


# ------------------------------------------------ jit-safety contract
def test_jit_tap_stages_nothing_without_session(monkeypatch):
    # fresh closure per trace: jax caches traces by function identity,
    # which is exactly why the trace-time gate makes sessions have to
    # be entered before the instrumented step is first compiled
    def make_fn():
        def fn(x):
            obs.jit_tap("t.tap", {"m": jnp.mean(x)})
            return x * 2
        return fn

    assert not obs.enabled()
    assert "callback" not in str(jax.make_jaxpr(make_fn())(jnp.ones(4)))
    with obs.session():
        assert "callback" in str(jax.make_jaxpr(make_fn())(jnp.ones(4)))

    # the engine's round: its spans and counters emit nothing without a
    # session, and the fused step traces to the same program with no
    # session and under one that streams nothing out of jit
    emitted = []
    monkeypatch.setattr(obs.ObsSession, "emit",
                        lambda self, *a, **k: emitted.append(a))
    eng = _round_engine()
    assert eng.engine_cfg.effective_fused
    step, shapes = eng._fused_step, []

    def spy(*args):
        shapes.append(jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), args))
        return step(*args)

    eng._fused_step = spy
    state = eng.start_run()
    for t in (1, 2):
        _one_round(eng, state, t)
    assert emitted == [] and len(shapes) == 2
    bare = str(jax.make_jaxpr(eng._build_fused_step_fn())(*shapes[0]))
    assert "callback" not in bare
    with obs.session(jit_stream=False):
        traced = str(jax.make_jaxpr(eng._build_fused_step_fn())(
            *shapes[0]))
    assert traced == bare


def test_jit_tap_delivers_values_under_jit():
    with obs.session() as sess:
        def fn(x):
            obs.jit_tap("t.tap", {"m": jnp.mean(x), "n": x.shape[0]})
            return x * 2
        jax.jit(fn)(jnp.arange(4.0)).block_until_ready()
        taps = [e for e in sess.events if e["name"] == "t.tap"]
    assert len(taps) == 1
    assert taps[0]["kind"] == "jit"
    assert taps[0]["m"] == pytest.approx(1.5)
    assert taps[0]["n"] == 4


def test_wire_encode_stages_no_callback_without_session():
    from repro.kernels.ops import mixed_res_wire_aggregate

    def make_agg():
        def agg(flat, w):
            return mixed_res_wire_aggregate(flat, w, 0.5, 4)[0]
        return agg

    flat = jnp.ones((2, 256))
    w = jnp.full((2,), 0.5)
    assert not obs.enabled()
    assert "callback" not in str(jax.make_jaxpr(make_agg())(flat, w))
    with obs.session():
        assert "callback" in str(jax.make_jaxpr(make_agg())(flat, w))


# ------------------------------------------- spans inside the round
def _round_engine():
    """A tiny Table III engine (fused step, bisection-LP power) whose
    second round of four evaluates and whose first does not."""
    from repro.sim.scenarios import build_problem
    from repro.sim.sweep import _make_engine

    scn = _tiny("paper-table3", T=4)
    eng = _make_engine(scn, build_problem(scn),
                       ("mixed-resolution", {"lambda_": 0.2, "b": 4}),
                       "bisection-lp")
    eng.fl = dataclasses.replace(eng.fl, eval_every=2)
    return eng


def _one_round(eng, state, t):
    """One round through the public API, as the benchmark drives it."""
    work = eng.train_round(state, t)
    sol = eng.solve_uplink_host(state.chan, work.bits_np, work.active)
    eng.finish_round(state, work, sol.straggler_s,
                     per_user_s=sol.latencies)


def test_engine_round_spans_and_lp_count(monkeypatch):
    from repro.core.power import bisection_lp

    lps = []
    feasible = bisection_lp._feasible_powers

    def counted(*args):
        lps.append(1)
        return feasible(*args)

    monkeypatch.setattr(bisection_lp, "_feasible_powers", counted)
    eng = _round_engine()
    state = eng.start_run()
    assert eng.power.name == "bisection-lp"
    assert not eng.eval_due(1) and eng.eval_due(2)
    with obs.session() as sess:
        for t in (1, 2):
            with obs.context(round=t):
                _one_round(eng, state, t)
    phases = [e for e in sess.events if e["kind"] == "phase"]
    order = ["engine.draw", "engine.gather", "engine.h2d",
             "engine.dispatch", "engine.fetch", "engine.solve"]
    for t, tail in ((1, []), (2, ["eval"])):
        names = [e["name"] for e in phases if e["round"] == t
                 and e["name"] != "jax.compile"]
        assert names == order + tail, (t, names)
    # every span of the round is a top-level call of the round's API
    assert all(e["parent"] is None for e in phases
               if e["name"] != "jax.compile")
    # the step compiles in round 1, inside its dispatch
    assert any(e["parent"] == "engine.dispatch" for e in phases
               if e["name"] == "jax.compile" and e["round"] == 1)
    assert lps and sess.counters["phy.lp_solves"] == len(lps)
    (total,) = [e["total"] for e in sess.events
                if e["kind"] == "counter" and e["name"] == "phy.lp_solves"]
    assert total == len(lps)
    # the report shows the count
    assert f"phy.lp_solves: {len(lps)}" in render_report(sess.events)


def test_report_renders_counter_totals(tmp_path):
    path = str(tmp_path / "counters.jsonl")
    with obs.session(jsonl=path):
        obs.counter("phy.lp_solves", 14)
        obs.counter("phy.lp_solves", 13)
        obs.counter("unit.count")
    events = load_events(path)
    assert counter_totals(events) == {"phy.lp_solves": 27.0,
                                      "unit.count": 1.0}
    text = render_report(events)
    assert "== counters ==\n  phy.lp_solves: 27\n  unit.count: 1" in text
    # two sessions' totals in one trace add up; no counters, no section
    assert counter_totals(events + events)["phy.lp_solves"] == 54.0
    assert "== counters ==" not in render_report(
        [e for e in events if e["kind"] != "counter"])


def test_phase_breakdown_self_time():
    def span(name, t0, t1, parent=None):
        return {"kind": "phase", "name": name, "dur_s": (t1 - t0) * 1e-9,
                "t0_ns": t0, "t1_ns": t1, "parent": parent}

    events = [span("engine.draw", 10, 20, "train_round"),
              span("engine.h2d", 30, 60, "train_round"),
              span("train_round", 0, 100),
              span("engine.draw", 110, 115, "train_round"),
              span("train_round", 100, 150),
              {"kind": "phase", "name": "finish_round", "dur_s": 4e-8}]
    rows = {r["phase"]: r for r in phase_breakdown(events)}
    assert rows["train_round"]["total_s"] == pytest.approx(150e-9)
    assert rows["train_round"]["self_s"] == pytest.approx(105e-9)
    assert rows["train_round"]["calls"] == 2
    assert rows["engine.draw"]["self_s"] == pytest.approx(15e-9)
    assert rows["engine.h2d"]["self_s"] == pytest.approx(30e-9)
    # no wall-clock times: self time is the total
    assert rows["finish_round"]["self_s"] == pytest.approx(40e-9)
    assert "self_s" in render_report(events)


# --------------------------------------------------- sim-engine smoke
@pytest.fixture(scope="module")
def traced_grid():
    scn = _tiny("churn-0.7", participation=0.5)
    baseline = run_grid_batched([scn], QUANTIZERS, POWERS, quick=False)
    with obs.session() as sess:
        traced = run_grid_batched([scn], QUANTIZERS, POWERS,
                                  quick=False)
        events = list(sess.events)
    return baseline, traced, events


def test_round_events_match_returned_logs(traced_grid):
    _, traced, events = traced_grid
    logs = traced[0].result.logs
    rounds = [e for e in events if e["name"] == "engine.round"]
    assert len(rounds) == len(logs)
    for e, log in zip(rounds, logs):
        assert e["round"] == e["t"] == log.round
        assert e["bits_mean"] == pytest.approx(
            float(np.mean(log.bits_per_user)))
        assert e["uplink_s"] == pytest.approx(log.uplink_latency_s)
        assert e["cum_latency_s"] == pytest.approx(log.cum_latency_s)
        assert e["mean_s"] == pytest.approx(log.mean_s)
        if log.test_acc is not None:
            assert e["acc"] == pytest.approx(log.test_acc)
        assert e["scenario"] == "churn-0.7-tiny"
        assert e["quantizer"] == "mixed"
        assert e["power"] == "ours"


def test_jit_round_taps_stream_per_round(traced_grid):
    _, traced, events = traced_grid
    logs = traced[0].result.logs
    taps = [e for e in events if e["name"] == "engine.jit_round"]
    assert len(taps) == len(logs)
    for e, log in zip(taps, logs):
        assert e["kind"] == "jit"
        assert e["round"] == log.round
        # bits stats over ALL users (absent users carry 0 bits)
        assert e["bits_min"] == pytest.approx(
            float(np.min(log.bits_per_user)))
        assert e["bits_median"] == pytest.approx(
            float(np.median(log.bits_per_user)))
        assert e["mean_s"] == pytest.approx(log.mean_s, rel=1e-5)


def test_phy_solve_events_carry_solver_diagnostics(traced_grid):
    _, traced, events = traced_grid
    solves = [e for e in events if e["name"] == "phy.solve"]
    assert len(solves) == len(traced[0].result.logs)
    for e in solves:
        assert e["power"] == "ours"
        assert 0 < e["rate_min"] <= e["rate_median"] <= e["rate_p95"]
        assert e["straggler_s_max"] >= e["straggler_s_min"] > 0
        assert e["bisection_iters_mean"] > 0
        assert 0.0 <= e["bisection_converged_mean"] <= 1.0


def test_phase_scopes_cover_round_lifecycle(traced_grid):
    _, traced, events = traced_grid
    T = len(traced[0].result.logs)
    phases = phase_breakdown(events)
    names = {p["phase"]: p for p in phases}
    for phase in ("train_round", "solve_uplink", "finish_round"):
        assert names[phase]["calls"] == T
        assert names[phase]["total_s"] > 0
    table = per_round_table(events)
    assert [r["round"] for r in table] == list(range(1, T + 1))
    assert all("train_s" in r and "bisect_iters" in r for r in table)


def test_obs_session_does_not_perturb_results(traced_grid):
    """Round outputs are bit-identical with and without a session."""
    baseline, traced, _ = traced_grid
    for rb, rt in zip(baseline, traced):
        lb, lt = rb.result.logs, rt.result.logs
        assert len(lb) == len(lt)
        for a, b in zip(lb, lt):
            np.testing.assert_array_equal(a.bits_per_user,
                                          b.bits_per_user)
            assert a.test_acc == b.test_acc
            assert a.mean_s == b.mean_s
            assert a.uplink_latency_s == b.uplink_latency_s
        assert rb.summary == rt.summary


# ------------------------------------------------ solver info growth
def test_solver_info_exposes_convergence_state():
    from repro.core.channel import CFmMIMOConfig, make_channel
    from repro.phy import (bisection_solve, bundle_from_realizations,
                           dinkelbach_solve, maxsum_solve)

    chan = make_channel(CFmMIMOConfig(M=8, N=2, K=4), seed=0)
    cb = bundle_from_realizations([chan])
    bits = np.full((1, 4), 1e6)

    sol = bisection_solve(cb, bits)
    assert bool(np.all(sol.info["bisection_converged"]))
    assert float(np.max(sol.info["bisection_gap"])) >= 0.0

    sol = dinkelbach_solve(cb, bits, outer=6)
    assert set(sol.info) >= {"dinkelbach_converged",
                             "dinkelbach_residual",
                             "dinkelbach_safeguard"}
    assert np.all(np.asarray(sol.info["dinkelbach_residual"]) >= 0.0)
    assert np.all(np.asarray(sol.info["dinkelbach_safeguard"]) >= 0.0)

    sol = maxsum_solve(cb, bits, iters=20)
    assert np.asarray(sol.info["maxsum_iters"]).item() == 20.0
    assert np.isfinite(float(np.max(sol.info["maxsum_grad_norm"])))


# -------------------------------------------------- report rendering
def test_report_renders_wire_and_csv(tmp_path):
    scn = _tiny("fused-wire", T=2)
    path = str(tmp_path / "wire.jsonl")
    with obs.session(jsonl=path):
        run_grid_batched([scn],
                         {"mixed": ("mixed-resolution",
                                    {"lambda_": 0.2, "b": 10})},
                         POWERS, quick=False)
    events = load_events(path)
    wire = wire_summary(events)
    assert wire["encode_bytes_out"] == wire["decode_bytes_in"] > 0
    assert wire["compression_ratio"] > 1.0
    # a CPU trace gets a host-clock rate, never a device roofline share
    assert wire["wire_gbps"] > 0
    assert "roofline_fraction" not in wire

    csv_out = str(tmp_path / "rounds.csv")
    text = render_report(events, csv_out=csv_out)
    for section in ("== per-round ==", "== phase time ==",
                    "== fused wire traffic ==", "== recompilations =="):
        assert section in text
    header = open(csv_out).readline()
    assert header.startswith("round,")


def _wire_trace(platform, kind):
    return [{"kind": "session", "name": "start", "platform": platform,
             "device_kind": kind, "device_count": 1},
            {"kind": "jit", "name": "wire.encode", "bytes_in": 4e6,
             "bytes_out": 1e6},
            {"kind": "jit", "name": "wire.decode", "bytes_in": 1e6,
             "bytes_out": 4e6},
            {"kind": "phase", "name": "train_round", "dur_s": 0.5}]


@pytest.mark.parametrize("platform,kind", [
    ("tpu", "TPU v5 lite"),
    ("cpu", "cpu"),
])
def test_wire_roofline_share_only_from_a_known_tpu(platform, kind):
    """The wire rate is bytes over the train phase's host wall time, a
    host-clock count on every platform: no trace gets a roofline share
    from it (the kernels' share comes from a profiler trace)."""
    wire = wire_summary(_wire_trace(platform, kind))
    assert wire["wire_gbps"] == pytest.approx(10e6 / 0.5 / 1e9)
    assert "roofline_fraction" not in wire


def test_unknown_device_kind_has_no_peaks():
    from repro.launch.roofline import device_peaks
    assert device_peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks("TPU v99")
    # the wire summary reads no peaks table, so an unknown chip is no
    # error there
    wire = wire_summary(_wire_trace("tpu", "TPU v99"))
    assert wire["wire_gbps"] == pytest.approx(10e6 / 0.5 / 1e9)
    assert "roofline_fraction" not in wire


# ------------------------------------- engine verbose / log_every knob
def test_engine_round_print_behind_verbose(capsys):
    from repro.sim.sweep import run_cell

    scn = _tiny("paper-table3", T=2)
    run_cell(scn, ("mixed-resolution", {"lambda_": 0.2, "b": 4}),
             quick=False)
    assert "[round" not in capsys.readouterr().out   # default: silent
    run_cell(scn, ("mixed-resolution", {"lambda_": 0.2, "b": 4}),
             quick=False, verbose=True)
    assert "[round" in capsys.readouterr().out       # quickstart line


def test_engine_log_every_throttles_console(capsys):
    from repro.sim.engine import EngineConfig
    from repro.sim.scenarios import build_problem
    from repro.sim.sweep import _make_engine

    scn = _tiny("paper-table3", T=4)
    engine = _make_engine(scn, build_problem(scn),
                          ("mixed-resolution", {"lambda_": 0.2, "b": 4}),
                          None)
    engine.engine_cfg = dataclasses.replace(
        engine.engine_cfg, verbose=True, log_every=2)
    engine.run()
    out = capsys.readouterr().out
    printed = [ln for ln in out.splitlines() if ln.startswith("[round")]
    # eval_every=1 on the tiny scenario: rounds 2 and 4 (t==T) print
    assert len(printed) == 2
    assert "[round    2]" in out and "[round    4]" in out
