"""Batched-phy sweep driver — one power solve per round for a grid.

``run_grid`` steps every (scenario, quantizer, power) cell's engine to
completion one cell at a time, paying one host numpy/scipy power solve
per cell per round: O(cells x rounds) host round-trips.  This driver
runs all cells of a scenario in LOCKSTEP over rounds and routes power
control through the batched repro.phy solvers — cells sharing a
power-controller spec are stacked into one ``ChannelBatch`` and solved
in a single jitted device call, so the per-round host round-trips drop
to O(power-specs) = O(1) per round regardless of grid width.

Two further de-duplications the lockstep structure buys:

* power control never feeds back into training, so all cells of a
  quantizer share ONE training state — the jitted train step runs once
  per quantizer per round, not once per (quantizer x power) cell.
  Host ``run_grid`` gets the same trajectories by re-running identical
  RNG streams per power label; a cell that exhausts its latency budget
  snapshots the shared params at its stopping round.
* the stacked channel bundle is cached per power group and re-built
  only when some cell's realization object changed (Monte-Carlo
  redraws); with a fixed realization the device bundle uploads once.

Churn is handled by the solvers' mask argument (same sub-channel
semantics as the engine's host path — no power, no interference, no
straggler contribution for absent users).  Summaries gain a ``max_p``
column (the largest power coefficient any user was allocated across
the run — the CI sanity script asserts max_p <= 1, i.e. transmit
power <= p_max).

Numerics: the batched path solves in jax's default dtype (f32 unless
JAX_ENABLE_X64=1) while the host path is numpy f64, so latencies agree
to the documented parity tolerances (DESIGN.md section 7), not
bit-for-bit; tests/test_phy_driver.py pins the drift on a churn
scenario.

``replicates=R`` (DESIGN.md section 8) adds the Monte-Carlo replicate
axis: every (quantizer, power) cell runs R independent trajectories —
distinct minibatch/churn RNG streams, distinct channel realizations,
independently evolving quantizer state — and the lockstep structure is
preserved: still ONE jitted train call per quantizer per round (the
engine vmaps the replicate axis) and ONE power solve per power spec
per round (the R x cells uplink problems stack into one flat
ChannelBatch).  Summaries become across-replicate means with
``<metric>_ci95`` confidence half-widths; ``SweepResult.result`` holds
the per-replicate FLResult list.  ``replicates=1`` exercises the same
machinery and reproduces the unreplicated driver bit-for-bit on
training metrics (tests/test_mc_replicates.py).

Async scenarios (``Scenario.async_active``, DESIGN.md section 11) keep
the lockstep structure but NOT the shared-training-state dedup: the
event clock's arrival times depend on the power solve, so each
(quantizer, power) cell gets its own track.  The batched solve still
groups cells by power label — one device solve per power spec per
round — and after it each async cell runs the host event clock plus
ONE jitted aggregate dispatch (``complete_round_async``) before the
usual finish/accounting stage, whose latency burn-down then uses the
event-clock round duration instead of the slowest user.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Tuple, Union

import jax
import numpy as np

from repro import obs as _obs
from repro.core.power import PowerController
from repro.phy import (batched_solver, bundle_from_realization_grid,
                       bundle_from_realizations)

from .engine import (ReplicatedRoundWork, ReplicatedRunState, RoundWork,
                     RunState, UplinkSolution, VectorizedFLEngine)
from .metrics import summarize_replicates
from .scenarios import Scenario, build_problem
from .sweep import (PowerSpec, QuantSpec, SweepCell, SweepResult,
                    _make_engine, _make_power, _resolve_scenario,
                    _to_result)


@dataclasses.dataclass
class _Track:
    """One quantizer's engine + its shared training state."""
    engine: VectorizedFLEngine
    state: RunState
    cells: List["_Cell"] = dataclasses.field(default_factory=list)

    @property
    def alive(self) -> bool:
        return any(c.alive for c in self.cells)


@dataclasses.dataclass
class _Cell:
    """One (quantizer, power) grid cell: per-cell latency accounting
    over the track's shared training trajectory."""
    track: _Track
    power: Optional[PowerController]
    qlabel: str
    plabel: str
    acct: RunState                 # logs / cum_latency / params snapshot
    alive: bool = True
    max_p: float = 0.0


@dataclasses.dataclass
class _ReplTrack:
    """One quantizer's engine + its shared R-replicate training state."""
    engine: VectorizedFLEngine
    state: ReplicatedRunState
    cells: List["_ReplCell"] = dataclasses.field(default_factory=list)

    @property
    def alive(self) -> bool:
        return any(c.alive.any() for c in self.cells)


@dataclasses.dataclass
class _ReplCell:
    """One (quantizer, power) cell with per-replicate accounting.

    The track's R trajectories are shared by all its cells; each cell
    keeps an [R] alive mask (a replicate stops logging once ITS latency
    budget is spent), per-replicate logs/cum-latency, and a params
    snapshot taken at each replicate's own stopping round.
    """
    track: _ReplTrack
    power: Optional[PowerController]
    qlabel: str
    plabel: str
    logs: List[List]               # [R] lists of RoundLog
    cum_latency: np.ndarray        # [R] float64
    alive: np.ndarray              # [R] bool
    rounds_done: np.ndarray        # [R] int
    params: List[object]           # [R] per-replicate final params
    max_p: float = 0.0


_BundleCache = Dict[str, Tuple[List[object], object]]


def _emit_solve_event(plabel: str, sol, mask: np.ndarray,
                      stragglers: np.ndarray) -> None:
    """Host-side ``phy.solve`` diagnostics for one power group's batched
    solve: user-rate percentiles over active users, straggler spread,
    and every per-cell solver info key (iteration counts, convergence
    flags, safeguard activations) reduced to mean/max."""
    rates = np.asarray(sol.rates, np.float64)
    act = rates[np.asarray(mask) > 0]
    fields: Dict[str, object] = {
        "power": plabel, "cells": int(rates.shape[0]),
        "straggler_s_max": float(np.max(stragglers)),
        "straggler_s_min": float(np.min(stragglers)),
    }
    if act.size:
        fields["rate_min"] = float(np.min(act))
        fields["rate_median"] = float(np.median(act))
        fields["rate_p95"] = float(np.percentile(act, 95.0))
    for k, v in sol.info.items():
        a = np.asarray(v)
        if a.ndim <= 1 and (np.issubdtype(a.dtype, np.number)
                            or a.dtype == np.bool_):
            fields[f"{k}_mean"] = float(np.mean(a))
            fields[f"{k}_max"] = float(np.max(a))
    _obs.record("phy.solve", **fields)


def _poison_bundle(cb):
    """The channel-estimate corruption fault: NaN out the cached device
    bundle's direct-link coefficients — the symptom `resilient_
    batched_solve` detects (non-finite solution rows) and recovers from
    by rebuilding the bundle from the retained realizations."""
    import jax.numpy as jnp

    return dataclasses.replace(cb, A_bar=cb.A_bar * jnp.float32(np.nan))


def _solve_round_batched(cells: List[_Cell], works: List[RoundWork],
                         cache: _BundleCache, t: int = 0,
                         resilience=None
                         ) -> Tuple[List[UplinkSolution], np.ndarray]:
    """One batched device solve per distinct power spec; returns one
    :class:`UplinkSolution` per cell — straggler latency plus per-user
    completion times [K] (zeros without a channel — the async event
    clock's input) for this round — and the per-cell count of power
    fallback stages consumed (all-zero without a resilience config)."""
    K0 = cells[0].track.engine.K if cells else 0
    uplinks = [0.0] * len(cells)
    per_user = [np.zeros(K0) for _ in cells]
    fb_counts = np.zeros(len(cells), np.int64)
    # group cells by power label (one spec per label within a grid)
    groups: Dict[str, List[int]] = {}
    for i, cell in enumerate(cells):
        if cell.power is None or cell.track.state.chan is None:
            continue
        groups.setdefault(cell.plabel, []).append(i)
    for plabel, idx in groups.items():
        chans = [cells[i].track.state.chan for i in idx]
        # cache holds the realization objects themselves (not ids —
        # GC id reuse across Monte-Carlo redraws would alias), so a
        # fixed realization uploads the device bundle exactly once
        hit = cache.get(plabel)
        if (hit is None or len(hit[0]) != len(chans)
                or any(a is not b for a, b in zip(hit[0], chans))):
            cache[plabel] = (chans, bundle_from_realizations(chans))
        cb = cache[plabel][1]
        K = chans[0].cfg.K
        bits = np.ones((len(idx), K))
        mask = np.zeros((len(idx), K))
        for row, i in enumerate(idx):
            mask[row] = works[i].active
            bits[row] = np.where(works[i].active > 0,
                                 np.maximum(works[i].bits_np, 1.0), 1.0)
        if resilience is not None:
            from repro.resilience.fallback import resilient_batched_solve

            if resilience.faults.channel_corrupt(t):
                cb = _poison_bundle(cb)
                cache[plabel] = (cache[plabel][0], cb)
            sol, fb, rebuilt = resilient_batched_solve(
                cells[idx[0]].power, cb, bits, mask,
                config=resilience, t=t, obs_tag=plabel,
                rebuild=lambda ch=chans: bundle_from_realizations(ch))
            if rebuilt is not None:
                cache[plabel] = (cache[plabel][0], rebuilt)
            for row, i in enumerate(idx):
                fb_counts[i] = fb[row]
        else:
            sol = batched_solver(cells[idx[0]].power)(cb, bits,
                                                      mask=mask)
        stragglers = np.asarray(sol.straggler_latency, np.float64)
        latencies = np.asarray(sol.latencies, np.float64)
        p_max_round = np.asarray(np.max(sol.p, axis=-1), np.float64)
        if _obs.enabled():
            _emit_solve_event(plabel, sol, mask, stragglers)
        for row, i in enumerate(idx):
            uplinks[i] = float(stragglers[row])
            per_user[i] = latencies[row]
            cells[i].max_p = max(cells[i].max_p, float(p_max_round[row]))
    return ([UplinkSolution(u, pu)
             for u, pu in zip(uplinks, per_user)], fb_counts)


def _run_scenario_lockstep(scn: Scenario, tracks: List[_Track],
                           verbose: bool, resilience=None,
                           ckpt=None) -> int:
    """Returns the resumed-from round frontier (0 for a fresh run)."""
    cache: _BundleCache = {}
    t0 = ckpt.restore_round(scn, tracks) if ckpt is not None else 0
    for t in range(t0 + 1, scn.T + 1):
        live_tracks = [tr for tr in tracks if tr.alive]
        if not live_tracks:
            break
        with _obs.round_scope(t):
            # ONE jitted training step per quantizer, shared by cells
            track_work = {}
            with _obs.scope("train_round"):
                for tr in live_tracks:
                    with _obs.context(quantizer=tr.cells[0].qlabel):
                        track_work[id(tr)] = tr.engine.train_round(
                            tr.state, t)
                        if _obs.enabled():
                            # deliver this track's jit taps under its
                            # quantizer tag (and time real compute)
                            jax.block_until_ready(tr.state.params)
            live = [c for tr in live_tracks for c in tr.cells
                    if c.alive]
            works = [track_work[id(c.track)] for c in live]
            with _obs.scope("solve_uplink"):
                sols, fallbacks = _solve_round_batched(
                    live, works, cache, t=t, resilience=resilience)
            with _obs.scope("finish_round"):
                for cell, work, (uplink, pu), fb in zip(
                        live, works, sols, fallbacks):
                    eng = cell.track.engine
                    info = None
                    with _obs.context(quantizer=cell.qlabel,
                                      power=cell.plabel):
                        if eng.engine_cfg.async_active:
                            # async tracks are per-(quantizer, power)
                            # cell (run_grid_batched), so completing on
                            # the TRACK's training state is exact
                            info = eng.complete_round_async(
                                cell.track.state, work, pu)
                        # accounting sees the shared trajectory's
                        # current params (snapshotted here, so a
                        # budget-stopped cell keeps the params of ITS
                        # final round even as the track trains on)
                        cell.acct.params = cell.track.state.params
                        cell.alive = eng.finish_round(
                            cell.acct, work, uplink, verbose=verbose,
                            async_info=info, per_user_s=pu,
                            power_fallbacks=int(fb))
        if ckpt is not None and t % ckpt.every == 0:
            ckpt.save_round(scn, tracks, t)
    return t0


def _solve_round_replicated(cells: List[_ReplCell],
                            works: List[ReplicatedRoundWork],
                            cache: _BundleCache, R: int, t: int = 0,
                            resilience=None
                            ) -> Tuple[np.ndarray, np.ndarray,
                                       np.ndarray]:
    """One batched device solve per distinct power spec over the
    flattened R x cells axis; returns per-(cell, replicate) straggler
    latencies [n_cells, R], per-user completion times [n_cells, R, K]
    and fallback-stage counts [n_cells, R] (zeros without a resilience
    config)."""
    uplinks = np.zeros((len(cells), R))
    K0 = cells[0].track.engine.K if cells else 0
    per_user = np.zeros((len(cells), R, K0))
    fb_counts = np.zeros((len(cells), R), np.int64)
    groups: Dict[str, List[int]] = {}
    for i, cell in enumerate(cells):
        if cell.power is None or cell.track.state.chans[0] is None:
            continue
        groups.setdefault(cell.plabel, []).append(i)
    for plabel, idx in groups.items():
        # row i * R + r of the flat bundle is (cell idx[i], replicate r)
        grid = [cells[i].track.state.chans for i in idx]
        flat = [chan for row in grid for chan in row]
        hit = cache.get(plabel)
        if (hit is None or len(hit[0]) != len(flat)
                or any(a is not b for a, b in zip(hit[0], flat))):
            cache[plabel] = (flat, bundle_from_realization_grid(grid))
        cb = cache[plabel][1]
        K = flat[0].cfg.K
        bits = np.ones((len(idx) * R, K))
        mask = np.zeros((len(idx) * R, K))
        for row, i in enumerate(idx):
            w = works[i]
            mask[row * R:(row + 1) * R] = w.active
            bits[row * R:(row + 1) * R] = np.where(
                w.active > 0, np.maximum(w.bits_np, 1.0), 1.0)
        if resilience is not None:
            from repro.resilience.fallback import resilient_batched_solve

            if resilience.faults.channel_corrupt(t):
                cb = _poison_bundle(cb)
                cache[plabel] = (cache[plabel][0], cb)
            sol, fb, rebuilt = resilient_batched_solve(
                cells[idx[0]].power, cb, bits, mask,
                config=resilience, t=t, obs_tag=plabel,
                rebuild=lambda g=grid: bundle_from_realization_grid(g))
            if rebuilt is not None:
                cache[plabel] = (cache[plabel][0], rebuilt)
            fb = np.asarray(fb, np.int64).reshape(len(idx), R)
            for row, i in enumerate(idx):
                fb_counts[i] = fb[row]
        else:
            sol = batched_solver(cells[idx[0]].power)(cb, bits,
                                                      mask=mask)
        stragglers = np.asarray(sol.straggler_latency,
                                np.float64).reshape(len(idx), R)
        latencies = np.asarray(sol.latencies,
                               np.float64).reshape(len(idx), R, K)
        if _obs.enabled():
            _emit_solve_event(plabel, sol, mask, stragglers)
        p_max_round = np.asarray(np.max(sol.p, axis=-1),
                                 np.float64).reshape(len(idx), R)
        for row, i in enumerate(idx):
            uplinks[i] = stragglers[row]
            per_user[i] = latencies[row]
            # max_p only over replicates still accounting (alive);
            # dead replicates' rows ride along for shape stability
            if cells[i].alive.any():
                cells[i].max_p = max(
                    cells[i].max_p,
                    float(np.max(p_max_round[row][cells[i].alive])))
    return uplinks, per_user, fb_counts


def _run_scenario_lockstep_replicated(scn: Scenario,
                                      tracks: List[_ReplTrack], R: int,
                                      verbose: bool, resilience=None,
                                      ckpt=None) -> int:
    """Returns the resumed-from round frontier (0 for a fresh run)."""
    cache: _BundleCache = {}
    t0 = ckpt.restore_round(scn, tracks) if ckpt is not None else 0
    for t in range(t0 + 1, scn.T + 1):
        live_tracks = [tr for tr in tracks if tr.alive]
        if not live_tracks:
            break
        with _obs.round_scope(t):
            # ONE jitted training step per quantizer, all R replicates
            track_work = {}
            with _obs.scope("train_round"):
                for tr in live_tracks:
                    with _obs.context(quantizer=tr.cells[0].qlabel):
                        track_work[id(tr)] = \
                            tr.engine.train_round_replicated(tr.state, t)
                        if _obs.enabled():
                            jax.block_until_ready(tr.state.params)
            live = [c for tr in live_tracks for c in tr.cells
                    if c.alive.any()]
            works = [track_work[id(c.track)] for c in live]
            with _obs.scope("solve_uplink"):
                uplinks, per_user, fallbacks = _solve_round_replicated(
                    live, works, cache, R, t=t, resilience=resilience)
            # async cells aggregate BEFORE eval (sync cells aggregated
            # inside the train step, so the eval ordering matches)
            infos: List[Optional[object]] = [None] * len(live)
            for i, (cell, work) in enumerate(zip(live, works)):
                eng = cell.track.engine
                if eng.engine_cfg.async_active:
                    with _obs.scope("complete_async"), \
                         _obs.context(quantizer=cell.qlabel,
                                      power=cell.plabel):
                        infos[i] = eng.complete_round_replicated_async(
                            cell.track.state, work, per_user[i])
            # per-replicate accuracy, once per track on eval rounds —
            # only for replicates some cell still accounts (a replicate
            # dead in EVERY cell of the track is never logged again)
            track_acc: Dict[int, Optional[np.ndarray]] = {}
            for tr in live_tracks:
                track_acc[id(tr)] = (
                    tr.engine.eval_accuracy_replicated(
                        tr.state,
                        alive=np.logical_or.reduce(
                            [c.alive for c in tr.cells]))
                    if tr.engine.eval_due(t) else None)
            with _obs.scope("finish_round"):
                for cell, work, uplink, pu, fb, info in zip(
                        live, works, uplinks, per_user, fallbacks,
                        infos):
                    _finish_replicated_cell(cell, work, uplink,
                                            track_acc, t, R, verbose,
                                            async_info=info,
                                            per_user=pu, fallbacks=fb)
        if ckpt is not None and t % ckpt.every == 0:
            ckpt.save_round(scn, tracks, t)
    for tr in tracks:
        for cell in tr.cells:
            for r in np.flatnonzero(cell.alive):
                cell.params[r] = tr.engine.replicate_params(
                    tr.state, int(r))
    return t0


def _finish_replicated_cell(cell: _ReplCell, work: ReplicatedRoundWork,
                            uplink: np.ndarray,
                            track_acc: Dict[int, Optional[np.ndarray]],
                            t: int, R: int, verbose: bool,
                            async_info=None,
                            per_user: Optional[np.ndarray] = None,
                            fallbacks: Optional[np.ndarray] = None
                            ) -> None:
    from repro.fl.loop import RoundLog

    from .engine import straggler_gap

    eng = cell.track.engine
    comp_lat = eng.comp_lat
    accs = track_acc[id(cell.track)]
    K = eng.K
    for r in np.flatnonzero(cell.alive):
        if async_info is not None:
            # async: the event clock's round duration burns the budget
            up = float(async_info.round_uplink_s[r])
            gap = float(async_info.straggler_gap_s[r])
            eff = float(async_info.effective_participation[r])
            stale = float(async_info.mean_staleness[r])
            dropped = int(async_info.dropped_stale[r]
                          + async_info.dropped_churn[r])
        else:
            up = float(uplink[r])
            gap = 0.0 if per_user is None else straggler_gap(
                per_user[r], work.active[r])
            eff = float(np.sum(work.active[r] > 0)) / K
            stale, dropped = 0.0, 0
        cell.cum_latency[r] += up + comp_lat
        acc = None if accs is None else float(accs[r])
        quarantined = (int(work.quarantined[r])
                       if getattr(work, "quarantined", None) is not None
                       else 0)
        cell.logs[r].append(RoundLog(
            t, work.bits_np[r], up, comp_lat,
            float(cell.cum_latency[r]), float(work.mean_s[r]),
            acc, straggler_gap_s=gap, mean_staleness=stale,
            effective_participation=eff, dropped_uploads=dropped,
            quarantined_users=quarantined,
            power_fallbacks=(int(fallbacks[r])
                             if fallbacks is not None else 0)))
        cell.rounds_done[r] = t
        if eng.budget_spent(cell.cum_latency[r]):
            cell.alive[r] = False
            # budget exhausted: snapshot THIS replicate's
            # params at its final round while the track trains on
            cell.params[r] = eng.replicate_params(
                cell.track.state, int(r))
    if _obs.enabled():
        budget = eng.fl.latency_budget_s
        cum = cell.cum_latency[cell.alive] if cell.alive.any() \
            else cell.cum_latency
        if async_info is not None:
            gap_mean = float(np.mean(async_info.straggler_gap_s))
        elif per_user is not None:
            gap_mean = float(np.mean(
                [straggler_gap(per_user[r], work.active[r])
                 for r in range(R)]))
        else:
            gap_mean = 0.0
        _obs.record(
            "engine.round", t=t, quantizer=cell.qlabel,
            power=cell.plabel, replicates=R,
            alive_replicates=int(np.sum(cell.alive)),
            acc=None if accs is None else float(np.nanmean(accs)),
            bits_mean=float(work.bits_np.mean()),
            uplink_s=float(np.mean(uplink)),
            cum_latency_s=float(np.max(cell.cum_latency)),
            mean_s=float(np.mean(work.mean_s)),
            straggler_gap_s=gap_mean,
            budget_remaining_s=None if budget is None
            else float(budget - np.min(cum)))
    if verbose and accs is not None:
        # dead replicates carry NaN — average the live ones
        print(f"[round {t:4d}] {cell.qlabel}/{cell.plabel} "
              f"acc={np.nanmean(accs):.4f}±"
              f"{np.nanstd(accs):.4f} (R={R})")


def _to_replicated_result(scn: Scenario, cell: _ReplCell) -> SweepResult:
    from repro.fl.loop import FLResult

    results = [FLResult(params=cell.params[r], logs=cell.logs[r],
                        rounds_completed=int(cell.rounds_done[r]))
               for r in range(len(cell.logs))]
    summary = summarize_replicates([res.logs for res in results])
    summary["max_p"] = cell.max_p
    return SweepResult(cell=SweepCell(scn, cell.qlabel, cell.plabel),
                       result=results, summary=summary)


def run_grid_batched(scenarios: List[Union[str, Scenario]],
                     quantizers: Mapping[str, QuantSpec],
                     powers: Optional[Mapping[str, PowerSpec]] = None,
                     quick: bool = True, out_csv: Optional[str] = None,
                     latency_budget_s: Optional[float] = None,
                     verbose: bool = False, mesh=None,
                     replicates: Optional[int] = None,
                     resilience=None,
                     checkpoint_dir: Optional[str] = None,
                     checkpoint_every: int = 1
                     ) -> List[SweepResult]:
    """``run_grid`` semantics on the batched phy path.

    Same grid, same summaries (plus ``max_p``); within a scenario all
    cells advance round-by-round together and every round's power
    problems are solved in one jitted call per power spec.

    ``replicates=R`` (int >= 1) switches a scenario to the Monte-Carlo
    replicate axis: R independent trajectories per cell, still one
    train call per quantizer and one power solve per power spec per
    round; summaries gain mean/ci95 columns and ``SweepResult.result``
    becomes the per-replicate FLResult list.  ``replicates=None``
    (default) keeps the unreplicated driver unless the scenario itself
    declares ``Scenario.replicates > 1``.

    ``resilience`` (a :class:`repro.resilience.ResilienceConfig`) arms
    the fault-injection + detection + recovery layer (DESIGN.md §14):
    engines gain jit-traced payload guards, power solves route through
    the bounded fallback chain, and detect/recover actions surface as
    the ``quarantined_users`` / ``power_fallbacks`` metric columns.
    ``ResilienceConfig.none()`` reproduces the unarmed driver
    bit-for-bit (tests/test_resilience.py).

    ``checkpoint_dir`` makes the sweep preemption-safe: round-granular
    state snapshots land there every ``checkpoint_every`` rounds, and a
    re-run with the same directory skips finished scenarios and resumes
    interrupted ones from the last completed round frontier —
    ``resumed_from_round`` records where a resumed scenario's cells
    picked up.
    """
    from .metrics import write_metrics_csv

    if replicates is not None and replicates < 1:
        raise ValueError(f"replicates must be >= 1, got {replicates}")
    powers = powers if powers is not None else {"none": None}
    ckpt = None
    if checkpoint_dir is not None:
        from repro.resilience import SweepCheckpointer
        ckpt = SweepCheckpointer(checkpoint_dir, resilience=resilience,
                                 every=checkpoint_every)
    results: List[SweepResult] = []
    for scenario in scenarios:
        scn = _resolve_scenario(scenario, quick, latency_budget_s)
        with _obs.context(scenario=scn.name):
            n_before = len(results)
            R = replicates if replicates is not None \
                else (scn.replicates if scn.replicates > 1 else None)
            expected = len(quantizers) * len(powers)
            if ckpt is not None:
                done = ckpt.completed_rows(scn.name, expected)
                if done is not None:
                    # scenario finished in an earlier run: rebuild its
                    # summary rows from the checkpoint ledger (no
                    # FLResult — the params were not retained)
                    for row in done:
                        results.append(SweepResult(
                            cell=SweepCell(scn, row["quantizer"],
                                           row["power"]),
                            result=None,
                            summary={k: v for k, v in row.items()
                                     if k not in ("scenario",
                                                  "quantizer",
                                                  "power")}))
                    continue
            problem = build_problem(scn)
            chan = problem[4]
            # sync cells share one training state per quantizer (power
            # never feeds back into training); async arrival times DO
            # feed back, so async scenarios build one track per
            # (quantizer, power) cell.  The batched solve still groups
            # by power label, so it stays one device solve per power
            # spec per round either way.
            pgroups = ([[item] for item in powers.items()]
                       if scn.async_active else [list(powers.items())])
            if R is not None:
                tracks_r: List[_ReplTrack] = []
                for qlabel, qspec in quantizers.items():
                    for group in pgroups:
                        engine = _make_engine(scn, problem, qspec, None,
                                              mesh=mesh,
                                              resilience=resilience)
                        track = _ReplTrack(
                            engine=engine,
                            state=engine.start_replicated_run(R))
                        for plabel, pspec in group:
                            pc = _make_power(pspec)
                            track.cells.append(_ReplCell(
                                track=track,
                                power=pc if chan is not None else None,
                                qlabel=qlabel, plabel=plabel,
                                logs=[[] for _ in range(R)],
                                cum_latency=np.zeros(R),
                                alive=np.ones(R, dtype=bool),
                                rounds_done=np.zeros(R, dtype=np.int64),
                                params=[None] * R))
                        tracks_r.append(track)
                t0 = _run_scenario_lockstep_replicated(
                    scn, tracks_r, R, verbose, resilience=resilience,
                    ckpt=ckpt)
                for track in tracks_r:
                    for cell in track.cells:
                        results.append(_to_replicated_result(scn, cell))
            else:
                tracks: List[_Track] = []
                for qlabel, qspec in quantizers.items():
                    for group in pgroups:
                        engine = _make_engine(scn, problem, qspec, None,
                                              mesh=mesh,
                                              resilience=resilience)
                        track = _Track(engine=engine,
                                       state=engine.start_run())
                        for plabel, pspec in group:
                            pc = _make_power(pspec)
                            acct = dataclasses.replace(
                                track.state, logs=[], cum_latency=0.0,
                                rounds_done=0)
                            track.cells.append(_Cell(
                                track=track,
                                power=pc if chan is not None else None,
                                qlabel=qlabel, plabel=plabel,
                                acct=acct))
                        tracks.append(track)
                t0 = _run_scenario_lockstep(scn, tracks, verbose,
                                            resilience=resilience,
                                            ckpt=ckpt)
                for track in tracks:
                    for cell in track.cells:
                        res = _to_result(scn, track.engine,
                                         track.engine.result(cell.acct),
                                         (cell.qlabel, cell.plabel))
                        res.summary["max_p"] = cell.max_p
                        results.append(res)
            if t0 > 0:
                for res in results[n_before:]:
                    res.summary["resumed_from_round"] = float(t0)
            if ckpt is not None:
                ckpt.mark_scenario_done(
                    scn.name, [r.row() for r in results[n_before:]])
            if _obs.enabled():
                for res in results[n_before:]:
                    _obs.record(
                        "sweep.cell",
                        quantizer=res.cell.quantizer_label,
                        power=res.cell.power_label,
                        **{k: v for k, v in res.summary.items()
                           if isinstance(v, (int, float))})
    if out_csv:
        write_metrics_csv([r.row() for r in results], out_csv)
    return results


__all__ = ["run_grid_batched"]
