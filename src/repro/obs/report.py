"""Render an obs JSONL trace into human-readable run diagnostics.

    PYTHONPATH=src python -m repro.obs.report runs/trace.jsonl
    PYTHONPATH=src python -m repro.obs.report runs/trace.jsonl --csv rounds.csv

Sections (each present only when the trace carries its events):

* per-round table — phase wall-clock (train/solve/finish), payload-bit
  percentiles streamed from inside the jitted round step, user-rate
  percentiles + straggler latency + solver iteration counts from the
  phy solve, accuracy and latency-budget burn-down;
* phase-time breakdown — per phase name, total seconds and self
  seconds (the total less what nested phases cover), with the share of
  self time ("where did the round time go");
* wire traffic — bytes moved by the fused encode/decode kernels and
  their rate over the train phase's host wall time (a host-clock
  count: the kernels' device time is in a profiler trace);
* async rounds — the event-clock telemetry from the async round
  engine (``engine.async`` events): arrivals and staleness per round,
  effective participation, straggler gap, buffer occupancy and
  dropped-upload totals;
* counters — the total of each ``obs.counter`` over the session (as
  ``phy.lp_solves``, the feasibility tests of the bisection power
  control, one linear solve each);
* recompilation summary — per-step trace counts from the retrace
  probes, flagging silent retrace storms;
* profiler captures — directories of ``jax.profiler`` traces armed via
  ``obs.session(profile_round=...)``.
"""
from __future__ import annotations

import argparse
import collections
import csv
import json
from bisect import bisect_right
from typing import Any, Dict, List, Optional


def load_events(path: str) -> List[Dict[str, Any]]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _mean(vals: List[float]) -> float:
    return sum(vals) / len(vals) if vals else float("nan")


def _num(v: Any) -> Optional[float]:
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v)


# ------------------------------------------------------------- sections
def _child_time_s(phases: List[Dict]) -> List[float]:
    """Per phase event, the seconds its direct children cover: each
    event naming a ``parent`` is charged to the latest-starting span of
    that name, if that span holds it on the wall clock (``t0_ns`` /
    ``t1_ns``).  Children of one span run one after another, so their
    lengths add."""
    spans: Dict[str, List[tuple]] = collections.defaultdict(list)
    for i, e in enumerate(phases):
        if "t0_ns" in e:
            spans[e["name"]].append((e["t0_ns"], e["t1_ns"], i))
    for v in spans.values():
        v.sort()
    covered = [0.0] * len(phases)
    for e in phases:
        cands = spans.get(e.get("parent"))
        if not cands or "t0_ns" not in e:
            continue
        j = bisect_right(cands, e["t0_ns"], key=lambda c: c[0]) - 1
        if j >= 0 and cands[j][1] >= e["t1_ns"]:
            covered[cands[j][2]] += (e["t1_ns"] - e["t0_ns"]) * 1e-9
    return covered


def phase_breakdown(events: List[Dict]) -> List[Dict[str, Any]]:
    """Total / self / count / mean duration per phase name, largest
    total first.  Self time is a phase's duration less what the phases
    nested in it cover (found through each event's ``parent``), so a
    nested span is not counted twice; a trace without wall-clock times
    gives self time equal to total."""
    phases = [e for e in events if e.get("kind") == "phase"]
    covered = _child_time_s(phases)
    acc: Dict[str, List[float]] = collections.defaultdict(list)
    own: Dict[str, float] = collections.defaultdict(float)
    for e, c in zip(phases, covered):
        d = float(e.get("dur_s", 0.0))
        acc[e["name"]].append(d)
        own[e["name"]] += max(d - c, 0.0)
    rows = [{"phase": name, "total_s": sum(d), "self_s": own[name],
             "calls": len(d), "mean_s": _mean(d)}
            for name, d in acc.items()]
    rows.sort(key=lambda r: -r["total_s"])
    return rows


_ROUND_FIELDS = [
    # (column, kind, event name, field, reducer over the round's events)
    ("train_s", "phase", "train_round", "dur_s", sum),
    ("solve_s", "phase", "solve_uplink", "dur_s", sum),
    ("finish_s", "phase", "finish_round", "dur_s", sum),
    ("eval_s", "phase", "eval", "dur_s", sum),
    ("bits_min", "jit", "engine.jit_round", "bits_min", min),
    ("bits_med", "jit", "engine.jit_round", "bits_median", _mean),
    ("bits_p95", "jit", "engine.jit_round", "bits_p95", max),
    ("rate_min", "event", "phy.solve", "rate_min", min),
    ("rate_med", "event", "phy.solve", "rate_median", _mean),
    ("rate_p95", "event", "phy.solve", "rate_p95", max),
    ("straggler_s", "event", "phy.solve", "straggler_s_max", max),
    ("bisect_iters", "event", "phy.solve", "bisection_iters_mean",
     _mean),
    ("dink_iters", "event", "phy.solve", "dinkelbach_iters_mean",
     _mean),
    ("gap_s", "event", "engine.round", "straggler_gap_s", max),
    ("arrived", "event", "engine.async", "arrived", _mean),
    ("staleness", "event", "engine.async", "mean_staleness", _mean),
    ("eff_part", "event", "engine.async", "effective_participation",
     _mean),
    ("in_flight", "event", "engine.async", "in_flight", _mean),
    ("dropped", "event", "engine.async", "dropped_stale", sum),
    ("acc", "event", "engine.round", "acc", max),
    ("cum_lat_s", "event", "engine.round", "cum_latency_s", max),
    ("budget_left_s", "event", "engine.round", "budget_remaining_s",
     min),
]


def per_round_table(events: List[Dict]) -> List[Dict[str, Any]]:
    """One row per round tag, reducing over cells/replicates."""
    by_round: Dict[int, List[Dict]] = collections.defaultdict(list)
    for e in events:
        r = e.get("round")
        if isinstance(r, int):
            by_round[r].append(e)
    rows = []
    for t in sorted(by_round):
        row: Dict[str, Any] = {"round": t}
        for col, kind, name, field, reduce_ in _ROUND_FIELDS:
            vals = [_num(e.get(field)) for e in by_round[t]
                    if e.get("kind") == kind and e.get("name") == name]
            vals = [v for v in vals if v is not None]
            if vals:
                row[col] = reduce_(vals)
        rows.append(row)
    return rows


def wire_summary(events: List[Dict]) -> Dict[str, float]:
    """Aggregate fused encode/decode traffic, and the wire bytes per
    second of train-phase wall clock (``wire_gbps``): a host-clock
    count on any backend, never a share of a device peak, since the
    train phase holds far more than the kernels (their device time,
    and so their roofline share, comes from a profiler trace)."""
    enc_in = enc_out = dec_in = dec_out = 0.0
    calls = 0
    for e in events:
        if e.get("kind") != "jit":
            continue
        if e.get("name") == "wire.encode":
            enc_in += float(e.get("bytes_in", 0))
            enc_out += float(e.get("bytes_out", 0))
            calls += 1
        elif e.get("name") == "wire.decode":
            dec_in += float(e.get("bytes_in", 0))
            dec_out += float(e.get("bytes_out", 0))
            calls += 1
    if not calls:
        return {}
    total = enc_in + enc_out + dec_in + dec_out
    train_s = sum(float(e.get("dur_s", 0.0)) for e in events
                  if e.get("kind") == "phase"
                  and e.get("name") == "train_round")
    out = {"encode_bytes_in": enc_in, "encode_bytes_out": enc_out,
           "decode_bytes_in": dec_in, "decode_bytes_out": dec_out,
           "wire_calls": float(calls), "total_bytes": total,
           "compression_ratio": enc_in / enc_out if enc_out else 0.0}
    if train_s > 0:
        out["wire_gbps"] = total / train_s / 1e9
    return out


def async_summary(events: List[Dict]) -> Dict[str, float]:
    """Aggregate the async round engine's event-clock telemetry
    (``engine.async`` events): arrival/staleness distribution,
    effective participation, buffer occupancy and dropped-upload
    totals.  Empty for lockstep traces."""
    evs = [e for e in events
           if e.get("kind") == "event" and e.get("name") == "engine.async"]
    if not evs:
        return {}
    def col(field):
        return [v for v in (_num(e.get(field)) for e in evs)
                if v is not None]
    out = {
        "async_rounds": float(len(evs)),
        "mean_arrivals_per_round": _mean(col("arrived")),
        "mean_staleness": _mean(col("mean_staleness")),
        "max_staleness_observed": max(col("max_staleness") or [0.0]),
        "mean_effective_participation":
            _mean(col("effective_participation")),
        "mean_straggler_gap_s": _mean(col("straggler_gap_s")),
        "mean_in_flight": _mean(col("in_flight")),
        "dropped_stale_total": sum(col("dropped_stale")),
        "dropped_churn_total": sum(col("dropped_churn")),
    }
    return out


def resilience_summary(events: List[Dict]) -> Dict[str, float]:
    """Aggregate the fault-handling telemetry (DESIGN.md §14):
    quarantine totals, solver fallback stages, checkpoint/resume and
    IO-retry counts.  Empty when no detect/recover action fired."""
    def named(name):
        return [e for e in events if e.get("kind") == "event"
                and e.get("name") == name]

    out: Dict[str, float] = {}
    quar = named("resilience.quarantine")
    if quar:
        out["quarantined_users_total"] = sum(
            _num(e.get("quarantined_users")) or 0.0 for e in quar)
        out["rounds_with_quarantine"] = float(sum(
            1 for e in quar if (_num(e.get("quarantined_users")) or 0.0) > 0))
    fb = named("resilience.fallback")
    if fb:
        out["fallback_rounds"] = float(len(fb))
        out["fallback_cells_total"] = sum(
            _num(e.get("cells")) or 0.0 for e in fb)
        out["channel_rebuilds"] = float(sum(
            1 for e in fb if e.get("rebuilt")))
    ck = named("resilience.checkpoint")
    if ck:
        out["checkpoints_saved"] = float(len(ck))
    rs = named("resilience.resume")
    if rs:
        out["resumes"] = float(len(rs))
        out["last_resume_round"] = _num(rs[-1].get("round")) or 0.0
    io = named("resilience.io_retry")
    if io:
        out["io_retries"] = float(len(io))
    return out


def counter_totals(events: List[Dict]) -> Dict[str, float]:
    """Each counter's total, as flushed once per name when its session
    closed; the totals of several sessions in one trace add up."""
    out: Dict[str, float] = collections.defaultdict(float)
    for e in events:
        if e.get("kind") == "counter":
            out[e["name"]] += float(e.get("total", 0.0))
    return dict(sorted(out.items()))


def retrace_summary(events: List[Dict]) -> List[Dict[str, Any]]:
    final: Dict[str, Dict[str, Any]] = {}
    for e in events:
        if e.get("kind") == "retrace":
            final[e["name"]] = {"name": e["name"],
                                "count": int(e.get("count", 0)),
                                "storm": bool(e.get("storm", False))}
    rows = sorted(final.values(), key=lambda r: -r["count"])
    return rows


def profile_captures(events: List[Dict]) -> List[str]:
    return sorted({e.get("dir", "") for e in events
                   if e.get("name") == "profile.captured"})


# ------------------------------------------------------------ rendering
def _fmt(v: Any) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        a = abs(v)
        if a != 0 and (a >= 1e5 or a < 1e-3):
            return f"{v:.3g}"
        return f"{v:.4f}".rstrip("0").rstrip(".") or "0"
    return str(v)


def _table(rows: List[Dict[str, Any]], columns: List[str]) -> str:
    cells = [[_fmt(r.get(c)) for c in columns] for r in rows]
    widths = [max(len(c), *(len(row[i]) for row in cells)) if cells
              else len(c) for i, c in enumerate(columns)]
    lines = ["  ".join(c.rjust(w) for c, w in zip(columns, widths))]
    for row in cells:
        lines.append("  ".join(c.rjust(w) for c, w in zip(row, widths)))
    return "\n".join(lines)


def render_report(events: List[Dict],
                  csv_out: Optional[str] = None) -> str:
    parts: List[str] = []
    rounds = per_round_table(events)
    if rounds:
        cols = ["round"] + [c for c, *_ in _ROUND_FIELDS
                            if any(c in r for r in rounds)]
        parts.append("== per-round ==\n" + _table(rounds, cols))
        if csv_out:
            with open(csv_out, "w", newline="") as f:
                w = csv.DictWriter(f, fieldnames=cols,
                                   extrasaction="ignore")
                w.writeheader()
                w.writerows(rounds)
    phases = phase_breakdown(events)
    if phases:
        total = sum(r["self_s"] for r in phases) or 1.0
        for r in phases:
            r["share"] = f"{100.0 * r['self_s'] / total:.1f}%"
        parts.append("== phase time ==\n" + _table(
            phases, ["phase", "total_s", "self_s", "calls", "mean_s",
                     "share"]))
    wire = wire_summary(events)
    if wire:
        lines = [f"  {k}: {_fmt(v)}" for k, v in wire.items()]
        parts.append("== fused wire traffic ==\n" + "\n".join(lines))
    async_ = async_summary(events)
    if async_:
        lines = [f"  {k}: {_fmt(v)}" for k, v in async_.items()]
        parts.append("== async rounds ==\n" + "\n".join(lines))
    resil = resilience_summary(events)
    if resil:
        lines = [f"  {k}: {_fmt(v)}" for k, v in resil.items()]
        parts.append("== resilience ==\n" + "\n".join(lines))
    counters = counter_totals(events)
    if counters:
        lines = [f"  {k}: {_fmt(v)}" for k, v in counters.items()]
        parts.append("== counters ==\n" + "\n".join(lines))
    retraces = retrace_summary(events)
    if retraces:
        lines = [f"  {r['name']}: {r['count']} trace(s)"
                 + ("  ** RETRACE STORM **" if r["storm"] else "")
                 for r in retraces]
        parts.append("== recompilations ==\n" + "\n".join(lines))
    for d in profile_captures(events):
        parts.append(f"profiler trace captured under: {d}")
    if not parts:
        parts.append("(no obs events)")
    return "\n\n".join(parts)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="render an obs JSONL trace (see repro.obs)")
    ap.add_argument("trace", help="JSONL file written by obs.session")
    ap.add_argument("--csv", default=None, metavar="OUT",
                    help="also write the per-round table as CSV")
    args = ap.parse_args()
    print(render_report(load_events(args.trace), csv_out=args.csv))


if __name__ == "__main__":
    main()
