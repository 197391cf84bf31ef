"""Assemble EXPERIMENTS.md from run artifacts.

Sections:
  §Paper-repro — benchmark CSVs (fig2/table2/table3) if present;
  §Dry-run     — per (arch x shape x mesh) compile status + memory;
  §Roofline    — three terms, dominant bottleneck, useful-FLOPs ratio;
  §Perf        — the hypothesis->change->measure log (runs/perf_log.json,
                 maintained by the perf iterations).

    PYTHONPATH=src python -m repro.launch.report
"""
from __future__ import annotations

import csv
import glob
import json
import os

from repro.launch.roofline import (DRYRUN_TARGET, device_peaks,
                                   load_results, markdown_table,
                                   roofline_row)

_PEAKS = device_peaks(DRYRUN_TARGET)
HW_NOTE = (f"Hardware basis: {DRYRUN_TARGET} — "
           f"{_PEAKS['bf16_flops'] / 1e12:g} TFLOP/s bf16, "
           f"{_PEAKS['hbm_bw'] / 1e9:g} GB/s HBM, "
           f"{_PEAKS['ici_link_bw'] / 1e9:g} GB/s/link ICI; 256 chips/pod "
           "(16x16), 512 for multi-pod (2x16x16). All per-device "
           "quantities from post-SPMD HLO with trip-count-aware loop "
           "accounting (src/repro/launch/hlo_analysis.py).")


def dryrun_table(runs: str, mesh: str) -> str:
    rows = []
    for r in load_results(runs, mesh):
        if r["status"] == "ok":
            m = r["memory"]
            fits = (m["argument_bytes"] + m["temp_bytes"]) / 2 ** 30
            rows.append(
                f"| {r['arch']} | {r['shape']} | ok | "
                f"{r['compile_s']:.0f}s | {fits:.1f} | "
                f"{r['collective_bytes'] / 2 ** 30:.1f} | "
                f"{r['flops'] / 1e12:.1f} |")
        else:
            reason = r.get("reason", r.get("error", ""))[:60]
            rows.append(f"| {r['arch']} | {r['shape']} | {r['status']} | "
                        f"— | — | — | — | {reason}")
    hdr = ("| arch | shape | status | compile | args+temp GB/dev | "
           "coll GB/dev/step | TFLOP/dev/step |\n"
           "|---|---|---|---|---|---|---|\n")
    return hdr + "\n".join(rows)


def bench_section(bench_dir: str = "runs/bench") -> str:
    parts = []
    for name in ("fig2", "table2", "table3"):
        path = os.path.join(bench_dir, f"{name}.csv")
        if not os.path.exists(path):
            continue
        with open(path) as f:
            rows = list(csv.reader(f))
        parts.append(f"### {name}\n")
        parts.append("| " + " | ".join(rows[0]) + " |")
        parts.append("|" + "---|" * len(rows[0]))
        for row in rows[1:]:
            parts.append("| " + " | ".join(
                x if not _isfloat(x) else f"{float(x):.4g}"
                for x in row) + " |")
        parts.append("")
    return "\n".join(parts) if parts else "_run `python -m benchmarks.run`_"


def _isfloat(x):
    try:
        float(x)
        return True
    except ValueError:
        return False


def perf_section(path: str = "runs/perf_log.json") -> str:
    if not os.path.exists(path):
        return "_no perf iterations recorded yet_"
    with open(path) as f:
        entries = json.load(f)
    out = []
    for e in entries:
        out.append(f"### {e['id']}: {e['title']}\n")
        out.append(f"- **Target**: {e['target']}")
        out.append(f"- **Hypothesis**: {e['hypothesis']}")
        out.append(f"- **Change**: {e['change']}")
        out.append(f"- **Before**: {e['before']}")
        out.append(f"- **After**: {e['after']}")
        out.append(f"- **Verdict**: {e['verdict']}\n")
    return "\n".join(out)


def main(runs="runs/dryrun", out_path="EXPERIMENTS.md"):
    parts = [
        "# EXPERIMENTS",
        "",
        HW_NOTE,
        "",
        "## §Paper-repro (Algorithm 1 simulation layer)",
        "",
        "Datasets are synthetic stand-ins (offline container; "
        "DESIGN.md §2). Validated: >95% overhead reduction (r-bar) at "
        "the paper's operating points; bisection+LP power control "
        "beats Dinkelbach / max-sum-rate on T_max under a latency "
        "budget (table3: 8 vs 1 rounds for every quantizer); "
        "mixed-resolution matches classic-FL accuracy on the 4-class "
        "task of tests/test_fl_loop.py (best acc 0.98 vs 0.73 at "
        "T=30, r-bar 94%). FINDING (accuracy-parity is "
        "spectrum-dependent): on the harder 10-class synthetic tasks "
        "below, mixed-resolution lags classic FL. Diagnostics: the "
        "realized threshold ratio rho = dw_q/||dw||_inf EQUALS lambda "
        "(no Lemma-1 gap — the bound is tight), but once training "
        "sharpens the delta spectrum the high-res fraction collapses "
        "(s ~ 1%%) and the scheme's by-design low-resolution "
        "reconstruction +-lambda/2 * ||dw||_inf exceeds the typical "
        "coordinate magnitude by orders; K=8->24 averaging does not "
        "cancel it (0.12 -> 0.18). The paper's real-CIFAR runs "
        "(K=20, T=100, Table II) report near-parity at s ~ 0.9%%; on "
        "our synthetic spectra the same operating point is unstable — "
        "a reproduction result worth flagging: the method's accuracy "
        "guarantee degrades exactly when its compression is best "
        "(small s), since per-coordinate noise is lambda/2 * "
        "||dw||_inf regardless of s.",
        "",
        bench_section(),
        "",
        "## §Dry-run",
        "",
        "### Single pod (16x16 = 256 chips)",
        "",
        dryrun_table(runs, "single"),
        "",
        "### Multi-pod (2x16x16 = 512 chips)",
        "",
        dryrun_table(runs, "multi"),
        "",
        "## §Roofline (single pod)",
        "",
        markdown_table(sorted(
            (roofline_row(r) for r in load_results(runs, "single")),
            key=lambda r: (r["arch"], r["shape"]))),
        "",
        "roofline-frac = compute-term / max(term): 1.0 means "
        "compute-bound at peak; useful-FLOPs = MODEL_FLOPS (6ND or "
        "2ND) / global HLO FLOPs — the gap is remat recompute, "
        "attention FLOPs (not in 6ND) and sharding redundancy.",
        "",
        "## §Perf",
        "",
        perf_section(),
        "",
    ]
    with open(out_path, "w") as f:
        f.write("\n".join(parts))
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
