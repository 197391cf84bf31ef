"""Roofline report: three terms per (arch x shape x mesh) from the
dry-run sweep JSONs.

    compute    = per-device HLO FLOPs / peak bf16 FLOP/s
    memory     = per-device HBM bytes / peak HBM bytes/s
    collective = per-device collective bytes / one ICI link's bytes/s

with the peaks of the target chip from :data:`DEVICE_PEAKS`.

All inputs are already per-device (post-SPMD HLO shapes), so no /chips
is applied — dividing the global quantities by chip count gives the
same numbers.  MODEL_FLOPS = 6*N_active*D (train) or 2*N_active*D
(inference) GLOBAL, compared against global HLO flops (per-device x
devices) to expose remat/redundancy waste.

    PYTHONPATH=src python -m repro.launch.roofline --runs runs/dryrun
"""
from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List

# Published per-chip peaks, keyed by jax's ``Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1 600 Gbit/s of inter-chip interconnect over
# four links (50 GB/s each).  A kind missing here is an error, never a
# default: a number divided by another chip's peak means nothing.
DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9},
}
# The dry-run sweep compiles on host devices and projects onto this chip.
DRYRUN_TARGET = "TPU v5 lite"


def device_peaks(kind: str) -> Dict[str, float]:
    """Peaks of one chip of ``kind``; raises KeyError for a kind that
    has no entry in :data:`DEVICE_PEAKS`."""
    if kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"have {sorted(DEVICE_PEAKS)}")
    return DEVICE_PEAKS[kind]


def load_results(runs_dir: str, mesh: str = "single") -> List[Dict]:
    out = []
    for path in sorted(glob.glob(os.path.join(runs_dir, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        if r.get("mesh") == mesh:
            out.append(r)
    return out


def roofline_row(r: Dict) -> Dict:
    if r["status"] != "ok":
        return {"arch": r["arch"], "shape": r["shape"],
                "status": r["status"]}
    peaks = device_peaks(DRYRUN_TARGET)
    t_comp = r["flops"] / peaks["bf16_flops"]
    t_mem = r["hbm_bytes"] / peaks["hbm_bw"]
    t_coll = r["collective_bytes"] / peaks["ici_link_bw"]
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    hlo_global = r["flops"] * r["n_devices"]
    useful = r["model_flops"] / hlo_global if hlo_global else 0.0
    # fraction of the bound the compute term occupies = roofline frac
    return {
        "arch": r["arch"], "shape": r["shape"], "status": "ok",
        "t_compute_s": t_comp, "t_memory_s": t_mem,
        "t_collective_s": t_coll, "dominant": dominant,
        "roofline_fraction": t_comp / bound if bound else 0.0,
        "model_flops": r["model_flops"],
        "useful_flops_ratio": useful,
        "mem_args_gb": r["memory"]["argument_bytes"] / 2 ** 30,
        "mem_temp_gb": r["memory"]["temp_bytes"] / 2 ** 30,
        "collective_breakdown": r.get("collective_breakdown", {}),
    }


def fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:7.2f}s "
    return f"{x * 1e3:7.1f}ms"


def markdown_table(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | compute | memory | collective | dominant | "
           "roofline-frac | useful-FLOPs | args GB | temp GB |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        if r.get("status") != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                         f"{r['status']} | — | — | — | — |")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {fmt_s(r['t_compute_s'])} | "
            f"{fmt_s(r['t_memory_s'])} | {fmt_s(r['t_collective_s'])} | "
            f"**{r['dominant']}** | {r['roofline_fraction']:.2f} | "
            f"{r['useful_flops_ratio']:.2f} | {r['mem_args_gb']:.1f} | "
            f"{r['mem_temp_gb']:.1f} |")
    return hdr + "\n".join(lines)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--runs", default="runs/dryrun")
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--json-out", default="runs/roofline.json")
    args = ap.parse_args()
    rows = [roofline_row(r) for r in load_results(args.runs, args.mesh)]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    print(markdown_table(rows))
    with open(args.json_out, "w") as f:
        json.dump(rows, f, indent=2)
    ok = [r for r in rows if r.get("status") == "ok"]
    if ok:
        worst = min(ok, key=lambda r: r["roofline_fraction"])
        collbound = [r for r in ok if r["dominant"] == "collective"]
        print(f"\nworst roofline fraction: {worst['arch']}/"
              f"{worst['shape']} ({worst['roofline_fraction']:.2f})")
        print(f"collective-bound pairs: "
              f"{[(r['arch'], r['shape']) for r in collbound]}")


if __name__ == "__main__":
    main()
