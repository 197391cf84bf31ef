"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Quick mode (default)
uses reduced K/T so the whole harness finishes on this CPU container;
pass --full for paper-scale settings.  The roofline/dry-run tables are
produced by launch/roofline.py from the dry-run sweep, not here.

``--json out.json`` additionally writes structured records
``{name, us_per_call, derived, status}`` — one per CSV row, plus one
``status: "error"`` record (with the traceback) per bench group that
crashed, so the CI regression gate (benchmarks/check_regression.py)
can distinguish "slow" from "crashed".  A failed bench group prints a
``name,nan,ERROR`` row and makes the harness exit 1, with or without
``--json`` (the JSON is still written first).  The JSON's ``meta``
names the device the numbers came from.

The persistent compilation cache is turned on before the first compile
(``repro.compile_cache``).  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def _env_meta() -> dict:
    """Environment stamp for emitted JSON: which jax and device produced
    the numbers (regression diffs across environments are expected, and
    the gate needs to see that in the artifact, not guess)."""
    import platform

    import jax

    dev = jax.devices()[0]
    return {"python": platform.python_version(),
            "jax_version": jax.__version__, "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": jax.device_count(),
            "x64": bool(jax.config.jax_enable_x64)}


def _parse_row(line: str) -> dict:
    name, us, derived = line.split(",", 2)
    try:
        us_val = float(us)
    except ValueError:
        us_val = None
    return {"name": name, "us_per_call": us_val, "derived": derived,
            "status": "ok"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: fig2,table2,table3,overhead,"
                         "sim_engine,phy_solvers,mc_replicates,"
                         "quant_kernels,async_rounds,cohort_scale,"
                         "layer_budget,resilience")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write structured per-bench records to OUT")
    args = ap.parse_args()
    quick = not args.full

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import async_rounds, cohort_scale, fig2_convergence, \
        layer_budget, mc_replicates, overhead, phy_solvers, \
        quant_kernels, resilience, sim_engine, table2_accuracy, \
        table3_latency
    benches = {
        "overhead": lambda: overhead.run(quick=quick),
        "fig2": lambda: fig2_convergence.run(T=40 if quick else 100,
                                             quick=quick),
        "table2": lambda: table2_accuracy.run(quick=quick),
        "table3": lambda: table3_latency.run(quick=quick),
        "sim_engine": lambda: sim_engine.run(quick=quick),
        "phy_solvers": lambda: phy_solvers.run(quick=quick),
        "mc_replicates": lambda: mc_replicates.run(quick=quick),
        "quant_kernels": lambda: quant_kernels.run(quick=quick),
        "async_rounds": lambda: async_rounds.run(quick=quick),
        "cohort_scale": lambda: cohort_scale.run(quick=quick),
        "layer_budget": lambda: layer_budget.run(quick=quick),
        "resilience": lambda: resilience.run(quick=quick),
    }
    selected = list(benches) if args.only is None \
        else args.only.split(",")

    print("name,us_per_call,derived")
    records = []
    failed = False
    for name in selected:
        t0 = time.time()
        # consume row-by-row so a generator bench crashing mid-group
        # still surfaces (and records) every row it produced first
        ok = True
        try:
            for line in benches[name]():
                print(line, flush=True)
                records.append(_parse_row(line))
        except Exception:
            ok = False
            failed = True
            traceback.print_exc()
            print(f"{name},nan,ERROR", flush=True)
            records.append({"name": name, "us_per_call": None,
                            "derived": "ERROR", "status": "error",
                            "error": traceback.format_exc()[-2000:]})
        if ok:
            records.append({"name": f"{name}/_wall", "us_per_call":
                            (time.time() - t0) * 1e6, "derived":
                            "group_wall_time", "status": "ok"})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benches": records,
                       "meta": {"quick": quick, "groups": selected,
                                **_env_meta()}}, f, indent=2)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
