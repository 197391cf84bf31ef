#!/usr/bin/env python3
"""Readings from which the limits of ``correct`` are set.

    python bench/calibrate.py --workload <name> --seeds 1 2 3 ... \\
        [--control-seeds 3] [--out file.jsonl]

In one process, for each seed: the cell's engine is built and driven
through its first checked rounds exactly as a benchmark run's set-up
does, the plain float32 reference follows them (each round from the
program's model before it), and the numbers of ``check.compare`` are
read for the program.  On the first
``--control-seeds`` seeds the same numbers are read for the control
(the reference at the precision ``high``, put in the program's place)
and for each fault the cell can have, planted in the reference put in
the program's place.  One JSON object per reading goes to standard output (and to
``--out``).  Needs the cell's chips; it is not part of a benchmark run.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))


def readings(workload: str, seeds, control_seeds: int = 3,
             spec=None, require_chip: bool = True):
    """Yield one record per reading (see the module docstring)."""
    import harness

    cell, cfg, traffic, _, _ = harness.load_cell(workload, spec)
    if require_chip:
        harness.require_devices(cell["chips"])
    sys.path.insert(0, str(harness.ROOT / "src"))
    harness.configure_jax(cfg)
    import check
    import gen
    import reference

    model = gen.model(cfg)
    ref = reference.Reference(cfg, model, "highest")
    ctrl = reference.Reference(cfg, model, "high")
    faults = ["half_users"]
    if cfg.get("M") is not None:
        faults.append("full_power")
    shards_over = 1
    if traffic.get("mesh"):
        shards_over = traffic["mesh"][0]
        faults.append("no_exchange")
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        inputs = gen.make_inputs(cfg, seed)
        eng = harness.build_engine(cfg, traffic, inputs)
        _, got = harness.checked_rounds(eng)
        del eng
        sels = gen.minibatch_indices(inputs.shards, cfg["L"],
                                     cfg["batch_size"], inputs.mb_seed,
                                     harness.CHECKED_ROUNDS)
        rows = [("program", got)]
        if i < control_seeds:
            rows.append(("control_high", ctrl.run(inputs, sels)))
            rows.extend((f"fault_{f}", ref.run(inputs, sels, fault=f,
                                               shards_over=shards_over))
                        for f in faults)
        rows = [(kind, check.against_reference(ref, inputs, sels, g,
                                               cfg["b"]))
                for kind, g in rows]
        for kind, numbers in rows:
            yield {"workload": workload, "seed": seed, "kind": kind,
                   **numbers}
        print(f"# seed {seed}: {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sink = open(args.out, "a") if args.out else None
    try:
        for rec in readings(args.workload, args.seeds,
                            args.control_seeds):
            line = json.dumps(rec)
            print(line, flush=True)
            if sink:
                sink.write(line + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()


if __name__ == "__main__":
    main()
