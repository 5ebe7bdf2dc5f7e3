"""Readings that the limits of ``correct`` are set from (not run by the
benchmark's own runs).

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 [--out readings.json]

For every seed of ``--seeds`` the program runs the cell's set-up and one
window round through the benchmark's own path, and its compared rounds are
held against the plain reference: the lower readings. For every seed of
``--control-seeds`` (each also in ``--seeds``, whose inputs it reuses) the
control, the reference computed in bfloat16, and each planted fault of the
reference (``reference.FAULTS``) are put in the program's place and held
against the float32 reference, free-running and teacher-forced on their own
state: the upper readings. A state left unchanged reads 1 on ``change_n``
and ``agg_update`` by construction and needs no run. All of it runs in this
one process, at the cell's own size.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from chipbench import compare, harness, reference, spec

    cell = spec.resolve(args.workload)
    harness.configure_jax(cell, CHECKOUT)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = [int(s) for s in args.control_seeds.split(",") if s]
    if not set(control) <= set(seeds):
        ap.error("every --control-seeds seed must also be in --seeds")
    readings = {"program": {}, "control": {}, "faults": {}}
    kept = {}
    for seed in seeds:
        t0 = time.perf_counter()
        run = harness.run_program(cell, seed, 0.0, t0)
        nums = harness.check(cell, seed, run)
        readings["program"][seed] = nums
        print(f"seed {seed}: {nums} compared rounds "
              f"{len(run.compare_rounds)} dropped "
              f"{[rd['dropped'] for rd in run.compare_rounds]} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
        if seed in control:
            run.prog = None
            kept[seed] = run
    for seed in control:
        run = kept.pop(seed)
        nums = harness.check(cell, seed, run, dtype="bfloat16")
        readings["control"][seed] = nums
        print(f"control seed {seed}: {nums}", flush=True)
        for fault in reference.FAULTS:
            nums = harness.check(cell, seed, run, fault=fault)
            readings["faults"].setdefault(fault, {})[seed] = nums
            print(f"fault {fault} seed {seed}: {nums}", flush=True)
    summary = {k: {"lower": max(r[k] for r in readings["program"].values()),
                   "control": min((r[k] for r in readings["control"]
                                   .values()), default=None)}
               for k in compare.NUMBERS}
    for k in compare.NUMBERS:
        for fault, by_seed in readings["faults"].items():
            summary[k][fault] = min(r[k] for r in by_seed.values())
    readings["summary"] = summary
    print(json.dumps(summary, indent=1), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(readings, f, indent=1)
    return readings


if __name__ == "__main__":
    main()
