"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell comes from ``BENCHMARK.json`` and the files it names
(``spec.py``). A run builds the program's simulation from the seed, warms
every program its rounds use (set-up), times back-to-back federated rounds
for ``--seconds``, then compares its first rounds with the plain reference
(``compare.py``: free-running and teacher-forced).
``--trace 1`` instead traces a window of at most
``harness.TRACE_WINDOW_S`` seconds and reports the per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted`` (rounds timed), ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``, each compared number
beside its limit; the checks are also the last lines of standard error. A
machine without the TPU chips the cell asks for gets a non-zero exit and no
result line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_chips(n: int):
    """The local devices, or SystemExit when they are not ``n`` TPU chips
    or more."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise SystemExit(
            f"chipbench: the cell needs {n} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)")
    return devices


def main(argv=None, *, bench_file: Path = CHECKOUT / "BENCHMARK.json",
         root: Path | None = None, chip_check=require_chips) -> dict:
    """Run a cell and print its result; returns the result. ``bench_file``,
    ``root`` (the directory of traffic and limits files) and ``chip_check``
    let the tests rehearse a small cell on the CPU."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import accounting, compare, harness, spec

    cell = spec.resolve(args.workload, bench_file, root or spec.HERE)
    devices = chip_check(cell.chips)
    cache_dir = harness.configure_jax(cell, CHECKOUT)
    log(f"chipbench: {cell.name} seed {args.seed} (program seed "
        f"{harness.program_seed(args.seed)}) on {len(devices)} x "
        f"{devices[0].device_kind}; compile cache {cache_dir}")
    tdir = tempfile.mkdtemp(prefix="chipbench_trace_") if args.trace else None
    seconds = (min(args.seconds, harness.TRACE_WINDOW_S) if args.trace
               else args.seconds)
    try:
        run = harness.run_program(cell, args.seed, seconds, T_START, tdir)
        if args.trace:
            per_layer = harness.per_layer(cell, run, devices[0].device_kind)
    finally:
        if tdir is not None:
            shutil.rmtree(tdir, ignore_errors=True)
    log(f"compile events, set-up: {run.compiles_setup}")
    log(f"compile events, window: {run.compiles_window}")
    log(f"python gc in the window: {run.gc_window[0]} collections, "
        f"{run.gc_window[1]:.3f} s")
    e2e = harness.end_to_end(run)
    hist = {k: run.window_dropped.count(k) for k in sorted(
        set(run.window_dropped))}
    log(f"window: {len(run.round_times)} rounds in {run.window_s:.3f} s "
        f"after {run.warm_rounds} set-up rounds; rounds by dropped count "
        f"{hist}; round_s {e2e['round_s']:.6f} round_p90_s "
        f"{e2e['round_p90_s']:.6f} setup_s {e2e['setup_s']:.3f}; "
        f"upload_vs_dense {accounting.upload_vs_dense(run.window_records)}")
    log("round times (s): " + " ".join(f"{t:.4f}" for t in run.round_times))
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": None, "attempted": len(run.round_times), "failed": 0}
    if args.trace:
        metrics, busy_s, window_s, breakdown = per_layer
        device.update(busy_s=busy_s, window_s=window_s)
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    t_check = time.perf_counter()
    nums = harness.check(cell, args.seed, run)
    ok = compare.verdict(nums, cell.limits)
    log(f"reference check took {time.perf_counter() - t_check:.3f} s")
    out.update(correct=ok, metrics=metrics, device=device)
    if args.trace:
        out["breakdown"] = breakdown
    names = compare.compared(cell.limits)
    for k in compare.NUMBERS:
        if k not in names:
            log(f"not compared in this cell: {k} {nums[k]!r}")
    # a NaN reading (it fails) is written as a string: JSON has no NaN
    out["checks"] = {k: {"value": nums[k] if math.isfinite(nums[k])
                         else str(nums[k]), "limit": cell.limits[k]}
                     for k in names}
    for k in names:
        log(f"check {k}: {nums[k]!r} (limit {cell.limits[k]!r}) "
            f"{'ok' if nums[k] <= cell.limits[k] else 'FAILED'}")
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
