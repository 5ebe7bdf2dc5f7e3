"""CLI for the benchmark suites and the CI regression gate.

    python -m repro.bench --quick                 # BENCH_round.json + BENCH_agg.json (cwd)
    python -m repro.bench --quick --out BENCH_ci.json   # one combined document
    python -m repro.bench --gate BENCH_ci.json    # compare vs committed baselines
    python -m repro.bench --csv --only table2,agg # legacy benchmarks/run.py surface

Device forcing: the sharded-round benchmark needs >1 device, so unless
``XLA_FLAGS`` already pins a host device count (or ``--devices 0`` opts out)
the CLI injects ``--xla_force_host_platform_device_count=<N>`` before the
first jax import. The flag only affects the CPU platform — on TPU it is
inert, and the real device topology wins.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_DEVICE_FLAG = "--xla_force_host_platform_device_count"


def _force_devices(n: int) -> None:
    if "jax" in sys.modules:  # too late to change the platform
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if _DEVICE_FLAG in flags:
        return
    os.environ["XLA_FLAGS"] = f"{flags} {_DEVICE_FLAG}={n}".strip()


def main(argv=None) -> int:
    from repro.bench import schema

    ap = argparse.ArgumentParser(
        prog="python -m repro.bench",
        description="Run the perf suites / gate a run against the baselines.")
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized workloads (the committed baselines are "
                         "quick-mode; entry names encode the size)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suites; JSON suites: "
                         "round,agg,cohort,serve; legacy CSV-only: "
                         "table1,table2,fig1,fig3,roofline")
    ap.add_argument("--out", default=None,
                    help="write ONE combined JSON document here instead of "
                         "per-suite BENCH_<suite>.json files in the cwd")
    ap.add_argument("--csv", action="store_true",
                    help="print legacy 'name,us_per_call,derived' CSV rows "
                         "instead of writing JSON")
    ap.add_argument("--devices", type=int, default=8,
                    help="force this many host-platform devices before jax "
                         "init (CPU only; 0 = leave XLA_FLAGS alone)")
    ap.add_argument("--gate", default=None, metavar="CURRENT_JSON",
                    help="gate mode: compare this document against the "
                         "baselines and exit 1 on regression (runs nothing)")
    ap.add_argument("--baseline", action="append", default=None,
                    help="baseline document(s) for --gate (default: "
                         "BENCH_round.json BENCH_agg.json "
                         "BENCH_cohort.json BENCH_serve.json)")
    ap.add_argument("--max-slowdown", type=float,
                    default=schema.DEFAULT_MAX_SLOWDOWN,
                    help="gate threshold (default %(default)s; generous — "
                         "CI runners are noisy)")
    args = ap.parse_args(argv)

    if args.gate is not None:
        current = schema.load_doc(args.gate)
        baselines = []
        for p in (args.baseline or ["BENCH_round.json", "BENCH_agg.json",
                                    "BENCH_cohort.json",
                                    "BENCH_serve.json"]):
            baselines.append(schema.load_doc(p))
        failures, compared = schema.gate_compare(
            current, baselines, max_slowdown=args.max_slowdown)
        if compared == 0:
            print("bench gate: no comparable entries — baseline stale? "
                  "(quick vs full runs never share entry names)",
                  file=sys.stderr)
            return 1
        for line in failures:
            print(f"bench gate REGRESSION: {line}", file=sys.stderr)
        print(f"bench gate: {compared} entries compared, "
              f"{len(failures)} regression(s) at >{args.max_slowdown:.1f}x")
        return 1 if failures else 0

    from repro.bench import JSON_SUITES, LEGACY_SUITES, make_doc, run_suite

    default = list(JSON_SUITES)
    chosen = args.only.split(",") if args.only else default
    if args.csv and args.out:
        print("error: --csv and --out are mutually exclusive (CSV mode "
              "writes no JSON; refresh baselines without --csv)",
              file=sys.stderr)
        return 2
    # every JSON-document run uses the same forced topology so a partial
    # refresh (--only agg) stays comparable with the full one and with CI.
    # CSV mode (the benchmarks/run.py legacy surface, whose default list
    # includes 'agg') keeps the real device count — forcing 8 fake devices
    # there would change the paper-table suites' timings and let the sim
    # engine's shard_clients='auto' silently go multi-device
    if args.devices and not args.csv and any(c in JSON_SUITES
                                             for c in chosen):
        _force_devices(args.devices)
    unknown = [c for c in chosen if c not in {**JSON_SUITES,
                                              **LEGACY_SUITES}]
    if unknown:
        print(f"error: unknown suite(s) {unknown}; know "
              f"{sorted(JSON_SUITES)} + {sorted(LEGACY_SUITES)}",
              file=sys.stderr)
        return 2
    if not args.csv:
        legacy = [c for c in chosen if c in LEGACY_SUITES]
        if legacy:
            print(f"error: {legacy} are CSV-only legacy suites; add --csv "
                  "(benchmarks/run.py does)", file=sys.stderr)
            return 2

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()   # after _force_devices: it imports jax
    results: dict[str, list[dict]] = {}
    failures = 0
    if args.csv:
        print("name,us_per_call,derived")
    for name in chosen:
        try:
            entries = run_suite(name, quick=args.quick)
        except Exception as e:  # keep the suite going; report the failure
            if args.csv:
                print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
                failures += 1
                continue
            raise
        results[name] = entries
        if args.csv:
            for e in entries:
                print(f"{e['name']},{e['us_per_call']:.1f},{e['derived']}",
                      flush=True)
    if args.csv:
        return 1 if failures else 0

    json_suites = {n: es for n, es in results.items() if n in JSON_SUITES}
    if args.out:
        doc = make_doc(None, suites=json_suites, quick=args.quick)
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, args.out)
        print(f"wrote {args.out} "
              f"({sum(len(v) for v in json_suites.values())} entries)")
    else:
        for name, entries in json_suites.items():
            path = JSON_SUITES[name][1]
            doc = make_doc(entries, suite=name, quick=args.quick)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1)
            os.replace(tmp, path)
            print(f"wrote {path} ({len(entries)} entries)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
