"""Benchmark aggregator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only SUBSTR] [--skip SUBSTR]

``--only`` / ``--skip`` match benchmark names by *substring* (e.g.
``--only cluster`` or ``--only maf fault``), so CI can gate on any
subset; the runner exits nonzero when a claim fails, a benchmark
errors, or ``--only`` matches nothing.

Each bench prints its table, persists results/bench/<name>.json, and
returns a ``claims`` dict of paper-claim booleans; the runner prints
the claim scoreboard at the end (EXPERIMENTS.md consumes it).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (bench_acceleration, bench_actuation,
                        bench_autoscaling, bench_bursty_grid,
                        bench_cluster_scaleout, bench_continuous_batching,
                        bench_executor, bench_hotpath, bench_ilp_oracle,
                        bench_control_space, bench_fault_tolerance,
                        bench_maf, bench_memory, bench_multiproc,
                        bench_pareto, bench_policies, bench_predictive,
                        bench_residency, bench_scalability,
                        bench_throughput_range)
from benchmarks.common import banner, emit_bench_json, save, table

ALL = {
    "actuation": bench_actuation.run,            # Fig 1a / 5b
    "memory": bench_memory.run,                  # Fig 4 / 5a
    "pareto": bench_pareto.run,                  # Fig 2
    "throughput_range": bench_throughput_range.run,   # Fig 5c
    "control_space": bench_control_space.run,    # Fig 13
    "bursty_grid": bench_bursty_grid.run,        # Fig 8
    "continuous_batching": bench_continuous_batching.run,  # §5 in-flight joins
    "cluster_scaleout": bench_cluster_scaleout.run,  # multi-replica plane
    "autoscaling": bench_autoscaling.run,        # reactive replica scaling
    "predictive": bench_predictive.run,          # forecast-led scaling + joins
    "residency": bench_residency.run,            # residency-aware placement
    "acceleration": bench_acceleration.run,      # Fig 9
    "maf": bench_maf.run,                        # Fig 10
    "fault_tolerance": bench_fault_tolerance.run,  # Fig 11a
    "scalability": bench_scalability.run,        # Fig 11b
    "policies": bench_policies.run,              # Fig 11c
    "ilp_oracle": bench_ilp_oracle.run,          # SS4.2.1 Eq. 1
    "hotpath": bench_hotpath.run,                # kernel/engine perf gate
    "executor": bench_executor.run,              # compiled-path serving
    "multiproc": bench_multiproc.run,            # proc transport (ipc.py)
}


def select(only, skip) -> list:
    """Substring-match benchmark names (exact names still match, being
    substrings of themselves)."""
    names = [n for n in ALL
             if only is None or any(s in n for s in only)]
    return [n for n in names if not any(s in n for s in skip)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="run benchmarks whose name contains any SUBSTR")
    ap.add_argument("--skip", nargs="*", default=[],
                    help="skip benchmarks whose name contains any SUBSTR")
    ap.add_argument("--emit-bench-json", action="store_true",
                    help="also write results/bench/BENCH_<name>.json per "
                         "bench: claims + flattened numeric scalars (the "
                         "compact artifact CI uploads)")
    args = ap.parse_args(argv)

    from repro import compat
    compat.enable_compile_cache()
    names = select(args.only, args.skip)
    if not names:
        print(f"--only {args.only} --skip {args.skip} matches no benchmark "
              f"out of: {', '.join(ALL)}")
        return 2
    scoreboard, failures = [], []
    for name in names:
        t0 = time.time()
        try:
            payload = ALL[name]()
            if args.emit_bench_json:
                emit_bench_json(name, payload)
            for claim, ok in (payload.get("claims") or {}).items():
                scoreboard.append([name, claim, "PASS" if ok else "FAIL"])
                if not ok:
                    failures.append((name, claim))
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            scoreboard.append([name, "<ran>", f"ERROR: {e!r}"])
            failures.append((name, repr(e)))
        print(f"[{name}: {time.time()-t0:.1f}s]")

    banner("PAPER-CLAIM SCOREBOARD")
    print(table(["benchmark", "claim", "status"], scoreboard))
    save("scoreboard", {"rows": scoreboard,
                        "failures": [list(f) for f in failures]})
    if failures:
        print(f"\n{len(failures)} claim(s) not reproduced")
        return 1
    print("\nall paper claims reproduced")
    return 0


if __name__ == "__main__":
    sys.exit(main())
