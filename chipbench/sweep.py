#!/usr/bin/env python3
"""Find a cell's knee: serve its traffic at several fixed rates, one
window each, in one process, and print what each rate sustained.

    python3 chipbench/sweep.py --workload <cell> --rates 400,600,800 --seconds 15

A rate is sustained when SLO attainment is at least 97% and the engine's
queue does not grow from one third of the window to the next. The cell's
traffic file then fixes 4/5 of the highest sustained rate (``PERF.md``).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import manifest, run   # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    run.enable_compile_cache()
    try:
        devices = run.chips(cell.chips, True)
    except run.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for rate in (float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   rate_qps=rate))
        r = run.one_run(c, args.seed, args.seconds, False, devices)
        rd = r["readings"]
        print(json.dumps({"workload": cell.name, "rate_qps": rate,
                          "outcomes": rd["outcomes"],
                          "queue_len_by_third": rd["queue_len_by_third"],
                          "lateness_ms": rd["lateness_ms"],
                          "correct": r["line"]["correct"]},
                         default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
