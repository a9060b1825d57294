#!/usr/bin/env python3
"""Read the correctness numbers of the program and of its lower-precision
controls over many seeds, in one process: for each seed, a window of the
cell at its own load, then the reference over the kept rows, the
program's numbers against it and each control's (the reference with its
weights rounded to int8 or fp8) on the same prompts.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

Prints one JSON line per seed; the limits in the configuration files were
set from such readings (``PERF.md``). Needs the cell's chips.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench import manifest, run   # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)
    cell = manifest.load_cell(args.workload)
    run.enable_compile_cache()
    try:
        devices = run.chips(cell.chips, True)
    except run.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        r = run.one_run(cell, seed, args.seconds, False, devices,
                        controls=tuple(args.controls.split(",")))
        rd = r["readings"]
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "compare": rd["compare"],
                          "served_subnets": rd["served_subnets"],
                          "outcomes": rd["outcomes"],
                          "reference_s": rd["reference_s"]},
                         default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
