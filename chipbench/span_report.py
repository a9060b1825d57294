#!/usr/bin/env python3
"""One traced run of a cell, and what the program's own spans say
about its traced slice.

    python3 chipbench/span_report.py --workload <cell> --seed <n> --seconds <s>

Runs ``chipbench/run.py``'s traced run (``--trace 1``) in process and
prints its result line and, as the last line, one JSON object:

- ``spans``: per span name, count and duration quantiles (ms) over the
  slice;
- ``per_call_ms``: the mean executor call split into its spans, the
  rest of ``worker.run`` (``other``) and the thread hop (``router.batch``
  minus ``worker.run`` of the same batch);
- ``per_chip``: each device plane, the replicas whose executor runs on
  it, its idle seconds inside those replicas' ``router.batch`` spans and
  in all, and that device id's ``executor.device_wait`` spans against
  the plane's programs (``waits_on_plane``);
- ``stalls``: device idle gaps over 100 ms, with the innermost program
  span over each gap's middle;
- ``module_names``: the names of the programs that ran in the slice;
- ``span_cost_us``: one span's enter and exit with the profiler on, and
  one span site's cost with it off, timed on this host.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from chipbench import run                                  # noqa: E402
from chipbench import program_spans                        # noqa: E402
from chipbench import trace as trace_mod                   # noqa: E402

STALL_S = 0.100
CALL_PARTS = ("executor.stack", "executor.pad", "executor.launch",
              "executor.device_wait", "executor.fetch")


def quantiles(ds: List[float]) -> Dict[str, float]:
    a = np.asarray(ds) * 1e3
    return {"n": len(a), "mean": float(a.mean()),
            "p50": float(np.percentile(a, 50)),
            "p99": float(np.percentile(a, 99)), "max": float(a.max())}


def per_call(recs) -> Dict[str, float]:
    runs = {s.sid: s for s in recs if s.name == "worker.run"}
    if not runs:
        return {}
    parts = {k: 0.0 for k in CALL_PARTS + ("other", "worker.run")}
    for r in runs.values():
        parts["worker.run"] += r.t1 - r.t0
        parts["other"] += r.t1 - r.t0
    for s in recs:
        if s.parent in runs and s.name in parts:
            parts[s.name] += s.t1 - s.t0
            parts["other"] -= s.t1 - s.t0
    out = {k: v / len(runs) * 1e3 for k, v in parts.items()}
    by_batch = {(r.attrs["replica"], r.attrs["batch"]): r
                for r in runs.values()}
    hops = [(b.t1 - b.t0) - (r.t1 - r.t0) for b in recs
            if b.name == "router.batch"
            for r in [by_batch.get((b.attrs["replica"], b.attrs["batch"]))]
            if r is not None]
    if hops:
        out["thread_hop"] = float(np.mean(hops)) * 1e3
    return out


def stalls(ctx, recs) -> List[Dict[str, Any]]:
    ns = program_spans.to_trace_ns(ctx)
    out = []
    for dev in ctx.trace.devices:
        for a, b in trace_mod.idle_gaps(dev, ctx.trace.window):
            if (b - a) * 1e-9 < STALL_S:
                continue
            mid = (a + b) / 2
            over = [s for s in recs if ns(s.t0) <= mid < ns(s.t1)]
            inner = min(over, key=lambda s: s.t1 - s.t0, default=None)
            out.append({"device": dev.name, "idle_ms": (b - a) * 1e-6,
                        "span": inner.name if inner else "none",
                        "attrs": inner.attrs if inner else {}})
    return out


def waits_on_plane(ctx, recs, dev, k,
                   tol_ns: float = 2e5) -> Dict[str, float]:
    """The ``executor.device_wait`` spans of device id ``k`` against the
    programs of plane ``dev``: the share inside which (give or take
    ``tol_ns``) a program ends, near 1 where plane ``/device:TPU:k`` is
    device id ``k``; and, over those, the mean ms from the wait's start
    to its program's start (inputs and dispatch: the device idle though
    the host already waits) and from the program's end to the wait's."""
    ns = program_spans.to_trace_ns(ctx)
    mods = sorted(dev.modules, key=lambda m: m.end)
    ends = np.asarray([m.end for m in mods])
    pre, post, n = [], [], 0
    for s in recs:
        if s.name != "executor.device_wait" or s.attrs["device"] != k:
            continue
        n += 1
        a, b = ns(s.t0), ns(s.t1)
        i = np.searchsorted(ends, b + tol_ns, "right") - 1
        if i >= 0 and ends[i] >= a - tol_ns:
            pre.append(max(0.0, mods[i].start - a) * 1e-6)
            post.append(max(0.0, b - mods[i].end) * 1e-6)
    if not n:
        return {}
    return {"waits_end_on_plane": len(pre) / n,
            "wait_before_program_ms": float(np.mean(pre)) if pre else None,
            "wait_after_program_ms": float(np.mean(post)) if post else None}


def span_cost_us(trace_dir: Path, n: int = 20000) -> Dict[str, float]:
    """Per span, profiler off and on (the traced run's options)."""
    from repro.serving import spans
    t = time.perf_counter()
    for _ in range(n):
        with spans.span("cost", replica=0, batch=1):
            pass
    off = (time.perf_counter() - t) / n * 1e6
    hooks = run.trace_hooks(trace_dir, 1.0)
    hooks["start"]()
    t = time.perf_counter()
    for _ in range(n):
        with spans.span("cost", replica=0, batch=1):
            pass
    on = (time.perf_counter() - t) / n * 1e6
    hooks["stop"]()
    spans.clear()
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {"on": on, "off": off}


def report(ctx) -> Dict[str, Any]:
    recs = program_spans.in_slice(ctx) or []
    names: Dict[str, List[float]] = {}
    for s in recs:
        names.setdefault(s.name, []).append(s.t1 - s.t0)
    on = program_spans.replica_devices(recs)
    chips = []
    for name, inside, idle in program_spans.idle_under_own_calls(ctx) or []:
        dev = next(d for d in ctx.trace.devices if d.name == name)
        k = program_spans.device_id(dev)
        chips.append({"plane": name, "replicas": sorted(
            r for r, d in on.items() if d == k), "idle_in_own_calls_s":
            inside, "idle_s": idle, "window_s": ctx.trace.window_s,
            **waits_on_plane(ctx, recs, dev, k)})
    return {"spans": {k: quantiles(v) for k, v in sorted(names.items())},
            "per_call_ms": per_call(recs), "per_chip": chips,
            "stalls": stalls(ctx, recs),
            "module_names": sorted({m.name.split("(")[0]
                                    for m in ctx.modules})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seen = []

    class Seen(run.MetricContext):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            seen.append(report(self))

    run.MetricContext = Seen
    cell = run.manifest.load_cell(args.workload)
    run.enable_compile_cache()
    try:
        devices = run.chips(cell.chips, True)
    except run.NoChip as e:
        print(f"span_report: {e}", file=sys.stderr)
        return 3
    r = run.one_run(cell, args.seed, args.seconds, True, devices)
    run.log(json.dumps(r["line"], default=float))
    out = dict(seen[0], span_cost_us=span_cost_us(
        run.ROOT / ".chipbench_trace" / "span_cost"))
    run.log(json.dumps(out, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
