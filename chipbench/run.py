#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration file
and a traffic file. Set-up makes the weights from the seed on each chip
the cell uses, builds the serving stack as ``launch/serve.py --execute
real`` does and warms exactly the traffic's buckets. The window then
offers the traffic's open-loop schedule for ``--seconds``, and the
served answers are checked against the float32 reference once the window
has closed and the program's state is freed.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces a slice of the window and reports its per-layer metrics. The last
line of standard output is one JSON object; the last lines of standard
error are the numbers compared, each with its limit. Without a TPU with
as many chips as the cell asks for, it exits nonzero and prints no
result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                          # noqa: E402
import gc                                                # noqa: E402
import importlib.util                                    # noqa: E402
import json                                              # noqa: E402
import os                                                # noqa: E402
import shutil                                            # noqa: E402
import sys                                               # noqa: E402
from pathlib import Path                                 # noqa: E402
from typing import Any, Dict, List, Sequence             # noqa: E402

import numpy as np                                       # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src"
# the TPU runtime would log to a fixed path under /tmp; a run writes only
# inside its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (str(ROOT), str(SRC)):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import manifest, outcomes, reference        # noqa: E402
from chipbench import sut, traffic as traffic_mod, window   # noqa: E402
from chipbench.context import MetricContext                 # noqa: E402

SAMPLE_PER_SUBNET = 64      # reservoir rows kept per served subnet
TRACE_START_S = 0.3         # traced slice: from 30% of the window ...
TRACE_S = 3.0               # ... for this long (at most 40% of it)


class NoChip(RuntimeError):
    pass


def log(*a) -> None:
    print(*a, flush=True)


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout, unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is cached."""
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def chips(n: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < n):
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:n]


def load_metric(name: str):
    path = Path(__file__).resolve().parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rel_l2(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def logit_gap(got: np.ndarray, ref: np.ndarray) -> float:
    """How far below the reference's best logit the reference puts the
    token that ``got`` ranks first, over the row's largest |logit|."""
    return float((ref.max() - ref[int(np.argmax(got))]) / np.abs(ref).max())


def compare(cfg: Dict[str, Any], weights, rows, payloads, subnets,
            controls: Sequence[str] = ()) -> Dict[str, Any]:
    """Reference over every kept row, grouped by subnet; the program's
    and each control's widest logit gap and mean relative L2 error."""
    gaps: Dict[str, List[float]] = {"program": []}
    l2: Dict[str, List[float]] = {"program": []}
    for c in controls:
        gaps[c], l2[c] = [], []
    for pi, kept in sorted(rows.items()):
        s = subnets[pi]
        shape = reference.subnet_shape(cfg, s["depth_frac"], s["ffn_frac"],
                                       s["head_frac"])
        if shape["row"] != s["subnet_id"]:
            raise ValueError(f"subnet {pi}: the program's id "
                             f"{s['subnet_id']} is not the reference's "
                             f"{shape['row']}")
        toks = payloads[[qid for qid, _ in kept]]
        # one shape for every subnet: a single compile of the reference
        pad = np.concatenate([toks, np.repeat(toks[:1], SAMPLE_PER_SUBNET
                                              - len(toks), axis=0)])
        want = reference.logits(cfg, weights, shape, pad)[:len(toks)]
        for (_, got), w in zip(kept, want):
            got = np.asarray(got, np.float32)
            gaps["program"].append(logit_gap(got, w))
            l2["program"].append(rel_l2(got, w))
        for c in controls:
            low = reference.logits(cfg, weights, shape, pad,
                                   control=c)[:len(toks)]
            for lo, w in zip(low, want):
                gaps[c].append(logit_gap(lo, w))
                l2[c].append(rel_l2(lo, w))
    return {k: {"logit_gap": max(gaps[k]), "mean_rel_l2": float(np.mean(l2[k])),
                "rows": len(gaps[k])} for k in gaps}


def one_run(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
            devices, controls: Sequence[str] = ()) -> Dict[str, Any]:
    """Set up, serve one window and check it. Returns the result line's
    fields, the compared numbers under ``checks`` and host readings
    under ``readings``."""
    import jax

    cfg, tr = cell.config, cell.traffic
    span = jax.profiler.TraceAnnotation if trace else sut.no_span
    system = sut.build(cfg, tr, seed, devices, span)
    slo_s = float(tr["slo_ms"]) / 1e3
    due = traffic_mod.arrivals(tr, seconds, seed)
    payloads = traffic_mod.prompts(tr, len(due), cfg["vocab_size"], seed)
    accs = {float(a): i for i, a in enumerate(system.executors[0].accs())}
    subnets = {i: system.subnet_of(i) for i in accs.values()}
    res = outcomes.Reservoir(SAMPLE_PER_SUBNET, seed)
    oc = outcomes.Outcomes(due, slo_s, cfg["vocab_size"], accs, res)
    router = system.make_router(slo_s, seed)
    from repro.compat import compile_events
    hooks = None
    trace_dir = ROOT / ".chipbench_trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        hooks = trace_hooks(trace_dir, seconds)
    gc.collect()
    wr = window.run(router, due, payloads, slo_s, seconds,
                    float(tr["grace_s"]), oc, span, compile_events, hooks)
    setup_s = wr.t0 - T_START
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    summary = oc.summary(seconds)
    metrics: Dict[str, Any] = {}
    breakdown = device_extra = None
    if trace:
        from chipbench import trace as trace_mod
        trd = trace_mod.read(str(trace_dir))
        ctx = MetricContext(cfg, tr, wr, trd, system.calls, subnets,
                            peaks_for(devices[0]))
        for m in cell.per_layer:
            v = load_metric(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busy = [trace_mod.busy_s(dv, trd.window) for dv in trd.devices]
        device_extra = {"busy_s": float(np.mean(busy)),
                        "window_s": trd.window_s}
        breakdown = trace_mod.breakdown(trd)
        idle = trace_mod.idle_by_label(trd)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        e2e = dict(summary, setup_s=setup_s)
        for m in cell.end_to_end:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    raw_profile_ms = system.raw_profile.lat * 1e3
    readings = {
        "setup_s": setup_s, "setup": system.setup_notes,
        "outcomes": summary,
        "lateness_ms": {q: float(np.percentile(wr.lateness_s, q) * 1e3)
                        for q in (50, 99, 100)},
        "late_over_20ms": int((wr.lateness_s > 0.020).sum()),
        "profile_raw_ms": np.round(raw_profile_ms, 3).tolist(),
        "rss_before_bytes": wr.rss_before, "rss_after_bytes": wr.rss_after,
        "gc_collections": wr.gc.count, "gc_max_pause_ms": wr.gc.max_pause_ms,
        "window_compiles": wr.compiles, "kept_rows": res.n_rows(),
        "queue_len_by_third": queue_by_third(wr),
        "served_by_replica": wr.served_by_replica,
        "served_subnets": {int(k): v for k, v in sorted(res.seen.items())},
    }
    if trace:
        readings["idle_s_by_label"] = idle
    weights0 = system.weights[0]
    system.free()
    del router, system, wr
    gc.collect()
    t = time.perf_counter()
    cmp = (compare(cfg, weights0, res.rows, payloads, subnets, controls)
           if res.rows else {})
    readings["reference_s"] = time.perf_counter() - t
    readings["compare"] = cmp
    checks = make_checks(cfg, summary, cmp, readings)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if device_extra:
        device.update(device_extra)
    out = {"correct": all(c["ok"] for c in checks.values()),
           "attempted": summary["attempted"], "failed": summary["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in checks.items()}
    return {"line": out, "checks": checks, "readings": readings}


def queue_by_third(wr) -> List[float]:
    """Mean engine queue length at dispatch in each third of the window:
    a queue that grows from third to third is past the knee."""
    out = []
    for k in range(3):
        lo = wr.t0 + k * wr.seconds / 3
        q = [ql for t, _, ql in wr.dispatches
             if lo <= t < lo + wr.seconds / 3]
        out.append(float(np.mean(q)) if q else 0.0)
    return out


def make_checks(cfg, summary, cmp, readings) -> Dict[str, Dict[str, Any]]:
    """Each number compared, its limit and whether it is within it. A
    limit of ``None`` has not been set, and fails."""
    lim = cfg["limits"]
    prog = cmp.get("program", {})
    checks = {
        "logit_gap": (prog.get("logit_gap"), lim["logit_gap"]),
        "mean_rel_l2": (prog.get("mean_rel_l2"), lim["mean_rel_l2"]),
        "bad_rows": (summary["bad_rows"], 0),
        "window_compiles": (readings["window_compiles"], 0),
        "unaccounted": (summary["attempted"] - summary["answered"]
                        - summary["dropped"] - summary["failed"], 0),
    }
    out = {}
    for k, (v, limit) in checks.items():
        ok = v is not None and limit is not None and v <= limit
        out[k] = {"value": v, "limit": limit, "ok": ok}
    return out


def peaks_for(device) -> Dict[str, Any]:
    table = manifest.load_json(Path(__file__).resolve().parent / "peaks.json")
    if device.device_kind not in table:
        raise KeyError(f"no peaks for device kind {device.device_kind!r} "
                       f"in chipbench/peaks.json")
    return table[device.device_kind]


def trace_hooks(trace_dir: Path, seconds: float) -> Dict[str, Any]:
    """The profiler over a slice of the window: device ops and the
    benchmark's spans, without Python function tracing."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    start_s = TRACE_START_S * seconds
    return {"start_s": start_s, "stop_s": start_s + min(TRACE_S, 0.4 * seconds),
            "start": lambda: jax.profiler.start_trace(
                str(trace_dir), profiler_options=opts),
            "stop": jax.profiler.stop_trace}


def main(argv=None, root: Path = ROOT, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chipbench: no program source at {SRC}", file=sys.stderr)
        return 2
    cell = manifest.load_cell(args.workload, root)
    enable_compile_cache()
    try:
        devices = chips(cell.chips, require_chip)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    r = one_run(cell, args.seed, args.seconds, bool(args.trace), devices)
    log("readings " + json.dumps(r["readings"], default=float))
    for k, c in r["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr, flush=True)
    log(json.dumps(r["line"], default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
