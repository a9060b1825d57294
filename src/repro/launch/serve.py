"""Serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch ofa_resnet \
        --policy slackfit --trace bursty --rate 7000 --cv2 8 --duration 10

Drives the production serving stack at full scale through the
discrete-event engine (the real asyncio runtime is demonstrated by
examples/serve_bursty.py on this host's actual devices). With
``--replicas N`` (N > 1) the same trace is served by the multi-replica
cluster plane — N engines behind the coordinator, placement chosen by
``--placement``. ``--autoscale`` adds the reactive replica autoscaler
(spawn/decommission from load signals, ``--min-replicas`` /
``--max-replicas`` bounds, ``--scale-policy`` signal) and reports
replica-seconds, the scale-event log, and goodput per replica-second.

Predictive serving (serving/forecast.py): ``--scale-policy predictive``
spawns ahead of the arrival forecast crossing capacity (reactive
fallback without signal); ``--predictive-joins`` opens forecast-led
join windows even at saturation; ``--forecast-window`` sets the shared
estimator window. The forecast snapshot rides the output JSON.

Multi-host serving plane (serving/ipc.py): ``--transport proc
--procs K`` serves the trace LIVE through K replica worker processes —
one OS process per replica group behind the IPC front door, placement
still owned by the in-process coordinator. ``--listen HOST:PORT`` (port
0 picks a free one) moves the transport onto TCP with an HMAC-token
handshake (``--token``, auto-generated when unset), the same front door
a REMOTE replica dials: run ``--connect HOST:PORT --token T`` on
another machine to serve as a replica child for that coordinator.
``--autoscale`` runs the live replica autoscaler over the proc
transport (spawn = fork/connect a child priced at cold start,
decommission = drain frame through the coordinator's surrender path),
and ``--execute real`` makes each child build its own AOT-warmed
``SubnetExecutor`` so completions carry real subnet logits. Echo
workers (optionally ``--work-ms`` of real CPU spin per batch) remain
the default stand-in; arrivals are capped at ``--queries``. Still
incompatible with ``--profile measured``, ``--faults`` and
``--replica-deaths`` (fault scripts stay inproc/simulated). Children
that run JAX are CPU-only (``JAX_PLATFORMS=cpu``; ``ipc.replica_env``):
a chip belongs to one process. So ``--execute real`` children build the
config's ``reduced()`` twin, and the coordinator profiles that twin.

Compiled execution path (serving/executor.py): ``--execute real`` runs
actual subnet forward passes on this host's devices — the config at its
published widths and dtype behind the AOT-warmed, shape-bucketed
``SubnetExecutor``, served by the asyncio Router/ClusterRouter with the
SAME engine/policy/residency stack as the simulator, from a profile
measured through the warmed executor (``--profile measured``, its
default; usable with ``--execute sim`` too). With ``--replicas N`` each
replica gets its own executor on ``jax.local_devices()[r % n]``: one
process drives every local chip. ``--reduced`` swaps in the config's
``reduced()`` twin (CPU tests and examples ask for it; nothing chooses
it from the platform). Both need a token-frontend LM arch, e.g.
``--arch qwen2-1.5b``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro import compat
from repro.configs import get_config
from repro.serving import cluster, policies, profiler, simulator, traces
from repro.serving.autoscaler import SCALINGS, AutoscaleConfig
from repro.serving.forecast import ForecastConfig


def _host_latency(executor, subnet_idx: int, seq_len: int,
                  iters: int = 3) -> float:
    """Best-of-k wall-clock for a warmed B=1 prefill on this host."""
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        executor.run_prefill(subnet_idx, np.ones((1, seq_len), np.int32))
        best = min(best, time.perf_counter() - t0)
    return best


@dataclass
class RealRun:
    """One ``--execute real`` run: the launcher's JSON (``out``) and the
    objects behind it, for in-process callers that check the results
    (chip_smoke.py)."""

    out: Dict[str, Any]
    executors: List[Any]                 # per replica (shared per device)
    profile: "profiler.LatencyProfile"   # what the engine scheduled from
    raw_profile: "profiler.LatencyProfile"  # the measurement, no cummax
    payloads: np.ndarray                 # (queries, seq_len) prompt tokens
    results: List[Tuple[Any, float]]     # (logits row | None, acc) each


def _device_json(dev) -> Dict[str, Any]:
    return {"platform": dev.platform, "kind": dev.device_kind,
            "id": dev.id}


def _measure(args, executors) -> Tuple["profiler.LatencyProfile",
                                       "profiler.LatencyProfile", list]:
    """AOT-warm every distinct executor's lattice, then measure the raw
    per-(subnet, batch) table through the first; the engine schedules
    from its monotonized copy."""
    batches = (1, 2, 4, 8)
    distinct = list({id(ex): ex for ex in executors}.values())
    warm = [ex.warmup(batches=batches, seqs=(args.seq_len,))
            for ex in distinct]
    raw = executors[0].measured_profile(batches=batches,
                                        seq_len=args.seq_len,
                                        monotonize=False)
    return profiler.monotonized(raw), raw, warm


def _policy(args, prof):
    if args.policy == "clipper":
        idx = args.clipper_idx if args.clipper_idx >= 0 else prof.n_pareto - 1
        return policies.ClipperFixed(idx)
    return policies.ALL_POLICIES[args.policy]()


def _trace(args, rate: float, duration: float) -> np.ndarray:
    if args.trace == "bursty":
        return traces.bursty_trace(rate * 0.2, rate * 0.8, args.cv2,
                                   duration, args.seed)
    if args.trace == "time_varying":
        return traces.time_varying_trace(rate * 0.4, rate, args.tau,
                                         args.cv2, duration, args.seed)
    return traces.maf_like_trace(rate, duration, seed=args.seed)


def config_of(args):
    """``--arch``'s config at its published widths, or its reduced twin
    when ``--reduced`` asks for it. Proc children that execute run on the
    CPU and build the reduced twin (``replica_proc.make_real_workers``),
    so ``--transport proc --execute real`` schedules its Pareto set."""
    cfg = get_config(args.arch)
    if args.reduced or (args.transport == "proc" and args.execute == "real"):
        return cfg.reduced()
    return cfg


def run_real(args) -> RealRun:
    """``--execute real``: build one executor per replica from
    ``--seed``, AOT-warm, measure the profile, pace the trace to the
    measured latencies, and serve it — all in this process."""
    from repro.serving.executor import build_replica_executors

    cfg = config_of(args)
    executors = build_replica_executors(cfg, args.replicas, seed=args.seed)
    prof, raw, warm = _measure(args, executors)
    pol = _policy(args, prof)
    # host-safe pacing: derive rate/SLO from latencies observed here
    # (examples/serve_bursty.py sizing: SLO ~= 25x the max-subnet B=1
    # latency, rate leaves 4x headroom on the min-subnet latency)
    rate, slo_ms = args.rate, args.slo_ms
    if rate is None:
        rate = 0.25 / _host_latency(executors[0], 0, args.seq_len)
    if slo_ms is None:
        slo_ms = 25e3 * _host_latency(executors[0],
                                      executors[0].n_subnets - 1,
                                      args.seq_len)
    arr = np.asarray(_trace(args, rate, args.queries / max(rate, 1e-9)),
                     dtype=float)[: args.queries]
    out, payloads, results = _serve_real(args, cfg, prof, pol, executors,
                                         arr, slo_ms / 1e3, rate)
    out.update({
        "warmup": warm,
        "profile_batches": list(prof.batches),
        "profile_ms": (prof.lat * 1e3).tolist(),
        "profile_raw_ms": (raw.lat * 1e3).tolist()})
    return RealRun(out, executors, prof, raw, payloads, results)


def _serve_real(args, cfg, prof, pol, executors, arr, slo_s, rate):
    """Serve ``arr`` with real forward passes through the asyncio
    router(s), replica ``r`` on ``executors[r]``; scheduling stays
    entirely inside the unchanged engine."""
    from repro.serving import runtime

    rng = np.random.default_rng(args.seed)
    payloads = rng.integers(0, cfg.vocab_size,
                            (len(arr), args.seq_len)).astype(np.int32)

    async def go():
        if args.replicas > 1:
            router = runtime.ClusterRouter(
                prof, pol, [ex.make_workers(args.workers)
                            for ex in executors],
                placement=args.placement, placement_seed=args.seed,
                slo=slo_s)
        else:
            router = runtime.Router(prof, pol,
                                    executors[0].make_workers(args.workers),
                                    executor=executors[0])
        await router.start()
        base = compat.compile_events()
        t0 = time.perf_counter()
        futs = []
        for i, t in enumerate(arr):
            now = time.perf_counter() - t0
            if t > now:
                await asyncio.sleep(t - now)
            futs.append(await router.submit(payloads[i], slo_s=slo_s))
        results = await asyncio.gather(*futs)
        await router.drain()
        return router, results, compat.compile_events() - base

    router, results, serve_compiles = asyncio.run(go())
    st = router.stats()
    recs = router.records()
    lats = sorted(r.finish - r.arrival for r in recs
                  if r.finish is not None)

    def pct(q: float):
        return (lats[min(int(q * len(lats)), len(lats) - 1)] * 1e3
                if lats else None)

    out = {"arch": args.arch, "mode": "real",
           "profile": args.profile_mode, "policy": pol.name,
           "d_model": cfg.d_model, "vocab_size": cfg.vocab_size,
           "dtype": cfg.dtype, "reduced": args.reduced,
           "device": _device_json(executors[0].device),
           "replica_devices": [str(ex.device) for ex in executors],
           "queries": len(recs), "replicas": args.replicas,
           "workers": args.workers,
           "rate_qps": round(rate, 1), "slo_ms": round(slo_s * 1e3, 3),
           "slo_attainment": st["slo_attainment"],
           "mean_acc": st["mean_acc"],
           "served": sum(1 for r in recs if not r.dropped),
           "dropped": sum(1 for r in recs if r.dropped),
           "p50_latency_ms": pct(0.50), "p99_latency_ms": pct(0.99),
           "switch_rate": st["switch_rate"],
           "actuation_seconds": st["actuation_seconds"],
           # SubNetAct live: compiles observed while serving; warmed
           # serving should report 0
           "serve_phase_compiles": serve_compiles,
           "executor": [ex.counters() for ex in
                        {id(ex): ex for ex in executors}.values()]}
    if args.replicas > 1:
        served = [r.replica for r in recs if not r.dropped]
        out["per_replica_served"] = {r: served.count(r)
                                     for r in range(args.replicas)}
    return out, payloads, list(results)


def _serve_proc(args, cfg, prof, pol, arr, slo_s, rate, autoscale=None):
    """Serve ``arr`` live through one OS process per replica group
    (serving/ipc.py) — socketpair children, or TCP with ``--listen``.
    The coordinator in THIS process still owns admission/placement/
    lifecycle (autoscaling included); the children own scheduling."""
    from repro.serving import runtime

    async def go():
        router = runtime.ClusterRouter(
            prof, pol, [args.workers] * args.procs,
            placement=args.placement, placement_seed=args.seed,
            transport="proc", work_ms=args.work_ms,
            host_devices=args.host_devices,
            listen=args.listen, token=args.token,
            execute=args.execute if args.execute == "real" else "echo",
            arch=args.arch if args.execute == "real" else None,
            seq_len=args.seq_len, seed=args.seed,
            autoscale=autoscale, slo=slo_s,
            spawn_timeout=300.0 if args.execute == "real" else 60.0,
            engine_cfg=(runtime.EngineConfig(
                continuous_batching=args.continuous_batching
                or args.predictive_joins,
                predictive_joins=args.predictive_joins,
                forecast=(ForecastConfig(window=args.forecast_window)
                          if args.predictive_joins else None))
                if args.continuous_batching or args.predictive_joins
                else None))
        await router.start()
        payloads = None
        if args.execute == "real":
            rng = np.random.default_rng(args.seed)
            payloads = rng.integers(
                0, cfg.vocab_size,
                (len(arr), args.seq_len)).astype(np.int32)
        t0 = time.perf_counter()
        futs = []
        for i, t in enumerate(arr):
            now = time.perf_counter() - t0
            if t > now:
                await asyncio.sleep(t - now)
            p = (payloads[i].tolist() if payloads is not None
                 else [float(i)])
            futs.append(await router.submit(p, slo_s=slo_s))
        await asyncio.gather(*futs)
        await router.drain(60.0)
        return router, time.perf_counter() - t0

    router, makespan = asyncio.run(go())
    st = router.stats()
    recs = router.records()
    out = {"arch": args.arch, "mode": "proc", "execute": args.execute,
           "policy": pol.name,
           "queries": len(recs), "procs": args.procs,
           "workers_per_proc": args.workers, "work_ms": args.work_ms,
           "rate_qps": round(rate, 1), "slo_ms": round(slo_s * 1e3, 3),
           "slo_attainment": st["slo_attainment"],
           "mean_acc": st["mean_acc"],
           "p50_latency_ms": st["p50_latency_s"] * 1e3,
           "p99_latency_ms": st["p99_latency_s"] * 1e3,
           "load_imbalance": st["load_imbalance"],
           "per_replica_served": {r: v["served"]
                                  for r, v in st["replicas"].items()},
           "makespan_s": round(makespan, 4),
           # adopted/remote replicas have no local pid
           "replica_pids": [None if ch.proc is None else ch.proc.pid
                            for ch in router._chans]}
    if args.listen:
        out["listen"] = list(router.listen_addr)
        out["handshake_rejects"] = router.handshake_rejects
    if autoscale is not None:
        router.autoscaler.finalize(router.clock.now())
        out.update({
            "autoscale_policy": autoscale.policy,
            "replicas_total": router.coord.n_replicas,   # ever existed
            "replica_seconds": round(router.autoscaler.replica_seconds(),
                                     4),
            "scale_events": [
                {"t": round(e.t, 4), "kind": e.kind, "rid": e.rid,
                 "committed": e.n_committed, "signal": round(e.signal, 3)}
                for e in router.autoscaler.events]})
    return out


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="ofa_resnet")
    ap.add_argument("--policy", default="slackfit",
                    choices=sorted(policies.ALL_POLICIES) + ["clipper"])
    ap.add_argument("--clipper-idx", type=int, default=-1)
    ap.add_argument("--trace", default="bursty",
                    choices=("bursty", "time_varying", "maf"))
    ap.add_argument("--rate", type=float, default=None,
                    help="mean arrival rate q/s (default 7000; "
                         "--execute real derives a host-safe rate from "
                         "the profile when unset)")
    ap.add_argument("--cv2", type=float, default=4)
    ap.add_argument("--tau", type=float, default=500)
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--execute", default="sim", choices=("sim", "real"),
                    help="sim: discrete-event simulation with profile "
                         "service times (default). real: execute actual "
                         "subnet forward passes on this host's devices "
                         "through the AOT-warmed SubnetExecutor "
                         "(serving/executor.py) behind the asyncio router "
                         "— token-frontend LM archs only; incompatible "
                         "with --autoscale/--faults/--replica-deaths")
    ap.add_argument("--profile", dest="profile_mode", default=None,
                    choices=("analytic", "measured"),
                    help="latency profile the engine schedules from. "
                         "analytic: deterministic hardware-roofline model "
                         "of an RTX 2080 Ti (profiler.build_profile; the "
                         "default for simulation and the proc transport). "
                         "measured: wall-clock per-(subnet, batch-bucket) "
                         "latencies measured on this host's device through "
                         "the warmed executor (token-frontend LM archs "
                         "only; the default, and the only choice, for "
                         "inproc --execute real)")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the config's reduced() twin (d_model 128, "
                         "vocab 512, float32) instead of its published "
                         "widths — for CPU tests and examples")
    ap.add_argument("--queries", type=int, default=64,
                    help="--execute real / --transport proc: number of "
                         "trace arrivals to serve (kept small — every "
                         "query is a real forward pass or a live IPC "
                         "round trip)")
    ap.add_argument("--seq-len", type=int, default=16,
                    help="--execute real / --profile measured: prompt "
                         "tokens per query (right-padded to the "
                         "executor's seq bucket)")
    ap.add_argument("--workers", type=int, default=8,
                    help="workers per replica group")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replica groups; >1 serves through the cluster "
                         "coordinator (one engine per replica)")
    ap.add_argument("--placement", default="round_robin",
                    choices=sorted(cluster.PLACEMENTS),
                    help="replica placement policy (cluster mode only)")
    ap.add_argument("--transport", default="inproc",
                    choices=("inproc", "proc"),
                    help="proc: serve LIVE through one OS process per "
                         "replica group over the IPC front door "
                         "(serving/ipc.py); inproc keeps the simulated/"
                         "in-process planes (default)")
    ap.add_argument("--procs", type=int, default=2,
                    help="--transport proc: replica worker processes "
                         "(each gets --workers workers)")
    ap.add_argument("--work-ms", type=float, default=0.0,
                    help="--transport proc: real CPU busy-spin per batch "
                         "in the worker processes (0 = pure echo)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="--transport proc: pin N fake XLA host devices "
                         "per replica process via XLA_FLAGS before the "
                         "child's first jax import (0 = no jax import)")
    ap.add_argument("--listen", default=None, metavar="HOST:PORT",
                    help="--transport proc: open a TCP listener and run "
                         "children through it (port 0 picks a free one); "
                         "remote replicas dial the same address with "
                         "--connect and pass the HMAC handshake")
    ap.add_argument("--token", default=None,
                    help="shared HMAC handshake token for --listen/"
                         "--connect (listener auto-generates one when "
                         "unset; --connect falls back to $REPRO_IPC_TOKEN)")
    ap.add_argument("--connect", default=None, metavar="HOST:PORT",
                    help="run as a REMOTE replica child: dial a "
                         "coordinator started with --listen and serve "
                         "one replica group for it (every other flag is "
                         "ignored — the coordinator's ReplicaSpec "
                         "configures this process)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="query SLO (default 36.0; --execute real "
                         "derives ~25x the max-subnet B=1 latency from "
                         "the profile when unset, sized for host jitter)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--faults", default="",
                    help="comma list wid:t, e.g. 7:12,6:24 "
                         "(cluster mode: rid.wid:t)")
    ap.add_argument("--replica-deaths", default="",
                    help="comma list rid:t — whole replica groups dying "
                         "(cluster mode only)")
    ap.add_argument("--continuous-batching", action="store_true",
                    help="keep forming batches open to in-flight joins "
                         "within the policy's latency budget (paper §5)")
    ap.add_argument("--predictive-joins", action="store_true",
                    help="forecast-led join windows: hold a forming batch "
                         "even on the last free worker when the arrival "
                         "forecast says a joinable query lands within "
                         "slack (implies in-flight joins)")
    ap.add_argument("--forecast-window", type=float, default=0.25,
                    help="arrival-forecaster sliding window (s), shared "
                         "by predictive joins and predictive scaling")
    ap.add_argument("--autoscale", action="store_true",
                    help="reactive replica autoscaling: spawn/decommission "
                         "replica groups from load signals (forces cluster "
                         "mode; --replicas is the initial count)")
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=8)
    ap.add_argument("--scale-policy", default="queue_pressure",
                    choices=sorted(k for k in SCALINGS if k != "scripted"),
                    help="autoscaling signal (see serving/autoscaler.py)")
    ap.add_argument("--cold-start", default="0.1",
                    help="spawn -> routable actuation cost (s), or 'auto' "
                         "to derive it from the ActuationModel as a full "
                         "weight-load of the heaviest subnet")
    ap.add_argument("--scale-cooldown", type=float, default=0.5,
                    help="min gap before a scale-down (s)")
    ap.add_argument("--load-on-switch", action="store_true",
                    help="charge a full weight page-in per subnet switch "
                         "(the non-weight-shared Clipper+/INFaaS cost "
                         "model) instead of the SubNetAct control swap — "
                         "the regime where --placement actuation_aware "
                         "and --policy slackfit_sticky earn their keep")
    return ap


def main(argv: Optional[List[str]] = None):
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.connect:
        # remote-replica child mode: this process serves frames for a
        # coordinator elsewhere; its ReplicaSpec arrives over the wire
        from repro.serving.replica_proc import main as replica_main
        replica_main(["--connect", args.connect]
                     + (["--token", args.token] if args.token else []))
        return
    try:
        cold_start = (None if args.cold_start == "auto"
                      else float(args.cold_start))
    except ValueError:
        ap.error(f"--cold-start must be a number or 'auto', "
                 f"got {args.cold_start!r}")

    cfg = config_of(args)
    real_inproc = args.execute == "real" and args.transport != "proc"
    if args.profile_mode is None:
        args.profile_mode = "measured" if real_inproc else "analytic"
    if args.transport == "proc" and (
            args.profile_mode == "measured"
            or args.faults or args.replica_deaths):
        ap.error("--transport proc does not combine with --profile "
                 "measured, --faults or --replica-deaths (fault scripts "
                 "and host-measured profiles stay inproc/simulated)")
    if args.listen and args.transport != "proc":
        ap.error("--listen is the proc transport's TCP front door; "
                 "add --transport proc")
    if args.execute == "real" or args.profile_mode == "measured":
        if cfg.family == "conv" or cfg.frontend != "token":
            ap.error(f"--execute real / --profile measured execute the "
                     f"LM path and need a token-frontend arch (try "
                     f"--arch qwen2-1.5b); {args.arch} is "
                     f"family={cfg.family}, frontend={cfg.frontend}")
    if real_inproc:
        if args.autoscale or args.faults or args.replica_deaths:
            ap.error("--execute real does not support --autoscale/"
                     "--faults/--replica-deaths inproc; --transport "
                     "proc runs autoscaled real execution, and the "
                     "simulator covers fault studies")
        if args.profile_mode != "measured":
            ap.error("inproc --execute real serves from the profile "
                     "measured through its executors; the analytic "
                     "profile models another device")
        compat.enable_compile_cache()
        print(json.dumps(run_real(args).out, indent=1))
        return

    if args.profile_mode == "measured":
        from repro.serving.executor import build_replica_executors
        compat.enable_compile_cache()
        prof = _measure(args, build_replica_executors(cfg, 1,
                                                      seed=args.seed))[0]
    else:
        prof = profiler.build_profile(cfg)
    pol = _policy(args, prof)

    rate = args.rate if args.rate is not None else 7000.0
    slo_ms = args.slo_ms if args.slo_ms is not None else 36.0
    duration = args.duration
    if args.execute == "real":
        # proc + real: the children execute on their CPUs; the parent
        # has no executor to time — pace for reduced-size CPU forwards
        # served over IPC
        if args.rate is None:
            rate = 20.0
        if args.slo_ms is None:
            slo_ms = 4000.0
        duration = args.queries / max(rate, 1e-9)
    arr = _trace(args, rate, duration)

    if args.transport == "proc":
        arr = np.asarray(arr, dtype=float)[: args.queries]
        autoscale = None
        if args.autoscale:
            if not (args.min_replicas <= args.procs
                    <= args.max_replicas):
                ap.error(f"--procs {args.procs} must start within "
                         f"[--min-replicas {args.min_replicas}, "
                         f"--max-replicas {args.max_replicas}]")
            autoscale = AutoscaleConfig(
                min_replicas=args.min_replicas,
                max_replicas=args.max_replicas, policy=args.scale_policy,
                cold_start=cold_start, cooldown=args.scale_cooldown,
                **({"rate_window": args.forecast_window}
                   if args.scale_policy == "predictive" else {}))
        out = _serve_proc(args, cfg, prof, pol, arr, slo_ms / 1e3, rate,
                          autoscale)
        print(json.dumps(out, indent=1))
        return

    if args.replicas > 1 or args.autoscale:
        faults = {}
        if args.faults:
            for part in args.faults.split(","):
                rw, t = part.split(":")
                rid, wid = rw.split(".")
                faults[(int(rid), int(wid))] = float(t)
        deaths = {}
        if args.replica_deaths:
            for part in args.replica_deaths.split(","):
                rid, t = part.split(":")
                deaths[int(rid)] = float(t)
        autoscale = None
        if args.autoscale:
            if not (args.min_replicas <= args.replicas
                    <= args.max_replicas):
                ap.error(f"--replicas {args.replicas} must start within "
                         f"[--min-replicas {args.min_replicas}, "
                         f"--max-replicas {args.max_replicas}]")
            autoscale = AutoscaleConfig(
                min_replicas=args.min_replicas,
                max_replicas=args.max_replicas, policy=args.scale_policy,
                cold_start=cold_start, cooldown=args.scale_cooldown,
                # the shared estimator window tunes the FORECAST-led
                # policy only (its reactive fallback stays comparable);
                # a plain reactive run keeps its own default window
                **({"rate_window": args.forecast_window}
                   if args.scale_policy == "predictive" else {}))
        # one shared ForecastConfig for the engines' predictive join
        # windows and (via the coordinator_forecast rule) the
        # coordinator-level forecaster behind --scale-policy predictive
        forecast = (ForecastConfig(window=args.forecast_window)
                    if args.predictive_joins
                    or (autoscale and autoscale.policy == "predictive")
                    else None)
        ccfg = simulator.ClusterConfig(
            n_replicas=args.replicas, workers_per_replica=args.workers,
            placement=args.placement, placement_seed=args.seed,
            slo=slo_ms / 1e3, fault_times=faults, replica_deaths=deaths,
            load_on_switch=args.load_on_switch,
            continuous_batching=args.continuous_batching,
            predictive_joins=args.predictive_joins, forecast=forecast,
            autoscale=autoscale)
        res = simulator.simulate_cluster(arr, prof, pol, ccfg)
        st = res.stats()
        extra = {"replicas": args.replicas, "placement": args.placement,
                 "load_imbalance": st["load_imbalance"],
                 "per_replica_served": {r: v["served"]
                                        for r, v in st["replicas"].items()}}
        if res.forecast is not None:
            extra["forecast"] = {k: None if v is None else round(v, 4)
                                 for k, v in res.forecast.items()}
            extra["predictive_windows"] = res.n_predictive_windows
        if args.autoscale:
            extra.update({
                "autoscale_policy": args.scale_policy,
                "replicas_total": res.n_replicas,   # ever existed
                "replica_seconds": res.replica_seconds,
                "goodput_per_replica_second":
                    st.get("goodput_per_replica_second", 0.0),
                "scale_events": [
                    {"t": round(e.t, 4), "kind": e.kind, "rid": e.rid,
                     "committed": e.n_committed, "signal": round(e.signal, 3)}
                    for e in res.scale_events]})
    else:
        faults = {}
        if args.faults:
            for part in args.faults.split(","):
                wid, t = part.split(":")
                faults[int(wid)] = float(t)
        scfg = simulator.SimConfig(n_workers=args.workers,
                                   slo=slo_ms / 1e3,
                                   load_on_switch=args.load_on_switch,
                                   fault_times=faults, seed=args.seed,
                                   continuous_batching=args.continuous_batching,
                                   predictive_joins=args.predictive_joins,
                                   forecast=(ForecastConfig(
                                       window=args.forecast_window)
                                       if args.predictive_joins else None))
        res = simulator.simulate(arr, prof, pol, scfg)
        extra = ({"predictive_windows": res.n_predictive_windows}
                 if args.predictive_joins else {})
    st = res.stats()
    out = {"arch": args.arch, "policy": pol.name, "queries": len(arr),
           "continuous_batching": args.continuous_batching,
           "slo_attainment": res.slo_attainment, "mean_acc": res.mean_acc,
           "p50_latency_ms": res.latency_p50 * 1e3,
           "p99_latency_ms": res.latency_p99 * 1e3,
           "join_rate": res.n_joins / max(len(arr), 1),
           "switch_rate": st["switch_rate"],
           "actuation_seconds": st["actuation_seconds"], **extra}
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
