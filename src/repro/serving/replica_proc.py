"""Replica worker-process entrypoint for the proc transport
(serving/ipc.py).

Two front doors, one serve loop:

* ``python -m repro.serving.replica_proc --fd N`` — local child over an
  inherited socketpair (trusted fd, no handshake);
* ``python -m repro.serving.replica_proc --connect HOST:PORT
  [--token T]`` — dial a coordinator's TCP listener from ANY host,
  answer its HMAC challenge (token from ``--token`` or the
  ``REPRO_IPC_TOKEN`` env var), and serve once admitted. A ``reject``
  frame (bad token, version mismatch) exits with a diagnostic.

Either way the first serving frame (``config``) carries a
``ReplicaSpec``, from which the child builds one full ``Router`` — its
own ``SchedulingEngine``, policy (rebuilt by registry name), worker
pool, and wall clock — then answers ``submit`` frames with
``completion`` frames as futures resolve, heartbeating in between.

Execution: ``spec.execute == "echo"`` serves echo workers with an
optional CPU spin (the scale-out benchmark's stand-in);
``spec.execute == "real"`` builds a ``SubnetExecutor`` in-child from
``get_config(spec.arch).reduced()`` (serving/executor.py), so
completion frames carry real subnet logits and the engine's batch
latencies are real forward passes. The child runs on the CPU, and the
reduced twin's small vocab keeps each logits row far under the frame
cap.

Device pinning: the parent spawns this process with its own env plus
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` when the spec
asks for fake devices (``ipc.replica_env`` — the HomebrewNLP-Jax/olmax
idiom), so the child's *first* jax import sees the flag and CPU CI gets
an N-device host without TPUs. A child that runs JAX is CPU-only.
Nothing in this module (or the serving stack it imports) touches jax
otherwise — the import happens here, after the flag is set, or not at
all.

Scheduling stays engine-owned: the child's router drops infeasible
queries, forms batches, and re-enqueues on worker faults exactly as
inproc; the parent only learns outcomes through completion frames.
"""
from __future__ import annotations

import argparse
import asyncio
import os
import socket
import time
from typing import Any, List, Optional

from repro.serving.ipc import (PROTOCOL_VERSION, TOKEN_ENV, FrameStream,
                               MalformedFrame, ReplicaSpec, auth_mac,
                               heartbeat_loop, engine_cfg_from_wire,
                               profile_from_wire, to_jsonable, KILL_ALL)
from repro.serving.policies import ALL_POLICIES
from repro.serving.queue import Query
from repro.serving.runtime import Router, WorkerHandle


def make_worker_run(work_ms: float):
    """Echo worker with an optional busy-spin: ``work_ms`` of real CPU
    per batch stands in for model execution, so the scale-out benchmark
    measures genuine multi-core parallelism (an inproc cluster's worker
    threads serialize this spin on the GIL; processes don't)."""

    def run(pareto_idx: int, payloads: List[Any]) -> List[Any]:
        if work_ms > 0:
            t_end = time.perf_counter() + work_ms / 1e3
            while time.perf_counter() < t_end:
                pass
        return list(payloads)

    return run


def make_real_workers(spec: ReplicaSpec) -> List[WorkerHandle]:
    """``execute="real"``: build the in-child ``SubnetExecutor`` from
    the wire spec — the arch's REDUCED config, AOT-warmed on the
    (1,2,4,8) x seq_len bucket lattice — and wrap its subnets as the
    worker pool. The coordinator's wire profile must schedule the same
    Pareto set the executor serves, or accuracies/subnet indices would
    silently disagree across the boundary."""
    from repro.serving.executor import build_serving_executor
    ex = build_serving_executor(spec.arch, seq_len=spec.seq_len,
                                seed=spec.seed, reduced=True)
    profile = profile_from_wire(spec.profile)
    if ex.n_subnets != profile.lat.shape[0]:
        raise ValueError(
            f"executor serves {ex.n_subnets} pareto subnets but the wire "
            f"profile schedules {profile.lat.shape[0]}: build the "
            f"coordinator's profile from the SAME reduced config "
            f"(get_config({spec.arch!r}).reduced())")
    return ex.make_workers(spec.n_workers)


def build_router(spec: ReplicaSpec, rid: int) -> Router:
    profile = profile_from_wire(spec.profile)
    policy = ALL_POLICIES[spec.policy]()
    if spec.execute == "real":
        workers = make_real_workers(spec)
    else:
        workers = [WorkerHandle(wid=i, run=make_worker_run(spec.work_ms))
                   for i in range(spec.n_workers)]
    return Router(profile, policy,
                  workers, engine_cfg=engine_cfg_from_wire(spec.engine_cfg),
                  replica_id=rid)


def _counters(router: Router, hb_errors: Optional[dict] = None) -> dict:
    eng = router.engine
    return {
        "n_joins": int(eng.n_joins),
        "n_switches": int(eng.residency.n_switches),
        "n_launches": int(eng.residency.n_launches),
        "actuation_seconds": float(eng.residency.actuation_seconds),
        "heartbeat_send_errors": int(
            (hb_errors or {}).get("heartbeat_send_errors", 0)),
        "stats": to_jsonable(router.stats()),
    }


async def serve(stream: FrameStream,
                cfg_frame: Optional[dict] = None) -> None:
    """The serve loop, transport-agnostic: ``cfg_frame`` is the already-
    received config when the TCP handshake consumed the stream head."""
    cfg = cfg_frame if cfg_frame is not None else await stream.recv()
    if cfg is None or cfg.get("t") != "config":
        raise MalformedFrame(f"expected a config frame, got {cfg!r}")
    spec = ReplicaSpec.from_wire(cfg["spec"])
    rid = int(cfg.get("rid", 0))

    devices: Optional[int] = None
    if spec.host_devices:
        # first jax import in this process: XLA_FLAGS (set by the
        # parent's env) takes effect here and nowhere earlier
        import jax
        devices = len(jax.devices())

    router = build_router(spec, rid)
    await router.start()
    await stream.send({"t": "hello", "rid": rid, "pid": os.getpid(),
                       "n_workers": spec.n_workers, "devices": devices,
                       "execute": spec.execute})

    hb_errors: dict = {}
    hb = asyncio.create_task(
        heartbeat_loop(stream, spec.heartbeat_s, errors=hb_errors))
    inflight: set = set()

    async def run_one(frame: dict) -> None:
        now = router.clock.now()
        q = Query(deadline=now + float(frame["slo"]), seq=0, arrival=now,
                  qid=int(frame["qid"]))
        fut = await router.submit_query(q, frame.get("payload"))
        pred, acc = await fut
        await stream.send({
            "t": "completion", "qid": q.qid,
            "dropped": bool(q.dropped), "timed_out": bool(q.timed_out),
            "acc": None if q.dropped else float(acc),
            "latency": (q.finish - q.arrival
                        if q.finish is not None else None),
            "pred": to_jsonable(pred)})

    try:
        while True:
            frame = await stream.recv()
            if frame is None:
                break                   # parent gone: exit quietly
            t = frame["t"]
            if t == "submit":
                task = asyncio.create_task(run_one(frame))
                inflight.add(task)
                task.add_done_callback(inflight.discard)
            elif t == "kill":
                wid = int(frame.get("wid", KILL_ALL))
                wids = ([w.wid for w in router.workers]
                        if wid == KILL_ALL else [wid])
                for w in wids:
                    router.kill_worker(w)
            elif t == "stats":
                await stream.send({"t": "stats",
                                   "counters": _counters(router,
                                                         hb_errors)})
            elif t == "drain":
                await router.drain(float(frame.get("timeout", 10.0)))
                # flush every pending completion before acking the drain
                if inflight:
                    await asyncio.gather(*list(inflight),
                                         return_exceptions=True)
                await stream.send({"t": "drained",
                                   "counters": _counters(router,
                                                         hb_errors)})
                break
            # unknown kinds are ignored: additive protocol evolution
    finally:
        hb.cancel()
        stream.close()


async def serve_fd(fd: int) -> None:
    sock = socket.socket(fileno=fd)
    reader, writer = await asyncio.open_connection(sock=sock)
    await serve(FrameStream(reader, writer))


async def serve_tcp(host: str, port: int, token: str) -> None:
    """Dial the coordinator's listener and run its handshake: recv
    ``challenge`` (nonce + protocol version), answer ``auth`` with
    ``HMAC(token, nonce:version)``, then the next frame is either a
    ``reject`` (exit with its reason) or the ``config`` that starts the
    serve loop."""
    reader, writer = await asyncio.open_connection(host, port)
    stream = FrameStream(reader, writer)
    challenge = await stream.recv()
    if challenge is None or challenge.get("t") != "challenge":
        raise MalformedFrame(
            f"expected a challenge frame, got {challenge!r}")
    version = challenge.get("version")
    if version != PROTOCOL_VERSION:
        stream.close()
        raise SystemExit(
            f"protocol version mismatch: coordinator speaks {version!r}, "
            f"this child speaks {PROTOCOL_VERSION}")
    await stream.send({"t": "auth", "version": PROTOCOL_VERSION,
                       "mac": auth_mac(token, challenge.get("nonce") or "")})
    first = await stream.recv()
    if first is None or first.get("t") == "reject":
        stream.close()
        reason = (first or {}).get("reason", "connection closed")
        raise SystemExit(f"coordinator rejected the handshake: {reason}")
    await serve(stream, cfg_frame=first)


def main(argv: Optional[List[str]] = None) -> None:
    p = argparse.ArgumentParser(
        description="serve one replica group for a proc-transport "
                    "coordinator (local --fd or remote --connect)")
    p.add_argument("--fd", type=int, default=None,
                   help="inherited socketpair fd connected to the "
                        "coordinator process (local spawn)")
    p.add_argument("--connect", default=None, metavar="HOST:PORT",
                   help="dial the coordinator's TCP listener instead of "
                        "inheriting a socket (remote replica)")
    p.add_argument("--token", default=None,
                   help="shared HMAC token for the --connect handshake "
                        f"(default: ${TOKEN_ENV})")
    args = p.parse_args(argv)
    if (args.fd is None) == (args.connect is None):
        p.error("exactly one of --fd (inherited socketpair) or "
                "--connect HOST:PORT (TCP) is required")
    if args.fd is not None:
        asyncio.run(serve_fd(args.fd))
        return
    host, _, port = args.connect.rpartition(":")
    if not host or not port.isdigit():
        p.error(f"--connect wants HOST:PORT, got {args.connect!r}")
    token = (args.token if args.token is not None
             else os.environ.get(TOKEN_ENV, ""))
    asyncio.run(serve_tcp(host, int(port), token))


if __name__ == "__main__":
    main()
