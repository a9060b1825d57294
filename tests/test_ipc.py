"""Multi-host serving plane (serving/ipc.py + replica_proc.py).

Four layers pinned here:
  * the wire protocol — length-prefixed JSON framing, monotonic
    sequence numbers, and the full FrameError taxonomy (truncated /
    malformed / oversized / out-of-order), on the shared sync decoder;
  * the spec boundary — LatencyProfile / EngineConfig survive the wire
    round trip with scheduling behavior intact;
  * the transport — a proc cluster (inherited socketpairs AND the TCP
    listener with its HMAC-token handshake) reproduces the inproc
    ClusterRouter's completion records record-for-record on a
    deterministic paced trace (modulo wall-clock latencies); bad-token
    and version-mismatch peers are rejected before any serving frame;
    remote children are adopted through the same front door;
  * lifecycle — replica-process death (out-of-band SIGKILL ->
    dead-peer detection, and the kill_replica API) drains and
    re-routes through the coordinator's existing redistribute path;
    the live autoscaler spawns/decommissions replica PROCESSES without
    losing a query; death racing shutdown resolves every future
    exactly once; execute="real" children return actual subnet logits."""
import asyncio
import subprocess
import sys

import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import policies, profiler
from repro.serving.engine import EngineConfig, VirtualClock
from repro.serving.ipc import (PROTOCOL_VERSION, FrameDecoder, FrameError,
                               FrameStream, MalformedFrame,
                               OutOfOrderFrame, OversizedFrame,
                               ProcClusterRouter, TruncatedFrame, _Channel,
                               auth_mac, encode_frame, engine_cfg_from_wire,
                               engine_cfg_to_wire, heartbeat_loop,
                               profile_from_wire, profile_to_wire,
                               to_jsonable)
from repro.serving.queue import Query
from repro.serving.runtime import ClusterRouter, WorkerHandle

PROF = profiler.build_profile(get_config("ofa_resnet"))


# --------------------------------------------------------------------------
# Wire protocol: framing + error taxonomy (sync decoder, no sockets)
# --------------------------------------------------------------------------


class TestFraming:
    def test_roundtrip_many_frames_one_feed(self):
        frames = [{"t": "submit", "qid": i, "payload": [i, i + 1]}
                  for i in range(5)]
        wire = b"".join(encode_frame(f, seq=i)
                        for i, f in enumerate(frames))
        dec = FrameDecoder()
        out = dec.feed(wire)
        assert [f["qid"] for f in out] == list(range(5))
        assert [f["seq"] for f in out] == list(range(5))
        dec.eof()                       # clean boundary: no error

    def test_byte_at_a_time_reassembly(self):
        wire = encode_frame({"t": "stats"}, seq=0)
        dec = FrameDecoder()
        out = []
        for i in range(len(wire)):
            out.extend(dec.feed(wire[i:i + 1]))
        assert len(out) == 1 and out[0]["t"] == "stats"

    def test_truncated_frame_detected_at_eof(self):
        wire = encode_frame({"t": "completion", "qid": 3}, seq=0)
        dec = FrameDecoder()
        assert dec.feed(wire[:-2]) == []
        with pytest.raises(TruncatedFrame):
            dec.eof()

    def test_truncated_header_detected_at_eof(self):
        dec = FrameDecoder()
        assert dec.feed(b"\x00\x00") == []
        with pytest.raises(TruncatedFrame):
            dec.eof()

    def test_malformed_json_body(self):
        body = b"{not json!"
        wire = len(body).to_bytes(4, "big") + body
        with pytest.raises(MalformedFrame):
            FrameDecoder().feed(wire)

    def test_malformed_non_object_body(self):
        body = b"[1,2,3]"
        wire = len(body).to_bytes(4, "big") + body
        with pytest.raises(MalformedFrame):
            FrameDecoder().feed(wire)

    def test_malformed_missing_seq(self):
        body = b'{"t":"submit"}'
        wire = len(body).to_bytes(4, "big") + body
        with pytest.raises(MalformedFrame):
            FrameDecoder().feed(wire)

    def test_oversized_declared_length(self):
        wire = (1 << 30).to_bytes(4, "big")
        with pytest.raises(OversizedFrame):
            FrameDecoder().feed(wire)

    def test_oversized_encode_refused(self):
        with pytest.raises(OversizedFrame):
            encode_frame({"t": "submit", "payload": "x" * 64}, seq=0,
                         max_frame=32)

    def test_out_of_order_sequence(self):
        dec = FrameDecoder()
        dec.feed(encode_frame({"t": "heartbeat"}, seq=0))
        with pytest.raises(OutOfOrderFrame):
            dec.feed(encode_frame({"t": "heartbeat"}, seq=2))

    def test_replayed_sequence(self):
        dec = FrameDecoder()
        dec.feed(encode_frame({"t": "heartbeat"}, seq=0))
        with pytest.raises(OutOfOrderFrame):
            dec.feed(encode_frame({"t": "heartbeat"}, seq=0))

    def test_taxonomy_is_frame_error(self):
        for exc in (TruncatedFrame, MalformedFrame, OversizedFrame,
                    OutOfOrderFrame):
            assert issubclass(exc, FrameError)

    def test_to_jsonable_numpy(self):
        out = to_jsonable({"a": np.float64(1.5), "b": np.arange(3),
                           "c": [np.int32(2)]})
        assert out == {"a": 1.5, "b": [0, 1, 2], "c": [2]}


# --------------------------------------------------------------------------
# Spec boundary: profile / engine config survive the wire
# --------------------------------------------------------------------------


class TestSpecWire:
    def test_profile_roundtrip_preserves_scheduling(self):
        prof2 = profile_from_wire(profile_to_wire(PROF))
        assert prof2.arch == PROF.arch
        np.testing.assert_allclose(prof2.accs, PROF.accs)
        np.testing.assert_allclose(prof2.lat, PROF.lat)
        assert prof2.batches == PROF.batches
        # the bucket structure (what SlackFit schedules from) rebuilds
        # identically from the wire fields
        for slack in (0.001, 0.01, 0.036, 0.1):
            assert (prof2.choose_slackfit(slack, 8)
                    == PROF.choose_slackfit(slack, 8))
        # residency's switch-cost inputs survive too
        assert [p.weight_mb for p in prof2.points] == \
            [p.weight_mb for p in PROF.points]

    def test_engine_cfg_roundtrip(self):
        cfg = EngineConfig(continuous_batching=True, max_join_window=0.5,
                           load_on_switch=True)
        cfg2 = engine_cfg_from_wire(engine_cfg_to_wire(cfg))
        assert cfg2 == cfg
        assert engine_cfg_from_wire(engine_cfg_to_wire(None)) is None


# --------------------------------------------------------------------------
# Transport switch plumbing
# --------------------------------------------------------------------------


def _groups(n_replicas, workers_per_replica):
    return [[WorkerHandle(wid=i, run=lambda idx, p: list(p))
             for i in range(workers_per_replica)]
            for _ in range(n_replicas)]


class TestTransportSwitch:
    def test_proc_transport_dispatches_subclass(self):
        r = ClusterRouter(PROF, policies.MaxAcc(), [1, 1], transport="proc")
        assert isinstance(r, ProcClusterRouter)
        assert isinstance(r, ClusterRouter)

    def test_unknown_transport_rejected(self):
        with pytest.raises(ValueError, match="transport"):
            ClusterRouter(PROF, policies.MaxAcc(), [1], transport="tcp")

    def test_inproc_rejects_proc_only_kwargs(self):
        with pytest.raises(TypeError, match="work_ms"):
            ClusterRouter(PROF, policies.SlackFit(), _groups(1, 1),
                          work_ms=5.0)

    def test_proc_accepts_autoscale(self):
        """PR 10 closes the guarded gap: the live autoscaler rides the
        proc transport (construction wires a ClusterAutoscaler with the
        proxy-spawning engine factory; the live cycle is exercised by
        TestProcAutoscale)."""
        from repro.serving.autoscaler import AutoscaleConfig
        r = ClusterRouter(PROF, policies.MaxAcc(), [1], transport="proc",
                          autoscale=AutoscaleConfig(max_replicas=3))
        assert r.autoscaler is not None
        assert r.autoscaler.engine_factory == r._spawn_proxy

    def test_proc_autoscale_validates_bounds(self):
        from repro.serving.autoscaler import AutoscaleConfig
        with pytest.raises(ValueError, match="max_replicas"):
            ClusterRouter(PROF, policies.MaxAcc(), [1, 1, 1],
                          transport="proc",
                          autoscale=AutoscaleConfig(max_replicas=2))
        with pytest.raises(ValueError, match="spawn_workers"):
            ClusterRouter(PROF, policies.MaxAcc(), [1, 2],
                          transport="proc",
                          autoscale=AutoscaleConfig(max_replicas=4))

    def test_proc_rejects_bad_execute(self):
        with pytest.raises(ValueError, match="execute"):
            ClusterRouter(PROF, policies.MaxAcc(), [1], transport="proc",
                          execute="gpu")

    def test_proc_real_requires_arch(self):
        with pytest.raises(ValueError, match="arch"):
            ClusterRouter(PROF, policies.MaxAcc(), [1], transport="proc",
                          execute="real")

    def test_token_requires_listen(self):
        with pytest.raises(ValueError, match="listen"):
            ClusterRouter(PROF, policies.MaxAcc(), [1], transport="proc",
                          token="sesame")

    def test_bad_listen_rejected(self):
        with pytest.raises(ValueError, match="HOST:PORT"):
            ClusterRouter(PROF, policies.MaxAcc(), [1], transport="proc",
                          listen="9999")

    def test_proc_rejects_virtual_clock(self):
        with pytest.raises(ValueError, match="wall-clock"):
            ClusterRouter(PROF, policies.MaxAcc(), [1], transport="proc",
                          clock=VirtualClock())

    def test_proc_run_virtual_unsupported(self):
        r = ClusterRouter(PROF, policies.MaxAcc(), [1], transport="proc")
        with pytest.raises(NotImplementedError):
            r.run_virtual([0.0], slo_s=0.036)


# --------------------------------------------------------------------------
# Proc-transport parity + death (real subprocesses)
# --------------------------------------------------------------------------

N_Q = 24
SLO = 10.0                              # generous: no wall-clock drops
PACE = 0.004


def _key(recs):
    """The timing-insensitive completion signature: which queries were
    served/dropped, at which accuracy, on which replica. Wall-clock
    fields (arrival/finish) are excluded by design — that's the
    'modulo wall-clock latencies' in the parity bar."""
    return sorted((r.qid, r.dropped,
                   None if r.served_acc is None
                   else round(float(r.served_acc), 9), r.replica)
                  for r in recs)


async def _run_paced(router):
    await router.start()
    futs = []
    for i in range(N_Q):
        futs.append(await router.submit([float(i)], slo_s=SLO))
        await asyncio.sleep(PACE)
    results = await asyncio.gather(*futs)
    await router.drain(30.0)
    return router.records(), results


class TestProcParity:
    def test_records_match_inproc(self):
        """Acceptance bar: record-for-record completion parity between
        the proc and inproc transports on a deterministic paced trace
        (maxacc + round_robin: accuracy and placement are independent
        of wall-clock batching, so the signature is deterministic)."""
        recs_in, _ = asyncio.run(_run_paced(
            ClusterRouter(PROF, policies.MaxAcc(), _groups(2, 2))))
        recs_proc, results = asyncio.run(_run_paced(
            ClusterRouter(PROF, policies.MaxAcc(), [2, 2],
                          transport="proc")))
        assert len(recs_proc) == N_Q
        assert _key(recs_proc) == _key(recs_in)
        # every future resolved with the served accuracy
        assert all(acc > 0 for _, acc in results)
        # both replicas actually served (round robin over 2)
        assert {r.replica for r in recs_proc} == {0, 1}

    def test_payloads_echo_through_the_wire(self):
        recs, results = asyncio.run(_run_paced(
            ClusterRouter(PROF, policies.MaxAcc(), [1, 1],
                          transport="proc")))
        for i, (pred, _) in enumerate(results):
            assert pred == [float(i)]


class TestProcDeath:
    def test_process_kill_drains_and_reroutes(self):
        """Out-of-band SIGKILL of a replica process: dead-peer
        detection (EOF on its stream) must push its pending queries
        through ClusterCoordinator.redistribute to the survivor — every
        query still resolves, and the orphans finish on replica 1."""
        async def main():
            router = ClusterRouter(PROF, policies.MaxAcc(), [1, 1],
                                   transport="proc", work_ms=150.0)
            await router.start()
            futs = [await router.submit([float(i)], slo_s=30.0)
                    for i in range(8)]
            await asyncio.sleep(0.08)   # replica 0 is mid-batch
            router._chans[0].proc.kill()
            await asyncio.gather(*futs)
            await router.drain(60.0)
            return router
        router = asyncio.run(main())
        recs = router.records()
        assert len(recs) == 8
        assert all(not r.dropped for r in recs)     # conservation
        assert not router.coord.alive[0]
        # round robin sent the even qids to replica 0; the ones still
        # pending at the kill must have been re-routed to replica 1
        assert any(r.qid % 2 == 0 and r.replica == 1 for r in recs)

    def test_kill_replica_api(self):
        """Coordinator-initiated death (the kill_replica surface) takes
        the same redistribute path, synchronously."""
        async def main():
            router = ClusterRouter(PROF, policies.MaxAcc(), [1, 1],
                                   transport="proc", work_ms=100.0)
            await router.start()
            futs = [await router.submit([float(i)], slo_s=30.0)
                    for i in range(6)]
            await asyncio.sleep(0.05)
            router.kill_replica(0)
            assert not router.coord.alive[0]        # immediate, not EOF
            await asyncio.gather(*futs)
            await router.drain(60.0)
            return router
        router = asyncio.run(main())
        recs = router.records()
        assert len(recs) == 6 and all(not r.dropped for r in recs)
        assert any(r.qid % 2 == 0 and r.replica == 1 for r in recs)

    def test_total_cluster_death_drops_resolve(self):
        """Every replica dead: redistribute has nowhere to route — the
        orphans drop, their futures still resolve (no hang)."""
        async def main():
            router = ClusterRouter(PROF, policies.MaxAcc(), [1],
                                   transport="proc", work_ms=200.0)
            await router.start()
            futs = [await router.submit([1.0], slo_s=30.0)
                    for _ in range(4)]
            await asyncio.sleep(0.05)
            router.kill_replica(0)
            results = await asyncio.gather(*futs)
            # dead cluster: further admissions drop immediately
            late = await (await router.submit([9.0], slo_s=30.0))
            await router.drain(5.0)
            return router, results, late
        router, results, late = asyncio.run(main())
        assert late == (None, 0.0)
        assert len(results) == 4        # every future resolved, no hang
        recs = router.records()
        assert len(recs) == 5
        assert all(r.dropped or r.finish is not None for r in recs)
        assert any(r.dropped for r in recs)     # the orphans did drop


class TestHostDevicePinning:
    def test_child_sees_forced_device_count(self):
        """The XLA_FLAGS fake-device idiom: the spec pins N host
        devices, the parent env carries the flag, and the child's first
        jax import reports exactly N devices — multi-device CI on CPU,
        no TPUs."""
        async def main():
            router = ClusterRouter(PROF, policies.MaxAcc(), [1],
                                   transport="proc", host_devices=3)
            await router.start()
            hello = router._chans[0].hello
            await router.drain(10.0)
            return hello
        hello = asyncio.run(main())
        assert hello["devices"] == 3

    def test_host_devices_env_flag(self, monkeypatch):
        from repro.serving.ipc import ReplicaSpec, replica_env
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        monkeypatch.delenv("XLA_FLAGS", raising=False)
        spec = ReplicaSpec(profile={}, policy="maxacc", host_devices=4)
        env = replica_env(spec)
        assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
        # the child inherits the parent's platform; nothing forces it
        assert env["JAX_PLATFORMS"] == "cpu"
        spec.host_devices = 0
        assert "XLA_FLAGS" not in replica_env(spec)

    def test_jax_children_refused_off_cpu(self, monkeypatch):
        """A child that runs JAX would contend for the parent's chip:
        spawning one without JAX_PLATFORMS=cpu is refused, loudly."""
        from repro.serving.ipc import ReplicaSpec, replica_env
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        for spec in (ReplicaSpec(profile={}, policy="maxacc",
                                 host_devices=2),
                     ReplicaSpec(profile={}, policy="maxacc",
                                 execute="real", arch="qwen2-1.5b")):
            with pytest.raises(RuntimeError, match="JAX_PLATFORMS"):
                replica_env(spec)
        # echo children never touch JAX: they inherit whatever is set
        env = replica_env(ReplicaSpec(profile={}, policy="maxacc"))
        assert "JAX_PLATFORMS" not in env


# --------------------------------------------------------------------------
# TCP transport: listener, HMAC handshake, remote adoption
# --------------------------------------------------------------------------


class TestTcpTransport:
    def test_tcp_records_match_inproc(self):
        """Acceptance bar: the SAME parity signature as the socketpair
        transport, with every child dialing the TCP listener and
        passing the handshake first."""
        recs_in, _ = asyncio.run(_run_paced(
            ClusterRouter(PROF, policies.MaxAcc(), _groups(2, 2))))
        recs_tcp, results = asyncio.run(_run_paced(
            ClusterRouter(PROF, policies.MaxAcc(), [2, 2],
                          transport="proc", listen="127.0.0.1:0")))
        assert len(recs_tcp) == N_Q
        assert _key(recs_tcp) == _key(recs_in)
        assert all(acc > 0 for _, acc in results)
        assert {r.replica for r in recs_tcp} == {0, 1}

    def test_token_autogenerated_with_listen(self):
        r = ClusterRouter(PROF, policies.MaxAcc(), [1], transport="proc",
                          listen="127.0.0.1:0")
        assert isinstance(r.token, str) and len(r.token) >= 16
        explicit = ClusterRouter(PROF, policies.MaxAcc(), [1],
                                 transport="proc", listen="127.0.0.1:0",
                                 token="sesame")
        assert explicit.token == "sesame"

    def test_auth_mac_binds_token_nonce_and_version(self):
        mac = auth_mac("tok", "nonce")
        assert mac == auth_mac("tok", "nonce", version=PROTOCOL_VERSION)
        assert mac != auth_mac("tok", "nonce", version=PROTOCOL_VERSION + 1)
        assert mac != auth_mac("other", "nonce")
        assert mac != auth_mac("tok", "other")


async def _dial(router) -> FrameStream:
    host, port = router.listen_addr
    reader, writer = await asyncio.open_connection(host, port)
    return FrameStream(reader, writer)


class TestHandshake:
    """The listener's challenge/auth gate, exercised with raw streams
    (no child process): rejected peers get a reject frame + EOF and
    never reach connection pairing."""

    def _router(self):
        return ClusterRouter(PROF, policies.MaxAcc(), [1],
                             transport="proc", listen="127.0.0.1:0")

    def _attempt(self, auth_builder):
        async def main():
            router = self._router()
            await router._start_listener()
            try:
                stream = await _dial(router)
                challenge = await stream.recv()
                assert challenge["t"] == "challenge"
                assert challenge["version"] == PROTOCOL_VERSION
                await stream.send(auth_builder(router, challenge))
                reply = await asyncio.wait_for(stream.recv(), timeout=5.0)
                eof = (None if reply is None
                       else await asyncio.wait_for(stream.recv(),
                                                   timeout=5.0))
                await asyncio.sleep(0.05)   # let pairing settle
                return router, reply, eof
            finally:
                router._server.close()
        return asyncio.run(main())

    def test_bad_token_rejected(self):
        router, reply, eof = self._attempt(
            lambda r, ch: {"t": "auth", "version": PROTOCOL_VERSION,
                           "mac": auth_mac("WRONG", ch["nonce"])})
        assert reply["t"] == "reject" and "token" in reply["reason"]
        assert eof is None                  # server closed after reject
        assert router.handshake_rejects == 1
        assert not router._pending_conns

    def test_missing_mac_rejected(self):
        router, reply, _ = self._attempt(
            lambda r, ch: {"t": "auth", "version": PROTOCOL_VERSION})
        assert reply["t"] == "reject" and "token" in reply["reason"]
        assert router.handshake_rejects == 1

    def test_version_mismatch_rejected(self):
        router, reply, _ = self._attempt(
            lambda r, ch: {"t": "auth", "version": 99,
                           "mac": auth_mac(r.token, ch["nonce"],
                                           version=99)})
        assert reply["t"] == "reject"
        assert "version" in reply["reason"]
        assert router.handshake_rejects == 1

    def test_non_auth_frame_rejected(self):
        router, reply, _ = self._attempt(
            lambda r, ch: {"t": "hello", "rid": 0})
        assert reply["t"] == "reject"
        assert router.handshake_rejects == 1

    def test_good_token_admitted_to_pairing(self):
        async def main():
            router = self._router()
            await router._start_listener()
            try:
                stream = await _dial(router)
                ch = await stream.recv()
                await stream.send(
                    {"t": "auth", "version": PROTOCOL_VERSION,
                     "mac": auth_mac(router.token, ch["nonce"])})
                await asyncio.sleep(0.1)    # let the accept task pair
                assert router.handshake_rejects == 0
                assert len(router._pending_conns) == 1
                stream.close()
            finally:
                router._server.close()
        asyncio.run(main())


class TestRemoteAdopt:
    def test_remote_child_adopted_and_serves(self):
        """A replica_proc started OUT OF BAND (the remote-host path:
        own Popen, --connect + --token on argv) is adopted through the
        listener and serves its round-robin share of a paced trace."""
        from repro.serving.ipc import ReplicaSpec, replica_env

        async def main():
            router = ClusterRouter(PROF, policies.MaxAcc(), [1],
                                   transport="proc",
                                   listen="127.0.0.1:0")
            await router.start()
            host, port = router.listen_addr
            proc = subprocess.Popen(
                [sys.executable, "-m", "repro.serving.replica_proc",
                 "--connect", f"{host}:{port}", "--token", router.token],
                env=replica_env(ReplicaSpec(profile={}, policy="maxacc")))
            try:
                rid = await router.adopt_replica(n_workers=1,
                                                 timeout=30.0)
                assert rid == 1
                assert router._chans[1].proc is None    # not our pid
                futs = []
                for i in range(8):
                    futs.append(await router.submit([float(i)],
                                                    slo_s=10.0))
                    await asyncio.sleep(PACE)
                results = await asyncio.gather(*futs)
                await router.drain(30.0)
            finally:
                proc.kill()
            return router, results

        router, results = asyncio.run(main())
        recs = router.records()
        assert len(recs) == 8 and all(not r.dropped for r in recs)
        assert {r.replica for r in recs} == {0, 1}
        assert all(pred is not None for pred, _ in results)
        assert router.handshake_rejects == 0


# --------------------------------------------------------------------------
# Live autoscaling over the proc transport
# --------------------------------------------------------------------------


class TestProcAutoscale:
    def test_autoscale_over_proc_conserves_queries(self):
        """A scripted spawn/decommission cycle on real replica
        processes: every query resolves exactly once, nothing drops
        (conservation across both scale events), and the spawned
        process serves real traffic once its cold start elapses."""
        from repro.serving.autoscaler import AutoscaleConfig

        async def main():
            cfg = AutoscaleConfig(
                min_replicas=1, max_replicas=3, policy="scripted",
                interval=0.05, cooldown=0.0, cold_start=0.05,
                spawn_workers=2, script=((0.2, +1), (2.0, -1)))
            router = ClusterRouter(PROF, policies.MaxAcc(), [2],
                                   transport="proc", autoscale=cfg,
                                   slo=10.0)
            await router.start()
            futs = []
            for i in range(40):
                futs.append(await router.submit([float(i)], slo_s=10.0))
                await asyncio.sleep(0.06)
            results = await asyncio.gather(*futs)
            await router.drain(30.0)
            return router, results

        router, results = asyncio.run(main())
        recs = router.records()
        assert len(recs) == 40
        assert len(results) == 40           # every future resolved
        assert all(not r.dropped for r in recs)     # conservation
        kinds = [e.kind for e in router.autoscaler.events]
        assert "spawn" in kinds and "ready" in kinds
        assert "decommission" in kinds
        # the forked replica process actually served traffic
        assert any(r.replica == 1 for r in recs)
        assert router._chans[1].proc is not None
        assert router.stats()["autoscale_errors"] == 0.0


# --------------------------------------------------------------------------
# Real execution in the child (execute="real")
# --------------------------------------------------------------------------


class TestRealExec:
    def test_real_child_returns_logits_not_echo(self):
        """The child builds a SubnetExecutor from the wire spec: each
        completion carries a finite (vocab,) logits row — real forward
        passes, not payload echoes. Slow (~child-side supernet init +
        AOT warmup on CPU), so the cell stays tiny."""
        cfg = get_config("qwen2-1.5b").reduced()
        prof = profiler.build_profile(cfg)

        async def main():
            router = ClusterRouter(prof, policies.MaxAcc(), [1],
                                   transport="proc", execute="real",
                                   arch="qwen2-1.5b", seq_len=8,
                                   spawn_timeout=300.0)
            await router.start()
            rng = np.random.default_rng(0)
            payloads = rng.integers(0, cfg.vocab_size, (4, 8))
            futs = [await router.submit(payloads[i].tolist(), slo_s=60.0)
                    for i in range(4)]
            results = await asyncio.gather(*futs)
            await router.drain(60.0)
            return router, payloads, results

        router, payloads, results = asyncio.run(main())
        recs = router.records()
        assert len(recs) == 4 and all(not r.dropped for r in recs)
        assert router._chans[0].hello["execute"] == "real"
        for i, (pred, acc) in enumerate(results):
            assert acc > 0
            row = np.asarray(pred, dtype=float)
            assert row.shape == (cfg.vocab_size,)
            assert np.all(np.isfinite(row))
            assert row.tolist() != [float(x) for x in payloads[i]]


# --------------------------------------------------------------------------
# Shutdown/death races (no subprocesses: fabricated channels)
# --------------------------------------------------------------------------


def _bare_router(n=2):
    """A proc router with channels but no processes: the death/shutdown
    bookkeeping paths under test never touch a stream."""
    router = ClusterRouter(PROF, policies.MaxAcc(), [1] * n,
                           transport="proc")
    router._chans = [_Channel(rid) for rid in range(n)]
    return router


def _pending_query(router, rid, qid, loop):
    q = Query(deadline=1e9, seq=0, arrival=0.0, qid=qid)
    q.replica = rid
    fut = loop.create_future()
    router.coord.queries.append(q)
    router._futs[qid] = fut
    router._payloads[qid] = [float(qid)]
    router._by_qid[qid] = q
    router.proxies[rid].pending[qid] = q
    router._all_done.clear()
    return q, fut


class TestShutdownRaces:
    def test_death_during_drain_resolves_once_not_timed_out(self):
        """The _closing gate: a replica dying mid-drain must NOT
        redistribute to peers that already acked drained — its orphans
        resolve immediately as dropped shutdown loss (timed_out stays
        False: lost to a death, not to the drain deadline), exactly
        once."""
        async def main():
            router = _bare_router(2)
            loop = asyncio.get_running_loop()
            q, fut = _pending_query(router, 0, 7, loop)
            router._closing = True
            router._on_death(0, "eof during drain")
            assert fut.done() and fut.result() == (None, 0.0)
            assert q.dropped and not q.timed_out
            assert not router.coord.alive[0]
            # no redistribute: the survivor's outbox saw no submit frame
            assert router._chans[1].outbox.qsize() == 0
            assert not router.proxies[1].pending
            assert router._all_done.is_set()
            # the race's second observation (watchdog after EOF) no-ops
            router._on_death(0, "heartbeat timeout")
            assert fut.result() == (None, 0.0)
            # ...and a stale completion from the dead child is ignored
            router._on_completion(0, {"qid": 7, "dropped": False,
                                      "acc": 0.9, "pred": [7.0]})
            assert fut.result() == (None, 0.0)
            return router
        asyncio.run(main())

    def test_death_before_drain_still_redistributes(self):
        """Contrast case: outside shutdown the same death DOES re-route
        through the coordinator — the survivor's outbox gets the
        re-serialized submit and the future stays pending for it."""
        async def main():
            router = _bare_router(2)
            loop = asyncio.get_running_loop()
            q, fut = _pending_query(router, 0, 7, loop)
            router._on_death(0, "eof")
            assert not fut.done()               # survivor will serve it
            assert q.replica == 1
            assert router.proxies[1].pending == {7: q}
            frame = router._chans[1].outbox.get_nowait()
            assert frame["t"] == "submit" and frame["qid"] == 7
            assert frame["payload"] == [7.0]
            return router
        asyncio.run(main())

    def test_stale_completion_after_reroute_ignored(self):
        """Re-routed query: the OLD replica's late completion must not
        resolve the future out from under the new assignment."""
        async def main():
            router = _bare_router(2)
            loop = asyncio.get_running_loop()
            q, fut = _pending_query(router, 0, 3, loop)
            router._on_death(0, "eof")          # re-routes 3 -> replica 1
            router._on_completion(0, {"qid": 3, "dropped": False,
                                      "acc": 0.5, "pred": [9.9]})
            assert not fut.done()               # stale: ignored
            router._on_completion(1, {"qid": 3, "dropped": False,
                                      "acc": 0.75, "pred": [3.0]})
            assert fut.done()
            assert fut.result() == ([3.0], 0.75)
            assert q.served_acc == 0.75
            return router
        asyncio.run(main())

    def test_drain_timeout_leftovers_marked_timed_out(self):
        """Leftover futures at the drain deadline resolve as dropped
        AND timed_out via the qid index (no per-qid linear scan)."""
        async def main():
            router = _bare_router(1)
            loop = asyncio.get_running_loop()
            q, fut = _pending_query(router, 0, 11, loop)
            await router.drain(timeout=0.01)
            assert fut.done() and fut.result() == (None, 0.0)
            assert q.dropped and q.timed_out
            assert not router._by_qid and not router._payloads
            return router
        asyncio.run(main())


class TestHeartbeatRobustness:
    def test_send_failure_ends_loop_and_counts(self):
        """Satellite bugfix: a heartbeat send hitting a dead connection
        exits the loop cleanly (no unobserved exception) and surfaces
        the failure in the counter the child folds into its stats."""
        class _BoomStream:
            async def send(self, frame):
                raise ConnectionError("peer gone")

        errors = {}
        asyncio.run(heartbeat_loop(_BoomStream(), interval=0.001,
                                   errors=errors))
        assert errors == {"heartbeat_send_errors": 1}

    def test_framestream_recv_is_fifo_from_one_burst(self):
        """Satellite bugfix: a single read burst finishing many frames
        must hand them out in order (deque semantics)."""
        async def main():
            reader = asyncio.StreamReader()
            wire = b"".join(encode_frame({"t": "heartbeat", "i": i},
                                         seq=i) for i in range(50))
            reader.feed_data(wire)
            reader.feed_eof()

            class _NullWriter:
                def close(self):
                    pass

            stream = FrameStream(reader, _NullWriter())
            out = [await stream.recv() for _ in range(50)]
            assert [f["i"] for f in out] == list(range(50))
            assert await stream.recv() is None      # clean EOF
        asyncio.run(main())
