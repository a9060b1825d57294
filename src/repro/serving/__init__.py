"""SuperServe serving layer: profiler, EDF queue, scheduling policies
(SlackFit et al.), trace generators, and ONE transport-agnostic
scheduling engine (serving/engine.py: admission, EDF, policy
invocation, continuous batching, actuation accounting, fault
re-enqueue) behind two transports — the discrete-event simulator
(virtual clock) and the asyncio router/worker runtime hosting a
SubNetAct supernet (wall clock).

Scale-out (serving/cluster.py): N replica groups — one engine each —
behind a ClusterCoordinator with pluggable replica placement
(round-robin / least-loaded / power-of-two / slack-aware) and
replica-death re-routing; both transports grow cluster counterparts
(simulate_cluster, ClusterRouter) over one shared event loop.

Autoscaling (serving/autoscaler.py): a ClusterAutoscaler rides on the
coordinator's replica-lifecycle surface and spawns / gracefully
decommissions replica groups from pluggable load signals
(queue_pressure / predictive / slo_headroom), with cold-start
actuation, replica-seconds accounting, and a scale-event log — same
control loop on both transports, so autoscaled schedules stay
deterministic.

Forecasting (serving/forecast.py): one deterministic, clock-agnostic
ArrivalForecaster (windowed rate + Holt trend + CV² burst detector)
feeds the predictive scaling policy, the engine's predictive join
windows at saturation, and coordinator forecast introspection.
Layering rule: forecasting state lives in forecast.py only —
coordinator/engines own and feed it, policies consume it, transports
never mutate it.

Residency (serving/residency.py): a per-engine ResidencyTracker owns
which subnet each worker last actuated, and an ActuationModel prices
switches (SubNetAct control swap vs full weight page-in) and replica
cold starts from one physical model. Consumers: the actuation_aware
placement, the slackfit_sticky policy, autoscaler cold-start
derivation, and the switch_rate / actuation_seconds metrics. Layering
rule: residency state lives in residency.py only — the engine is its
sole writer (actuate on launch, forget on death), everything else
reads; residency-blind configs replay pre-refactor schedules
bit-for-bit.

Multi-host plane (serving/ipc.py + serving/replica_proc.py):
``ClusterRouter(transport="proc")`` runs each replica group as its own
OS process behind a length-prefixed JSON frame protocol (seq-verified,
heartbeat dead-peer detection, typed FrameError taxonomy) over either
an inherited socketpair or a coordinator-side TCP listener with an
HMAC-token challenge/auth handshake — remote children join via
``replica_proc --connect`` and are adopted with ``adopt_replica()``.
The live ClusterAutoscaler drives this transport too (spawn = fork or
TCP-connect a process, decommission = drain frame through the
coordinator's surrender path), and ``execute="real"`` children build
their own AOT-warmed SubnetExecutor so completions carry real
predictions. XLA host-device pinning via ipc.replica_env.
Layering rule: the parent-side coordinator keeps sole ownership of
admission/placement/lifecycle; children own scheduling through a full
in-process Router; the transport only serializes placement decisions
out and completion records back — inproc/proc record parity (over
both front doors) is the gate (tests/test_ipc.py,
benchmarks/bench_multiproc.py)."""
