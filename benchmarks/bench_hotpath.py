"""Hot-path regression benchmark: the numbers ``tools/bench_diff.py``
gates PR-over-PR.

Four sections, one per layer of the serving hot path:

1. **Prefill kernel sweep** — block-skipping ``flash_attention_ref``
   vs the dense oracle at growing causal lengths (jitted, warmup +
   median-of-k via :func:`benchmarks.common.time_fn`). The headline
   gate: at the longest causal length the skipping path must be >= 2x
   the dense path, while agreeing numerically.
2. **Decode sweep** — block-skipping cached decode vs the dense cache
   scan at early/late positions in a long cache.
3. **Engine overhead-per-query** — wall-clock of the serving-engine
   event loop (:func:`repro.serving.simulator.simulate`) divided by
   queries handled; model compute is profiled latency, so this isolates
   scheduler/queue bookkeeping.
4. **Cluster event-loop throughput** — queries per wall-second through
   :func:`repro.serving.simulator.simulate_cluster`.

Claims split by kind, mirroring ``results/bench_baseline/tolerances.json``:

* *structural* (timing-insensitive; what CI's perf-smoke gates): skip
  vs dense numerics agreement, the live-block fraction actually
  shrinking, the engine resolving every query. Identical between ``--smoke`` and full runs — the simulator
  sections use the same seeded traces in both modes.
* *timing* (full runs only; CI skips via ``bench_diff --skip-timing``):
  the >= 2x prefill gate. ``--smoke`` drops timing iterations to 1 and
  omits the timing claim so a noisy shared runner can't flake it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import banner, emit_bench_json, save, table, time_fn
from repro.kernels import ops as _ops  # noqa: F401 — populates the registry
from repro.kernels import ref
from repro.kernels.dispatch import DISPATCHER
from repro.kernels.ref import _live_kv_range

PREFILL_LENGTHS = (512, 1024, 2048)
PREFILL_BLOCK = 256
DECODE_SMAX = 4096
DECODE_BLOCK = 256
DECODE_INDICES = (64, DECODE_SMAX - 1)
SPEEDUP_GATE = 2.0
_TOL = dict(rtol=2e-3, atol=2e-3)


def _mk_qkv(S, d=64, Hq=8, Hkv=4, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, Hq, S, d), jnp.float32),
            jax.random.normal(ks[1], (1, Hkv, S, d), jnp.float32),
            jax.random.normal(ks[2], (1, Hkv, S, d), jnp.float32))


def _live_fraction(S: int, block: int) -> float:
    """Fraction of kv blocks the skipping prefill visits (causal)."""
    n = -(-S // block)
    live = sum(hi - lo for qi in range(n)
               for lo, hi in [_live_kv_range(qi * block,
                                             min((qi + 1) * block, S),
                                             n, block, True, 0, None)])
    return live / (n * n)


def _prefill_sweep(warmup: int, iters: int):
    rows, out, agree_all = [], {}, True
    for S in PREFILL_LENGTHS:
        q, k, v = _mk_qkv(S)
        dense = jax.jit(lambda q, k, v: ref.flash_attention_dense_ref(
            q, k, v, causal=True))
        skip = jax.jit(lambda q, k, v: ref.flash_attention_ref(
            q, k, v, causal=True, q_block=PREFILL_BLOCK,
            kv_block=PREFILL_BLOCK))
        agree = bool(np.allclose(np.asarray(dense(q, k, v)),
                                 np.asarray(skip(q, k, v)), **_TOL))
        agree_all &= agree
        td = time_fn(lambda: jax.block_until_ready(dense(q, k, v)),
                     warmup=warmup, iters=iters)
        ts = time_fn(lambda: jax.block_until_ready(skip(q, k, v)),
                     warmup=warmup, iters=iters)
        out[f"S{S}"] = {"dense_ms": td * 1e3, "skip_ms": ts * 1e3,
                        "speedup": td / max(ts, 1e-9),
                        "live_frac": _live_fraction(S, PREFILL_BLOCK)}
        rows.append([S, f"{td*1e3:.2f}", f"{ts*1e3:.2f}",
                     f"{td/max(ts,1e-9):.2f}x",
                     f"{out[f'S{S}']['live_frac']:.3f}",
                     "yes" if agree else "NO"])
    print(table(["S (causal)", "dense ms", "skip ms", "speedup",
                 "live frac", "agree"], rows))
    return out, agree_all


def _decode_sweep(warmup: int, iters: int):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 8, 1, 64), jnp.float32)
    kc = jax.random.normal(ks[1], (1, 4, DECODE_SMAX, 64), jnp.float32)
    vc = jax.random.normal(ks[2], (1, 4, DECODE_SMAX, 64), jnp.float32)
    dense = jax.jit(lambda i: ref.decode_attention_dense_ref(q, kc, vc, i))
    skip = jax.jit(lambda i: ref.decode_attention_ref(
        q, kc, vc, i, kv_block=DECODE_BLOCK))
    rows, out, agree_all = [], {}, True
    for idx in DECODE_INDICES:
        i = jnp.int32(idx)
        agree = bool(np.allclose(np.asarray(dense(i)), np.asarray(skip(i)),
                                 **_TOL))
        agree_all &= agree
        td = time_fn(lambda: jax.block_until_ready(dense(i)),
                     warmup=warmup, iters=iters)
        ts = time_fn(lambda: jax.block_until_ready(skip(i)),
                     warmup=warmup, iters=iters)
        out[f"idx{idx}"] = {"dense_ms": td * 1e3, "skip_ms": ts * 1e3,
                            "speedup": td / max(ts, 1e-9)}
        rows.append([idx, f"{td*1e3:.3f}", f"{ts*1e3:.3f}",
                     f"{td/max(ts,1e-9):.2f}x", "yes" if agree else "NO"])
    print(table([f"idx (Smax={DECODE_SMAX})", "dense ms", "skip ms",
                 "speedup", "agree"], rows))
    return out, agree_all


def _engine_overhead(warmup: int, iters: int):
    from repro.configs import get_config
    from repro.serving import policies, profiler, simulator, traces
    prof = profiler.build_profile(get_config("ofa_resnet"))
    arr = traces.bursty_trace(800, 3200, 8.0, 4.0, seed=13)
    scfg = simulator.SimConfig(n_workers=8, slo=0.036)
    res_box = {}

    def go():
        res_box["res"] = simulator.simulate(arr, prof, policies.SlackFit(),
                                            scfg)

    wall = time_fn(go, warmup=warmup, iters=iters)
    res = res_box["res"]
    n = len(res.queries)
    resolved = sum(1 for qq in res.queries
                   if qq.finish is not None or qq.dropped)
    out = {"wall_s": wall, "n_queries": float(n),
           "overhead_us_per_query": wall / max(n, 1) * 1e6,
           "slo_attainment": res.slo_attainment,
           "resolved_frac": resolved / max(n, 1)}
    print(f"engine event loop: {n} queries in {wall*1e3:.0f} ms wall "
          f"-> {out['overhead_us_per_query']:.1f} us/query "
          f"(SLO {res.slo_attainment:.4f})")
    return out


def _cluster_throughput(warmup: int, iters: int):
    from repro.configs import get_config
    from repro.serving import policies, profiler, simulator, traces
    prof = profiler.build_profile(get_config("ofa_resnet"))
    arr = traces.bursty_trace(800, 3200, 8.0, 4.0, seed=17)
    ccfg = simulator.ClusterConfig(n_replicas=2, workers_per_replica=4,
                                   placement="least_loaded", slo=0.036)
    res_box = {}

    def go():
        res_box["res"] = simulator.simulate_cluster(arr, prof,
                                                    policies.SlackFit(), ccfg)

    wall = time_fn(go, warmup=warmup, iters=iters)
    res = res_box["res"]
    n = len(res.queries)
    out = {"wall_s": wall, "n_queries": float(n),
           "event_qps": n / max(wall, 1e-9),
           "slo_attainment": res.slo_attainment}
    print(f"cluster event loop: {n} queries in {wall*1e3:.0f} ms wall "
          f"-> {out['event_qps']:.0f} q/s (SLO {res.slo_attainment:.4f})")
    return out


def run(smoke: bool = False) -> dict:
    banner("bench_hotpath (kernel/engine/cluster perf trajectory)"
           + (" [smoke]" if smoke else ""))
    warmup, iters = (1, 1) if smoke else (2, 5)

    prefill, prefill_agree = _prefill_sweep(warmup, iters)
    decode, decode_agree = _decode_sweep(warmup, iters)
    engine = _engine_overhead(warmup, iters)
    cluster = _cluster_throughput(warmup, iters)

    longest = f"S{PREFILL_LENGTHS[-1]}"
    payload = {
        "prefill": prefill, "decode": decode, "engine": engine,
        "cluster": cluster,
        "claims": {
            # structural: stable across hosts/modes, gated in CI smoke
            "prefill_skip_matches_dense": prefill_agree,
            "decode_skip_matches_dense": decode_agree,
            "prefill_skips_dead_blocks":
                prefill[longest]["live_frac"] <= 0.75,
            "engine_resolves_all_queries":
                engine["resolved_frac"] >= 1.0,
        },
    }
    if not smoke:
        # timing: gated only in full runs (CI smoke skips via
        # bench_diff --skip-timing + the omitted claim)
        payload["claims"]["ref_skip_speedup_ge_2x"] = (
            prefill[longest]["speedup"] >= SPEEDUP_GATE)
    save("hotpath", payload)
    return payload


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="structural claims only; single timing iteration")
    args = ap.parse_args()
    payload = run(smoke=args.smoke)
    path = emit_bench_json("hotpath", payload)
    print(f"\nwrote {path}")
    bad = [c for c, ok in payload["claims"].items() if not ok]
    raise SystemExit(1 if bad else 0)
