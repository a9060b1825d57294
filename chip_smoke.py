#!/usr/bin/env python3
"""Bring-up check of the serving main path on TPU chips.

    python3 chip_smoke.py [--seed 0] [--queries 100]
    python3 chip_smoke.py --chips 4

One process, one chip by default: the environment, the four Pallas
kernels against their oracles at qwen2-1.5b widths, then the
``launch/serve.py --execute real --profile measured`` path in process
(``serve.run_real``) — full-width bf16 qwen2-1.5b built from ``--seed``,
the (1, 2, 4, 8) x seq-128 lattice AOT-warmed, the profile measured,
about 100 bursty open-loop queries (CV^2 = 8) served with SlackFit — and
the served logits checked against a float32 reference on the ``ref``
tier. ``--chips 4`` runs only the replicas-across-chips path: four
replicas, one executor per chip, against one replica at the same
per-replica rate.

Every phase prints its own lines. The numbers are bring-up readings
from one run, not benchmark results. The last line of standard output
is one JSON object, printed only when every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU (or outside a checkout of the repository) the script
exits nonzero and prints no result.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# max |got - want| / max |want| for one bf16 kernel output against its
# oracle: bf16 keeps 8 significant bits (relative step 2^-8 ~= 0.004)
KERNEL_TOL = 1e-2
# the same measure for served bf16 logits against the float32 reference
# of the same params on the ref tier, after 28 layers of bf16 rounding
LOGITS_TOL = 5e-2
# argmax must agree on every checked row whose reference top-two margin
# (over max |logit|) exceeds this. Argmax can flip only where the margin
# is under twice the row's error, and the worst bf16 error measured on a
# TPU v5e was 1.3e-2, so a margin above 3e-2 is no rounding call.
ARGMAX_MARGIN = 3e-2
N_CHECK = 4          # served queries checked per end of the Pareto front


class PhaseFailed(Exception):
    pass


def phase(name, fn, *args):
    print(f"== {name}", flush=True)
    t0 = time.perf_counter()
    try:
        out = fn(*args)
    except Exception as e:
        traceback.print_exc()
        print(f"== {name}: FAILED ({type(e).__name__}: {e})", flush=True)
        raise PhaseFailed(name) from e
    print(f"== {name}: ok ({time.perf_counter() - t0:.1f} s)", flush=True)
    return out


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------


def environment(chips: int):
    import jax
    import jaxlib
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"python {sys.version.split()[0]}  jax {jax.__version__}  "
          f"jaxlib {jaxlib.__version__}  libtpu {libtpu}")
    devices = jax.devices()
    print(f"devices: {devices}")
    dev = devices[0]
    print(f"platform {dev.platform}  device_kind {dev.device_kind}  "
          f"count {len(devices)}")
    check(dev.platform == "tpu",
          f"no TPU found: JAX sees platform {dev.platform!r}")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {len(devices)}")
    return dev


def kernels(cfg):
    """Each Pallas kernel on the chip at qwen2-1.5b widths, bf16 in and
    out, against its oracle in kernels/ref.py."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.dispatch import model_tier

    check(model_tier() == "tpu", f"model tier is {model_tier()!r}")
    print("model tier: tpu")
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    hd, bf = cfg.resolved_head_dim, jnp.bfloat16

    def normal(k, shape):
        return jax.random.normal(k, shape, jnp.float32).astype(bf)

    q = normal(ks[0], (1, cfg.n_heads, 512, hd))
    k = normal(ks[1], (1, cfg.n_kv_heads, 512, hd))
    v = normal(ks[2], (1, cfg.n_kv_heads, 512, hd))
    qd = normal(ks[3], (8, cfg.n_heads, 1, hd))
    kc = normal(ks[4], (8, cfg.n_kv_heads, 2048, hd))
    vc = normal(ks[5], (8, cfg.n_kv_heads, 2048, hd))
    x = normal(ks[6], (8, 128, cfg.d_model))
    table = jax.random.normal(ks[7], (cfg.elastic.num_subnets, cfg.d_model))
    w = normal(ks[0], (cfg.d_model, cfg.d_ff))
    a_in, a_out = jnp.int32(cfg.d_model * 2 // 3), jnp.int32(cfg.d_ff // 2)
    cases = {
        "flash_attention": (
            ops.flash_attention(q, k, v, causal=True, q_block=512,
                                kv_block=512, tier="tpu"),
            ref.flash_attention_dense_ref(q, k, v, causal=True)),
        "decode_attention": (
            ops.decode_attention(qd, kc, vc, jnp.int32(1000), kv_block=512,
                                 tier="tpu"),
            ref.decode_attention_dense_ref(qd, kc, vc, jnp.int32(1000))),
        "subnet_rmsnorm": (
            ops.subnet_rmsnorm(x, table, jnp.int32(17), tier="tpu"),
            ref.subnet_rmsnorm_ref(x, table, jnp.int32(17))),
        "sliced_matmul": (
            ops.sliced_matmul(x, w, a_in, a_out, tier="tpu"),
            ref.sliced_matmul_ref(x.reshape(-1, cfg.d_model), w, a_in,
                                  a_out).reshape(8, 128, cfg.d_ff)),
    }
    for name, (got, want) in cases.items():
        err = rel_err(got, want)
        print(f"{name}: shape {tuple(got.shape)}  max rel err {err:.3e}  "
              f"(tol {KERNEL_TOL:g})")
        check(err <= KERNEL_TOL, f"{name} error {err} > {KERNEL_TOL}")


def serve_args(seed: int, queries: int, replicas: int = 1, rate=None,
               slo_ms=None):
    from repro.launch import serve
    argv = ["--arch", "qwen2-1.5b", "--execute", "real",
            "--profile", "measured", "--policy", "slackfit",
            "--trace", "bursty", "--cv2", "8", "--seq-len", "128",
            "--queries", str(queries), "--replicas", str(replicas),
            "--seed", str(seed)]
    if rate is not None:
        argv += ["--rate", repr(rate)]
    if slo_ms is not None:
        argv += ["--slo-ms", repr(slo_ms)]
    return serve.build_parser().parse_args(argv)


def serve_path(args):
    from repro.launch import serve
    cfg = serve.config_of(args)
    depth = sum(st.repeat for st in cfg.stages)
    print(f"config {cfg.name}: {depth}L d_model {cfg.d_model} "
          f"{cfg.n_heads}H/{cfg.n_kv_heads}KV head_dim "
          f"{cfg.resolved_head_dim} d_ff {cfg.d_ff} vocab {cfg.vocab_size} "
          f"{cfg.dtype}; replicas {args.replicas}")
    run = serve.run_real(args)
    out = run.out
    for w in out["warmup"]:
        print(f"warmup: {w['n_compiled']:.0f} buckets compiled in "
              f"{w['seconds']:.3f} s")
    return run


def print_profiles(run):
    accs = run.executors[0].accs()
    print("profile ms, per (subnet, batch): raw measurement | engine's "
          "(monotonized)")
    print("subnet  acc    " + "  ".join(f"B={b:<5d}" for b in
                                        run.profile.batches) + "  |  engine")
    for i, acc in enumerate(accs):
        raw = "  ".join(f"{v * 1e3:7.3f}" for v in run.raw_profile.lat[i])
        eng = "  ".join(f"{v * 1e3:7.3f}" for v in run.profile.lat[i])
        print(f"{i:6d}  {acc:5.2f}  {raw}  |  {eng}")


def print_readings(run):
    import jax
    out = run.out
    print(f"served {out['served']}  dropped {out['dropped']}  of "
          f"{len(run.payloads)} at {out['rate_qps']} q/s, SLO "
          f"{out['slo_ms']} ms")
    print(f"slo_attainment {out['slo_attainment']}  p50 "
          f"{out['p50_latency_ms']} ms  p99 {out['p99_latency_ms']} ms  "
          f"mean_acc {out['mean_acc']}")
    print(f"serve_phase_compiles {out['serve_phase_compiles']}  "
          f"switch_rate {out['switch_rate']}")
    for c in out["executor"]:
        print(f"executor counters: {json.dumps(c)}")
    if "per_replica_served" in out:
        for r, dev in enumerate(out["replica_devices"]):
            print(f"replica {r}: device {dev}  served "
                  f"{out['per_replica_served'][r]}")
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        print(f"{dev}: peak_bytes_in_use {stats.get('peak_bytes_in_use')}")


def check_served(run):
    import numpy as np
    out = run.out
    n = len(run.payloads)
    check(len(run.results) == n, f"{len(run.results)} results for {n}")
    check(out["served"] + out["dropped"] == n,
          f"served {out['served']} + dropped {out['dropped']} != {n}")
    check(out["served"] > 0, "nothing was served")
    check(out["serve_phase_compiles"] == 0,
          f"{out['serve_phase_compiles']} compiles while serving")
    vocab = run.executors[0].cfg.vocab_size
    for pred, _ in run.results:
        if pred is not None:
            row = np.asarray(pred)
            check(row.shape == (vocab,), f"logits shape {row.shape}")
            check(np.isfinite(row.astype(np.float32)).all(),
                  "non-finite logits")
    print(f"all {n} queries accounted for; {out['served']} logits rows "
          f"finite, shape ({vocab},)")


def check_reference(run):
    """Served logits on the smallest and the largest subnet against the
    same params cast to float32 on the ``ref`` tier."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compat
    from repro.core import subnet as sn
    from repro.models import lm

    ex = run.executors[0]
    accs = ex.accs()
    rows = {0: [], ex.n_subnets - 1: []}
    for i, (pred, acc) in enumerate(run.results):
        pi = accs.index(acc) if pred is not None else None
        if pi in rows and len(rows[pi]) < N_CHECK:
            rows[pi].append((run.payloads[i], np.asarray(pred), "served"))
    for pi, got in rows.items():
        # a subnet SlackFit never picked: the same compiled entry, called
        # directly on served prompts
        for tokens in run.payloads[:N_CHECK - len(got)]:
            got.append((tokens, ex.prefill(pi, tokens[None])[0], "direct"))

    cfg32 = ex.cfg.replace(dtype="float32")
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), ex.params)
    compat.set_kernel_tier("ref")
    # full float32 matmuls: the TPU's default precision rounds f32
    # operands to bf16, which would make the reference as coarse as the
    # served path
    enforced = 0
    try:
        fwd = jax.jit(lambda p, tokens, ctrl: lm.forward(
            p, cfg32, {"tokens": tokens}, ctrl)[:, -1])
        for pi, got in rows.items():
            ctrl = sn.make_control(ex.cfg, ex.points[pi].sub)
            tokens = np.stack([t for t, _, _ in got])
            with jax.default_matmul_precision("float32"):
                want = np.asarray(fwd(params32, tokens, ctrl))
            for (_, served, how), ref_row in zip(got, want):
                err = rel_err(served, ref_row)
                top2 = np.sort(ref_row)[-2:]
                margin = float((top2[1] - top2[0]) / np.abs(ref_row).max())
                agree = int(np.argmax(served)) == int(np.argmax(ref_row))
                print(f"subnet {pi} ({how}): max rel err {err:.3e} "
                      f"(tol {LOGITS_TOL:g})  argmax agree {agree}  "
                      f"ref top-2 margin {margin:.3e}")
                check(err <= LOGITS_TOL, f"logits error {err}")
                check(agree or margin <= ARGMAX_MARGIN,
                      f"argmax disagrees at margin {margin}")
                enforced += margin > ARGMAX_MARGIN
        print(f"argmax enforced on {enforced} of "
              f"{sum(len(g) for g in rows.values())} rows "
              f"(ref top-2 margin > {ARGMAX_MARGIN:g})")
    finally:
        compat.reset_kernel_tier()
        del params32


# --------------------------------------------------------------------------
# main
# --------------------------------------------------------------------------


def one_chip(args):
    from repro.configs import get_config
    phase("kernels", kernels, get_config("qwen2-1.5b"))
    run = phase("serve (launch/serve.py --execute real --profile measured)",
                serve_path, serve_args(args.seed, args.queries))
    print_profiles(run)
    print_readings(run)
    phase("check served run", check_served, run)
    phase("check against float32 reference", check_reference, run)


def four_chips(args):
    """Four replicas, one per chip, against one replica at the same
    per-replica rate and SLO."""
    one = phase("serve: 1 replica", serve_path,
                serve_args(args.seed, args.queries))
    print_readings(one)
    phase("check 1-replica run", check_served, one)
    rate, slo_ms = one.out["rate_qps"], one.out["slo_ms"]
    del one
    four = phase("serve: 4 replicas", serve_path,
                 serve_args(args.seed, 4 * args.queries, replicas=4,
                            rate=4 * rate, slo_ms=slo_ms))
    print_readings(four)

    def distinct():
        check_served(four)
        devs = four.out["replica_devices"]
        check(len(set(devs)) == 4, f"replicas share devices: {devs}")
        served = four.out["per_replica_served"]
        check(all(served[r] > 0 for r in range(4)),
              f"a replica served nothing: {served}")

    phase("check 4-replica run", distinct)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=100)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository source at {SRC}; run the "
              f"script from a checkout", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    try:
        dev = phase("environment", environment, args.chips)
        from repro import compat
        print(f"compile cache: {compat.enable_compile_cache()}")
        (four_chips if args.chips == 4 else one_chip)(args)
    except PhaseFailed as e:
        print(f"chip_smoke: phase {e} failed", file=sys.stderr)
        return 1
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
