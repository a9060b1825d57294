"""Compile-counter suite for the AOT-warmed subnet executor
(serving/executor.py) and the compat probes behind it.

The load-bearing assertions lean on ``compat.CompileCounter`` — the
``jax.monitoring`` backend-compile listener — so they prove the
SubNetAct property (actuation never recompiles) and the bucketing
property (the jit cache is bounded by the bucket lattice) against the
real XLA compile pipeline, not proxies. The launcher's in-process
real-execution path (``launch/serve.py --execute real``) and the
one-executor-per-device placement close the file.
"""
import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import tiny_dense
from repro import compat
from repro.core import subnet as sn
from repro.models import lm
from repro.serving.executor import (DecodeCache, ExecutorConfig,
                                    SubnetExecutor, bucket_of,
                                    build_executor)

# --------------------------------------------------------------------------
# pure bucketing / config plumbing (no compilation)
# --------------------------------------------------------------------------


def test_bucket_of_rounds_up_to_configured_bucket():
    assert bucket_of(1, (1, 2, 4)) == 1
    assert bucket_of(3, (1, 2, 4)) == 4
    assert bucket_of(4, (1, 2, 4)) == 4


def test_bucket_of_beyond_largest_goes_power_of_two():
    assert bucket_of(5, (1, 2, 4)) == 8
    assert bucket_of(9, (1, 2, 4)) == 16
    assert bucket_of(16, (1, 2, 4)) == 16


def test_bucket_of_rejects_nonpositive():
    with pytest.raises(ValueError):
        bucket_of(0, (1, 2))


def test_executor_config_validates():
    with pytest.raises(ValueError):
        ExecutorConfig(batch_buckets=(4, 2, 1))    # not sorted
    with pytest.raises(ValueError):
        ExecutorConfig(seq_buckets=())
    with pytest.raises(ValueError):
        ExecutorConfig(max_entries=0)


# --------------------------------------------------------------------------
# one shared warmed executor for the compile-counting tests
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def warmed():
    cfg = tiny_dense()
    xcfg = ExecutorConfig(batch_buckets=(1, 2, 4), seq_buckets=(8, 16),
                          max_entries=16)
    ex = build_executor(cfg, exec_cfg=xcfg)
    ex.warmup(batches=(1, 2, 4), seqs=(8,), decode=True)
    return ex


def test_warmed_actuation_never_recompiles(warmed):
    """SubNetAct: >= 3 subnets x >= 3 batch shapes after warmup ->
    zero XLA compilations (the control tuple is traced data; raw
    shapes collapse onto warmed buckets)."""
    assert warmed.n_subnets >= 3
    with compat.CompileCounter() as cc:
        for idx in range(3):
            for B in (1, 2, 3):
                out = warmed.prefill(idx, np.ones((B, 7), np.int32))
                assert out.shape == (B, warmed.cfg.vocab_size)
    assert cc.count == 0


def test_subnets_differ_through_one_executable(warmed):
    """The zero-compile path still actuates: different subnet indices
    give different logits through the same compiled entry."""
    toks = np.arange(8, dtype=np.int32)[None, :] % warmed.cfg.vocab_size
    a = warmed.prefill(0, toks)
    b = warmed.prefill(warmed.n_subnets - 1, toks)
    assert not np.allclose(a, b)


def test_bucket_reuse_hits_cache(warmed):
    before = warmed.counters()
    warmed.prefill(0, np.ones((2, 5), np.int32))   # bucket (2, 8)
    warmed.prefill(1, np.ones((2, 8), np.int32))   # same bucket
    after = warmed.counters()
    assert after["compiles"] == before["compiles"]
    assert after["hits"] == before["hits"] + 2


def test_router_stats_surface_executor_counters(warmed):
    """Router.stats()['executor'] exposes the executor counters; the
    engine's own stat keys are untouched."""
    from repro.serving import policies, runtime

    prof = warmed.measured_profile(batches=(1, 2), seq_len=8,
                                   warmup=0, iters=1)

    async def go():
        router = runtime.Router(prof, policies.SlackFit(),
                                warmed.make_workers(2), executor=warmed)
        await router.start()
        futs = [await router.submit(np.ones((8,), np.int32), slo_s=5.0)
                for _ in range(4)]
        await asyncio.gather(*futs)
        await router.drain()
        return router.stats()

    st = asyncio.run(go())
    assert st["served"] == 4.0
    assert st["executor"]["compiles"] >= 1.0
    assert 0.0 <= st["executor"]["hit_rate"] <= 1.0


def test_real_router_serving_is_compile_free(warmed):
    """The acceptance probe end-to-end: an executor-backed Router
    serving across subnets and batch shapes triggers zero XLA
    compilations once the buckets are warm."""
    from repro.serving import policies, runtime

    prof = warmed.measured_profile(batches=(1, 2, 4), seq_len=8,
                                   warmup=0, iters=1)

    async def go():
        router = runtime.Router(prof, policies.SlackFit(),
                                warmed.make_workers(2), executor=warmed)
        await router.start()
        futs = []
        for i in range(12):
            futs.append(await router.submit(
                np.full((7,), i, np.int32), slo_s=5.0))
        await asyncio.gather(*futs)
        await router.drain()
        return router.stats()

    with compat.CompileCounter() as cc:
        st = asyncio.run(go())
    assert st["served"] == 12.0
    assert cc.count == 0


def test_compiled_steps_are_named_after_their_functions(warmed):
    """The device trace names a program after the jitted function: the
    executor's steps read ``jit_prefill_step`` and ``jit_decode_step``."""
    from repro.serving.executor import prefill_fn
    lowered = jax.jit(prefill_fn(warmed.cfg)).lower(
        warmed.params, warmed.stacked_ctrl, np.ones((1, 8), np.int32),
        np.int32(0), np.full((1,), 8, np.int32))
    assert "@jit_prefill_step" in lowered.as_text()
    assert "HloModule jit_prefill_step" in \
        warmed._get("prefill", 1, 8).as_text()
    assert "HloModule jit_decode_step" in \
        warmed._get("decode", 1, 8).as_text()


# --------------------------------------------------------------------------
# LRU eviction
# --------------------------------------------------------------------------


def test_lru_evicts_at_cap():
    cfg = tiny_dense()
    ex = build_executor(cfg, exec_cfg=ExecutorConfig(
        batch_buckets=(1, 2), seq_buckets=(8, 16), max_entries=2))
    ex.prefill(0, np.ones((1, 8), np.int32))       # (1, 8)
    ex.prefill(0, np.ones((2, 8), np.int32))       # (2, 8)
    ex.prefill(0, np.ones((1, 16), np.int32))      # (1, 16) -> evict (1, 8)
    c = ex.counters()
    assert c["entries"] == 2.0
    assert c["evictions"] == 1.0
    keys = {k[:3] for k in ex.cache_keys()}
    assert ("prefill", 1, 8) not in keys
    # the evicted bucket recompiles on return (counted as a miss)
    before = ex.counters()["compiles"]
    ex.prefill(0, np.ones((1, 8), np.int32))
    assert ex.counters()["compiles"] == before + 1


def test_warmup_refuses_lattice_beyond_cap():
    cfg = tiny_dense()
    ex = build_executor(cfg, exec_cfg=ExecutorConfig(
        batch_buckets=(1, 2), seq_buckets=(8, 16), max_entries=2))
    with pytest.raises(ValueError, match="lattice"):
        ex.warmup(batches=(1, 2), seqs=(8, 16))


# --------------------------------------------------------------------------
# padding-mask numerics: bucketed == unpadded, at every CPU tier
# --------------------------------------------------------------------------


@pytest.mark.parametrize("tier", ["ref", "interpret"])
def test_padded_prefill_matches_unpadded(tier):
    if not compat.tier_available(tier):
        pytest.skip(f"{tier} tier unavailable")
    compat.set_kernel_tier(tier)
    try:
        cfg = tiny_dense()
        ex = build_executor(cfg, exec_cfg=ExecutorConfig(
            batch_buckets=(1, 2, 4), seq_buckets=(8, 16)))
        rng = np.random.default_rng(3)
        toks = rng.integers(0, cfg.vocab_size, (3, 7)).astype(np.int32)
        ctrl = sn.make_control(cfg, ex.points[2].sub)
        ref_out = lm.prefill(ex.params, cfg, {"tokens": jnp.asarray(toks)},
                             ctrl)
        got = ex.prefill(2, toks)                  # pads to (4, 8)
        np.testing.assert_allclose(np.asarray(ref_out)[:, -1, :], got,
                                   rtol=2e-4, atol=2e-4)
    finally:
        compat.reset_kernel_tier()


def test_ragged_lengths_gather_each_rows_last_position():
    """Rows with different true lengths in one bucketed batch each get
    the logits of their own final position."""
    cfg = tiny_dense()
    ex = build_executor(cfg, exec_cfg=ExecutorConfig(
        batch_buckets=(1, 2, 4), seq_buckets=(8,)))
    rng = np.random.default_rng(5)
    full = rng.integers(0, cfg.vocab_size, (2, 8)).astype(np.int32)
    lengths = [5, 8]
    ragged = full.copy()
    ragged[0, 5:] = 0                               # pad tail of row 0
    got = ex.prefill(1, ragged, lengths=lengths)
    ctrl = sn.make_control(cfg, ex.points[1].sub)
    for row, L in enumerate(lengths):
        solo = lm.prefill(ex.params, cfg,
                          {"tokens": jnp.asarray(full[row:row + 1, :L])},
                          ctrl)
        np.testing.assert_allclose(np.asarray(solo)[0, -1, :], got[row],
                                   rtol=2e-4, atol=2e-4)


# --------------------------------------------------------------------------
# decode path: numerics, donation, compile-freedom
# --------------------------------------------------------------------------


def test_decode_matches_reference_and_donates(warmed):
    cfg = warmed.cfg
    toks = np.arange(2, dtype=np.int32)[:, None] + 1
    dc = warmed.init_cache(2, 8)
    assert (dc.batch, dc.seq_cap) == (2, 8)
    with compat.CompileCounter() as cc:
        logits, dc2 = warmed.decode_step(1, toks, dc, 0)
    assert cc.count == 0                           # warmed with decode=True
    ctrl = sn.make_control(cfg, warmed.points[1].sub)
    state = lm.init_cache(cfg, 2, 8, dtype=cfg.dtype)
    ref_logits, _ = lm.decode_step(warmed.params, cfg, jnp.asarray(toks),
                                   ctrl, state, jnp.int32(0))
    np.testing.assert_allclose(np.asarray(ref_logits)[:, 0], logits,
                               rtol=2e-4, atol=2e-4)
    assert isinstance(dc2, DecodeCache)
    if warmed.donate:
        # the donated input cache was consumed in place
        assert jax.tree.leaves(dc.state)[0].is_deleted()


def test_decode_pads_small_batches_into_cache_bucket(warmed):
    dc = warmed.init_cache(2, 8)
    logits, _ = warmed.decode_step(0, np.ones((1, 1), np.int32), dc, 0)
    assert logits.shape == (1, warmed.cfg.vocab_size)


# --------------------------------------------------------------------------
# satellite regression: lm.generate compiles the decode step once
# --------------------------------------------------------------------------


def test_generate_compiles_decode_step_exactly_once():
    cfg = tiny_dense(d_ff=192)     # unique cfg -> cold decode-step cache
    params = lm.init_model(jax.random.PRNGKey(0), cfg)
    from repro.core.pareto import pareto_subnets
    pts = pareto_subnets(cfg)
    prompt = np.arange(4, dtype=np.int32)[None, :] % cfg.vocab_size
    ctrl_a = sn.make_control(cfg, pts[0].sub)
    ctrl_b = sn.make_control(cfg, pts[-1].sub)
    with compat.CompileCounter() as first:
        out_a = lm.generate(params, cfg, jnp.asarray(prompt), ctrl_a,
                            max_new=2, seq_cap=8)
    assert first.count >= 1                        # the one real compile
    with compat.CompileCounter() as again:
        lm.generate(params, cfg, jnp.asarray(prompt), ctrl_a,
                    max_new=2, seq_cap=8)
        # a different subnet rides the same executable: ctrl is traced
        lm.generate(params, cfg, jnp.asarray(prompt), ctrl_b,
                    max_new=2, seq_cap=8)
    assert again.count == 0
    assert out_a.shape == (1, prompt.shape[1] + 2)


# --------------------------------------------------------------------------
# launcher: in-process real execution, widths, one executor per device
# --------------------------------------------------------------------------


def _launch_args(*argv):
    from repro.launch import serve
    return serve.build_parser().parse_args(["--arch", "qwen2-1.5b", *argv])


def test_launcher_full_width_unless_reduced_is_asked():
    from repro.configs import get_config
    from repro.launch import serve
    full = get_config("qwen2-1.5b")
    assert serve.config_of(_launch_args("--execute", "real")) == full
    assert serve.config_of(_launch_args("--reduced")) == full.reduced()


def test_launcher_real_execution_in_process():
    """``--execute real`` serves every query through the warmed executor
    in this process, compile-free, from the measured profile."""
    from repro.launch import serve
    run = serve.run_real(_launch_args(
        "--execute", "real", "--reduced", "--profile", "measured",
        "--queries", "12", "--seq-len", "16"))
    out, n = run.out, len(run.payloads)
    assert out["reduced"] and out["dtype"] == "float32"
    assert len(run.results) == n and out["served"] + out["dropped"] == n
    assert out["serve_phase_compiles"] == 0
    assert out["replica_devices"] == [str(jax.devices()[0])]
    # the engine's profile is the raw measurement with the cummax applied
    assert (run.profile.lat >= run.raw_profile.lat).all()
    for pred, _ in run.results:
        if pred is not None:
            assert np.asarray(pred).shape == (run.executors[0].cfg.vocab_size,)


def test_launcher_refuses_full_width_proc_children():
    """Proc children run on the CPU and build the reduced twin; the
    coordinator schedules that twin's Pareto set, never the full one."""
    from repro.configs import get_config
    from repro.launch import serve
    args = _launch_args("--transport", "proc", "--execute", "real")
    assert serve.config_of(args) == get_config("qwen2-1.5b").reduced()


REPLICA_DEVICES = """
import jax, numpy as np
from repro.configs import get_config
from repro.serving.executor import build_replica_executors
cfg = get_config("qwen2-1.5b").reduced()
exs = build_replica_executors(cfg, 5)
assert [e.device.id for e in exs] == [0, 1, 2, 3, 0], exs
assert exs[4] is exs[0]
for e in exs[:4]:
    leaves = jax.tree.leaves((e.params, e.stacked_ctrl))
    assert {d for a in leaves for d in a.devices()} == {e.device}
    assert e.prefill(0, np.ones((1, 16), np.int32)).shape == (1, cfg.vocab_size)
print("ok")
"""


def test_replica_executors_one_per_device():
    """Replica r runs on local device r % n, params committed there; a
    device hosting two replicas holds one executor."""
    import subprocess
    import sys
    from pathlib import Path

    from conftest import cpu_subprocess_env
    r = subprocess.run(
        [sys.executable, "-c", REPLICA_DEVICES], capture_output=True,
        text=True, timeout=300, cwd=Path(__file__).resolve().parents[1],
        env=cpu_subprocess_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert r.returncode == 0 and r.stdout.strip() == "ok", r.stderr[-2000:]
