"""Sharding plan rules + shard_map collectives + elastic restore.
Multi-device tests run in subprocesses (the main pytest process keeps
the default single CPU device)."""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config


def _abstract_plan(arch, shape=(2, 16, 16), axes=("pod", "data", "model")):
    from repro.distributed.sharding import ShardingPlan
    # a device-free AbstractMesh: the rules need no devices
    return ShardingPlan.abstract(shape, axes, get_config(arch))


class TestShardingRules:
    def test_attention_tp(self):
        plan = _abstract_plan("qwen2.5-14b")
        # stacked (repeat, d, Hq*hd)
        assert plan.param_spec("stages/0/0:attn/wq", (48, 5120, 5120)) == \
            P(None, None, "model")
        assert plan.param_spec("stages/0/0:attn/wo", (48, 5120, 5120)) == \
            P(None, "model", None)

    def test_embed_vocab_sharded(self):
        plan = _abstract_plan("qwen2.5-14b")
        assert plan.param_spec("embed", (152064, 5120)) == P("model", None)
        assert plan.param_spec("head", (5120, 152064)) == P(None, "model")

    def test_moe_expert_parallel(self):
        plan = _abstract_plan("llama4-maverick-400b-a17b")
        spec = plan.param_spec("stages/0/1:moe/wg", (24, 128, 5120, 8192))
        assert spec == P(None, "model", None, None)      # EP: 128 experts / 16

    def test_moe_few_experts_ffn_sharded(self):
        plan = _abstract_plan("mixtral-8x7b")
        spec = plan.param_spec("stages/0/1:moe/wg", (32, 8, 4096, 14336))
        assert spec == P(None, None, None, "model")      # 8 experts < 16: TP d_ff

    def test_norm_tables_replicated(self):
        plan = _abstract_plan("qwen2.5-14b")
        assert plan.param_spec("stages/0/0:attn/norm_gamma", (48, 18, 5120)) \
            == P(None, None, None)

    def test_batch_dp(self):
        plan = _abstract_plan("qwen2.5-14b")
        assert plan.batch_spec("tokens", (256, 4096)) == P(("pod", "data"), None)
        # batch=1 cannot cover dp -> replicated
        assert plan.batch_spec("tokens", (1, 1)) == P(None, None)

    def test_cache_sp_fallback(self):
        """B=1 long-context cache: sequence takes the DP axes (SP)."""
        plan = _abstract_plan("zamba2-2.7b")
        spec = plan.cache_spec("stages/0/0:mamba/k",
                               (54, 1, 32, 524288, 80))
        assert spec[1] is None                 # B unshardable
        assert spec[3] == ("pod", "data")      # S over DP

    def test_cache_batch_dp_heads_tp(self):
        plan = _abstract_plan("zamba2-2.7b")
        spec = plan.cache_spec("shared_attn/k", (9, 128, 32, 32768, 80))
        assert spec[1] == ("pod", "data") and spec[2] == "model"


MULTIDEV = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from repro.launch.mesh import make_mesh
    from repro.distributed import collectives, elastic
    from repro.distributed.sharding import ShardingPlan
    from repro.configs import get_config

    mesh = make_mesh((4, 2), ("data", "model"))
    # 1) seq-sharded flash-decode combine vs oracle
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 4, 1, 16), jnp.float32)
    kc = jax.random.normal(ks[1], (2, 2, 32, 16), jnp.float32)
    vc = jax.random.normal(ks[2], (2, 2, 32, 16), jnp.float32)
    with mesh:
        y = collectives.seq_sharded_decode(mesh, q, kc, vc, jnp.int32(17))
    yr = collectives.seq_sharded_decode_ref(q, kc, vc, 17)
    err = float(jnp.abs(y - yr).max())
    assert err < 2e-3, err

    # 2) elastic reshard params onto a smaller mesh
    cfg = get_config("qwen2-1.5b").reduced()
    from repro.models import lm
    params = lm.init_model(jax.random.PRNGKey(0), cfg)
    plan = ShardingPlan(mesh, cfg)
    params = jax.tree.map(jax.device_put, params, plan.params(params))
    small = elastic.shrink_mesh(mesh, cfg, drop_axis="data", factor=2)
    plan2 = ShardingPlan(small, cfg)
    params2 = elastic.reshard_params(params, plan2)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32))
    # 3) int8 all-reduce on mesh
    from repro.training import compress
    g = {"w": jnp.ones((16, 16)) * 0.25}
    e = {"w": jnp.zeros((16, 16))}
    with mesh:
        mg, ne = compress.all_reduce_int8(mesh, g, e, axis="data")
    assert float(jnp.abs(mg["w"] - 0.25).max()) < 0.01
    print(json.dumps({"ok": True, "err": err}))
""")


def test_multidevice_collectives_subprocess():
    from conftest import cpu_subprocess_env
    r = subprocess.run([sys.executable, "-c", MULTIDEV], capture_output=True,
                       text=True, env=cpu_subprocess_env(),
                       cwd="/root/repo", timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"]


def test_mini_dryrun_subprocess():
    """End-to-end dry-run machinery on a reduced config + 8-device mesh
    (the full 512-device sweep runs via launch/dryrun.py)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.configs.base import ShapeSpec
        from repro.distributed.sharding import ShardingPlan
        from repro.launch import specs as S
        from repro.launch.mesh import make_mesh
        from repro.launch.dryrun import _step_fn
        from repro.roofline import hlo as H
        from repro.roofline.report import RooflineTerms

        cfg = get_config("qwen2-1.5b").reduced()
        shape = ShapeSpec("mini_train", "train", 64, 8)
        mesh = make_mesh((4, 2), ("data", "model"))
        plan = ShardingPlan(mesh, cfg)
        sp = S.input_specs(cfg, shape)
        sh = S.input_shardings(plan, cfg, shape, sp)
        step = _step_fn(cfg, "train", moe_groups=plan.dp_size)
        with mesh:
            lowered = jax.jit(step, in_shardings=(sh["params"], sh["batch"],
                                                  sh["ctrl"])).lower(
                sp["params"], sp["batch"], sp["ctrl"])
            compiled = lowered.compile()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        cb, bd = H.collective_bytes(compiled.as_text())
        t = RooflineTerms(arch="mini", shape="mini_train", mesh="8dev",
                          chips=8, hlo_flops_per_device=ca["flops"],
                          hlo_bytes_per_device=ca["bytes accessed"],
                          collective_bytes_per_device=cb,
                          model_flops_total=S.model_flops(cfg, shape),
                          argument_bytes_per_device=ma.argument_size_in_bytes,
                          temp_bytes_per_device=ma.temp_size_in_bytes)
        assert t.t_compute > 0 and t.t_memory > 0
        assert cb > 0, "sharded train step must communicate"
        print(json.dumps({"ok": True, "dominant": t.dominant,
                          "coll_bytes": cb}))
    """)
    from conftest import cpu_subprocess_env
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=cpu_subprocess_env(),
                       cwd="/root/repo", timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["coll_bytes"] > 0
