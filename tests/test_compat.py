"""The installed JAX surfaces the repo calls directly + kernel
dispatcher tiers: import sweep over every repro.* module, tier
resolution, the compile cache switch, and per-kernel agreement between
the tiers this host can run.
"""
import importlib
import os
import pkgutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.kernels import ops, ref
from repro.kernels.dispatch import DISPATCHER, coerce_tier, model_tier

KERNELS = ("flash_attention", "decode_attention", "sliced_matmul",
           "subnet_rmsnorm")


# --------------------------------------------------------------------------
# import sweep: every module must import on this JAX version
# --------------------------------------------------------------------------


def _all_repro_modules():
    import repro
    names = ["repro"]
    for mod in pkgutil.walk_packages(repro.__path__, prefix="repro."):
        names.append(mod.name)
    return names


@pytest.mark.parametrize("name", _all_repro_modules())
def test_module_imports(name):
    """No repro.* module may blow up at import time on this host.

    This is the canary for version drift: the seed repo failed here on
    jax 0.4.37 (TPUCompilerParams rename, AxisType, AbstractMesh)."""
    before = os.environ.get("XLA_FLAGS")
    importlib.import_module(name)
    # importing never changes the process environment
    assert os.environ.get("XLA_FLAGS") == before


# --------------------------------------------------------------------------
# installed surfaces, called directly
# --------------------------------------------------------------------------


def test_compiler_params_resolve_on_this_version():
    """The kernels pass ``pltpu.CompilerParams`` directly: a field this
    release lacks raises instead of being dropped in silence."""
    from jax.experimental.pallas import tpu as pltpu
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")
    with pytest.raises(TypeError):
        pltpu.CompilerParams(not_a_real_field=1)


def test_make_abstract_mesh_both_signatures():
    from repro.configs import get_config
    from repro.distributed.sharding import ShardingPlan
    mesh = ShardingPlan.abstract((2, 16, 16), ("pod", "data", "model"),
                                 get_config("qwen2-1.5b")).mesh
    assert tuple(mesh.axis_names) == ("pod", "data", "model")
    assert dict(mesh.shape) == {"pod": 2, "data": 16, "model": 16}


def test_make_mesh_single_device():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    assert mesh.shape["data"] == 1


def test_cpu_subprocess_env_pins_backend():
    from conftest import cpu_subprocess_env
    env = cpu_subprocess_env(EXTRA="x")
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PYTHONPATH"] == "src"
    assert env["EXTRA"] == "x"


# --------------------------------------------------------------------------
# tier resolution
# --------------------------------------------------------------------------


def test_process_tier_valid_and_available():
    tier = compat.kernel_tier()
    assert tier in compat.KERNEL_TIERS
    assert compat.tier_available(tier)
    if not compat.is_tpu_backend():
        assert tier != "tpu"


def test_ref_tier_always_available():
    assert compat.tier_available("ref")


def test_interpret_probe_runs_here():
    # this repo's CI floor: the Pallas interpreter must work on CPU
    assert compat.tier_available("interpret")
    x = jnp.ones((8, 128), jnp.float32)
    gt = jnp.full((2, 128), 2.0, jnp.float32)
    y = ops.subnet_rmsnorm(x, gt, jnp.int32(1), tier="interpret")
    np.testing.assert_allclose(np.asarray(y), 2.0, rtol=1e-5)


def test_compile_cache_env_stays_in_charge(monkeypatch, tmp_path):
    monkeypatch.setenv(compat.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compat.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(compat.CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compat.enable_compile_cache()
        assert path == compat.enable_compile_cache()      # stable
        assert path.endswith(".jax_cache")
        assert (compat.DEFAULT_CACHE_DIR.parent / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_set_kernel_tier_validates():
    with pytest.raises(ValueError):
        compat.set_kernel_tier("gpu")
    if not compat.is_tpu_backend():
        with pytest.raises(RuntimeError):
            compat.set_kernel_tier("tpu")


def test_env_override_respected():
    before = compat.kernel_tier()
    saved = os.environ.get("REPRO_KERNEL_TIER")
    os.environ["REPRO_KERNEL_TIER"] = "ref"
    try:
        compat.reset_kernel_tier()
        assert compat.kernel_tier() == "ref"
        assert compat.explicit_kernel_tier() == "ref"
        assert model_tier() == "ref"
    finally:
        if saved is None:
            os.environ.pop("REPRO_KERNEL_TIER", None)
        else:
            os.environ["REPRO_KERNEL_TIER"] = saved
        compat.reset_kernel_tier()
    assert compat.kernel_tier() == before


def test_set_kernel_tier_roundtrip():
    before = compat.kernel_tier()
    try:
        assert compat.set_kernel_tier("ref") == "ref"
        assert compat.kernel_tier() == "ref"
        assert compat.explicit_kernel_tier() == "ref"
    finally:
        compat.reset_kernel_tier()
    assert compat.kernel_tier() == before


def test_model_tier_never_probed_interpret():
    if compat.explicit_kernel_tier() is None:
        assert model_tier() in ("tpu", "ref")


def test_coerce_tier_legacy_interpret_flag():
    assert coerce_tier(None, None) is None
    assert coerce_tier(None, True) == "interpret"
    assert coerce_tier(None, False) == "tpu"
    assert coerce_tier("ref", True) == "ref"      # explicit tier wins


# --------------------------------------------------------------------------
# dispatcher registry
# --------------------------------------------------------------------------


def test_all_kernels_registered_all_tiers():
    assert set(KERNELS) <= set(DISPATCHER.kernels())
    for name in KERNELS:
        tiers = DISPATCHER.registered_tiers(name)
        assert tiers == compat.KERNEL_TIERS


def test_resolve_unknown_kernel_raises():
    with pytest.raises(KeyError):
        DISPATCHER.resolve("not_a_kernel")
    with pytest.raises(ValueError):
        DISPATCHER.register("flash_attention", "not_a_tier", lambda: None)


def test_resolve_never_falls_down_the_chain():
    DISPATCHER.register("_chain_probe", "ref", lambda: "ref")
    try:
        # process tier here is interpret (CPU) or tpu; the only
        # registered tier is ref, and resolution must not substitute it
        with pytest.raises(KeyError, match="no 'interpret' tier|no 'tpu'"):
            DISPATCHER.resolve("_chain_probe")
        tier, fn = DISPATCHER.resolve("_chain_probe", "ref")
        assert tier == "ref" and fn() == "ref"
    finally:
        DISPATCHER._impls.pop("_chain_probe")


# --------------------------------------------------------------------------
# fallback-tier agreement, one test per kernel
# --------------------------------------------------------------------------

_TOL = dict(rtol=2e-3, atol=2e-3)


def _host_tiers(name):
    """Tiers executable on this host for ``name`` (the compiled tier
    needs its TPU; interpret mode covers the kernel bodies on CPU)."""
    return [t for t in DISPATCHER.registered_tiers(name)
            if t != "ref" and compat.tier_available(t)]


def test_tier_agreement_flash_attention():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 32, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 32, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 32, 16), jnp.float32)
    want = ops.flash_attention(q, k, v, tier="ref")
    for tier in _host_tiers("flash_attention"):
        got = ops.flash_attention(q, k, v, q_block=16, kv_block=16, tier=tier)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


def test_tier_agreement_decode_attention():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 1, 16), jnp.float32)
    kc = jax.random.normal(ks[1], (1, 2, 64, 16), jnp.float32)
    vc = jax.random.normal(ks[2], (1, 2, 64, 16), jnp.float32)
    want = ops.decode_attention(q, kc, vc, jnp.int32(17), tier="ref")
    for tier in _host_tiers("decode_attention"):
        got = ops.decode_attention(q, kc, vc, jnp.int32(17), kv_block=16,
                                   tier=tier)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


def test_tier_agreement_sliced_matmul():
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (128, 256), jnp.float32)
    ai, ao = jnp.int32(128), jnp.int32(128)
    want = ops.sliced_matmul(x, w, ai, ao, tier="ref")
    assert want.shape == (2, 16, 256)
    for tier in _host_tiers("sliced_matmul"):
        got = ops.sliced_matmul(x, w, ai, ao, tier=tier)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


def test_tier_agreement_subnet_rmsnorm():
    x = jax.random.normal(jax.random.PRNGKey(4), (24, 128), jnp.float32)
    gt = jax.random.normal(jax.random.PRNGKey(5), (3, 128), jnp.float32)
    for sid in (0, 2):
        want = ops.subnet_rmsnorm(x, gt, jnp.int32(sid), tier="ref")
        for tier in _host_tiers("subnet_rmsnorm"):
            got = ops.subnet_rmsnorm(x, gt, jnp.int32(sid), tier=tier)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       **_TOL)


def test_model_impls_match_kernel_tiers():
    """The model-grade wrappers agree with the oracle regardless of
    which tier they resolved to on this host."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (1, 4, 32, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 32, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 32, 16), jnp.float32)
    got = ops.model_flash_attention(q, k, v)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)

    qd = jax.random.normal(ks[0], (1, 4, 1, 16), jnp.float32)
    got = ops.model_decode_attention(qd, k, v, index=jnp.int32(9))
    want = ref.decode_attention_ref(qd, k, v, 9)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)
