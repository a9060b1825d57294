"""Kernel-tier resolution + block-skipping ref numerics.

Two concerns:

* **Resolution** — the tier follows the platform (``tpu`` on a TPU
  backend; ``interpret`` for the process and ``ref`` for model paths
  elsewhere), an explicit ``REPRO_KERNEL_TIER`` is honored verbatim
  where the host can run it, and fails *loudly* (never silently
  substituted) where not.
* **Numerics** — the block-skipping ref tier agrees with the dense
  oracle across causal/window/kv_len corners (property-tested).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.kernels import ops, ref
from repro.kernels.dispatch import DISPATCHER, model_tier

from _hypothesis_compat import given, settings, strategies as st

_TOL = dict(rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# resolution on CPU
# --------------------------------------------------------------------------


def test_probed_chain_skips_triton_off_gpu():
    """Off a TPU the process tier is the interpreter and model paths
    take the ref/XLA path; the ``tpu`` tier is not available."""
    if compat.is_tpu_backend():
        pytest.skip("TPU attached; the platform tier is tpu")
    if compat.explicit_kernel_tier() is not None:
        pytest.skip("explicit tier pinned in this process")
    assert not compat.tier_available("tpu")
    assert compat.kernel_tier() == "interpret"
    assert model_tier() == "ref"
    tier, _ = DISPATCHER.resolve("flash_attention", None)
    assert tier == "interpret"


def test_model_tier_follows_platform(monkeypatch):
    """On a TPU backend model paths run the ``tpu`` kernels: no probe
    can drop them to ``ref`` behind the operator's back."""
    if compat.explicit_kernel_tier() is not None:
        pytest.skip("explicit tier pinned in this process")
    monkeypatch.setattr(compat, "is_tpu_backend", lambda: True)
    assert model_tier() == "tpu"
    monkeypatch.setattr(compat, "is_tpu_backend", lambda: False)
    assert model_tier() == "ref"


def test_model_calls_unchanged_by_triton_registration():
    """CPU model attention routes to the block-skipping XLA path and
    matches it exactly."""
    if compat.explicit_kernel_tier() is not None:
        pytest.skip("explicit tier pinned in this process")
    if compat.is_tpu_backend():
        pytest.skip("accelerator attached")
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 48, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 48, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 48, 16), jnp.float32)
    got = ops.model_flash_attention(q, k, v, causal=True)
    from repro.models.attention import flash_attention as xla_flash
    want = xla_flash(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_env_override_honored_verbatim(monkeypatch):
    """REPRO_KERNEL_TIER=tpu pins process AND model tier when the host
    can serve it."""
    real_avail = compat.tier_available
    monkeypatch.setattr(compat, "tier_available",
                        lambda t: True if t == "tpu" else real_avail(t))
    monkeypatch.setenv("REPRO_KERNEL_TIER", "tpu")
    compat.reset_kernel_tier()
    try:
        assert compat.kernel_tier() == "tpu"
        assert compat.explicit_kernel_tier() == "tpu"
        assert model_tier() == "tpu"
        for name in ("flash_attention", "decode_attention",
                     "sliced_matmul", "subnet_rmsnorm"):
            tier, _ = DISPATCHER.resolve(name, None)
            assert tier == "tpu", name
    finally:
        compat.reset_kernel_tier()


def test_env_override_unavailable_fails_loudly(monkeypatch):
    """An explicit tier the host cannot serve raises instead of being
    silently swapped — 'verbatim or error', never 'verbatim-ish'."""
    if compat.tier_available("tpu"):
        pytest.skip("TPU attached; the override would be legal here")
    monkeypatch.setenv("REPRO_KERNEL_TIER", "tpu")
    compat.reset_kernel_tier()
    try:
        with pytest.raises(RuntimeError):
            compat.kernel_tier()
    finally:
        compat.reset_kernel_tier()


# --------------------------------------------------------------------------
# block-skipping ref == dense oracle (property)
# --------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(sq=st.integers(min_value=1, max_value=96),
       sk=st.integers(min_value=1, max_value=96),
       qb=st.sampled_from([0, 16, 32, 256]),
       kb=st.sampled_from([0, 16, 32, 256]),
       causal=st.sampled_from([True, False]),
       window=st.sampled_from([0, 8, 24]),
       kv_frac=st.floats(min_value=0.1, max_value=1.0))
def test_skip_ref_matches_dense_ref(sq, sk, qb, kb, causal, window, kv_frac):
    ks = jax.random.split(jax.random.PRNGKey(sq * 97 + sk), 3)
    q = jax.random.normal(ks[0], (1, 4, sq, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, sk, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, sk, 16), jnp.float32)
    for kv_len in (None, max(1, int(sk * kv_frac)),
                   jnp.int32(max(1, int(sk * kv_frac)))):
        got = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                      kv_len=kv_len, q_block=qb, kv_block=kb)
        want = ref.flash_attention_dense_ref(q, k, v, causal=causal,
                                             window=window, kv_len=kv_len)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


@settings(max_examples=15, deadline=None)
@given(smax=st.sampled_from([64, 96, 200]),
       kb=st.sampled_from([0, 16, 32, 512]),
       window=st.sampled_from([0, 16]),
       idx_frac=st.floats(min_value=0.0, max_value=1.0))
def test_skip_decode_matches_dense_decode(smax, kb, window, idx_frac):
    ks = jax.random.split(jax.random.PRNGKey(smax), 3)
    q = jax.random.normal(ks[0], (1, 4, 1, 16), jnp.float32)
    kc = jax.random.normal(ks[1], (1, 2, smax, 16), jnp.float32)
    vc = jax.random.normal(ks[2], (1, 2, smax, 16), jnp.float32)
    idx = jnp.int32(min(smax - 1, int(smax * idx_frac)))
    got = ref.decode_attention_ref(q, kc, vc, idx, window=window,
                                   kv_block=kb)
    want = ref.decode_attention_dense_ref(q, kc, vc, idx, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)


def test_xla_model_path_matches_dense_with_offset():
    """The block-skipping XLA prefill (models/attention.py) agrees with
    the dense oracle under a static q_offset (chunked prefill)."""
    from repro.models.attention import flash_attention as xla_flash
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 4, 32, 16), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 96, 16), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 96, 16), jnp.float32)
    for off in (0, 64):
        got = xla_flash(q, k, v, causal=True, q_offset=off,
                        q_block=16, kv_block=32)
        qpad = jnp.pad(q, ((0, 0), (0, 0), (off, 0), (0, 0)))
        want = ref.flash_attention_dense_ref(qpad, k, v,
                                             causal=True)[:, :, off:]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_TOL)
