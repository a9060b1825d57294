"""Shared fixtures. NOTE: no XLA_FLAGS here — unit/smoke tests must see
the real single CPU device; multi-device tests spawn subprocesses."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig, ElasticSpec, Stage


def cpu_subprocess_env(**extra) -> dict:
    """Minimal env for a test's CPU-pinned python subprocess. Without
    ``JAX_PLATFORMS=cpu`` a host with the TPU library installed (but no
    chip attached) stalls for minutes looking for one."""
    env = {
        "PYTHONPATH": "src",
        "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
        "HOME": os.path.expanduser("~"),
        "JAX_PLATFORMS": "cpu",
    }
    env.update(extra)
    return env


def tiny_dense(**kw) -> ArchConfig:
    base = dict(
        name="tiny-dense", family="dense",
        stages=(Stage(("attn", "mlp"), repeat=3),),
        d_model=64, n_heads=4, n_kv_heads=2, d_ff=256, vocab_size=128,
        head_dim=16, dtype="float32",
        elastic=ElasticSpec(depth_fracs=(1 / 3, 2 / 3, 1.0),
                            ffn_fracs=(0.5, 1.0), head_fracs=(0.5, 1.0)),
    )
    base.update(kw)
    return ArchConfig(**base)


@pytest.fixture(scope="session")
def dense_cfg():
    return tiny_dense()


@pytest.fixture(scope="session")
def dense_params(dense_cfg):
    from repro.models import lm
    return lm.init_model(jax.random.PRNGKey(0), dense_cfg)


@pytest.fixture(scope="session")
def token_batch():
    key = jax.random.PRNGKey(7)
    toks = jax.random.randint(key, (2, 16), 0, 128)
    return {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
