"""The main path's Pallas kernels and the whole qwen2-1.5b prefill step,
compiled for a described TPU v5e chip (no chip attached).

Interpret mode runs the kernel bodies on the CPU but never asks Mosaic,
the TPU kernel compiler, whether it accepts them: block tiling, vector
indexing and VMEM limits are only checked here. Each test lowers at
qwen2-1.5b's published widths and asserts that the kernel survived as
a ``tpu_custom_call``; the whole-step test also asserts that the
program fits one 16 GiB chip.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and the
test workers all import this file.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config

CHIP_HBM_BYTES = 16 * 2**30
QWEN = get_config("qwen2-1.5b")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_calls(compiled) -> dict:
    """Count the program's ``tpu_custom_call``s by kernel name (each
    ``pallas_call`` is named after its kernel)."""
    counts: dict = {}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT )?%([A-Za-z_]+)", line).group(1)
            counts[name] = counts.get(name, 0) + 1
    return counts


def _assert_kernel(compiled, name):
    assert _kernel_calls(compiled) == {name: 1}


@pytest.mark.parametrize("seq", [16, 2048])
def test_flash_attention_compiles_for_v5e(one_chip, seq):
    from repro.kernels.flash_attention import flash_attention
    hd = QWEN.resolved_head_dim
    q = _spec((2, QWEN.n_heads, seq, hd), jnp.bfloat16, one_chip)
    kv = _spec((2, QWEN.n_kv_heads, seq, hd), jnp.bfloat16, one_chip)
    _assert_kernel(flash_attention.lower(
        q, kv, kv, causal=True, q_block=512, kv_block=512).compile(), "flash_attention")


def test_decode_attention_compiles_for_v5e(one_chip):
    from repro.kernels.decode_attention import decode_attention
    hd = QWEN.resolved_head_dim
    q = _spec((8, QWEN.n_heads, 1, hd), jnp.bfloat16, one_chip)
    cache = _spec((8, QWEN.n_kv_heads, 2048, hd), jnp.bfloat16, one_chip)
    index = _spec((), jnp.int32, one_chip)
    _assert_kernel(decode_attention.lower(
        q, cache, cache, index, kv_block=512).compile(), "decode_attention")


def test_subnet_rmsnorm_compiles_for_v5e(one_chip):
    from repro.kernels.subnet_rmsnorm import subnet_rmsnorm
    n_subnets = QWEN.elastic.num_subnets
    assert n_subnets == 18
    x = _spec((8, 256, QWEN.d_model), jnp.bfloat16, one_chip)
    table = _spec((n_subnets, QWEN.d_model), jnp.float32, one_chip)
    sid = _spec((), jnp.int32, one_chip)
    _assert_kernel(subnet_rmsnorm.lower(x, table, sid).compile(), "subnet_rmsnorm")


def test_sliced_matmul_compiles_for_v5e(one_chip):
    from repro.kernels.sliced_matmul import sliced_matmul
    x = _spec((8, 256, QWEN.d_model), jnp.bfloat16, one_chip)
    w = _spec((QWEN.d_model, QWEN.d_ff), jnp.bfloat16, one_chip)
    width = _spec((), jnp.int32, one_chip)
    _assert_kernel(sliced_matmul.lower(x, w, width, width).compile(), "sliced_matmul")


def test_full_width_prefill_step_compiles_and_fits_v5e(one_chip,
                                                       monkeypatch):
    """The executor's prefill step over the whole bf16 supernet (28L,
    18 Pareto subnets' control stack), B=8, S=256, with the ``tpu``
    kernel tier forced in the model path."""
    from repro.core.pareto import pareto_subnets
    from repro.kernels import ops
    from repro.models import lm
    from repro.serving.executor import prefill_fn, stacked_controls

    monkeypatch.setattr(ops, "model_tier", lambda: "tpu")
    params = jax.eval_shape(lambda: lm.init_model(jax.random.PRNGKey(0),
                                                  QWEN))
    stacked = stacked_controls(QWEN, pareto_subnets(QWEN))
    assert len(stacked["subnet_id"]) == 18

    def shaped(tree):
        return jax.tree.map(
            lambda a: _spec(a.shape, a.dtype, one_chip), tree)

    B, S = 8, 256
    compiled = jax.jit(prefill_fn(QWEN)).lower(
        shaped(params), shaped(stacked),
        _spec((B, S), jnp.int32, one_chip), _spec((), jnp.int32, one_chip),
        _spec((B,), jnp.int32, one_chip)).compile()
    # the attention kernel (one scanned layer body) and the norm kernel
    # (attention and MLP pre-norms, final norm) all survived
    assert _kernel_calls(compiled) == {"flash_attention": 1,
                                       "subnet_rmsnorm": 3}
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert need < CHIP_HBM_BYTES, need
