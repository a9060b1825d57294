"""Single-token decode attention over a KV cache as a Pallas TPU kernel.

Decode is memory-bound: the cost is streaming the KV cache HBM->VMEM.
The grid walks kv blocks; blocks entirely beyond the current position
are neither DMA'd (index remap) nor computed (pl.when) — a 32k-slot
cache at position 1k reads ~1k slots. GQA handled by processing all G
query heads of one kv head per grid row (one cache stream feeds G
queries — the whole point of GQA at decode time).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            kb: int, nk: int, window: int, smax: int, scale: float):
    ki = pl.program_id(1)
    index = idx_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_first = ki * kb
    live = k_first <= index if not window else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (G, d)
        k = k_ref[0, 0].astype(jnp.float32)                 # (kb, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        pos = k_first + lax.iota(jnp.int32, kb)
        if window:
            age = (index - pos) % smax                   # rolling buffer
            mask = age < jnp.minimum(window, index + 1)
        else:
            mask = pos <= index
        s = jnp.where(mask[None, :], s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new) * mask[None, :]
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "kv_block", "interpret"))
def decode_attention(q, k_cache, v_cache, index, *, window: int = 0,
                     kv_block: int = 256, interpret: bool = False):
    """q: (B, Hq, 1, d); caches: (B, Hkv, Smax, d) -> (B, Hq, 1, d)."""
    B, Hq, _, d = q.shape
    _, Hkv, Smax, _ = k_cache.shape
    G = Hq // Hkv
    scale = float(d ** -0.5)

    kb = min(kv_block, Smax)
    pk = (-Smax) % kb
    if pk:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nk = (Smax + pk) // kb

    q3 = q.reshape(B, Hkv, G, d)
    idx = jnp.asarray(index, jnp.int32).reshape(1)

    def kv_index(bh, ki, idx_s):
        if not window:
            # blocks beyond the live prefix re-map to block 0
            ki = jnp.minimum(ki, lax.div(idx_s[0], kb))
        return (bh // Hkv, bh % Hkv, ki, 0)

    out = pl.pallas_call(
        functools.partial(_kernel, kb=kb, nk=nk, window=window, smax=Smax,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * Hkv, nk),
            in_specs=[
                pl.BlockSpec((1, 1, G, d),
                             lambda bh, ki, idx_s: (bh // Hkv, bh % Hkv, 0, 0)),
                pl.BlockSpec((1, 1, kb, d), kv_index),
                pl.BlockSpec((1, 1, kb, d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, 1, G, d),
                                   lambda bh, ki, idx_s: (bh // Hkv, bh % Hkv, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, d), v_cache.dtype),
        interpret=interpret,
        name="decode_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(idx, q3, k_cache, v_cache)
    return out.reshape(B, Hq, 1, d)
