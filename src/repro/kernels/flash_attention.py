"""Blockwise online-softmax (flash) attention as a Pallas TPU kernel.

TPU adaptation of the paper's serving hot loop for the assigned LM
archs: q/k/v tiles stream HBM->VMEM block-by-block; softmax statistics
(m, l) and the output accumulator live in VMEM scratch across the kv
grid axis. Causally-dead kv blocks are skipped: their DMA is remapped to
block 0 and their compute predicated out, so prefill cost tracks the
~S^2/2 causal triangle rather than S^2.

Supports GQA (Hq % Hkv == 0) via head-index arithmetic in the
index_maps, sliding windows, and a traced valid-KV length (decode /
chunked prefill over a cache).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            qb: int, kb: int, nk: int, causal: bool, window: int, scale: float):
    qi, ki = pl.program_id(1), pl.program_id(2)
    kv_len = lens_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_first = qi * qb
    q_pos = q_first + lax.iota(jnp.int32, qb)
    k_first = ki * kb
    # block-level liveness (causal upper-triangle + window lower bound);
    # scalar bounds, since Mosaic cannot index a vector by position
    live = k_first < kv_len
    if causal:
        live &= k_first <= q_first + qb - 1
    if window:
        live &= (k_first + kb) > (q_first - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (qb, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (kb, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        k_pos = k_first + lax.iota(jnp.int32, kb)
        mask = k_pos[None, :] < kv_len
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new) * mask    # mask again: fully-dead rows
        corr = jnp.exp(m_prev - m_new)   # would otherwise get exp(0)=1
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "q_block", "kv_block", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    kv_len=None, q_block: int = 256, kv_block: int = 256,
                    scale=None, interpret: bool = False):
    """q: (B, Hq, Sq, d); k/v: (B, Hkv, Sk, d) -> (B, Hq, Sq, d)."""
    B, Hq, Sq, d = q.shape
    _, Hkv, Sk, _ = k.shape
    G = Hq // Hkv
    scale = float(scale if scale is not None else d ** -0.5)

    qb, kb = min(q_block, Sq), min(kv_block, Sk)
    pq, pk = (-Sq) % qb, (-Sk) % kb
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    Sqp, Skp = Sq + pq, Sk + pk
    nq, nk = Sqp // qb, Skp // kb

    lens = jnp.array([Sk if kv_len is None else kv_len], jnp.int32)

    grid = (B * Hq, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, qb=qb, kb=kb, nk=nk, causal=causal,
                          window=window, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, qb, d),
                             lambda bh, qi, ki, lens: (bh // Hq, bh % Hq, qi, 0)),
                # causally-dead kv blocks re-map to block 0 (no new DMA)
                pl.BlockSpec((1, 1, kb, d),
                             _kv_index(Hq, Hkv, qb, kb, causal)),
                pl.BlockSpec((1, 1, kb, d),
                             _kv_index(Hq, Hkv, qb, kb, causal)),
            ],
            out_specs=pl.BlockSpec((1, 1, qb, d),
                                   lambda bh, qi, ki, lens: (bh // Hq, bh % Hq, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((qb, 1), jnp.float32),
                pltpu.VMEM((qb, 1), jnp.float32),
                pltpu.VMEM((qb, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sqp, d), v.dtype),
        interpret=interpret,
        name="flash_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(lens, q, k, v)
    return out[:, :, :Sq]


def _kv_index(Hq: int, Hkv: int, qb: int, kb: int, causal: bool):
    G = Hq // Hkv
    def index(bh, qi, ki, lens):
        b, h = bh // Hq, (bh % Hq) // G
        if causal:
            # clamp dead blocks (k_start > q_end) back to block 0
            last_live = ((qi + 1) * qb - 1) // kb
            ki = jnp.minimum(ki, last_live)
        return (b, h, ki, 0)
    return index
