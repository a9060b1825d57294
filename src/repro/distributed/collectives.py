"""Explicit shard_map collectives — the beyond-paper distributed
optimizations.

``seq_sharded_decode``: flash-decode over a *sequence-sharded* KV cache
(SP). Each shard computes partial online-softmax statistics (m, l, o)
over its local cache slice; the cross-shard combine is three tiny
collectives (pmax on m, psum on l and o) instead of all-gathering the
cache — for a 512k-token cache sharded 256 ways that is ~KBs of ICI
traffic instead of GBs.

``ring_allgather_kv``: collective-permute ring all-gather used by the
perf pass to overlap KV movement with per-step compute where SP is not
available.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _partial_decode(q, k, v, first_pos, index):
    """Local online-softmax stats for one cache shard.

    q: (B, Hkv, G, d); k/v: (B, Hkv, S_loc, d); first_pos: absolute
    position of this shard's slot 0. Returns (m, l, o)."""
    qf = q.astype(jnp.float32)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, k.astype(jnp.float32))
    s = s * (q.shape[-1] ** -0.5)
    pos = first_pos + lax.iota(jnp.int32, k.shape[2])
    mask = pos <= index
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m = s.max(axis=-1)                                   # (B,Hkv,G)
    p = jnp.exp(s - m[..., None]) * mask[None, None, None]
    l = p.sum(axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return m, l, o


def seq_sharded_decode(mesh: Mesh, q, k_cache, v_cache, index,
                       seq_axes: Tuple[str, ...] = ("data",)):
    """Decode attention with the KV cache sharded along sequence.

    q: (B, Hq, 1, d) replicated over ``seq_axes``;
    caches: (B, Hkv, S, d) sharded on S over ``seq_axes``.
    Returns (B, Hq, 1, d) replicated over ``seq_axes``.
    """
    B, Hq, _, d = q.shape
    _, Hkv, S, _ = k_cache.shape
    G = Hq // Hkv
    n_shards = 1
    for a in seq_axes:
        n_shards *= mesh.shape[a]
    s_loc = S // n_shards

    ax = seq_axes if len(seq_axes) > 1 else seq_axes[0]

    def body(q_, k_, v_):
        first = lax.axis_index(seq_axes) * s_loc
        q3 = q_.reshape(B, Hkv, G, d)
        m, l, o = _partial_decode(q3, k_, v_, first, index)
        # cross-shard online-softmax combine: 3 tiny collectives
        m_g = lax.pmax(m, ax)
        corr = jnp.exp(m - m_g)
        l_g = lax.psum(l * corr, ax)
        o_g = lax.psum(o * corr[..., None], ax)
        out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
        return out.reshape(B, Hq, 1, d).astype(v_.dtype)

    spec_q = P(None, None, None, None)
    spec_kv = P(None, None, ax, None)
    out = jax.shard_map(body, mesh=mesh,
                        in_specs=(spec_q, spec_kv, spec_kv),
                        out_specs=spec_q, check_vma=False)(q, k_cache, v_cache)
    return out


def seq_sharded_decode_ref(q, k_cache, v_cache, index):
    """Unsharded oracle for the combine (tests)."""
    from repro.kernels.ref import decode_attention_ref
    return decode_attention_ref(q, k_cache, v_cache, index)


def ring_allgather(mesh: Mesh, x, axis: str):
    """Collective-permute ring all-gather along ``axis`` (double-buffered
    building block for overlap experiments; perf pass only)."""
    n = mesh.shape[axis]

    def body(x_):
        def step(i, carry):
            buf, cur = carry
            nxt = lax.ppermute(cur, axis, [(j, (j + 1) % n) for j in range(n)])
            buf = lax.dynamic_update_index_in_dim(
                buf, nxt, (lax.axis_index(axis) - i - 1) % n, 0)
            return buf, nxt
        buf0 = jnp.zeros((n,) + x_.shape, x_.dtype)
        buf0 = lax.dynamic_update_index_in_dim(buf0, x_, lax.axis_index(axis), 0)
        buf, _ = lax.fori_loop(0, n - 1, step, (buf0, x_))
        return buf

    return jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                         out_specs=P(None, axis), check_vma=False)(x)
