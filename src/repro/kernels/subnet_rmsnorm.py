"""SubnetNorm as a Pallas TPU kernel: RMSNorm whose gain row is fetched
from the per-subnet table by a scalar-prefetched ``subnet_id``.

This is SubNetAct's actuation cost made explicit at the kernel level:
switching subnets changes *one scalar*, which re-routes a single (1, d)
DMA — no weight movement, no recompilation, < 1 microsecond of extra
traffic (paper Fig 5b's "near-instantaneous actuation").

The table is viewed as ``(n_subnets, 1, d)`` so the gain block's last
two dims equal the array's: a ``(1, d)`` block over ``(n_subnets, d)``
breaks Mosaic's (8, 128) tiling rule for any ``n_subnets != 1``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(sid_ref, x_ref, g_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * g_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "eps", "interpret"))
def subnet_rmsnorm(x, gamma_table, subnet_id, *, bm: int = 256,
                   eps: float = 1e-5, interpret: bool = False):
    """x: (..., d); gamma_table: (n_subnets, d); subnet_id: traced int32."""
    orig_shape = x.shape
    d = x.shape[-1]
    M = 1
    for s in orig_shape[:-1]:
        M *= s
    x2 = x.reshape(M, d)
    bm_eff = min(bm, M)
    pm = (-M) % bm_eff
    if pm:
        x2 = jnp.pad(x2, ((0, pm), (0, 0)))
    sid = jnp.asarray(subnet_id, jnp.int32).reshape(1)

    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=((M + pm) // bm_eff,),
            in_specs=[
                pl.BlockSpec((bm_eff, d), lambda i, sid: (i, 0)),
                # the actuation: subnet_id routes the gain-row DMA
                pl.BlockSpec((pl.squeezed, 1, d),
                             lambda i, sid: (sid[0], 0, 0)),
            ],
            out_specs=pl.BlockSpec((bm_eff, d), lambda i, sid: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((M + pm, d), x.dtype),
        interpret=interpret,
        name="subnet_rmsnorm",
    )(sid, x2, gamma_table.reshape(gamma_table.shape[0], 1, d))
    return out[:M].reshape(orig_shape)
