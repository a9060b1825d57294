"""WeightSlice Pallas TPU kernel: matmul over the *active prefix* of the
contraction and output dimensions.

The SubNetAct insight at kernel level: the active widths arrive as
scalar-prefetch values, the grid's index_map routes inactive K/N blocks
back to block 0 (no fresh DMA) and ``pl.when`` skips their compute —
so a half-width subnet costs ~half the MXU work and ~half the HBM->VMEM
traffic of the full supernet layer, with zero weight movement and zero
recompilation on actuation.

Block sizes are MXU-aligned (multiples of 128 lanes / 8 sublanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(nact_ref, x_ref, w_ref, o_ref, acc_ref, *, bk: int, nk: int):
    """Grid: (m, n, k). nact_ref holds (k_blocks_active, n_blocks_active)."""
    mi, ni, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    k_act, n_act = nact_ref[0], nact_ref[1]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(ki < k_act, ni < n_act))
    def _compute():
        # Partial K block: mask trailing channels of the boundary block.
        x = x_ref[...]
        w = w_ref[...]
        acc_ref[...] += jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[...] = jnp.where(ni < n_act, acc_ref[...], 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def sliced_matmul(x, w, active_in, active_out, *, bm: int = 128, bk: int = 128,
                  bn: int = 128, interpret: bool = False):
    """y[..., :active_out] = x[..., :active_in] @ w[:active_in, :active_out].

    ``active_in``/``active_out`` are traced int32 scalars (the WeightSlice
    control inputs). Widths are rounded up to block granularity — the
    core/subnet.py control lowering aligns widths to 128, so blocks are
    exact for every real subnet.
    """
    orig_shape = x.shape
    M = 1
    for s in orig_shape[:-1]:
        M *= s
    K = x.shape[-1]
    N = w.shape[1]
    x2 = x.reshape(M, K)

    pm, pk, pn = (-M) % bm, (-K) % bk, (-N) % bn
    if pm or pk:
        x2 = jnp.pad(x2, ((0, pm), (0, pk)))
    wp = jnp.pad(w, ((0, pk), (0, pn))) if (pk or pn) else w
    Mp, Kp, Np = x2.shape[0], x2.shape[1], wp.shape[1]
    nk = Kp // bk

    # zero channels of x beyond active_in so a partial boundary block
    # contributes nothing (then whole blocks beyond it are skipped)
    x2 = x2 * (lax.iota(jnp.int32, Kp)[None, :] < active_in).astype(x2.dtype)

    nact = jnp.stack([
        lax.div(active_in + bk - 1, bk).astype(jnp.int32),
        lax.div(active_out + bn - 1, bn).astype(jnp.int32),
    ])

    grid = (Mp // bm, Np // bn, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # inactive K blocks re-map to block 0: no fresh DMA
                pl.BlockSpec((bm, bk),
                             lambda m, n, k, nact: (m, jnp.minimum(k, nact[0] - 1))),
                pl.BlockSpec((bk, bn),
                             lambda m, n, k, nact: (jnp.minimum(k, nact[0] - 1),
                                                    jnp.minimum(n, nact[1] - 1))),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda m, n, k, nact: (m, n)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        interpret=interpret,
        name="sliced_matmul",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(nact, x2, wp)
    out = out[:M, :N]
    # mask the partial boundary block of the output dimension
    out = out * (lax.iota(jnp.int32, N)[None, :] < active_out).astype(out.dtype)
    return out.reshape(*orig_shape[:-1], N)
