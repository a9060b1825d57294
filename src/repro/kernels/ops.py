"""Public kernel entry points, routed through the three-tier dispatcher.

Every kernel resolves to one of the tiers registered in
:mod:`repro.kernels.dispatch` — ``tpu`` (compiled Pallas),
``interpret`` (Pallas interpreter; CPU numerics validation), ``ref``
(pure-jnp from :mod:`repro.kernels.ref`, block-skipping for the
attention kernels). The process default comes from
:func:`repro.compat.kernel_tier`; per-call overrides take ``tier=`` (or
the legacy ``interpret=`` bool, mapped to ``interpret``/``tpu``).
"""
from __future__ import annotations

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.dispatch import (DISPATCHER, coerce_tier, model_tier,
                                    register)
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.sliced_matmul import sliced_matmul as _sliced_pallas
from repro.kernels.subnet_rmsnorm import subnet_rmsnorm as _rmsnorm_pallas


@register("flash_attention", "tpu")
def _flash_tpu(q, k, v, *, causal, window, kv_len, q_block, kv_block):
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         kv_len=kv_len, q_block=q_block,
                         kv_block=kv_block, interpret=False)


@register("flash_attention", "interpret")
def _flash_interpret(q, k, v, *, causal, window, kv_len, q_block, kv_block):
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         kv_len=kv_len, q_block=q_block,
                         kv_block=kv_block, interpret=True)


@register("decode_attention", "tpu")
def _decode_tpu(q, k_cache, v_cache, index, *, window, kv_block):
    return _decode_pallas(q, k_cache, v_cache, index, window=window,
                          kv_block=kv_block, interpret=False)


@register("decode_attention", "interpret")
def _decode_interpret(q, k_cache, v_cache, index, *, window, kv_block):
    return _decode_pallas(q, k_cache, v_cache, index, window=window,
                          kv_block=kv_block, interpret=True)


@register("sliced_matmul", "tpu")
def _sliced_tpu(x, w, active_in, active_out, *, bm, bk, bn):
    return _sliced_pallas(x, w, active_in, active_out, bm=bm, bk=bk,
                          bn=bn, interpret=False)


@register("sliced_matmul", "interpret")
def _sliced_interpret(x, w, active_in, active_out, *, bm, bk, bn):
    return _sliced_pallas(x, w, active_in, active_out, bm=bm, bk=bk,
                          bn=bn, interpret=True)


@register("subnet_rmsnorm", "tpu")
def _rmsnorm_tpu(x, gamma_table, subnet_id, *, eps):
    return _rmsnorm_pallas(x, gamma_table, subnet_id, eps=eps,
                           interpret=False)


@register("subnet_rmsnorm", "interpret")
def _rmsnorm_interpret(x, gamma_table, subnet_id, *, eps):
    return _rmsnorm_pallas(x, gamma_table, subnet_id, eps=eps,
                           interpret=True)


@register("flash_attention", "ref")
def _flash_ref(q, k, v, *, causal, window, kv_len, q_block=256, kv_block=256):
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   kv_len=kv_len, q_block=q_block,
                                   kv_block=kv_block)


@register("decode_attention", "ref")
def _decode_ref(q, k_cache, v_cache, index, *, window, kv_block=256):
    return ref.decode_attention_ref(q, k_cache, v_cache, index,
                                    window=window, kv_block=kv_block)


@register("sliced_matmul", "ref")
def _sliced_ref(x, w, active_in, active_out, *, bm=0, bk=0, bn=0):
    orig_shape = x.shape
    y = ref.sliced_matmul_ref(x.reshape(-1, x.shape[-1]), w,
                              active_in, active_out)
    return y.reshape(*orig_shape[:-1], w.shape[1])


@register("subnet_rmsnorm", "ref")
def _rmsnorm_ref(x, gamma_table, subnet_id, *, eps):
    return ref.subnet_rmsnorm_ref(x, gamma_table, subnet_id, eps=eps)


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------


def flash_attention(q, k, v, *, causal=True, window=0, kv_len=None,
                    q_block=256, kv_block=256, tier=None, interpret=None):
    return DISPATCHER.call(
        "flash_attention", q, k, v, causal=causal, window=window,
        kv_len=kv_len, q_block=q_block, kv_block=kv_block,
        tier=coerce_tier(tier, interpret))


def decode_attention(q, k_cache, v_cache, index, *, window=0, kv_block=256,
                     tier=None, interpret=None):
    return DISPATCHER.call(
        "decode_attention", q, k_cache, v_cache, index, window=window,
        kv_block=kv_block, tier=coerce_tier(tier, interpret))


def sliced_matmul(x, w, active_in, active_out, *, bm=128, bk=128, bn=128,
                  tier=None, interpret=None):
    return DISPATCHER.call(
        "sliced_matmul", x, w, active_in, active_out, bm=bm, bk=bk, bn=bn,
        tier=coerce_tier(tier, interpret))


def subnet_rmsnorm(x, gamma_table, subnet_id, *, eps=1e-5, tier=None,
                   interpret=None):
    return DISPATCHER.call(
        "subnet_rmsnorm", x, gamma_table, subnet_id, eps=eps,
        tier=coerce_tier(tier, interpret))


# --------------------------------------------------------------------------
# model-grade impls (the wiring used by models/attention + backbone)
# --------------------------------------------------------------------------


def model_flash_attention(q, k, v, *, causal=True, window=0, q_offset=0,
                          kv_len=None, q_block=512, kv_block=512, scale=None):
    """Full-sequence attention for model forward passes.

    Pallas kernel when the model tier says so;
    the block-skipping XLA path from :mod:`repro.models.attention`
    otherwise (same math, asserted equal by the kernel tests). The
    Pallas kernels do not take ``q_offset``/``scale`` — calls using
    them route to the XLA path on every tier rather than silently
    dropping the arguments. ``q_block``/``kv_block`` plumb through to
    whichever tier serves the call.
    """
    tier = model_tier()
    pallas_ok = isinstance(q_offset, int) and q_offset == 0 and scale is None
    if pallas_ok and tier != "ref":
        return flash_attention(q, k, v, causal=causal, window=window,
                               kv_len=kv_len, q_block=q_block,
                               kv_block=kv_block, tier=tier)
    from repro.models.attention import flash_attention as xla_flash
    return xla_flash(q, k, v, causal=causal, window=window, q_offset=q_offset,
                     kv_len=kv_len, q_block=q_block, kv_block=kv_block,
                     scale=scale)


def model_decode_attention(q, k_cache, v_cache, *, index, window=0,
                           kv_block=512):
    """Single-token cached decode for model decode steps: the Pallas
    kernel when the model tier says so, the XLA path otherwise."""
    tier = model_tier()
    if tier != "ref":
        return decode_attention(q, k_cache, v_cache, index, window=window,
                                kv_block=kv_block, tier=tier)
    from repro.models.attention import decode_attention as xla_decode
    return xla_decode(q, k_cache, v_cache, index=index, window=window)


def model_subnet_rmsnorm(x, gamma_table, subnet_id, *, eps=1e-5):
    """SubnetNorm (RMS flavor) for model blocks; None = use XLA path."""
    tier = model_tier()
    if tier != "ref":
        return subnet_rmsnorm(x, gamma_table, subnet_id, eps=eps, tier=tier)
    return None


# references re-exported for tests (the *_dense_ref pair are the
# mathematical oracles; the plain *_ref pair block-skip)
flash_attention_ref = ref.flash_attention_ref
flash_attention_dense_ref = ref.flash_attention_dense_ref
decode_attention_ref = ref.decode_attention_ref
decode_attention_dense_ref = ref.decode_attention_dense_ref
sliced_matmul_ref = ref.sliced_matmul_ref
subnet_rmsnorm_ref = ref.subnet_rmsnorm_ref
