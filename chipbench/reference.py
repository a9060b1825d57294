"""Plain float32 reference of the served prefill, and its lower-precision
control. Imports nothing of the program.

It follows the published architecture (pre-norm decoder: RMSNorm,
grouped-query attention with rotary positions, SwiGLU MLP, final norm
and LM head) and SuperServe's subnet semantics as the configuration
file states them:

- depth: the first ``ceil(L * depth_frac)`` layers run, the rest are
  skipped;
- FFN width: the first ``k`` hidden channels, ``k`` the nearest multiple
  of ``channel_align`` to ``intermediate_size * ffn_frac`` (at least one
  multiple, at most the full width);
- heads: in each KV group, the first ``round(group * head_frac)`` query
  heads (at least one) contribute; the others' outputs are zero;
- norms: every norm of subnet ``s`` uses its own gain row ``s``, the
  subnet's index in the product of the sorted depth, FFN and head
  fractions.

It runs one layer at a time, each matmul at full float32 precision, and
returns the logits at the last position of each prompt. ``control=int8``
or ``fp8`` computes the same with every weight matrix rounded to that
type (per output channel for int8): weight-only quantization, the step a
later change might take.
"""
from __future__ import annotations

import itertools
import math
from functools import partial
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def subnet_shape(cfg: Dict[str, Any], depth_frac: float, ffn_frac: float,
                 head_frac: float) -> Dict[str, int]:
    """Active layers, FFN channels, heads per KV group and gain row."""
    e = cfg["elastic"]
    align = e["channel_align"]
    f = cfg["intermediate_size"]
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    space = list(itertools.product(sorted(e["depth_fracs"]),
                                   sorted(e["ffn_fracs"]),
                                   sorted(e["head_fracs"])))
    return {
        "layers": max(1, math.ceil(cfg["num_hidden_layers"] * depth_frac)),
        "ffn": min(f, max(align, int(round(f * ffn_frac / align)) * align)),
        "heads_per_group": max(1, int(round(group * head_frac))),
        "row": space.index((depth_frac, ffn_frac, head_frac)),
    }


def quantize(w, control: Optional[str], axis: int = -2):
    """``w`` in float32, rounded to the control's type (``None``: as is).
    int8 scales each output channel (the slice along ``axis``'s
    complement) by its largest magnitude."""
    w = w.astype(jnp.float32)
    if control is None:
        return w
    if control == "int8":
        s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return jnp.round(w / s).clip(-127, 127) * s
    if control == "fp8":
        return w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(f"unknown control {control!r}")


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (n, S, H, hd), rotate-half convention, positions 0..S-1."""
    hd, S = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


@partial(jax.jit, static_argnames=("cfgk", "control"))
def _layer(x, lw, row, ffn, heads_per_group, *, cfgk, control):
    """One decoder layer in float32. ``cfgk``: hashable config items."""
    c = dict(cfgk)
    n, S, d = x.shape
    hq, hkv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    G = hq // hkv
    eps = c["rms_norm_eps"]
    a, m = lw["attn"], lw["mlp"]
    h = _rms(x, a["norm_gamma"][row].astype(jnp.float32), eps)
    q = jnp.matmul(h, quantize(a["wq"], control), precision=HI)
    k = jnp.matmul(h, quantize(a["wk"], control), precision=HI)
    v = jnp.matmul(h, quantize(a["wv"], control), precision=HI)
    if "bq" in a:
        q, k, v = (q + a["bq"].astype(jnp.float32), k + a["bk"].astype(jnp.float32),
                   v + a["bv"].astype(jnp.float32))
    q = _rope(q.reshape(n, S, hq, hd), c["rope_theta"])
    k = _rope(k.reshape(n, S, hkv, hd), c["rope_theta"])
    v = v.reshape(n, S, hkv, hd)
    kq = jnp.repeat(k, G, axis=2)                    # q head h reads kv h // G
    vq = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("nqhd,nkhd->nhqk", q, kq, precision=HI) * hd ** -0.5
    pos = jnp.arange(S)
    live = pos[None, :] <= pos[:, None]
    if c["sliding_window"]:
        live &= pos[None, :] > pos[:, None] - c["sliding_window"]
    s = jnp.where(live[None, None], s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, axis=-1), vq,
                   precision=HI)
    head_on = (jnp.arange(hq) % G) < heads_per_group
    o = o * head_on[None, None, :, None]
    x = x + jnp.matmul(o.reshape(n, S, hq * hd), quantize(a["wo"], control),
                       precision=HI)
    h = _rms(x, m["norm_gamma"][row].astype(jnp.float32), eps)
    act = (jax.nn.silu(jnp.matmul(h, quantize(m["wg"], control), precision=HI))
           * jnp.matmul(h, quantize(m["wu"], control), precision=HI))
    act = act * (jnp.arange(act.shape[-1]) < ffn)
    return x + jnp.matmul(act, quantize(m["wd"], control), precision=HI)


@partial(jax.jit, static_argnames=("control",))
def _embed(table, tokens, *, control):
    # rows of the table are tokens: int8 scales each row, so a gathered
    # row quantizes alone
    return quantize(table[tokens], control, axis=-1)


@partial(jax.jit, static_argnames=("eps", "tied", "control"))
def _head(x_last, gain, head, *, eps, tied, control):
    h = _rms(x_last, gain.astype(jnp.float32), eps)
    if tied:          # head is the (V, d) embedding table
        return jnp.matmul(h, quantize(head, control, axis=-1).T, precision=HI)
    return jnp.matmul(h, quantize(head, control), precision=HI)


CFG_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "sliding_window")


def logits(cfg: Dict[str, Any], weights: Dict[str, Any], sub: Dict[str, int],
           tokens: np.ndarray, control: Optional[str] = None) -> np.ndarray:
    """(n, V) float32 logits at the last position of each prompt in
    ``tokens`` (n, S), for the subnet ``sub`` (from :func:`subnet_shape`)."""
    cfgk = tuple((k, cfg[k] or 0) for k in CFG_KEYS)
    stage = weights["backbone"]["stages"][0]
    x = _embed(weights["embed"], jnp.asarray(tokens), control=control)
    for layer in range(sub["layers"]):
        lw = {"attn": jax.tree.map(lambda w: w[layer], stage["0:attn"]),
              "mlp": jax.tree.map(lambda w: w[layer], stage["1:mlp"])}
        x = _layer(x, lw, sub["row"], sub["ffn"], sub["heads_per_group"],
                   cfgk=cfgk, control=control)
    tied = cfg["tie_word_embeddings"]
    out = _head(x[:, -1], weights["final_gamma"][sub["row"]],
                weights["embed"] if tied else weights["head"],
                eps=cfg["rms_norm_eps"], tied=tied, control=control)
    return np.asarray(out)
