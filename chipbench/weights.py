"""Seeded random weights in the program's parameter layout, made on the
device in one jitted call and in the type they are served in.

The benchmark makes the weights, not the program, so that the reference
(``reference.py``) takes nothing the program made. The layout is the
one ``repro.models.lm`` serves a dense attention + SwiGLU stack from:
per-layer leaves stacked along a leading layer axis, and per-subnet
norm gain tables (``(n_subnets, d)``, float32), one row per subnet.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import numpy as np

GAIN_JITTER = 0.1       # per-subnet gain rows: 1 + 0.1 N(0, 1)
BIAS_STD = 0.1


def n_subnets(cfg: Dict[str, Any]) -> int:
    e = cfg["elastic"]
    return len(e["depth_fracs"]) * len(e["ffn_fracs"]) * len(e["head_fracs"])


def layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Nested dict of ``(shape, dtype, kind)`` leaves; kind is ``"w"``
    (normal, std 1/sqrt(fan_in)), ``"gain"`` or ``"bias"``."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd, hq, hkv = (cfg["head_dim"], cfg["num_attention_heads"],
                   cfg["num_key_value_heads"])
    L, V, ns = cfg["num_hidden_layers"], cfg["vocab_size"], n_subnets(cfg)
    dt = cfg["torch_dtype"]
    attn = {"wq": ((L, d, hq * hd), dt, "w"), "wk": ((L, d, hkv * hd), dt, "w"),
            "wv": ((L, d, hkv * hd), dt, "w"), "wo": ((L, hq * hd, d), dt, "w"),
            "norm_gamma": ((L, ns, d), "float32", "gain")}
    if cfg["attention_bias"]:
        attn.update(bq=((L, hq * hd), dt, "bias"), bk=((L, hkv * hd), dt, "bias"),
                    bv=((L, hkv * hd), dt, "bias"))
    mlp = {"wg": ((L, d, f), dt, "w"), "wu": ((L, d, f), dt, "w"),
           "wd": ((L, f, d), dt, "w"), "norm_gamma": ((L, ns, d), "float32", "gain")}
    out = {"embed": ((V, d), dt, "w"),
           "backbone": {"stages": [{"0:attn": attn, "1:mlp": mlp}]},
           "final_gamma": ((ns, d), "float32", "gain")}
    if not cfg["tie_word_embeddings"]:
        out["head"] = ((d, V), dt, "w")
    return out


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[2], str)


def seed32(seed: int) -> int:
    """A 32-bit key for ``jax.random`` from any whole-number seed."""
    return int(np.random.default_rng([seed, 0]).integers(2 ** 31))


def make(cfg: Dict[str, Any], seed: int, device):
    """The weights for ``cfg`` from ``seed``, committed to ``device``."""
    import jax
    import jax.numpy as jnp

    spec = layout(cfg)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    def one(key, leaf: Tuple) -> Any:
        shape, dtype, kind = leaf
        z = jax.random.normal(key, shape, jnp.float32)
        if kind == "w":
            # fan_in: rows of a (in, out) matrix; the embedding table is
            # read by rows, so its fan-in is its width
            fan_in = shape[-1] if shape == (cfg["vocab_size"],
                                            cfg["hidden_size"]) else shape[-2]
            z = z / math.sqrt(fan_in)
        elif kind == "gain":
            z = 1.0 + GAIN_JITTER * z
        else:
            z = BIAS_STD * z
        return z.astype(dtype)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [one(k, leaf) for k, leaf
                                            in zip(keys, leaves)])

    sharding = jax.sharding.SingleDeviceSharding(device)
    with jax.default_device(device):
        key = jax.random.PRNGKey(seed32(seed))
    return jax.jit(init, out_shardings=sharding)(key)
