"""Useful work of one served prefill call, from the configuration file
and the subnet the engine chose: the FLOPs the subnet needs for its real
rows, with the LM head at the last position only. Work the program does
beyond that (masked heads and FFN channels, padded rows, the LM head at
every position) is not useful and does not count."""
from __future__ import annotations

from typing import Any, Dict

from chipbench.reference import subnet_shape


def prefill_flops(cfg: Dict[str, Any], sub: Dict[str, float], rows: int,
                  seq: int) -> float:
    s = subnet_shape(cfg, sub["depth_frac"], sub["ffn_frac"], sub["head_frac"])
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hkv = cfg["num_key_value_heads"]
    ha = hkv * s["heads_per_group"]
    per_token = (2 * d * ha * hd            # q
                 + 2 * 2 * d * hkv * hd     # k, v
                 + 2 * ha * hd * d          # o
                 + 3 * 2 * d * s["ffn"])    # gate, up, down
    attn = 4 * ha * hd * seq * (seq + 1) // 2     # causal, per sequence
    per_row = s["layers"] * (seq * per_token + attn) + 2 * d * cfg["vocab_size"]
    return float(rows * per_row)
