"""Open-loop traffic from a traffic file and a seed.

Every seed offers the same work: the gaps between arrivals are one fixed
set, drawn once from ``GAP_SEED`` and scaled so that they fill the
window exactly, and the seed only permutes them. The prompts are drawn
from the seed.

- ``"arrivals": "poisson"``: exponential gaps at ``rate_qps``.
- ``"arrivals": "bursty"``: an even base stream at ``base_share`` of the
  rate, plus a stream of gamma gaps with squared coefficient of
  variation ``cv2`` at the rest of it (the paper's Fig. 12a
  construction, as ``repro.serving.traces.bursty_trace`` builds it).
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

GAP_SEED = 20231227      # the fixed set of gaps; seeds only reorder it


def _fixed_gaps(kind: str, n: int, cv2: float) -> np.ndarray:
    rng = np.random.default_rng(GAP_SEED)
    if kind == "exponential":
        return rng.exponential(1.0, n)
    return rng.gamma(1.0 / cv2, cv2, n)


def _stream(kind: str, n: int, cv2: float, seconds: float,
            rng: np.random.Generator) -> np.ndarray:
    """``n`` arrivals in (0, seconds]; the last one lands at ``seconds``."""
    if n == 0:
        return np.empty(0)
    gaps = rng.permutation(_fixed_gaps(kind, n, cv2))
    t = np.cumsum(gaps)
    return t * (seconds / t[-1])


def arrivals(traffic: Dict[str, Any], seconds: float, seed: int) -> np.ndarray:
    """Sorted due times (s from the window's start) of every query."""
    rate = float(traffic["rate_qps"])
    n = int(round(rate * seconds))
    rng = np.random.default_rng([seed, 1])
    kind = traffic["arrivals"]
    if kind == "poisson":
        return _stream("exponential", n, 1.0, seconds, rng)
    if kind == "bursty":
        n_base = int(round(float(traffic["base_share"]) * n))
        base = (np.arange(n_base) + 0.5) * (seconds / max(n_base, 1))
        burst = _stream("gamma", n - n_base, float(traffic["cv2"]),
                        seconds, rng)
        return np.sort(np.concatenate([base, burst]))
    raise ValueError(f"unknown arrivals {kind!r}")


def prompts(traffic: Dict[str, Any], n: int, vocab: int,
            seed: int) -> np.ndarray:
    """(n, prompt_len) int32 token ids, uniform over the vocabulary."""
    rng = np.random.default_rng([seed, 2])
    return rng.integers(0, vocab, (n, int(traffic["prompt_len"])),
                        dtype=np.int32)
