"""The correctness check's control at a size a test run holds: the
reference with its weights rounded to fp8 (the control the limits were
set against), at each configuration's published widths and a few layers,
read against the float32 reference. The control has to fail the
configuration's committed limit; on the chip it was read at full size
(``chipbench/control.py``, ``PERF.md``)."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import reference, weights
from chipbench.run import logit_gap, rel_l2

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.mark.parametrize("name", ["qwen2-1.5b", "h2o-danube-3-4b"])
def test_control_fails_the_limit(name):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    limits = cfg["limits"]
    cfg.update(num_hidden_layers=3, vocab_size=8192)
    w = weights.make(cfg, seed=2 ** 33 + 17, device=jax.devices()[0])
    toks = np.random.default_rng(5).integers(0, 8192, (4, 128),
                                             dtype=np.int32)
    sub = reference.subnet_shape(cfg, 1.0, 1.0, 1.0)
    want = reference.logits(cfg, w, sub, toks)
    low = reference.logits(cfg, w, sub, toks, control="fp8")
    l2 = np.mean([rel_l2(a, b) for a, b in zip(low, want)])
    # three layers of rounding instead of the full depth: the chip read
    # 0.18 to 0.25 at full size, and three layers still read over twice
    # the limit
    assert l2 > 1.5 * limits["mean_rel_l2"], l2
    assert max(logit_gap(a, b) for a, b in zip(low, want)) > 0
