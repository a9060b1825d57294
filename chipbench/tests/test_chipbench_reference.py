"""The benchmark's weights and float32 reference against the program,
at a small size on the CPU where both compute in float32."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, sut, weights

ROOT = Path(__file__).resolve().parents[2]


def tiny_cfg(**kw):
    cfg = json.loads((ROOT / "chipbench/configs/qwen2-1.5b.json").read_text())
    cfg.update(hidden_size=64, intermediate_size=384, num_hidden_layers=4,
               num_attention_heads=8, num_key_value_heads=2, head_dim=16,
               vocab_size=300, torch_dtype="float32")
    cfg.update(kw)
    return cfg


CONFIGS = {
    "qwen-like": tiny_cfg(),
    "danube-like": tiny_cfg(tie_word_embeddings=False, attention_bias=False,
                            sliding_window=8, rope_theta=10000.0,
                            rms_norm_eps=1e-5, head_dim=24),
}


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def setup(request):
    from repro import compat
    cfg = CONFIGS[request.param]
    prog = sut.program_config(cfg)
    w = weights.make(cfg, seed=2 ** 35 + 3, device=jax.devices()[0])
    compat.set_kernel_tier("ref")
    yield cfg, prog, w
    compat.reset_kernel_tier()


def test_weights_have_the_program_layout(setup):
    from repro.models import lm
    cfg, prog, w = setup
    want = jax.eval_shape(lambda k: lm.init_model(k, prog),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), w)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (x.shape, x.dtype)


def test_weights_follow_the_seed(setup):
    cfg, _, w = setup
    again = weights.make(cfg, seed=2 ** 35 + 3, device=jax.devices()[0])
    other = weights.make(cfg, seed=4, device=jax.devices()[0])
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(w), jax.tree.leaves(again)))
    assert not np.array_equal(w["embed"], other["embed"])
    g = np.asarray(w["final_gamma"])
    assert not np.allclose(g[0], g[1])          # a gain row per subnet


def test_reference_matches_the_program_on_every_subnet(setup):
    """Every subnet of the space, served through the executor's compiled
    prefill, against the reference on the same weights: both in float32,
    so they agree to rounding."""
    from repro.serving.executor import SubnetExecutor
    cfg, prog, w = setup
    ex = SubnetExecutor(w, prog)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg["vocab_size"], (3, 16), dtype=np.int32)
    seen_rows = set()
    for pi, p in enumerate(ex.points):
        s = p.sub
        shape = reference.subnet_shape(cfg, s.depth_frac, s.ffn_frac,
                                       s.head_frac)
        assert shape["row"] == s.subnet_id
        seen_rows.add(shape["row"])
        got = ex.run_prefill(pi, toks)
        want = reference.logits(cfg, w, shape, toks)
        err = np.abs(got - want).max() / np.abs(want).max()
        assert err < 2e-5, (pi, err)
    assert len(seen_rows) == len(ex.points) > 1


def test_reference_sees_each_subnet_knob(setup):
    """Depth, FFN width, heads and the gain row each change the answer."""
    cfg, _, w = setup
    toks = np.arange(32, dtype=np.int32).reshape(2, 16) % cfg["vocab_size"]
    base = reference.subnet_shape(cfg, 1.0, 1.0, 1.0)
    want = reference.logits(cfg, w, base, toks)
    for k, v in (("layers", 2), ("ffn", 128), ("heads_per_group", 2),
                 ("row", 0)):
        got = reference.logits(cfg, w, dict(base, **{k: v}), toks)
        assert np.abs(got - want).max() > 1e-3 * np.abs(want).max(), k


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_control_departs_from_the_reference(setup, control):
    cfg, _, w = setup
    toks = np.arange(48, dtype=np.int32).reshape(3, 16) % cfg["vocab_size"]
    shape = reference.subnet_shape(cfg, 1.0, 1.0, 1.0)
    want = reference.logits(cfg, w, shape, toks)
    low = reference.logits(cfg, w, shape, toks, control=control)
    err = np.linalg.norm(low - want) / np.linalg.norm(want)
    assert 1e-4 < err < 0.5
