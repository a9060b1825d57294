"""Whole runs of the harness on the CPU at a tiny size: the look for a
chip skipped, everything else as on the chip. A sound run is correct;
a run with the timed path broken underneath is not."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import run

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout-like tree with one tiny cell: qwen2-1.5b's structure
    and limits at small widths, bursty enough that batches fill."""
    root = tmp_path_factory.mktemp("bench")
    (root / "chipbench" / "configs").mkdir(parents=True)
    (root / "chipbench" / "traffic").mkdir()
    cfg = json.loads((ROOT / "chipbench/configs/qwen2-1.5b.json").read_text())
    cfg.update(name="tiny", hidden_size=64, intermediate_size=256,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, vocab_size=256)
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(cfg))
    tr = json.loads((ROOT / "chipbench/traffic/"
                     "bursty-cv8-160qps-slo100ms-1w.json").read_text())
    tr.update(rate_qps=400.0, slo_ms=1000.0, prompt_len=16)
    (root / "chipbench/traffic/tiny.json").write_text(json.dumps(tr))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [dict(bench["configs"][0], name="tiny",
                             file="chipbench/configs/tiny.json")]
    bench["workloads"] = [dict(bench["workloads"][0], name="tiny.bursty",
                               config="tiny", traffic="tiny")]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.bursty"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def ref_tier(monkeypatch):
    """The pure-JAX kernel tier, and no persistent compile cache: this
    process runs other tests after these."""
    from repro import compat
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setenv("REPRO_KERNEL_TIER", "ref")
    compat.reset_kernel_tier()
    yield
    compat.reset_kernel_tier()


def run_tiny(root, capsys, seed=2 ** 33 + 1, trace=0):
    rc = run.main(["--workload", "tiny.bursty", "--seed", str(seed),
                   "--seconds", "3", "--trace", str(trace)], root=root,
                  require_chip=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(tiny_root, ref_tier, capsys):
    line = run_tiny(tiny_root, capsys)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] == 1200
    assert set(line["metrics"]) == {"slo_attainment", "p95_latency_ms",
                                    "mean_served_acc", "goodput_qps",
                                    "setup_s"}
    assert list(line)[-1] == "checks"
    assert line["checks"]["mean_rel_l2"]["value"] < 0.05


def _token_altered(orig):
    def bad(self, idx, batch):
        b = np.array(batch)
        b[:, -1] = (b[:, -1] + 1) % self.cfg.vocab_size
        return orig(self, idx, b)
    return bad


def _half_batch(orig):
    def bad(self, idx, batch):
        h = (len(batch) + 1) // 2
        out = orig(self, idx, batch[:h])
        return np.concatenate([out, out[:len(batch) - h]])
    return bad


@pytest.mark.parametrize("fault", [_token_altered, _half_batch],
                         ids=["token_altered", "half_batch_left_out"])
def test_broken_timed_path_is_not_correct(tiny_root, ref_tier, capsys,
                                          monkeypatch, fault):
    from repro.serving.executor import SubnetExecutor
    monkeypatch.setattr(SubnetExecutor, "run_prefill",
                        fault(SubnetExecutor.run_prefill))
    line = run_tiny(tiny_root, capsys)
    assert line["correct"] is False
    assert line["checks"]["mean_rel_l2"]["value"] > \
        line["checks"]["mean_rel_l2"]["limit"]


def _bench_env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_no_chip_exits_nonzero_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen2-1.5b.bursty", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=ROOT, env=_bench_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen2-1.5b.bursty", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, env=_bench_env(), capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout == ""
