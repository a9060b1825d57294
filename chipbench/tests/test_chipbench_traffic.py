"""The benchmark's traffic generator and manifest."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import manifest, traffic

ROOT = Path(__file__).resolve().parents[2]
TRAFFIC = sorted((ROOT / "chipbench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_every_seed_offers_the_same_work(path):
    tr = json.loads(path.read_text())
    a = traffic.arrivals(tr, 20.0, seed=1)
    b = traffic.arrivals(tr, 20.0, seed=2 ** 33 + 5)
    assert len(a) == len(b) == round(tr["rate_qps"] * 20.0)
    assert np.all(np.diff(a) >= 0) and a[0] > 0 and a[-1] <= 20.0 + 1e-9
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, traffic.arrivals(tr, 20.0, seed=1))
    if tr["arrivals"] == "poisson":
        # the same gaps in another order
        np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                                   np.sort(np.diff(b, prepend=0.0)))


def test_bursty_stream_is_bursty():
    tr = {"arrivals": "bursty", "rate_qps": 160.0, "base_share": 0.2,
          "cv2": 8.0}
    a = traffic.arrivals(tr, 51.0, seed=9)
    gaps = np.diff(a)
    assert gaps.var() / gaps.mean() ** 2 > 2.0       # Poisson: 1
    counts = np.histogram(a, bins=np.arange(0, 51.05, 0.1))[0]
    assert counts.max() > 3 * counts.mean()


def test_prompts_follow_the_seed():
    tr = {"prompt_len": 128}
    p = traffic.prompts(tr, 5, 1000, seed=2 ** 40)
    assert p.shape == (5, 128) and p.dtype == np.int32
    assert p.min() >= 0 and p.max() < 1000
    np.testing.assert_array_equal(p, traffic.prompts(tr, 5, 1000, 2 ** 40))


def test_manifest_reads_every_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = manifest.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.traffic["prompt_len"] > 0
        assert int(cell.traffic.get("replicas", 1)) in (1, cell.chips)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_manifest_reads_the_four_chip_traffic_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    files = [p for p in TRAFFIC if json.loads(p.read_text()).get("replicas")]
    assert files, "no traffic file with replicas"
    for p in files:
        tr = json.loads(p.read_text())
        assert tr["replicas"] == 4
    for w in four:
        cell = manifest.load_cell(w["name"])
        assert cell.traffic["replicas"] == 4
