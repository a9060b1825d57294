"""Outcome accounting and the reservoir of the benchmark's window."""
import gc
import weakref

import numpy as np
import pytest

from chipbench.outcomes import (ANSWERED, DROPPED, FAILED, Outcomes,
                                Reservoir)

V = 16
ACCS = {79.0: 0, 80.0: 1}


def make(n=4, slo=0.1, cap=2):
    return Outcomes(np.arange(n, dtype=float), slo, V, ACCS,
                    Reservoir(cap, seed=7))


def row(v=0.0):
    return np.full(V, v, np.float32)


def test_dropped_query_is_a_miss_and_not_failed():
    oc = make()
    oc.resolve(0, 0.01, result=(None, 0.0))
    oc.resolve(1, 1.01, result=(row(), 80.0))
    oc.close()
    s = oc.summary(seconds=4.0)
    assert oc.state[0] == DROPPED
    assert s["dropped"] == 1 and s["failed"] == 2    # 2, 3 never resolved
    assert s["unresolved"] == 2 and s["raised"] == 0
    assert not oc.met()[0] and oc.met()[1]
    assert s["slo_attainment"] == 25.0
    assert s["goodput_qps"] == 0.25
    assert s["drop_pct"] == 25.0


@pytest.mark.parametrize("case", ["raised", "nonfinite", "shape",
                                  "unresolved"])
def test_failed_outcomes(case):
    oc = make(n=1)
    if case == "raised":
        oc.resolve(0, 0.01, error=RuntimeError("boom"))
    elif case == "nonfinite":
        oc.resolve(0, 0.01, result=(row(np.nan), 80.0))
    elif case == "shape":
        oc.resolve(0, 0.01, result=(np.zeros(V + 1, np.float32), 80.0))
    oc.close()
    # a resolution after the wait ran out changes nothing
    oc.resolve(0, 9.0, result=(row(), 80.0))
    s = oc.summary(seconds=1.0)
    assert oc.state[0] == FAILED and s["failed"] == 1
    assert s["answered"] == 0 and s["dropped"] == 0
    assert s["bad_rows"] == (case in ("nonfinite", "shape"))
    assert s["raised"] == (case == "raised")
    assert s["unresolved"] == (case == "unresolved")
    assert oc.reservoir.n_rows() == 0


def test_late_answer_is_a_miss_and_not_failed():
    oc = make(n=2, slo=0.1)
    oc.resolve(0, 0.0 + 0.05, result=(row(), 80.0))
    oc.resolve(1, 1.0 + 0.5, result=(row(), 80.0))
    oc.close()
    s = oc.summary(seconds=2.0)
    assert list(oc.state) == [ANSWERED, ANSWERED]
    assert s["failed"] == 0 and s["late_answers"] == 1
    assert s["slo_attainment"] == 50.0
    assert s["p50_latency_ms"] == pytest.approx(275.0)


def test_reservoir_caps_each_subnet_and_covers_every_served_one():
    res = Reservoir(cap=3, seed=11)
    rng = np.random.default_rng(0)
    served = {0: 1, 4: 2, 7: 50, 9: 1000}
    qid = 0
    for subnet, n in served.items():
        for _ in range(n):
            res.offer(subnet, qid, rng.normal(size=V))
            qid += 1
    assert set(res.rows) == set(served)
    for subnet, n in served.items():
        assert len(res.rows[subnet]) == min(3, n)
    assert res.seen == served


def test_reservoir_is_seeded_and_keeps_late_rows_too():
    def picks(seed):
        res = Reservoir(cap=2, seed=seed)
        for q in range(200):
            res.offer(0, q, row(q))
        return sorted(q for q, _ in res.rows[0])
    assert picks(3) == picks(3)
    got = {tuple(picks(s)) for s in range(20)}
    assert len(got) > 1
    assert max(max(p) for p in got) >= 2        # not only the first rows


def test_kept_row_does_not_keep_its_batch_alive():
    res = Reservoir(cap=4, seed=1)
    batch = np.empty((8, V), np.float32)
    batch[:] = np.arange(8 * V).reshape(8, V)
    ref = weakref.ref(batch)
    view = batch[3]
    assert view.base is batch
    res.offer(0, 3, view)
    kept = res.rows[0][0][1]
    assert kept.base is None and np.array_equal(kept, batch[3])
    del batch, view
    gc.collect()
    assert ref() is None


def test_outcomes_keep_rows_only_through_the_reservoir():
    oc = make(n=3, cap=1)
    batch = np.ones((8, V), np.float32)
    ref = weakref.ref(batch)
    for i in range(3):
        oc.resolve(i, float(i), result=(batch[i], 80.0))
    assert oc.reservoir.n_rows() == 1
    del batch
    gc.collect()
    assert ref() is None
