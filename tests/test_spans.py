"""The serving path's spans (serving/spans.py): nothing is recorded and
no clock is read while no profiler session is active; under one, every
batch an executor-backed Router serves yields its router, worker and
executor spans, linked by parent and by attributes, in memory and in
the profiler's written trace."""
import asyncio
import glob
from types import SimpleNamespace as NS

import numpy as np
import pytest

import jax

from conftest import tiny_dense
from repro.serving import policies, runtime, spans
from repro.serving.executor import ExecutorConfig, build_executor

N_QUERIES = 10
EXECUTOR_SPANS = ("executor.stack", "executor.pad", "executor.launch",
                  "executor.device_wait", "executor.fetch")


@pytest.fixture(scope="module")
def executor():
    xcfg = ExecutorConfig(batch_buckets=(1, 2, 4), seq_buckets=(8, 16),
                          max_entries=8)
    ex = build_executor(tiny_dense(), exec_cfg=xcfg)
    ex.warmup(batches=(1, 2, 4), seqs=(8,))
    prof = ex.measured_profile(batches=(1, 2, 4), seq_len=8, warmup=0,
                               iters=1)
    return ex, prof


def serve(ex, prof, n=N_QUERIES):
    """Serve ``n`` queries through a two-worker Router; the served
    queries' ids."""
    async def go():
        router = runtime.Router(prof, policies.SlackFit(),
                                ex.make_workers(2), executor=ex)
        await router.start()
        futs = [await router.submit(np.full((7,), i, np.int32), slo_s=5.0)
                for i in range(n)]
        await asyncio.gather(*futs)
        await router.drain()
        return [r.qid for r in router.records()
                if not r.dropped and r.finish is not None]
    return asyncio.run(go())


def by_name(recs):
    out = {}
    for s in recs:
        out.setdefault(s.name, []).append(s)
    return out


def inside(child, parent):
    return parent.t0 <= child.t0 and child.t1 <= parent.t1


def test_off_returns_the_shared_null_and_records_nothing(executor,
                                                         monkeypatch):
    spans.clear()
    assert not spans.enabled()
    assert spans.span("engine.policy", replica=0) is spans.NULL
    assert spans.span("router.batch", awaits=True) is spans.NULL
    with spans.span("x", a=1) as sp:
        sp.set(b=2)
    spans.interval("engine.queue_wait", 0.0, 1.0, qid=0)

    def no_clock():
        raise AssertionError("a span read the clock with the profiler off")
    monkeypatch.setattr(spans, "time", NS(perf_counter=no_clock))
    served = serve(*executor)
    assert len(served) == N_QUERIES
    assert spans.recorded() == []


def test_parents_on_the_thread_and_none_across_awaits(tmp_path):
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        assert spans.enabled()
        with spans.span("outer", n=1) as outer:
            with spans.span("detached", awaits=True):
                with spans.span("inner", n=2) as inner:
                    inner.set(m=3)
        spans.interval("gap", 1.0, 2.0, qid=7)
    rec = {s.name: s for s in spans.recorded()}
    assert rec["inner"].parent == outer.sid     # not the detached span
    assert rec["detached"].parent is None
    assert rec["outer"].parent is None
    assert rec["inner"].attrs == {"n": 2, "m": 3}
    assert inside(rec["inner"], rec["outer"])
    assert rec["gap"][1:4] == (1.0, 2.0, {"qid": 7})
    spans.clear()
    assert spans.recorded() == []


@pytest.fixture(scope="module")
def traced(executor, tmp_path_factory):
    """One traced serve: the recorded spans, the served ids and the
    written trace's directory."""
    ex, prof = executor
    d = tmp_path_factory.mktemp("trace")
    spans.clear()
    with jax.profiler.trace(str(d)):
        served = serve(ex, prof)
        ex.prefill(0, np.ones((1, 12), np.int32))    # a cold (1, 16) bucket
    recs = spans.recorded()
    spans.clear()
    return recs, served, d


def test_every_batch_yields_router_worker_and_executor_spans(executor,
                                                             traced):
    ex, _ = executor
    recs, served, _ = traced
    named = by_name(recs)
    batches = named["router.batch"]
    assert sum(b.attrs["rows"] for b in batches) == N_QUERIES
    runs = {(s.attrs["replica"], s.attrs["batch"]): s
            for s in named["worker.run"]}
    assert len(runs) == len(batches) == len(named["engine.policy"])
    dev = ex.device.id
    for b in batches:
        assert b.parent is None                  # open across an await
        assert set(b.attrs) == {"replica", "batch", "rows", "subnet"}
        run = runs[(b.attrs["replica"], b.attrs["batch"])]
        assert inside(run, b)
        kids = {s.name: s for s in recs if s.parent == run.sid}
        assert set(kids) == set(EXECUTOR_SPANS)
        for s in kids.values():
            assert inside(s, run)
        for k in ("executor.launch", "executor.device_wait",
                  "executor.fetch"):
            assert kids[k].attrs["device"] == dev
        pad = kids["executor.pad"].attrs
        assert pad["rows"] == kids["executor.stack"].attrs["rows"] \
            == b.attrs["rows"]
        assert pad["bucket_rows"] >= pad["rows"] and pad["bucket_seq"] == 8
        assert kids["executor.fetch"].attrs["bytes"] == \
            pad["bucket_rows"] * ex.cfg.vocab_size * 4
    for p in named["engine.policy"]:
        assert set(p.attrs) == {"replica", "queue_len", "batch_size",
                                "subnet"}
    # the cold bucket outside the Router compiled under a span
    comp, = named["executor.compile"]
    assert comp.attrs == {"kind": 0, "bucket_rows": 1, "bucket_seq": 16}


def test_one_queue_wait_per_served_query(traced):
    recs, served, _ = traced
    named = by_name(recs)
    waits = named["engine.queue_wait"]
    assert sorted(w.attrs["qid"] for w in waits) == sorted(served)
    batch_ids = {(b.attrs["replica"], b.attrs["batch"])
                 for b in named["router.batch"]}
    for w in waits:
        assert w.t1 >= w.t0
        assert (w.attrs["replica"], w.attrs["batch"]) in batch_ids


def test_the_written_trace_holds_the_spans_with_their_stats(traced):
    from jax.profiler import ProfileData
    recs, _, d = traced
    path, = glob.glob(str(d / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    {k: v for k, v in ev.stats})
    want = by_name(recs)
    for name in ("engine.policy", "router.batch", "worker.run",
                 "executor.compile") + EXECUTOR_SPANS:
        got = sorted(events[name], key=sorted_items)
        assert got == sorted((s.attrs for s in want[name]), key=sorted_items)
    # the queue-wait intervals stay in memory
    assert "engine.queue_wait" not in events


def sorted_items(d):
    return sorted(d.items())
