"""The readers of the program's own spans (``chipbench/program_spans.py``
and the six metrics on it), on the synthetic planes of
``test_chipbench_trace.py`` plus recorded spans: each gives its
hand-computed value, the perf_counter-to-trace map holds under an
offset and a drift, and idle on one chip under another chip's replica
is not counted."""
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import program_spans, run
from chipbench import trace as trace_mod
from chipbench.context import MetricContext
from chipbench.tests.test_chipbench_run import (  # noqa: F401
    ref_tier, run_tiny, tiny_root)
from chipbench.tests.test_chipbench_trace import plane, synthetic
from repro.serving import spans

MS = 1e-3                # perf_counter seconds per ms
T0 = 100.0               # perf_counter at the slice's start
READERS = ("engine.queue_wait_p95_ms", "engine.policy_us",
           "executor.host_ms_per_call", "executor.fetch_ms",
           "executor.pad_waste_pct", "device_idle_in_call_pct")


class Recorder:
    """Spans as the program records them: ``(name, t0, t1, attrs,
    parent, sid)``, times in ms from the slice's start."""

    def __init__(self):
        self.out = []

    def add(self, name, a, b, parent=None, **attrs):
        s = spans.Span(name, T0 + a * MS, T0 + b * MS, attrs, parent,
                       len(self.out) + 1)
        self.out.append(s)
        return s.sid

    def batch(self, replica, batch, device, a, b, wait, fetch, rows,
              bucket_rows):
        """One call: ``router.batch`` over [a, b], ``worker.run`` 0.5 ms
        inside it, the executor's spans inside that; ``wait`` ms of
        device wait, ``fetch`` ms of copy."""
        self.add("router.batch", a, b, replica=replica, batch=batch,
                 rows=rows, subnet=17)
        run = self.add("worker.run", a + 0.5, b - 0.5, replica=replica,
                       batch=batch)
        t = a + 0.6
        self.add("executor.stack", t, t + 0.1, run, rows=rows)
        self.add("executor.pad", t + 0.1, t + 0.2, run, rows=rows,
                 bucket_rows=bucket_rows, seq=128, bucket_seq=128)
        self.add("executor.launch", t + 0.2, t + 0.3, run, device=device)
        self.add("executor.device_wait", t + 0.3, t + 0.3 + wait, run,
                 device=device)
        self.add("executor.fetch", t + 0.3 + wait, t + 0.3 + wait + fetch,
                 run, device=device, bytes=rows * 4)


def recorded_one_chip():
    r = Recorder()
    # the calls of synthetic(): 11-20 ms and 45-50 ms (the second ends
    # where the slice does)
    r.batch(0, 0, 0, 11, 20, wait=6.0, fetch=1.0, rows=3, bucket_rows=4)
    r.batch(0, 1, 0, 45, 50, wait=2.0, fetch=1.5, rows=1, bucket_rows=1)
    r.add("engine.policy", 10.9, 10.91, replica=0, queue_len=3,
          batch_size=3, subnet=17)
    r.add("engine.policy", 44.9, 44.93, replica=0, queue_len=1,
          batch_size=1, subnet=17)
    r.add("engine.policy", 49.99, 50.2, replica=0)   # ends past the slice
    for q in range(20):                              # 1, 2, ..., 20 ms
        r.add("engine.queue_wait", 30.0 - (q + 1), 30.0, qid=q, batch=0,
              replica=0)
    r.add("engine.queue_wait", -5.0, 1.0, qid=99)    # began before it
    return r.out


def context(trace, slice_=(T0, T0 + 50 * MS)):
    return MetricContext({}, {"prompt_len": 128}, NS(trace_slice=slice_),
                         trace, [], {}, {})


@pytest.fixture
def record(monkeypatch):
    def use(recs):
        monkeypatch.setattr(spans, "recorded", lambda: list(recs))
    return use


def read(name, ctx):
    return run.load_metric(name).read(ctx)


def test_one_chip_readers_give_the_hand_computed_values(record):
    record(recorded_one_chip())
    ctx = context(synthetic())
    waits = np.arange(1, 21) * MS
    assert read("engine.queue_wait_p95_ms", ctx) == pytest.approx(
        np.percentile(waits, 95) * 1e3)
    assert read("engine.policy_us", ctx) == pytest.approx(20.0)
    # worker.run 8 ms less 6 ms of wait, and 4 ms less 2 ms
    assert read("executor.host_ms_per_call", ctx) == pytest.approx(2.0)
    assert read("executor.fetch_ms", ctx) == pytest.approx(1.25)
    assert read("executor.pad_waste_pct", ctx) == pytest.approx(100 / 5)
    # idle 0-13, 18-46, 49-50 ms; calls 11-20 and 45-50: 2+2+1+1 ms
    assert read("device_idle_in_call_pct", ctx) == pytest.approx(
        100 * 6 / 50)
    assert read("device_idle_in_call_pct", ctx) <= read("device_idle_pct",
                                                        ctx)


def two_chips(w0=0.0, drift=1.0):
    """Two chips over a 50-ms slice, their events on a trace clock that
    starts at ``w0`` ns and runs ``drift`` times as fast as perf_counter.
    Chip 0 runs 10-20 and 30-40 ms, chip 1 runs 5-25 ms."""
    def ev(name, a, b):
        return (name, w0 + a * 1e6 * drift, (b - a) * 1e6 * drift)
    host = plane("/host:CPU", {"python": [ev(trace_mod.MARK, 0, 50)]})
    op = "%a = f32[] add()"
    chip0 = plane("/device:TPU:0",
                  {"XLA Ops": [ev(op, 10, 20), ev(op, 30, 40)]})
    chip1 = plane("/device:TPU:1", {"XLA Ops": [ev(op, 5, 25)]})
    return trace_mod.from_planes([host, chip0, chip1])


def recorded_two_chips():
    r = Recorder()
    r.batch(0, 0, 0, 8, 22, wait=9.0, fetch=1.0, rows=2, bucket_rows=2)
    r.batch(1, 0, 1, 24, 30, wait=3.0, fetch=1.0, rows=2, bucket_rows=2)
    r.batch(0, 1, 0, 42, 48, wait=3.0, fetch=1.0, rows=2, bucket_rows=2)
    return r.out


@pytest.mark.parametrize("w0,drift", [(0.0, 1.0), (7e6, 1.0),
                                      (7e6, 1.001), (-3e9, 0.999)],
                         ids=["same", "offset", "offset_drift",
                              "negative_drift"])
def test_idle_counts_only_under_the_own_replicas_call(record, w0, drift):
    record(recorded_two_chips())
    ctx = context(two_chips(w0, drift))
    assert program_spans.replica_devices(recorded_two_chips()) == {0: 0,
                                                                   1: 1}
    per = program_spans.idle_under_own_calls(ctx)
    # chip 0 idle 0-10, 20-30, 40-50 under replica 0's 8-22 and 42-48:
    # 2 + 2 + 6 ms. Chip 1 idle 0-5, 25-50 under replica 1's 24-30: 5
    # ms; its idle under replica 0's 42-48 is not counted.
    assert [p[0] for p in per] == ["/device:TPU:0", "/device:TPU:1"]
    assert [p[1] for p in per] == pytest.approx([0.010 * drift,
                                                 0.005 * drift])
    assert [p[2] for p in per] == pytest.approx([0.030 * drift,
                                                 0.030 * drift])
    assert read("device_idle_in_call_pct", ctx) == pytest.approx(
        100 * 15 / 100)
    assert read("device_idle_pct", ctx) == pytest.approx(60.0)


@pytest.mark.parametrize("w0,drift", [(7e6, 1.0), (7e6, 1.001),
                                      (1.5e12, 0.98)])
def test_perf_counter_maps_onto_the_trace_clock(w0, drift):
    ctx = context(two_chips(w0, drift))
    ns = program_spans.to_trace_ns(ctx)
    assert ns(T0) == pytest.approx(w0)
    assert ns(T0 + 50 * MS) == pytest.approx(w0 + 50e6 * drift)
    assert ns(T0 + 20 * MS) == pytest.approx(w0 + 20e6 * drift)
    assert ns(T0 - 1.0) == pytest.approx(w0 - 1e9 * drift)


def test_overlap_of_sorted_intervals():
    a = [(0, 10), (20, 30), (40, 50)]
    assert program_spans.overlap(a, [(8, 22), (42, 48)]) == 10
    assert program_spans.overlap(a, []) == 0
    assert program_spans.overlap([(0, 100)], a) == 30


def test_a_program_without_spans_reads_nothing(monkeypatch):
    import repro.serving
    monkeypatch.delattr(repro.serving, "spans")
    monkeypatch.setitem(sys.modules, "repro.serving.spans", None)
    ctx = context(two_chips())
    assert program_spans.in_slice(ctx) is None
    for name in READERS:
        assert read(name, ctx) is None


def test_no_spans_in_the_slice_reads_nothing_but_idle_zero(record):
    record([])
    ctx = context(synthetic())
    for name in READERS[:-1]:
        assert read(name, ctx) is None
    assert read("device_idle_in_call_pct", ctx) == 0.0


def test_a_traced_run_reports_the_program_span_metrics(tiny_root, ref_tier,
                                                       capsys, monkeypatch):
    """The whole harness, traced, on the CPU: the program records its
    spans while the profiler runs, with no switch of the benchmark's,
    and nothing once it has stopped."""
    monkeypatch.setattr(run, "peaks_for", lambda _: {
        "bf16_flops_per_s": 1.97e14, "hbm_bytes_per_s": 8.19e11})
    line = run_tiny(tiny_root, capsys, trace=1)
    assert line["correct"] is True, line["checks"]
    got = line["metrics"]
    for name in READERS[:-1]:
        assert got[name]["value"] >= 0, name
    assert got["engine.policy_us"]["unit"] == "us"
    n = len(spans.recorded())
    run_tiny(tiny_root, capsys, trace=0)
    assert len(spans.recorded()) == n
