"""The trace reduction: busy time, idle gaps and their labels, kernel
events, and the matching of program executions to executor calls, on
the committed slice of a traced run."""
import json
from pathlib import Path
from types import SimpleNamespace as NS

import numpy as np
import pytest

from chipbench import trace as trace_mod
from chipbench.context import MetricContext
from chipbench.sut import Call

DATA = Path(__file__).resolve().parent / "data"


def plane(name, lines):
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=e[0], start_ns=e[1], duration_ns=e[2],
                               stats=list(e[3].items()) if len(e) > 3 else [])
                            for e in evs])
        for ln, evs in lines.items()])


# op events carry their HLO instruction's text, as the TPU trace has it
FUSION = ("%fusion.{} = bf16[8,128,1536]{{2,1,0:T(8,128)(2,1)}} fusion("
          "bf16[8,128,1536]{{2,1,0:T(8,128)(2,1)S(1)}} %fusion.32), "
          "kind=kOutput, calls=%fused_computation")
FLASH = ("%flash_attention.3 = bf16[8,12,128,128]{3,2,1,0:T(8,128)(2,1)S(1)} "
         "custom-call(s32[1]{0:T(128)} %constant.98, bf16[8,12,128,128]"
         "{3,2,1,0:T(8,128)(2,1)S(1)} %fusion.39, bf16[8,2,128,128]{3,2,1,0:"
         "T(8,128)(2,1)S(1)} %fusion.53, bf16[8,2,128,128]{3,2,1,0:T(8,128)"
         "(2,1)S(1)} %bitcast.111), custom_call_target=\"tpu_custom_call\"")
RMSNORM = ("%subnet_rmsnorm.8 = bf16[1024,1536]{1,0:T(8,128)(2,1)S(1)} "
           "custom-call(s32[1]{0:T(128)} %bitcast.18, bf16[1024,1536]{1,0:"
           "T(8,128)(2,1)} %bitcast.19, f32[18,1,1536]{2,1,0:T(1,128)S(1)} "
           "%d), custom_call_target=\"tpu_custom_call\"")
WHILE = ("%while.1 = (s32[]{:T(128)}, bf16[8,128,1536]{2,1,0:T(8,128)(2,1)}) "
         "while((s32[]{:T(128)}, bf16[8,128,1536]) %tuple.3), condition=%c")


def synthetic():
    """One device; 1 ms per unit. Host: the generator waits 0-10, submits
    10-11, an executor call runs 11-20 whose program runs 13-18 with two
    kernels; waits again 20-40; a stall 40-45 with no span; a call 45-50
    (program 46-49)."""
    ms = 1_000_000
    host = {"python": [
        (trace_mod.MARK, 0, 50 * ms),
        ("generator.wait", 0, 10 * ms), ("generator.submit", 10 * ms, ms),
        ("generator.wait", 20 * ms, 20 * ms)],
        "worker": [("executor_call", 11 * ms, 9 * ms),
                   ("executor_call", 45 * ms, 5 * ms)]}
    dev = {"XLA Modules": [("jit_fn", 13 * ms, 5 * ms),
                           ("jit_fn", 46 * ms, 3 * ms)],
           "XLA Ops": [(FUSION.format(1), 13 * ms, 2 * ms),
                       (FLASH, 15 * ms, ms),
                       (RMSNORM, 16 * ms, 2 * ms),
                       (FUSION.format(2), 46 * ms, 3 * ms),
                       (WHILE, 13 * ms, 5 * ms)]}
    return trace_mod.from_planes([plane("/host:CPU", host),
                                  plane("/device:TPU:0", dev)])


def test_busy_and_labelled_gaps():
    tr = synthetic()
    assert tr.window_s == pytest.approx(0.050)
    dev = tr.devices[0]
    assert trace_mod.busy_s(dev, tr.window) == pytest.approx(0.008)
    gaps = trace_mod.idle_gaps(dev, tr.window)
    labels = [trace_mod.label((s + e) / 2, tr.spans) for s, e in gaps]
    # 0-13: middle 6.5 in a wait; 18-46: middle 32 in a wait;
    # 49-50: middle 49.5 inside the second call
    assert labels == ["generator.wait", "generator.wait", "executor_call"]
    assert trace_mod.label(42.5e6, tr.spans) == "none"
    assert trace_mod.label(10.5e6, tr.spans) == "generator.submit"
    by = trace_mod.idle_by_label(tr)
    assert by["generator.wait"] == pytest.approx(0.041)
    b = trace_mod.breakdown(tr)
    # the while loop holds the other ops and is not counted again
    assert dict(b["device_ops"]) == pytest.approx(
        {"fusion_kOutput": 0.005, "flash_attention": 0.001,
         "subnet_rmsnorm": 0.002})
    assert {k for k, _ in b["idle_gaps"]} <= {"generator.wait",
                                              "executor_call"}


def test_calls_programs_and_kernels_of_the_slice():
    tr = synthetic()
    # perf_counter seconds: the slice runs from 100.0 to 100.050
    calls = [Call(99.990, 100.005, 0, 17, 8),     # began before the slice
             Call(100.011, 100.020, 0, 17, 8), Call(100.045, 100.050, 0, 0, 1)]
    wr = NS(trace_slice=(100.0, 100.050))
    cfg = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                      "qwen2-1.5b.json").read_text())
    full = {"depth_frac": 1.0, "ffn_frac": 1.0, "head_frac": 1.0,
            "subnet_id": 17}
    ctx = MetricContext(cfg, {"prompt_len": 128, "batches": [1, 2, 4, 8]},
                        wr, tr, calls, {17: full, 0: full},
                        {"bf16_flops_per_s": 1.97e14,
                         "hbm_bytes_per_s": 8.19e11})
    assert [c.t0 for c in ctx.calls] == [100.011, 100.045]
    assert ctx.device_s_per_call() == pytest.approx(0.004)
    # flash: q, k, v and the result sit in on-chip memory (S(1)), so
    # the causal triangle's FLOPs bound it
    flops = 4 * 8 * 12 * 128 * (128 * 129 // 2)
    assert ctx.kernel_share("flash_attention") == pytest.approx(
        100 * flops / 1.97e14 / 1e-3)
    # rmsnorm: x comes from HBM, the result stays on chip; one gain row
    rms = 2 * 1024 * 1536 + 4 + 4 * 1536
    assert ctx.kernel_share("subnet_rmsnorm") == pytest.approx(
        100 * rms / 8.19e11 / 2e-3)


def test_merged_clips_and_joins():
    iv = [(5, 8), (0, 2), (1, 3), (9, 30)]
    assert trace_mod.merged(iv, 1, 20) == [(1, 3), (5, 8), (9, 20)]
    assert trace_mod.op_kind(FUSION.format(12)) == "fusion_kOutput"
    assert trace_mod.op_kind(WHILE) == "while"
    name, opcode, shapes = trace_mod.parse_op(FLASH)
    assert (name, opcode) == ("flash_attention.3", "custom-call")
    assert shapes[0] == ("bf16", (8, 12, 128, 128), 1)
    assert shapes[1] == ("s32", (1,), 0)
    assert shapes[3] == ("bf16", (8, 2, 128, 128), 1)
    assert trace_mod.hbm_bytes(shapes) == 4.0


def committed_slice():
    """73 ms of a traced qwen2-1.5b.bursty run on a TPU v5 lite (five
    executor calls): the device's op and program events with their HLO
    text, and the benchmark's host spans."""
    import gzip
    with gzip.open(DATA / "trace_slice.json.gz", "rt") as f:
        d = json.load(f)
    host = {"python": [(k, s, e - s) for k, v in d["spans"].items()
                       for s, e in v]
            + [(trace_mod.MARK, d["window"][0],
                d["window"][1] - d["window"][0])]}
    planes = [plane("/host:CPU", host)]
    for dv in d["devices"]:
        planes.append(plane(dv["name"], {
            "XLA Ops": [(n, s, e - s) for s, e, n in dv["ops"]],
            "XLA Modules": [(n, s, e - s) for s, e, n in dv["modules"]]}))
    return trace_mod.from_planes(planes)


def test_committed_slice_gaps_get_the_span_labels():
    tr = committed_slice()
    dev, = tr.devices
    busy = trace_mod.busy_s(dev, tr.window)
    assert 0.5 * tr.window_s < busy < tr.window_s
    by = trace_mod.idle_by_label(tr)
    assert set(by) <= {"executor_call", "generator.submit",
                       "generator.wait", "none"}
    # the host's part of each call (logits copy, padding, dispatch) is
    # device idle inside an executor_call span
    assert by["executor_call"] > 0.005 and by["generator.wait"] > 0
    assert abs(sum(by.values()) - (tr.window_s - busy)) < 1e-9
    ops = dict(trace_mod.breakdown(tr)["device_ops"])
    assert max(ops, key=ops.get) == "fusion_kOutput"
    assert {"flash_attention", "subnet_rmsnorm"} <= set(ops)
    assert not set(ops) & set(trace_mod.CONTAINERS)


def test_committed_slice_kernel_shares_are_shares():
    tr = committed_slice()
    cfg = json.loads((Path(__file__).resolve().parents[1] / "configs" /
                      "qwen2-1.5b.json").read_text())
    lo = 100.0
    wr = NS(trace_slice=(lo, lo + tr.window_s))
    ctx = MetricContext(cfg, {"prompt_len": 128, "batches": [1, 2, 4, 8]},
                        wr, tr, [], {}, {"bf16_flops_per_s": 1.97e14,
                                         "hbm_bytes_per_s": 8.19e11})
    for k in ("flash_attention", "subnet_rmsnorm"):
        share = ctx.kernel_share(k)
        assert 0 < share <= 100, (k, share)
    assert 5.0 < ctx.device_s_per_call() * 1e3 < 30.0
