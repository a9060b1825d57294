"""The program's own spans (``repro.serving.spans``) over the traced
slice, for the per-layer metrics that read them.

The program records its spans exactly while the profiler runs, so a
traced run holds them with no switch of the benchmark's. They are on
``time.perf_counter``; device events are on the trace's clock. The
two are tied by the ``chipbench.traced`` span: ``ctx.window.trace_slice``
is stamped beside its enter and exit, and ``ctx.trace.window`` is the
same span in the trace. A program without the module has no spans, and
every reader returns ``None``.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Tuple

from chipbench import trace as trace_mod

_DEVICE_ID = re.compile(r":(\d+)$")


def in_slice(ctx) -> Optional[list]:
    """Recorded spans that start and end inside the traced slice;
    ``None`` where the program records none."""
    try:
        from repro.serving import spans
    except ImportError:
        return None
    lo, hi = ctx.window.trace_slice
    return [s for s in spans.recorded() if lo <= s.t0 and s.t1 <= hi]


def named(ctx, name: str) -> Optional[list]:
    recs = in_slice(ctx)
    return None if recs is None else [s for s in recs if s.name == name]


def mean_s(ctx, name: str) -> Optional[float]:
    """Mean duration (s) of the slice's spans called ``name``."""
    ss = named(ctx, name)
    return sum(s.t1 - s.t0 for s in ss) / len(ss) if ss else None


def to_trace_ns(ctx) -> Callable[[float], float]:
    """perf_counter seconds -> trace nanoseconds, linear through the
    slice's two anchors (offset and drift between the clocks)."""
    (s0, s1), (w0, w1) = ctx.window.trace_slice, ctx.trace.window
    k = (w1 - w0) / (s1 - s0)
    return lambda t: w0 + (t - s0) * k


def device_id(dev) -> Optional[int]:
    """The JAX device id of a device plane, ``/device:TPU:<id>``."""
    m = _DEVICE_ID.search(dev.name)
    return int(m.group(1)) if m else None


def replica_devices(recs) -> Dict[int, int]:
    """replica -> device id, from executor spans (``device``) whose
    parent is a ``worker.run`` (``replica``)."""
    runs = {s.sid: s.attrs["replica"] for s in recs if s.name == "worker.run"}
    return {runs[s.parent]: s.attrs["device"] for s in recs
            if "device" in s.attrs and s.parent in runs}


def idle_under_own_calls(ctx) -> Optional[List[Tuple[str, float, float]]]:
    """Per device plane: (name, idle s inside a ``router.batch`` of a
    replica on that chip, idle s in all) over the slice."""
    recs = in_slice(ctx)
    if recs is None:
        return None
    ns = to_trace_ns(ctx)
    on = replica_devices(recs)
    out = []
    for dev in ctx.trace.devices:
        k = device_id(dev)
        calls = trace_mod.merged(
            [(ns(s.t0), ns(s.t1)) for s in recs if s.name == "router.batch"
             and on.get(s.attrs["replica"]) == k], *ctx.trace.window)
        gaps = trace_mod.idle_gaps(dev, ctx.trace.window)
        out.append((dev.name, overlap(gaps, calls) * 1e-9,
                    sum(e - s for s, e in gaps) * 1e-9))
    return out


def overlap(a: List[Tuple[float, float]],
            b: List[Tuple[float, float]]) -> float:
    """Total length common to two sorted lists of disjoint intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
