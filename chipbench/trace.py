"""Reduction of the traced slice's profiler trace to what the per-layer
metrics read: device op intervals, program (module) executions, kernel
events, the benchmark's host spans, and idle gaps labelled by the host
span that covers each gap's middle.

The benchmark writes its spans into the profiler's own trace
(``jax.profiler.TraceAnnotation``), so host spans and device events
share one clock:

- ``chipbench.traced``: the traced slice itself;
- ``generator.wait``: the generator sleeps until the next due time;
- ``generator.submit``: the generator is submitting a query;
- ``executor_call``: the wrapper around ``run_prefill`` is in flight.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import numpy as np

from chipbench.window import MARK  # noqa: F401  (re-exported)
# an idle gap takes the first of these whose span covers its middle
SPANS = ("executor_call", "generator.submit", "generator.wait")


@dataclass
class Event:
    name: str             # for device ops: the HLO instruction's text
    start: float          # ns, trace clock
    end: float


@dataclass
class DeviceTrace:
    name: str
    ops: List[Event]
    modules: List[Event]


@dataclass
class Trace:
    window: Tuple[float, float]            # ns, the traced slice
    devices: List[DeviceTrace]
    spans: Dict[str, np.ndarray]           # name -> (n, 2) ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _events(line) -> List[Event]:
    return [Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def from_planes(planes) -> Trace:
    """Build a :class:`Trace` from ``ProfileData``-like planes (objects
    with ``name`` and ``lines``; lines with ``name`` and ``events``)."""
    devices, spans = [], {s: [] for s in SPANS + (MARK,)}
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            devices.append(DeviceTrace(
                plane.name, _events(lines["XLA Ops"]),
                _events(lines["XLA Modules"])
                if "XLA Modules" in lines else []))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in spans:
                        spans[ev.name].append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns))
    mark = spans.pop(MARK)
    if not mark:
        raise ValueError(f"trace has no {MARK!r} span")
    arr = {k: np.asarray(sorted(v), float).reshape(-1, 2)
           for k, v in spans.items()}
    return Trace(window=(min(m[0] for m in mark), max(m[1] for m in mark)),
                 devices=devices, spans=arr)


def read(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return from_planes(ProfileData.from_file(paths[-1]).planes)


# -- reductions ----------------------------------------------------------

def merged(intervals: List[Tuple[float, float]], lo: float,
           hi: float) -> List[Tuple[float, float]]:
    """Union of intervals, clipped to [lo, hi], sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(dev: DeviceTrace, window: Tuple[float, float]) -> float:
    return sum(e - s for s, e in merged([(o.start, o.end) for o in dev.ops],
                                        *window)) * 1e-9


def idle_gaps(dev: DeviceTrace,
              window: Tuple[float, float]) -> List[Tuple[float, float]]:
    busy = merged([(o.start, o.end) for o in dev.ops], *window)
    edges = [window[0]] + [x for iv in busy for x in iv] + [window[1]]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def label(t: float, spans: Dict[str, np.ndarray]) -> str:
    for name in SPANS:
        iv = spans.get(name)
        if iv is not None and len(iv) and np.any((iv[:, 0] <= t) & (t < iv[:, 1])):
            return name
    return "none"


# device op events are named by their HLO instruction's text:
# "%fusion.35 = bf16[8,128,1536]{...} fusion(...), kind=kOutput, ..."
_NAME = re.compile(r"^%?([^\s=]+)\s*=\s*")
_SHAPE = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\](\{[^}]*\})?")
_SPACE = re.compile(r"S\((\d+)\)")
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
            "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}
# ops that hold other ops of the same program: counting them would count
# their contents twice
CONTAINERS = ("while", "conditional", "call")


Shape = Tuple[str, Tuple[int, ...], int]     # dtype, dims, memory space


def parse_op(text: str) -> Tuple[str, str, List[Shape]]:
    """(instruction name, opcode, shapes) of an op event; the first shape
    is the result's, the rest the operands'. A shape's memory space is
    its layout's ``S(n)`` (0, the device's HBM, where there is none)."""
    m = _NAME.match(text)
    if not m:
        return text, text, []
    name, rest = m.group(1), text[m.end():]
    depth, i = 0, 0
    while i < len(rest) and not (rest[i] == " " and depth == 0):
        depth += rest[i] in "([{"
        depth -= rest[i] in ")]}"
        i += 1
    opcode = rest[i:].strip().split("(", 1)[0]
    # result type and operand list only: attributes after the operands
    # (operand_layout_constraints=...) repeat shapes without placement
    j = rest.find("(", i)
    depth, k = 0, j
    while 0 <= k < len(rest):
        depth += rest[k] == "("
        depth -= rest[k] == ")"
        if depth == 0:
            break
        k += 1
    shapes = []
    for dt, dims, layout in _SHAPE.findall(rest[:k + 1] if j >= 0 else rest):
        sp = _SPACE.search(layout or "")
        shapes.append((dt, tuple(int(x) for x in dims.split(",") if x),
                       int(sp.group(1)) if sp else 0))
    return name, opcode, shapes


def hbm_bytes(shapes: List[Shape]) -> float:
    """Bytes an op moves through HBM: its result and operands that live in
    the default memory space, each once (an operand the compiler placed
    in on-chip memory, ``S(1)``, costs no HBM traffic)."""
    total = 0
    for dt, dims, space in shapes:
        if space == 0:
            n = 1
            for x in dims:
                n *= x
            total += n * ITEMSIZE[dt]
    return float(total)


def op_kind(text: str) -> str:
    """What the breakdown groups an op by: the kernel's name for a
    custom call, ``fusion_<kind>`` for a fusion, else the opcode."""
    name, opcode, _ = parse_op(text)
    base = re.sub(r"(\.\d+)+$", "", name)
    if opcode == "custom-call":
        return base
    if opcode == "fusion":
        k = re.search(r"kind=(k\w+)", text)
        return f"fusion_{k.group(1)}" if k else "fusion"
    return opcode or base


def breakdown(tr: Trace, n: int = 10) -> Dict[str, List[List[Any]]]:
    """The device ops that took most time, and the longest idle gaps by
    what the host was doing (all devices of the trace)."""
    tot: Dict[str, float] = {}
    gaps = []
    for dev in tr.devices:
        lo, hi = tr.window
        for o in dev.ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                k = op_kind(o.name)
                if k not in CONTAINERS:
                    tot[k] = tot.get(k, 0.0) + d * 1e-9
        for s, e in idle_gaps(dev, tr.window):
            gaps.append([label((s + e) / 2, tr.spans), (e - s) * 1e-9])
    ops = sorted(([k, v] for k, v in tot.items()), key=lambda x: -x[1])[:n]
    gaps.sort(key=lambda x: -x[1])
    return {"device_ops": ops, "idle_gaps": gaps[:n]}


def idle_by_label(tr: Trace) -> Dict[str, float]:
    """Idle seconds per label, summed over the trace's devices."""
    out: Dict[str, float] = {}
    for dev in tr.devices:
        for s, e in idle_gaps(dev, tr.window):
            k = label((s + e) / 2, tr.spans)
            out[k] = out.get(k, 0.0) + (e - s) * 1e-9
    return out


def kernel_events(tr: Trace, kernel: str) -> List[Event]:
    """Events of the Pallas kernel named ``kernel`` inside the slice."""
    lo, hi = tr.window
    return [o for dev in tr.devices for o in dev.ops
            if o.start >= lo and o.end <= hi and op_kind(o.name) == kernel]


def modules_in(tr: Trace) -> List[Event]:
    """Program executions inside the slice, on every device."""
    lo, hi = tr.window
    return [m for dev in tr.devices for m in dev.modules
            if m.start >= lo and m.end <= hi]
