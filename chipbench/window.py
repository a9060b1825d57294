"""The measured window: an open-loop generator submits each query into
the router at its due time, with the deadline the client has left, and
records what becomes of it. Host readings that explain stalls (resident
memory, garbage-collector pauses, generator lateness) are taken here.
"""
from __future__ import annotations

import asyncio
import gc
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from chipbench.outcomes import Outcomes

MARK = "chipbench.traced"     # host span over the traced slice


def rss_bytes() -> int:
    """Resident memory of this process."""
    with open("/proc/self/statm") as f:
        pages = int(f.read().split()[1])
    import resource
    return pages * resource.getpagesize()


class GcWatch:
    """Collections and the longest pause of each generation, read from
    ``gc.callbacks``. A reading only: the collector's settings are the
    program's."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.max_pause_ms = [0.0, 0.0, 0.0]
        self._t = 0.0

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t = time.perf_counter()
            return
        g = info["generation"]
        self.count[g] += 1
        self.max_pause_ms[g] = max(self.max_pause_ms[g],
                                   (time.perf_counter() - self._t) * 1e3)

    def __enter__(self) -> "GcWatch":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)


@dataclass
class WindowResult:
    outcomes: Outcomes
    t0: float                      # window start, perf_counter s
    seconds: float
    lateness_s: np.ndarray         # submit time - due time, per query
    dispatches: List[tuple]        # (t, real rows, queue length) per batch
    compiles: int
    rss_before: int
    rss_after: int
    gc: GcWatch
    served_by_replica: Dict[int, int] = field(default_factory=dict)
    trace_slice: Optional[tuple] = None   # (t_start, t_stop) perf_counter
    extra: Dict[str, Any] = field(default_factory=dict)


def _dispatches(router) -> List[tuple]:
    routers = getattr(router, "routers", [router])
    return sorted((d.t, d.batch, d.queue_len) for r in routers
                  for d in r.engine.dispatches)


async def _serve(router, due: np.ndarray, payloads: np.ndarray, slo_s: float,
                 seconds: float, grace_s: float, outcomes: Outcomes,
                 span: Callable, trace: Optional[Dict[str, Any]]):
    await router.start()
    t0 = time.perf_counter()
    due_abs = t0 + due
    outcomes.due = due_abs
    late = np.zeros(len(due))
    pending: Dict[int, asyncio.Future] = {}
    tracer = _Tracer(trace, t0, span)

    def done(i: int, fut: asyncio.Future) -> None:
        pending.pop(i, None)
        t = time.perf_counter()
        if fut.cancelled():
            outcomes.resolve(i, t, error=asyncio.CancelledError())
        elif fut.exception() is not None:
            outcomes.resolve(i, t, error=fut.exception())
        else:
            outcomes.resolve(i, t, result=fut.result())

    for i in range(len(due)):
        tracer.poll()
        delay = due_abs[i] - time.perf_counter()
        if delay > 0:
            with span("generator.wait"):
                await asyncio.sleep(delay)
        now = time.perf_counter()
        late[i] = now - due_abs[i]
        with span("generator.submit"):
            fut = await router.submit(payloads[i], slo_s=slo_s - late[i])
        pending[i] = fut
        fut.add_done_callback(lambda f, i=i: done(i, f))
    cutoff = t0 + seconds + slo_s + grace_s
    while pending:
        left = cutoff - time.perf_counter()
        if left <= 0:
            break
        await asyncio.wait(list(pending.values()), timeout=left)
    outcomes.close()
    await router.drain(timeout=1.0)
    await tracer.finish()
    return t0, late, tracer.slice


class _Tracer:
    """Starts and stops the profiler at fixed offsets into the window.
    Starting and stopping (which writes the trace) run in a thread, so
    the generator keeps its schedule; ``MARK`` spans the traced slice."""

    def __init__(self, trace: Optional[Dict[str, Any]], t0: float,
                 span: Callable):
        self.trace, self.t0, self.span = trace, t0, span
        self.slice: Optional[tuple] = None
        self._starting: Optional[asyncio.Future] = None
        self._stopping: Optional[asyncio.Future] = None
        self._mark = None
        self._lo = 0.0

    def poll(self) -> None:
        if self.trace is None:
            return
        now = time.perf_counter() - self.t0
        if self._starting is None and now >= self.trace["start_s"]:
            self._starting = asyncio.ensure_future(self._start())
        elif (self._mark is not None and self._stopping is None
              and now >= self.trace["stop_s"]):
            self._stop()

    async def _start(self) -> None:
        await asyncio.to_thread(self.trace["start"])
        self._mark = self.span(MARK)
        self._mark.__enter__()
        self._lo = time.perf_counter()

    def _stop(self) -> None:
        self.slice = (self._lo, time.perf_counter())
        self._mark.__exit__(None, None, None)
        self._stopping = asyncio.ensure_future(
            asyncio.to_thread(self.trace["stop"]))

    async def finish(self) -> None:
        if self._starting is not None:
            await self._starting
        if self._mark is not None and self._stopping is None:
            self._stop()
        if self._stopping is not None:
            await self._stopping


def run(router, due: np.ndarray, payloads: np.ndarray, slo_s: float,
        seconds: float, grace_s: float, outcomes: Outcomes,
        span: Callable, compile_count: Callable[[], int],
        trace: Optional[Dict[str, Any]] = None) -> WindowResult:
    """Serve one window. ``trace`` (traced runs only) holds ``start_s``
    and ``stop_s``, offsets into the window, and the profiler's
    ``start()`` and ``stop()``."""
    rss0 = rss_bytes()
    c0 = compile_count()
    with GcWatch() as gw:
        t0, late, tslice = asyncio.run(_serve(
            router, due, payloads, slo_s, seconds, grace_s, outcomes, span,
            trace))
    served: Dict[int, int] = {}
    for r in router.records():
        if not r.dropped and r.finish is not None:
            served[r.replica] = served.get(r.replica, 0) + 1
    return WindowResult(outcomes=outcomes, t0=t0, seconds=seconds,
                        served_by_replica=served,
                        lateness_s=late, dispatches=_dispatches(router),
                        compiles=compile_count() - c0, rss_before=rss0,
                        rss_after=rss_bytes(), gc=gw, trace_slice=tslice)
