"""Spans at the layer boundaries of the serving path.

A span records exactly while a JAX profiler session is active
(``jax.profiler.start_trace``, ``jax.profiler.trace``, or a capture from
TensorBoard/xprof). Then it both lands in the profiler's trace, as a
``jax.profiler.TraceAnnotation`` on the same clock as the device's ops,
and is appended to a bounded in-memory buffer on ``time.perf_counter``
(the clock of ``engine.WallClock``), which :func:`recorded` returns.
With no session active a span site returns one shared null object: no
allocation, no clock read. There is no switch of its own.

Linking. A synchronous span takes the innermost open span of its own
thread as its ``parent``. A span that stays open across an ``await``
(``awaits=True``) takes no parent and is pushed on no stack; it is
linked by its attributes instead: spans of one request share ``qid``,
spans of one batch share ``replica`` and ``batch`` (the engine's
dispatch sequence number on that replica). Attribute values are ints.

The serving path's spans:

- ``engine.policy``: ``SchedulingEngine.next_dispatch`` around the
  policy's choice (replica, queue_len, batch_size, subnet);
- ``engine.queue_wait``: one interval per query from arrival to its
  batch's launch, in memory only (qid, batch, replica);
- ``router.batch``: ``Router._run_batch`` from launch until the batch's
  futures are resolved (replica, batch, rows, subnet);
- ``worker.run``: in the worker thread, around the worker's step
  (replica, batch); the parent of the executor's spans;
- ``executor.stack``: stacking the payloads into one array (rows);
- ``executor.pad``: padding to the shape bucket (rows, bucket_rows,
  seq, bucket_seq);
- ``executor.launch``: the compiled call, which only enqueues (device);
- ``executor.device_wait``: blocking until the result is ready (device);
- ``executor.fetch``: the device-to-host copy of the bucket's logits
  (device, bytes);
- ``executor.compile``: a compile on a cache miss (kind, bucket_rows,
  bucket_seq).

``device`` is the executor's ``jax.Device.id``.
"""
from __future__ import annotations

import collections
import itertools
import sys
import threading
import time
from typing import Deque, Dict, List, NamedTuple, Optional

CAPACITY = 65536          # spans kept; the oldest go first


class Span(NamedTuple):
    name: str
    t0: float                     # time.perf_counter() s
    t1: float
    attrs: Dict[str, int]
    parent: Optional[int]         # ``sid`` of the enclosing span
    sid: int


_buffer: Deque[Span] = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_local = threading.local()


def enabled() -> bool:
    """True while a JAX profiler session is active. A process that has
    not imported JAX has no session, and the serving control plane does
    not import JAX to ask."""
    prof = sys.modules.get("jax.profiler")
    return prof is not None and prof.TraceAnnotation.is_enabled()


class _Null:
    """What a span site gets while no profiler session is active."""

    __slots__ = ()

    def __enter__(self) -> "_Null":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


NULL = _Null()


def _stack() -> List[int]:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


class _Live:
    __slots__ = ("name", "attrs", "awaits", "sid", "parent", "t0", "_ann")

    def __init__(self, name: str, attrs: Dict[str, int], awaits: bool):
        self.name, self.attrs, self.awaits = name, attrs, awaits

    def set(self, **attrs) -> None:
        """Attributes known only once the span's work has run."""
        self.attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __enter__(self) -> "_Live":
        from jax.profiler import TraceAnnotation
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.sid = next(_ids)
        self.parent = None
        if not self.awaits:
            stack = _stack()
            self.parent = stack[-1] if stack else None
            stack.append(self.sid)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if not self.awaits:
            _stack().pop()
        self._ann.__exit__(*exc)
        _buffer.append(Span(self.name, self.t0, t1, self.attrs, self.parent,
                            self.sid))
        return False


def span(name: str, awaits: bool = False, **attrs: int):
    """A context manager over the work it encloses. ``awaits=True`` for
    a span that stays open across an ``await``. The object it returns
    has ``set(**attrs)`` for attributes known only at the end."""
    if not enabled():
        return NULL
    return _Live(name, attrs, awaits)


def interval(name: str, t0: float, t1: float, **attrs: int) -> None:
    """Record an interval whose ends the caller stamped on
    ``time.perf_counter``. In memory only: it does not enter the
    profiler's trace."""
    if enabled():
        _buffer.append(Span(name, t0, t1, attrs, None, next(_ids)))


def recorded() -> List[Span]:
    """The spans recorded so far, oldest first (at most ``CAPACITY``)."""
    return list(_buffer)


def clear() -> None:
    _buffer.clear()
