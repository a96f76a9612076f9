"""The program's spans and counters, on the profiler's clock.

Tracing is on exactly while a JAX profiler session is
(``jax.profiler.start_trace`` … ``stop_trace``); there is no flag of its
own. While it is on, every span

  * opens a ``jax.profiler.TraceAnnotation``, so it lands on the
    profiler's host plane, on the same clock as the device planes, and
  * is kept in memory as a ``Span`` (name, start and end on
    ``time.perf_counter_ns``, parent span's name, step id, metadata), so a
    reader can see it after the trace file is gone.

While it is off, ``span`` returns a shared no-op and costs one
``TraceAnnotation.is_enabled()`` check. ``step_span`` always reads the
clock, because its duration is returned to the caller
(``StepResult.gate_s``), and starts a new step id that the spans opened
inside it share.

Names of the program's spans start with ``session.``; a name never starts
with ``bench.``, which is the benchmark's own prefix. The record is one
per process, like the profiler session it follows: ``snapshot()`` reads
it and ``clear()`` empties it.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

from jax.profiler import TraceAnnotation

#: spans kept in memory at most; later ones are counted as dropped
MAX_SPANS = 1 << 16

_enabled = TraceAnnotation.is_enabled
_clock = time.perf_counter_ns


class Span(NamedTuple):
    """One closed span."""

    name: str
    t0_ns: int
    t1_ns: int
    parent: str | None      # name of the span it was opened inside
    step: int | None        # id of the enclosing step span
    meta: dict


class Snapshot(NamedTuple):
    spans: list
    counts: dict
    dropped: int


class _Record:
    def __init__(self):
        self.lock = threading.Lock()
        self.spans: list[Span] = []
        self.counts: collections.Counter = collections.Counter()
        self.dropped = 0
        self.local = threading.local()      # per-thread stack of open spans

    def stack(self) -> list:
        s = getattr(self.local, "stack", None)
        if s is None:
            s = self.local.stack = []
        return s


_RECORD = _Record()
_STEP_IDS = itertools.count()


class _Noop:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP = _Noop()


class _Live:
    """A span while tracing is on."""

    __slots__ = ("name", "meta", "step", "new_step", "parent", "ann",
                 "t0_ns", "t1_ns")

    def __init__(self, name: str, meta: dict, new_step: bool):
        self.name, self.meta, self.new_step = name, meta, new_step
        self.step = next(_STEP_IDS) if new_step else None

    def __enter__(self):
        stack = _RECORD.stack()
        top = stack[-1] if stack else None
        self.parent = top.name if top is not None else None
        if self.step is None and top is not None:
            self.step = top.step
        stack.append(self)
        meta = {**self.meta, "step": self.step} if self.new_step \
            else self.meta
        self.ann = TraceAnnotation(self.name, **meta)
        self.ann.__enter__()
        self.t0_ns = _clock()
        return self

    def __exit__(self, *exc):
        self.t1_ns = _clock()
        self.ann.__exit__(*exc)
        _RECORD.stack().pop()
        span = Span(self.name, self.t0_ns, self.t1_ns, self.parent,
                    self.step, self.meta)
        with _RECORD.lock:
            if len(_RECORD.spans) < MAX_SPANS:
                _RECORD.spans.append(span)
            else:
                _RECORD.dropped += 1
        return False

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


class _Clock:
    """A step span while tracing is off: the two clock reads only."""

    __slots__ = ("t0_ns", "t1_ns")

    def __enter__(self):
        self.t0_ns = _clock()
        return self

    def __exit__(self, *exc):
        self.t1_ns = _clock()
        return False

    @property
    def seconds(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


def span(name: str, **meta):
    """Context manager: a span named ``name`` while tracing is on."""
    return _Live(name, meta, False) if _enabled() else NOOP


def step_span(name: str):
    """Context manager for the root span of one step: a new step id, and
    ``.seconds`` (its duration) after it closes, traced or not."""
    return _Live(name, {}, True) if _enabled() else _Clock()


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name`` while tracing is on."""
    if _enabled():
        with _RECORD.lock:
            _RECORD.counts[name] += n


def sync(site: str):
    """A sanctioned device→host sync at ``site`` (a qualname): a
    ``session.sync`` span, counted in ``session.syncs``."""
    if not _enabled():
        return NOOP
    count("session.syncs")
    return _Live("session.sync", {"site": site}, False)


def snapshot() -> Snapshot:
    """A copy of what was recorded since the last ``clear``."""
    with _RECORD.lock:
        return Snapshot(list(_RECORD.spans), dict(_RECORD.counts),
                        _RECORD.dropped)


def clear() -> None:
    with _RECORD.lock:
        _RECORD.spans.clear()
        _RECORD.counts.clear()
        _RECORD.dropped = 0
