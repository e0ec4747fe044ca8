"""Spans of the live JAX path, on the profiler's clock.

``span(name)`` enters a ``jax.profiler.TraceAnnotation``, so a profile taken
with ``jax.profiler.trace`` holds the span in its host plane, on the clock
that the device planes share.  It also keeps ``(start, end, step, parent)``
from ``time.perf_counter`` in a bounded in-memory record, one deque per span
name, whether or not a profile is being taken: a few microseconds a span.
``step_span`` is the same for the root of one training step, which XProf's
step view reads.

``spans(name)`` and ``reset()`` are the read API.  Nothing is exported from
here: the profile is the export.  The record is process-wide and keyed by
span name alone, so it reads one trainer per process: where several
trainers share a process (the operator's jobs), their spans interleave.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Deque, Dict, List, NamedTuple, Optional

import jax

MAXLEN = 16384          # records kept per span name; the oldest are dropped


class SpanRecord(NamedTuple):
    start: float                # time.perf_counter() seconds
    end: float
    step: Optional[int]         # the training step; a child takes its parent's
    parent: Optional[str]       # the span open around it when it began

    @property
    def seconds(self) -> float:
        return self.end - self.start


_records: Dict[str, Deque[SpanRecord]] = {}
_lock = threading.Lock()
_open = threading.local()       # each thread's stack of open spans


def _stack() -> List["Span"]:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


class Span:
    """One open span; ``record`` holds what was kept once it has closed."""

    __slots__ = ("name", "step", "record", "_annotation", "_parent", "_start")

    def __init__(self, name: str, step: Optional[int], annotation):
        self.name, self.step, self._annotation = name, step, annotation
        self.record: Optional[SpanRecord] = None

    def __enter__(self) -> "Span":
        stack = _stack()
        outer = stack[-1] if stack else None
        self._parent = outer.name if outer else None
        if self.step is None and outer is not None:
            self.step = outer.step
        stack.append(self)
        self._annotation.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self._annotation.__exit__(*exc)
        _stack().pop()
        self.record = SpanRecord(self._start, end, self.step, self._parent)
        q = _records.get(self.name)
        if q is None:
            with _lock:
                q = _records.setdefault(self.name, deque(maxlen=MAXLEN))
        q.append(self.record)


def span(name: str, step: Optional[int] = None) -> Span:
    """A span named ``name``; without ``step`` it takes its parent's."""
    return Span(name, step, jax.profiler.TraceAnnotation(name))


def step_span(name: str, step: int) -> Span:
    """The root span of training step ``step``, marked as a step for the
    profiler's step view."""
    return Span(name, step,
                jax.profiler.StepTraceAnnotation(name, step_num=step))


def spans(name: str) -> List[SpanRecord]:
    """The kept records of ``name``, oldest first."""
    return list(_records.get(name, ()))


def reset() -> None:
    """Forget every record."""
    with _lock:
        _records.clear()
