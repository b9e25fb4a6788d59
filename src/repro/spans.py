"""Program spans: where the search and serving paths spend host time.

``with span(name, counter):`` does two things:

* while a JAX profiler trace is being recorded, it opens a
  ``jax.profiler.TraceAnnotation(name)``, so the span lands on the trace's
  host plane, on the same clock as the device's operations;
* it adds the span's self time to ``counter[name + ".ns"]`` (nanoseconds)
  and one to ``counter[name + ".n"]``.

Self time is the span's wall time less the wall time of the spans opened
inside it on the same thread. Nested spans therefore split time between
them and never count it twice: a GA operator that calls an evaluation
wrapped in its own span keeps only its own work.

The counters are module-level ``Counter``s beside the code they measure
(``batchsim_compiled.totals``, ``ga.totals``, ``runtime.engine.totals``)
and are totals since the process started. Every span name starts with
``puzzle.``, apart from any span a caller opens around the program.
Spans sit at the granularity of a batch, a generation step or a task,
never inside a per-event or per-layer loop.
"""
from __future__ import annotations

import sys
import threading
import time
from collections import Counter
from typing import Any

_lock = threading.Lock()
_local = threading.local()
_annotation: Any = None


def _recording() -> Any:
    """``jax.profiler.TraceAnnotation`` while a trace is being recorded,
    else None. A trace can only record in a process that imported JAX, and
    the host-only search path must not import it for a span."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return None
        _annotation = profiler.TraceAnnotation
    return _annotation if _annotation.is_enabled() else None


def _stack() -> list:
    """This thread's open spans: the wall time of each one's inner spans."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """Time the ``with`` block as span ``name`` into ``counter``."""

    __slots__ = ("_name", "_counter", "_ann", "_t0")

    def __init__(self, name: str, counter: Counter) -> None:
        self._name = name
        self._counter = counter

    def __enter__(self) -> "span":
        ann = _recording()
        self._ann = None if ann is None else ann(self._name)
        if self._ann is not None:
            self._ann.__enter__()
        _stack().append(0)
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: object) -> None:
        wall = time.perf_counter_ns() - self._t0
        stack = _local.stack
        inner = stack.pop()
        if stack:
            stack[-1] += wall
        with _lock:
            self._counter[self._name + ".ns"] += wall - inner
            self._counter[self._name + ".n"] += 1
        if self._ann is not None:
            self._ann.__exit__(*exc)
