"""The program's spans and counters: where the tuner's host time goes.

A span names one stretch of work at a layer boundary::

    with span("looptune.harvest"):
        ...

While a profiler session collects (``jax.profiler.start_trace`` or any
other), a span opens a ``jax.profiler.TraceAnnotation`` of its name, so the
trace shows it on the host plane on the device trace's clock, and adds its
duration and its self time (the duration less its child spans on the same
thread) to in-memory totals; :func:`count` adds to a counter the same way.
With no session a span costs one check of the profiler's flag and nothing
else.  :func:`timed` is a span whose ``seconds`` the caller reads whether or
not a session collects: a boundary that reports its own seconds
(``compile_s``, ``tune_time_s``) keeps one timer, this one.

The totals hold what the spans recorded since :func:`reset`, per thread:
:func:`totals` merges the threads or reads one.  No event list is kept; the
profiler's trace holds every event.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, List, Optional, Tuple

import jax

#: every span the program opens, outermost first
SPAN_NAMES = (
    "looptune.tune_model",       # launch/tune.tune_model, one per table
    "looptune.harvest",          # launch/tune.harvest_model
    "looptune.contraction",      # core/tuner.LoopTuner.tune, one per contraction
    "looptune.compile.trace",    # core/jax_backend: Python trace + jax.export
    "looptune.compile.load",     # core/jax_backend: an executable deserialized
    "looptune.compile.wait",     # core/jax_backend: blocked on another's build
    "looptune.compile.backend",  # core/jax_backend: an executable's first call
    "looptune.inputs",           # core/jax_backend: operands made and copied
    "looptune.measure",          # core/measure.MeasurementPolicy.measure
    "looptune.registry.flush",   # core/registry.ScheduleRegistry.flush
)
#: every counter the program adds to
COUNTER_NAMES = (
    "looptune.inputs.bytes",     # operand bytes copied to the device
    "looptune.measure.runs",     # runs of a schedule, warm-ups and timed
)

_clock = time.perf_counter
_OFF = contextlib.nullcontext()
_local = threading.local()
_lock = threading.Lock()
#: (thread name, span or counter name) -> [count, seconds, self seconds]
_totals: Dict[Tuple[str, str], List[float]] = {}


def recording() -> bool:
    """Whether a profiler session is collecting.  The one place that reads
    JAX's host tracer flag, which ``jax.profiler.start_trace`` turns on and
    ``stop_trace`` off."""
    from jax._src.lib import _profiler

    return _profiler.TraceMe.is_enabled()


def _add(name: str, n: float, seconds: float, self_seconds: float) -> None:
    key = (threading.current_thread().name, name)
    with _lock:
        t = _totals.setdefault(key, [0, 0.0, 0.0])
        t[0] += n
        t[1] += seconds
        t[2] += self_seconds


class _Span:
    __slots__ = ("name", "seconds", "_t0", "_children", "_annotation",
                 "_stack")

    def __init__(self, name: str, record: bool):
        if record and name not in SPAN_NAMES:
            raise ValueError(f"unknown span {name!r}; spans.SPAN_NAMES "
                             "lists every span")
        self.name = name
        self.seconds = 0.0
        self._stack: Optional[list] = None
        if record:
            self._stack = _local.__dict__.setdefault("stack", [])
            self._annotation = jax.profiler.TraceAnnotation(name)

    def __enter__(self) -> "_Span":
        if self._stack is not None:
            self._children = 0.0
            self._annotation.__enter__()
            self._stack.append(self)
        self._t0 = _clock()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = _clock() - self._t0
        stack = self._stack
        if stack is None:
            return
        stack.pop()
        if stack:
            stack[-1]._children += self.seconds
        self._annotation.__exit__(*exc)
        _add(self.name, 1, self.seconds, self.seconds - self._children)


def span(name: str):
    """A span of ``name`` (one of :data:`SPAN_NAMES`); a no-op with no
    profiler session."""
    return _Span(name, True) if recording() else _OFF


def timed(name: str) -> _Span:
    """A span of ``name`` whose ``seconds`` is set on exit, with or without
    a profiler session."""
    return _Span(name, recording())


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name`` (one of :data:`COUNTER_NAMES`)
    while a profiler session collects."""
    if not recording():
        return
    if name not in COUNTER_NAMES:
        raise ValueError(f"unknown counter {name!r}; spans.COUNTER_NAMES "
                         "lists every counter")
    _add(name, n, 0.0, 0.0)


def totals(thread: Optional[str] = None) -> Dict[str, Dict[str, float]]:
    """``{name: {"count", "seconds", "self_seconds"}}`` of what the spans
    and counters recorded since :func:`reset`, on every thread or on the
    thread of that name.  A counter's ``count`` is its sum."""
    out: Dict[str, Dict[str, float]] = {}
    with _lock:
        items = [(k, list(v)) for k, v in _totals.items()]
    for (t, name), (n, s, own) in items:
        if thread is not None and t != thread:
            continue
        d = out.setdefault(name, {"count": 0, "seconds": 0.0,
                                  "self_seconds": 0.0})
        d["count"] += n
        d["seconds"] += s
        d["self_seconds"] += own
    return out


def reset() -> None:
    """Forget every total."""
    with _lock:
        _totals.clear()
