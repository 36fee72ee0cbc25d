"""The serving program's tracer: named spans and integer counters.

One :class:`Tracer` serves an engine (``engine.tracer``).  The scheduler and
the runner open spans at their boundaries and count the work they grant:

* ``span(name, **attrs)`` is a context manager.  While the tracer is on it
  records ``(name, start, end, span id, parent id, attrs)`` on
  ``time.perf_counter`` into a ring of :data:`SPAN_RING` spans (a
  ``deque``; spans that roll off are counted in ``dropped``), and enters
  ``jax.profiler.TraceAnnotation(name, **attrs)`` so that a profiler trace
  shows the span beside the device's events.  While the tracer is off,
  ``span`` returns one shared no-op object: no allocation, no clock read.
* ``timed(name, **attrs)`` is a span that reads the clock even while the
  tracer is off, for a caller that uses its duration (the scheduler's
  per-request times and latency windows); it is recorded only while on.
* ``record(name, start, end, **attrs)`` keeps a span whose ends were read
  elsewhere: the per-request spans, which do not nest on one thread and so
  stay in memory only.
* ``count(name, n=1)`` adds to an integer counter, whether on or off.

Device counters: model code adds counts that only the device knows (the
MoE layer's routed rows) with :func:`add_device_count` while a runner
program traces inside :func:`device_counts`; the program returns them
beside its tokens and the runner adds them to the tracer's counters when
it reads those tokens, so they cost no extra host sync.

The tracer is on after ``enable()`` (``engine.tracer.enable()``) and
while an in-process profiler session runs (``jax.profiler.start_trace`` or
``jax.profiler.trace``): a profile of the serving loop carries the
program's spans without a switch.

Span names (``sched.*``, ``runner.*``, ``health.probe``, ``request.*``) and
counter names are listed in README.md ("Tracing the serving loop").
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import time
import warnings
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

try:  # the in-process profiler session, if any (JAX keeps it privately)
    from jax._src.profiler import _profile_state as _PROFILE_STATE
except ImportError:  # pragma: no cover - tests/test_trace.py catches it
    _PROFILE_STATE = None
    warnings.warn("repro.serving.trace: this JAX keeps its profiler session "
                  "elsewhere; the tracer records only after enable()")

SPAN_RING = 1 << 16          # spans kept in memory before the oldest roll off
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

_backend_compiles = 0
_listening = False


def _on_event(event: str, duration: float, **_: Any) -> None:
    global _backend_compiles
    if event == BACKEND_COMPILE:
        _backend_compiles += 1


def backend_compiles() -> int:
    """XLA executables this process has compiled or loaded from the
    persistent cache since the first tracer was built."""
    return _backend_compiles


_DEVICE_COUNTS: Optional[Dict[str, jax.Array]] = None


@contextlib.contextmanager
def device_counts() -> Iterator[Dict[str, jax.Array]]:
    """Collect the counts that model code adds with :func:`add_device_count`
    while a program traces in this context; yields the dict (name -> traced
    int32 scalar) they are summed into."""
    global _DEVICE_COUNTS
    prev, _DEVICE_COUNTS = _DEVICE_COUNTS, {}
    try:
        yield _DEVICE_COUNTS
    finally:
        _DEVICE_COUNTS = prev


def add_device_count(name: str, n: jax.Array) -> None:
    """Add the traced integer ``n`` to the device counter ``name`` of the
    program tracing inside :func:`device_counts` (a no-op outside one).
    Call it outside the scans the counted model call opens: such a scan
    returns its counts as outputs and the caller adds their sum."""
    if _DEVICE_COUNTS is not None:
        n = jnp.asarray(n, jnp.int32)
        _DEVICE_COUNTS[name] = _DEVICE_COUNTS.get(name, 0) + n


def profiler_running() -> bool:
    return (_PROFILE_STATE is not None
            and _PROFILE_STATE.profile_session is not None)


class _NoSpan:
    """The shared span of a tracer that is off."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


NO_SPAN = _NoSpan()


class Span:
    """One timed interval; recorded into its tracer's ring when ``keep``."""

    __slots__ = ("tracer", "name", "attrs", "keep", "id", "parent", "start",
                 "end", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any],
                 keep: bool):
        self.tracer, self.name, self.attrs, self.keep = (tracer, name, attrs,
                                                        keep)
        self.id = self.parent = None
        self.start = self.end = 0.0

    def __enter__(self) -> "Span":
        if self.keep:
            tr = self.tracer
            self.id = next(tr._ids)
            self.parent = tr._open[-1] if tr._open else None
            tr._open.append(self.id)
            self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                            **self.attrs)
            self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> bool:
        self.end = time.perf_counter()
        if self.keep:
            tr = self.tracer
            self._annotation.__exit__(*exc)
            tr._open.pop()
            tr._keep((self.name, self.start, self.end, self.id, self.parent,
                      self.attrs))
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Tracer:
    """Spans into a bounded ring while on; counters always."""

    def __init__(self):
        global _listening
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _listening = True
        self.enabled = False
        self.spans: "collections.deque[Tuple]" = collections.deque(
            maxlen=SPAN_RING)
        self.dropped = 0
        self.counters: Dict[str, int] = {}
        self._ids = itertools.count(1)
        self._open: List[int] = []

    def enable(self) -> None:
        self.enabled = True

    @property
    def on(self) -> bool:
        return self.enabled or profiler_running()

    def span(self, name: str, **attrs: Any):
        if not self.on:
            return NO_SPAN
        return Span(self, name, attrs, True)

    def timed(self, name: str, **attrs: Any) -> Span:
        return Span(self, name, attrs, self.on)

    def record(self, name: str, start: float, end: float, **attrs: Any
               ) -> None:
        if self.on:
            self._keep((name, start, end, next(self._ids), None, attrs))

    def count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n

    def _keep(self, rec: Tuple) -> None:
        if len(self.spans) == self.spans.maxlen:
            self.dropped += 1
        self.spans.append(rec)

    def stats(self) -> Dict[str, Any]:
        """``{"counters": {...}}``, and ``"trace": {"spans": [...],
        "dropped": n}`` once the tracer is on or has recorded a span.  Each
        span is a dict of ``name``, ``start``, ``end`` (perf_counter
        seconds), ``id``, ``parent`` (None at the top) and ``attrs``."""
        out: Dict[str, Any] = {"counters": dict(self.counters)}
        if self.spans or self.dropped or self.on:
            out["trace"] = {
                "spans": [{"name": n, "start": a, "end": b, "id": i,
                           "parent": p, "attrs": dict(at)}
                          for n, a, b, i, p, at in self.spans],
                "dropped": self.dropped}
        return out
