"""Nestable spans of the serving stack, with two sinks.

``span("fleet/step")`` wraps any region of the serving stack.  Where the
span goes depends on the installed tracer:

* ``Tracer`` (``enable_tracing()``) collects Chrome ``trace_event`` JSON;
  the written file loads directly into chrome://tracing or
  https://ui.perfetto.dev (drag it in, or File > Open).
* ``ProfilerTracer`` (``enable_tracing(profiler=True)``) enters a
  ``jax.profiler.TraceAnnotation`` per span.  While a profiler trace is
  running, the span lands on the ``/host:CPU`` plane of its ``.xplane.pb``
  with its args as event stats, on the same clock as the device's
  ``XLA Ops`` events, so device idle time can be put down to the host span
  open at that moment.

Same zero-perturbation contract as ``repro.obs.metrics``: the module-global
tracer starts as the no-op ``NULL_TRACER`` (``enable_tracing()`` swaps in a
real one), and spans time Python-level regions only — they never read or
synchronise traced jax values, so every golden fixture passes integer-exact
with tracing fully on.

Chrome-JSON event format: one ``"ph": "X"`` (complete) event per span,
``ts``/``dur`` in microseconds relative to the tracer's epoch.  Besides the
wall-clock fields, every span records a deterministic ``seq`` (global entry
order) and ``depth`` (per-thread nesting level) in ``args`` — tests assert
nesting and ordering on those, not on timestamps.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import nullcontext

__all__ = [
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "ProfilerTracer",
    "get_tracer",
    "set_tracer",
    "enable_tracing",
    "disable_tracing",
]


class _Span:
    __slots__ = ("_tracer", "_name", "_args", "_t0", "_seq", "_depth")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        self._seq, self._depth = self._tracer._enter()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._tracer._exit(self._name, self._t0, t1, self._seq, self._depth,
                           self._args)
        return False


class Tracer:
    """Collects complete-events; thread-safe (the async checkpoint writer
    may close spans from its background thread)."""

    enabled = True

    def __init__(self):
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._epoch = time.perf_counter()
        self._seq = 0
        self._local = threading.local()

    def span(self, name: str, **args) -> _Span:
        """Nestable timed region: ``with tracer.span("fleet/step", n=4): ...``
        ``args`` must be JSON-serialisable (they land in the event's
        ``args``); never pass traced jax values."""
        return _Span(self, name, args)

    def _enter(self) -> tuple[int, int]:
        with self._lock:
            seq = self._seq
            self._seq += 1
        depth = getattr(self._local, "depth", 0)
        self._local.depth = depth + 1
        return seq, depth

    def _exit(self, name, t0, t1, seq, depth, args) -> None:
        self._local.depth = depth
        event = {
            "name": name,
            "ph": "X",
            "ts": round((t0 - self._epoch) * 1e6, 3),
            "dur": round((t1 - t0) * 1e6, 3),
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": {**args, "seq": seq, "depth": depth},
        }
        with self._lock:
            self._events.append(event)

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome_trace(self) -> dict:
        """The JSON-object form of the trace_event format (both
        chrome://tracing and Perfetto accept it)."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=1)

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._seq = 0


_NULL_CM = nullcontext()


class NullTracer:
    """The disabled tracer: ``span()`` returns one shared stateless context
    manager — no clock reads, no allocation beyond the call itself."""

    enabled = False

    def span(self, name, **args):
        return _NULL_CM

    def events(self):
        return []

    def to_chrome_trace(self):
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def save(self, path):
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=1)

    def reset(self):
        pass


class ProfilerTracer(NullTracer):
    """Spans as ``jax.profiler.TraceAnnotation``s.  Nothing is kept here
    (``events()`` and ``save()`` are the null sink's): the profiler's own
    trace holds the spans, and drops them while no trace runs.  ``args``
    become the event's stats, so pass ints, floats or strings."""

    enabled = True

    def __init__(self):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation

    def span(self, name: str, **args):
        return self._annotation(name, **args)


NULL_TRACER = NullTracer()
_TRACER: Tracer | NullTracer | ProfilerTracer = NULL_TRACER


def get_tracer() -> Tracer | NullTracer | ProfilerTracer:
    """Resolved at call time by every span site, so ``enable_tracing()``
    takes effect everywhere immediately."""
    return _TRACER


def set_tracer(tracer) -> None:
    global _TRACER
    _TRACER = tracer


def enable_tracing(tracer: Tracer | None = None, *,
                   profiler: bool = False) -> Tracer | ProfilerTracer:
    """Switch tracing ON process-wide; returns the installed tracer: a new
    Chrome-JSON ``Tracer``, or with ``profiler=True`` a ``ProfilerTracer``."""
    if tracer is None:
        tracer = ProfilerTracer() if profiler else Tracer()
    set_tracer(tracer)
    return tracer


def disable_tracing() -> None:
    set_tracer(NULL_TRACER)
