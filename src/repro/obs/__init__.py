"""Fleet-wide observability: metrics + tracing with a zero-perturbation
guarantee (ISSUE 9).

Two halves, both off by default (shared no-op singletons) and both resolved
at call time by every instrumentation site:

* ``repro.obs.metrics`` — a process-local ``MetricsRegistry`` of counters,
  gauges and fixed-bucket histograms (p50/p95/p99), deterministic sorted-JSON
  export, counters round-tripped through checkpoints.
* ``repro.obs.trace`` — nestable spans, serialised as Chrome ``trace_event``
  JSON for chrome://tracing / Perfetto, or with ``enable_tracing(
  profiler=True)`` written into a running ``jax.profiler`` trace.

The contract: instrumentation may time and count Python-level events, never
touch traced values — with everything enabled, every golden fixture and
bit-identity battery still passes integer-exact (``tests/test_obs.py``).

Quick start::

    from repro import obs
    reg = obs.enable()                 # metrics on
    tracer = obs.enable_tracing()      # spans on
    ...serve...
    reg.save_json("metrics.json")
    tracer.save("trace.json")          # open in Perfetto
    obs.disable_all()

Instrumented layers: ``serving/lstm_engine.py`` (submit latency, admit-queue
depth, slot occupancy, whole-step time, admission rejections, staged
timesteps; spans of admission and of every part of the step), ``serving/ingest.py`` (enqueue
and admission latency, queue depth; spans of enqueue and of each drain),
``checkpoint/checkpoint.py`` (save/restore duration, payload bytes, torn
sweeps), ``serving/faults.py::retry_io`` (retry counts),
``core/lstm.py::recurrent_forward`` (per-backend dispatch counts +
block-shape tags), ``qat/search.py`` (per-point eval timing).
"""

from repro.obs.metrics import (DEFAULT_US_EDGES, NULL_REGISTRY, Histogram,
                               MetricsRegistry, NullRegistry, disable, enable,
                               get_registry, set_registry, use_registry)
from repro.obs.trace import (NULL_TRACER, NullTracer, ProfilerTracer, Tracer,
                             disable_tracing, enable_tracing, get_tracer,
                             set_tracer)

__all__ = [
    "DEFAULT_US_EDGES",
    "Histogram",
    "MetricsRegistry",
    "NullRegistry",
    "NULL_REGISTRY",
    "get_registry",
    "set_registry",
    "use_registry",
    "enable",
    "disable",
    "Tracer",
    "NullTracer",
    "NULL_TRACER",
    "ProfilerTracer",
    "get_tracer",
    "set_tracer",
    "enable_tracing",
    "disable_tracing",
    "disable_all",
]


def disable_all() -> None:
    """Back to the no-op defaults for both metrics and tracing."""
    disable()
    disable_tracing()
