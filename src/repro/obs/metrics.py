"""Process-local metrics: counters, gauges, fixed-bucket histograms (ISSUE 9).

The paper's headline numbers (17 534 inf/s, 3.8 uJ/inference) are
*measurements*; this module is the reproduction's measurement substrate.
Every layer of the serving stack (``SensorFleetEngine``, checkpoint I/O,
kernel dispatch, QAT search) counts and times itself through one
``MetricsRegistry`` — under a hard **zero-perturbation contract**:

* **Off by default.**  The module-global registry starts as the shared
  ``NULL_REGISTRY`` whose every method is a no-op; instrumentation sites pay
  one attribute lookup + one no-op call.  ``enable()`` swaps in a real
  registry (``disable()`` swaps it back), so observability is a process-mode
  switch, never a datapath branch.
* **Never touch traced values.**  Instrumentation may *count* and *time*
  Python-level events; it must never read, convert or synchronise a traced
  jax value.  With a fully enabled registry every golden fixture and
  bit-identity battery still passes integer-exact
  (``tests/test_obs.py::test_golden_integers_unchanged_with_obs_enabled``).
* **Deterministic export.**  ``snapshot()`` / ``to_json()`` emit sorted-key
  JSON; nothing reads a wall clock except explicitly *timed* histograms
  (``time(name)``), which are flagged ``"timed": true`` so deterministic
  consumers can drop them (``to_json(drop_timed=True)`` — two identical
  runs produce byte-identical output).

Histograms use **fixed bucket edges** (default: the log-spaced microsecond
ladder ``DEFAULT_US_EDGES``), so percentile estimates (p50/p95/p99) are a
deterministic function of the bucket counts — no raw-sample storage, O(1)
memory per metric.

Counters survive kill -> restore: ``SensorFleetEngine.checkpoint_payload``
embeds ``snapshot()`` in the checkpoint side-car and ``restore`` feeds it
back through ``merge_snapshot``, so a resumed fleet reports cumulative (not
reset) counts — including the restore's own timing, recorded before the
merge.
"""

from __future__ import annotations

import bisect
import json
import threading
import time
from contextlib import nullcontext

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
]

# Log-spaced microsecond ladder: 1 us .. 5 s, the whole range a serving-path
# event can plausibly take (submit validation ~ us, checkpoint I/O ~ ms-s).
DEFAULT_US_EDGES = (
    1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
    1e3, 2e3, 5e3, 1e4, 2e4, 5e4, 1e5, 2e5, 5e5, 1e6, 2e6, 5e6,
)


class Histogram:
    """Fixed-bucket histogram: ``len(edges) + 1`` buckets — one per upper
    edge plus an overflow bucket.  Quantiles are estimated as the upper edge
    of the first bucket whose cumulative count covers the rank (overflow
    bucket reports the observed max), so the estimate is a deterministic
    function of (edges, counts, min, max)."""

    __slots__ = ("edges", "counts", "count", "sum", "min", "max", "timed")

    def __init__(self, edges=DEFAULT_US_EDGES, *, timed: bool = False):
        if not edges or list(edges) != sorted(edges):
            raise ValueError(f"bucket edges must be ascending, got {edges!r}")
        self.edges = tuple(float(e) for e in edges)
        self.counts = [0] * (len(self.edges) + 1)
        self.count = 0
        self.sum = 0.0
        self.min: float | None = None
        self.max: float | None = None
        self.timed = timed

    def observe(self, value: float) -> None:
        v = float(value)
        self.counts[bisect.bisect_left(self.edges, v)] += 1
        self.count += 1
        self.sum += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)

    def observe_many(self, values) -> None:
        """``observe`` each of ``values``: one call for a batch."""
        for v in values:
            self.observe(v)

    def quantile(self, q: float) -> float | None:
        if self.count == 0:
            return None
        rank = q * self.count
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= rank and c:
                if i < len(self.edges):
                    return self.edges[i]
                return self.max          # overflow bucket: report observed max
        return self.max

    def snapshot(self) -> dict:
        return {
            "edges": list(self.edges),
            "counts": list(self.counts),
            "count": self.count,
            "sum": self.sum,
            "min": self.min,
            "max": self.max,
            "p50": self.quantile(0.50),
            "p95": self.quantile(0.95),
            "p99": self.quantile(0.99),
            "timed": self.timed,
        }

    def load(self, snap: dict) -> None:
        """Replace this histogram's state with a ``snapshot()`` dict."""
        edges = tuple(float(e) for e in snap["edges"])
        counts = [int(c) for c in snap["counts"]]
        if len(counts) != len(edges) + 1:
            raise ValueError("histogram snapshot counts/edges length mismatch")
        self.edges = edges
        self.counts = counts
        self.count = int(snap["count"])
        self.sum = float(snap["sum"])
        self.min = None if snap["min"] is None else float(snap["min"])
        self.max = None if snap["max"] is None else float(snap["max"])
        self.timed = bool(snap.get("timed", self.timed))

    def merge(self, snap: dict) -> None:
        """Add a ``snapshot()`` dict into this histogram (the checkpoint-
        restore path: saved cumulative observations + whatever this process
        already recorded).  Mismatched edges fall back to ``load``."""
        edges = tuple(float(e) for e in snap["edges"])
        if edges != self.edges:
            self.load(snap)
            return
        for i, c in enumerate(snap["counts"]):
            self.counts[i] += int(c)
        self.count += int(snap["count"])
        self.sum += float(snap["sum"])
        for attr, pick in (("min", min), ("max", max)):
            other = snap[attr]
            if other is not None:
                mine = getattr(self, attr)
                setattr(self, attr, float(other) if mine is None
                        else pick(mine, float(other)))


class _Timer:
    """Context manager: one explicitly-timed observation (microseconds)."""

    __slots__ = ("_reg", "_name", "_t0")

    def __init__(self, reg: "MetricsRegistry", name: str):
        self._reg = reg
        self._name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        us = (time.perf_counter() - self._t0) * 1e6
        self._reg.observe(self._name, us, timed=True)
        return False


class MetricsRegistry:
    """Thread-safe registry of counters, gauges and fixed-bucket histograms.

    All mutators are safe to call from the checkpoint writer's background
    thread; the only wall-clock reads are inside ``time(name)`` (explicitly
    timed histograms, flagged in the snapshot).
    """

    enabled = True

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, int] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, Histogram] = {}

    # -- mutators -------------------------------------------------------------

    def inc(self, name: str, value: int = 1) -> None:
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + int(value)

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self._gauges[name] = float(value)

    def observe(self, name: str, value: float, *, edges=DEFAULT_US_EDGES,
                timed: bool = False) -> None:
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(edges, timed=timed)
            h.observe(value)

    def observe_many(self, name: str, values, *, edges=DEFAULT_US_EDGES,
                     timed: bool = False) -> None:
        """``observe`` each of ``values`` into one histogram, under one lock
        (a drain's per-stream readings in one call)."""
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram(edges, timed=timed)
            h.observe_many(values)

    def time(self, name: str) -> _Timer:
        """``with reg.time("fleet/step_us"): ...`` — the ONLY sanctioned
        wall-clock read; the histogram it feeds is flagged ``timed``."""
        return _Timer(self, name)

    # -- pre-registration (zero-valued metrics appear in every snapshot) ------

    def declare_counter(self, name: str) -> None:
        with self._lock:
            self._counters.setdefault(name, 0)

    def declare_gauge(self, name: str, value: float = 0.0) -> None:
        with self._lock:
            self._gauges.setdefault(name, float(value))

    def declare_hist(self, name: str, *, edges=DEFAULT_US_EDGES,
                     timed: bool = False) -> None:
        with self._lock:
            if name not in self._hists:
                self._hists[name] = Histogram(edges, timed=timed)

    # -- export / restore -----------------------------------------------------

    def snapshot(self, *, drop_timed: bool = False) -> dict:
        """JSON-serialisable state, keys sorted (deterministic given the same
        sequence of non-timed observations).  ``drop_timed`` excludes the
        explicitly-timed histograms so the result is byte-stable across
        runs."""
        with self._lock:
            return {
                "counters": {k: self._counters[k]
                             for k in sorted(self._counters)},
                "gauges": {k: self._gauges[k] for k in sorted(self._gauges)},
                "histograms": {k: self._hists[k].snapshot()
                               for k in sorted(self._hists)
                               if not (drop_timed and self._hists[k].timed)},
            }

    def to_json(self, *, drop_timed: bool = False) -> str:
        return json.dumps(self.snapshot(drop_timed=drop_timed),
                          sort_keys=True, indent=1)

    def save_json(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())

    def load_snapshot(self, snap: dict) -> None:
        """Adopt a ``snapshot()`` dict wholesale.  Existing same-named
        metrics are overwritten; others are kept."""
        with self._lock:
            for k, v in snap.get("counters", {}).items():
                self._counters[k] = int(v)
            for k, v in snap.get("gauges", {}).items():
                self._gauges[k] = float(v)
            for k, hsnap in snap.get("histograms", {}).items():
                h = self._hists.get(k)
                if h is None:
                    h = self._hists[k] = Histogram(hsnap["edges"])
                h.load(hsnap)

    def merge_snapshot(self, snap: dict) -> None:
        """ADD a ``snapshot()`` dict into this registry — the checkpoint-
        restore path: a resumed process reports the saved cumulative counts
        plus everything it already recorded itself (e.g. the restore's own
        timing), so counters never reset across kill -> restore.  Gauges are
        point-in-time: the saved value only fills a key this process hasn't
        set."""
        with self._lock:
            for k, v in snap.get("counters", {}).items():
                self._counters[k] = self._counters.get(k, 0) + int(v)
            for k, v in snap.get("gauges", {}).items():
                self._gauges.setdefault(k, float(v))
            for k, hsnap in snap.get("histograms", {}).items():
                h = self._hists.get(k)
                if h is None:
                    h = self._hists[k] = Histogram(
                        hsnap["edges"], timed=bool(hsnap.get("timed", False)))
                h.merge(hsnap)

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


_NULL_CM = nullcontext()


class NullRegistry:
    """The disabled registry: every method is a no-op, ``time()`` hands back
    one shared stateless context manager.  This is the off-by-default path —
    instrumented code costs one attribute lookup + one no-op call per site
    (< 5% of the fleet step path; bench row ``serving/lstm_fleet_observed``).
    """

    enabled = False

    def inc(self, name, value=1):
        pass

    def gauge(self, name, value):
        pass

    def observe(self, name, value, *, edges=None, timed=False):
        pass

    def observe_many(self, name, values, *, edges=None, timed=False):
        pass

    def time(self, name):
        return _NULL_CM

    def declare_counter(self, name):
        pass

    def declare_gauge(self, name, value=0.0):
        pass

    def declare_hist(self, name, *, edges=None, timed=False):
        pass

    def snapshot(self, *, drop_timed=False):
        return {"counters": {}, "gauges": {}, "histograms": {}}

    def to_json(self, *, drop_timed=False):
        return json.dumps(self.snapshot(), sort_keys=True, indent=1)

    def save_json(self, path):
        with open(path, "w") as f:
            f.write(self.to_json())

    def load_snapshot(self, snap):
        pass

    def merge_snapshot(self, snap):
        pass

    def reset(self):
        pass


NULL_REGISTRY = NullRegistry()
_REGISTRY: MetricsRegistry | NullRegistry = NULL_REGISTRY


def get_registry() -> MetricsRegistry | NullRegistry:
    """The process-local registry every instrumentation site resolves at
    call time (so ``enable()`` takes effect everywhere immediately)."""
    return _REGISTRY


def set_registry(reg) -> None:
    global _REGISTRY
    _REGISTRY = reg


def use_registry(reg):
    """Context manager: install ``reg`` globally, restore the previous
    registry on exit (test isolation)."""
    import contextlib

    @contextlib.contextmanager
    def _use():
        prev = _REGISTRY
        set_registry(reg)
        try:
            yield reg
        finally:
            set_registry(prev)

    return _use()


def enable(registry: MetricsRegistry | None = None) -> MetricsRegistry:
    """Switch metrics ON process-wide; returns the installed registry."""
    reg = registry if registry is not None else MetricsRegistry()
    set_registry(reg)
    return reg


def disable() -> None:
    """Back to the shared no-op registry (the zero-overhead default)."""
    set_registry(NULL_REGISTRY)
