"""Deterministic fault injection for fleet serving (ISSUE 6).

The paper's 17 534 inf/s at 3.8 uJ only matters if the serving loop keeps
producing those integers through restarts, device loss and garbage sensor
input.  This module is the adversary: every failure mode the
checkpoint/restore + validation machinery claims to survive is injected
*deterministically* here, so the bit-identity batteries can assert the
recovery path produces the same integers as an uninterrupted run.

Injectable faults:

* **kill-between-steps** — ``FaultPlan(kill_after_steps=N)`` raises
  ``InjectedKill`` after the N-th engine step of a ``serve_with_checkpoints``
  loop, emulating SIGKILL between kernel dispatches (the engine object is
  abandoned; only what ``CheckpointManager`` published survives).
* **torn checkpoint write** — ``FaultPlan(torn_write_at=K)`` makes the save
  scheduled at step K die mid-write: ``torn_save`` writes the
  ``step_<N>.tmp/`` payload and "crashes" before manifest + atomic rename —
  exactly the on-disk state a real kill mid-``save_pytree`` leaves.
  ``corrupt_published`` models the other torn state (post-publish disk
  damage: manifest gone/unreadable); both must fall back to the latest
  valid step on restore.
* **flaky checkpoint I/O** — ``FlakyCheckpointManager(inner, fail_first=N)``
  raises ``OSError`` from the first N ``save`` calls (NFS hiccup, full
  disk); the engine's bounded ``retry_io`` backoff must ride through it.
* **poison input** — ``poison_stream(kind, ...)`` builds every malformed
  ``SensorStream`` the ``submit`` boundary must reject (NaN/Inf, wrong
  dtype/ndim/feature-width, empty, fixed-point overflow), and
  ``poison_mid_flight`` corrupts an *admitted* stream's ``qxs``: the engine
  serves the copy it staged at the claim, so the corruption must show
  isolation (the stream completes with its claim-time integers, the other
  lanes are untouched), not a quarantine.
* **ingest queue overflow** — ``IngestFaultPlan(overflow_at=N,
  overflow_burst=B)`` floods the ``IngestQueue`` with B extra arrivals
  just before serving step N (an arrival storm): the queue's backpressure
  policy — not an exception in the serving loop — must absorb it
  (``reject`` → counted ``QueueFullError``s, ``drop-oldest`` → bounded
  evictions), and the streams already enqueued still finish bit-exact.
* **slow consumer** — ``IngestFaultPlan(stall_from=N, stall_steps=K)``
  freezes the serving side (no ``pump``, no ``engine.step``) for K loop
  iterations starting at step N while arrivals keep landing, so the queue
  backs up exactly as it would behind a stalled device; admission must
  resume FIFO afterwards with identical integers.

Device-count change (D -> D') is not a fault to inject — it is the restore
path itself: ``SensorFleetEngine.restore(..., mesh=)`` /
``checkpoint.elastic.elastic_fleet_restore`` re-derive slot placement for
whatever devices are alive (battery:
``tests/spmd_scripts/check_fleet_restore.py``).
"""

from __future__ import annotations

import dataclasses
import shutil
import time
from typing import Any, Callable

import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager, _flatten_with_names
from repro.obs.metrics import get_registry as _obs_metrics

__all__ = [
    "InjectedKill", "FaultPlan", "IngestFaultPlan", "retry_io", "torn_save",
    "corrupt_published", "FlakyCheckpointManager", "poison_stream",
    "poison_mid_flight", "POISON_KINDS", "serve_with_checkpoints",
    "serve_through_ingest",
]


class InjectedKill(RuntimeError):
    """The deterministic stand-in for SIGKILL: whatever state was not yet
    published through the CheckpointManager is gone."""


@dataclasses.dataclass
class FaultPlan:
    """What goes wrong, and exactly when (all step counts are relative to
    the current ``serve_with_checkpoints`` call, so a resumed loop can carry
    its own fresh plan)."""

    kill_after_steps: int | None = None   # SIGKILL after the N-th step
    torn_write_at: int | None = None      # the save at step K dies mid-write


@dataclasses.dataclass
class IngestFaultPlan(FaultPlan):
    """``FaultPlan`` extended with the ingest-layer faults
    ``serve_through_ingest`` injects (step counts are loop iterations of
    the current call, like the base plan's):

    * ``overflow_at``/``overflow_burst`` — queue-overflow burst: before
      loop step N, submit B extra streams (from ``burst_streams``) on top
      of the scheduled arrivals; the queue's policy must absorb the storm.
    * ``stall_from``/``stall_steps`` — slow consumer: loop steps
      ``[stall_from, stall_from + stall_steps)`` skip the serving side
      entirely (no pump, no engine step) while arrivals continue, so the
      queue depth grows against capacity.
    """

    overflow_at: int | None = None        # burst lands before loop step N
    overflow_burst: int = 0               # how many extra streams in the burst
    stall_from: int | None = None         # first stalled loop step
    stall_steps: int = 0                  # how many steps the consumer stalls


def retry_io(fn: Callable[[], Any], *, attempts: int = 3,
             base_delay: float = 0.05, sleep: Callable[[float], None] = time.sleep,
             exceptions: tuple = (OSError,)) -> Any:
    """Bounded retry with exponential backoff around checkpoint I/O.

    ``attempts`` total tries; delays ``base_delay * 2**k`` between them.
    Bounded by design: serving must degrade (surface the error, keep the
    streams in memory) rather than hang forever on a dead filesystem.
    """
    if attempts < 1:
        raise ValueError("attempts must be >= 1")
    m = _obs_metrics()
    for k in range(attempts):
        try:
            return fn()
        except exceptions:
            if k == attempts - 1:
                m.inc("ckpt/io_failures_total")
                raise
            m.inc("ckpt/io_retries_total")
            sleep(base_delay * (2 ** k))


def torn_save(manager: CheckpointManager, step: int, tree: Any,
              extra: dict | None = None):
    """Crash a ``save`` mid-write, deterministically.

    Writes the payload into ``step_<N>.tmp/`` and returns before the
    manifest and the atomic rename — the exact torn state a kill inside
    ``save_pytree`` leaves on disk.  ``extra`` is accepted (signature-
    compatible with ``manager.save``) and deliberately never written.
    Returns the orphaned tmp path.
    """
    del extra
    manager.wait()
    tmp = (manager.root / f"step_{step}").with_suffix(".tmp")
    if tmp.exists():
        shutil.rmtree(tmp)
    tmp.mkdir(parents=True)
    names, leaves, _ = _flatten_with_names(tree)
    arrays = {n.replace("/", "%"): np.asarray(a) for n, a in zip(names, leaves)}
    np.savez(tmp / "arrays.npz", **arrays)
    return tmp


def corrupt_published(manager: CheckpointManager, step: int) -> None:
    """Damage an already-published step (the post-publish disk-rot variant of
    a torn write): truncate its manifest so validity filtering must skip it."""
    (manager.root / f"step_{step}" / "manifest.json").write_text("{ torn")


class FlakyCheckpointManager:
    """Delegating wrapper whose first ``fail_first`` ``save`` calls raise —
    the deterministic flaky-filesystem for exercising ``retry_io``."""

    def __init__(self, inner: CheckpointManager, fail_first: int = 0,
                 exc: type = OSError):
        self._inner = inner
        self._fail_left = fail_first
        self._exc = exc
        self.failures_injected = 0

    def save(self, *args, **kwargs):
        if self._fail_left > 0:
            self._fail_left -= 1
            self.failures_injected += 1
            raise self._exc("injected checkpoint I/O failure")
        return self._inner.save(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._inner, name)


# ---------------------------------------------------------------------------
# Poison inputs: every malformed stream the submit boundary must reject
# ---------------------------------------------------------------------------

POISON_KINDS = ("nan", "inf", "float", "wrong_width", "wrong_ndim", "empty",
                "overflow")


def poison_stream(kind: str, n_in: int, fmt, *, rid: int = 666, t: int = 4):
    """A ``SensorStream`` malformed in exactly one way (see POISON_KINDS)."""
    from repro.serving.lstm_engine import SensorStream

    if kind == "nan":
        qxs = np.full((t, n_in), np.nan, np.float32)
    elif kind == "inf":
        qxs = np.full((t, n_in), np.inf, np.float32)
    elif kind == "float":
        qxs = np.ones((t, n_in), np.float32)
    elif kind == "wrong_width":
        qxs = np.zeros((t, n_in + 1), np.int32)
    elif kind == "wrong_ndim":
        qxs = np.zeros((t,), np.int32)
    elif kind == "empty":
        qxs = np.zeros((0, n_in), np.int32)
    elif kind == "overflow":
        qxs = np.full((t, n_in), fmt.qmax + 1, np.int64)
    else:
        raise ValueError(f"unknown poison kind {kind!r} (want {POISON_KINDS})")
    return SensorStream(rid=rid, qxs=qxs)


def poison_mid_flight(stream, n_in: int) -> None:
    """Corrupt an ADMITTED stream (a buggy caller swapping its ``qxs`` for
    a wrong-shape array under the engine).  The engine reads only its own
    staging after the claim, so the stream must still complete with the
    integers of its claim-time input, and no other lane's may change."""
    stream.qxs = np.zeros((max(1, stream.cursor), n_in + 3), np.int32)


# ---------------------------------------------------------------------------
# The checkpointed serving loop the batteries drive
# ---------------------------------------------------------------------------


def serve_with_checkpoints(engine, pending: list, manager, *, every: int = 1,
                           plan: FaultPlan | None = None, mode: str = "sync",
                           attempts: int = 3, base_delay: float = 0.05,
                           sleep=time.sleep) -> int:
    """Drive ``pending`` streams to completion, checkpointing every ``every``
    steps, with ``plan``'s faults injected at their exact step counts.

    ``pending`` is drained IN PLACE as streams are admitted, so after an
    ``InjectedKill`` the caller still holds exactly the never-admitted
    streams (admitted ones live in the engine — i.e. in its checkpoints —
    and are reconstructed by ``SensorFleetEngine.restore``).  Malformed
    pending streams are rejected into ``engine.quarantined`` (admission
    control), never crashing the loop.  Returns the number of engine steps
    this call ran.
    """
    plan = plan or FaultPlan()
    steps_done = 0
    while pending or engine.active:
        engine.admit(pending)
        engine.step()
        steps_done += 1
        if every and steps_done % every == 0:
            if plan.torn_write_at == steps_done:
                torn_save(manager, engine.steps_run, *engine.checkpoint_payload())
                raise InjectedKill(f"killed mid-save at step {steps_done}")
            engine.save(manager, mode=mode, attempts=attempts,
                        base_delay=base_delay, sleep=sleep)
        if plan.kill_after_steps is not None \
                and steps_done >= plan.kill_after_steps:
            raise InjectedKill(f"killed after step {steps_done}")
    return steps_done


def serve_through_ingest(queue, arrivals: list, manager=None, *,
                         every: int = 0, plan: IngestFaultPlan | None = None,
                         burst_streams: list | None = None,
                         mode: str = "sync") -> dict:
    """Drive scheduled ``arrivals`` through an ``IngestQueue`` with the
    ingest-layer faults injected at their exact loop steps.

    ``arrivals`` is a list of ``(at_step, stream)`` pairs in FIFO order
    (drained IN PLACE, like ``serve_with_checkpoints``'s pending list, so
    after an ``InjectedKill`` the caller holds exactly the never-submitted
    tail); every loop iteration submits the arrivals due at that step, then
    — unless the slow-consumer stall window is active — runs one
    ``queue.step()`` and the optional checkpoint cadence (``manager`` +
    ``every``, through ``queue.save`` so enqueued streams ride along).
    ``QueueFullError`` and validation rejections are counted, never raised:
    backpressure is the behaviour under test, not a loop failure.  Returns
    the counts ``{"steps", "enqueued", "queue_full", "rejected",
    "stalled_steps"}``.
    """
    from repro.serving.ingest import QueueFullError

    plan = plan or IngestFaultPlan()
    burst = list(burst_streams or [])
    stats = {"steps": 0, "enqueued": 0, "queue_full": 0, "rejected": 0,
             "stalled_steps": 0}

    def _submit(s):
        try:
            queue.submit(s)
            stats["enqueued"] += 1
        except QueueFullError:
            stats["queue_full"] += 1
        except (TypeError, ValueError):
            stats["rejected"] += 1

    loop_i = 0
    while arrivals or queue.depth or queue.engine.active:
        loop_i += 1
        if plan.overflow_at == loop_i:
            for s in burst[:plan.overflow_burst]:
                _submit(s)
        while arrivals and arrivals[0][0] <= loop_i:
            _submit(arrivals.pop(0)[1])
        if plan.stall_from is not None \
                and plan.stall_from <= loop_i \
                < plan.stall_from + plan.stall_steps:
            stats["stalled_steps"] += 1   # consumer frozen: queue backs up
            continue
        queue.step()
        stats["steps"] += 1
        if manager is not None and every and stats["steps"] % every == 0:
            if plan.torn_write_at == stats["steps"]:
                torn_save(manager, queue.engine.steps_run,
                          *queue.checkpoint_payload())
                raise InjectedKill(
                    f"killed mid-save at ingest step {stats['steps']}")
            queue.save(manager, mode=mode)
        if plan.kill_after_steps is not None \
                and stats["steps"] >= plan.kill_after_steps:
            raise InjectedKill(f"killed after ingest step {stats['steps']}")
    return stats
