"""Non-blocking ingest: a bounded admission queue in front of the fleet.

The paper's throughput number (17 534 inf/s on the XC7S15) is a *device*
rate; at fleet scale the host-side ``submit`` path becomes the bottleneck
long before the ``pallas_fxp`` kernel does.  ``SensorFleetEngine.submit``
is already cheap, but the caller-facing contract it offers — "False when
full, try again later" — forces every producer to poll the engine, and a
bulk ``admit`` loop interleaves admission with device steps, so a burst of
arrivals can stall behind a kernel dispatch.  ``IngestQueue`` is the
missing admission layer (ROADMAP open item 1, single-host half): ``submit``
becomes an O(validation) enqueue that NEVER waits on a device step, and
admission happens on the serving side, draining the queue head into free
slots inside ``step()`` (or an explicit ``pump()``).

Backpressure is explicit, per queue, chosen at construction:

* ``policy="reject"`` — a full queue raises the typed ``QueueFullError``
  (producer-visible backpressure; the stream is never enqueued).
* ``policy="drop-oldest"`` — the oldest *queued* (never-admitted) stream is
  evicted to make room: bounded memory and bounded staleness under
  overload, at the cost of losing the head of the backlog.  Evicted
  streams land in ``queue.dropped`` with ``error`` set.
* ``policy="block-with-deadline"`` — the ONLY policy that waits: the
  submitting thread drives ``pump()`` + ``engine.step()`` until queue
  space frees or ``deadline_s`` expires (then ``QueueFullError``).  This
  trades submit latency for zero loss — the single-producer fallback when
  neither rejecting nor dropping is acceptable.

Determinism: admission is FIFO in arrival order, and a drain admits
exactly as many streams as there are free slots, in order — the same
schedule ``SensorFleetEngine.run``'s ``admit(pending); step()`` loop
produces.  Serving THROUGH the queue is therefore bit-identical to the
direct submit loop (asserted per stream and against the golden fixture in
``tests/test_ingest.py``, sharded in
``tests/spmd_scripts/check_sharded_fleet.py``).  The wall-clock reads
below feed metrics only — nothing schedule-visible depends on them.

Checkpointing: in-queue streams ride the engine checkpoint —
``checkpoint_payload`` extends the engine's payload with a ``tree["ingest"]``
subtree (one ``qxs``/``qh0``/``qc0`` leaf group per queue position) and an
``extra["ingest"]`` side-car (capacity/policy/queue order), and ``save``
reuses the engine's retry/async machinery via ``payload=``.
``IngestQueue.restore`` rebuilds engine + queue from the same step, so a
kill with streams still enqueued loses nothing (battery:
``tests/spmd_scripts/check_fleet_restore.py``).

Observability (all no-op while ``repro.obs`` is disabled):

* ``fleet/ingest_submit_us`` — enqueue latency histogram (the p50/p95/p99
  the churn benchmark reports; bounded because enqueue never dispatches).
* ``fleet/ingest_wait_us`` — admission latency: enqueue → slot claim.
* ``fleet/ingest_queue_depth`` gauge + ``fleet/ingest_queue_depth_hist``
  histogram (power-of-two depth edges up to capacity).
* counters: ``fleet/ingest_enqueued_total``, ``fleet/ingest_admitted_total``,
  ``fleet/ingest_rejected_total`` (+ ``fleet/ingest_rejected/<Exc>``),
  ``fleet/ingest_dropped_total``, ``fleet/ingest_queue_full_total``,
  ``fleet/ingest_deadline_expired_total``, ``fleet/ingest_admit_rejected_total``.
* tracer spans: ``fleet/enqueue`` (child ``fleet/validate``, both with the
  stream's ``rid``) around each ``submit``, ``fleet/ingest`` around each
  drain: the engine's drain check ``fleet/validate`` and ``fleet/claim``
  (arg ``streams`` each) nest inside it, then one ``fleet/admit_write``
  (arg ``streams``) that writes every admitted stream's initial state at
  once.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serving.lstm_engine import SensorFleetEngine, SensorStream

__all__ = ["IngestQueue", "QueueFullError", "POLICIES"]

POLICIES = ("reject", "drop-oldest", "block-with-deadline")

# depth-histogram edges: powers of two, like the engine's t_step buckets
_DEPTH_EDGES = [float(2 ** k) for k in range(17)]   # 1 .. 65536


class QueueFullError(RuntimeError):
    """Typed backpressure signal: the ingest queue is at capacity and the
    policy does not make room (``reject`` always; ``block-with-deadline``
    once the deadline expires).  Carries enough context to route the retry:
    ``rid`` (the stream that could not be enqueued), ``capacity`` and
    ``depth`` at the time of the failure."""

    def __init__(self, msg: str, *, rid=None, capacity: int | None = None,
                 depth: int | None = None):
        super().__init__(msg)
        self.rid = rid
        self.capacity = capacity
        self.depth = depth


class IngestQueue:
    """Bounded FIFO admission queue in front of a ``SensorFleetEngine``.

    ``submit`` validates (via ``engine.validate_stream``) and enqueues —
    O(validation), no device work; ``pump`` drains the queue head into free
    slots; ``step`` = ``pump`` + ``engine.step``.  See the module docstring
    for policies, determinism and checkpoint semantics.
    """

    def __init__(self, engine: SensorFleetEngine, *, capacity: int = 256,
                 policy: str = "reject", deadline_s: float = 1.0,
                 clock=time.monotonic):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if policy not in POLICIES:
            raise ValueError(f"policy must be one of {POLICIES}, got {policy!r}")
        if policy == "block-with-deadline" and deadline_s <= 0:
            raise ValueError("block-with-deadline needs deadline_s > 0")
        self.engine = engine
        self.capacity = int(capacity)
        self.policy = policy
        self.deadline_s = float(deadline_s)
        self._clock = clock
        # (stream, enqueue time) — the time feeds fleet/ingest_wait_us only
        self._queue: collections.deque = collections.deque()
        self.dropped: list[SensorStream] = []   # drop-oldest evictions

    # --- observability ------------------------------------------------------

    @property
    def obs(self):
        """The engine's registry — ingest and engine metrics land together
        (one snapshot, one checkpoint ride-along)."""
        return self.engine.obs

    @property
    def depth(self) -> int:
        return len(self._queue)

    @property
    def queued(self) -> tuple:
        """The enqueued streams in FIFO (admission) order — a read-only
        snapshot for callers reconciling ownership after a restore."""
        return tuple(s for s, _ in self._queue)

    def _gauge_depth(self) -> None:
        self.obs.gauge("fleet/ingest_queue_depth", len(self._queue))

    # --- producer side ------------------------------------------------------

    def submit(self, stream: SensorStream) -> bool:
        """Enqueue ``stream`` for admission; returns True once enqueued.

        O(validation): malformed streams raise TypeError/ValueError here
        (counted under ``fleet/ingest_rejected/*`` — they never reach the
        engine), well-formed ones are appended FIFO.  Never dispatches a
        kernel — except under ``policy="block-with-deadline"`` when the
        queue is full, which is that policy's documented trade.
        """
        m = self.obs
        tr = obs_trace.get_tracer()
        m.inc("fleet/ingest_submit_total")
        with m.time("fleet/ingest_submit_us"), \
                tr.span("fleet/enqueue", rid=stream.rid):
            try:
                with tr.span("fleet/validate", rid=stream.rid):
                    qxs, _, _ = self.engine.validate_stream(stream)
            except (TypeError, ValueError) as e:
                m.inc("fleet/ingest_rejected_total")
                m.inc(f"fleet/ingest_rejected/{type(e).__name__}")
                raise
            # normalise now (like the engine does at slot claim) so the
            # checkpointed queue is int32-exact and the pump re-check is cheap
            stream.qxs = qxs
            if len(self._queue) >= self.capacity:
                self._make_room(stream)
            self._queue.append((stream, self._clock()))
        m.inc("fleet/ingest_enqueued_total")
        self._gauge_depth()
        m.observe("fleet/ingest_queue_depth_hist", len(self._queue),
                  edges=_DEPTH_EDGES)
        return True

    def _make_room(self, stream: SensorStream) -> None:
        """Apply the backpressure policy to a full queue (or raise)."""
        m = self.obs
        if self.policy == "reject":
            m.inc("fleet/ingest_queue_full_total")
            raise QueueFullError(
                f"ingest queue full ({self.capacity}) — stream {stream.rid} "
                "rejected (policy=reject)",
                rid=stream.rid, capacity=self.capacity, depth=len(self._queue))
        if self.policy == "drop-oldest":
            old, _ = self._queue.popleft()
            old.error = "dropped: ingest queue full (policy=drop-oldest)"
            self.dropped.append(old)
            m.inc("fleet/ingest_dropped_total")
            return
        # block-with-deadline: drive the serving side until space frees
        deadline = self._clock() + self.deadline_s
        while len(self._queue) >= self.capacity:
            self.pump()
            if len(self._queue) < self.capacity:
                return
            if self._clock() >= deadline:
                m.inc("fleet/ingest_deadline_expired_total")
                m.inc("fleet/ingest_queue_full_total")
                raise QueueFullError(
                    f"ingest queue still full ({self.capacity}) after "
                    f"{self.deadline_s}s — stream {stream.rid} rejected "
                    "(policy=block-with-deadline)",
                    rid=stream.rid, capacity=self.capacity,
                    depth=len(self._queue))
            self.engine.step()

    # --- serving side -------------------------------------------------------

    def pump(self) -> int:
        """Drain the queue head into free slots, FIFO, as one batch
        (``engine.submit_many``: one state write for every stream admitted);
        returns the number of streams admitted.  Stops at the first
        ``engine full``.

        The streams were validated at enqueue; the engine checks the drain
        again in one pass (O(1) attribute checks per stream, one range check
        over all inputs) and runs the full per-stream ``validate_stream``
        only where that check fails.  So a stream corrupted AFTER enqueue is
        still rejected by the engine's own submit boundary into
        ``engine.quarantined`` (counted there as ``fleet/submit_rejected/*``,
        plus ``fleet/ingest_admit_rejected_total`` here), with the error it
        would get alone — it cannot block the streams behind it.  The
        drained head leaves the deque at once, the counters move by the
        drain's counts, and ``fleet/ingest_wait_us`` takes one observation
        per admitted stream in one call.
        """
        if not self._queue:
            return 0
        m = self.obs
        tr = obs_trace.get_tracer()
        with tr.span("fleet/ingest", depth=len(self._queue)):
            outcomes = self.engine.submit_many(s for s, _ in self._queue)
            now = self._clock()
            popleft = self._queue.popleft
            taken = [popleft() for _ in outcomes]
            admitted = outcomes.count(None)
            if admitted < len(taken):
                for (s, _), err in zip(taken, outcomes):
                    if err is not None:
                        s.error = f"{type(err).__name__}: {err}"
                        self.engine.quarantined.append(s)
                        m.inc("fleet/ingest_admit_rejected_total")
            if admitted:
                m.inc("fleet/ingest_admitted_total", admitted)
            if admitted and m.enabled:
                m.observe_many("fleet/ingest_wait_us", [
                    (now - t_enq) * 1e6
                    for (_, t_enq), err in zip(taken, outcomes) if err is None])
        self._gauge_depth()
        return admitted

    def step(self) -> None:
        """One serving step: admit what fits, then advance the fleet."""
        self.pump()
        self.engine.step()

    def run(self, streams: list[SensorStream]) -> list[SensorStream]:
        """Drive ``streams`` to completion through the queue.

        Under ``policy="reject"`` a full queue is drained by stepping the
        engine until space frees (the caller-side retry loop, made
        deterministic); the admission schedule is identical to
        ``SensorFleetEngine.run`` on the same list, so the results are
        bit-identical to the direct submit loop.
        """
        for s in streams:
            while True:
                try:
                    self.submit(s)
                    break
                except QueueFullError:
                    self.step()
        while self._queue or self.engine.active:
            self.step()
        return streams

    # --- checkpoint/restore -------------------------------------------------

    def checkpoint_payload(self) -> tuple[dict, dict]:
        """The engine's ``(tree, extra)`` extended with the in-queue streams:
        ``tree["ingest"]["<pos>"]`` holds each queued stream's arrays (FIFO
        position keyed) and ``extra["ingest"]`` the queue config + order, so
        enqueued-but-never-admitted streams survive kill → restore."""
        tree, extra = self.engine.checkpoint_payload()
        qtree: dict[str, dict] = {}
        order = []
        for i, (s, _) in enumerate(self._queue):
            leaf = {"qxs": np.asarray(s.qxs, np.int32)}
            if s.qh0 is not None:
                leaf["qh0"] = np.asarray(s.qh0, np.int32)
            if s.qc0 is not None:
                leaf["qc0"] = np.asarray(s.qc0, np.int32)
            qtree[str(i)] = leaf
            order.append({"rid": s.rid})
        if qtree:
            tree["ingest"] = qtree
        extra["ingest"] = {
            "capacity": self.capacity,
            "policy": self.policy,
            "deadline_s": self.deadline_s,
            "queue": order,
        }
        return tree, extra

    def save(self, manager, step: int | None = None, *, mode: str = "sync",
             attempts: int = 3, base_delay: float = 0.05,
             sleep=time.sleep) -> int:
        """Checkpoint engine + queue in one atomic step (same manifest):
        delegates to ``engine.save`` with the extended payload, so async
        mode, bounded retry and the save metrics all apply unchanged."""
        return self.engine.save(manager, step, mode=mode, attempts=attempts,
                                base_delay=base_delay, sleep=sleep,
                                payload=self.checkpoint_payload())

    @classmethod
    def restore(cls, manager, qparams, fmt, luts: dict | None = None,
                *, step: int | None = None, capacity: int | None = None,
                policy: str | None = None, deadline_s: float | None = None,
                clock=time.monotonic, **engine_kw) -> "IngestQueue":
        """Rebuild engine AND queue from a checkpoint written by ``save``.

        The engine restores exactly as ``SensorFleetEngine.restore`` (same
        ``engine_kw``: mesh, backend, metrics, ...), then the queued
        streams are reloaded in their checkpointed FIFO order.  Queue
        config defaults to the checkpointed values; pass ``capacity=`` /
        ``policy=`` / ``deadline_s=`` to override (e.g. a restored fleet
        under lighter load can shrink the queue).  Checkpoints written by
        ``engine.save`` directly restore to an empty queue.
        """
        eng = SensorFleetEngine.restore(manager, qparams, fmt, luts,
                                        step=step, **engine_kw)
        step = manager.latest_step() if step is None else step
        manifest = manager.manifest(step)
        icfg = manifest["extra"].get("ingest", {})
        q = cls(eng,
                capacity=capacity if capacity is not None
                else icfg.get("capacity", 256),
                policy=policy if policy is not None
                else icfg.get("policy", "reject"),
                deadline_s=deadline_s if deadline_s is not None
                else icfg.get("deadline_s", 1.0),
                clock=clock)
        order = icfg.get("queue", [])
        if order:
            template: dict = {"ingest": {}}
            for name, info in manifest["leaves"].items():
                parts = name.split("/")
                if parts[0] != "ingest":
                    continue
                d = template["ingest"]
                for p in parts[1:-1]:
                    d = d.setdefault(p, {})
                d[parts[-1]] = np.zeros(info["shape"], info["dtype"])
            tree, _, _ = manager.restore(template, step=step)
            t0 = q._clock()
            for i, meta in enumerate(order):
                leaf = tree["ingest"][str(i)]
                # np.array (not asarray): npz-restored buffers are read-only
                s = SensorStream(rid=int(meta["rid"]),
                                 qxs=np.array(leaf["qxs"], np.int32))
                if "qh0" in leaf:
                    s.qh0 = np.array(leaf["qh0"], np.int32)
                if "qc0" in leaf:
                    s.qc0 = np.array(leaf["qc0"], np.int32)
                q._queue.append((s, t0))
            q._gauge_depth()
        return q
