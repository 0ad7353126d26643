"""The serving loop: one thread feeds requests to the system under test and
records when each is due, claims a slot and completes.

Per turn: the generator submits what is due (a closed loop tops the ingest
queue up to its capacity; an open loop submits every request whose due
time has passed), ``IngestQueue.pump`` admits the queue head into free
slots, ``SensorFleetEngine.step`` advances every occupied slot, and the
streams that finished are harvested.  Each part runs inside a
``jax.profiler.TraceAnnotation`` named ``bench.<part>`` when spans are on,
so a profiler trace can attribute device idle time to it.

A request completes when the engine has served every timestep of its window
and copied its final state to the host: ``SensorStream.done``.
"""

from __future__ import annotations

import collections
import contextlib
import time

import numpy as np

NO_SPAN = contextlib.nullcontext()


class Loop:
    def __init__(self, queue, engine, requests, stream_cls, open_loop: bool):
        self.q = queue
        self.eng = engine
        self.reqs = requests
        self.stream_cls = stream_cls
        self.open = open_loop
        self.spans = False
        self.t0 = time.perf_counter()      # open loop: due times count from here
        self.next = 0                      # requests submitted so far
        self.pending = collections.deque()  # submitted, not yet given a slot
        self.due = {}                      # rid -> due time (perf_counter)
        self.claim = {}                    # rid -> slot-claim time
        self.done = {}                     # rid -> completion time
        self.streams = {}                  # rid -> finished SensorStream
        self.failed = set()                # rids rejected or quarantined
        self.late = {}                     # rid -> submit time - due time
        self.steps = []                    # (end time, occupied slots, t_step)

    def span(self, name: str):
        if not self.spans:
            return NO_SPAN
        from jax.profiler import TraceAnnotation

        return TraceAnnotation("bench." + name)

    def _submit(self, rid: int, due: float, now: float) -> None:
        k = rid % len(self.reqs)
        s = self.stream_cls(rid=rid, qxs=self.reqs.qxs(k))
        self.due[rid] = due
        self.late[rid] = now - due
        try:
            self.q.submit(s)
        except (TypeError, ValueError, RuntimeError) as e:
            s.error = f"{type(e).__name__}: {e}"
            self.failed.add(rid)
            return
        self.pending.append(s)

    def feed(self) -> None:
        now = time.perf_counter()
        if self.open:
            due = self.reqs.due_s
            while self.next < len(self.reqs) and self.t0 + due[self.next] <= now:
                self._submit(self.next, self.t0 + due[self.next], now)
                self.next += 1
        else:
            while self.q.depth < self.q.capacity:
                self._submit(self.next, now, now)
                self.next += 1

    def turn(self) -> bool:
        """One turn; False when there was nothing to step."""
        with self.span("gen"):
            self.feed()
        with self.span("pump"):
            self.q.pump()
        now = time.perf_counter()
        while self.pending and (self.pending[0].h_seq is not None
                                or self.pending[0].error is not None):
            s = self.pending.popleft()
            if s.error is not None:
                self.failed.add(s.rid)
            else:
                self.claim[s.rid] = now
        if not self.eng.active:
            return False
        before = dict(self.eng.active)
        n0 = self.eng.timesteps_run
        with self.span("step"):
            self.eng.step()
        now = time.perf_counter()
        self.steps.append((now, len(before), self.eng.timesteps_run - n0))
        for s in before.values():
            if s.done:
                self.done[s.rid] = now
                self.streams[s.rid] = s
            elif s.error is not None:
                self.failed.add(s.rid)
        return True

    def idle(self) -> None:
        """Open loop with nothing to do: sleep until the next request is due."""
        if self.next < len(self.reqs):
            wait = self.t0 + self.reqs.due_s[self.next] - time.perf_counter()
            if wait > 0:
                with self.span("sleep"):
                    time.sleep(wait)

    def progress(self) -> float:
        """Forecasts' worth of timesteps served to the requests in flight."""
        return sum(s.cursor / len(s.qxs) for s in self.eng.active.values())

    def run_closed(self, seconds: float, grace_s: float):
        """Serve a closed backlog for ``seconds``, then on to the first step
        boundary with no request partly served (at most ``grace_s`` more).
        Returns ``(t_start, t_end, forecasts, rids completed in it)``."""
        t_start = time.perf_counter()
        p0 = self.progress()
        first = len(self.done)
        while True:
            self.turn()
            now = time.perf_counter()
            if now - t_start >= seconds and (self.progress() == 0
                                             or now - t_start >= seconds + grace_s):
                break
        rids = list(self.done)[first:]
        return t_start, now, len(rids) + self.progress() - p0, rids

    def run_open(self, until_rid_done, deadline: float) -> None:
        """Serve the open loop until ``until_rid_done()`` or ``deadline``."""
        while not until_rid_done() and time.perf_counter() < deadline:
            if not self.turn() and not self.q.depth:
                self.idle()


def percentile(samples, q: float) -> float:
    """Nearest-rank percentile (``benchmarks/common.sample_stats``'s rule)."""
    ss = np.sort(np.asarray(samples, np.float64))
    if q == 50:
        n = len(ss)
        return float(ss[n // 2] if n % 2 else (ss[n // 2 - 1] + ss[n // 2]) / 2)
    return float(ss[min(len(ss) - 1, int(q / 100 * len(ss)))])
