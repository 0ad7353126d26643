"""Ingest layer (ISSUE 10): non-blocking admission in front of the fleet.

Contract families:

* **non-blocking submit** — enqueue is O(validation): no engine step, no
  slot claim, malformed streams reject at the boundary;
* **determinism** — queue-drained serving is bit-identical to the direct
  ``submit``-loop serving (per-stream, run-twice, and against the golden
  fleet fixture — the sharded variant rides
  ``spmd_scripts/check_sharded_fleet.py``);
* **backpressure** — each policy's exact behaviour at capacity (typed
  ``QueueFullError``, deterministic drop-oldest eviction, bounded
  block-with-deadline);
* **checkpoint** — in-queue streams ride the engine checkpoint and
  survive kill → restore (the resharding battery variant rides
  ``spmd_scripts/check_fleet_restore.py``);
* **faults** — queue-overflow bursts and slow-consumer stalls degrade by
  policy, never corrupt the admitted streams' integers.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpoint import CheckpointManager
from repro.core.fxp import FxpFormat, quantize
from repro.core.lstm import LSTMParams, init_lstm_params
from repro.core.lut import make_lut_pair
from repro.obs.metrics import MetricsRegistry
from repro.serving.faults import (POISON_KINDS, IngestFaultPlan, InjectedKill,
                                  poison_stream, serve_through_ingest)
from repro.serving.ingest import POLICIES, IngestQueue, QueueFullError
from repro.serving.lstm_engine import SensorFleetEngine, SensorStream

pytestmark = pytest.mark.ingest

FMT = FxpFormat(8, 16)
N_IN, N_H = 2, 12
LENS = [13, 5, 21, 8, 17, 3, 11, 9]


@pytest.fixture(scope="module")
def setup():
    p = init_lstm_params(jax.random.PRNGKey(0), N_IN, N_H)
    qp = LSTMParams(w=quantize(p.w, FMT), b=quantize(p.b, FMT))
    return qp, make_lut_pair(64)


def _streams(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [SensorStream(rid=i, qxs=np.asarray(quantize(
                jnp.asarray(rng.normal(size=(T, N_IN)).astype(np.float32)),
                FMT)))
            for i, T in enumerate(lens)]


def _engine(setup, **kw):
    qp, luts = setup
    kw.setdefault("batch_slots", 4)
    kw.setdefault("chunk", 4)
    kw.setdefault("backend", "fxp")
    return SensorFleetEngine(qp, FMT, luts, **kw)


def _assert_streams_equal(ref, got):
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        assert a.rid == b.rid and a.done and b.done
        np.testing.assert_array_equal(a.h_seq, b.h_seq,
                                      err_msg=f"stream {a.rid} h_seq")
        np.testing.assert_array_equal(a.qh, b.qh)
        np.testing.assert_array_equal(a.qc, b.qc)


# -- non-blocking submit ------------------------------------------------------


def test_submit_is_enqueue_only(setup):
    """submit never touches the engine: no step, no slot claim, no device
    dispatch — admission happens in pump()/step()."""
    eng = _engine(setup)
    steps = []
    orig_step = eng.step
    eng.step = lambda: steps.append(1) or orig_step()
    q = IngestQueue(eng, capacity=16)
    for s in _streams(LENS):
        assert q.submit(s) is True
    assert q.depth == len(LENS)
    assert eng.active == {} and eng.steps_run == 0 and steps == []
    assert q.pump() == 4                     # batch_slots free slots, FIFO
    assert sorted(s.rid for s in eng.active.values()) == [0, 1, 2, 3]
    assert q.depth == len(LENS) - 4


@pytest.mark.parametrize("kind", POISON_KINDS)
def test_malformed_streams_reject_at_enqueue(setup, kind):
    eng = _engine(setup, metrics=(reg := MetricsRegistry()))
    q = IngestQueue(eng, capacity=4)
    with pytest.raises((TypeError, ValueError)):
        q.submit(poison_stream(kind, N_IN, FMT))
    assert q.depth == 0                      # never enqueued
    snap = reg.snapshot()["counters"]
    assert snap["fleet/ingest_rejected_total"] == 1
    # boundary rejections never touch the engine's counters
    assert snap.get("fleet/submit_total", 0) == 0
    assert snap.get("fleet/quarantined_total", 0) == 0


# -- determinism: FIFO drain == direct submit loop ----------------------------


def test_queue_drained_bit_identical_to_direct_and_repeatable(setup):
    ref = _engine(setup).run(_streams(LENS))
    runs = []
    for _ in range(2):                       # run twice -> byte-identical
        q = IngestQueue(_engine(setup), capacity=3, policy="reject")
        runs.append(q.run(_streams(LENS)))
    _assert_streams_equal(ref, runs[0])
    _assert_streams_equal(runs[0], runs[1])


def test_explicit_pump_step_loop_matches_engine_run(setup):
    """The pump-inside-step path (no run() helper): same integers."""
    ref = _engine(setup).run(_streams(LENS))
    q = IngestQueue(_engine(setup), capacity=len(LENS))
    got = _streams(LENS)
    for s in got:
        q.submit(s)
    while q.depth or q.engine.active:
        q.step()
    _assert_streams_equal(ref, got)


@pytest.mark.parametrize("check", ["drain", "per_stream"])
def test_golden_replay_through_ingest_queue(check):
    """Acceptance: the committed golden fleet schedule replayed THROUGH the
    ingest queue reproduces every stream's integers exactly, with the
    engine's one-pass drain check and with ``validate_stream`` on every
    stream of every drain."""
    from test_golden import FLEET_PATH, _load, _stored_luts
    from test_serving import per_stream_checks

    g = _load(FLEET_PATH)
    qps = [LSTMParams(w=jnp.asarray(w, jnp.int32), b=jnp.asarray(b, jnp.int32))
           for w, b in zip(g["qw"], g["qb"])]
    streams = [SensorStream(
        rid=s["rid"], qxs=np.asarray(s["qxs"], np.int32),
        qh0=None if s["qh0"] is None else np.asarray(s["qh0"], np.int32),
        qc0=None if s["qc0"] is None else np.asarray(s["qc0"], np.int32),
    ) for s in g["streams"]]
    eng = SensorFleetEngine(qps, g["_fmt"], _stored_luts(g),
                            batch_slots=g["engine"]["batch_slots"],
                            chunk=g["engine"]["chunk"], backend="fxp")
    if check == "per_stream":
        per_stream_checks(eng)
    IngestQueue(eng, capacity=4, policy="reject").run(streams)
    assert all(s.done for s in streams)
    for s, out in zip(streams, g["outputs"]):
        np.testing.assert_array_equal(s.h_seq, np.asarray(out["h_seq"]),
                                      err_msg=f"golden stream {s.rid} h_seq")
        np.testing.assert_array_equal(s.qh, np.asarray(out["qh"]))
        np.testing.assert_array_equal(s.qc, np.asarray(out["qc"]))


# -- backpressure policies at capacity ----------------------------------------


def test_invalid_queue_config(setup):
    eng = _engine(setup)
    with pytest.raises(ValueError):
        IngestQueue(eng, capacity=0)
    with pytest.raises(ValueError):
        IngestQueue(eng, policy="spill-to-disk")
    with pytest.raises(ValueError):
        IngestQueue(eng, policy="block-with-deadline", deadline_s=0)
    assert set(POLICIES) == {"reject", "drop-oldest", "block-with-deadline"}


def test_reject_policy_raises_typed_error(setup):
    eng = _engine(setup, metrics=(reg := MetricsRegistry()))
    q = IngestQueue(eng, capacity=2, policy="reject")
    ss = _streams([6, 6, 6])
    q.submit(ss[0]), q.submit(ss[1])
    with pytest.raises(QueueFullError) as ei:
        q.submit(ss[2])
    assert isinstance(ei.value, RuntimeError)
    assert (ei.value.rid, ei.value.capacity, ei.value.depth) == (2, 2, 2)
    assert q.depth == 2                      # the full queue is untouched
    snap = reg.snapshot()["counters"]
    assert snap["fleet/ingest_queue_full_total"] == 1
    assert snap["fleet/ingest_enqueued_total"] == 2


def test_drop_oldest_policy_evicts_head_deterministically(setup):
    eng = _engine(setup, batch_slots=2, metrics=(reg := MetricsRegistry()))
    q = IngestQueue(eng, capacity=2, policy="drop-oldest")
    ss = _streams([6, 6, 6, 6])
    for s in ss:
        q.submit(s)
    assert [s.rid for s in q.dropped] == [0, 1]          # oldest first
    assert all("drop-oldest" in s.error for s in q.dropped)
    assert q.depth == 2
    assert reg.snapshot()["counters"]["fleet/ingest_dropped_total"] == 2
    # the survivors still serve bit-identically to a direct run
    while q.depth or eng.active:
        q.step()
    ref = _engine(setup, batch_slots=2).run(_streams([6, 6, 6, 6])[2:])
    for a, b in zip(ref, ss[2:]):
        np.testing.assert_array_equal(a.h_seq, b.h_seq)


def test_block_with_deadline_blocks_until_space(setup):
    eng = _engine(setup, batch_slots=2)
    q = IngestQueue(eng, capacity=2, policy="block-with-deadline",
                    deadline_s=30.0)
    ss = _streams([6, 6, 6, 6, 6])
    for s in ss:                             # blocks, drives steps, succeeds
        q.submit(s)
    assert q.depth <= 2 and not q.dropped
    q.run([])                                # drain the tail
    assert all(s.done for s in ss)


def test_block_with_deadline_expires_on_stalled_engine(setup):
    """A consumer that never frees space must surface QueueFullError at the
    deadline (fake clock: no real sleeping)."""
    now = [0.0]
    eng = _engine(setup, batch_slots=1, metrics=(reg := MetricsRegistry()))
    eng.step = lambda: now.__setitem__(0, now[0] + 0.25)   # stalled device
    q = IngestQueue(eng, capacity=1, policy="block-with-deadline",
                    deadline_s=1.0, clock=lambda: now[0])
    long_stream, blocked = _streams([40, 6])
    q.submit(long_stream)
    q.pump()                                 # slot claimed
    q.submit(SensorStream(rid=77, qxs=long_stream.qxs.copy()))  # queue full
    with pytest.raises(QueueFullError):
        q.submit(blocked)
    snap = reg.snapshot()["counters"]
    assert snap["fleet/ingest_deadline_expired_total"] == 1


# -- checkpoint: in-queue streams survive kill -> restore ---------------------


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_kill_restore_with_streams_still_enqueued(setup, tmp_path, mode):
    qp, luts = setup
    ref = _engine(setup).run(_streams(LENS, seed=3))

    eng = _engine(setup, metrics=MetricsRegistry())
    q = IngestQueue(eng, capacity=len(LENS), policy="reject")
    ss = _streams(LENS, seed=3)
    for s in ss:
        q.submit(s)
    q.step()                                 # 4 admitted + stepped; 4 queued
    assert q.depth > 0
    mgr = CheckpointManager(tmp_path / "ck")
    q.save(mgr, mode=mode)
    mgr.wait()
    depth_at_save = q.depth
    del eng, q                               # the "killed" process

    q2 = IngestQueue.restore(mgr, qp, FMT, luts, backend="fxp",
                             metrics=MetricsRegistry())
    assert q2.depth == depth_at_save
    assert q2.capacity == len(LENS) and q2.policy == "reject"
    got = {s.rid: s for s in list(q2.engine.active.values())
           + [s for s, _ in q2._queue]}
    while q2.depth or q2.engine.active:
        q2.step()
    for r in ref:
        s = got[r.rid]
        assert s.done
        np.testing.assert_array_equal(r.h_seq, s.h_seq,
                                      err_msg=f"restored stream {r.rid}")
        np.testing.assert_array_equal(r.qh, s.qh)
        np.testing.assert_array_equal(r.qc, s.qc)


def test_restore_plain_engine_checkpoint_into_queue(setup, tmp_path):
    """Checkpoints written by engine.save (no ingest section) restore to an
    empty queue with default config — forward compatibility both ways."""
    qp, luts = setup
    eng = _engine(setup)
    eng.admit(_streams([9, 7]))
    eng.step()
    mgr = CheckpointManager(tmp_path / "ck")
    eng.save(mgr)
    q = IngestQueue.restore(mgr, qp, FMT, luts, backend="fxp",
                            capacity=7, policy="drop-oldest")
    assert q.depth == 0 and q.capacity == 7 and q.policy == "drop-oldest"
    assert len(q.engine.active) == 2


# -- fault plans: queue overflow + slow consumer ------------------------------


def test_queue_overflow_burst_absorbed_by_policy(setup):
    eng = _engine(setup, metrics=(reg := MetricsRegistry()))
    q = IngestQueue(eng, capacity=4, policy="reject")
    arrivals = [(1, s) for s in _streams(LENS, seed=5)]
    expected = {s.rid: s for _, s in arrivals}
    plan = IngestFaultPlan(overflow_at=2, overflow_burst=6)
    burst = [SensorStream(rid=1000 + i, qxs=np.zeros((5, N_IN), np.int32))
             for i in range(6)]
    stats = serve_through_ingest(q, arrivals, plan=plan, burst_streams=burst)
    assert stats["queue_full"] > 0           # the storm hit backpressure
    assert reg.snapshot()["counters"]["fleet/ingest_queue_full_total"] \
        == stats["queue_full"]
    # every stream that made it through the queue still finished bit-exact
    ref = _engine(setup).run(_streams(LENS, seed=5))
    for r in ref:
        s = expected[r.rid]
        if s.done:
            np.testing.assert_array_equal(r.h_seq, s.h_seq)


def test_slow_consumer_stall_backs_up_then_drains_fifo(setup):
    eng = _engine(setup, metrics=(reg := MetricsRegistry()))
    q = IngestQueue(eng, capacity=len(LENS), policy="reject")
    ss = _streams(LENS, seed=7)
    arrivals = [(i + 1, s) for i, s in enumerate(ss)]
    plan = IngestFaultPlan(stall_from=2, stall_steps=5)
    stats = serve_through_ingest(q, arrivals, plan=plan)
    assert stats["stalled_steps"] == 5 and stats["queue_full"] == 0
    hist = reg.snapshot()["histograms"]["fleet/ingest_queue_depth_hist"]
    assert hist["max"] >= 5                  # the backlog actually grew
    assert all(s.done for s in ss)
    _assert_streams_equal(_engine(setup).run(_streams(LENS, seed=7)), ss)


def test_ingest_kill_plan_preserves_enqueued_streams(setup, tmp_path):
    qp, luts = setup
    eng = _engine(setup, metrics=MetricsRegistry())
    q = IngestQueue(eng, capacity=len(LENS))
    arrivals = [(1, s) for s in _streams(LENS, seed=9)]
    mgr = CheckpointManager(tmp_path / "ck")
    with pytest.raises(InjectedKill):
        serve_through_ingest(q, arrivals, mgr, every=1,
                             plan=IngestFaultPlan(kill_after_steps=1))
    q2 = IngestQueue.restore(mgr, qp, FMT, luts, backend="fxp",
                             metrics=MetricsRegistry())
    assert q2.depth > 0                      # enqueued tail survived the kill
    got = {s.rid: s for s in list(q2.engine.active.values())
           + [s for s, _ in q2._queue]}
    while q2.depth or q2.engine.active:
        q2.step()
    for r in _engine(setup).run(_streams(LENS, seed=9)):
        np.testing.assert_array_equal(r.h_seq, got[r.rid].h_seq,
                                      err_msg=f"stream {r.rid} after kill")


# -- observability ------------------------------------------------------------


def test_ingest_metrics_and_spans(setup):
    from repro import obs

    obs.disable_all()
    try:
        reg = MetricsRegistry()
        obs.enable_tracing()
        eng = _engine(setup, metrics=reg)
        q = IngestQueue(eng, capacity=len(LENS))
        q.run(_streams(LENS))
        snap = reg.snapshot()
        c = snap["counters"]
        assert c["fleet/ingest_submit_total"] == len(LENS)
        assert c["fleet/ingest_enqueued_total"] == len(LENS)
        assert c["fleet/ingest_admitted_total"] == len(LENS)
        assert snap["histograms"]["fleet/ingest_submit_us"]["count"] == len(LENS)
        assert snap["histograms"]["fleet/ingest_wait_us"]["count"] == len(LENS)
        assert snap["histograms"]["fleet/ingest_queue_depth_hist"]["max"] > 0
        assert snap["gauges"]["fleet/ingest_queue_depth"] == 0.0
        names = [e["name"] for e in obs.get_tracer().events()]
        assert "fleet/ingest" in names and "fleet/step" in names
    finally:
        obs.disable_all()


def test_pump_admits_a_batch_in_one_state_write(setup):
    """One pump of k streams writes their initial states once:
    ``fleet/admit_writes_total`` +1 and one ``fleet/admit_batch``
    observation of k, on power-of-two edges up to the slot count; a stream
    corrupted after enqueue is quarantined without taking a slot."""
    reg = MetricsRegistry()
    eng = _engine(setup, batch_slots=8, metrics=reg)
    q = IngestQueue(eng, capacity=8)
    streams = _streams([6, 9, 4, 7, 5, 8])
    for s in streams:
        q.submit(s)
    streams[2].qxs = streams[2].qxs.astype(np.float32)   # corrupted in queue
    assert q.pump() == 5
    snap = reg.snapshot()
    c, batch = snap["counters"], snap["histograms"]["fleet/admit_batch"]
    assert c["fleet/admit_writes_total"] == 1
    assert (batch["count"], batch["sum"]) == (1, 5)
    assert batch["edges"] == [1.0, 2.0, 4.0, 8.0]
    assert c["fleet/ingest_admitted_total"] == 5
    assert c["fleet/ingest_admit_rejected_total"] == 1
    assert c["fleet/submit_rejected/TypeError"] == 1
    assert eng.quarantined == [streams[2]] and q.depth == 0
    assert [s.rid for s in eng.active.values()] == [0, 1, 3, 4, 5]
    assert q.pump() == 0                       # empty queue: no write
    assert reg.snapshot()["counters"]["fleet/admit_writes_total"] == 1
    for s in _streams([3, 3, 3, 3], seed=5):
        q.submit(s)
    assert q.pump() == 3                       # 3 free slots, 1 left queued
    snap = reg.snapshot()
    assert snap["counters"]["fleet/admit_writes_total"] == 2
    assert snap["histograms"]["fleet/admit_batch"]["sum"] == 8
    assert q.depth == 1 and snap["counters"]["fleet/submit_full_total"] == 1


def test_churn_benchmark_smoke():
    """The benchmark path itself (small N): emits a well-formed row with
    p50/p95/p99 submit latency and sustained throughput."""
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    try:
        from benchmarks.churn import run_churn
    finally:
        sys.path.pop(0)
    res = run_churn(24, slots=4, capacity=8, policy="drop-oldest")
    row = res["row"]
    assert row["name"] == "serving/lstm_fleet_churn"
    assert {"us_per_call", "p50_us", "p95_us", "p99_us", "cv", "n",
            "derived"} <= set(row)
    assert row["n"] == 24 and row["p99_us"] >= row["p50_us"] > 0
    assert res["counts"]["completed"] > 0
    assert res["sustained_timesteps_per_s"] > 0


# -- the engine's one-pass drain check behind the queue -----------------------


@pytest.mark.parametrize("kind", ["none", "int64", "float", "nan",
                                  "out_of_range", "shape", "empty", "qh0",
                                  "qc0"])
@pytest.mark.parametrize("cell,n_layers", [("lstm", 1), ("lstm", 2),
                                           ("gru", 1)])
def test_pump_drain_check_equals_per_stream_validation(cell, n_layers, kind):
    """Seven valid streams enqueued, then stream 2 corrupted in the queue
    (``kind``; out of range in place): one pump into 4 slots admits, rejects,
    counts, quarantines and stops at engine full exactly as it does with
    ``validate_stream`` on every stream, and only the corrupted stream (the
    whole head, for the range check) is validated again."""
    from test_serving import (CORRUPTIONS, _cell_stack, _stateful_streams,
                              admission_record, assert_same_admission,
                              corrupt, count_validations, per_stream_checks)

    qps, luts = _cell_stack(cell, n_layers)
    got = []
    for reference in (False, True):
        reg = MetricsRegistry()
        eng = SensorFleetEngine(qps, FMT, luts, batch_slots=4, chunk=4,
                                backend="fxp", metrics=reg)
        if reference:
            per_stream_checks(eng)
        q = IngestQueue(eng, capacity=8)
        streams = _stateful_streams(cell, n_layers, [5, 9, 3, 7, 6, 4, 8])
        for s in streams:
            s.qxs = np.array(s.qxs)
            q.submit(s)
        calls = count_validations(eng)
        corrupt(streams[2], kind, cell, n_layers)
        admitted = q.pump()
        got.append(admission_record(eng, reg))
        got[-1].update(admitted=admitted, calls=calls,
                       queued=[s.rid for s in q.queued])
    fast, ref = got
    assert_same_admission(fast, ref, kind, n_layers)
    assert fast["admitted"] == ref["admitted"] == 4
    bad = CORRUPTIONS[kind] is not None
    assert fast["queued"] == ref["queued"] == ([5, 6] if bad else [4, 5, 6])
    assert fast["counters"]["fleet/ingest_admitted_total"] == 4
    assert fast["counters"].get("fleet/ingest_admit_rejected_total", 0) == bad
    assert fast["calls"] == {"none": [], "out_of_range": [0, 1, 2, 3, 4]
                             }.get(kind, [2])
    assert ref["calls"] == [0, 1, 2, 3, 4] + ([5] if bad else [])
