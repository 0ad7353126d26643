"""Fault tolerance (ISSUE 6): every injected failure — kill between steps,
torn checkpoint write, flaky checkpoint I/O, poison input at submit — must
either recover bit-identically or fail exactly one stream, never the
fleet; corruption of an admitted stream's arrays must not reach the kernel
at all.

The multi-device half (restore onto D′ ≠ D devices) lives in
``tests/spmd_scripts/check_fleet_restore.py`` via ``test_spmd.py``; this
module is the single-process battery: boundary validation, quarantine at
admission, isolation of admitted streams, retry-with-backoff, torn-write
fallback, and kill→restore bit-identity.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint.checkpoint import CheckpointManager
from repro.checkpoint.elastic import elastic_fleet_restore, fleet_devices
from repro.core.fxp import FxpFormat, quantize
from repro.core.lstm import LSTMParams, init_lstm_params, lstm_forward
from repro.core.lut import make_lut_pair
from repro.serving.faults import (POISON_KINDS, FaultPlan,
                                  FlakyCheckpointManager, InjectedKill,
                                  corrupt_published, poison_mid_flight,
                                  poison_stream, retry_io,
                                  serve_with_checkpoints, torn_save)
from repro.serving.lstm_engine import SensorFleetEngine, SensorStream

pytestmark = pytest.mark.faults

FMT = FxpFormat(8, 16)
N_IN, N_H = 2, 10


def _stack_setup(n_layers=1, key=0, depth=64):
    qps = []
    for li in range(n_layers):
        p = init_lstm_params(jax.random.PRNGKey(key + li),
                             N_IN if li == 0 else N_H, N_H)
        qps.append(LSTMParams(w=quantize(p.w, FMT), b=quantize(p.b, FMT)))
    return qps, make_lut_pair(depth)


def _make_streams(lens, seed=0, n_layers=1, with_state=()):
    rng = np.random.default_rng(seed)
    out = []
    for i, T in enumerate(lens):
        qxs = np.asarray(quantize(
            jnp.asarray(rng.normal(size=(T, N_IN)).astype(np.float32)), FMT))
        s = SensorStream(rid=i, qxs=qxs)
        if i in with_state:
            s.qh0 = rng.integers(-100, 100, (n_layers, N_H)).astype(np.int32)
            s.qc0 = rng.integers(-100, 100, (n_layers, N_H)).astype(np.int32)
        out.append(s)
    return out


def _engine(qps, luts, **kw):
    kw.setdefault("batch_slots", 4)
    kw.setdefault("chunk", 4)
    kw.setdefault("backend", "fxp")
    return SensorFleetEngine(qps, FMT, luts, **kw)


def _golden(qps, luts, lens, **kw):
    streams = _make_streams(lens, n_layers=len(qps), with_state=(1,))
    _engine(qps, luts, **kw).run(streams)
    return streams


def _assert_matches_golden(got_by_rid, golden, *, require_all=False):
    compared = 0
    for g in golden:
        s = got_by_rid.get(g.rid)
        if s is None:
            assert not require_all, f"stream {g.rid} missing"
            continue
        np.testing.assert_array_equal(s.h_seq, g.h_seq,
                                      err_msg=f"stream {g.rid} h_seq")
        np.testing.assert_array_equal(s.qh, g.qh, err_msg=f"stream {g.rid} qh")
        np.testing.assert_array_equal(s.qc, g.qc, err_msg=f"stream {g.rid} qc")
        compared += 1
    return compared


# ---------------------------------------------------------------------------
# Submit-boundary validation: one case per rejection reason
# ---------------------------------------------------------------------------

# each poison kind with the error submit raises for it; an overflow's codes
# were quantised to a different format, and int32 would wrap where the
# datapath saturates, so it is rejected at the door
REJECTIONS = {
    "nan": (ValueError, "non-finite"),
    "inf": (ValueError, "non-finite"),
    "float": (TypeError, "quantise"),
    "wrong_width": (ValueError, rf"want \(T, {N_IN}\)"),
    "wrong_ndim": (ValueError, "want"),
    "empty": (ValueError, "empty"),
    "overflow": (ValueError, "fixed-point range"),
}


@pytest.mark.parametrize("kind", POISON_KINDS)
def test_submit_rejects_poison(kind):
    exc, match = REJECTIONS[kind]
    eng = _engine(*_stack_setup())
    with pytest.raises(exc, match=match):
        eng.submit(poison_stream(kind, N_IN, FMT))


def test_submit_rejects_float_initial_state():
    eng = _engine(*_stack_setup())
    s = _make_streams([4])[0]
    s.qh0 = np.full(N_H, np.nan, np.float32)
    with pytest.raises(TypeError, match="qh0 must be integer"):
        eng.submit(s)


def test_rejection_happens_before_slot_allocation():
    """A rejected stream must not leak a slot or any engine state."""
    eng = _engine(*_stack_setup())
    for kind in POISON_KINDS:
        with pytest.raises((TypeError, ValueError)):
            eng.submit(poison_stream(kind, N_IN, FMT))
    assert eng.free_slots() == list(range(eng.slots)) and not eng.active


# ---------------------------------------------------------------------------
# Isolation: a poison stream fails alone at admission; once admitted, a
# stream is served from the engine's staging, out of the caller's reach
# ---------------------------------------------------------------------------


def test_admission_quarantines_poison_keeps_healthy_streams_exact(tmp_path):
    """Bulk serving with every poison kind interleaved: all healthy streams
    finish integer-identical to a poison-free run; every poison stream lands
    in quarantine with a recorded reason."""
    qps, luts = _stack_setup()
    lens = [5, 9, 16, 7, 12, 3, 6]              # one per poison kind
    assert len(lens) == len(POISON_KINDS)
    golden = _golden(qps, luts, lens)
    streams = _make_streams(lens, n_layers=1, with_state=(1,))
    mixed = []
    for i, s in enumerate(streams):
        mixed.append(s)
        mixed.append(poison_stream(POISON_KINDS[i], N_IN, FMT, rid=1000 + i))
    eng = _engine(qps, luts)
    mgr = CheckpointManager(tmp_path, keep=2)
    serve_with_checkpoints(eng, list(mixed), mgr, every=3)
    assert all(s.done for s in streams)
    assert _assert_matches_golden({s.rid: s for s in streams}, golden,
                                  require_all=True) == len(golden)
    assert sorted(s.rid for s in eng.quarantined) == \
        [1000 + i for i in range(len(POISON_KINDS))]
    assert all(s.error for s in eng.quarantined)
    assert not any(s.done for s in eng.quarantined)


def test_mid_flight_poison_quarantined_without_touching_other_lanes():
    """A caller corrupting an ADMITTED stream under the engine
    (``poison_mid_flight`` swaps its ``qxs`` for a wrong-shape array; here
    its ``h_seq`` and ``cursor`` are clobbered too) cannot reach the kernel:
    the engine serves the copy it staged at the claim, so the stream
    completes integer-equal to the golden of its claim-time input, every
    other stream is unchanged, and nothing is quarantined or counted."""
    from repro.obs.metrics import MetricsRegistry

    qps, luts = _stack_setup()
    lens = [12, 14, 10, 16]
    golden = _golden(qps, luts, lens)
    streams = _make_streams(lens, n_layers=1, with_state=(1,))
    reg = MetricsRegistry()
    eng = _engine(qps, luts, metrics=reg)
    for s in streams:
        assert eng.submit(s)
    eng.step()
    poison_mid_flight(streams[2], N_IN)      # corrupt qxs shape mid-flight
    streams[2].h_seq = None
    streams[2].cursor = -7
    while eng.active:
        eng.step()
    assert eng.quarantined == []
    assert all(s.done and s.error is None for s in streams)
    assert streams[2].cursor == lens[2]      # the mirror, restored by a step
    assert _assert_matches_golden({s.rid: s for s in streams}, golden,
                                  require_all=True) == len(lens)
    assert not any(k.startswith("fleet/quarantined")
                   for k in reg.snapshot()["counters"])


def test_in_place_input_overwrite_after_claim_serves_claim_time_input():
    """A caller that writes into the very ``qxs`` array it submitted, after
    the claim and again mid-flight, still gets the integers of the input
    as it was at the claim: the claim copied it into the staging."""
    qps, luts = _stack_setup(2)
    lens = [9, 13, 6]
    golden = _golden(qps, luts, lens)
    streams = _make_streams(lens, n_layers=2, with_state=(1,))
    for s in streams:
        s.qxs = np.array(s.qxs)              # writable, and passed as is
    eng = _engine(qps, luts)
    eng.admit(list(streams))
    assert all(s.qxs is not None and s.h_seq is not None for s in streams)
    for s in streams:
        s.qxs[:] = FMT.qmax
    eng.step()
    for s in streams:
        s.qxs[:] = FMT.qmin
    eng.run([])
    assert _assert_matches_golden({s.rid: s for s in streams}, golden,
                                  require_all=True) == len(lens)


@pytest.mark.parametrize("n_layers", [1, 2])
def test_finished_results_do_not_alias_the_staging(n_layers):
    """A finished stream's ``h_seq``/``qh``/``qc`` are its own arrays: on a
    one-slot engine every later stream reuses the slot's staging, and the
    earlier results still equal the golden afterwards."""
    qps, luts = _stack_setup(n_layers)
    lens = [4, 6, 8, 5]
    golden = _golden(qps, luts, lens)
    streams = _make_streams(lens, n_layers=n_layers, with_state=(1,))
    eng = _engine(qps, luts, batch_slots=1)
    eng.run(streams)
    assert all(s.done for s in streams)
    for s in streams:
        for a in (s.h_seq, s.qh, s.qc):
            assert not np.shares_memory(a, eng._h_stage)
            assert not np.shares_memory(a, eng._x_stage)
    assert _assert_matches_golden({s.rid: s for s in streams}, golden,
                                  require_all=True) == len(lens)


# ---------------------------------------------------------------------------
# Checkpoint/restore: kill between steps, bit-identical resume
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_layers,mode", [(1, "sync"), (2, "async")])
def test_kill_restore_resumes_bit_identical(tmp_path, n_layers, mode):
    """Kill after N steps, restore from the last published checkpoint,
    drive to completion: every surviving stream integer-identical to the
    uninterrupted run (sync and async checkpoint cadence, 1- and 2-layer)."""
    qps, luts = _stack_setup(n_layers)
    lens = [5, 9, 16, 7, 23, 3, 12, 8]
    golden = _golden(qps, luts, lens)
    mgr = CheckpointManager(tmp_path, keep=3)
    streams = _make_streams(lens, n_layers=n_layers, with_state=(1,))
    pending = list(streams)
    with pytest.raises(InjectedKill):
        serve_with_checkpoints(_engine(qps, luts), pending, mgr, every=2,
                               mode=mode, plan=FaultPlan(kill_after_steps=5))
    mgr.wait()
    eng = SensorFleetEngine.restore(mgr, qps, FMT, luts)
    assert eng.backend == "fxp" and eng.chunk == 4   # geometry from manifest
    inflight = list(eng.active.values())
    assert inflight, "kill must land with streams in flight"
    serve_with_checkpoints(eng, pending, mgr, every=2, mode=mode)
    mgr.wait()
    got = {s.rid: s for s in inflight + pending if s.done}
    assert _assert_matches_golden(got, golden) >= len(inflight)


def test_restore_refuses_different_params_fmt_and_geometry(tmp_path):
    qps, luts = _stack_setup()
    mgr = CheckpointManager(tmp_path, keep=2)
    eng = _engine(qps, luts)
    assert eng.submit(_make_streams([8])[0])
    eng.step()
    eng.save(mgr)
    with pytest.raises(ValueError, match="params differ"):
        SensorFleetEngine.restore(
            mgr, [LSTMParams(w=qps[0].w + 1, b=qps[0].b)], FMT, luts)
    with pytest.raises(ValueError, match="fmt"):
        SensorFleetEngine.restore(mgr, qps, FxpFormat(6, 16), luts)
    with pytest.raises(ValueError, match="geometry"):   # L=2 vs saved L=1
        SensorFleetEngine.restore(mgr, _stack_setup(2, key=5)[0], FMT, luts,
                                  strict_params=False)
    # strict_params=False skips only the checksum, not the geometry check
    eng2 = SensorFleetEngine.restore(
        mgr, [LSTMParams(w=qps[0].w + 1, b=qps[0].b)], FMT, luts,
        strict_params=False)
    assert eng2.active


def test_restore_empty_fleet(tmp_path):
    """A checkpoint with no in-flight streams restores to an idle engine."""
    qps, luts = _stack_setup()
    mgr = CheckpointManager(tmp_path, keep=2)
    eng = _engine(qps, luts)
    eng.save(mgr, step=0)
    eng2 = SensorFleetEngine.restore(mgr, qps, FMT, luts)
    assert not eng2.active and eng2.free_slots() == list(range(eng2.slots))


def test_elastic_fleet_restore_single_device(tmp_path):
    """The policy layer on a 1-device host: picks mesh=None and resumes."""
    qps, luts = _stack_setup()
    golden = _golden(qps, luts, [9, 13])
    mgr = CheckpointManager(tmp_path, keep=2)
    streams = _make_streams([9, 13], n_layers=1, with_state=(1,))
    eng = _engine(qps, luts)
    for s in streams:
        assert eng.submit(s)
    eng.step()
    eng.save(mgr)
    eng2, mesh = elastic_fleet_restore(mgr, qps, FMT, luts)
    assert mesh is None                  # one local device on the CI host
    inflight = list(eng2.active.values())
    while eng2.active:
        eng2.step()
    assert _assert_matches_golden({s.rid: s for s in inflight}, golden,
                                  require_all=True) == 2
    assert len(fleet_devices(4)) in (1, 2, 4)


# ---------------------------------------------------------------------------
# Torn writes and flaky I/O
# ---------------------------------------------------------------------------


def test_torn_write_falls_back_to_last_valid_checkpoint(tmp_path):
    """A save that dies mid-write (orphaned tmp dir, no manifest) must be
    swept at restore time, falling back to the last published step — and the
    resumed fleet is still integer-identical (it just recomputes more)."""
    qps, luts = _stack_setup()
    lens = [5, 9, 16, 7, 23, 3]
    golden = _golden(qps, luts, lens)
    mgr = CheckpointManager(tmp_path, keep=3)
    streams = _make_streams(lens, n_layers=1, with_state=(1,))
    pending = list(streams)
    with pytest.raises(InjectedKill, match="mid-save"):
        serve_with_checkpoints(_engine(qps, luts), pending, mgr, every=2,
                               plan=FaultPlan(torn_write_at=6))
    assert list(mgr.root.glob("step_*.tmp")), "torn tmp dir must exist"
    last_valid = mgr.latest_step()
    eng = SensorFleetEngine.restore(mgr, qps, FMT, luts)
    assert not list(mgr.root.glob("step_*.tmp")), "sweep must remove orphans"
    assert eng.steps_run == last_valid
    inflight = list(eng.active.values())
    serve_with_checkpoints(eng, pending, mgr, every=2)
    got = {s.rid: s for s in inflight + pending if s.done}
    assert _assert_matches_golden(got, golden) >= len(inflight)


def test_corrupt_published_step_skipped(tmp_path):
    """Post-publish disk rot: an unreadable manifest drops that step from
    discovery, so restore lands on the previous intact one."""
    qps, luts = _stack_setup()
    mgr = CheckpointManager(tmp_path, keep=3)
    eng = _engine(qps, luts)
    assert eng.submit(_make_streams([12])[0])
    eng.step()
    eng.save(mgr, step=1)
    eng.step()
    eng.save(mgr, step=2)
    corrupt_published(mgr, 2)
    assert mgr.steps() == [1]
    eng2 = SensorFleetEngine.restore(mgr, qps, FMT, luts)
    assert eng2.steps_run == 1


def test_checkpoint_io_retries_with_backoff(tmp_path):
    """Two injected I/O failures, three attempts: the save lands and the
    backoff schedule is exponential.  One more failure than attempts: the
    error surfaces (bounded retry) and the engine keeps serving in memory."""
    qps, luts = _stack_setup()
    eng = _engine(qps, luts)
    assert eng.submit(_make_streams([20])[0])
    eng.step()
    delays = []
    flaky = FlakyCheckpointManager(CheckpointManager(tmp_path, keep=2),
                                   fail_first=2)
    eng.save(flaky, attempts=3, base_delay=0.01, sleep=delays.append)
    assert flaky.failures_injected == 2 and delays == [0.01, 0.02]
    assert flaky.latest_step() == eng.steps_run

    flaky = FlakyCheckpointManager(CheckpointManager(tmp_path / "b", keep=2),
                                   fail_first=3)
    with pytest.raises(OSError, match="injected"):
        eng.save(flaky, attempts=3, base_delay=0.0, sleep=lambda _: None)
    eng.step()                                   # serving unaffected
    assert eng.active


def test_retry_io_bounds():
    with pytest.raises(ValueError, match="attempts"):
        retry_io(lambda: 1, attempts=0)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("nope")
        return "ok"

    assert retry_io(flaky, attempts=3, base_delay=0, sleep=lambda _: None) == "ok"
    assert len(calls) == 3


def test_torn_save_leaves_exact_torn_state(tmp_path):
    """The injector's on-disk state is what a real mid-save kill leaves:
    tmp dir with payload, no manifest, nothing published."""
    mgr = CheckpointManager(tmp_path, keep=2)
    tmp = torn_save(mgr, 7, {"x": np.arange(3)})
    assert tmp.name == "step_7.tmp" and (tmp / "arrays.npz").exists()
    assert not (tmp / "manifest.json").exists()
    assert mgr.steps() == [] and mgr.latest_step() is None
