"""Observability layer (ISSUE 9): metrics + tracing under the
zero-perturbation contract.

Three families:

* unit — histogram bucket/quantile determinism, snapshot byte-stability,
  span nesting/ordering (asserted on the deterministic ``seq``/``depth``
  fields, never on timestamps), the disabled no-op path;
* integration — the instrumented ``SensorFleetEngine`` produces the same
  integers with metrics+tracing fully enabled as disabled, and the golden
  fxp fixture replays integer-exact under a live registry;
* persistence — the registry snapshot rides the checkpoint side-car, so a
  kill -> restore -> resume fleet reports *cumulative* counters.
"""

import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.checkpoint.checkpoint import CheckpointManager
from repro.core.fxp import FxpFormat, quantize
from repro.core.lstm import LSTMParams, init_lstm_params, lstm_layer_fxp
from repro.core.lut import make_lut_pair
from repro.obs.metrics import (DEFAULT_US_EDGES, NULL_REGISTRY, Histogram,
                               MetricsRegistry, use_registry)
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serving.faults import retry_io
from repro.serving.lstm_engine import SensorFleetEngine, SensorStream

pytestmark = pytest.mark.obs

FMT = FxpFormat(8, 16)
N_IN, N_H = 2, 10


@pytest.fixture(autouse=True)
def _obs_globals_reset():
    """Every test starts and ends on the no-op defaults."""
    obs.disable_all()
    yield
    obs.disable_all()


def _qps(n_layers=1, key=0):
    out = []
    for li in range(n_layers):
        p = init_lstm_params(jax.random.PRNGKey(key + li),
                             N_IN if li == 0 else N_H, N_H)
        out.append(LSTMParams(w=quantize(p.w, FMT), b=quantize(p.b, FMT)))
    return out


def _streams(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [SensorStream(rid=i, qxs=np.asarray(quantize(
                jnp.asarray(rng.normal(size=(T, N_IN)).astype(np.float32)),
                FMT)))
            for i, T in enumerate(lens)]


def _engine(qps, luts, **kw):
    kw.setdefault("batch_slots", 4)
    kw.setdefault("chunk", 4)
    kw.setdefault("backend", "fxp")
    return SensorFleetEngine(qps, FMT, luts, **kw)


# -- histograms ---------------------------------------------------------------


def test_histogram_bucket_edges():
    h = Histogram(edges=(1.0, 10.0, 100.0))
    for v in (0.5, 1.0, 5.0, 10.0, 99.0, 100.0, 1e6):
        h.observe(v)
    # bisect_left: a value equal to an edge lands in that edge's bucket
    assert h.counts == [2, 2, 2, 1]          # <=1, <=10, <=100, overflow
    assert h.count == 7
    assert h.min == 0.5 and h.max == 1e6


def test_histogram_quantiles_deterministic():
    h = Histogram(edges=(1.0, 2.0, 5.0))
    for v in [0.5] * 50 + [1.5] * 45 + [10.0] * 5:
        h.observe(v)
    assert h.quantile(0.50) == 1.0           # upper edge of covering bucket
    assert h.quantile(0.95) == 2.0
    assert h.quantile(0.99) == 10.0          # overflow -> observed max
    snap = h.snapshot()
    assert snap["p50"] == 1.0 and snap["p95"] == 2.0 and snap["p99"] == 10.0


def test_observe_many_equals_one_observe_each():
    """A batch of readings in one call (a drain's per-stream waits) leaves
    the histogram exactly as one ``observe`` per reading."""
    values = [0.5, 3.0, 3.0, 47.0, 2e6]
    one, many = MetricsRegistry(), MetricsRegistry()
    for v in values:
        one.observe("fleet/ingest_wait_us", v)
    many.observe_many("fleet/ingest_wait_us", values)
    many.observe_many("fleet/ingest_wait_us", [])
    assert many.snapshot() == one.snapshot()
    NULL_REGISTRY.observe_many("fleet/ingest_wait_us", values)
    assert NULL_REGISTRY.snapshot()["histograms"] == {}


def test_histogram_rejects_bad_edges():
    with pytest.raises(ValueError):
        Histogram(edges=(5.0, 1.0))
    with pytest.raises(ValueError):
        Histogram(edges=())


def test_histogram_snapshot_load_round_trip():
    h = Histogram()
    for v in (3.0, 7.0, 5e6, 123.4):
        h.observe(v)
    h2 = Histogram()
    h2.load(h.snapshot())
    assert h2.snapshot() == h.snapshot()


def test_default_edges_are_ascending_microsecond_ladder():
    assert list(DEFAULT_US_EDGES) == sorted(DEFAULT_US_EDGES)
    assert DEFAULT_US_EDGES[0] == 1.0 and DEFAULT_US_EDGES[-1] == 5e6


# -- registry -----------------------------------------------------------------


def test_snapshot_determinism_byte_identical():
    """Two registries fed the same non-timed sequence export byte-identical
    JSON once explicitly-timed histograms are dropped."""
    def feed(reg):
        reg.inc("b/count", 2)
        reg.inc("a/count")
        reg.gauge("z/gauge", 0.25)
        for v in (3.0, 17.0, 400.0):
            reg.observe("lat", v)
        with reg.time("wall_us"):            # the only wall-clock read
            pass
        return reg

    j1 = feed(MetricsRegistry()).to_json(drop_timed=True)
    j2 = feed(MetricsRegistry()).to_json(drop_timed=True)
    assert j1 == j2
    snap = json.loads(j1)
    assert snap["counters"] == {"a/count": 1, "b/count": 2}
    assert "wall_us" not in snap["histograms"]
    # without drop_timed the timed histogram is present and flagged
    full = feed(MetricsRegistry()).snapshot()
    assert full["histograms"]["wall_us"]["timed"] is True
    assert full["histograms"]["lat"]["timed"] is False


def test_registry_merge_snapshot_adds():
    a = MetricsRegistry()
    a.inc("n", 5)
    a.observe("lat", 3.0)
    b = MetricsRegistry()
    b.inc("n", 2)                            # recorded BEFORE the merge
    b.observe("lat", 400.0)
    b.gauge("occ", 0.5)
    b.merge_snapshot(a.snapshot())
    snap = b.snapshot()
    assert snap["counters"]["n"] == 7        # saved + already-recorded
    h = snap["histograms"]["lat"]
    assert h["count"] == 2 and h["min"] == 3.0 and h["max"] == 400.0
    assert snap["gauges"]["occ"] == 0.5      # point-in-time: local wins


def test_registry_load_snapshot_cumulative():
    a = MetricsRegistry()
    a.inc("n", 5)
    a.observe("lat", 3.0)
    b = MetricsRegistry()
    b.load_snapshot(a.snapshot())
    b.inc("n", 2)
    b.observe("lat", 400.0)
    snap = b.snapshot()
    assert snap["counters"]["n"] == 7
    assert snap["histograms"]["lat"]["count"] == 2


def test_null_registry_is_noop():
    NULL_REGISTRY.inc("x")
    NULL_REGISTRY.gauge("y", 1.0)
    NULL_REGISTRY.observe("z", 2.0)
    with NULL_REGISTRY.time("w"):
        pass
    assert NULL_REGISTRY.snapshot() == {"counters": {}, "gauges": {},
                                        "histograms": {}}
    assert NULL_REGISTRY.enabled is False
    # the default global IS the null registry unless enable() ran
    assert obs.get_registry() is NULL_REGISTRY
    # time() hands back one shared context manager — no per-call allocation
    assert NULL_REGISTRY.time("a") is NULL_REGISTRY.time("b")


def test_enable_disable_swap_global():
    reg = obs.enable()
    assert obs.get_registry() is reg and reg.enabled
    obs.disable()
    assert obs.get_registry() is NULL_REGISTRY


def test_use_registry_restores_previous():
    reg = MetricsRegistry()
    with use_registry(reg) as r:
        assert obs.get_registry() is r is reg
    assert obs.get_registry() is NULL_REGISTRY


# -- tracing ------------------------------------------------------------------


def test_span_nesting_and_ordering():
    tr = Tracer()
    with tr.span("outer", tag="a"):
        with tr.span("inner1"):
            pass
        with tr.span("inner2"):
            pass
    with tr.span("later"):
        pass
    ev = {e["name"]: e for e in tr.events()}
    assert set(ev) == {"outer", "inner1", "inner2", "later"}
    # seq is global ENTRY order; depth is per-thread nesting
    assert ev["outer"]["args"]["seq"] == 0
    assert ev["inner1"]["args"]["seq"] == 1
    assert ev["inner2"]["args"]["seq"] == 2
    assert ev["later"]["args"]["seq"] == 3
    assert ev["outer"]["args"]["depth"] == 0
    assert ev["inner1"]["args"]["depth"] == 1
    assert ev["inner2"]["args"]["depth"] == 1
    assert ev["later"]["args"]["depth"] == 0
    assert ev["outer"]["args"]["tag"] == "a"
    # children are contained in the parent's [ts, ts+dur] interval
    o = ev["outer"]
    for name in ("inner1", "inner2"):
        c = ev[name]
        assert c["ts"] >= o["ts"]
        assert c["ts"] + c["dur"] <= o["ts"] + o["dur"] + 1e-3


def test_chrome_trace_format(tmp_path):
    tr = Tracer()
    with tr.span("fleet/step", t_step=8):
        with tr.span("fleet/wait"):
            pass
    doc = tr.to_chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    phs = {e["name"]: e["ph"] for e in doc["traceEvents"]}
    assert phs == {"fleet/step": "X", "fleet/wait": "X"}
    for e in doc["traceEvents"]:
        assert {"name", "ph", "ts", "pid", "tid", "args"} <= set(e)
    path = tmp_path / "t.json"
    tr.save(path)
    assert json.loads(path.read_text()) == doc


def test_null_tracer_is_noop(tmp_path):
    with NULL_TRACER.span("x"):
        pass
    assert NULL_TRACER.events() == []
    assert obs.get_tracer() is NULL_TRACER
    tr = obs.enable_tracing()
    assert obs.get_tracer() is tr
    obs.disable_tracing()
    assert obs.get_tracer() is NULL_TRACER


# -- zero-perturbation: goldens + engine bit-identity -------------------------


def test_golden_integers_unchanged_with_obs_enabled():
    """The committed golden fxp fixture replays integer-exact with metrics
    AND tracing fully enabled — instrumentation never touches the datapath."""
    from repro.core.lut import LutSpec

    g = json.loads((pathlib.Path(__file__).parent / "golden"
                    / "lstm_fxp_golden.json").read_text())
    from repro.core.fxp import fmt_from_dict
    fmt = fmt_from_dict(g["fmt"])
    luts = {}
    for name in ("sigmoid", "tanh"):
        e = g["lut"][name]
        spec = LutSpec(name, g["lut"]["depth"], e["lo"], e["hi"])
        luts[name] = (jnp.asarray(np.asarray(e["table"], np.float32)), spec)
    qp = LSTMParams(w=jnp.asarray(g["qw"], jnp.int32),
                    b=jnp.asarray(g["qb"], jnp.int32))

    reg = obs.enable()
    obs.enable_tracing()
    qxs = jnp.asarray(g["qxs"], jnp.int32)
    out = g["outputs"]
    # the bare layer scan...
    h_seq, (qh, qc) = lstm_layer_fxp(qp, qxs, fmt, luts, return_sequence=True)
    np.testing.assert_array_equal(np.asarray(h_seq), np.asarray(out["h_seq"]))
    np.testing.assert_array_equal(np.asarray(qh), np.asarray(out["qh"]))
    np.testing.assert_array_equal(np.asarray(qc), np.asarray(out["qc"]))
    # ...and the instrumented dispatcher, same integers
    from repro.core.lstm import lstm_forward
    h_seq, (qh, qc) = lstm_forward(qp, qxs, backend="fxp", fmt=fmt, luts=luts,
                                   return_sequence=True)
    np.testing.assert_array_equal(np.asarray(h_seq), np.asarray(out["h_seq"]))
    np.testing.assert_array_equal(np.asarray(qh), np.asarray(out["qh"]))
    np.testing.assert_array_equal(np.asarray(qc), np.asarray(out["qc"]))
    # and the registry actually saw the dispatch
    assert reg.snapshot()["counters"]["kernel/dispatch/lstm/fxp"] >= 1


def test_engine_bit_identical_with_and_without_obs():
    qps, luts = _qps(), make_lut_pair(64)
    plain = _streams([5, 9, 3, 7])
    _engine(qps, luts).run(plain)            # registry: global NULL

    reg = MetricsRegistry()
    obs.enable_tracing()
    observed = _streams([5, 9, 3, 7])
    eng = _engine(qps, luts, metrics=reg)
    eng.run(observed)
    for a, b in zip(plain, observed):
        np.testing.assert_array_equal(a.h_seq, b.h_seq)
        np.testing.assert_array_equal(a.qh, b.qh)
        np.testing.assert_array_equal(a.qc, b.qc)

    snap = eng.metrics()
    assert snap["counters"]["fleet/submit_total"] == 4
    assert snap["counters"]["fleet/admitted_total"] == 4
    # timesteps_total mirrors timesteps_run: t_step per batched call
    assert snap["counters"]["fleet/timesteps_total"] == eng.timesteps_run
    assert snap["counters"]["fleet/steps_total"] == eng.steps_run
    assert snap["histograms"]["fleet/submit_us"]["count"] == 4
    assert snap["histograms"]["fleet/step_us"]["count"] == eng.steps_run
    # occupied slot-timesteps: each stream's timesteps, served once
    assert snap["counters"]["fleet/slot_timesteps_total"] == 5 + 9 + 3 + 7
    assert snap["derived"]["timesteps_per_s"] == pytest.approx(
        24 / (snap["histograms"]["fleet/step_us"]["sum"] / 1e6))
    # the t_step histogram uses the engine's power-of-two bucket edges
    assert snap["histograms"]["fleet/t_step"]["edges"] == sorted(
        float(b) for b in eng._buckets)
    names = [e["name"] for e in obs.get_tracer().events()]
    assert "fleet/step" in names and "fleet/dispatch" in names


def test_engine_quarantine_counts_by_reason():
    """The single-count rejection contract (see the ``lstm_engine`` module
    docstring): a stream malformed at the submit boundary counts ONCE
    under ``fleet/submit_rejected/*`` — admit() adds its own disposition
    count; and corruption of an admitted stream moves no counter at all,
    because the engine serves its staged copy: the victim completes with
    the integers of its claim-time input."""
    qps, luts = _qps(), make_lut_pair(64)
    reg = MetricsRegistry()
    eng = _engine(qps, luts, metrics=reg)
    good = _streams([4])
    bad = SensorStream(rid=99, qxs=np.zeros((3, N_IN), np.float64))  # dtype
    eng.admit([good[0], bad])
    eng.run([])
    snap = reg.snapshot()
    # boundary rejection: submit counters only, exactly once
    assert snap["counters"]["fleet/submit_rejected_total"] == 1
    assert snap["counters"]["fleet/submit_rejected/TypeError"] == 1
    assert snap["counters"]["fleet/admit_rejected_total"] == 1
    assert snap["counters"].get("fleet/quarantined_total", 0) == 0
    assert good[0].done
    assert eng.quarantined == [bad] and bad.error

    # mid-flight corruption: out of the kernel's reach, nothing counted
    from repro.serving.faults import poison_mid_flight
    eng2 = _engine(qps, luts, metrics=(reg2 := MetricsRegistry()))
    victim, survivor = _streams([8, 8], seed=1)
    clean = _streams([8, 8], seed=1)
    _engine(qps, luts).run(clean)
    eng2.admit([victim, survivor])
    eng2.step()
    poison_mid_flight(victim, N_IN)
    eng2.run([])
    snap2 = reg2.snapshot()["counters"]
    assert not any(k.startswith("fleet/quarantined") for k in snap2)
    assert snap2.get("fleet/submit_rejected_total", 0) == 0
    assert eng2.quarantined == [] and victim.done and survivor.done
    for a, b in zip((victim, survivor), clean):
        np.testing.assert_array_equal(a.h_seq, b.h_seq)
        np.testing.assert_array_equal(a.qh, b.qh)
        np.testing.assert_array_equal(a.qc, b.qc)


def test_slot_occupancy_gauge_updates_when_slots_free():
    """Regression (ISSUE 10): the gauge must reflect freed slots after a
    step, not the pre-kernel batch size — an idle fleet reports 0.0."""
    qps, luts = _qps(), make_lut_pair(64)
    reg = MetricsRegistry()
    eng = _engine(qps, luts, metrics=reg)      # 4 slots
    short, long = _streams([4, 12])
    eng.admit([short, long])
    assert reg.snapshot()["gauges"]["fleet/slot_occupancy"] == 2 / 4
    eng.step()                                 # t_step=4: short finishes
    assert short.done and not long.done
    assert reg.snapshot()["gauges"]["fleet/slot_occupancy"] == 1 / 4
    eng.run([])                                # drain: all slots free
    assert long.done
    assert reg.snapshot()["gauges"]["fleet/slot_occupancy"] == 0.0


# -- the profiler sink: spans in the jax.profiler trace ------------------------

# a drain is fleet/ingest (through the queue), fleet/admit (direct bulk) or
# fleet/submit (one direct submit, with the rid)
DRAINS = {"fleet/ingest", "fleet/admit", "fleet/submit"}
# fleet/validate is the drain's check (arg streams), the enqueue check, or a
# stream's own validation inside the drain's check (both with the rid)
PARENT = {"fleet/enqueue": {None}, "fleet/ingest": {None},
          "fleet/admit": {None}, "fleet/submit": {None}, "fleet/step": {None},
          "fleet/validate": {"fleet/enqueue", "fleet/validate"} | DRAINS,
          "fleet/claim": DRAINS, "fleet/admit_write": DRAINS,
          "fleet/stage": {"fleet/claim"},
          "fleet/assemble": {"fleet/step"}, "fleet/dispatch": {"fleet/step"},
          "fleet/wait": {"fleet/step"}, "fleet/harvest": {"fleet/step"}}


def _profiled(tmp_path, serve):
    """Run ``serve()`` with the profiler sink on, under a ``jax.profiler``
    trace written to ``tmp_path``; returns the trace's ``fleet/`` spans as
    ``(name, start_ns, end_ns, stats)``, parents before their children."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    obs.enable_tracing(profiler=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        serve()
    finally:
        jax.profiler.stop_trace()
        obs.disable_tracing()
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns, dict(e.stats))
                             for e in line.events if e.name.startswith("fleet/"))
    return sorted(spans, key=lambda sp: (sp[1], -sp[2]))


def _parents(spans):
    """The innermost span enclosing each span (None at the top)."""
    out, stack = [], []
    for sp in spans:
        while stack and stack[-1][2] <= sp[1]:
            stack.pop()
        out.append(stack[-1] if stack else None)
        stack.append(sp)
    return out


def _check_span_tree(spans, streams, drain):
    """Every span under its expected parent; request spans carry their rid,
    drain spans their stream counts: per ``drain`` span one ``fleet/claim``
    and, when it admitted streams, one ``fleet/admit_write`` and, inside the
    claim, one ``fleet/stage`` with the same ``streams``, no more than its
    ``fleet/validate`` checked; the writes sum to all the streams."""
    assert {sp[0] for sp in spans} == set(PARENT) - (DRAINS - {drain}) \
        - ({"fleet/enqueue"} if drain != "fleet/ingest" else set())
    per_drain, staged = {}, {}          # staged: claim -> its stage's streams
    for sp, parent in zip(spans, _parents(spans)):
        name, _, _, stats = sp
        pname = parent[0] if parent else None
        assert pname in PARENT[name], (name, parent)
        if name in ("fleet/enqueue", "fleet/submit") or (
                name == "fleet/validate" and pname not in DRAINS):
            assert isinstance(stats.get("rid"), int), sp
            if pname == "fleet/enqueue":
                assert stats["rid"] == parent[3]["rid"], (sp, parent)
            if pname == "fleet/validate":
                assert isinstance(parent[3].get("streams"), int), (sp, parent)
        if name == "fleet/stage":
            assert id(parent) not in staged, (sp, parent)
            staged[id(parent)] = stats["streams"]
        if pname in DRAINS:
            assert pname == drain, (sp, parent)
            assert isinstance(stats.get("streams"), int), sp
            seen = per_drain.setdefault(id(parent), {})
            if name == "fleet/validate":
                seen[name] = seen.get(name, 0) + stats["streams"]
            else:
                assert name not in seen, (sp, parent)
                seen[name] = stats["streams"]
    for seen in per_drain.values():
        assert seen.get("fleet/admit_write", 0) == seen["fleet/claim"], seen
        assert seen["fleet/claim"] <= seen["fleet/validate"], seen
    assert sum(seen.get("fleet/admit_write", 0)
               for seen in per_drain.values()) == len(streams)
    claims = [sp for sp in spans if sp[0] == "fleet/claim"]
    assert [staged.get(id(sp), 0) for sp in claims] == [
        sp[3]["streams"] for sp in claims]


def test_profiler_spans_form_the_request_and_step_tree(tmp_path):
    from repro.serving.ingest import IngestQueue

    qps, luts = _qps(), make_lut_pair(64)
    lens = [5, 9, 3, 7, 6]
    streams = _streams(lens)
    eng = _engine(qps, luts)                          # 4 slots, chunk 4
    spans = _profiled(tmp_path,
                      lambda: IngestQueue(eng, capacity=2).run(streams))
    assert all(s.done for s in streams)
    _check_span_tree(spans, streams, "fleet/ingest")
    # the dispatch args count exactly the occupied slot-timesteps served
    dispatch = [sp[3] for sp in spans if sp[0] == "fleet/dispatch"]
    assert len(dispatch) == eng.steps_run
    assert sum(d["occupied"] * d["t_step"] for d in dispatch) == sum(lens)
    steps = [sp for sp in spans if sp[0] == "fleet/step"]
    assert len(steps) == eng.steps_run


def test_profiler_spans_of_the_direct_admit_path(tmp_path):
    """``engine.run`` without the queue: each ``admit`` drain is a
    ``fleet/admit`` span holding one ``fleet/validate``, one ``fleet/claim``
    and one ``fleet/admit_write``."""
    qps, luts = _qps(), make_lut_pair(64)
    streams = _streams([5, 9, 3, 7, 6, 4])
    eng = _engine(qps, luts)                          # 4 slots, chunk 4
    spans = _profiled(tmp_path, lambda: eng.run(streams))
    assert all(s.done for s in streams)
    _check_span_tree(spans, streams, "fleet/admit")
    first = next(sp for sp in spans if sp[0] == "fleet/admit_write")
    assert first[3]["streams"] == 4                   # the first drain fills


def test_profiler_spans_of_direct_submit_and_a_rejected_stream(tmp_path):
    """A direct ``submit`` is a drain of one, a ``fleet/submit`` span with
    the ``rid``; a stream the drain check refuses is validated again under
    its own ``fleet/validate`` with its ``rid``, inside the drain's."""
    qps, luts = _qps(), make_lut_pair(64)
    streams = _streams([5, 3, 7])
    bad = SensorStream(rid=99, qxs=np.zeros((3, N_IN), np.float64))
    eng = _engine(qps, luts)                          # 4 slots

    def serve():
        for s in streams:
            assert eng.submit(s)
        with pytest.raises(TypeError):
            eng.submit(bad)
        eng.run([])

    spans = _profiled(tmp_path, serve)
    assert all(s.done for s in streams)
    _check_span_tree(spans, streams, "fleet/submit")
    own = [sp[3]["rid"] for sp, parent in zip(spans, _parents(spans))
           if sp[0] == "fleet/validate" and parent[0] == "fleet/validate"]
    assert own == [99]


def test_staging_counter_gauge_and_one_stage_span_per_drain(tmp_path):
    """``fleet/staged_timesteps_total`` counts the timesteps the claims
    copied into the staging (every admitted stream's length, once),
    ``fleet/stage_capacity`` holds ``cap`` (17 steps: 32), and each drain
    that admitted streams holds one ``fleet/stage`` inside its
    ``fleet/claim``, with the streams of its ``fleet/admit_write``."""
    qps, luts = _qps(), make_lut_pair(64)
    lens = [5, 9, 3, 7, 6, 4, 17]
    streams = _streams(lens)
    reg = MetricsRegistry()
    eng = _engine(qps, luts, metrics=reg)             # 4 slots, chunk 4
    spans = _profiled(tmp_path, lambda: eng.run(streams))
    assert all(s.done for s in streams)
    snap = reg.snapshot()
    assert snap["counters"]["fleet/staged_timesteps_total"] == sum(lens)
    assert snap["gauges"]["fleet/stage_capacity"] == 32 == eng._cap
    stages = [(sp, parent) for sp, parent in zip(spans, _parents(spans))
              if sp[0] == "fleet/stage"]
    writes = [sp[3]["streams"] for sp in spans if sp[0] == "fleet/admit_write"]
    assert [sp[3]["streams"] for sp, _ in stages] == writes
    assert all(parent[0] == "fleet/claim" for _, parent in stages)
    assert len(stages) == snap["counters"]["fleet/admit_writes_total"] > 1


def test_fleet_golden_integer_equal_with_profiler_spans(tmp_path):
    """The committed slot-churn fleet golden (10 ragged 2-layer streams over
    8 slots) replays integer-exact through the ingest queue while every
    span goes into a running profiler trace."""
    from repro.core.fxp import fmt_from_dict
    from repro.core.lut import LutSpec
    from repro.serving.ingest import IngestQueue

    g = json.loads((pathlib.Path(__file__).parent / "golden"
                    / "lstm_fleet_sharded_golden.json").read_text())
    luts = {}
    for name in ("sigmoid", "tanh"):
        e = g["lut"][name]
        luts[name] = (jnp.asarray(np.asarray(e["table"], np.float32)),
                      LutSpec(name, g["lut"]["depth"], e["lo"], e["hi"]))
    qps = [LSTMParams(w=jnp.asarray(w, jnp.int32), b=jnp.asarray(b, jnp.int32))
           for w, b in zip(g["qw"], g["qb"])]
    streams = [SensorStream(
        rid=s["rid"], qxs=np.asarray(s["qxs"], np.int32),
        qh0=None if s["qh0"] is None else np.asarray(s["qh0"], np.int32),
        qc0=None if s["qc0"] is None else np.asarray(s["qc0"], np.int32),
    ) for s in g["streams"]]
    eng = SensorFleetEngine(qps, fmt_from_dict(g["fmt"]), luts,
                            batch_slots=g["engine"]["batch_slots"],
                            chunk=g["engine"]["chunk"], backend="fxp")
    spans = _profiled(tmp_path,
                      lambda: IngestQueue(eng, capacity=4).run(streams))
    assert sum(sp[3]["streams"] for sp in spans
               if sp[0] == "fleet/admit_write") == len(streams)
    for s, out in zip(streams, g["outputs"]):
        assert s.done
        np.testing.assert_array_equal(s.h_seq, np.asarray(out["h_seq"]))
        np.testing.assert_array_equal(s.qh, np.asarray(out["qh"]))
        np.testing.assert_array_equal(s.qc, np.asarray(out["qc"]))


def test_no_trace_annotation_is_built_while_tracing_is_off(monkeypatch):
    from repro.serving.ingest import IngestQueue

    built = []
    real = jax.profiler.TraceAnnotation

    def counting(name, **args):
        built.append(name)
        return real(name, **args)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    qps, luts = _qps(), make_lut_pair(64)
    IngestQueue(_engine(qps, luts), capacity=2).run(_streams([5, 3, 7]))
    assert obs.get_tracer() is NULL_TRACER and built == []
    # the control: the same serving with the profiler sink on builds them
    obs.enable_tracing(profiler=True)
    IngestQueue(_engine(qps, luts), capacity=2).run(_streams([5, 3, 7]))
    assert {"fleet/enqueue", "fleet/admit_write", "fleet/wait"} <= set(built)


def test_stream_admit_and_done_times(tmp_path):
    """``t_admit`` is set at the slot claim and ``t_done`` when the final
    state reaches the host; neither rides the checkpoint, and a kill ->
    restore -> resume still serves the same integers."""
    qps, luts = _qps(), make_lut_pair(64)
    eng = _engine(qps, luts)
    long, short = _streams([12, 4])
    assert long.t_admit is None and long.t_done is None
    eng.admit([long, short])
    assert long.t_admit is not None and long.t_done is None
    eng.step()                                   # t_step 4: short finishes
    assert short.done and short.t_admit <= short.t_done
    mgr = CheckpointManager(tmp_path / "ck", keep=2)
    eng.save(mgr, step=1)
    tree, extra = eng.checkpoint_payload()
    assert not any("t_admit" in str(k) or "t_done" in str(k)
                   for k in (*tree["streams"]["0"], *extra["slot_table"]["0"]))
    eng2 = SensorFleetEngine.restore(mgr, qps, FMT, luts)
    (restored,) = eng2.active.values()
    assert restored.t_admit is None              # not checkpointed
    eng.run([])
    eng2.run([])
    assert long.t_admit <= long.t_done and restored.t_done is not None
    np.testing.assert_array_equal(restored.h_seq, long.h_seq)
    np.testing.assert_array_equal(restored.qh, long.qh)
    np.testing.assert_array_equal(restored.qc, long.qc)


# -- persistence: counters survive kill -> restore -> resume ------------------


def test_metrics_survive_kill_restore_resume(tmp_path):
    qps, luts = _qps(2), make_lut_pair(64)
    mgr = CheckpointManager(tmp_path / "ck", keep=3)

    reg_a = MetricsRegistry()
    eng = _engine(qps, luts, metrics=reg_a)
    eng.admit(_streams([12, 9, 14]))
    for _ in range(3):
        eng.step()
    eng.save(mgr, step=3)
    steps_at_save = reg_a.snapshot()["counters"]["fleet/steps_total"]
    ts_at_save = reg_a.snapshot()["counters"]["fleet/timesteps_total"]
    assert steps_at_save == 3
    del eng, reg_a                           # the "killed" process

    reg_b = MetricsRegistry()                # fresh process: fresh registry
    eng2 = SensorFleetEngine.restore(mgr, qps, FMT, luts, metrics=reg_b)
    snap = reg_b.snapshot()
    assert snap["counters"]["fleet/steps_total"] == steps_at_save
    assert snap["counters"]["fleet/timesteps_total"] == ts_at_save
    while eng2.active:                       # resume to completion
        eng2.step()
    snap = reg_b.snapshot()
    # CUMULATIVE, not reset: resumed steps add on top of the restored count
    assert snap["counters"]["fleet/steps_total"] == eng2.steps_run > steps_at_save
    assert snap["counters"]["fleet/timesteps_total"] > ts_at_save
    assert snap["counters"]["fleet/ckpt_restores_total"] == 1
    assert snap["histograms"]["fleet/ckpt_restore_us"]["count"] == 1


def test_checkpoint_io_metrics(tmp_path):
    qps, luts = _qps(), make_lut_pair(64)
    with use_registry(MetricsRegistry()) as reg:
        mgr = CheckpointManager(tmp_path / "ck", keep=2)
        eng = _engine(qps, luts)             # uses the enabled global
        eng.admit(_streams([6, 4]))
        eng.step()
        eng.save(mgr, step=1)
        snap = reg.snapshot()
        assert snap["counters"]["ckpt/saves_total"] == 1
        assert snap["counters"]["fleet/ckpt_saves_total"] == 1
        assert snap["counters"]["fleet/ckpt_payload_bytes"] > 0
        assert snap["histograms"]["ckpt/save_us"]["count"] == 1
        # orphaned tmp dir -> swept and counted on restore
        (mgr.root / "step_9.tmp").mkdir()
        SensorFleetEngine.restore(mgr, qps, FMT, luts)
        snap = reg.snapshot()
        assert snap["counters"]["ckpt/restores_total"] == 1
        assert snap["counters"]["ckpt/torn_sweeps_total"] == 1
        assert snap["histograms"]["ckpt/restore_us"]["count"] == 1


def test_retry_io_metrics():
    with use_registry(MetricsRegistry()) as reg:
        calls = []

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise OSError("transient")
            return "ok"

        assert retry_io(flaky, attempts=4, sleep=lambda _: None) == "ok"
        assert reg.snapshot()["counters"]["ckpt/io_retries_total"] == 2
        with pytest.raises(OSError):
            retry_io(lambda: (_ for _ in ()).throw(OSError("dead")),
                     attempts=2, sleep=lambda _: None)
        snap = reg.snapshot()["counters"]
        assert snap["ckpt/io_failures_total"] == 1
        assert snap["ckpt/io_retries_total"] == 3


def test_submit_rejection_counters():
    qps, luts = _qps(), make_lut_pair(64)
    reg = MetricsRegistry()
    eng = _engine(qps, luts, metrics=reg)
    with pytest.raises(TypeError):
        eng.submit(SensorStream(rid=0, qxs=np.zeros((3, N_IN), np.float64)))
    snap = reg.snapshot()["counters"]
    assert snap["fleet/submit_total"] == 1
    assert snap["fleet/submit_rejected_total"] == 1
    assert snap["fleet/submit_rejected/TypeError"] == 1
    assert snap.get("fleet/admitted_total", 0) == 0
