"""bench/program_spans.py: self time, innermost attribution of device idle
and gap labels from the program's ``fleet/`` spans, on hand-made events and
on two small traces recorded on a TPU v5e (``tests/bench/data/``)."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import program_spans as ps  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

DATA = ROOT / "tests/bench/data"

# one pump admitting two streams, one engine step
BENCH = [("pump", 0, 100), ("step", 100, 200)]
PROGRAM = [("fleet/ingest", 5, 95, {}),
           ("fleet/submit", 10, 40, {"rid": 1}),
           ("fleet/claim", 12, 20, {"rid": 1}),
           ("fleet/state_write", 25, 38, {"rid": 1}),
           ("fleet/submit", 52, 90, {"rid": 2}),
           ("fleet/state_write", 58, 85, {"rid": 2}),
           ("fleet/step", 105, 195, {"active": 2}),
           ("fleet/dispatch", 110, 115, {"t_step": 4, "occupied": 2}),
           ("fleet/wait", 115, 180, {}),
           ("fleet/harvest", 180, 192, {})]
# two scatters, then the kernel; idle 0..31, 36..70, 80..125, 170..200
OPS = [("%s.1 = s32[] dynamic-update-slice()", 31, 36),
       ("%s.1 = s32[] dynamic-update-slice()", 70, 80),
       ("%k.1 = s32[] custom-call()", 125, 170)]


def test_innermost_pieces():
    assert ps.innermost([("a", 0, 10), ("b", 2, 4), ("c", 4, 6), ("d", 20, 30)]) \
        == [(0, 2, "a", "a"), (2, 4, "b", "a"), (4, 6, "c", "a"),
            (6, 10, "a", "a"), (20, 30, "d", "d")]
    # a child that outlasts its parent is cut at the parent's end
    assert ps.innermost([("a", 0, 10), ("b", 5, 12)]) \
        == [(0, 5, "a", "a"), (5, 10, "b", "a")]


def test_self_time_and_innermost_idle():
    devices = {"/device:TPU:0": OPS}
    r = ps.reduce(BENCH, devices, PROGRAM)
    ns = pytest.approx
    t = r["spans"]
    assert sorted(t) == sorted({n for n, _, _, _ in PROGRAM})
    want = {  # name: (count, total, self, idle in self), in ns
        "fleet/ingest": (1, 90, 22, 22),
        "fleet/submit": (2, 68, 20, 20),
        "fleet/claim": (1, 8, 8, 8),
        "fleet/state_write": (2, 40, 40, 25),
        "fleet/step": (1, 90, 8, 8),
        "fleet/dispatch": (1, 5, 5, 5),
        "fleet/wait": (1, 65, 65, 20),
        "fleet/harvest": (1, 12, 12, 12),
    }
    for name, (count, total, self_, idle) in want.items():
        assert t[name] == {"count": count, "total_s": ns(total * 1e-9),
                           "self_s": ns(self_ * 1e-9), "idle_s": ns(idle * 1e-9)}
    # of each bench span's idle (trace_reduce's), the part under the program
    assert tr.reduce(BENCH, devices)["idle_s_by_span"] == {"pump": ns(85e-9),
                                                           "step": ns(55e-9)}
    assert r["idle_s_under_program"] == {"pump": ns(75e-9), "step": ns(45e-9)}
    # each gap names the program span that holds most of it
    assert r["idle_gaps"] == [["step>fleet/wait", ns(45e-9)],
                              ["pump>fleet/state_write", ns(34e-9)],
                              ["pump>fleet/claim", ns(31e-9)],
                              ["step>fleet/harvest", ns(30e-9)]]
    assert ps.dispatches(PROGRAM) == [(2, 4)]
    # without program spans: trace_reduce's labels, an empty table
    plain = ps.reduce(BENCH, devices, [])
    assert plain["idle_gaps"] == tr.reduce(BENCH, devices)["idle_gaps"]
    assert plain["spans"] == {} and plain["idle_s_under_program"] == {}


def _profile(name):
    return ps.load(str(DATA / name))


def test_chip_trace_without_program_spans():
    """The first trace, recorded before the program had a profiler sink."""
    spans, devices = tr.collect(_profile("small_trace.xplane.pb.gz"))
    assert ps.collect(_profile("small_trace.xplane.pb.gz")) == []
    plain = tr.reduce(spans, devices)
    assert ps.reduce(spans, devices, [])["idle_gaps"] == plain["idle_gaps"]
    # a program span over every pump and step takes all of their idle time
    program = [("fleet/ingest" if n == "pump" else "fleet/step", s, e, {})
               for n, s, e in spans if n in ("pump", "step")]
    r = ps.reduce(spans, devices, program)
    assert {g[0] for g in r["idle_gaps"]} <= {"pump>fleet/ingest",
                                              "step>fleet/step", "gen"}
    assert r["spans"]["fleet/ingest"]["idle_s"] == pytest.approx(
        plain["idle_s_by_span"]["pump"])
    assert r["idle_s_under_program"]["step"] == pytest.approx(
        plain["idle_s_by_span"]["step"])


# The second trace: four engine steps of ``pems_l1.backlog6`` at 8 slots (24
# sensors), the program's spans on, traced on one TPU v5 lite by
# ``run.run_cell(..., trace=True, keep_trace=path)`` with ``trace_steps`` 4,
# then gzipped.  The loop's steps in that run, as its log gave them: each
# generation admits 8 streams of 6 timesteps, then steps t_step 4 and 2.
PROGRAM_TRACE = "small_trace_program.xplane.pb.gz"
LOOP_STEPS = [(8, 4), (8, 2), (8, 4), (8, 2)]


def test_chip_trace_with_program_spans():
    from bench import run, work

    profile = _profile(PROGRAM_TRACE)
    spans, devices = tr.collect(profile)
    program = ps.collect(profile)
    r = ps.reduce(spans, devices, program)
    t = r["spans"]
    counts = {n: row["count"] for n, row in t.items()}
    # two generations of 8 streams: each enqueued and validated once, each
    # admitted once; the pump after a t_step-4 step finds no free slot once
    assert counts == {"fleet/enqueue": 16, "fleet/validate": 16 + 18,
                      "fleet/ingest": 4, "fleet/submit": 18, "fleet/claim": 18,
                      "fleet/state_write": 16, "fleet/step": 4,
                      "fleet/assemble": 4, "fleet/dispatch": 4, "fleet/wait": 4,
                      "fleet/harvest": 4}
    for row in t.values():
        assert 0 <= row["idle_s"] <= row["self_s"] + 1e-12
        assert row["self_s"] <= row["total_s"] + 1e-12
    # self times add up to the time under program spans
    roots = [(n, s, e) for n, s, e, _ in program
             if n in ("fleet/enqueue", "fleet/ingest", "fleet/step")]
    assert sum(row["self_s"] for row in t.values()) == pytest.approx(
        sum(e - s for _, s, e in roots) * 1e-9)
    # the work from the dispatch args is bench/work.py's count of the steps
    assert ps.dispatches(program) == LOOP_STEPS
    cfg = run.cell_parts(run.load_spec(), "pems_l1.backlog6")["config"]
    assert sum(work.call_work(cfg, o, s)[0] for o, s in ps.dispatches(program)) \
        == sum(work.call_work(cfg, o, s)[0] for o, s in LOOP_STEPS) == 389760
    # the program's spans hold the device idle time inside bench.pump
    pump_idle = tr.reduce(spans, devices)["idle_s_by_span"]["pump"]
    assert r["idle_s_under_program"]["pump"] >= 0.95 * pump_idle
    labels = [g[0] for g in r["idle_gaps"]]
    assert any(g.startswith("pump>fleet/") for g in labels)
    assert all(">fleet/" in g for g in labels)


def test_cli_reduces_a_kept_trace(capsys):
    assert ps.main(["--xplane", str(DATA / PROGRAM_TRACE),
                    "--workload", "pems_l1.backlog6"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["dispatch"] == {"calls": 4, "slot_timesteps": 8 * 12,
                               "operations": 389760}
    assert set(out["summary"]) == {"submit_us_per_stream",
                                   "state_write_us_per_stream", "step_ms",
                                   "wait_ms_per_step"}
    assert out["summary"]["state_write_us_per_stream"] \
        < out["summary"]["submit_us_per_stream"]
    assert out["idle_s_under_program"]["pump"] <= out["idle_s_by_span"]["pump"]


def row(count, total_s):
    return {"count": count, "total_s": total_s, "self_s": 0.0, "idle_s": 0.0}


# one generation of 4 streams: 5 submits (the last finds the engine full),
# 4 state writes, 2 engine steps
TABLE = {"fleet/submit": row(5, 0.0144), "fleet/state_write": row(4, 0.0120),
         "fleet/step": row(2, 0.0300), "fleet/wait": row(2, 0.0010)}


@pytest.mark.parametrize("key,want,needs", [
    ("submit_us_per_stream", 0.0144e6 / 4, "fleet/submit"),
    ("state_write_us_per_stream", 0.0120e6 / 4, "fleet/state_write"),
    ("step_ms", 15.0, "fleet/step"),
    ("wait_ms_per_step", 0.5, "fleet/wait"),
])
def test_summary(key, want, needs):
    assert ps.summary(TABLE)[key] == pytest.approx(want)
    # where its spans are not in the trace, the figure is left out
    assert key not in ps.summary({k: v for k, v in TABLE.items() if k != needs})
    assert ps.summary({}) == {}
