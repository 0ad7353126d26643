"""bench/trace_reduce.py: busy union, kernel time and attribution of idle
gaps to the benchmark's host spans, on hand-made events and on a small
trace recorded on a TPU v5e (``tests/bench/data/``)."""

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

DATA = ROOT / "tests/bench/data"


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_reduce_by_hand():
    # window 0..100 ns from the spans; ops overlap at 10..30, so busy = 40
    spans = [("pump", 0, 50), ("step", 50, 100)]
    ops = [("%k.1 = s32[] custom-call()", 10, 30), ("%f.2 = add()", 20, 30),
           ("%k.1 = s32[] custom-call()", 60, 80), ("%x = y()", 120, 130)]
    r = tr.reduce(spans, {"/device:TPU:0": ops})
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert r["op_time"]["k.1"] == pytest.approx(40e-9)
    assert r["op_time"]["f.2"] == pytest.approx(10e-9)
    assert "x" not in r["op_time"]                       # outside the window
    # idle: 0..10 and 30..50 in pump, 50..60 and 80..100 in step; the gap
    # 30..60 is named after the span it overlaps most
    assert r["idle_s_by_span"]["pump"] == pytest.approx(30e-9)
    assert r["idle_s_by_span"]["step"] == pytest.approx(30e-9)
    assert r["idle_gaps"] == [["pump", pytest.approx(30e-9)],
                              ["step", pytest.approx(20e-9)],
                              ["pump", pytest.approx(10e-9)]]
    assert r["device_ops"][0] == ["k.1", pytest.approx(40e-9)]


def test_reduce_averages_over_devices_and_splits_gaps():
    spans = [("gen", 0, 40), ("step", 40, 100)]
    d0 = [("%k = c()", 0, 100)]                          # never idle
    d1 = [("%k = c()", 0, 20)]                           # idle 20..100
    r = tr.reduce(spans, {"/device:TPU:0": d0, "/device:TPU:1": d1})
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx(60e-9)
    assert r["idle_s_by_span"]["gen"] == pytest.approx(10e-9)
    assert r["idle_s_by_span"]["step"] == pytest.approx(30e-9)
    assert r["idle_gaps"] == [["step", pytest.approx(40e-9)]]


def test_reduce_refuses_a_trace_without_spans():
    with pytest.raises(ValueError, match="no benchmark span"):
        tr.reduce([], {"/device:TPU:0": []})


@pytest.fixture(scope="module")
def chip_trace():
    """Three engine steps of ``pems_l1.ragged_open`` at its real size,
    traced on one TPU v5 lite by ``run.run_cell(..., trace=True,
    keep_trace=path)`` with ``trace_steps`` 3, then gzipped."""
    import gzip

    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "small_trace.xplane.pb.gz").read_bytes())
    return tr.collect(ProfileData.from_serialized_xspace(raw))


def _sweep_busy(intervals):
    """Covered length by a +1/-1 sweep, independent of ``tr.union``."""
    edges = sorted([(s, 1) for s, _ in intervals] + [(e, -1) for _, e in intervals])
    busy, depth, last = 0, 0, None
    for t, d in edges:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_chip_trace_busy_kernel_and_gaps(chip_trace):
    spans, devices = chip_trace
    assert list(devices) == ["/device:TPU:0"]
    assert sorted({n for n, _, _ in spans}) == ["gen", "pump", "step"]
    r = tr.reduce(spans, devices)
    w0 = min(s for _, s, _ in spans)
    w1 = max(e for _, _, e in spans)
    ops = [(n, max(s, w0), min(e, w1)) for n, s, e in devices["/device:TPU:0"]
           if e > w0 and s < w1]
    busy = _sweep_busy([(s, e) for _, s, e in ops]) * 1e-9
    assert r["window_s"] == pytest.approx((w1 - w0) * 1e-9)
    assert r["busy_s"] == pytest.approx(busy)
    assert 0 < r["busy_s"] < r["window_s"]
    # the fused kernel: one event per engine step, named after its wrapper
    kernel = [(s, e) for n, s, e in ops
              if tr.short_name(n).startswith("_rnn_seq_fxp_call")]
    assert len(kernel) == 3
    assert sum(v for n, v in r["op_time"].items() if "_rnn_seq_fxp_call" in n) \
        == pytest.approx(sum(e - s for s, e in kernel) * 1e-9)
    # every idle nanosecond lies under exactly one label
    assert sum(r["idle_s_by_span"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert set(r["idle_s_by_span"]) <= {"gen", "pump", "step", "other"}
    assert max(r["idle_s_by_span"], key=r["idle_s_by_span"].get) == "pump"
    assert len(r["idle_gaps"]) == 10 and len(r["device_ops"]) == 10
    assert r["idle_gaps"] == sorted(r["idle_gaps"], key=lambda g: -g[1])
