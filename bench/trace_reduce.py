"""Reduce one profiler trace (``jax.profiler``'s ``.xplane.pb``) to numbers.

The device planes (``/device:TPU:<n>``) carry one event per operation on
their ``XLA Ops`` line; the host plane carries the benchmark's own
``jax.profiler.TraceAnnotation`` spans (names starting ``bench.``) on the
same clock.  From them:

* the window: from the first benchmark span's start to the last one's end;
* busy time per device: the union of its operations' intervals inside the
  window (overlapping operations count once);
* device time per operation name (short name, e.g. ``_rnn_seq_fxp_call.1``),
  from which a metric takes its kernel's time by the name the trace gives it;
* idle gaps: the window minus the busy union, each gap split over the host
  spans it overlaps (time under no benchmark span is ``other``);
* the operations that took the most device time, by their short name.

Times are seconds; per-device figures are averaged over the devices found.
"""

from __future__ import annotations

import collections
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def short_name(op: str) -> str:
    """``%fusion.3 = s32[...] fusion(...)`` -> ``fusion.3``."""
    return op.split(" = ", 1)[0].lstrip("%")


def union(intervals):
    """Sorted, merged ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def collect(profile):
    """``(spans, devices)``: host spans ``[(name, start_ns, end_ns)]`` and, per
    device plane name, its operations ``[(name, start_ns, end_ns)]``."""
    spans, devices = [], {}
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.end_ns) for e in line.events)
            devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name[len(SPAN_PREFIX):], e.start_ns, e.end_ns)
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return spans, devices


def reduce(spans, devices, n_gaps: int = 10, n_ops: int = 10) -> dict:
    """Numbers from ``collect``'s output; see the module docstring."""
    if not spans:
        raise ValueError("the trace holds no benchmark span")
    if not devices:
        raise ValueError("the trace holds no device plane")
    w0 = min(s for _, s, _ in spans)
    w1 = max(e for _, _, e in spans)
    leaf = sorted(spans, key=lambda x: x[1])
    n_dev = len(devices)
    busy = 0.0
    idle_by_span = collections.Counter()
    op_time = collections.Counter()
    gaps = []
    for ops in devices.values():
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in ops if e > w0 and s < w1]
        merged = union((s, e) for _, s, e in inside)
        busy += sum(e - s for s, e in merged)
        for n, s, e in inside:
            op_time[short_name(n)] += e - s
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            share = collections.Counter()
            for name, s, e in leaf:
                if s >= g1:
                    break
                share[name] += overlap(g0, g1, s, e)
            share["other"] = (g1 - g0) - sum(share.values())
            idle_by_span.update(share)
            top = max(share.items(), key=lambda kv: kv[1])[0]
            gaps.append(((g1 - g0) * 1e-9 / n_dev, top))
    gaps.sort(reverse=True)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * 1e-9 / n_dev,
        "op_time": {n: t * 1e-9 / n_dev for n, t in op_time.items()},
        "n_devices": n_dev,
        "idle_s_by_span": {k: v * 1e-9 / n_dev for k, v in idle_by_span.items()
                           if v > 0},
        "span_counts": dict(collections.Counter(n for n, _, _ in spans)),
        "device_ops": [[n, t * 1e-9 / n_dev] for n, t in op_time.most_common(n_ops)],
        "idle_gaps": [[name, s] for s, name in gaps[:n_gaps]],
    }
