"""Chip benchmark of the served fixed-point LSTM fleet.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process serves one cell of ``BENCHMARK.json`` on the cell's chips:
``IngestQueue.submit`` -> ``pump`` -> ``SensorFleetEngine.step`` ->
``recurrent_forward(backend="pallas_fxp")``.  Everything a cell is made of
is found by name: its configuration at the ``file`` that ``BENCHMARK.json``
gives, with the plain reference it names beside it under ``configs/``; its
traffic mix at ``traffic/<name>.json``; each per-layer metric's reader at
``metrics/<name>.py``.

A run refuses any platform but TPU before any work.  It makes its inputs and
weights from ``--seed``, warms every engine-step shape the traffic uses,
measures for ``--seconds`` seconds, and checks every request the window
completed against the reference.  With ``--trace 0`` the result line holds
the cell's end-to-end metrics; with ``--trace 1`` the first engine steps of
the window run under the profiler, and the line holds the per-layer metrics
read from that trace.  The last line of standard output is one JSON object.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import check, trace_reduce, work  # noqa: E402
from bench.loop import Loop, percentile  # noqa: E402
from bench.traffic.pems import build_requests  # noqa: E402

# closed loop: longest wait past --seconds for a step boundary with no request
# partly served; long enough for a whole 4096-slot generation, so the window
# holds whole generations and in-flight credit is a last resort
CLOSED_GRACE_S = 60.0
OPEN_DRAIN_S = 60.0      # open loop: longest wait for the window's requests
OPEN_TAIL_S = 70.0       # open loop: arrivals scheduled past the window


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(spec: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    """Everything one cell is made of, found by the names in ``spec``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    e2e = [m for m in spec["end_to_end"] if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if name in m["workloads"] or ("workloads" not in m
                                               and m["moves"] in e2e_names)]
    readers = {m["name"]: root / "bench" / "metrics" / f"{m['name']}.py"
               for m in per_layer}
    ref = root / "bench" / "configs" / f"{cfg['reference']}.py"
    missing = [str(p) for p in [ref, *readers.values()] if not p.exists()]
    if missing:
        raise FileNotFoundError(f"cell {name}: missing {missing}")
    return {"cell": cell, "config": cfg, "traffic": traffic, "end_to_end": e2e,
            "per_layer": per_layer, "readers": readers, "reference": ref}


def require_chips(n: int):
    """The cell's TPUs, or exit before any work."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"bench/run.py needs a TPU; JAX found platform "
                 f"{devices[0].platform!r} ({len(devices)} device(s))")
    if len(devices) < n:
        sys.exit(f"this cell needs {n} TPUs; JAX found {len(devices)}")
    return devices[:n]


def load_peaks(kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(have {sorted(peaks)})")
    return peaks[kind]


# --- the system under test ------------------------------------------------


def make_weights(cfg: dict, seed: int):
    """Seeded Glorot-uniform weights and biases (forget-gate bias 1.0 for an
    LSTM), quantised to the configuration's fixed point, made on the default
    device in one jitted call: ``[(w (F, G*H), b (G*H,)) int32]`` per layer."""
    import jax
    import jax.numpy as jnp

    gates = 4 if cfg["cell"] == "lstm" else 3
    widths = work.layer_widths(cfg)
    scale = float(1 << int(cfg["frac_bits"]))
    lim = 1 << (int(cfg["total_bits"]) - 1)

    def q(v):
        return jnp.clip(jnp.floor(v * scale + 0.5), -lim, lim - 1).astype(jnp.int32)

    def init(key):
        out = []
        for k, (F, H) in zip(jax.random.split(key, len(widths)), widths):
            bound = (6.0 / (F + gates * H)) ** 0.5
            w = jax.random.uniform(k, (F, gates * H), jnp.float32, -bound, bound)
            b = jnp.zeros((gates * H,), jnp.float32)
            if gates == 4:
                b = b.at[H:2 * H].set(1.0)
            out.append((q(w), q(b)))
        return out

    return jax.jit(init)(jax.random.PRNGKey(seed))


def build_system(cfg: dict, traffic: dict, weights, tables: dict, devices):
    """``(queue, engine, stream class)`` for one cell on ``devices``."""
    from repro.core.cell import GRUParams
    from repro.core.fxp import FxpFormat
    from repro.core.lstm import LSTMParams
    from repro.core.lut import LutSpec
    from repro.parallel.sharding import fleet_mesh
    from repro.serving.ingest import IngestQueue
    from repro.serving.lstm_engine import SensorFleetEngine, SensorStream

    cls = LSTMParams if cfg["cell"] == "lstm" else GRUParams
    params = [cls(w, b) for w, b in weights]
    luts = {fn: (tables[fn], LutSpec(fn, int(cfg["lut_depth"]),
                                     *map(float, cfg["lut_ranges"][fn])))
            for fn in ("sigmoid", "tanh")}
    slots = int(traffic["slots_per_chip"]) * len(devices)
    eng = SensorFleetEngine(
        params if len(params) > 1 else params[0],
        FxpFormat(int(cfg["frac_bits"]), int(cfg["total_bits"])), luts,
        batch_slots=slots, chunk=int(traffic["chunk"]),
        time_tile=int(traffic["time_tile"]), backend="pallas_fxp",
        mesh=fleet_mesh(devices) if len(devices) > 1 else None)
    capacity = int(traffic.get("queue_capacity", slots))
    queue = IngestQueue(eng, capacity=capacity, policy="reject")
    return queue, eng, SensorStream


def warm_up(loop: Loop, traffic: dict) -> None:
    """Compile and run once each ``t_step`` bucket the traffic uses: one
    stream of exactly that many timesteps, alone in the engine."""
    pts = loop.reqs.points
    for i, t in enumerate(traffic["t_steps"]):
        s = loop.stream_cls(rid=-1 - i, qxs=pts[i % len(pts), :t][:, None].copy())
        loop.q.submit(s)
        while not s.done:
            loop.q.pump()
            loop.eng.step()
        if s.error is not None:
            raise RuntimeError(f"warm-up stream failed: {s.error}")


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# --- one run --------------------------------------------------------------


def run_cell(parts: dict, seed: int, seconds: float, trace: bool, devices,
             keep_trace: str | None = None,
             sizes: dict | None = None, t_process: float = T_PROCESS,
             control: bool = False) -> dict:
    """One run of one cell; returns the result object.  ``sizes`` overrides
    traffic and configuration numbers (tests run a cell at a small size).
    ``control`` adds ``control_checks``: the reference in bfloat16
    (``Datapath(lowp=True)``) put in the program's place, compared alike.
    ``keep_trace`` copies a traced run's raw ``.xplane.pb`` to that path."""
    import jax

    cfg = dict(parts["config"], **(sizes or {}).get("config", {}))
    traffic = dict(parts["traffic"], **(sizes or {}).get("traffic", {}))
    ref = load_module(parts["reference"])
    name = parts["cell"]["name"]
    chips = len(devices)
    open_loop = "rate_per_s" in traffic
    slots = int(traffic["slots_per_chip"]) * chips

    if open_loop:
        n_req = int(float(traffic["rate_per_s"])
                    * (float(traffic["lead_in_s"]) + seconds + OPEN_TAIL_S))
    else:
        n_req = int(traffic["backlog_requests"])

    def phase(what: str) -> None:
        log(f"set-up: {what} done at {time.perf_counter() - t_process:.3f} s")

    phase("imports and device")
    reqs = build_requests(traffic, int(cfg["n_sensors"]), seed, n_req,
                          int(cfg["frac_bits"]), int(cfg["total_bits"]))
    phase(f"{n_req} requests")
    tables = ref.make_tables(cfg)
    with jax.default_device(devices[0]):
        weights = make_weights(cfg, seed)
    queue, eng, stream_cls = build_system(cfg, traffic, weights, tables, devices)
    loop = Loop(queue, eng, reqs, stream_cls, open_loop)
    phase("weights and engine")
    warm_up(loop, traffic)
    phase(f"warm-up of t_step {traffic['t_steps']}")

    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None

    def start_trace():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        loop.spans = True
        return len(loop.steps)

    def stop_trace():
        loop.spans = False
        jax.profiler.stop_trace()

    traced = None
    metrics = {}
    if open_loop:
        lead = float(traffic["lead_in_s"])
        loop.t0 = time.perf_counter()
        loop.run_open(lambda: time.perf_counter() >= loop.t0 + lead,
                      loop.t0 + lead)
        t_start = loop.t0 + lead
        window = [rid for rid in range(len(reqs))
                  if lead <= reqs.due_s[rid] < lead + seconds]
        wset = set(window)
        if trace:
            first = start_trace()
            n_trace = int(traffic["trace_steps"])
            loop.run_open(lambda: len(loop.steps) >= first + n_trace,
                          t_start + seconds)
            stop_trace()
            traced = loop.steps[first:]
        loop.run_open(lambda: all(r in loop.done or r in loop.failed for r in wset),
                      t_start + seconds + OPEN_DRAIN_S)
        done = [r for r in window if r in loop.done]
        failed = len(window) - len(done)
        lat = [loop.done[r] - loop.due[r] for r in done]
        waits = [loop.claim[r] - loop.due[r] for r in window if r in loop.claim]
        late = [loop.late[r] for r in window if r in loop.late]
        log(f"{name}: {len(window)} requests due in the window, {len(done)} "
            f"completed, {failed} failed; generator late by p99 "
            f"{percentile(late, 99) * 1e3:.3f} ms, max {max(late) * 1e3:.3f} ms")
        attempted = len(window)
        in_window = sum(t_start <= loop.done[r] < t_start + seconds for r in loop.done)
        window_info = {"completed_per_s": in_window / seconds,
                       "offered_per_s": float(traffic["rate_per_s"]),
                       "queue_depth_end": queue.depth}
        if lat:
            metrics["latency_p50_ms"] = (percentile(lat, 50) * 1e3, "ms")
            metrics["latency_p99_ms"] = (percentile(lat, 99) * 1e3, "ms")
        compared = window
    else:
        # one clean generation boundary before the window opens
        while eng.active or not loop.steps:
            loop.turn()
        if trace:
            first = start_trace()
            n_trace = int(traffic["trace_steps"])
            while len(loop.steps) < first + n_trace:
                loop.turn()
            stop_trace()
            traced = loop.steps[first:]
            while eng.active:
                loop.turn()
        n_fail0 = len(loop.failed)
        t_start, t_end, forecasts, done = loop.run_closed(seconds, CLOSED_GRACE_S)
        failed = len(loop.failed) - n_fail0
        attempted = len(done) + failed
        waits = []
        log(f"{name}: {forecasts:.3f} forecasts in {t_end - t_start:.3f} s "
            f"({len(done)} completed, {failed} failed, "
            f"{len(loop.steps)} engine steps so far)")
        metrics["inferences_per_s"] = (forecasts / (t_end - t_start), "inf/s")
        window_info = {"seconds": t_end - t_start, "forecasts": forecasts,
                       "engine_steps": sum(t_start <= t < t_end for t, _, _ in loop.steps)}
        compared = done
    metrics["setup_s"] = (t_start - t_process, "s")
    mem = memory_peak(devices)

    per_layer = {}
    breakdown = None
    busy = None
    if trace:
        path = trace_reduce.find_xplane(log_dir)
        if keep_trace:
            shutil.copy(path, keep_trace)
        spans, devs = trace_reduce.collect(trace_reduce.load(path))
        shutil.rmtree(log_dir, ignore_errors=True)
        red = trace_reduce.reduce(spans, devs)
        readers = {e["name"]: load_module(parts["readers"][e["name"]])
                   for e in parts["per_layer"]}
        for r in readers.values():
            kernel = getattr(r, "KERNEL", None)
            if kernel is not None:
                log(f"kernel {kernel!r} matched in the trace as "
                    f"{sorted(n for n in red['op_time'] if kernel in n)}")
        ops = nbytes = 0
        for _, occupied, t_step in traced:
            o, b = work.call_work(cfg, occupied, t_step)
            ops += o
            nbytes += b
        peaks = load_peaks(devices[0].device_kind)
        t_ops = ops / peaks["int8_ops_per_s"]
        t_bytes = nbytes / peaks["hbm_bytes_per_s"]
        log(f"traced {len(traced)} engine steps: {ops} operations, {nbytes} "
            f"bytes; least time {max(t_ops, t_bytes):.3e} s, bound by "
            f"{'HBM bandwidth' if t_bytes >= t_ops else 'int8 compute'}")
        m = {"trace": red, "steps": len(traced), "ops": ops, "bytes": nbytes,
             "chips": chips, "peaks": peaks, "queue_wait_s": waits}
        for entry in parts["per_layer"]:
            value = readers[entry["name"]].read(m)
            if value is not None:
                per_layer[entry["name"]] = (value, entry["unit"])
        busy = (red["busy_s"], red["window_s"])
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}

    # correctness: every request the window completed, against the reference
    n_layers = int(cfg["num_layers"])
    served = [check.served_outputs(loop.streams[r], n_layers)
              if r in loop.streams else None for r in compared]
    qxs = [reqs.qxs(r % len(reqs)) for r in compared]
    weights_np = [(np.asarray(w), np.asarray(b)) for w, b in weights]
    del queue, eng, loop.q, loop.eng
    dp = ref.Datapath(cfg, tables)
    wanted = check.reference_outputs(ref, dp, weights_np, qxs)
    checks = check.compare(served, wanted)
    ok = check.passed(checks)
    control_checks = None
    if control:
        lowp = ref.Datapath(cfg, tables, lowp=True)
        control_checks = check.compare(
            check.reference_outputs(ref, lowp, weights_np, qxs), wanted)

    out_metrics = per_layer if trace else {
        m["name"]: metrics[m["name"]] for m in parts["end_to_end"]
        if m["name"] in metrics}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": chips,
              "memory_peak_bytes": mem}
    if busy is not None:
        device["busy_s"], device["window_s"] = busy
    result = {
        "correct": ok,
        "attempted": attempted,
        "failed": failed,
        "metrics": {k: {"value": v, "unit": u} for k, (v, u) in out_metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["window"] = window_info
    if control_checks is not None:
        result["control_checks"] = control_checks
    result["checks"] = checks
    log(f"{name}: slots {slots}, seed {seed}, correct {ok}: "
        f"{check.format_checks(checks)}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    parts = cell_parts(load_spec(), args.workload)
    devices = require_chips(int(parts["cell"]["chips"]))
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    log(f"{args.workload} on {len(devices)} x {devices[0].device_kind}, "
        f"compile cache {cache}")
    result = run_cell(parts, args.seed, args.seconds, bool(args.trace), devices)
    print(json.dumps(result))
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['rule']} {c['limit']})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
