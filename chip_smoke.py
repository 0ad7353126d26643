"""Smoke run of the served fixed-point path on a TPU.

Proves, through the entry points a user calls, that the fused fixed-point
kernel compiles for the chip and serves integers equal to its oracle:

1. golden replay: every committed fixture under ``tests/golden/`` that runs
   through the fused kernel or the fleet engine, replayed through the
   compiled kernel and through ``SensorFleetEngine``;
2. the paper's model (``configs/lstm_pems.py``: input 1, H=20, (8,16), LUT
   depth 256, seeded weights) serving all 11160 PeMS-4W sensors as ragged
   6-18 step streams through ``IngestQueue`` -> ``SensorFleetEngine(backend=
   "pallas_fxp")`` -> the dense head, equal to the ``fxp`` oracle run on the
   host CPU in the same process.

``--chips 4`` runs only the slot-sharded fleet over four chips, compared with
the single-device engine.  The script needs a TPU and exits non-zero on any
other platform.  Its timings are smoke timings, not benchmark measurements.
The last line of standard output is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py [--chips 4] [--seed 0]
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
GOLDEN = ROOT / "tests" / "golden"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# The fxp oracle runs on the host CPU backend in this same process, so a
# platform list that names only the accelerator gets the CPU added.
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = f"{_platforms},cpu"
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs.lstm_pems import CONFIG  # noqa: E402
from repro.core.cell import GRUParams  # noqa: E402
from repro.core.fxp import FxpFormat, fmt_from_dict, fxp_matmul, quantize  # noqa: E402
from repro.core.lstm import LSTMParams, lstm_forward  # noqa: E402
from repro.core.lut import LutSpec, make_lut_pair  # noqa: E402
from repro.core.quantize import quantize_lstm_model  # noqa: E402
from repro.data.traffic import make_fleet_windows  # noqa: E402
from repro.kernels.lstm_fxp_seq import (  # noqa: E402
    gru_sequence_fxp_pallas, lstm_sequence_fxp_pallas,
    lstm_sequence_fxp_stack_pallas)
from repro.models.lstm_model import init_traffic_model  # noqa: E402
from repro.parallel.sharding import fleet_mesh  # noqa: E402
from repro.serving.ingest import IngestQueue  # noqa: E402
from repro.serving.lstm_engine import SensorFleetEngine, SensorStream  # noqa: E402


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(n_chips: int):
    """Exit before any work unless JAX sees at least ``n_chips`` TPUs."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found platform "
                 f"{devices[0].platform!r} ({len(devices)} device(s))")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke.py --chips {n_chips} needs {n_chips} TPUs; "
                 f"JAX found {len(devices)}")
    return devices


class CompileClock:
    """Seconds the process spends in backend compiles (cache loads included:
    a warm persistent cache shows as fewer seconds)."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


# --- phase 1: golden replay -------------------------------------------------


def _load_golden(name: str) -> dict:
    g = json.loads((GOLDEN / f"{name}_golden.json").read_text())
    g["_fmt"] = fmt_from_dict(g["fmt"])
    g["_luts"] = {
        fn: (np.asarray(g["lut"][fn]["table"], np.float32),
             LutSpec(fn, g["lut"]["depth"], g["lut"][fn]["lo"],
                     g["lut"][fn]["hi"]))
        for fn in ("sigmoid", "tanh")}
    return g


def _kernel_luts(luts: dict) -> dict:
    (sig_t, sig_s), (tanh_t, tanh_s) = luts["sigmoid"], luts["tanh"]
    return dict(sig_table=sig_t, tanh_table=tanh_t,
                sig_lo=sig_s.bounds[0], sig_hi=sig_s.bounds[1],
                tanh_lo=tanh_s.bounds[0], tanh_hi=tanh_s.bounds[1])


def _eq(name: str, got, want) -> None:
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=name)


def _params(g, cls, src=None):
    """A fixture's ``qw``/``qb`` as one params object, or a per-layer list
    when the fixture stores one matrix per layer."""
    src = g if src is None else src
    ws, bs = src["qw"], src["qb"]
    if np.ndim(ws[0]) == 1:
        return cls(np.asarray(ws, np.int32), np.asarray(bs, np.int32))
    return [cls(np.asarray(w, np.int32), np.asarray(b, np.int32))
            for w, b in zip(ws, bs)]


def _serve_rows(params, fmt, luts, qxs, interpret):
    """Each batch row of a fixture as one stream through the fleet engine."""
    streams = [SensorStream(rid=i, qxs=np.asarray(x, np.int32))
               for i, x in enumerate(qxs)]
    SensorFleetEngine(params, fmt, luts, batch_slots=len(streams), chunk=8,
                      backend="pallas_fxp", interpret=interpret).run(streams)
    return streams


def _serve_schedule(params, fmt, luts, fl, interpret):
    """A fixture's committed slot-churn schedule through the fleet engine."""
    def arr(v):
        return None if v is None else np.asarray(v, np.int32)

    streams = [SensorStream(rid=s["rid"], qxs=arr(s["qxs"]), qh0=arr(s["qh0"]),
                            qc0=arr(s["qc0"])) for s in fl["streams"]]
    SensorFleetEngine(params, fmt, luts, batch_slots=fl["batch_slots"],
                      chunk=fl["chunk"], backend="pallas_fxp",
                      interpret=interpret).run(streams)
    for s, out in zip(streams, fl["outputs"]):
        for k in ("h_seq", "qh", "qc"):
            _eq(f"stream {s.rid} {k}", getattr(s, k), out[k])
    return len(streams)


def replay_goldens(interpret: bool = False) -> None:
    """Phase 1: the committed fixtures through the compiled kernel (un-tiled,
    and time-tiled by 8 with a ragged tail) and the fleet engine,
    ``assert_array_equal`` against their stored integers."""
    # single-layer LSTM, GRU and the QAT-frozen model
    for name in ("lstm_fxp", "gru_fxp", "lstm_qat_frozen"):
        g = _load_golden(name)
        fmt, luts, out = g["_fmt"], g["_luts"], g["outputs"]
        gru = name.startswith("gru")
        p = _params(g, GRUParams if gru else LSTMParams)
        qxs = np.asarray(g["qxs"], np.int32)
        fmt_kw = dict(frac_bits=fmt.frac_bits, total_bits=fmt.total_bits)
        for tt in (None, 8):
            if gru:
                h_seq, qh = gru_sequence_fxp_pallas(
                    qxs, p.w, p.b, None, return_sequence=True, time_tile=tt,
                    interpret=interpret, **fmt_kw, **_kernel_luts(luts))
            else:
                h_seq, qh, qc = lstm_sequence_fxp_pallas(
                    qxs, p.w, p.b, None, None, return_sequence=True,
                    time_tile=tt, interpret=interpret, **fmt_kw,
                    **_kernel_luts(luts))
                _eq(f"{name} kernel qc", qc, out["qc"])
            _eq(f"{name} kernel h_seq", h_seq, out["h_seq"])
            _eq(f"{name} kernel qh", qh, out["qh"])
        streams = _serve_rows(p, fmt, luts, qxs, interpret)
        _eq(f"{name} engine h_seq", np.stack([s.h_seq for s in streams]),
            out["h_seq"])
        _eq(f"{name} engine qh", np.stack([s.qh for s in streams]), out["qh"])
        if not gru:
            _eq(f"{name} engine qc", np.stack([s.qc for s in streams]),
                out["qc"])
        if "qy" in out:
            qh = jnp.asarray(np.stack([s.qh for s in streams]))
            _eq(f"{name} dense head", fxp_matmul(
                qh, jnp.asarray(g["dense_qw"], jnp.int32), fmt,
                bias=jnp.asarray(g["dense_qb"], jnp.int32)), out["qy"])
        log(f"golden {name}: kernel (time_tile None, 8) and engine "
            f"({len(streams)} streams) integer-equal")

    # 2-layer stack: fused stack kernel and engine (all layers' carry)
    g = _load_golden("lstm_fxp_stack2")
    fmt, luts, out = g["_fmt"], g["_luts"], g["outputs"]
    ps = _params(g, LSTMParams)
    qxs = np.asarray(g["qxs"], np.int32)
    for tt in (None, 8):
        h_seq, qh, qc = lstm_sequence_fxp_stack_pallas(
            qxs, [p.w for p in ps], [p.b for p in ps], None, None,
            return_sequence=True, time_tile=tt, interpret=interpret,
            frac_bits=fmt.frac_bits, total_bits=fmt.total_bits,
            **_kernel_luts(luts))
        _eq("stack2 kernel h_seq", h_seq, out["h_seq_top"])
        _eq("stack2 kernel qh", qh, out["qh"])
        _eq("stack2 kernel qc", qc, out["qc"])
    streams = _serve_rows(ps, fmt, luts, qxs, interpret)
    _eq("stack2 engine h_seq", np.stack([s.h_seq for s in streams]),
        out["h_seq_top"])
    _eq("stack2 engine qh", np.stack([s.qh for s in streams], 1), out["qh"])
    _eq("stack2 engine qc", np.stack([s.qc for s in streams], 1), out["qc"])
    log("golden lstm_fxp_stack2: kernel and engine integer-equal")

    # mixed precision: the hetero-H stack through the kernel, the uniform-H
    # fleet section through the engine
    g = _load_golden("lstm_mixed")
    sf, luts, st = g["_fmt"], g["_luts"], g["stack"]
    ps = _params(g, LSTMParams, src=st)
    for tt in (None, 8):
        h_seq, hs, cs = lstm_sequence_fxp_stack_pallas(
            np.asarray(st["qxs"], np.int32), [p.w for p in ps],
            [p.b for p in ps], None, None, formats=sf, return_sequence=True,
            time_tile=tt, interpret=interpret, **_kernel_luts(luts))
        _eq("mixed kernel h_seq", h_seq, st["outputs"]["h_seq_top"])
        for li in range(len(st["h_sizes"])):
            _eq(f"mixed kernel layer {li} qh", hs[li], st["outputs"]["qh"][li])
            _eq(f"mixed kernel layer {li} qc", cs[li], st["outputs"]["qc"][li])
    n = _serve_schedule(_params(g, LSTMParams, src=g["fleet"]), sf, luts,
                        g["fleet"], interpret)
    log(f"golden lstm_mixed: hetero-H stack kernel and engine ({n} streams) "
        "integer-equal")

    # the sharded-fleet schedule, single-device side
    g = _load_golden("lstm_fleet_sharded")
    n = _serve_schedule(_params(g, LSTMParams), g["_fmt"], g["_luts"],
                        dict(g["engine"], streams=g["streams"],
                             outputs=g["outputs"]), interpret)
    log(f"golden lstm_fleet_sharded: engine ({n} streams) integer-equal")


# --- phase 2: the paper's model serving the full PeMS-4W fleet --------------


def build_fleet(n_sensors: int, seed: int, cpu):
    """Seeded paper-width model, LUTs and ragged sensor streams, all made on
    the host CPU and handed over as numpy (so chip and oracle share them)."""
    fmt = FxpFormat(CONFIG.frac_bits, CONFIG.total_bits)
    windows = make_fleet_windows(n_sensors, seed)
    with jax.default_device(cpu):
        params = init_traffic_model(jax.random.PRNGKey(seed),
                                    CONFIG.input_size, CONFIG.hidden_size,
                                    CONFIG.out_size)
        qmodel = jax.tree.map(np.asarray, quantize_lstm_model(
            params, fmt, CONFIG.lut_depth))
        luts = {fn: (np.asarray(table), spec) for fn, (table, spec)
                in make_lut_pair(CONFIG.lut_depth).items()}
        flat = np.asarray(quantize(jnp.asarray(np.concatenate(windows)), fmt))
    qxs = np.split(flat, np.cumsum([len(w) for w in windows])[:-1])
    return qmodel, luts, qxs


def fleet_engine(qmodel, luts, slots: int, interpret: bool, mesh=None):
    return SensorFleetEngine(qmodel.lstm, qmodel.fmt, luts, batch_slots=slots,
                             chunk=8, time_tile=8, backend="pallas_fxp",
                             interpret=interpret, mesh=mesh)


def serve_fleet(eng, qxs):
    """All streams through ``IngestQueue`` -> ``eng``; returns the finished
    streams and the serving wall seconds."""
    streams = [SensorStream(rid=i, qxs=q) for i, q in enumerate(qxs)]
    t0 = time.perf_counter()
    IngestQueue(eng, capacity=eng.slots).run(streams)
    wall = time.perf_counter() - t0
    bad = [s.rid for s in streams if not s.done or s.error is not None]
    if bad or eng.quarantined:
        raise RuntimeError(f"{len(bad)} streams unfinished or quarantined "
                           f"(first rids {bad[:5]})")
    return streams, wall


def fxp_oracle(qmodel, luts, qxs, cpu) -> list[tuple]:
    """``(h_seq, qh, qc)`` per stream from the ``fxp`` backend on the host
    CPU, one batched call per stream length."""
    by_len = collections.defaultdict(list)
    for i, q in enumerate(qxs):
        by_len[len(q)].append(i)
    out = [None] * len(qxs)
    with jax.default_device(cpu):
        for idx in by_len.values():
            seq, (h, c) = lstm_forward(
                qmodel.lstm, jnp.asarray(np.stack([qxs[i] for i in idx])),
                backend="fxp", fmt=qmodel.fmt, luts=luts,
                return_sequence=True)
            seq, h, c = np.asarray(seq), np.asarray(h), np.asarray(c)
            for j, i in enumerate(idx):
                out[i] = (seq[j], h[j], c[j])
    return out


def predict(qmodel, qh, device=None):
    """Dense head on the final hidden states: ``(N, 1)`` int32 predictions."""
    with jax.default_device(device):
        return np.asarray(fxp_matmul(jnp.asarray(qh), qmodel.dense_w,
                                     qmodel.fmt, bias=qmodel.dense_b))


def fleet_phase(n_sensors: int, slots: int, seed: int, interpret: bool,
                cpu) -> None:
    """Phase 2: the full fleet on one chip against the CPU ``fxp`` oracle."""
    t0 = time.perf_counter()
    qmodel, luts, qxs = build_fleet(n_sensors, seed, cpu)
    log(f"fleet: {len(qxs)} ragged streams, {sum(map(len, qxs))} timesteps, "
        f"made in {time.perf_counter() - t0:.2f} s")
    eng = fleet_engine(qmodel, luts, slots, interpret)
    if not interpret:
        check_lowering(eng)
    streams, wall = serve_fleet(eng, qxs)
    log(f"fleet: served through IngestQueue -> SensorFleetEngine "
        f"({slots} slots, pallas_fxp) in {wall:.2f} s wall, "
        f"{eng.steps_run} engine steps (smoke timing, compiles included)")
    t0 = time.perf_counter()
    oracle = fxp_oracle(qmodel, luts, qxs, cpu)
    for s, (h_seq, qh, qc) in zip(streams, oracle):
        _eq(f"stream {s.rid} h_seq", s.h_seq, h_seq)
        _eq(f"stream {s.rid} qh", s.qh, qh)
        _eq(f"stream {s.rid} qc", s.qc, qc)
    chip_qh = np.stack([s.qh for s in streams])
    _eq("predictions", predict(qmodel, chip_qh),
        predict(qmodel, np.stack([o[1] for o in oracle]), cpu))
    log(f"fleet: every stream's h_seq/qh/qc and every prediction equal to "
        f"the CPU fxp oracle (oracle {time.perf_counter() - t0:.2f} s)")


def sharded_phase(n_sensors: int, slots: int, seed: int, n_chips: int,
                  interpret: bool, cpu, devices) -> None:
    """``--chips N``: the slot-sharded fleet against the single-device
    engine, with the carry's shards on N distinct devices."""
    qmodel, luts, qxs = build_fleet(n_sensors, seed, cpu)
    log(f"sharded fleet: {len(qxs)} ragged streams over {slots} slots")
    eng = fleet_engine(qmodel, luts, slots, interpret,
                       mesh=fleet_mesh(devices[:n_chips]))
    sharded, wall = serve_fleet(eng, qxs)
    tree, _ = eng.checkpoint_payload()
    on = {shard.device for shard in tree["qh"].addressable_shards}
    if len(on) != n_chips:
        raise RuntimeError(f"(L, slots, H) carry sits on {len(on)} devices, "
                           f"want {n_chips}")
    log(f"sharded fleet: {n_chips} chips, carry shards on {len(on)} distinct "
        f"devices, {wall:.2f} s wall (smoke timing)")
    single, wall = serve_fleet(fleet_engine(qmodel, luts, slots, interpret),
                               qxs)
    for a, b in zip(sharded, single):
        _eq(f"stream {a.rid} h_seq", a.h_seq, b.h_seq)
        _eq(f"stream {a.rid} qh", a.qh, b.qh)
        _eq(f"stream {a.rid} qc", a.qc, b.qc)
    log(f"sharded fleet: every stream integer-equal to the single-device "
        f"engine ({wall:.2f} s wall single-device)")


def check_lowering(eng) -> None:
    """The engine's jitted step must hold a compiled kernel, not a
    fallback: each ``t_step`` bucket lowers to a ``tpu_custom_call``."""
    for t_step in (8, 4, 2, 1):
        if "tpu_custom_call" not in eng.lower_step(t_step).as_text():
            raise RuntimeError(f"engine step (t_step={t_step}) holds no "
                               "tpu_custom_call: the kernel did not compile")
    log("engine step lowers to a tpu_custom_call for t_step 8, 4, 2, 1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the slot-sharded fleet over four chips "
                         "against the single-device engine")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the model weights and the stream draw")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    cpu = jax.devices("cpu")[0]
    dev = devices[0]
    log(f"smoke run (not a benchmark) on {len(devices)} x {dev.device_kind} "
        f"({dev.platform}), jax {jax.__version__}, compile cache {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 1:
        replay_goldens(interpret=False)
        fleet_phase(CONFIG.n_sensors, 1024, args.seed, False, cpu)
    else:
        sharded_phase(CONFIG.n_sensors, 1024, args.seed, args.chips, False,
                      cpu, devices)
    log(f"compile seconds {clock.seconds:.2f} over {clock.count} compiles; "
        f"wall seconds {time.perf_counter() - t0:.2f} (smoke timings)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
