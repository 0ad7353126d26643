"""End-to-end driver (paper kind: inference system): train -> quantise ->
sweep configurations -> SERVE the whole PeMS sensor fleet as one batch.

The paper deploys one sensor's model on one XC7S15.  At pod scale the same
workload is "serve all 11 160 PeMS-4W sensors continuously": this example
builds the batched fixed-point serving step (one fused-cell LSTM over the
full sensor batch), runs it for a simulated day of 5-minute ticks, and
reports throughput — the TPU-scale restatement of Table 3.

``--engine`` swaps the lockstep batch for the ``SensorFleetEngine``: each
sensor becomes an independent *ragged* stream (sensors report different
history lengths), streams join and leave slots mid-flight, and every
prediction is still bit-identical to running that sensor alone — the
multi-sensor serving story of the parameterised-architecture follow-up.
``--engine --shard`` additionally shards the slot axis across every local
device (a 1-D mesh data axis): the fleet scales past one chip and the
integers still don't move (``tests/spmd_scripts/check_sharded_fleet.py``).
``--engine --checkpoint-dir DIR`` snapshots the full serving state while it
runs; add ``--kill-after N`` to crash the fleet mid-flight, restore from the
last checkpoint, and watch every surviving stream finish bit-identical to
an uninterrupted run (``tests/spmd_scripts/check_fleet_restore.py``).

``--engine --ingest`` puts the bounded admission queue
(``repro.serving.ingest.IngestQueue``) in front of the engine: sensor
submits become O(validation) enqueues that never wait on a device step,
backpressure is an explicit policy (``--ingest-policy``
reject / drop-oldest / block-with-deadline) instead of an implicit stall,
and the drained integers stay bit-identical to calling ``engine.run``
directly (``tests/test_ingest.py``).  With ``--checkpoint-dir`` /
``--kill-after`` the still-enqueued streams ride the checkpoint and
survive the crash too.

``--cell gru`` runs the same pipeline end to end on the quantised GRU
(``repro.core.cell.GRU_CELL``): training, PTQ/QAT, the fused stack kernel
and the fleet engine are all cell-generic, and every flag above composes.
``--metrics-json PATH`` / ``--trace-json PATH`` switch on the fleet-wide
observability layer (``repro.obs``): latency histograms, slot occupancy,
quarantine counts and checkpoint I/O timings land in PATH as sorted JSON,
and spans land as Chrome ``trace_event`` JSON viewable in chrome://tracing
or https://ui.perfetto.dev — with zero perturbation of the served integers.

    PYTHONPATH=src python examples/traffic_speed_e2e.py [--sensors 512] [--ticks 16]
    PYTHONPATH=src python examples/traffic_speed_e2e.py --engine --sensors 64
    PYTHONPATH=src python examples/traffic_speed_e2e.py --cell gru --engine --layers 2
    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python examples/traffic_speed_e2e.py --engine --shard --sensors 64
    PYTHONPATH=src python examples/traffic_speed_e2e.py --engine --sensors 32 \
        --checkpoint-dir /tmp/fleet_ck --kill-after 4
    PYTHONPATH=src python examples/traffic_speed_e2e.py --engine --sensors 32 \
        --metrics-json m.json --trace-json t.json
    PYTHONPATH=src python examples/traffic_speed_e2e.py --engine --ingest \
        --ingest-capacity 32 --sensors 64
"""

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.fxp import FxpFormat
from repro.core.quantize import quantize_lstm_model, quantized_lstm_forward
from repro.data.traffic import (make_fleet_windows, make_pems_like_fleet,
                                make_traffic_dataset, normalize)
from repro.models.lstm_model import (evaluate_mse, evaluate_quantized_mse,
                                     train_traffic_model)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sensors", type=int, default=512, help="full PeMS = 11160")
    ap.add_argument("--ticks", type=int, default=16, help="5-min steps to serve")
    ap.add_argument("--epochs", type=int, default=12)
    ap.add_argument("--backend", choices=["fxp", "pallas_fxp"], default="fxp",
                    help="quantised LSTM datapath: jnp scan simulator or the "
                         "fused full-sequence Pallas kernel (bit-identical)")
    ap.add_argument("--engine", action="store_true",
                    help="serve ragged per-sensor streams through the "
                         "slot-based SensorFleetEngine instead of one "
                         "lockstep batch")
    ap.add_argument("--slots", type=int, default=16,
                    help="engine batch slots (--engine only)")
    ap.add_argument("--shard", action="store_true",
                    help="shard the engine's slot axis across all local "
                         "devices (1-D jax.sharding.Mesh data axis; slots "
                         "round up to a multiple of the device count) — "
                         "bit-identical to unsharded serving (--engine only; "
                         "try XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=8 on CPU)")
    ap.add_argument("--layers", type=int, default=1,
                    help="stacked LSTM depth: L > 1 serves all layers' "
                         "(h, c) per slot; on pallas_fxp the stack runs as "
                         "one fused kernel with the inter-layer sequence "
                         "resident in VMEM")
    ap.add_argument("--cell", choices=["lstm", "gru"], default="lstm",
                    help="gated recurrent cell (repro.core.cell.CellSpec): "
                         "the whole pipeline — training, PTQ/QAT, the fused "
                         "kernel, the fleet engine, sharding and "
                         "checkpointing — is cell-generic; 'gru' carries a "
                         "single hidden state per slot")
    ap.add_argument("--qat", action="store_true",
                    help="fine-tune under the quantiser (repro.qat) at a "
                         "calibrated low-bit format and serve the QAT-frozen "
                         "model instead of the (8,16) PTQ one — the "
                         "training-side half of the energy story")
    ap.add_argument("--qat-frac-bits", type=int, default=4,
                    help="fractional bits of the QAT operating point "
                         "(total width sized by range calibration)")
    ap.add_argument("--qat-epochs", type=int, default=2)
    ap.add_argument("--ingest", action="store_true",
                    help="front the engine with the bounded admission queue "
                         "(repro.serving.ingest.IngestQueue): submits become "
                         "O(validation) enqueues, admission drains FIFO into "
                         "free slots, served integers unchanged "
                         "(--engine only)")
    ap.add_argument("--ingest-capacity", type=int, default=64,
                    help="admission queue capacity (--ingest only)")
    ap.add_argument("--ingest-policy", default="reject",
                    choices=["reject", "drop-oldest", "block-with-deadline"],
                    help="backpressure policy when the queue is full "
                         "(--ingest only; the driver retries rejected "
                         "submits after a step, so 'reject' still serves "
                         "every sensor)")
    ap.add_argument("--checkpoint-dir", metavar="DIR",
                    help="snapshot the engine's full serving state (slot "
                         "table, all layers' (h, c) carry, per-stream "
                         "cursors) into DIR every 2 steps while serving "
                         "(--engine only)")
    ap.add_argument("--kill-after", type=int, metavar="N",
                    help="inject a crash after N engine steps, restore from "
                         "the last checkpoint in --checkpoint-dir, and "
                         "resume — surviving streams finish bit-identical "
                         "to an uninterrupted run (--engine only)")
    ap.add_argument("--metrics-json", metavar="PATH",
                    help="enable the repro.obs metrics registry (counters, "
                         "gauges, latency histograms across serving, "
                         "checkpointing and kernel dispatch) and write its "
                         "snapshot to PATH on exit; zero-perturbation — the "
                         "served integers are unchanged")
    ap.add_argument("--trace-json", metavar="PATH",
                    help="enable repro.obs span tracing and write a Chrome "
                         "trace_event JSON to PATH on exit (open in "
                         "chrome://tracing or https://ui.perfetto.dev)")
    args = ap.parse_args(argv)
    if args.shard and not args.engine:
        ap.error("--shard only shards the SensorFleetEngine; pass --engine too")
    if (args.checkpoint_dir or args.kill_after is not None) and not args.engine:
        ap.error("--checkpoint-dir/--kill-after checkpoint the "
                 "SensorFleetEngine; pass --engine too")
    if args.kill_after is not None and not args.checkpoint_dir:
        ap.error("--kill-after needs --checkpoint-dir to restore from")
    if args.ingest and not args.engine:
        ap.error("--ingest fronts the SensorFleetEngine; pass --engine too")
    enable_compile_cache()
    _enable_obs(args)

    # --- train on one sensor (paper; --cell gru swaps the recurrent cell) ---
    data = make_traffic_dataset(seed=0)
    params, _ = train_traffic_model(data, epochs=args.epochs,
                                    num_layers=args.layers, cell=args.cell)
    print(f"float ({args.cell}) test MSE: "
          f"{evaluate_mse(params, data.x_test, data.y_test):.5f}")

    # --- PTQ sweep: pick the paper config -----------------------------------
    xs_t, ys_t = jnp.asarray(data.x_test), jnp.asarray(data.y_test)
    for fb, depth in [(6, 128), (8, 256)]:
        qm = quantize_lstm_model(params, FxpFormat(fb, 16), depth)
        mse = evaluate_quantized_mse(qm, xs_t, ys_t)
        print(f"PTQ ({fb},16) LUT{depth}: MSE {mse:.5f}")

    if args.qat:
        # --- QAT: fine-tune under the quantiser, freeze losslessly ----------
        from repro.core.timing_model import (SPARTAN7, LstmModelShape,
                                             parameterised_energy_per_inference_uj,
                                             stack_shapes)
        from repro.qat.calibrate import calibrated_format
        from repro.qat.qat_lstm import finetune_qat, freeze

        depth = 256
        fmt = calibrated_format(params, data.x_train[:256], args.qat_frac_bits)
        ptq = quantize_lstm_model(params, fmt, depth)
        ptq_mse = evaluate_quantized_mse(ptq, xs_t, ys_t)
        qat_params, _ = finetune_qat(params, data, fmt, depth,
                                     epochs=args.qat_epochs)
        qmodel = freeze(qat_params, fmt, depth)
        qat_mse = evaluate_quantized_mse(qmodel, xs_t, ys_t)
        uj = parameterised_energy_per_inference_uj(
            stack_shapes(LstmModelShape(), args.layers), SPARTAN7["XC7S15"],
            fmt.total_bits, depth)
        print(f"QAT ({fmt.frac_bits},{fmt.total_bits}) LUT{depth}: "
              f"MSE {qat_mse:.5f} (PTQ same format: {ptq_mse:.5f}, "
              f"x{ptq_mse / qat_mse:.2f}) ~{uj:.2f} uJ/inf modeled")
        print("serving the QAT-frozen model (bit-exact to QAT eval forward)")
    else:
        qmodel = quantize_lstm_model(params, FxpFormat(8, 16), 256)

    if args.engine:
        serve_fleet_engine(qmodel, args)
        _dump_obs(args)
        return

    # --- fleet serving -------------------------------------------------------
    print(f"serving {args.sensors} sensors (windows of 6 x 5-min points) "
          f"via backend={args.backend!r}")
    fleet = np.stack([normalize(series)[0] for series in
                      make_pems_like_fleet(range(args.sensors))])  # (N, 8064)
    serve = jax.jit(functools.partial(quantized_lstm_forward, backend=args.backend))

    total = 0
    t0 = time.time()
    for tick in range(args.ticks):
        lo = 100 + tick
        window = fleet[:, lo : lo + 6][:, :, None].astype(np.float32)  # (N,6,1)
        pred = serve(qmodel, jnp.asarray(window))
        pred.block_until_ready()
        total += args.sensors
    dt = time.time() - t0
    print(f"{total} inferences in {dt:.2f}s -> {total/dt:.0f} inf/s on this host")
    print("(paper: 17 534 inf/s on the XC7S15 at 71 mW; a v5e pod serves the "
          "full 11 160-sensor fleet in one batched call per tick)")
    _dump_obs(args)


def _enable_obs(args):
    """Switch on the process-wide metrics/tracing globals per the CLI flags
    (off by default: the no-op singletons)."""
    from repro import obs
    if args.metrics_json:
        obs.enable()
    if args.trace_json:
        obs.enable_tracing()


def _dump_obs(args):
    from repro import obs
    if args.metrics_json:
        obs.get_registry().save_json(args.metrics_json)
        print(f"metrics snapshot -> {args.metrics_json}")
    if args.trace_json:
        obs.get_tracer().save(args.trace_json)
        print(f"Chrome trace -> {args.trace_json} "
              "(chrome://tracing / ui.perfetto.dev)")


def serve_fleet_engine(qmodel, args):
    """Multi-sensor serving: ragged streams, continuous batching, exactness.

    Each sensor submits a stream of 6..18 recent 5-minute points (sensors
    report unevenly in the wild); the engine batches whatever is in flight
    through the quantised kernel, and the dense head maps each sensor's
    final hidden state to its speed prediction.
    """
    from repro.core import fxp as fxp_mod
    from repro.core.lut import make_lut_pair
    from repro.parallel.sharding import fleet_mesh
    from repro.serving.lstm_engine import SensorFleetEngine, SensorStream

    fmt = qmodel.fmt
    luts = make_lut_pair(qmodel.lut_depth) if qmodel.lut_depth else None
    rng = np.random.default_rng(0)
    n_layers = (len(qmodel.lstm) if isinstance(qmodel.lstm, (list, tuple))
                else 1)
    mesh, slots = None, args.slots
    if args.shard:
        mesh = fleet_mesh()
        ndev = mesh.devices.size
        slots = -(-args.slots // ndev) * ndev   # engine needs slots % ndev == 0
        print(f"sharding the slot axis over {ndev} device(s); "
              f"slots {args.slots} -> {slots}")
    print(f"fleet engine: {args.sensors} ragged sensor streams via "
          f"{slots} slots, backend={args.backend!r}, "
          f"{n_layers}-layer {qmodel.cell} stack "
          "(all layers' state carried per slot)")

    def _streams():
        return [SensorStream(rid=s, qxs=np.asarray(
                    fxp_mod.quantize(jnp.asarray(window), fmt)))
                for s, window in enumerate(make_fleet_windows(args.sensors))]

    def _engine():
        return SensorFleetEngine(qmodel.lstm, fmt, luts, batch_slots=slots,
                                 chunk=8, time_tile=8, backend=args.backend,
                                 mesh=mesh)

    streams = _streams()
    eng = _engine()
    queue = None
    if args.ingest:
        from repro.serving.ingest import IngestQueue
        queue = IngestQueue(eng, capacity=args.ingest_capacity,
                            policy=args.ingest_policy)
        print(f"ingest queue: capacity {queue.capacity}, policy "
              f"{queue.policy!r} — submits are O(validation) enqueues that "
              "never wait on a device step")
    t0 = time.time()
    if args.checkpoint_dir and queue is not None:
        from repro.checkpoint.checkpoint import CheckpointManager
        from repro.serving.faults import (IngestFaultPlan, InjectedKill,
                                          serve_through_ingest)
        from repro.serving.ingest import IngestQueue
        mgr = CheckpointManager(args.checkpoint_dir, keep=3)
        # sensors trickle in four per tick instead of all up front, so the
        # kill lands with streams still sitting in the admission queue
        arrivals = [(i // 4 + 1, s) for i, s in enumerate(streams)]
        try:
            serve_through_ingest(
                queue, arrivals, mgr, every=2,
                plan=IngestFaultPlan(kill_after_steps=args.kill_after))
        except InjectedKill:
            print(f"KILLED after {args.kill_after} steps; last published "
                  f"checkpoint: step {mgr.latest_step()} — restoring "
                  "(in-queue streams ride the checkpoint)...")
            queue = IngestQueue.restore(mgr, qmodel.lstm, fmt, luts,
                                        mesh=mesh, backend=args.backend,
                                        chunk=8, time_tile=8,
                                        capacity=args.ingest_capacity,
                                        policy=args.ingest_policy)
            eng = queue.engine
            print(f"restored with {queue.depth} stream(s) still enqueued "
                  f"and {len(eng.active)} in flight")
            # streams submitted after the last checkpoint died with the
            # process; their clients resubmit from scratch (fresh copies —
            # the dead objects' buffers are half-written)
            fresh = _streams()
            alive = ({s.rid for s in eng.active.values()}
                     | {s.rid for s in queue.queued}
                     | {p.rid for _, p in arrivals})
            lost = [fresh[s.rid] for s in streams
                    if not s.done and s.rid not in alive]
            if lost:
                print(f"{len(lost)} streams admitted after the checkpoint "
                      "were lost with the process; resubmitting")
            survivors = (list(eng.active.values()) + list(queue.queued)
                         + [p for _, p in arrivals] + lost)
            queue.run([p for _, p in arrivals] + lost)
            golden = _streams()                  # uninterrupted oracle run
            _engine().run(golden)
            golden_by_rid = {g.rid: g for g in golden}
            for s in survivors:
                np.testing.assert_array_equal(s.h_seq,
                                              golden_by_rid[s.rid].h_seq)
            print(f"{len(survivors)} surviving streams (incl. the enqueued "
                  "ones) resumed and finished BIT-IDENTICAL to the "
                  "uninterrupted run")
            by_rid = {s.rid: s for s in streams}
            by_rid.update((s.rid, s) for s in survivors)
            streams = [by_rid[r] for r in sorted(by_rid)]
    elif args.checkpoint_dir:
        from repro.checkpoint.checkpoint import CheckpointManager
        from repro.serving.faults import (FaultPlan, InjectedKill,
                                          serve_with_checkpoints)
        mgr = CheckpointManager(args.checkpoint_dir, keep=3)
        pending = list(streams)
        try:
            serve_with_checkpoints(eng, pending, mgr, every=2,
                                   plan=FaultPlan(kill_after_steps=args.kill_after))
        except InjectedKill:
            print(f"KILLED after {args.kill_after} steps; last published "
                  f"checkpoint: step {mgr.latest_step()} — restoring...")
            eng = SensorFleetEngine.restore(mgr, qmodel.lstm, fmt, luts,
                                            mesh=mesh, backend=args.backend,
                                            chunk=8, time_tile=8)
            # streams admitted after the last checkpoint died with the
            # process; their clients resubmit from scratch (fresh copies —
            # the dead objects' buffers are half-written)
            fresh = _streams()
            alive = ({s.rid for s in eng.active.values()}
                     | {p.rid for p in pending})
            lost = [fresh[s.rid] for s in streams
                    if not s.done and s.rid not in alive]
            if lost:
                print(f"{len(lost)} streams admitted after the checkpoint "
                      "were lost with the process; resubmitting")
            pending.extend(lost)
            survivors = list(eng.active.values()) + pending
            while pending or eng.active:
                eng.admit(pending)
                eng.step()
            golden = _streams()                  # uninterrupted oracle run
            _engine().run(golden)
            golden_by_rid = {g.rid: g for g in golden}
            for s in survivors:
                np.testing.assert_array_equal(s.h_seq,
                                              golden_by_rid[s.rid].h_seq)
            print(f"{len(survivors)} surviving streams resumed and finished "
                  "BIT-IDENTICAL to the uninterrupted run")
            by_rid = {s.rid: s for s in streams}
            by_rid.update((s.rid, s) for s in survivors)
            streams = [by_rid[r] for r in sorted(by_rid)]
    elif queue is not None:
        queue.run(streams)
    else:
        eng.run(streams)
    dt = time.time() - t0

    # dense head on each stream's TOP-layer final hidden state, then
    # dequantise (multi-layer engines hand back (L, H) per stream)
    qh = jnp.asarray(np.stack([s.qh if s.qh.ndim == 1 else s.qh[-1]
                               for s in streams]))
    qy = fxp_mod.fxp_matmul(qh, qmodel.dense_w, fmt, bias=qmodel.dense_b)
    preds = np.asarray(fxp_mod.dequantize(qy, fmt))[:, 0]
    steps = sum(len(s.qxs) for s in streams)
    print(f"{len(streams)} sensors ({steps} total timesteps) in {dt:.2f}s "
          f"-> {len(streams)/dt:.0f} inf/s, {eng.steps_run} batched calls")
    print(f"prediction spread: mean {preds.mean():+.3f}, std {preds.std():.3f} "
          f"(normalised speed)")
    print("(every stream's integers are bit-identical to serving that sensor "
          "alone — see tests/test_serving.py)")


if __name__ == "__main__":
    main()
