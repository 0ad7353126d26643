"""Multi-sensor LSTM serving engine: continuous batching over the fxp datapath.

The paper deploys one sensor's quantised LSTM on one XC7S15; its follow-up
parameterised-architecture work scales one cell design to deeper models and
many concurrent sensor workloads.  This engine is that fleet-scale
restatement on TPU: ``SensorFleetEngine`` holds the quantised parameters
device-resident once and continuously batches many *independent* sensor
streams through ``repro.core.lstm.lstm_forward(backend="pallas_fxp")`` — the
C1–C5 fused kernel — with per-slot, per-layer ``h``/``c`` state so every
stream's recurrence is bit-identical to running it alone.

Design (mirrors ``repro.serving.engine.ServingEngine``, the LM analogue):

* **slots** — a fixed batch of ``batch_slots`` lanes; each active stream owns
  one lane's ``(h, c)`` rows *in every layer*.  Finished streams release
  their slot and new streams join mid-flight (continuous batching at sensor
  granularity).
* **chunked advance** — each engine step advances all active slots by the
  same number of timesteps ``t_step``: the largest power-of-two bucket
  ``<= min(chunk, shortest remaining stream)``.  Chunking with carried state
  is exact because the kernel computes the recurrence step-by-step — the op
  sequence is identical to one long call (asserted in
  ``tests/test_serving.py``).
* **shape-bucketed jit** — restricting ``t_step`` to power-of-two buckets
  bounds the number of compiled shapes at ``log2(chunk) + 1`` while still
  draining any stream length exactly (greedy binary decomposition of the
  remainder).
* **masked lanes** — empty slots run on zero inputs and their computed state
  is discarded with a ``where`` on the slot axis, so occupancy never changes
  the bits of occupied lanes.

Cells: the engine is cell-generic over ``repro.core.cell`` — pass
``GRUParams`` (bare or per-layer list) and the fleet serves the fxp GRU
through the same fused stack kernel, carrying ``(L, slots, H)`` hidden state
only (``_qc`` is ``None``; streams' ``qc0``/``qc`` must be/stay ``None``).
The cell kind rides in the checkpoint manifest (``extra["engine"]["cell"]``,
defaulting to ``"lstm"`` for pre-GRU checkpoints) and restore refuses a
params/checkpoint cell mismatch.

Stacked models: pass a *list* of per-layer ``LSTMParams`` (uniform hidden
size ``H``).  ``fmt`` may be a single ``FxpFormat`` or a per-layer/per-gate
``StackFormats`` (mixed precision): the kernel rescales between formats
inside the fused stack, the engine validates submitted inputs against the
*input* format (``layers[0].data``), and checkpoints store the full nested
format (``fmt_to_dict``) so restore refuses a mismatched datapath.
Per-slot state is ``(L, slots, H)`` and every engine step
carries ALL layers' ``(h, c)`` via ``lstm_forward(..., return_state="all")``,
so the chunked continuation of the whole stack is exact — on
``backend="pallas_fxp"`` the stack additionally runs as one fused kernel
with the inter-layer hidden sequence resident in VMEM
(``lstm_sequence_fxp_stack_pallas``).

Sharding (``mesh=``): the step is pure data parallelism over slots —
independent streams never interact — so ``mesh=`` shards the slot axis of
the inputs, lane mask and ``(L, slots, H)`` state over the mesh's ``data``
axis via ``shard_map`` (specs from ``repro.parallel.sharding
.fleet_slot_specs``), with the quantised params replicated on every device.
Each device runs the *same* fused kernel on its own contiguous slot block,
so the integers are unchanged: sharded serving is bit-identical to the
single-device engine (and hence to per-stream execution), proven on forced
host devices by ``tests/spmd_scripts/check_sharded_fleet.py``.

**Slot→device placement invariant:** with ``S`` slots on ``D`` devices,
slot ``s`` lives on device ``s * D // S`` (block partition) for the
engine's whole lifetime.  ``submit`` hands a joining stream the lowest free
slot and never migrates an active one, so a stream's ``h``/``c`` carry
stays on one device across join/leave churn — occupancy can change *which*
devices do useful work, never the bits they produce.

Fault tolerance (ISSUE 6): ``save(manager)`` / ``restore(manager, ...)``
snapshot and rebuild the WHOLE serving state — ``(L, slots, H)`` carry,
slot table, per-stream cursors and emitted outputs, serving counters and a
sha256 of the quantised params — through ``repro.checkpoint``'s atomic
manifested writes (``mode="async"`` snapshots device→host between
``step()`` calls so serving never stalls on disk; checkpoint I/O rides a
bounded retry-with-backoff).  Because checkpoints store the carry
*gathered* and placement is a pure function of the slot index, restoring
onto a different device count D′ ≠ D just re-partitions the same slot
blocks — every surviving stream continues bit-identically (battery:
``tests/spmd_scripts/check_fleet_restore.py``).  Input faults degrade
gracefully instead of crashing the fleet: ``submit`` validates
dtype/ndim/feature-width/finiteness/fixed-point range at the boundary
(reject, don't crash), ``admit`` turns those rejections into per-stream
quarantine for bulk serving, and ``step`` quarantines a stream whose
buffers were corrupted mid-flight — one poison stream fails alone, the
rest of the batch's integers are untouched (masked lanes never interact).

Observability (ISSUE 9): the engine reports itself through ``repro.obs`` —
submit latency (``fleet/submit_us``), admit-queue depth, slot occupancy,
per-step kernel-dispatch time (``fleet/step_us``), ``t_step`` bucket usage,
quarantine counts by reason kind, and checkpoint save/restore timings +
payload bytes — under the zero-perturbation contract: metrics/spans time and
count Python-level events only and never touch traced values, so every
bit-identity battery passes unchanged with observability fully enabled
(``tests/test_obs.py``).  Off by default: instrumentation resolves the
process-local registry/tracer at call time (no-op singletons unless
``repro.obs.enable()`` / ``enable_tracing()`` ran, or a per-engine registry
was passed via ``metrics=``).  ``engine.metrics()`` returns the snapshot;
the full snapshot also rides the checkpoint side-car so counters survive
kill -> restore (cumulative, not reset).
"""

from __future__ import annotations

import dataclasses
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core import fxp as fxp_mod
from repro.core.cell import GRUParams, cell_spec
from repro.core.fxp import FxpFormat, StackFormats
from repro.core.lstm import LSTMParams, lstm_forward, recurrent_forward
from repro.kernels.lstm_fxp_seq import DEFAULT_BLOCK_B
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.parallel.sharding import fleet_slot_specs

__all__ = ["SensorStream", "SensorFleetEngine", "SlotShardingError"]


class SlotShardingError(ValueError):
    """The engine's slot geometry cannot be block-partitioned onto the mesh:
    ``batch_slots`` is not a multiple of the data-axis size, so some device
    would own a ragged slot block and the slot->device placement invariant
    (``slot_to_shard``) would stop being a pure function of the slot index.
    Raised at construction — a ragged fleet must never start serving."""


@dataclasses.dataclass
class SensorStream:
    """One sensor's quantised input stream and its per-step results.

    For an ``L``-layer engine, ``qh0``/``qc0``/``qh``/``qc`` are ``(L, H)``
    (single-layer engines keep the ``(H,)`` form for backward compatibility);
    ``h_seq`` is always the top layer's ``(T, H)``.
    """

    rid: int
    qxs: np.ndarray                     # (T, n_in) int32, quantised to fmt
    qh0: np.ndarray | None = None       # (H,) or (L, H) int32 initial state (default 0)
    qc0: np.ndarray | None = None       # LSTM only; must stay None on a GRU engine
    h_seq: np.ndarray | None = None     # (T, H) int32 top layer, filled as chunks land
    qh: np.ndarray | None = None        # (H,) or (L, H) int32 final hidden state
    qc: np.ndarray | None = None        # (H,) or (L, H) int32 final cell state (None for GRU)
    done: bool = False
    cursor: int = 0                     # timesteps consumed so far
    error: str | None = None            # set when rejected or quarantined

    @property
    def remaining(self) -> int:
        return len(self.qxs) - self.cursor


class SensorFleetEngine:
    """Slot-based continuous batching of (stacked) sensor LSTMs into
    ``pallas_fxp``, optionally slot-sharded across a device mesh (``mesh=``,
    ``shard_slots=``; see the module docstring's placement invariant)."""

    def __init__(
        self,
        qparams,
        fmt: FxpFormat | StackFormats,
        luts: dict | None = None,
        *,
        batch_slots: int = 8,
        chunk: int = 16,
        time_tile: int | None = None,
        backend: str = "pallas_fxp",
        block_b: int | None = None,
        interpret: bool | None = None,
        mesh=None,
        shard_slots: bool | None = None,
        data_axis: str = "data",
        metrics=None,
    ):
        layers = list(qparams) if isinstance(qparams, (list, tuple)) else [qparams]
        if not layers:
            raise ValueError("qparams must name at least one layer")
        # cell kind is read off the param class (GRUParams -> "gru"), like
        # everywhere else in the datapath; it decides the state arity (GRU
        # carries h only — self._qc stays None and streams' qc0/qc are None)
        self.cell = "gru" if isinstance(layers[0], GRUParams) else "lstm"
        self._arity = cell_spec(self.cell).state_arity
        hidden = {p.hidden_size for p in layers}
        if len(hidden) > 1:
            raise ValueError(
                "SensorFleetEngine carries per-slot state as one (L, slots, H) "
                f"buffer, which needs a uniform hidden size; got {sorted(hidden)}")
        if batch_slots < 1:
            raise ValueError("batch_slots must be >= 1")
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        self.shard_slots = bool(mesh is not None if shard_slots is None
                                else shard_slots)
        if self.shard_slots:
            if mesh is None:
                raise ValueError("shard_slots=True needs mesh=jax.sharding.Mesh(...)")
            if data_axis not in mesh.axis_names:
                raise ValueError(
                    f"mesh has no {data_axis!r} axis (axes: {mesh.axis_names}); "
                    "pass data_axis= to name the slot-sharding axis")
            self.n_shards = int(mesh.shape[data_axis])
            if batch_slots % self.n_shards != 0:
                raise SlotShardingError(
                    f"batch_slots={batch_slots} must be a multiple of the "
                    f"{data_axis!r} axis size {self.n_shards} so every device "
                    "owns the same contiguous slot block")
        else:
            self.n_shards = 1
        self.mesh = mesh
        self.data_axis = data_axis
        self.fmt = fmt
        # normalised per-layer view: validates a StackFormats' length against
        # the params and gives submit the format the INPUT arrives in
        self._stack_fmt = fxp_mod.as_stack_formats(fmt, len(layers))
        self.in_fmt = self._stack_fmt.in_fmt
        self.luts = luts
        self.backend = backend
        self.time_tile = time_tile
        self.slots = batch_slots
        self.chunk = chunk
        self.n_layers = len(layers)
        self.n_in = layers[0].input_size
        self.n_h = layers[0].hidden_size
        for li, p in enumerate(layers[1:], start=1):
            if p.input_size != self.n_h:
                raise ValueError(
                    f"layer {li}: input_size {p.input_size} != hidden_size "
                    f"{self.n_h} of the layer below")
        # params live on device once; every step call reuses the same buffers
        self._ws = [jnp.asarray(p.w, jnp.int32) for p in layers]
        self._bs = [jnp.asarray(p.b, jnp.int32) for p in layers]
        # power-of-two t_step buckets, largest first
        self._buckets = [1 << k for k in range(chunk.bit_length() - 1, -1, -1)
                         if (1 << k) <= chunk]
        # ALL layers' carry, one lane per slot: the multi-layer state plumbing
        self._qh = jnp.zeros((self.n_layers, batch_slots, self.n_h), jnp.int32)
        self._qc = (jnp.zeros((self.n_layers, batch_slots, self.n_h), jnp.int32)
                    if self._arity == 2 else None)
        self.active: dict[int, SensorStream] = {}
        self.quarantined: list[SensorStream] = []   # rejected/poisoned streams
        self.steps_run = 0              # batched kernel invocations so far
        self.timesteps_run = 0          # sum of t_step over those invocations

        # Observability: metrics=None resolves the process-local registry at
        # every call site (the no-op singleton unless repro.obs.enable() ran),
        # so a fleet built before enable() still starts reporting after it;
        # pass an explicit MetricsRegistry for per-engine isolation.  The
        # declares below make every snapshot carry the serving surface —
        # submit latency, occupancy, quarantine, checkpoint I/O — even before
        # the first event (and they no-op on the disabled registry).
        self._metrics_override = metrics
        m = self.obs
        m.declare_hist("fleet/submit_us", timed=True)
        m.declare_hist("fleet/step_us", timed=True)
        m.declare_hist("ckpt/save_us", timed=True)
        m.declare_hist("ckpt/restore_us", timed=True)
        m.declare_hist("fleet/ckpt_save_us", timed=True)
        m.declare_hist("fleet/ckpt_restore_us", timed=True)
        m.declare_counter("fleet/quarantined_total")
        m.declare_counter("fleet/steps_total")
        m.declare_counter("fleet/timesteps_total")
        m.declare_gauge("fleet/slot_occupancy")
        m.declare_gauge("fleet/admit_queue_depth")

        # block_b defaults to the kernel's compile-friendly row tile: any slot
        # count (per device, under shard_map) tiles over the batch grid
        fwd_kwargs = dict(
            backend=backend, fmt=fmt, luts=luts, return_sequence=True,
            return_state="all", interpret=interpret, time_tile=time_tile,
            block_b=DEFAULT_BLOCK_B if block_b is None else block_b,
        )

        if self.cell == "gru":
            def step_fn(ws, bs, qx, qh, lane_mask):
                params = [GRUParams(w, b) for w, b in zip(ws, bs)]
                seq, hs = recurrent_forward(
                    "gru", params, qx, h0=list(qh), **fwd_kwargs)
                keep = lane_mask[None, :, None]
                return seq, jnp.where(keep, jnp.stack(hs), qh)
        else:
            def step_fn(ws, bs, qx, qh, qc, lane_mask):
                params = [LSTMParams(w, b) for w, b in zip(ws, bs)]
                seq, (hs, cs) = lstm_forward(
                    params, qx, h0=list(qh), c0=list(qc), **fwd_kwargs)
                keep = lane_mask[None, :, None]
                h = jnp.stack(hs)
                c = jnp.stack(cs)
                return seq, jnp.where(keep, h, qh), jnp.where(keep, c, qc)

        self._state_sharding = None
        if self.shard_slots:
            # shard_map over the mesh data axis: each device runs the SAME
            # kernel on its own slot block — no collectives, identical bits
            specs = fleet_slot_specs(data_axis)
            n_state = self._arity      # (h,) for GRU, (h, c) for LSTM
            step_fn = jax.shard_map(
                step_fn, mesh=mesh,
                in_specs=(specs["params"], specs["params"], specs["x"],
                          *(specs["state"],) * n_state, specs["mask"]),
                out_specs=(specs["seq"], *(specs["state"],) * n_state),
                check_vma=False)
            self._state_sharding = NamedSharding(mesh, specs["state"])
            self._qh = jax.device_put(self._qh, self._state_sharding)
            if self._qc is not None:
                self._qc = jax.device_put(self._qc, self._state_sharding)
            self._ws = [jax.device_put(w, NamedSharding(mesh, specs["params"]))
                        for w in self._ws]
            self._bs = [jax.device_put(b, NamedSharding(mesh, specs["params"]))
                        for b in self._bs]

        # jit re-specialises per input shape, i.e. once per t_step bucket
        self._step = jax.jit(step_fn)

    def lower_step(self, t_step: int):
        """The jitted step lowered for one ``t_step`` bucket on the engine's
        own buffers: ``.as_text()`` shows what the device runs (a compiled
        kernel appears as a ``tpu_custom_call``), ``.compile()`` builds it."""
        x = jnp.zeros((self.slots, t_step, self.n_in), jnp.int32)
        mask = jnp.zeros((self.slots,), bool)
        state = (self._qh,) if self._qc is None else (self._qh, self._qc)
        return self._step.lower(self._ws, self._bs, x, *state, mask)

    # --- observability ------------------------------------------------------

    @property
    def obs(self):
        """The metrics registry this engine reports into: the per-engine one
        passed as ``metrics=``, else the process-local registry (resolved at
        call time so ``repro.obs.enable()`` takes effect immediately)."""
        if self._metrics_override is not None:
            return self._metrics_override
        return obs_metrics.get_registry()

    def metrics(self) -> dict:
        """Snapshot of the engine's metrics registry (counters, gauges,
        histograms with p50/p95/p99), plus a ``derived`` section with the
        kernel-dispatch throughput when step timings exist.  ``{}``-shaped
        (all maps empty) while observability is disabled."""
        snap = self.obs.snapshot()
        step_us = snap.get("histograms", {}).get("fleet/step_us")
        if step_us and step_us["sum"]:
            snap["derived"] = {
                "timesteps_per_s": self.timesteps_run * self.slots
                / (step_us["sum"] / 1e6),
            }
        return snap

    def _count_quarantine(self, kind: str) -> None:
        """Count a MID-FLIGHT quarantine (an admitted stream whose buffers
        were corrupted under us).

        Metric contract (pinned by tests/test_obs.py): a stream failure is
        counted exactly once, under the boundary where it happened —

        * ``fleet/submit_rejected_total`` + ``fleet/submit_rejected/<Exc>``:
          validation failures at the engine's submit boundary (direct
          ``submit`` and ``admit`` drains route here, once; the ingest
          queue's enqueue-time rejections count under
          ``fleet/ingest_rejected/*`` instead — the stream never reaches
          the engine).
        * ``fleet/quarantined_total`` + ``fleet/quarantined/<kind>``: ONLY
          streams evicted mid-flight by ``_poison_reason`` — never
          boundary rejections.
        * ``fleet/admit_rejected_total``: how many streams ``admit()``
          dropped from its pending list — a disposition count that overlaps
          ``fleet/submit_rejected_total`` by design (same event, admission
          view), NOT the quarantine counters.
        """
        m = self.obs
        m.inc("fleet/quarantined_total")
        m.inc(f"fleet/quarantined/{kind}")

    @staticmethod
    def _reason_kind(reason: str) -> str:
        """Collapse a free-text quarantine reason (``_poison_reason`` embeds
        shapes/dtypes) to a stable metric-key slug."""
        for prefix, kind in (("qxs dtype", "qxs_dtype"),
                             ("qxs shape", "qxs_shape"),
                             ("cursor", "cursor"),
                             ("h_seq", "h_seq")):
            if reason.startswith(prefix):
                return kind
        return "other"

    # --- scheduling ---------------------------------------------------------

    def free_slots(self) -> list[int]:
        return [s for s in range(self.slots) if s not in self.active]

    def slot_to_shard(self, slot: int) -> int:
        """The mesh data-axis index that owns ``slot``'s state block — a pure
        function of the slot number (the placement invariant: a stream's
        ``h``/``c`` carry never changes device while it is active)."""
        if not 0 <= slot < self.slots:
            raise ValueError(f"slot {slot} out of range [0, {self.slots})")
        return slot * self.n_shards // self.slots

    def _state_init(self, rid: int, s0, name: str) -> np.ndarray:
        """Normalise a stream's initial state to ``(L, H)`` (zeros default;
        ``(H,)`` accepted as layer 0 of a single-layer engine)."""
        if s0 is None:
            return np.zeros((self.n_layers, self.n_h), np.int32)
        s0 = np.asarray(s0)
        if not np.issubdtype(s0.dtype, np.integer):
            # float state would smuggle NaN/rounding into the integer carry
            raise TypeError(
                f"stream {rid}: {name} must be integer fixed point "
                f"(quantise with repro.core.fxp.quantize first), got {s0.dtype}")
        s0 = s0.astype(np.int32)
        if s0.shape == (self.n_h,) and self.n_layers == 1:
            return s0[None]
        if s0.shape != (self.n_layers, self.n_h):
            raise ValueError(
                f"stream {rid}: {name} must be ({self.n_layers}, {self.n_h}) "
                f"(or ({self.n_h},) for a single-layer engine), got {s0.shape}")
        return s0

    def submit(self, stream: SensorStream) -> bool:
        """Claim a slot for ``stream`` (mid-flight join); False if full.

        Malformed streams raise immediately — before the free-slot check —
        so a bad request can't hide in the queue until a slot frees up:
        wrong dtype (TypeError), non-finite values, wrong ndim/feature
        width, empty streams and values outside the engine's fixed-point
        range all reject at this boundary instead of surfacing as an opaque
        failure deep inside the Pallas kernel.
        """
        m = self.obs
        m.inc("fleet/submit_total")
        with m.time("fleet/submit_us"):
            try:
                ok = self._submit_inner(stream)
            except (TypeError, ValueError) as e:
                m.inc("fleet/submit_rejected_total")
                m.inc(f"fleet/submit_rejected/{type(e).__name__}")
                raise
        if ok:
            m.inc("fleet/admitted_total")
            m.gauge("fleet/slot_occupancy", len(self.active) / self.slots)
        else:
            m.inc("fleet/submit_full_total")
        return ok

    def validate_stream(self, stream: SensorStream):
        """Validate ``stream`` at the submit boundary WITHOUT claiming a
        slot, returning the normalised ``(qxs, h0, c0)`` arrays.

        This is the O(validation) part of ``submit`` — dtype/shape/range
        checks plus state normalisation, no device work and no slot claim —
        factored out so the ingest layer (``repro.serving.ingest``) can
        reject malformed streams at enqueue time, long before a slot frees
        up.  Raises TypeError/ValueError exactly like ``submit``; does not
        mutate the stream.
        """
        qxs = np.asarray(stream.qxs)
        if not np.issubdtype(qxs.dtype, np.integer):
            if np.issubdtype(qxs.dtype, np.floating) \
                    and not np.isfinite(qxs).all():
                raise ValueError(
                    f"stream {stream.rid}: non-finite input (NaN/Inf) — a "
                    "poisoned sensor reading must be dropped by the caller, "
                    "not quantised")
            raise TypeError(
                f"stream {stream.rid}: inputs must be integer fixed point "
                f"(quantise with repro.core.fxp.quantize first), got {qxs.dtype}")
        if qxs.ndim != 2 or qxs.shape[1] != self.n_in:
            raise ValueError(f"stream {stream.rid}: want (T, {self.n_in}) "
                             f"int32 inputs, got {qxs.shape}")
        if len(qxs) == 0:
            raise ValueError(f"stream {stream.rid}: empty stream")
        in_fmt = self.in_fmt
        if qxs.size and (qxs.min() < in_fmt.qmin or qxs.max() > in_fmt.qmax):
            # int32 would happily wrap what the y-bit datapath saturates;
            # out-of-range codes mean the producer quantised to a DIFFERENT
            # format, so the outputs would be silently wrong — reject
            raise ValueError(
                f"stream {stream.rid}: inputs exceed the "
                f"({in_fmt.frac_bits},{in_fmt.total_bits}) fixed-point "
                f"range [{in_fmt.qmin}, {in_fmt.qmax}]")
        qxs = qxs.astype(np.int32)
        h0 = self._state_init(stream.rid, stream.qh0, "qh0")
        if self._arity == 1:
            if stream.qc0 is not None:
                raise ValueError(
                    f"stream {stream.rid}: qc0 must be None on a GRU engine "
                    "(the GRU carries a single hidden state)")
            c0 = None
        else:
            c0 = self._state_init(stream.rid, stream.qc0, "qc0")
        return qxs, h0, c0

    def _submit_inner(self, stream: SensorStream) -> bool:
        qxs, h0, c0 = self.validate_stream(stream)
        free = self.free_slots()
        if not free:
            return False
        slot = free[0]
        stream.qxs = qxs
        stream.cursor = 0
        stream.h_seq = np.zeros((len(qxs), self.n_h), np.int32)
        self._qh = self._qh.at[:, slot].set(jnp.asarray(h0))
        if c0 is not None:
            self._qc = self._qc.at[:, slot].set(jnp.asarray(c0))
        if self._state_sharding is not None:
            # keep the carry pinned to the block partition so the joining
            # stream's state lands on (and stays on) slot_to_shard(slot)
            self._qh = jax.device_put(self._qh, self._state_sharding)
            if self._qc is not None:
                self._qc = jax.device_put(self._qc, self._state_sharding)
        self.active[slot] = stream
        return True

    def _pick_t_step(self) -> int:
        shortest = min(s.remaining for s in self.active.values())
        for b in self._buckets:
            if b <= shortest:
                return b
        return 1  # unreachable: buckets always contain 1

    def _poison_reason(self, s: SensorStream) -> str | None:
        """Did the caller corrupt an admitted stream's buffers under us?
        (Value corruption can't crash the integer datapath; shape/dtype
        corruption would crash the whole batch — catch it per stream.)"""
        qxs = np.asarray(s.qxs)
        if not np.issubdtype(qxs.dtype, np.integer):
            return f"qxs dtype corrupted to {qxs.dtype}"
        if qxs.ndim != 2 or qxs.shape[1] != self.n_in:
            return f"qxs shape corrupted to {qxs.shape}"
        if not 0 <= s.cursor < len(qxs):
            return f"cursor {s.cursor} outside stream of {len(qxs)} steps"
        if s.h_seq is None or s.h_seq.shape != (len(qxs), self.n_h):
            return "h_seq output buffer corrupted"
        return None

    def _quarantine(self, slot: int, reason: str) -> None:
        """Fail ONE stream without touching the rest of the batch: its lane
        just goes back to masked (masked lanes never influence occupied
        lanes' bits, so the survivors' integers are untouched)."""
        s = self.active.pop(slot)
        s.error = reason
        self.quarantined.append(s)
        self._count_quarantine(self._reason_kind(reason))

    def admit(self, pending: list) -> None:
        """Drain ``pending`` (in place) into free slots, quarantining
        malformed streams instead of raising — the graceful bulk-admission
        face of ``submit`` (one poison request must not kill the fleet).

        A rejected stream is counted ONCE, by ``submit``'s boundary
        counters (``fleet/submit_rejected/*``); admit only adds
        ``fleet/admit_rejected_total`` (its own disposition count) and
        never touches the quarantine counters, which are reserved for
        mid-flight corruption (see ``_count_quarantine``)."""
        m = self.obs
        m.gauge("fleet/admit_queue_depth", len(pending))
        try:
            while pending:
                try:
                    if not self.submit(pending[0]):
                        return                  # engine full: keep the rest
                except (TypeError, ValueError) as e:
                    bad = pending.pop(0)
                    bad.error = f"{type(e).__name__}: {e}"
                    self.quarantined.append(bad)
                    m.inc("fleet/admit_rejected_total")
                    continue
                pending.pop(0)
        finally:
            m.gauge("fleet/admit_queue_depth", len(pending))

    def step(self) -> None:
        """One batched kernel call: advance every active slot ``t_step``.

        Instrumented (no-op while observability is disabled): counts/timers
        only — nothing here reads or converts the traced arrays, so the
        integers are identical with metrics and tracing fully enabled.
        """
        m = self.obs
        tr = obs_trace.get_tracer()
        with tr.span("fleet/step", active=len(self.active)):
            for slot in list(self.active):
                reason = self._poison_reason(self.active[slot])
                if reason is not None:
                    self._quarantine(slot, reason)
            if not self.active:
                return
            t_step = self._pick_t_step()
            m.gauge("fleet/slot_occupancy", len(self.active) / self.slots)
            # t_step buckets are a deterministic function of the schedule —
            # edges at the power-of-two buckets the jit specialises on
            m.observe("fleet/t_step", t_step,
                      edges=[float(b) for b in sorted(self._buckets)])
            x = np.zeros((self.slots, t_step, self.n_in), np.int32)
            mask = np.zeros((self.slots,), bool)
            for slot, s in self.active.items():
                x[slot] = s.qxs[s.cursor : s.cursor + t_step]
                mask[slot] = True

            # fleet/step_us times the dispatch only (jax is async; the
            # np.asarray below is where the host blocks on the result)
            with m.time("fleet/step_us"), \
                    tr.span("fleet/kernel", t_step=t_step,
                            backend=self.backend):
                if self._arity == 1:
                    seq, self._qh = self._step(
                        self._ws, self._bs, jnp.asarray(x), self._qh,
                        jnp.asarray(mask))
                else:
                    seq, self._qh, self._qc = self._step(
                        self._ws, self._bs, jnp.asarray(x), self._qh, self._qc,
                        jnp.asarray(mask))
            self.steps_run += 1
            self.timesteps_run += t_step
            m.inc("fleet/steps_total")
            m.inc("fleet/timesteps_total", t_step)

        seq_np = np.asarray(seq)
        finished = []
        for slot, s in self.active.items():
            s.h_seq[s.cursor : s.cursor + t_step] = seq_np[slot]
            s.cursor += t_step
            if s.remaining == 0:
                finished.append(slot)
        if finished:
            qh_np = np.asarray(self._qh)
            qc_np = None if self._qc is None else np.asarray(self._qc)
            for slot in finished:
                s = self.active.pop(slot)   # slot freed for the next submit
                if self.n_layers == 1:      # back-compat: (H,) for one layer
                    s.qh = qh_np[0, slot].copy()
                    s.qc = None if qc_np is None else qc_np[0, slot].copy()
                else:
                    s.qh = qh_np[:, slot].copy()
                    s.qc = None if qc_np is None else qc_np[:, slot].copy()
                s.done = True
            # freed slots must show immediately: between steps the gauge is
            # the live occupancy, not the pre-kernel batch size
            m.gauge("fleet/slot_occupancy", len(self.active) / self.slots)

    def run(self, streams: list[SensorStream]) -> list[SensorStream]:
        """Drive ``streams`` to completion with continuous batching.

        Streams beyond ``batch_slots`` queue and join as slots free up; the
        per-stream results (``h_seq``, ``qh``, ``qc`` — all layers) are
        bit-identical to ``lstm_forward(..., backend="pallas_fxp",
        return_state="all")`` on each stream alone.
        """
        pending = list(streams)
        while pending or self.active:
            self.admit(pending)
            self.step()
        return streams

    # --- checkpoint/restore of serving state --------------------------------

    def params_checksum(self) -> str:
        """sha256 over the quantised weights/biases: a restored fleet must
        resume onto the SAME integers or the continuation contract is void."""
        h = hashlib.sha256()
        for arr in (*self._ws, *self._bs):
            a = np.asarray(jax.device_get(arr))
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
        return h.hexdigest()

    def checkpoint_payload(self) -> tuple[dict, dict]:
        """``(tree, extra)`` for ``repro.checkpoint``: the array pytree
        (state carry + per-stream buffers, see checkpoint.py's serving-state
        layout) and the JSON side-car (slot table, geometry, counters)."""
        streams: dict[str, dict] = {}
        table: dict[str, dict] = {}
        for slot, s in self.active.items():
            leaf = {"qxs": np.asarray(s.qxs, np.int32),
                    "h_seq": np.asarray(s.h_seq, np.int32)}
            if s.qh0 is not None:
                leaf["qh0"] = np.asarray(s.qh0, np.int32)
            if s.qc0 is not None:
                leaf["qc0"] = np.asarray(s.qc0, np.int32)
            streams[str(slot)] = leaf
            table[str(slot)] = {"rid": s.rid, "cursor": s.cursor}
        tree = {"qh": self._qh, "streams": streams}
        if self._qc is not None:
            tree["qc"] = self._qc
        extra = {
            "kind": "sensor_fleet",
            "engine": {
                "cell": self.cell,
                "n_layers": self.n_layers, "n_in": self.n_in,
                "n_h": self.n_h, "batch_slots": self.slots,
                "chunk": self.chunk, "time_tile": self.time_tile,
                "backend": self.backend,
                "fmt": fxp_mod.fmt_to_dict(self.fmt),
                "params_sha256": self.params_checksum(),
            },
            "slot_table": table,
            # steps_run/timesteps_run stay as first-class keys (pre-ISSUE-9
            # checkpoints only have those); the full registry snapshot rides
            # alongside so ALL counters/histograms survive kill -> restore
            "counters": {"steps_run": self.steps_run,
                         "timesteps_run": self.timesteps_run,
                         "metrics": self.obs.snapshot()},
        }
        return tree, extra

    def save(self, manager, step: int | None = None, *, mode: str = "sync",
             attempts: int = 3, base_delay: float = 0.05,
             sleep=time.sleep, payload: tuple | None = None) -> int:
        """Checkpoint the in-flight serving state through ``manager``
        (``repro.checkpoint.CheckpointManager``: atomic tmp-rename writes,
        manifest validation).

        ``mode="async"`` snapshots device→host now and writes in a
        background thread, so the next ``step()`` never waits on disk; the
        synchronous path rides a bounded retry-with-backoff
        (``serving.faults.retry_io``) so one flaky I/O burst doesn't drop
        the fleet.  Returns the step number written.

        ``payload=`` overrides the ``(tree, extra)`` written — wrappers
        that extend the serving state (``IngestQueue`` rides its in-queue
        streams alongside) reuse the same retry/async/metrics machinery.
        """
        from repro.serving.faults import retry_io

        m = self.obs
        tr = obs_trace.get_tracer()
        step = self.steps_run if step is None else step
        with m.time("fleet/ckpt_save_us"), tr.span("fleet/ckpt_save",
                                                   step=step, mode=mode):
            tree, extra = (self.checkpoint_payload() if payload is None
                           else payload)
            if mode == "async":
                manager.save_async(step, tree, extra=extra)
            elif mode == "sync":
                retry_io(lambda: manager.save(step, tree, extra=extra),
                         attempts=attempts, base_delay=base_delay, sleep=sleep)
            else:
                raise ValueError(
                    f"mode must be 'sync' or 'async', got {mode!r}")
        m.inc("fleet/ckpt_saves_total")
        if m.enabled:
            # nbytes is metadata — no device->host transfer happens here
            m.inc("fleet/ckpt_payload_bytes", sum(
                getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(tree)))
        return step

    @classmethod
    def restore(cls, manager, qparams, fmt: FxpFormat | StackFormats,
                luts: dict | None = None,
                *, step: int | None = None, mesh=None,
                shard_slots: bool | None = None, data_axis: str = "data",
                backend: str | None = None, chunk: int | None = None,
                time_tile: int | None = None, block_b: int | None = None,
                interpret: bool | None = None,
                strict_params: bool = True,
                metrics=None) -> "SensorFleetEngine":
        """Rebuild a fleet from its latest (or ``step``-th) checkpoint and
        continue every in-flight stream bit-identically.

        Elastic by construction: pass whatever ``mesh`` the devices alive
        NOW support (D′ may differ from the saving fleet's D, including
        D′ = 1) — the carry is stored gathered and slot→device placement is
        a pure function of the slot index, so the same slot blocks simply
        re-partition onto the new mesh.  ``backend``/``chunk``/``time_tile``
        default to the checkpointed engine's values.  ``strict_params``
        verifies the quantised params' sha256 against the checkpoint —
        different weights cannot produce an integer-identical continuation,
        so a mismatch raises instead of silently serving garbage.

        ``metrics=`` installs a per-engine registry on the restored fleet;
        either way the checkpointed registry snapshot (if any) is loaded
        back, so counters resume cumulative rather than from zero.
        """
        m_restore = (metrics if metrics is not None
                     else obs_metrics.get_registry())
        with m_restore.time("fleet/ckpt_restore_us"), \
                obs_trace.get_tracer().span("fleet/ckpt_restore"):
            eng = cls._restore_inner(
                manager, qparams, fmt, luts, step=step, mesh=mesh,
                shard_slots=shard_slots, data_axis=data_axis, backend=backend,
                chunk=chunk, time_tile=time_tile, block_b=block_b,
                interpret=interpret, strict_params=strict_params,
                metrics=metrics)
        m_restore.inc("fleet/ckpt_restores_total")
        return eng

    @classmethod
    def _restore_inner(cls, manager, qparams, fmt, luts=None,
                       *, step, mesh, shard_slots, data_axis, backend, chunk,
                       time_tile, block_b, interpret, strict_params,
                       metrics) -> "SensorFleetEngine":
        manager.wait()
        manager.sweep_orphans()         # torn tmp dirs from a crash mid-save
        step = manager.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no valid checkpoints under {manager.root}")
        manifest = manager.manifest(step)
        extra = manifest["extra"]
        if extra.get("kind") != "sensor_fleet":
            raise ValueError(
                f"step_{step} is not a SensorFleetEngine checkpoint "
                f"(kind={extra.get('kind')!r})")
        cfg = extra["engine"]
        if fxp_mod.fmt_to_dict(fmt) != cfg["fmt"]:
            raise ValueError(
                f"restore fmt {fxp_mod.fmt_to_dict(fmt)} != checkpointed "
                f"{cfg['fmt']} — the integer codes would mean different values")
        eng = cls(qparams, fmt, luts,
                  batch_slots=cfg["batch_slots"],
                  chunk=cfg["chunk"] if chunk is None else chunk,
                  time_tile=cfg.get("time_tile") if time_tile is None else time_tile,
                  backend=cfg.get("backend", "pallas_fxp") if backend is None
                  else backend,
                  block_b=block_b, interpret=interpret, mesh=mesh,
                  shard_slots=shard_slots, data_axis=data_axis,
                  metrics=metrics)
        ckpt_cell = cfg.get("cell", "lstm")   # pre-GRU checkpoints are LSTM
        if eng.cell != ckpt_cell:
            raise ValueError(
                f"qparams are a {eng.cell!r} stack but the checkpoint was "
                f"saved by a {ckpt_cell!r} fleet — the state geometry and "
                "integer semantics differ")
        if (eng.n_layers, eng.n_in, eng.n_h) != (cfg["n_layers"], cfg["n_in"],
                                                 cfg["n_h"]):
            raise ValueError(
                f"qparams geometry (L={eng.n_layers}, n_in={eng.n_in}, "
                f"H={eng.n_h}) != checkpointed (L={cfg['n_layers']}, "
                f"n_in={cfg['n_in']}, H={cfg['n_h']})")
        if strict_params and eng.params_checksum() != cfg["params_sha256"]:
            raise ValueError(
                "quantised params differ from the checkpointed fleet's — "
                "in-flight streams cannot continue bit-identically "
                "(pass strict_params=False to override)")

        # template from the manifest's own leaf inventory, then the
        # validated payload (restore_pytree re-checks shapes + checksum)
        template: dict = {}
        for name, info in manifest["leaves"].items():
            parts = name.split("/")
            d = template
            for p in parts[:-1]:
                d = d.setdefault(p, {})
            d[parts[-1]] = np.zeros(info["shape"], info["dtype"])
        tree, _, _ = manager.restore(template, step=step)

        eng._qh = jnp.asarray(np.asarray(tree["qh"]), jnp.int32)
        if eng._arity == 2:
            eng._qc = jnp.asarray(np.asarray(tree["qc"]), jnp.int32)
        if eng._state_sharding is not None:
            # elastic resharding: the SAME gathered carry, block-partitioned
            # onto the new mesh by the slot->device placement function
            eng._qh = jax.device_put(eng._qh, eng._state_sharding)
            if eng._qc is not None:
                eng._qc = jax.device_put(eng._qc, eng._state_sharding)
        for slot_str, meta in extra["slot_table"].items():
            leaf = tree.get("streams", {})[slot_str]
            # np.array (not asarray): npz-restored buffers arrive read-only
            # and h_seq keeps being written as chunks land
            s = SensorStream(rid=int(meta["rid"]),
                             qxs=np.array(leaf["qxs"], np.int32))
            s.cursor = int(meta["cursor"])
            s.h_seq = np.array(leaf["h_seq"], np.int32)
            if "qh0" in leaf:
                s.qh0 = np.array(leaf["qh0"], np.int32)
            if "qc0" in leaf:
                s.qc0 = np.array(leaf["qc0"], np.int32)
            eng.active[int(slot_str)] = s
        counters = extra.get("counters", {})
        eng.steps_run = int(counters.get("steps_run", 0))
        eng.timesteps_run = int(counters.get("timesteps_run", 0))
        msnap = counters.get("metrics")
        if msnap:
            # merge, not load: the resumed process keeps what it already
            # recorded (this restore's own timing) on top of the saved counts
            eng.obs.merge_snapshot(msnap)
        return eng
