"""The paper's LSTM cell — sequential baseline and throughput-optimised form.

Paper equations (§3.1, standard LSTM; ``*`` = Hadamard, ``[h, x]`` = concat):

    f_t = sigmoid(W_f [h_{t-1}, x_t] + b_f)          (3.1)
    i_t = sigmoid(W_i [h_{t-1}, x_t] + b_i)          (3.2)
    g_t = tanh   (W_g [h_{t-1}, x_t] + b_g)          (3.3)
    C_t = f_t * C_{t-1} + i_t * g_t                  (3.4)
    h_t = o_t * tanh(C_t)                            (3.5)
    o_t = sigmoid(W_o [h_{t-1}, x_t] + b_o)          (3.6)

Three functionally-identical cell implementations live here:

* ``lstm_cell_sequential`` — four *separate* gate mat-vecs executed one after
  another; this mirrors the FPGA baseline the paper's Fig. 3 profiles (and is
  the numerical oracle for everything else).
* ``lstm_cell_fused`` — the paper's optimisation C1+C2 adapted to TPU: the
  four gate weight matrices are stacked into one ``(n_i+n_h, 4 n_h)`` operand
  so a single MXU matmul computes all four gates "in parallel", and the
  elementwise state update (3.4)/(3.5) fuses behind it.
* ``lstm_cell_fxp`` — the full quantised inference path: ``(x, y)`` fixed
  point (C4) + shared LUT activations (C3), exactly the arithmetic the
  bitstream executes.

Gate order everywhere is ``i, f, g, o`` along the stacked ``4*n_h`` axis
(``r, z, n`` along ``3*n_h`` for the GRU siblings — see
``repro.core.cell``).  Weights act on ``[x_t, h_{t-1}]`` (input features
first, then hidden).

Backend matrix
--------------

``recurrent_forward(spec, params, xs, backend=...)`` is the single
cell-generic entry point every workload (models, examples, benchmarks)
selects a datapath through; ``lstm_forward`` / ``gru_forward`` are its
per-cell faces.  The backend registry ``RECURRENT_BACKENDS`` (==
``LSTM_BACKENDS``) is shared by every cell, and every backend serves both
cell kinds (LSTM and GRU).  One implementation per role:

======================  ===============  ==============================  =========================
backend                 role             executes                        exactness contract
======================  ===============  ==============================  =========================
``"sequential"``        float oracle     separate gate mat-vecs,         numerical oracle for the
                                         ``lax.scan`` over t             float path (Fig. 3
                                                                         baseline schedule)
``"fused"``             float train      1 stacked matmul/step (C1+C2),  allclose to sequential
                        path             ``lax.scan`` over t             (same float ops, fused)
``"fxp"``               fixed-point      ``lstm_layer_fxp`` /            THE bitstream spec:
                        oracle           ``gru_layer_fxp`` — bit-level   quantised arithmetic,
                                         ``(x, y)`` simulator,           LUT activations
                                         ``lax.scan`` over t
``"pallas_fxp"``        served kernel    ``_rnn_seq_fxp_call`` — C1–C5   *integer-equal* to
                                         for all layers in one kernel,   ``"fxp"`` (and to the
                                         int32 state resident in VMEM    ``ref.*_sequence_fxp_ref``
                                                                         oracles)
======================  ===============  ==============================  =========================

When to use which: train with ``"fused"`` (differentiable, fast on any
backend) and check it against ``"sequential"`` (the Fig. 3 schedule);
validate quantisation with ``"fxp"`` (the readable spec); serve the quantised
model with ``"pallas_fxp"`` (the paper's measured datapath — throughput path,
O(1) HBM traffic in sequence length).  Float backends take float ``xs``; fxp
backends take int32 ``xs`` already quantised to ``fmt`` (plus optional
``luts`` from ``repro.core.lut.make_lut_pair``).  Any other backend name
raises ``ValueError``.

``time_tile`` (``"pallas_fxp"`` only): by default the kernel stages the whole
``(block_b, n_seq, n_in)`` input in one VMEM block, which bounds ``n_seq``.
``time_tile=tt`` streams the sequence through VMEM in double-buffered
``tt``-step chunks with ``h``/``c`` carried across chunks in VMEM scratch —
``n_seq`` becomes unbounded and the result stays integer-equal to ``"fxp"``
(ragged tails are masked in-kernel).  Cross-backend equivalence, including
the tiled path at ``n_seq >> time_tile``, is locked down by
``tests/test_backend_equiv.py`` and the golden fixtures in ``tests/golden/``.

Multi-layer state (``return_state``): ``lstm_forward(...,
return_state="all")`` returns EVERY layer's final ``(h, c)`` as per-layer
lists (default ``"top"`` keeps the historical top-layer pair), and
``h0``/``c0`` accept per-layer lists or a stacked ``(L, ...)`` array — so a
chunked continuation of a *stacked* LSTM is exact on every backend.  On
``"pallas_fxp"``, EVERY call — one layer or a stack — runs ONE kernel
(``lstm_sequence_fxp_stack_pallas``) — heterogeneous hidden sizes are padded
to ``max_l H_l`` with in-kernel lane masking, so there is no layer-by-layer
fallback: the per-step loop chains the layers, keeping the inter-layer
hidden sequence in VMEM instead of bouncing it through HBM between layers.

Mixed precision: the fxp backends take ``fmt`` as a plain ``FxpFormat`` (one
global format, the paper's configuration), a ``LayerFormats`` (per-gate
pre-activation formats inside one layer) or a ``StackFormats`` (per-layer
data formats + per-gate formats).  ``"fxp"`` is the readable per-gate-format
oracle (``lstm_cell_fxp`` with per-gate rescale shifts, ``fxp_convert``
between layers); ``"pallas_fxp"`` executes the identical arithmetic with the
shifts baked in as static kernel constants — integer-equal, locked by
``tests/golden/lstm_mixed_golden.json``.

Fleet serving: ``repro.serving.lstm_engine.SensorFleetEngine`` continuously
batches many independent sensor streams — single-layer or stacked (state
``(L, slots, H)``, carried via ``return_state="all"``) — through
``lstm_forward(..., backend="pallas_fxp")`` with per-slot ``h0``/``c0``
carry, bit-identical to running each stream alone
(``tests/test_serving.py``).

Sharded batches: every backend of ``lstm_forward`` is *batch-pure* — no op
mixes rows of the leading batch axis (the recurrence runs along time, the
matmuls contract the feature axis) — so the whole dispatcher is a valid
per-device body for a ``shard_map`` whose specs shard only the batch dim:
each device traces the same kernel on its local ``(B/D, n_seq, n_in)``
block, no collectives appear, and no host round-trip interposes between the
sharded input and the kernel.  The fleet engine leans on this to shard its
slot axis over a mesh ``data`` axis (``SensorFleetEngine(mesh=...)``, specs
from ``repro.parallel.sharding.fleet_slot_specs``) while staying
integer-equal to single-device serving; the slot→device placement invariant
(slot ``s`` of ``S`` lives on device ``s * D // S`` for the engine's
lifetime, so a stream's ``h``/``c`` carry never crosses devices over
join/leave churn) is proven on forced host devices by
``tests/spmd_scripts/check_sharded_fleet.py`` against the golden schedule in
``tests/golden/lstm_fleet_sharded_golden.json``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import fxp as fxp_mod
from repro.core import lut as lut_mod
from repro.core.cell import (CELL_SPECS, GRU_CELL, LSTM_CELL, CellSpec,
                             GRUParams, cell_spec)
from repro.core.fxp import FxpFormat
from repro.obs.metrics import get_registry as _obs_metrics

__all__ = [
    "LSTMParams",
    "GRUParams",
    "init_lstm_params",
    "init_gru_params",
    "init_recurrent_params",
    "split_gate_params",
    "lstm_cell_sequential",
    "lstm_cell_fused",
    "lstm_cell_fxp",
    "gru_cell_sequential",
    "gru_cell_fused",
    "gru_cell_fxp",
    "lstm_layer",
    "lstm_layer_fxp",
    "gru_layer",
    "gru_layer_fxp",
    "lstm_forward",
    "gru_forward",
    "recurrent_forward",
    "LSTM_BACKENDS",
    "RECURRENT_BACKENDS",
]

GATE_ORDER = ("i", "f", "g", "o")


@dataclasses.dataclass
class LSTMParams:
    """Stacked-gate parameters: ``w: (n_in + n_h, 4*n_h)``, ``b: (4*n_h,)``."""

    w: jax.Array
    b: jax.Array

    @property
    def hidden_size(self) -> int:
        return self.w.shape[1] // 4

    @property
    def input_size(self) -> int:
        return self.w.shape[0] - self.hidden_size

    def tree_flatten(self):  # pragma: no cover - registered below
        return (self.w, self.b), None

    @classmethod
    def tree_unflatten(cls, aux, children):  # pragma: no cover
        return cls(*children)


jax.tree_util.register_pytree_node(
    LSTMParams, LSTMParams.tree_flatten, LSTMParams.tree_unflatten
)


def init_lstm_params(
    key: jax.Array, input_size: int, hidden_size: int, dtype=jnp.float32,
    forget_bias: float = 1.0,
) -> LSTMParams:
    """Glorot-uniform weights; forget-gate bias initialised to +1 (standard)."""
    k_w, _ = jax.random.split(key)
    fan_in = input_size + hidden_size
    fan_out = 4 * hidden_size
    limit = (6.0 / (fan_in + fan_out)) ** 0.5
    w = jax.random.uniform(k_w, (fan_in, fan_out), dtype, -limit, limit)
    b = jnp.zeros((fan_out,), dtype)
    # gate order i, f, g, o -> forget block is [h : 2h)
    b = b.at[hidden_size : 2 * hidden_size].set(forget_bias)
    return LSTMParams(w=w, b=b)


def init_gru_params(
    key: jax.Array, input_size: int, hidden_size: int, dtype=jnp.float32,
) -> GRUParams:
    """Glorot-uniform stacked GRU weights (gate order ``r, z, n``), zero
    bias — the GRU has no forget-bias analogue worth seeding."""
    k_w, _ = jax.random.split(key)
    fan_in = input_size + hidden_size
    fan_out = 3 * hidden_size
    limit = (6.0 / (fan_in + fan_out)) ** 0.5
    w = jax.random.uniform(k_w, (fan_in, fan_out), dtype, -limit, limit)
    return GRUParams(w=w, b=jnp.zeros((fan_out,), dtype))


def init_recurrent_params(spec: "CellSpec | str", key: jax.Array,
                          input_size: int, hidden_size: int, dtype=jnp.float32):
    """Cell-generic init: the ``CellSpec`` picks the params class and gate
    arity (``LSTMParams`` for ``"lstm"``, ``GRUParams`` for ``"gru"``)."""
    spec = cell_spec(spec)
    if spec.kind == "lstm":
        return init_lstm_params(key, input_size, hidden_size, dtype)
    if spec.kind == "gru":
        return init_gru_params(key, input_size, hidden_size, dtype)
    raise ValueError(f"no param init registered for cell {spec.kind!r}")


def split_gate_params(params: LSTMParams) -> dict[str, tuple[jax.Array, jax.Array]]:
    """Unstack into the four per-gate ``(w, b)`` pairs (the FPGA view: one
    weight memory placed next to each ALU)."""
    h = params.hidden_size
    out = {}
    for k, name in enumerate(GATE_ORDER):
        sl = slice(k * h, (k + 1) * h)
        out[name] = (params.w[:, sl], params.b[sl])
    return out


# ---------------------------------------------------------------------------
# Float cells
# ---------------------------------------------------------------------------


def lstm_cell_sequential(
    params: LSTMParams, x_t: jax.Array, h: jax.Array, c: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Baseline cell: the four gate mat-vecs issued as four separate matmuls,
    then the elementwise update strictly afterwards — the schedule the paper's
    Fig. 3 shows is 97.1 % bound on (3.1)-(3.3),(3.6)."""
    gates = split_gate_params(params)
    xh = jnp.concatenate([x_t, h], axis=-1)
    i_t = jax.nn.sigmoid(xh @ gates["i"][0] + gates["i"][1])
    f_t = jax.nn.sigmoid(xh @ gates["f"][0] + gates["f"][1])
    g_t = jnp.tanh(xh @ gates["g"][0] + gates["g"][1])
    o_t = jax.nn.sigmoid(xh @ gates["o"][0] + gates["o"][1])
    c_t = f_t * c + i_t * g_t
    h_t = o_t * jnp.tanh(c_t)
    return h_t, c_t


def lstm_cell_fused(
    params: LSTMParams,
    x_t: jax.Array,
    h: jax.Array,
    c: jax.Array,
    sigmoid_fn: Callable[[jax.Array], jax.Array] = jax.nn.sigmoid,
    tanh_fn: Callable[[jax.Array], jax.Array] = jnp.tanh,
) -> tuple[jax.Array, jax.Array]:
    """Paper-optimised cell (C1+C2): one stacked matmul for all four gates.

    ``sigmoid_fn``/``tanh_fn`` are injectable so the LUT variants (C3) slot in
    without touching the dataflow — mirroring the FPGA design where the LUT
    modules sit behind a shared bus.
    """
    hdim = params.hidden_size
    xh = jnp.concatenate([x_t, h], axis=-1)
    z = xh @ params.w + params.b  # (..., 4h): the single MXU pass
    zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
    i_t = sigmoid_fn(zi)
    f_t = sigmoid_fn(zf)
    g_t = tanh_fn(zg)
    o_t = sigmoid_fn(zo)
    c_t = f_t * c + i_t * g_t
    h_t = o_t * tanh_fn(c_t)
    del hdim
    return h_t, c_t


def gru_cell_sequential(params: GRUParams, x_t: jax.Array, h: jax.Array) -> jax.Array:
    """Baseline GRU cell: the three gate mat-vecs issued separately (the
    per-gate column blocks of the stacked weight; see ``GRU_CELL``)."""
    hdim = params.hidden_size
    xh = jnp.concatenate([x_t, h], axis=-1)
    r_t = jax.nn.sigmoid(xh @ params.w[:, :hdim] + params.b[:hdim])
    z_t = jax.nn.sigmoid(
        xh @ params.w[:, hdim:2 * hdim] + params.b[hdim:2 * hdim])
    xrh = jnp.concatenate([x_t, r_t * h], axis=-1)
    n_t = jnp.tanh(xrh @ params.w[:, 2 * hdim:] + params.b[2 * hdim:])
    return (1.0 - z_t) * n_t + z_t * h


def gru_cell_fused(
    params: GRUParams,
    x_t: jax.Array,
    h: jax.Array,
    sigmoid_fn: Callable[[jax.Array], jax.Array] = jax.nn.sigmoid,
    tanh_fn: Callable[[jax.Array], jax.Array] = jnp.tanh,
) -> jax.Array:
    """C1-style GRU cell: ``r``/``z`` from one stacked matmul over
    ``[x_t, h]``; the candidate ``n`` is the one pass the GRU structure
    forces to wait for ``r`` (its matmul runs over ``[x_t, r_t * h]``)."""
    hdim = params.hidden_size
    xh = jnp.concatenate([x_t, h], axis=-1)
    z_rz = xh @ params.w[:, :2 * hdim] + params.b[:2 * hdim]
    r_t = sigmoid_fn(z_rz[..., :hdim])
    z_t = sigmoid_fn(z_rz[..., hdim:])
    xrh = jnp.concatenate([x_t, r_t * h], axis=-1)
    n_t = tanh_fn(xrh @ params.w[:, 2 * hdim:] + params.b[2 * hdim:])
    return (1.0 - z_t) * n_t + z_t * h


# ---------------------------------------------------------------------------
# Fixed-point + LUT cells (the bitstream-exact inference path)
# ---------------------------------------------------------------------------


def _lut_fxp(table: jax.Array, spec: lut_mod.LutSpec, q: jax.Array, fmt: FxpFormat) -> jax.Array:
    """Apply a LUT to fixed-point inputs, returning fixed point — shared
    semantics in ``core.lut.lut_apply_fxp`` (also the QAT forward's LUT)."""
    return lut_mod.lut_apply_fxp(q, table, spec, fmt)


def _fxp_acts(data: FxpFormat, luts):
    """The shared ``(act_sigmoid, act_tanh)`` pair of the fxp cells: LUT
    activations when ``luts`` is given (C3), full-precision-through-the-grid
    otherwise (the paper's Fig. 6 sweep quantises data but not activations).
    Each takes ``(q, in_fmt)`` and lands the result at the layer's ``data``
    format — identical ops for every cell kind."""
    if luts is None:
        act_sig = lambda q, in_fmt: fxp_mod.quantize(
            jax.nn.sigmoid(fxp_mod.dequantize(q, in_fmt)), data)
        act_tanh = lambda q, in_fmt: fxp_mod.quantize(
            jnp.tanh(fxp_mod.dequantize(q, in_fmt)), data)
    else:
        sig_table, sig_spec = luts["sigmoid"]
        tanh_table, tanh_spec = luts["tanh"]
        act_sig = lambda q, in_fmt: lut_mod.lut_apply_fxp(
            q, sig_table, sig_spec, in_fmt, out_fmt=data)
        act_tanh = lambda q, in_fmt: lut_mod.lut_apply_fxp(
            q, tanh_table, tanh_spec, in_fmt, out_fmt=data)
    return act_sig, act_tanh


def lstm_cell_fxp(
    qparams: LSTMParams,
    qx_t: jax.Array,
    qh: jax.Array,
    qc: jax.Array,
    fmt: "FxpFormat | fxp_mod.LayerFormats",
    luts: dict[str, tuple[jax.Array, lut_mod.LutSpec]] | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Quantised cell: fixed-point matmul (int accumulate + rounding shift),
    shared sigmoid/tanh LUTs.  ``luts=None`` keeps activations full precision
    (the paper's Fig. 6 sweep quantises data but not activations).

    ``fmt`` may be a plain ``FxpFormat`` (one format everywhere — the paper's
    configuration) or a ``LayerFormats``: data/weights/state/activations live
    in ``fmt.data`` while each gate's pre-activation is rescaled straight out
    of the 2x-fractional accumulator into its own ``fmt.gates[g]`` (the FPGA
    view — four ALUs, four shift/saturate constants).  This is THE per-gate
    oracle the mixed-precision Pallas kernel is integer-equal to.
    """
    lf = fmt if isinstance(fmt, fxp_mod.LayerFormats) else fxp_mod.LayerFormats.uniform(fmt)
    data = lf.data
    hdim = qparams.hidden_size
    qxh = jnp.concatenate([qx_t, qh], axis=-1)
    if lf.is_uniform:
        z = fxp_mod.fxp_matmul(qxh, qparams.w, data, bias=qparams.b)
        zs = list(jnp.split(z, 4, axis=-1))
    else:
        # Per-gate column blocks of the stacked matmul have independent int32
        # accumulators, so splitting the matmul is bit-exact — each block's
        # single rounding shift lands in that gate's own format.
        zs = [fxp_mod.fxp_matmul(
                  qxh, qparams.w[:, k * hdim:(k + 1) * hdim], data,
                  bias=qparams.b[k * hdim:(k + 1) * hdim],
                  out_fmt=lf.gates[k])
              for k in range(4)]
    act_sig, act_tanh = _fxp_acts(data, luts)
    i_t = act_sig(zs[0], lf.gates.i)
    f_t = act_sig(zs[1], lf.gates.f)
    g_t = act_tanh(zs[2], lf.gates.g)
    o_t = act_sig(zs[3], lf.gates.o)
    c_t = fxp_mod.fxp_add(fxp_mod.fxp_mul(f_t, qc, data), fxp_mod.fxp_mul(i_t, g_t, data), data)
    h_t = fxp_mod.fxp_mul(o_t, act_tanh(c_t, data), data)
    return h_t, c_t


def gru_cell_fxp(
    qparams: GRUParams,
    qx_t: jax.Array,
    qh: jax.Array,
    fmt: "FxpFormat | fxp_mod.LayerFormats",
    luts: dict[str, tuple[jax.Array, lut_mod.LutSpec]] | None = None,
) -> jax.Array:
    """Quantised GRU cell — the single-state face of the same C1–C4 recipe
    ``lstm_cell_fxp`` pins (and THE integer oracle the fused GRU kernel and
    ``ref.gru_sequence_fxp_ref`` are equal to).  Gate order ``r, z, n``:
    ``r``/``z`` rescale out of the stacked matmul over ``[x, h]`` (per-gate
    formats supported exactly as for LSTM), the candidate's matmul runs over
    ``[x, fxp_mul(r, h)]``, and the state update represents the constant 1
    exactly as ``1 << frac_bits`` on the integer grid:
    ``h' = sat(fxp_mul(sat(one - z), n) + fxp_mul(z, h))``."""
    lf = fmt if isinstance(fmt, fxp_mod.LayerFormats) else fxp_mod.LayerFormats.uniform(fmt)
    data = lf.data
    hdim = qparams.hidden_size
    qxh = jnp.concatenate([qx_t, qh], axis=-1)
    if lf.is_uniform:
        z_rz = fxp_mod.fxp_matmul(qxh, qparams.w[:, :2 * hdim], data,
                                  bias=qparams.b[:2 * hdim])
        zs = [z_rz[..., :hdim], z_rz[..., hdim:]]
    else:
        # Independent per-gate-column accumulators, as in lstm_cell_fxp.
        zs = [fxp_mod.fxp_matmul(
                  qxh, qparams.w[:, k * hdim:(k + 1) * hdim], data,
                  bias=qparams.b[k * hdim:(k + 1) * hdim],
                  out_fmt=lf.gates[k])
              for k in range(2)]
    act_sig, act_tanh = _fxp_acts(data, luts)
    r_t = act_sig(zs[0], lf.gates[0])
    z_t = act_sig(zs[1], lf.gates[1])
    qxrh = jnp.concatenate([qx_t, fxp_mod.fxp_mul(r_t, qh, data)], axis=-1)
    z_n = fxp_mod.fxp_matmul(
        qxrh, qparams.w[:, 2 * hdim:], data, bias=qparams.b[2 * hdim:],
        out_fmt=None if lf.is_uniform else lf.gates[2])
    n_t = act_tanh(z_n, data if lf.is_uniform else lf.gates[2])
    one = jnp.int32(1 << data.frac_bits)
    one_minus_z = fxp_mod.saturate(one - z_t, data)
    return fxp_mod.fxp_add(fxp_mod.fxp_mul(one_minus_z, n_t, data),
                           fxp_mod.fxp_mul(z_t, qh, data), data)


# ---------------------------------------------------------------------------
# Layers: scan over the time dimension
# ---------------------------------------------------------------------------


def lstm_layer(
    params: LSTMParams,
    xs: jax.Array,
    h0: jax.Array | None = None,
    c0: jax.Array | None = None,
    cell: Callable = lstm_cell_fused,
    return_sequence: bool = False,
    **cell_kwargs,
):
    """Run the cell over ``xs: (..., n_seq, n_in)`` via ``lax.scan``.

    The recurrence is inherently sequential in t (paper §3.2: "increasing the
    number of LSTM cells in the LSTM layer cannot help") — throughput comes
    from making each step cheap, which is exactly what the fused cell does.
    """
    n_h = params.hidden_size
    batch_shape = xs.shape[:-2]
    dtype = xs.dtype
    h = h0 if h0 is not None else jnp.zeros((*batch_shape, n_h), dtype)
    c = c0 if c0 is not None else jnp.zeros((*batch_shape, n_h), dtype)

    def step(carry, x_t):
        h, c = carry
        h, c = cell(params, x_t, h, c, **cell_kwargs)
        return (h, c), (h if return_sequence else None)

    xs_t = jnp.moveaxis(xs, -2, 0)  # (n_seq, ..., n_in)
    (h, c), seq = jax.lax.scan(step, (h, c), xs_t)
    if return_sequence:
        return jnp.moveaxis(seq, 0, -2), (h, c)
    return h, c


def lstm_layer_fxp(
    qparams: LSTMParams,
    qxs: jax.Array,
    fmt: "FxpFormat | fxp_mod.LayerFormats",
    luts: dict | None = None,
    qh0: jax.Array | None = None,
    qc0: jax.Array | None = None,
    return_sequence: bool = False,
):
    """Quantised layer scan: int32 state carried step to step (C5: the FPGA
    keeps h/C in the shared BRAM between recursions — here they stay in
    registers/VMEM across the scan)."""
    n_h = qparams.hidden_size
    batch_shape = qxs.shape[:-2]
    qh = qh0 if qh0 is not None else jnp.zeros((*batch_shape, n_h), jnp.int32)
    qc = qc0 if qc0 is not None else jnp.zeros((*batch_shape, n_h), jnp.int32)

    def step(carry, qx_t):
        qh, qc = carry
        qh, qc = lstm_cell_fxp(qparams, qx_t, qh, qc, fmt, luts)
        return (qh, qc), (qh if return_sequence else None)

    qxs_t = jnp.moveaxis(qxs, -2, 0)
    (qh, qc), seq = jax.lax.scan(step, (qh, qc), qxs_t)
    if return_sequence:
        return jnp.moveaxis(seq, 0, -2), (qh, qc)
    return qh, qc


def gru_layer(
    params: GRUParams,
    xs: jax.Array,
    h0: jax.Array | None = None,
    cell: Callable = gru_cell_fused,
    return_sequence: bool = False,
    **cell_kwargs,
):
    """Float GRU over ``xs: (..., n_seq, n_in)`` via ``lax.scan`` — the
    single-state sibling of ``lstm_layer``."""
    n_h = params.hidden_size
    batch_shape = xs.shape[:-2]
    h = h0 if h0 is not None else jnp.zeros((*batch_shape, n_h), xs.dtype)

    def step(h, x_t):
        h = cell(params, x_t, h, **cell_kwargs)
        return h, (h if return_sequence else None)

    h, seq = jax.lax.scan(step, h, jnp.moveaxis(xs, -2, 0))
    if return_sequence:
        return jnp.moveaxis(seq, 0, -2), h
    return h


def gru_layer_fxp(
    qparams: GRUParams,
    qxs: jax.Array,
    fmt: "FxpFormat | fxp_mod.LayerFormats",
    luts: dict | None = None,
    qh0: jax.Array | None = None,
    return_sequence: bool = False,
):
    """Quantised GRU layer scan: int32 ``h`` carried step to step (C5), the
    readable oracle the fused GRU stack kernel is integer-equal to."""
    n_h = qparams.hidden_size
    batch_shape = qxs.shape[:-2]
    qh = qh0 if qh0 is not None else jnp.zeros((*batch_shape, n_h), jnp.int32)

    def step(qh, qx_t):
        qh = gru_cell_fxp(qparams, qx_t, qh, fmt, luts)
        return qh, (qh if return_sequence else None)

    qh, seq = jax.lax.scan(step, qh, jnp.moveaxis(qxs, -2, 0))
    if return_sequence:
        return jnp.moveaxis(seq, 0, -2), qh
    return qh


# ---------------------------------------------------------------------------
# Unified dispatcher: one API, four datapaths (see module docstring matrix)
# ---------------------------------------------------------------------------

LSTM_BACKENDS = ("sequential", "fused", "fxp", "pallas_fxp")

# The dispatcher is cell-generic; every cell kind serves every backend.
RECURRENT_BACKENDS = LSTM_BACKENDS

_FXP_BACKENDS = ("fxp", "pallas_fxp")


def _lut_kernel_args(luts: dict | None) -> dict:
    """Unpack a ``make_lut_pair`` dict into the kernel's table/bound kwargs."""
    if luts is None:
        return {}
    sig_table, sig_spec = luts["sigmoid"]
    tanh_table, tanh_spec = luts["tanh"]
    return dict(
        sig_table=sig_table, tanh_table=tanh_table,
        sig_lo=sig_spec.bounds[0], sig_hi=sig_spec.bounds[1],
        tanh_lo=tanh_spec.bounds[0], tanh_hi=tanh_spec.bounds[1],
    )


def _forward_one_layer(spec, p, xs, h0, c0, need_seq, backend, fmt, luts):
    """One layer of one cell kind through one of the ``lax.scan`` backends
    (``"sequential"``, ``"fused"``, ``"fxp"``).  Returns
    ``(h_seq | None, h_T, c_T)`` — ``c_T`` is ``None`` for arity-1 cells."""
    if spec.kind == "gru":
        if backend == "fxp":
            out = gru_layer_fxp(p, xs, fmt, luts, qh0=h0,
                                return_sequence=need_seq)
        else:
            cell = gru_cell_sequential if backend == "sequential" else gru_cell_fused
            out = gru_layer(p, xs, h0, cell=cell, return_sequence=need_seq)
        return (out[0], out[1], None) if need_seq else (None, out, None)

    if backend == "fxp":
        out = lstm_layer_fxp(p, xs, fmt, luts, qh0=h0, qc0=c0,
                             return_sequence=need_seq)
    else:
        cell = lstm_cell_sequential if backend == "sequential" else lstm_cell_fused
        out = lstm_layer(p, xs, h0, c0, cell=cell, return_sequence=need_seq)
    return (out[0], *out[1]) if need_seq else (None, *out)


def recurrent_forward(
    spec: "CellSpec | str",
    params,
    xs: jax.Array,
    *,
    backend: str = "fused",
    fmt: FxpFormat | None = None,
    luts: dict | None = None,
    h0=None,
    c0=None,
    return_sequence: bool = False,
    return_state: str = "top",
    num_layers: int | None = None,
    interpret: bool | None = None,
    block_b: int = 128,
    time_tile: int | None = None,
):
    """Run a (stacked) gated recurrence of cell kind ``spec`` through one of
    the registered backends.  ``lstm_forward`` / ``gru_forward`` are the
    per-cell faces of this dispatcher.

    Parameters
    ----------
    spec : a ``CellSpec`` or registered kind string (``"lstm"`` / ``"gru"``).
    params : the spec's param class (``LSTMParams`` / ``GRUParams``) or a
        list of them (one per stacked layer; layer ``l``'s ``input_size``
        must equal layer ``l-1``'s ``hidden_size`` — hidden sizes may differ
        between layers).  Every ``"pallas_fxp"`` call, one layer or a
        stack, runs as ONE kernel with the inter-layer hidden sequence
        resident in VMEM (``*_sequence_fxp_stack_pallas``, which pads
        heterogeneous ``H`` in-kernel); the other backends run layer by
        layer, where inter-layer traffic is the full hidden-state sequence.
    xs : ``(B, n_seq, n_in)`` or ``(n_seq, n_in)``.  Float for the float
        backends; int32 fixed point (already quantised to layer 0's data
        format) for ``"fxp"``/``"pallas_fxp"``.
    backend : one of ``RECURRENT_BACKENDS`` — see the module docstring
        matrix; any other name raises ``ValueError``.
    fmt, luts : fixed-point format — ``FxpFormat`` (global), ``LayerFormats``
        (per-gate) or ``StackFormats`` (per-layer + per-gate) — plus optional
        ``make_lut_pair`` tables (fxp backends only).
    h0, c0 : initial state — a single ``(B, n_h)`` array (applied to layer 0
        of a single-layer stack), a per-layer list (required for
        heterogeneous-``H`` stacks), or a stacked ``(L, ...)`` array
        (multi-layer, uniform ``H``); default zeros.  ``c0`` is LSTM-only:
        arity-1 cells (GRU) reject a non-``None`` ``c0``.
    return_sequence : also return the top layer's per-step hidden states.
    return_state : ``"top"`` (default) returns the top layer's final state —
        ``(h_T, c_T)`` for LSTM, bare ``h_T`` for GRU; ``"all"`` returns
        per-layer lists (``([h_T^l...], [c_T^l...])`` / ``[h_T^l...]``) so a
        chunked continuation of a *stacked* recurrence is exact: feed the
        lists back as ``h0``/``c0`` of the next chunk and the integers match
        one long call.
    num_layers : optional cross-check against ``len(params)``.
    interpret : ``"pallas_fxp"`` only — Pallas interpret mode; ``None`` =
        auto (compiled on TPU, interpret elsewhere so every backend runs
        everywhere).
    block_b : ``"pallas_fxp"`` only — batch rows per kernel grid step.
    time_tile : ``"pallas_fxp"`` only — stream the sequence through VMEM in
        double-buffered ``time_tile``-step chunks (``None`` = whole sequence
        in one block); integer-equal either way.  See the module docstring.

    Returns the final state (shaped per ``return_state`` / the cell's state
    arity, see above), or ``(h_seq, state)`` when ``return_sequence`` is set
    — the same convention as ``lstm_layer`` / ``gru_layer``.
    """
    spec = cell_spec(spec)
    if backend not in LSTM_BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {LSTM_BACKENDS}")
    if return_state not in ("top", "all"):
        raise ValueError(
            f"return_state must be 'top' or 'all', got {return_state!r}")
    if spec.state_arity == 1 and c0 is not None:
        raise ValueError(
            f"cell kind {spec.kind!r} carries a single hidden state; "
            "c0 must be None")

    layers = list(params) if isinstance(params, (list, tuple)) else [params]
    if num_layers is not None and num_layers != len(layers):
        raise ValueError(f"num_layers={num_layers} but {len(layers)} param sets given")

    # Dispatch counters (ISSUE 9): Python-level dispatches — i.e. trace-time
    # under jit, once per recompile — never per traced step, and never a read
    # of a traced value.
    _m = _obs_metrics()
    if _m.enabled:
        _m.inc("kernel/dispatch_total")
        _m.inc(f"kernel/dispatch/{spec.kind}/{backend}")
        if backend == "pallas_fxp":
            _m.inc(f"kernel/blocks/{backend}/"
                   f"L{len(layers)}_bb{block_b}_tt{time_tile}")

    is_fxp = backend in _FXP_BACKENDS
    stack_fmt = None
    if is_fxp:
        if fmt is None:
            raise ValueError(f"backend {backend!r} needs fmt=FxpFormat(...)")
        if not jnp.issubdtype(jnp.asarray(xs).dtype, jnp.integer):
            raise TypeError(
                f"backend {backend!r} takes int32 fixed-point inputs; "
                "quantise with repro.core.fxp.quantize(xs, fmt) first")
        # Normalise FxpFormat / LayerFormats / StackFormats to one per-layer
        # view; the uniform case is bit-identical to the historical path.
        stack_fmt = fxp_mod.as_stack_formats(fmt, len(layers))

    # The kernel takes a single (B, T, n_in) batch axis; fold extra leading
    # dims into it (and unfold on the way out) so every backend accepts the
    # same (..., n_seq, n_in) inputs.
    xs_ndim = jnp.asarray(xs).ndim      # pre-fold, for state_for's rank check
    squeeze_batch = False
    lead_shape = None
    if backend == "pallas_fxp":
        if xs.ndim == 2:
            xs, squeeze_batch = xs[None], True
        elif xs.ndim > 3:
            lead_shape = xs.shape[:-2]
            xs = xs.reshape(-1, *xs.shape[-2:])
        elif xs.ndim != 3:
            raise ValueError(
                f"backend {backend!r} takes (..., n_seq, n_in) inputs, "
                f"got shape {xs.shape}")

    def state_for(layer_idx, s):
        if s is None:
            return None
        if isinstance(s, (list, tuple)):
            s = s[layer_idx]
        elif len(layers) > 1:
            # A stacked array has one MORE axis than a per-layer state (whose
            # rank matches xs minus the time axis plus H, i.e. xs.ndim - 1),
            # so the rank check keeps a (B, H) single-layer-convention array
            # from being silently mistaken for (L, ...) when B == L.
            s = jnp.asarray(s)
            if s.ndim != xs_ndim or s.shape[0] != len(layers):
                raise ValueError(
                    "multi-layer stacks take per-layer h0/c0 lists or a "
                    f"stacked ({len(layers)}, ..., n_h) array of rank "
                    f"{xs_ndim}, got shape {s.shape}")
            s = s[layer_idx]
        if squeeze_batch:
            return s[None]
        if lead_shape is not None:
            return s.reshape(-1, s.shape[-1])
        return s

    # EVERY pallas_fxp call runs ONE kernel — one layer or a stack, uniform
    # or heterogeneous H, uniform or per-gate/per-layer formats: the per-step
    # loop chains the layers, so the inter-layer hidden-state sequence never
    # bounces through HBM between layers (see kernels/lstm_fxp_seq.py).
    if backend == "pallas_fxp":
        def stacked_state(s):
            if s is None:
                return None
            return [state_for(li, s) for li in range(len(layers))]

        kernel_kwargs = dict(
            formats=stack_fmt,
            return_sequence=return_sequence, block_b=block_b,
            time_tile=time_tile,
            interpret=(jax.default_backend() != "tpu" if interpret is None
                       else interpret),
            **_lut_kernel_args(luts),
        )
        ws, bs = [p.w for p in layers], [p.b for p in layers]
        if spec.state_arity == 1:
            from repro.kernels.lstm_fxp_seq import gru_sequence_fxp_stack_pallas

            out = gru_sequence_fxp_stack_pallas(
                xs, ws, bs, stacked_state(h0), **kernel_kwargs)
            if return_sequence:
                xs, h_all = out
            else:
                h_all = out
            hs, cs = list(h_all), [None] * len(layers)
        else:
            from repro.kernels.lstm_fxp_seq import lstm_sequence_fxp_stack_pallas

            out = lstm_sequence_fxp_stack_pallas(
                xs, ws, bs, stacked_state(h0), stacked_state(c0),
                **kernel_kwargs)
            if return_sequence:
                seq, h_all, c_all = out
                xs = seq
            else:
                h_all, c_all = out
            hs, cs = list(h_all), list(c_all)
    else:
        hs, cs = [], []
        for li, p in enumerate(layers):
            need_seq = return_sequence or li < len(layers) - 1
            seq, h, c = _forward_one_layer(
                spec, p, xs, state_for(li, h0), state_for(li, c0), need_seq,
                backend, None if stack_fmt is None else stack_fmt[li], luts)
            hs.append(h)
            cs.append(c)
            if need_seq:
                xs = seq
                if stack_fmt is not None and li + 1 < len(layers):
                    # Inter-layer requantisation of the oracle path — the
                    # in-kernel static shift of the fused stack (fxp_convert
                    # is a no-op for a uniform stack).
                    xs = fxp_mod.fxp_convert(
                        xs, stack_fmt[li].data, stack_fmt[li + 1].data)

    if squeeze_batch:
        hs = [h[0] for h in hs]
        cs = [c if c is None else c[0] for c in cs]
        xs = xs[0] if return_sequence else xs
    elif lead_shape is not None:
        hs = [h.reshape(*lead_shape, h.shape[-1]) for h in hs]
        cs = [c if c is None else c.reshape(*lead_shape, c.shape[-1])
              for c in cs]
        if return_sequence:
            xs = xs.reshape(*lead_shape, *xs.shape[-2:])
    if spec.state_arity == 1:
        state = hs if return_state == "all" else hs[-1]
    else:
        state = (hs, cs) if return_state == "all" else (hs[-1], cs[-1])
    if return_sequence:
        return xs, state
    return state


def lstm_forward(
    params,
    xs: jax.Array,
    *,
    backend: str = "fused",
    fmt: FxpFormat | None = None,
    luts: dict | None = None,
    h0=None,
    c0=None,
    return_sequence: bool = False,
    return_state: str = "top",
    num_layers: int | None = None,
    interpret: bool | None = None,
    block_b: int = 128,
    time_tile: int | None = None,
):
    """Run a (stacked) LSTM through one of the four backends.

    The LSTM face of :func:`recurrent_forward`; see ``recurrent_forward``
    for the parameter documentation (with ``spec=LSTM_CELL``, states are
    ``(h, c)`` pairs).

    Returns ``(h_T, c_T)`` (top layer, or per-layer lists with
    ``return_state="all"``), or ``(h_seq, (h_T, c_T))`` when
    ``return_sequence`` is set — the same convention as ``lstm_layer``.
    """
    return recurrent_forward(
        LSTM_CELL, params, xs,
        backend=backend, fmt=fmt, luts=luts, h0=h0, c0=c0,
        return_sequence=return_sequence, return_state=return_state,
        num_layers=num_layers, interpret=interpret,
        block_b=block_b, time_tile=time_tile,
    )


def gru_forward(
    params,
    xs: jax.Array,
    *,
    backend: str = "fused",
    fmt: FxpFormat | None = None,
    luts: dict | None = None,
    h0=None,
    return_sequence: bool = False,
    return_state: str = "top",
    num_layers: int | None = None,
    interpret: bool | None = None,
    block_b: int = 128,
    time_tile: int | None = None,
):
    """Run a (stacked) GRU — the arity-1 face of :func:`recurrent_forward`.

    Same conventions as ``lstm_forward`` except the state is a bare ``h``
    (``h_T``, or a per-layer ``[h_T^l...]`` list with ``return_state="all"``)
    and there is no ``c0``.
    """
    return recurrent_forward(
        GRU_CELL, params, xs,
        backend=backend, fmt=fmt, luts=luts, h0=h0,
        return_sequence=return_sequence, return_state=return_state,
        num_layers=num_layers, interpret=interpret,
        block_b=block_b, time_tile=time_tile,
    )
