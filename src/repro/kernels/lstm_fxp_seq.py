"""Fused fixed-point LSTM *sequence* — Pallas TPU kernel (paper C1–C5 in one
kernel), with double-buffered time-tiling for arbitrarily long sequences and
in-VMEM multi-layer stacking — including heterogeneous hidden sizes and
per-gate/per-layer ``(x, y)`` formats (ROADMAP item 5).

This is the bitstream-exact datapath run the way the FPGA actually runs it:
the paper's 17534 inf/s come from a design where the stacked-gate weights,
the pre-shifted biases and the shared sigmoid/tanh LUT tables are resident
on-chip for the *whole* recurrence, and ``h``/``C`` never leave the shared
BRAM between recursions.  The pure-jnp path ``repro.core.lstm.lstm_layer_fxp``
simulates the same arithmetic but scans at the Python/XLA level, paying a
per-step HBM round-trip — exactly the throughput bottleneck the paper removes.

One ``pallas_call`` performs all ``n_seq`` steps of all ``L`` layers:

* int32 stacked-gate weights ``(L*4, F, Hp)``, biases and both LUT tables are
  loaded into VMEM once (C5);
* each step is, per layer, one exact integer matmul over ``[x_t, h]`` (C1;
  bf16 pieces on the MXU, see ``_int_dot``), a round-half-up shift +
  saturate into that gate's own ``(x, y)``
  format (C4), the LUT gather for all four gates (C3, as a one-hot MXU
  contraction), and the fused elementwise tail (C2) — all against
  VMEM-resident tiles;
* ``h``/``c`` of **every** layer are carried as int32 in VMEM, so HBM traffic
  for state is O(1) in sequence length.

Multi-layer stacking (``lstm_sequence_fxp_stack_pallas``): a stacked LSTM's
dataflow lets layer ``l`` consume layer ``l-1``'s hidden state *of the same
timestep*, so the kernel chains all ``L`` layers inside the per-step loop —
the inter-layer hidden-state sequence is never materialised in HBM (the naive
alternative runs the single-layer kernel ``L`` times and bounces the full
``(B, T, H)`` sequence through HBM between layers).

Heterogeneous hidden sizes: layers may have *different* ``H_l``.  All tiles
are padded to ``Hp = max_l H_l``; weight rows/columns beyond each layer's
real extent are zero, and the fresh ``h``/``c`` of every step are masked to
zero on lanes ``>= H_l`` (the LUT maps a zero pre-activation to a *non-zero*
activation — sigmoid(0) = 0.5 — so padded lanes would otherwise accumulate
garbage).  Zero rows against zero-padded inputs add nothing to the int32
accumulators, preserving bit-exactness.

Per-gate/per-layer formats (``formats=``): each layer carries a data format
``(x_l, y_l)`` (inputs, weights, biases, activations, ``h``/``c``, the
elementwise tail) and four per-gate pre-activation formats ``(x_{l,g},
y_{l,g})``.  The gate matmul accumulator holds ``2*x_l`` fractional bits and
is rescaled by the *static* shift ``2*x_l - x_{l,g}`` (free inside the
kernel: the layer/gate loops unroll at trace time, so every shift and
saturation rail is a compile-time constant).  Between layers the hidden
state is requantised ``(x_l, y_l) -> (x_{l+1}, y_{l+1})`` with the same
round-half-up shift, exactly ``repro.core.fxp.fxp_convert``.

Time-tiling (``time_tile``): with the default ``time_tile=None`` the whole
``(bb, T, n_in)`` input block must fit in one VMEM window, which bounds
``n_seq``.  Passing ``time_tile=tt`` adds a second (inner, sequential) grid
dimension over ``ceil(T / tt)`` time chunks: each grid step sees only a
``(bb, tt, n_in)`` input window while every layer's ``h``/``c`` persist
across chunks in VMEM *scratch* (the BRAM analogue — state never round-trips
HBM between chunks).  Because consecutive grid steps read consecutive input
windows, Pallas's pipeline emitter overlaps the DMA of chunk ``t+1`` with the
compute of chunk ``t`` (double buffering), so the recurrence streams
sequences of any length at the single-block kernel's steady-state rate.  A
ragged tail (``T % tt != 0``) is padded and masked inside the kernel,
preserving integer-exactness.

Cell-generic template (``repro.core.cell.CellSpec``): the kernel body is a
template over the cell kind — the gate-major layout generalises from
``(L*4, F, Hp)`` to ``(L*n_gates, F, Hp)``, the per-gate static shift
constants come from the first ``n_gates`` entries of each layer's gate
formats, and only the elementwise tail (C2) and the state arity differ per
cell.  ``gru_sequence_fxp_stack_pallas`` / ``gru_sequence_fxp_pallas`` run
the 3-gate, single-state GRU (gate order ``r, z, n``; candidate matmul over
``[x_t, r_t * h]``; no ``c`` inputs/outputs/scratch) through the same
machinery — oracle ``repro.kernels.ref.gru_sequence_fxp_ref``.

Bit-exactness: every operation replicates ``repro.core.fxp`` /
``repro.core.lut`` arithmetic operation-for-operation (same rounding mode,
same saturation points, same float32 index computation), so in interpret
mode the kernel is *integer-equal* to ``lstm_layer_fxp`` (layer by layer,
with ``fxp_convert`` between layers, for stacks) — asserted across the
paper's Fig. 6 ``(x, y)`` sweep and Table 1 LUT depths in
``tests/test_lstm_forward.py``, across the backend x shape x time-tile x
depth product in ``tests/test_backend_equiv.py``, and for the mixed-precision
hetero-``H`` stack against ``tests/golden/lstm_mixed_golden.json``.  Oracle:
``repro.kernels.ref.lstm_sequence_fxp_ref``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.cell import cell_spec
from repro.core.fxp import FxpFormat, LayerFormats, StackFormats, as_stack_formats

__all__ = [
    "lstm_sequence_fxp_pallas",
    "lstm_sequence_fxp_stack_pallas",
    "gru_sequence_fxp_pallas",
    "gru_sequence_fxp_stack_pallas",
    "DEFAULT_BLOCK_B",
]

# Batch rows per grid step.  Every LUT gather materialises a (bb, 128, depth)
# f32 one-hot, so Mosaic's compile time grows with bb: compiling for TPU v5e
# takes 1-2 s per (L=1, H=20, depth-256) shape at 16 rows against ~10 s at
# 64, ~40 s at 128 and over 10 minutes at 1024.  Larger batches tile over the
# (sequential) batch grid instead.
DEFAULT_BLOCK_B = 16


# C1 on the MXU.  TPU v5e's MXU multiplies bf16 and int8 but refuses an
# int32 x int32 dot, so the integer matmul is decomposed exactly: every
# operand of at most 16 bits splits as ``a = hi * 256 + lo`` with ``hi`` in
# [-128, 128] and ``lo`` in [-128, 127] -- both exact in bf16 (a signed int8
# split would overflow at hi = 128, i.e. for a >= 32640).  Each of the four
# partial products is at most 2**14 in magnitude, so an f32 accumulation over
# F < 1024 terms stays below 2**24 and is exact; the int32 recombination
# wraps like the oracle's int32 matmul, so the result is the int32 dot bit
# for bit.  ``_check_int_dot_widths`` enforces both bounds at trace time.
_INT_DOT_MAX_BITS = 16
_INT_DOT_MAX_F = 1024


def _split8(a):
    """Split int32 ``a`` (at most 16 bits) into exact bf16 ``(hi, lo)`` pieces
    with ``a == hi * 256 + lo``."""
    hi = (a + 128) >> 8
    lo = a - (hi << 8)
    return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)


def _int_dot(a, b):
    """Exact int32 ``a @ b`` from ``_split8`` pieces of both operands: four
    bf16 MXU passes accumulated in f32, recombined in int32."""
    (a_hi, a_lo), (b_hi, b_lo) = a, b

    def dot(x, y):
        return jax.lax.dot_general(
            x, y, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(jnp.int32)

    return ((dot(a_hi, b_hi) << 16)
            + ((dot(a_hi, b_lo) + dot(a_lo, b_hi)) << 8)
            + dot(a_lo, b_lo))


def _check_int_dot_widths(fmt_spec, F):
    """Trace-time guard: ``_int_dot`` is exact only for operands of at most
    16 bits contracted over F < 1024 terms.  The operands are each layer's
    data-format integers (inputs, ``h`` and weights), whose width is static."""
    for l, ((_, y_d), _) in enumerate(fmt_spec):
        if y_d > _INT_DOT_MAX_BITS:
            raise ValueError(
                f"layer {l}: data format has {y_d} total bits; the fused "
                f"kernel's exact MXU matmul takes at most {_INT_DOT_MAX_BITS}")
    if F >= _INT_DOT_MAX_F:
        raise ValueError(
            f"contraction width F = {F} (input + hidden lanes) must be < "
            f"{_INT_DOT_MAX_F} for the kernel's f32 accumulation to be exact")


def _rnn_seq_fxp_kernel(
    xs_ref, w_ref, b_ref, sig_ref, tanh_ref,
    *refs,
    cell_kind: str,      # "lstm" | "gru" — selects the elementwise tail (C2)
    n_layers: int,
    time_tile: int,
    n_seq: int,
    has_tail: bool,
    fmt_spec: tuple,     # per layer: ((x_d, y_d), n_gates x (x_g, y_g)) — static
    h_sizes: tuple,      # per layer: real H_l (<= Hp) — static
    sig_lo: float,
    sig_step: float,
    sig_depth: int,
    tanh_lo: float,
    tanh_step: float,
    tanh_depth: int,
    use_lut: bool,
    return_sequence: bool,
):
    spec = cell_spec(cell_kind)
    arity, n_gates = spec.state_arity, spec.n_gates
    # Remaining refs, in order: state inputs (h0 [, c0]), outputs
    # ([h_seq,] h [, c]) and VMEM scratch (h [, c]) — each state tensor is
    # (L, bb, Hp); arity-1 cells simply have no c slots.
    h0_ref = refs[0]
    c0_ref = refs[1] if arity == 2 else None
    scr = refs[len(refs) - arity:]
    out_refs = refs[arity:len(refs) - arity]
    h_scr = scr[0]
    c_scr = scr[1] if arity == 2 else None
    h_seq_ref = None
    if return_sequence:
        h_seq_ref, out_refs = out_refs[0], out_refs[1:]
    h_out_ref = out_refs[0]
    c_out_ref = out_refs[1] if arity == 2 else None

    tb = pl.program_id(1)                   # time-chunk index (sequential)

    @pl.when(tb == 0)
    def _():                                # fresh batch tile: load h0 (and c0)
        h_scr[...] = h0_ref[...]
        if arity == 2:
            c_scr[...] = c0_ref[...]

    w = w_ref[...]                      # (L*4, F, Hp) int32 — loaded once (C5)
    b = b_ref[...]                      # (L*4, Hp) int32
    F, Hp = w.shape[1], w.shape[2]
    w_hi, w_lo = _split8(w)             # bf16 MXU pieces, split once per tile
    in_w = F - Hp                       # padded input width (= n_in for L=1)

    def sat(v, y):
        return jnp.clip(v, -(1 << (y - 1)), (1 << (y - 1)) - 1)

    def shift_rs(acc, shift, y):
        # fxp._shift_round_sat: round-half-up shift by `shift` fractional
        # bits (static; <= 0 is a left shift), saturate to y bits.  The
        # kernel's accumulators stay inside the documented int32 envelope,
        # so no wrap clamp is needed for bit-equality with the oracle.
        if shift > 0:
            acc = (acc + (1 << (shift - 1))) >> shift
        elif shift < 0:
            acc = acc << (-shift)
        return sat(acc, y)

    def quant(yf, x_bits, y_bits):
        # fxp.quantize: round-half-up (floor(v + 0.5)), then saturate.
        return sat(jnp.floor(yf * (1 << x_bits) + 0.5).astype(jnp.int32), y_bits)

    def gather(table, idx, depth):
        # One-hot MXU contraction (exact: adding zeros to the hit entry).
        # HIGHEST keeps the f32 table entries whole; the TPU default would
        # round them to bf16 before the multiply.
        iota = jax.lax.broadcasted_iota(jnp.int32, (*idx.shape, depth), idx.ndim)
        onehot = (iota == idx[..., None]).astype(jnp.float32)
        return jax.lax.dot_general(
            onehot, table, (((idx.ndim,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    def lut_act(q, table, lo, step, depth, in_frac, out_x, out_y):
        x = q.astype(jnp.float32) * (2.0 ** (-in_frac))
        # A power-of-two bin width (every default range and depth) divides
        # exactly as a multiply by its reciprocal, which leaves no rounding
        # choice to the TPU's divide; other widths keep the oracle's divide.
        if math.frexp(step)[0] == 0.5:
            pos = (x - lo) * (1.0 / step)
        else:
            pos = (x - lo) / step
        idx = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, depth - 1)
        return quant(gather(table, idx, depth), out_x, out_y)

    if use_lut:
        act_sig = lambda q, in_frac, xd, yd: lut_act(
            q, sig_ref[0], sig_lo, sig_step, sig_depth, in_frac, xd, yd)
        act_tanh = lambda q, in_frac, xd, yd: lut_act(
            q, tanh_ref[0], tanh_lo, tanh_step, tanh_depth, in_frac, xd, yd)
    else:
        act_sig = lambda q, in_frac, xd, yd: quant(
            jax.nn.sigmoid(q.astype(jnp.float32) * (2.0 ** (-in_frac))), xd, yd)
        act_tanh = lambda q, in_frac, xd, yd: quant(
            jnp.tanh(q.astype(jnp.float32) * (2.0 ** (-in_frac))), xd, yd)

    t0 = tb * time_tile                    # global index of this chunk's step 0

    def step(t, state):
        hs = state[0]                                  # (L, bb, Hp)
        cs = state[1] if arity == 2 else None
        inp = xs_ref[:, t, :]                          # (bb, in_w) dynamic slice
        new_h, new_c = [], []
        for l in range(n_layers):                      # unrolled at trace time
            (xd, yd), gate_fmts = fmt_spec[l]
            H_l = h_sizes[l]
            qh = hs[l]
            # C2 building block: rescale+saturate after every multiply.
            fmul = lambda a, bb_: shift_rs(a * bb_, xd, yd)

            # C1: stacked-gate matmul — per-gate int32 accumulators are
            # identical to the (F, n_gates*H) stacked form, so gate-major
            # keeps bit-exactness; zero-padded rows x zero-padded inputs add
            # 0.  The accumulator carries 2*xd fractional bits; each gate's
            # rescale shift 2*xd - x_g lands directly in that gate's format.
            def zgate(g, x_pieces):
                k = n_gates * l + g
                return shift_rs(_int_dot(x_pieces, (w_hi[k], w_lo[k]))
                                + (b[k][None, :] << xd),
                                2 * xd - gate_fmts[g][0], gate_fmts[g][1])

            if cell_kind == "lstm":
                qc = cs[l]
                qxh = _split8(jnp.concatenate([inp, qh], axis=-1))  # (bb, F)
                z = [zgate(g, qxh) for g in range(4)]
                i_t = act_sig(z[0], gate_fmts[0][0], xd, yd)
                f_t = act_sig(z[1], gate_fmts[1][0], xd, yd)
                g_t = act_tanh(z[2], gate_fmts[2][0], xd, yd)
                o_t = act_sig(z[3], gate_fmts[3][0], xd, yd)
                # C2: fused elementwise tail, same saturation order as the
                # oracle (each product rescaled+saturated, sum saturated).
                qc_new = sat(fmul(f_t, qc) + fmul(i_t, g_t), yd)
                qh_new = fmul(o_t, act_tanh(qc_new, xd, xd, yd))
            else:                                      # gru (see core.cell)
                qxh = _split8(jnp.concatenate([inp, qh], axis=-1))
                r_t = act_sig(zgate(0, qxh), gate_fmts[0][0], xd, yd)
                z_t = act_sig(zgate(1, qxh), gate_fmts[1][0], xd, yd)
                # Candidate gate's matmul runs over [x_t, r_t * h_{t-1}] —
                # the reset is applied to the state ENTERING the matmul.
                qxh2 = _split8(jnp.concatenate([inp, fmul(r_t, qh)], axis=-1))
                n_t = act_tanh(zgate(2, qxh2), gate_fmts[2][0], xd, yd)
                # h' = (1 - z)*n + z*h with 1 exactly on-grid as 1 << xd.
                one_minus_z = sat(jnp.int32(1 << xd) - z_t, yd)
                qh_new = sat(fmul(one_minus_z, n_t) + fmul(z_t, qh), yd)
                qc_new = None
            if H_l < Hp:
                # Padded lanes must stay zero: a zero pre-activation maps to
                # a NON-zero activation (sigmoid(0) = 0.5, and the midpoint-
                # sampled tanh LUT bin at 0 need not be 0), so without the
                # mask garbage would accumulate in the state beyond H_l.
                lane = jax.lax.broadcasted_iota(jnp.int32, qh_new.shape, 1)
                qh_new = jnp.where(lane < H_l, qh_new, 0)
                if qc_new is not None:
                    qc_new = jnp.where(lane < H_l, qc_new, 0)
            if has_tail:
                # Padded steps past n_seq must not advance the recurrence.
                valid = t0 + t < n_seq
                qh_new = jnp.where(valid, qh_new, qh)
                if qc_new is not None:
                    qc_new = jnp.where(valid, qc_new, cs[l])
            new_h.append(qh_new)
            if qc_new is not None:
                new_c.append(qc_new)
            if l + 1 < n_layers:
                # Layer l's fresh h_t is layer l+1's input AT THIS TIMESTEP —
                # it stays in VMEM/registers, never visiting HBM.  Requantise
                # into layer l+1's data format (fxp_convert, static shift).
                nxt_xd, nxt_yd = fmt_spec[l + 1][0]
                nxt = qh_new
                if (xd, yd) != (nxt_xd, nxt_yd):
                    nxt = shift_rs(nxt, xd - nxt_xd, nxt_yd)
                if in_w != Hp:
                    nxt = jnp.pad(nxt, ((0, 0), (0, in_w - Hp)))
                inp = nxt
        if return_sequence:
            h_seq_ref[:, t, :] = new_h[-1]             # top layer only
        if arity == 2:
            return jnp.stack(new_h), jnp.stack(new_c)
        return (jnp.stack(new_h),)

    init = (h_scr[...], c_scr[...]) if arity == 2 else (h_scr[...],)
    state = jax.lax.fori_loop(0, time_tile, step, init)
    hs = state[0]
    h_scr[...] = hs                        # state persists to the next chunk
    h_out_ref[...] = hs                    # same (i, 0) block every chunk:
    if arity == 2:                         # the final chunk's write survives
        cs = state[1]
        c_scr[...] = cs
        c_out_ref[...] = cs


@functools.partial(
    jax.jit,
    static_argnames=(
        "cell_kind", "fmt_spec", "h_sizes", "sig_lo", "sig_hi", "tanh_lo",
        "tanh_hi", "return_sequence", "block_b", "time_tile", "interpret",
    ),
)
def _rnn_seq_fxp_call(
    qxs, w4, b4, sig_table, tanh_table, qh0, qc0, *,
    cell_kind, fmt_spec, h_sizes, sig_lo, sig_hi, tanh_lo, tanh_hi,
    return_sequence, block_b, time_tile, interpret,
):
    spec = cell_spec(cell_kind)
    arity, n_gates = spec.state_arity, spec.n_gates
    B, T, in_w = qxs.shape
    Lg, F, Hp = w4.shape
    L = Lg // n_gates
    _check_int_dot_widths(fmt_spec, F)
    use_lut = sig_table.shape[0] > 1 or tanh_table.shape[0] > 1
    sig_depth = sig_table.shape[0]
    tanh_depth = tanh_table.shape[0]

    bb = min(block_b, B)
    pad_b = (-B) % bb
    if pad_b:
        qxs = jnp.pad(qxs, ((0, pad_b), (0, 0), (0, 0)))
        qh0 = jnp.pad(qh0, ((0, 0), (0, pad_b), (0, 0)))
        if arity == 2:
            qc0 = jnp.pad(qc0, ((0, 0), (0, pad_b), (0, 0)))
    Bp = B + pad_b

    tt = T if time_tile is None else min(time_tile, T)
    pad_t = (-T) % tt
    if pad_t:
        qxs = jnp.pad(qxs, ((0, 0), (0, pad_t), (0, 0)))
    Tp = T + pad_t
    n_tt = Tp // tt

    kernel = functools.partial(
        _rnn_seq_fxp_kernel,
        cell_kind=cell_kind,
        n_layers=L, time_tile=tt, n_seq=T, has_tail=bool(pad_t),
        fmt_spec=fmt_spec, h_sizes=h_sizes,
        sig_lo=sig_lo, sig_step=(sig_hi - sig_lo) / sig_depth, sig_depth=sig_depth,
        tanh_lo=tanh_lo, tanh_step=(tanh_hi - tanh_lo) / tanh_depth,
        tanh_depth=tanh_depth,
        use_lut=use_lut, return_sequence=return_sequence,
    )

    state_spec = lambda: pl.BlockSpec((L, bb, Hp), lambda i, t: (0, i, 0))
    out_specs = [state_spec() for _ in range(arity)]
    out_shape = [jax.ShapeDtypeStruct((L, Bp, Hp), jnp.int32)
                 for _ in range(arity)]
    if return_sequence:
        out_specs = [pl.BlockSpec((bb, tt, Hp), lambda i, t: (i, t, 0))] + out_specs
        out_shape = [jax.ShapeDtypeStruct((Bp, Tp, Hp), jnp.int32)] + out_shape

    in_specs = [
        pl.BlockSpec((bb, tt, in_w), lambda i, t: (i, t, 0)),
        pl.BlockSpec((Lg, F, Hp), lambda i, t: (0, 0, 0)),
        pl.BlockSpec((Lg, Hp), lambda i, t: (0, 0)),
        pl.BlockSpec((1, sig_depth), lambda i, t: (0, 0)),
        pl.BlockSpec((1, tanh_depth), lambda i, t: (0, 0)),
    ] + [state_spec() for _ in range(arity)]
    operands = [qxs, w4, b4, sig_table.reshape(1, sig_depth),
                tanh_table.reshape(1, tanh_depth), qh0]
    if arity == 2:
        operands.append(qc0)

    outs = pl.pallas_call(
        kernel,
        # Batch tiles outer, time chunks inner: the innermost grid dimension
        # iterates fastest, so for each batch tile the chunks run in order and
        # the VMEM scratch legally carries the state from chunk to chunk.
        grid=(Bp // bb, n_tt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            # per-state-tensor scratch: all layers' h (and c), across chunks
            pltpu.VMEM((L, bb, Hp), jnp.int32) for _ in range(arity)
        ],
        # Neither grid dimension is safely parallelisable: time chunks carry
        # the recurrence, and batch tiles re-initialise the shared scratch.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*operands)

    if arity == 1:
        if return_sequence:
            h_seq, h = outs
            return h_seq[:B, :T], h[:, :B]
        (h,) = outs
        return h[:, :B]
    if return_sequence:
        h_seq, h, c = outs
        return h_seq[:B, :T], h[:, :B], c[:, :B]
    h, c = outs
    return h[:, :B], c[:, :B]


def _pack_gate_major(qw, qb, n_in_l, in_w, H, Hp, n_gates=4):
    """One layer's stacked ``(F_l, n_gates*H)`` weights -> gate-major
    ``(n_gates, in_w + Hp, Hp)`` with the input rows at ``[0:n_in_l]``, the
    hidden rows at ``[in_w:in_w+H]`` and the real output columns at
    ``[0:H]``; every other row/column is zero (zero rows meet zero-padded
    inputs, and zero columns keep padded output lanes inert)."""
    F_l = qw.shape[0]
    wl = qw.reshape(F_l, n_gates, H).transpose(1, 0, 2)     # (n_gates, F_l, H)
    if n_in_l == in_w and H == Hp:
        packed = wl
    else:
        packed = jnp.zeros((n_gates, in_w + Hp, Hp), jnp.int32)
        packed = packed.at[:, :n_in_l, :H].set(wl[:, :n_in_l, :])
        packed = packed.at[:, in_w:in_w + H, :H].set(wl[:, n_in_l:, :])
    qb = qb.reshape(n_gates, H)
    if H != Hp:
        qb = jnp.pad(qb, ((0, 0), (0, Hp - H)))
    return packed, qb


def _fmt_spec(formats: StackFormats, n_gates=4) -> tuple:
    """Hashable static spec the jitted call keys on: per layer,
    ``((x_d, y_d), n_gates x (x_g, y_g))``.  Only the first ``n_gates``
    entries of each layer's gate container are consumed, so the arity-4
    uniform default serves 3-gate cells too."""
    return tuple(
        ((lf.data.frac_bits, lf.data.total_bits),
         tuple((lf.gates[g].frac_bits, lf.gates[g].total_bits)
               for g in range(n_gates)))
        for lf in formats.layers)


def lstm_sequence_fxp_stack_pallas(
    qxs: jax.Array,                 # (B, T, n_in) int32 fixed point
    qws,                            # length-L sequence of (F_l, 4*H_l) int32
    qbs,                            # length-L sequence of (4*H_l,) int32
    qh0=None,                       # (L, B, H) int32, or per-layer list of (B, H_l)
    qc0=None,                       # (L, B, H) int32, or per-layer list of (B, H_l)
    sig_table: jax.Array | None = None,   # (depth,) float32 LUT, None = exact sigmoid
    tanh_table: jax.Array | None = None,  # (depth,) float32 LUT, None = exact tanh
    *,
    formats: StackFormats | LayerFormats | FxpFormat | None = None,
    frac_bits: int = 8,
    total_bits: int = 16,
    sig_lo: float = -8.0,
    sig_hi: float = 8.0,
    tanh_lo: float = -4.0,
    tanh_hi: float = 4.0,
    return_sequence: bool = False,
    block_b: int = DEFAULT_BLOCK_B,
    time_tile: int | None = None,
    interpret: bool = False,
):
    """Run an ``L``-layer quantised stack in ONE Pallas kernel.

    Layers may have different hidden sizes ``H_l`` (layer ``l >= 1`` has
    input size ``H_{l-1}``; layer 0's input size is ``qxs.shape[-1]``) and
    different per-gate/per-layer formats (``formats=``, a ``StackFormats`` —
    ``frac_bits``/``total_bits`` remain as the uniform-format shorthand).
    Everything is padded to ``Hp = max_l H_l`` with padded lanes masked to
    zero in-kernel.  The per-step loop chains the layers, so the inter-layer
    hidden sequence stays in VMEM — integer-equal to running
    ``lstm_layer_fxp`` layer by layer with ``fxp_convert`` between layers.

    Returns ``(qh, qc)`` stacked ``(L, B, H)`` for a uniform-``H`` stack
    (back-compat), or per-layer lists of ``(B, H_l)`` otherwise; with
    ``return_sequence=True``, ``(qh_seq, qh, qc)`` (``qh_seq`` is the top
    layer's ``(B, T, H_{L-1})``).
    """
    return _stack_fxp_pallas(
        "lstm", qxs, qws, qbs, qh0, qc0, sig_table, tanh_table,
        formats=formats, frac_bits=frac_bits, total_bits=total_bits,
        sig_lo=sig_lo, sig_hi=sig_hi, tanh_lo=tanh_lo, tanh_hi=tanh_hi,
        return_sequence=return_sequence, block_b=block_b, time_tile=time_tile,
        interpret=interpret,
    )


def _stack_fxp_pallas(
    cell_kind, qxs, qws, qbs, qh0, qc0, sig_table, tanh_table, *,
    formats, frac_bits, total_bits, sig_lo, sig_hi, tanh_lo, tanh_hi,
    return_sequence, block_b, time_tile, interpret,
):
    """Shared cell-generic body of the ``*_sequence_fxp_stack_pallas``
    faces: validate, pack the gate-major layout, pad/stack the state and
    dispatch to the jitted kernel call."""
    spec = cell_spec(cell_kind)
    arity, n_gates = spec.state_arity, spec.n_gates
    if time_tile is not None and time_tile < 1:
        raise ValueError(f"time_tile must be >= 1, got {time_tile}")
    qws, qbs = list(qws), list(qbs)
    if len(qws) != len(qbs) or not qws:
        raise ValueError("qws and qbs must be equal-length, non-empty lists")
    L = len(qws)
    hs_l = [w.shape[1] // n_gates for w in qws]
    n_in = qxs.shape[-1]
    B = qxs.shape[0]
    for l, w in enumerate(qws):
        exp_in = n_in if l == 0 else hs_l[l - 1]
        if w.shape[0] != exp_in + hs_l[l]:
            raise ValueError(
                f"layer {l}: want weights "
                f"({exp_in + hs_l[l]}, {n_gates * hs_l[l]}), got {w.shape}")

    if formats is None:
        formats = FxpFormat(frac_bits, total_bits)
    formats = as_stack_formats(formats, L)

    Hp = max(hs_l)
    uniform_h = all(h == Hp for h in hs_l)
    in_w = max(n_in, Hp) if L > 1 else n_in
    if n_in < in_w:
        qxs = jnp.pad(qxs, ((0, 0), (0, 0), (0, in_w - n_in)))
    packed = [_pack_gate_major(w, b, n_in if l == 0 else hs_l[l - 1],
                               in_w, hs_l[l], Hp, n_gates)
              for l, (w, b) in enumerate(zip(qws, qbs))]
    w4 = jnp.concatenate([p[0] for p in packed], axis=0)    # (L*n_gates, F, Hp)
    b4 = jnp.concatenate([p[1] for p in packed], axis=0)    # (L*n_gates, Hp)

    def to_stacked(s, name):
        if s is None:
            return jnp.zeros((L, B, Hp), jnp.int32)
        if isinstance(s, (list, tuple)):
            if len(s) != L:
                raise ValueError(f"{name}: want {L} per-layer arrays, got {len(s)}")
            return jnp.stack([
                jnp.pad(jnp.asarray(si), ((0, 0), (0, Hp - hs_l[li])))
                if hs_l[li] != Hp else jnp.asarray(si)
                for li, si in enumerate(s)])
        if not uniform_h:
            raise ValueError(
                f"{name}: a heterogeneous-H stack takes per-layer state "
                f"lists, not a stacked array (layer widths {hs_l})")
        return s

    qh0 = to_stacked(qh0, "qh0")
    qc0 = to_stacked(qc0, "qc0") if arity == 2 else None
    if (sig_table is None) != (tanh_table is None):
        raise ValueError("pass both LUT tables or neither")
    # depth-1 dummies signal "no LUT" to the jitted call (real tables have
    # depth >= 2, enforced by LutSpec).
    if sig_table is None:
        sig_table = jnp.zeros((1,), jnp.float32)
    if tanh_table is None:
        tanh_table = jnp.zeros((1,), jnp.float32)
    out = _rnn_seq_fxp_call(
        qxs, w4, b4,
        jnp.asarray(sig_table, jnp.float32), jnp.asarray(tanh_table, jnp.float32),
        qh0, qc0,
        cell_kind=cell_kind,
        fmt_spec=_fmt_spec(formats, n_gates), h_sizes=tuple(hs_l),
        sig_lo=sig_lo, sig_hi=sig_hi, tanh_lo=tanh_lo, tanh_hi=tanh_hi,
        return_sequence=return_sequence, block_b=block_b, time_tile=time_tile,
        interpret=interpret,
    )
    if arity == 1:
        if return_sequence:
            h_seq, h = out
            h_seq = h_seq[..., :hs_l[-1]]
        else:
            h = out
        if not uniform_h:
            h = [h[li, :, :hs_l[li]] for li in range(L)]
        return (h_seq, h) if return_sequence else h
    if return_sequence:
        h_seq, h, c = out
        h_seq = h_seq[..., :hs_l[-1]]
    else:
        h, c = out
    if not uniform_h:
        h = [h[li, :, :hs_l[li]] for li in range(L)]
        c = [c[li, :, :hs_l[li]] for li in range(L)]
    return (h_seq, h, c) if return_sequence else (h, c)


def gru_sequence_fxp_stack_pallas(
    qxs: jax.Array,                 # (B, T, n_in) int32 fixed point
    qws,                            # length-L sequence of (F_l, 3*H_l) int32
    qbs,                            # length-L sequence of (3*H_l,) int32
    qh0=None,                       # (L, B, H) int32, or per-layer list of (B, H_l)
    sig_table: jax.Array | None = None,   # (depth,) float32 LUT, None = exact sigmoid
    tanh_table: jax.Array | None = None,  # (depth,) float32 LUT, None = exact tanh
    *,
    formats: StackFormats | LayerFormats | FxpFormat | None = None,
    frac_bits: int = 8,
    total_bits: int = 16,
    sig_lo: float = -8.0,
    sig_hi: float = 8.0,
    tanh_lo: float = -4.0,
    tanh_hi: float = 4.0,
    return_sequence: bool = False,
    block_b: int = DEFAULT_BLOCK_B,
    time_tile: int | None = None,
    interpret: bool = False,
):
    """Run an ``L``-layer quantised GRU stack in ONE Pallas kernel — the
    arity-1 instantiation of the same kernel template as
    ``lstm_sequence_fxp_stack_pallas`` (gate-major weights ``(L*3, F, Hp)``,
    gate order ``r, z, n``; no cell-state tensors anywhere: one state input,
    one state output, one VMEM scratch buffer).  Semantics per
    ``repro.core.cell.GRU_CELL``; oracle:
    ``repro.kernels.ref.gru_sequence_fxp_ref`` /
    ``repro.core.lstm.gru_layer_fxp``.

    Returns ``qh`` stacked ``(L, B, H)`` for a uniform-``H`` stack, or
    per-layer lists of ``(B, H_l)`` otherwise; with
    ``return_sequence=True``, ``(qh_seq, qh)`` (``qh_seq`` is the top
    layer's ``(B, T, H_{L-1})``).
    """
    return _stack_fxp_pallas(
        "gru", qxs, qws, qbs, qh0, None, sig_table, tanh_table,
        formats=formats, frac_bits=frac_bits, total_bits=total_bits,
        sig_lo=sig_lo, sig_hi=sig_hi, tanh_lo=tanh_lo, tanh_hi=tanh_hi,
        return_sequence=return_sequence, block_b=block_b, time_tile=time_tile,
        interpret=interpret,
    )


def gru_sequence_fxp_pallas(
    qxs: jax.Array,                 # (B, T, n_in) int32 fixed point
    qw: jax.Array,                  # (F, 3H) int32 stacked gates, r,z,n blocks
    qb: jax.Array,                  # (3H,) int32
    qh0: jax.Array | None = None,   # (B, H) int32
    sig_table: jax.Array | None = None,   # (depth,) float32 LUT, None = exact sigmoid
    tanh_table: jax.Array | None = None,  # (depth,) float32 LUT, None = exact tanh
    *,
    formats: LayerFormats | FxpFormat | None = None,
    frac_bits: int = 8,
    total_bits: int = 16,
    sig_lo: float = -8.0,
    sig_hi: float = 8.0,
    tanh_lo: float = -4.0,
    tanh_hi: float = 4.0,
    return_sequence: bool = False,
    block_b: int = DEFAULT_BLOCK_B,
    time_tile: int | None = None,
    interpret: bool = False,
):
    """Run the whole quantised GRU recurrence in one Pallas kernel (one
    layer) — the ``L = 1`` face of ``gru_sequence_fxp_stack_pallas``, same
    conventions as ``lstm_sequence_fxp_pallas`` minus the cell state.
    Returns ``qh_T`` int32, or ``(qh_seq, qh_T)`` with
    ``return_sequence=True``.
    """
    out = gru_sequence_fxp_stack_pallas(
        qxs, [qw], [qb],
        None if qh0 is None else qh0[None],
        sig_table, tanh_table,
        formats=formats, frac_bits=frac_bits, total_bits=total_bits,
        sig_lo=sig_lo, sig_hi=sig_hi, tanh_lo=tanh_lo, tanh_hi=tanh_hi,
        return_sequence=return_sequence, block_b=block_b, time_tile=time_tile,
        interpret=interpret,
    )
    if return_sequence:
        h_seq, h = out
        return h_seq, h[0]
    return out[0]


def lstm_sequence_fxp_pallas(
    qxs: jax.Array,                 # (B, T, n_in) int32 fixed point
    qw: jax.Array,                  # (F, 4H) int32 stacked gates, i,f,g,o blocks
    qb: jax.Array,                  # (4H,) int32
    qh0: jax.Array | None = None,   # (B, H) int32
    qc0: jax.Array | None = None,   # (B, H) int32
    sig_table: jax.Array | None = None,   # (depth,) float32 LUT, None = exact sigmoid
    tanh_table: jax.Array | None = None,  # (depth,) float32 LUT, None = exact tanh
    *,
    formats: LayerFormats | FxpFormat | None = None,
    frac_bits: int = 8,
    total_bits: int = 16,
    sig_lo: float = -8.0,
    sig_hi: float = 8.0,
    tanh_lo: float = -4.0,
    tanh_hi: float = 4.0,
    return_sequence: bool = False,
    block_b: int = DEFAULT_BLOCK_B,
    time_tile: int | None = None,
    interpret: bool = False,
):
    """Run the whole quantised recurrence in one Pallas kernel (one layer).

    Weight layout is the stacked ``(n_in + H, 4H)`` of ``LSTMParams`` (gate
    blocks i,f,g,o along the last axis); it is reshaped to gate-major
    ``(4, F, H)`` for MXU-aligned per-gate tiles — integer accumulation is
    order-independent, so this preserves bit-exactness with the stacked
    oracle.  ``formats=`` (a ``LayerFormats``) selects per-gate formats;
    ``time_tile=None`` keeps the whole sequence in one VMEM block;
    ``time_tile=tt`` streams it through VMEM in double-buffered ``tt``-step
    chunks with ``h``/``c`` carried in scratch (see module docstring), so
    ``n_seq`` is unbounded.  Both paths are integer-equal to
    ``lstm_layer_fxp``.  Returns ``(qh_T, qc_T)`` int32, or
    ``(qh_seq, qh_T, qc_T)`` with ``return_sequence=True``.

    This is the ``L = 1`` face of ``lstm_sequence_fxp_stack_pallas`` — the
    same kernel executes both.
    """
    out = lstm_sequence_fxp_stack_pallas(
        qxs, [qw], [qb],
        None if qh0 is None else qh0[None],
        None if qc0 is None else qc0[None],
        sig_table, tanh_table,
        formats=formats, frac_bits=frac_bits, total_bits=total_bits,
        sig_lo=sig_lo, sig_hi=sig_hi, tanh_lo=tanh_lo, tanh_hi=tanh_hi,
        return_sequence=return_sequence, block_b=block_b, time_tile=time_tile,
        interpret=interpret,
    )
    if return_sequence:
        h_seq, h, c = out
        return h_seq, h[0], c[0]
    h, c = out
    return h[0], c[0]
