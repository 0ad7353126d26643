"""Pure-jnp oracles, each restated self-contained from the paper's equations.

Every function here is what a test compares a datapath against:

* ``lstm_sequence_fxp_ref`` / ``gru_sequence_fxp_ref`` — the bit-level spec
  of the fused fixed-point kernel (``lstm_fxp_seq.py``), integer-equal;
* ``ssd_chunk_scan_ref`` — the SSD kernel (``ssd_scan.py``), allclose;
* ``lstm_step_ref`` / ``lstm_sequence_ref`` — the float C1+C2 cell and its
  scan, against ``repro.core.lstm.lstm_cell_fused`` and
  ``lstm_forward(backend="fused")``;
* ``lut_act_ref`` / ``fxp_matmul_ref`` — C3 and C4, against
  ``repro.core.lut.lut_apply`` / ``lut_apply_fxp`` and
  ``repro.core.fxp.fxp_matmul``.

``tests/test_kernels.py`` holds those comparisons.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "lstm_step_ref",
    "lstm_sequence_ref",
    "lstm_sequence_fxp_ref",
    "gru_sequence_fxp_ref",
    "lut_act_ref",
    "fxp_matmul_ref",
    "ssd_chunk_scan_ref",
]


def lstm_step_ref(xh: jax.Array, w: jax.Array, b: jax.Array, c: jax.Array):
    """Fused LSTM step oracle.

    xh: (B, F) pre-concatenated [x_t, h_{t-1}];  w: (4, F, H) stacked gates
    in i,f,g,o order;  b: (4, H);  c: (B, H).  Returns (h', c').
    """
    z = jnp.einsum("bf,gfh->gbh", xh, w) + b[:, None, :]
    i_t = jax.nn.sigmoid(z[0])
    f_t = jax.nn.sigmoid(z[1])
    g_t = jnp.tanh(z[2])
    o_t = jax.nn.sigmoid(z[3])
    c_t = f_t * c + i_t * g_t
    h_t = o_t * jnp.tanh(c_t)
    return h_t, c_t


def lstm_sequence_ref(xs: jax.Array, w: jax.Array, b: jax.Array,
                      h0: jax.Array, c0: jax.Array):
    """Full-sequence oracle.  xs: (B, T, n_in); w: (4, n_in+H, H); b: (4, H);
    h0/c0: (B, H).  Returns (h_T, c_T)."""

    def step(carry, x_t):
        h, c = carry
        xh = jnp.concatenate([x_t, h], axis=-1)
        h, c = lstm_step_ref(xh, w, b, c)
        return (h, c), None

    (h, c), _ = jax.lax.scan(step, (h0, c0), jnp.moveaxis(xs, 1, 0))
    return h, c


def lstm_sequence_fxp_ref(
    qxs: jax.Array,                 # (B, T, n_in) int32 fixed point
    qw: jax.Array,                  # (n_in + H, 4H) int32 stacked gates (i,f,g,o)
    qb: jax.Array,                  # (4H,) int32
    qh0: jax.Array | None = None,   # (B, H) int32
    qc0: jax.Array | None = None,   # (B, H) int32
    sig_table: jax.Array | None = None,   # (depth,) float32; None = exact sigmoid
    tanh_table: jax.Array | None = None,  # (depth,) float32; None = exact tanh
    *,
    frac_bits: int = 8,
    total_bits: int = 16,
    sig_bounds: tuple[float, float] = (-8.0, 8.0),
    tanh_bounds: tuple[float, float] = (-4.0, 4.0),
    return_sequence: bool = False,
):
    """Fused fixed-point sequence oracle — the bit-level spec of
    ``lstm_sequence_fxp_pallas`` (and of ``repro.core.lstm.lstm_layer_fxp``,
    restated self-contained): ``(x, y)`` fixed point with int32 accumulation,
    round-half-up rescale after every multiply, saturation to the ``y``-bit
    range, and LUT activations addressed by ``floor((q*2^-x - lo)/step)``.

    Returns ``(qh_T, qc_T)`` int32, or ``(qh_seq, qh_T, qc_T)`` when
    ``return_sequence`` is set (flat, matching the Pallas kernel).
    """
    B = qxs.shape[0]
    H = qw.shape[1] // 4
    qmin, qmax = -(1 << (total_bits - 1)), (1 << (total_bits - 1)) - 1
    half = (1 << (frac_bits - 1)) if frac_bits > 0 else 0
    scale = 2.0 ** (-frac_bits)

    def sat(v):
        return jnp.clip(v, qmin, qmax)

    def rescale(acc):
        return sat((acc + half) >> frac_bits)

    def quant(y):
        # fxp.quantize: round-half-up (floor(v + 0.5)), then saturate.
        return sat(jnp.floor(y * (1 << frac_bits) + 0.5).astype(jnp.int32))

    def lut(q, table, bounds):
        lo, hi = bounds
        step = (hi - lo) / table.shape[0]
        x = q.astype(jnp.float32) * scale
        idx = jnp.clip(jnp.floor((x - lo) / step).astype(jnp.int32),
                       0, table.shape[0] - 1)
        return quant(jnp.take(table, idx, axis=0))

    if sig_table is None:
        act_sig = lambda q: quant(jax.nn.sigmoid(q.astype(jnp.float32) * scale))
    else:
        act_sig = lambda q: lut(q, sig_table, sig_bounds)
    if tanh_table is None:
        act_tanh = lambda q: quant(jnp.tanh(q.astype(jnp.float32) * scale))
    else:
        act_tanh = lambda q: lut(q, tanh_table, tanh_bounds)

    def fmul(a, b):
        return rescale(a.astype(jnp.int32) * b.astype(jnp.int32))

    def step(carry, qx_t):
        qh, qc = carry
        qxh = jnp.concatenate([qx_t, qh], axis=-1)
        acc = jnp.matmul(qxh.astype(jnp.int32), qw.astype(jnp.int32))
        acc = acc + (qb.astype(jnp.int32) << frac_bits)
        z = rescale(acc)
        zi, zf, zg, zo = jnp.split(z, 4, axis=-1)
        i_t = act_sig(zi)
        f_t = act_sig(zf)
        g_t = act_tanh(zg)
        o_t = act_sig(zo)
        qc = sat(fmul(f_t, qc) + fmul(i_t, g_t))
        qh = fmul(o_t, act_tanh(qc))
        return (qh, qc), (qh if return_sequence else None)

    qh0 = qh0 if qh0 is not None else jnp.zeros((B, H), jnp.int32)
    qc0 = qc0 if qc0 is not None else jnp.zeros((B, H), jnp.int32)
    (qh, qc), seq = jax.lax.scan(step, (qh0, qc0), jnp.moveaxis(qxs, 1, 0))
    if return_sequence:
        return jnp.moveaxis(seq, 0, 1), qh, qc
    return qh, qc


def gru_sequence_fxp_ref(
    qxs: jax.Array,                 # (B, T, n_in) int32 fixed point
    qw: jax.Array,                  # (n_in + H, 3H) int32 stacked gates (r,z,n)
    qb: jax.Array,                  # (3H,) int32
    qh0: jax.Array | None = None,   # (B, H) int32
    sig_table: jax.Array | None = None,   # (depth,) float32; None = exact sigmoid
    tanh_table: jax.Array | None = None,  # (depth,) float32; None = exact tanh
    *,
    frac_bits: int = 8,
    total_bits: int = 16,
    sig_bounds: tuple[float, float] = (-8.0, 8.0),
    tanh_bounds: tuple[float, float] = (-4.0, 4.0),
    return_sequence: bool = False,
):
    """Fused fixed-point GRU sequence oracle — the bit-level spec of
    ``gru_sequence_fxp_pallas`` (and of ``repro.core.lstm.gru_layer_fxp``,
    restated self-contained), using the same ``(x, y)`` arithmetic as
    ``lstm_sequence_fxp_ref``.

    Cell semantics (``repro.core.cell.GRU_CELL``): gates ``r, z`` come from
    the stacked matmul over ``[x_t, h_{t-1}]`` (columns ``[0, 2H)``); the
    candidate ``n`` is a second matmul over ``[x_t, r_t * h_{t-1}]``
    (columns ``[2H, 3H)``); ``h_t = (1 - z_t) * n_t + z_t * h_{t-1}`` with
    ``1`` represented exactly as ``1 << frac_bits``.

    Returns ``qh_T`` int32, or ``(qh_seq, qh_T)`` when ``return_sequence``
    is set.
    """
    B = qxs.shape[0]
    H = qw.shape[1] // 3
    qmin, qmax = -(1 << (total_bits - 1)), (1 << (total_bits - 1)) - 1
    half = (1 << (frac_bits - 1)) if frac_bits > 0 else 0
    scale = 2.0 ** (-frac_bits)

    def sat(v):
        return jnp.clip(v, qmin, qmax)

    def rescale(acc):
        return sat((acc + half) >> frac_bits)

    def quant(y):
        # fxp.quantize: round-half-up (floor(v + 0.5)), then saturate.
        return sat(jnp.floor(y * (1 << frac_bits) + 0.5).astype(jnp.int32))

    def lut(q, table, bounds):
        lo, hi = bounds
        step = (hi - lo) / table.shape[0]
        x = q.astype(jnp.float32) * scale
        idx = jnp.clip(jnp.floor((x - lo) / step).astype(jnp.int32),
                       0, table.shape[0] - 1)
        return quant(jnp.take(table, idx, axis=0))

    if sig_table is None:
        act_sig = lambda q: quant(jax.nn.sigmoid(q.astype(jnp.float32) * scale))
    else:
        act_sig = lambda q: lut(q, sig_table, sig_bounds)
    if tanh_table is None:
        act_tanh = lambda q: quant(jnp.tanh(q.astype(jnp.float32) * scale))
    else:
        act_tanh = lambda q: lut(q, tanh_table, tanh_bounds)

    def fmul(a, b):
        return rescale(a.astype(jnp.int32) * b.astype(jnp.int32))

    one = jnp.int32(1 << frac_bits)

    def step(qh, qx_t):
        qxh = jnp.concatenate([qx_t, qh], axis=-1)
        acc = jnp.matmul(qxh.astype(jnp.int32), qw[:, :2 * H].astype(jnp.int32))
        acc = acc + (qb[:2 * H].astype(jnp.int32) << frac_bits)
        z_rz = rescale(acc)
        r_t = act_sig(z_rz[..., :H])
        z_t = act_sig(z_rz[..., H:])
        qxrh = jnp.concatenate([qx_t, fmul(r_t, qh)], axis=-1)
        acc_n = jnp.matmul(qxrh.astype(jnp.int32), qw[:, 2 * H:].astype(jnp.int32))
        acc_n = acc_n + (qb[2 * H:].astype(jnp.int32) << frac_bits)
        n_t = act_tanh(rescale(acc_n))
        one_minus_z = sat(one - z_t)
        qh = sat(fmul(one_minus_z, n_t) + fmul(z_t, qh))
        return qh, (qh if return_sequence else None)

    qh0 = qh0 if qh0 is not None else jnp.zeros((B, H), jnp.int32)
    qh, seq = jax.lax.scan(step, qh0, jnp.moveaxis(qxs, 1, 0))
    if return_sequence:
        return jnp.moveaxis(seq, 0, 1), qh
    return qh


def lut_act_ref(x: jax.Array, table: jax.Array, lo: float, hi: float):
    """LUT activation oracle: clamp -> bin index -> gather."""
    depth = table.shape[0]
    step = (hi - lo) / depth
    idx = jnp.clip(jnp.floor((x - lo) / step).astype(jnp.int32), 0, depth - 1)
    return jnp.take(table, idx, axis=0)


def fxp_matmul_ref(a_q: jax.Array, b_q: jax.Array, bias_q: jax.Array | None,
                   frac_bits: int, total_bits: int):
    """Fixed-point matmul oracle: int32 accumulate, pre-shifted bias,
    round-half-up shift, saturate."""
    acc = jnp.matmul(a_q.astype(jnp.int32), b_q.astype(jnp.int32))
    if bias_q is not None:
        acc = acc + (bias_q.astype(jnp.int32) << frac_bits)
    half = 1 << (frac_bits - 1) if frac_bits > 0 else 0
    shifted = (acc + half) >> frac_bits
    qmin, qmax = -(1 << (total_bits - 1)), (1 << (total_bits - 1)) - 1
    return jnp.clip(shifted, qmin, qmax).astype(jnp.int32)


def ssd_chunk_scan_ref(x: jax.Array, a_log: jax.Array, b: jax.Array, c: jax.Array,
                       chunk: int, h0: jax.Array | None = None):
    """Mamba-2 SSD oracle — naive sequential scan (exact).

    x: (B, T, H, P)   inputs per head (P = head dim)
    a_log: (B, T, H)  per-step log decay (<= 0)
    b: (B, T, H, N)   input projection onto state (N = d_state)
    c: (B, T, H, N)   output projection
    h0: (B, H, P, N)  initial state
    Returns y: (B, T, H, P), h_T: (B, H, P, N).

    ``chunk`` is unused here (the oracle is the O(T) recurrence); the kernel
    must match it for every chunk size.
    """
    B, T, H, P = x.shape
    N = b.shape[-1]
    h = h0 if h0 is not None else jnp.zeros((B, H, P, N), x.dtype)

    def step(h, inp):
        x_t, a_t, b_t, c_t = inp  # (B,H,P), (B,H), (B,H,N), (B,H,N)
        decay = jnp.exp(a_t)[..., None, None]          # (B,H,1,1)
        h = decay * h + x_t[..., None] * b_t[..., None, :]  # outer product
        y_t = jnp.einsum("bhpn,bhn->bhp", h, c_t)
        return h, y_t

    inputs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(a_log, 1, 0),
              jnp.moveaxis(b, 1, 0), jnp.moveaxis(c, 1, 0))
    h, ys = jax.lax.scan(step, h, inputs)
    return jnp.moveaxis(ys, 0, 1), h
