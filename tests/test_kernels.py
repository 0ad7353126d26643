"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lut import LutSpec, build_table
from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def _rand(shape, dtype=np.float32, scale=1.0):
    return jnp.asarray((RNG.normal(size=shape) * scale).astype(dtype))


# ---------------------------------------------------------------------------
# fused LSTM step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,f,h", [(1, 3, 4), (8, 24, 20), (5, 21, 20),
                                   (16, 40, 33), (128, 64, 128), (130, 48, 129)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_lstm_step_sweep(b, f, h, dtype):
    xh = _rand((b, f), dtype)
    w = _rand((4, f, h), dtype, 0.2)
    bias = _rand((4, h), dtype, 0.1)
    c = _rand((b, h), dtype)
    h1, c1 = ops.lstm_step(xh, w, bias, c, impl="ref")
    h2, c2 = ops.lstm_step(xh, w, bias, c, impl="interpret", block_b=64, block_h=64)
    np.testing.assert_allclose(h1, h2, atol=2e-6)
    np.testing.assert_allclose(c1, c2, atol=2e-6)


# Batch sizes deliberately NOT multiples of block_b=4 (1 < block, 5 and 9
# straddle a partial tile) so the padding path is always exercised, and
# n_seq in {1, 7, 24} so the fori_loop time slicing covers degenerate,
# odd, and paper-Fig.6-scale sequence lengths.
@pytest.mark.parametrize("b", [1, 5, 9])
@pytest.mark.parametrize("t", [1, 7, 24])
@pytest.mark.parametrize("n_in,h", [(2, 20)])
def test_lstm_sequence_sweep(b, t, n_in, h):
    xs = _rand((b, t, n_in))
    w = _rand((4, n_in + h, h), scale=0.2)
    bias = _rand((4, h), scale=0.1)
    h0 = jnp.zeros((b, h))
    c0 = jnp.zeros((b, h))
    r1 = ops.lstm_sequence(xs, w, bias, h0, c0, impl="ref")
    r2 = ops.lstm_sequence(xs, w, bias, h0, c0, impl="interpret", block_b=4)
    np.testing.assert_allclose(r1[0], r2[0], atol=5e-6)
    np.testing.assert_allclose(r1[1], r2[1], atol=5e-6)


def test_lstm_sequence_return_sequence():
    b, t, n_in, h = 5, 7, 3, 16
    xs = _rand((b, t, n_in))
    w = _rand((4, n_in + h, h), scale=0.2)
    bias = _rand((4, h), scale=0.1)
    h0 = jnp.zeros((b, h))
    c0 = jnp.zeros((b, h))
    from repro.kernels.lstm_step import lstm_sequence_pallas
    h_seq, hT, cT = lstm_sequence_pallas(xs, w, bias, h0, c0, block_b=4,
                                         return_sequence=True, interpret=True)
    hr, cr = ops.lstm_sequence(xs, w, bias, h0, c0, impl="ref")
    assert h_seq.shape == (b, t, h)
    np.testing.assert_allclose(h_seq[:, -1], hr, atol=5e-6)
    np.testing.assert_allclose(hT, hr, atol=5e-6)
    np.testing.assert_allclose(cT, cr, atol=5e-6)


# ---------------------------------------------------------------------------
# fused fixed-point sequence (C1–C5 in one kernel)
# ---------------------------------------------------------------------------

def _fxp_seq_inputs(b, t, n_in, h, total):
    hi = 2 ** (total - 3)
    qxs = jnp.asarray(RNG.integers(-hi, hi, (b, t, n_in)), jnp.int32)
    qw = jnp.asarray(RNG.integers(-hi // 4, hi // 4, (n_in + h, 4 * h)), jnp.int32)
    qb = jnp.asarray(RNG.integers(-hi // 4, hi // 4, (4 * h,)), jnp.int32)
    return qxs, qw, qb


@pytest.mark.parametrize("b,t", [(1, 1), (5, 7), (9, 24)])
@pytest.mark.parametrize("frac,total", [(8, 16), (6, 12)])
@pytest.mark.parametrize("mxu", [True, False])
def test_lstm_sequence_fxp_kernel_vs_oracle(b, t, frac, total, mxu):
    from repro.core.lut import make_lut_pair
    n_in, h = 2, 20
    qxs, qw, qb = _fxp_seq_inputs(b, t, n_in, h, total)
    luts = make_lut_pair(64)
    (sig_t, sig_s), (tanh_t, tanh_s) = luts["sigmoid"], luts["tanh"]
    kw = dict(frac_bits=frac, total_bits=total,
              sig_lo=sig_s.bounds[0], sig_hi=sig_s.bounds[1],
              tanh_lo=tanh_s.bounds[0], tanh_hi=tanh_s.bounds[1])
    o1 = ops.lstm_sequence_fxp(qxs, qw, qb, None, None, sig_t, tanh_t,
                               impl="ref", **kw)
    o2 = ops.lstm_sequence_fxp(qxs, qw, qb, None, None, sig_t, tanh_t,
                               impl="interpret", block_b=4, mxu_onehot=mxu, **kw)
    np.testing.assert_array_equal(np.asarray(o1[0]), np.asarray(o2[0]))
    np.testing.assert_array_equal(np.asarray(o1[1]), np.asarray(o2[1]))


def _int_dot_pallas(a, b):
    """The kernel's ``_int_dot`` on ``_split8`` pieces, inside a Pallas call
    in interpret mode, exactly as the fused kernel runs it."""
    from jax.experimental import pallas as pl

    from repro.kernels.lstm_fxp_seq import _int_dot, _split8

    def kernel(a_ref, b_ref, o_ref):
        o_ref[...] = _int_dot(_split8(a_ref[...]), _split8(b_ref[...]))

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((a.shape[0], b.shape[1]), jnp.int32),
        interpret=True)(a, b)


# 32640..32767 is where a signed int8 split would overflow (high piece 128);
# F = 1023 is the largest contraction the f32 accumulation holds exactly.
@pytest.mark.parametrize("F", [1, 21, 148, 1023])
@pytest.mark.parametrize("values", ["edges", "int8_overflow_band", "random16"])
def test_int_dot_equals_int32_dot(F, values):
    edges = np.array([-32768, 32767, -32767, 32640, -32640, 128, -128, 127,
                      -129, 255, 256, 0, 1, -1], np.int64)
    pools = {"edges": edges,
             "int8_overflow_band": np.concatenate([np.arange(32640, 32768),
                                                   -np.arange(32640, 32769)]),
             "random16": np.arange(-32768, 32768)}
    pool = pools[values]
    a = RNG.choice(pool, (8, F)).astype(np.int32)
    b = RNG.choice(pool, (F, 128)).astype(np.int32)
    a[0], b[:, 0] = -32768, -32768        # largest product, every term
    a[1], b[:, 1] = 32767, -32768
    want = jax.lax.dot_general(jnp.asarray(a), jnp.asarray(b),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)
    np.testing.assert_array_equal(np.asarray(_int_dot_pallas(a, b)),
                                  np.asarray(want))


@pytest.mark.parametrize("frac,total,n_in", [(8, 17, 1), (8, 16, 1004)],
                         ids=["17_bit_format", "F_over_1023"])
def test_fxp_kernel_refuses_inexact_widths(frac, total, n_in):
    """The exact MXU matmul takes at most 16-bit operands over F < 1024
    lanes; anything wider is refused at trace time, never computed wrong."""
    qxs, qw, qb = _fxp_seq_inputs(2, 3, n_in, 20, 16)
    with pytest.raises(ValueError, match="total bits|contraction width"):
        ops.lstm_sequence_fxp(qxs, qw, qb, impl="interpret",
                              frac_bits=frac, total_bits=total)


def test_lstm_sequence_fxp_no_lut_and_seq_output():
    b, t, n_in, h = 3, 7, 1, 20
    qxs, qw, qb = _fxp_seq_inputs(b, t, n_in, h, 16)
    o1 = ops.lstm_sequence_fxp(qxs, qw, qb, impl="ref", return_sequence=True)
    o2 = ops.lstm_sequence_fxp(qxs, qw, qb, impl="interpret", block_b=2,
                               return_sequence=True)
    assert o1[0].shape == (b, t, h)
    for a, e in zip(o1, o2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(e))


# ---------------------------------------------------------------------------
# LUT activation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fn", ["sigmoid", "tanh"])
@pytest.mark.parametrize("depth", [64, 256])
@pytest.mark.parametrize("shape", [(7,), (3, 50), (2, 5, 130)])
@pytest.mark.parametrize("mxu", [True, False])
def test_lut_act_sweep(fn, depth, shape, mxu):
    spec = LutSpec(fn, depth)
    table = build_table(spec)
    lo, hi = spec.bounds
    x = _rand(shape, scale=4.0)
    y1 = ops.lut_act(x, table, lo, hi, impl="ref")
    y2 = ops.lut_act(x, table, lo, hi, impl="interpret", mxu_onehot=mxu,
                     block_rows=8)
    np.testing.assert_allclose(y1, y2, atol=1e-6)


# ---------------------------------------------------------------------------
# fixed-point matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(1, 4, 3), (9, 21, 17), (64, 32, 64),
                                   (130, 21, 20)])
@pytest.mark.parametrize("frac,total", [(8, 16), (4, 8), (12, 16)])
def test_fxp_matmul_sweep(m, k, n, frac, total):
    hi = 2 ** (total - 2)
    aq = jnp.asarray(RNG.integers(-hi, hi, size=(m, k)), jnp.int32)
    bq = jnp.asarray(RNG.integers(-hi, hi, size=(k, n)), jnp.int32)
    bias = jnp.asarray(RNG.integers(-hi // 2, hi // 2, size=(n,)), jnp.int32)
    o1 = ops.fxp_matmul(aq, bq, bias, frac_bits=frac, total_bits=total, impl="ref")
    o2 = ops.fxp_matmul(aq, bq, bias, frac_bits=frac, total_bits=total,
                        impl="interpret", block_m=32, block_n=32)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))


# ---------------------------------------------------------------------------
# SSD chunked scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,h,p,n,chunk", [
    (1, 8, 1, 4, 4, 4), (2, 37, 3, 8, 16, 8), (2, 64, 2, 16, 8, 16),
    (1, 100, 4, 8, 8, 32),
])
def test_ssd_scan_sweep(b, t, h, p, n, chunk):
    x = _rand((b, t, h, p))
    a_log = -jnp.abs(_rand((b, t, h), scale=0.3))
    bb = _rand((b, t, h, n), scale=0.3)
    cc = _rand((b, t, h, n), scale=0.3)
    y1, h1 = ops.ssd_chunk_scan(x, a_log, bb, cc, impl="ref")
    y2, h2 = ops.ssd_chunk_scan(x, a_log, bb, cc, chunk=chunk, impl="interpret")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-5)


def test_ssd_scan_with_initial_state():
    b, t, h, p, n = 2, 16, 2, 4, 8
    x = _rand((b, t, h, p))
    a_log = -jnp.abs(_rand((b, t, h), scale=0.2))
    bb = _rand((b, t, h, n), scale=0.3)
    cc = _rand((b, t, h, n), scale=0.3)
    h0 = _rand((b, h, p, n), scale=0.5)
    y1, hf1 = ops.ssd_chunk_scan(x, a_log, bb, cc, h0, impl="ref")
    y2, hf2 = ops.ssd_chunk_scan(x, a_log, bb, cc, h0, chunk=8, impl="interpret")
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(hf1), np.asarray(hf2), atol=2e-5)


def test_ssd_chunked_pure_jax_matches_ref():
    """models/ssm.ssd_chunked (the dry-run path) against the oracle too."""
    from repro.models.ssm import ssd_chunked
    b, t, h, p, n = 2, 50, 3, 8, 16
    x = _rand((b, t, h, p))
    a_log = -jnp.abs(_rand((b, t, h), scale=0.3))
    bb = _rand((b, t, h, n), scale=0.3)
    cc = _rand((b, t, h, n), scale=0.3)
    y1, h1 = ref.ssd_chunk_scan_ref(x, a_log, bb, cc, 16)
    y2, h2 = ssd_chunked(x, a_log, bb, cc, 16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=2e-5)
