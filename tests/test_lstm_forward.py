"""Unified LSTM dispatcher tests.

Two contracts (ISSUE 1 acceptance criteria):

* ``lstm_sequence_fxp_pallas(interpret=True)`` is *integer-equal* (not
  allclose) to ``lstm_layer_fxp`` across the paper's Fig. 6 ``(x, y)``
  format sweep and Table 1 LUT depths, for multiple sequence lengths.
* ``lstm_forward`` dispatches all four backends through one shared
  signature, with multi-layer stacking and sequence output.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fxp import FxpFormat, quantize
from repro.core.lstm import (LSTM_BACKENDS, LSTMParams, init_lstm_params,
                             lstm_forward, lstm_layer, lstm_layer_fxp)
from repro.core.lut import make_lut_pair
from repro.kernels.lstm_fxp_seq import lstm_sequence_fxp_pallas

RNG = np.random.default_rng(0)

B, N_IN, N_H = 3, 2, 20


def _float_setup(key=0, n_in=N_IN, n_h=N_H, t=7, b=B):
    params = init_lstm_params(jax.random.PRNGKey(key), n_in, n_h)
    xs = jnp.asarray(RNG.normal(size=(b, t, n_in)).astype(np.float32))
    return params, xs


def _quantized(params, xs, fmt):
    qp = LSTMParams(w=quantize(params.w, fmt), b=quantize(params.b, fmt))
    return qp, quantize(xs, fmt)


def _fused_kernel_out(qp, qxs, fmt, luts):
    (sig_t, sig_s), (tanh_t, tanh_s) = luts["sigmoid"], luts["tanh"]
    return lstm_sequence_fxp_pallas(
        qxs, qp.w, qp.b, None, None, sig_t, tanh_t,
        frac_bits=fmt.frac_bits, total_bits=fmt.total_bits,
        sig_lo=sig_s.bounds[0], sig_hi=sig_s.bounds[1],
        tanh_lo=tanh_s.bounds[0], tanh_hi=tanh_s.bounds[1],
        block_b=2, interpret=True)


# ---------------------------------------------------------------------------
# The headline contract: fused fxp sequence kernel == lstm_layer_fxp, bit for
# bit, across formats (Fig. 6 sweep) x LUT depths (Table 1) x seq lengths.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("frac,total", [(8, 16), (6, 12), (12, 16)])
@pytest.mark.parametrize("depth", [64, 256])
@pytest.mark.parametrize("t", [6, 24])
def test_fused_fxp_sequence_bit_exact(frac, total, depth, t):
    fmt = FxpFormat(frac, total)
    params, xs = _float_setup(t=t)
    qp, qxs = _quantized(params, xs, fmt)
    luts = make_lut_pair(depth)

    qh_ref, qc_ref = lstm_layer_fxp(qp, qxs, fmt, luts)
    qh_ker, qc_ker = _fused_kernel_out(qp, qxs, fmt, luts)

    np.testing.assert_array_equal(np.asarray(qh_ref), np.asarray(qh_ker))
    np.testing.assert_array_equal(np.asarray(qc_ref), np.asarray(qc_ker))


def test_fused_fxp_sequence_bit_exact_without_luts():
    """Fig. 6's sweep quantises data but not activations (luts=None)."""
    fmt = FxpFormat(8, 16)
    params, xs = _float_setup(t=6)
    qp, qxs = _quantized(params, xs, fmt)
    qh_ref, qc_ref = lstm_layer_fxp(qp, qxs, fmt, None)
    qh_ker, qc_ker = lstm_sequence_fxp_pallas(qxs, qp.w, qp.b, block_b=2,
                                              interpret=True)
    np.testing.assert_array_equal(np.asarray(qh_ref), np.asarray(qh_ker))
    np.testing.assert_array_equal(np.asarray(qc_ref), np.asarray(qc_ker))


# ---------------------------------------------------------------------------
# Dispatcher: one signature, four backends
# ---------------------------------------------------------------------------

def _forward(backend, params, xs, qp, qxs, fmt, luts, **kw):
    if backend in ("fxp", "pallas_fxp"):
        return lstm_forward(qp, qxs, backend=backend, fmt=fmt, luts=luts,
                            block_b=2, **kw)
    return lstm_forward(params, xs, backend=backend, **kw)


def test_all_backends_dispatch_one_signature():
    fmt = FxpFormat(8, 16)
    params, xs = _float_setup()
    qp, qxs = _quantized(params, xs, fmt)
    luts = make_lut_pair(128)

    outs = {be: _forward(be, params, xs, qp, qxs, fmt, luts)
            for be in LSTM_BACKENDS}
    for be, (h, c) in outs.items():
        assert h.shape == (B, N_H) and c.shape == (B, N_H), be

    # float backends agree numerically
    np.testing.assert_allclose(outs["fused"][0], outs["sequential"][0], atol=1e-5)
    np.testing.assert_allclose(outs["fused"][1], outs["sequential"][1], atol=1e-5)
    # fxp backends agree bitwise
    np.testing.assert_array_equal(np.asarray(outs["fxp"][0]),
                                  np.asarray(outs["pallas_fxp"][0]))
    np.testing.assert_array_equal(np.asarray(outs["fxp"][1]),
                                  np.asarray(outs["pallas_fxp"][1]))


@pytest.mark.parametrize("backend", ["fused", "sequential", "fxp", "pallas_fxp"])
def test_return_sequence_last_step_matches_final_state(backend):
    fmt = FxpFormat(8, 16)
    params, xs = _float_setup()
    qp, qxs = _quantized(params, xs, fmt)
    luts = make_lut_pair(64)
    seq, (h, c) = _forward(backend, params, xs, qp, qxs, fmt, luts,
                           return_sequence=True)
    assert seq.shape == (B, xs.shape[1], N_H)
    np.testing.assert_array_equal(np.asarray(seq[:, -1]), np.asarray(h))


@pytest.mark.parametrize("backend", ["fused", "sequential"])
def test_two_layer_stack_float(backend):
    params, xs = _float_setup()
    p2 = init_lstm_params(jax.random.PRNGKey(1), N_H, N_H)
    stack = [params, p2]
    h, c = lstm_forward(stack, xs, backend=backend, block_b=2, num_layers=2)
    # oracle: layer 1 sees layer 0's full hidden sequence
    seq0, _ = lstm_layer(params, xs, return_sequence=True)
    h_ref, c_ref = lstm_layer(p2, seq0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c), np.asarray(c_ref), atol=1e-5)


def test_two_layer_stack_fxp_bit_exact():
    fmt = FxpFormat(8, 16)
    params, xs = _float_setup()
    p2 = init_lstm_params(jax.random.PRNGKey(1), N_H, N_H)
    qp1, qxs = _quantized(params, xs, fmt)
    qp2 = LSTMParams(w=quantize(p2.w, fmt), b=quantize(p2.b, fmt))
    luts = make_lut_pair(64)
    o_sim = lstm_forward([qp1, qp2], qxs, backend="fxp", fmt=fmt, luts=luts)
    o_ker = lstm_forward([qp1, qp2], qxs, backend="pallas_fxp", fmt=fmt,
                         luts=luts, block_b=2)
    np.testing.assert_array_equal(np.asarray(o_sim[0]), np.asarray(o_ker[0]))
    np.testing.assert_array_equal(np.asarray(o_sim[1]), np.asarray(o_ker[1]))


@pytest.mark.parametrize("n_layers", [1, 2])
def test_pallas_fxp_one_branch_at_every_depth(monkeypatch, n_layers):
    """One layer or a stack, ``pallas_fxp`` takes the one stacked-kernel
    branch: exactly one call of the stack face with every layer, never the
    single-layer face, and the result is integer-equal to ``fxp``."""
    from repro.kernels import lstm_fxp_seq

    fmt = FxpFormat(8, 16)
    params, xs = _float_setup()
    layers = [params] + [init_lstm_params(jax.random.PRNGKey(k), N_H, N_H)
                         for k in range(1, n_layers)]
    qps = [_quantized(p, xs, fmt)[0] for p in layers]
    qxs = quantize(xs, fmt)
    calls = []
    face = lstm_fxp_seq.lstm_sequence_fxp_stack_pallas

    def counted(*a, **kw):
        calls.append(len(a[1]))
        return face(*a, **kw)

    def refused(*a, **kw):
        raise AssertionError("the dispatcher took the single-layer face")

    monkeypatch.setattr(lstm_fxp_seq, "lstm_sequence_fxp_stack_pallas", counted)
    monkeypatch.setattr(lstm_fxp_seq, "lstm_sequence_fxp_pallas", refused)
    got = lstm_forward(qps if n_layers > 1 else qps[0], qxs,
                       backend="pallas_fxp", fmt=fmt, block_b=2)
    assert calls == [n_layers]
    want = lstm_forward(qps, qxs, backend="fxp", fmt=fmt)
    for a, e in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(e))


def test_dispatcher_validation():
    fmt = FxpFormat(8, 16)
    params, xs = _float_setup()
    with pytest.raises(ValueError, match="unknown backend"):
        lstm_forward(params, xs, backend="warp_drive")
    with pytest.raises(ValueError, match="needs fmt"):
        lstm_forward(params, xs, backend="fxp")
    with pytest.raises(TypeError, match="int32 fixed-point"):
        lstm_forward(params, xs, backend="fxp", fmt=fmt)
    with pytest.raises(ValueError, match="num_layers"):
        lstm_forward(params, xs, backend="fused", num_layers=2)


def test_unbatched_input_pallas_backends():
    """An unbatched ``(n_seq, n_in)`` input runs the kernel as a batch of
    one and comes back unbatched, integer-equal to the ``fxp`` oracle, with
    and without a sequence output."""
    fmt = FxpFormat(8, 16)
    params, xs = _float_setup()
    qp, qxs = _quantized(params, xs, fmt)
    luts = make_lut_pair(64)
    for kw in ({}, {"return_sequence": True}):
        want = lstm_forward(qp, qxs[0], backend="fxp", fmt=fmt, luts=luts, **kw)
        got = lstm_forward(qp, qxs[0], backend="pallas_fxp", fmt=fmt,
                           luts=luts, block_b=2, **kw)
        h = got[1][0] if kw else got[0]
        assert h.shape == (N_H,)
        for a, e in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            assert a.shape == e.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(e))


def test_extra_leading_batch_dims_fold_into_pallas_batch():
    """(..., n_seq, n_in) holds for every backend: ``pallas_fxp`` folds the
    leading dims into the kernel's one batch axis and unfolds on the way
    out — the same integers as the flat batch and as the ``fxp`` oracle."""
    fmt = FxpFormat(8, 16)
    params, _ = _float_setup()
    xs4 = jnp.asarray(RNG.normal(size=(2, 3, 7, N_IN)).astype(np.float32))
    qp, qxs4 = _quantized(params, xs4, fmt)
    h, c = lstm_forward(qp, qxs4, backend="pallas_fxp", fmt=fmt, block_b=2)
    assert h.shape == (2, 3, N_H) and c.shape == (2, 3, N_H)
    h_flat, c_flat = lstm_forward(qp, qxs4.reshape(6, 7, N_IN),
                                  backend="pallas_fxp", fmt=fmt, block_b=2)
    np.testing.assert_array_equal(np.asarray(h).reshape(6, N_H),
                                  np.asarray(h_flat))
    np.testing.assert_array_equal(np.asarray(c).reshape(6, N_H),
                                  np.asarray(c_flat))
    a = lstm_forward(qp, qxs4, backend="fxp", fmt=fmt)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(h))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(c))


def test_per_layer_initial_state_list_unbatched_input():
    fmt = FxpFormat(8, 16)
    params, xs = _float_setup()
    p2 = init_lstm_params(jax.random.PRNGKey(1), N_H, N_H)
    qp1, qxs = _quantized(params, xs, fmt)
    qp2 = LSTMParams(w=quantize(p2.w, fmt), b=quantize(p2.b, fmt))
    h0 = [quantize(jnp.full((N_H,), 0.1), fmt),
          quantize(jnp.full((N_H,), -0.1), fmt)]
    c0 = [jnp.zeros((N_H,), jnp.int32), quantize(jnp.full((N_H,), 0.2), fmt)]
    h_ref, c_ref = lstm_forward([qp1, qp2], qxs[0], backend="fxp", fmt=fmt,
                                h0=h0, c0=c0)
    h, c = lstm_forward([qp1, qp2], qxs[0], backend="pallas_fxp", fmt=fmt,
                        h0=h0, c0=c0, block_b=2)
    assert h.shape == (N_H,) and c.shape == (N_H,)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c_ref))

# ---------------------------------------------------------------------------
# Mixed precision + heterogeneous hidden sizes through the FUSED stack kernel
# (the tentpole contract: no layer-by-layer fallback, integer-equal to the
# per-layer lstm_layer_fxp + fxp_convert oracle)
# ---------------------------------------------------------------------------


def _mixed_formats():
    from repro.core.fxp import GateFormats, LayerFormats, StackFormats

    return StackFormats((
        LayerFormats(FxpFormat(8, 16),
                     GateFormats(FxpFormat(7, 14), FxpFormat(8, 16),
                                 FxpFormat(6, 12), FxpFormat(8, 15))),
        LayerFormats(FxpFormat(6, 12),
                     GateFormats(FxpFormat(6, 12), FxpFormat(5, 11),
                                 FxpFormat(6, 13), FxpFormat(6, 12))),
    ))


def _mixed_stack_setup(h_sizes, sf, key=5, t=9, b=3, n_in=4):
    rng = np.random.default_rng(key)
    qps = []
    fan = n_in
    for li, h in enumerate(h_sizes):
        frac = sf[li].data.frac_bits
        qps.append(LSTMParams(
            w=jnp.asarray(rng.integers(-1 << frac, 1 << frac,
                                       (fan + h, 4 * h)), jnp.int32),
            b=jnp.asarray(rng.integers(-1 << (frac - 1), 1 << (frac - 1),
                                       (4 * h,)), jnp.int32)))
        fan = h
    in_frac = sf.in_fmt.frac_bits
    qxs = jnp.asarray(rng.integers(-2 << in_frac, 2 << in_frac,
                                   (b, t, n_in)), jnp.int32)
    return qps, qxs


def _stack_oracle(qps, qxs, sf, luts):
    """Layer-by-layer lstm_layer_fxp at each layer's own formats, chained
    with the inter-layer fxp_convert — the ground truth the fused kernel
    must reproduce integer for integer."""
    from repro.core import fxp as fxp_mod
    from repro.core.lstm import lstm_layer_fxp

    seq, hs, cs = qxs, [], []
    for li, qp in enumerate(qps):
        seq, (h, c) = lstm_layer_fxp(qp, seq, sf[li], luts,
                                     return_sequence=True)
        hs.append(h)
        cs.append(c)
        if li + 1 < len(qps):
            seq = fxp_mod.fxp_convert(seq, sf[li].data, sf[li + 1].data)
    return seq, hs, cs


@pytest.mark.parametrize("h_sizes", [(10, 10), (10, 6), (6, 10)])
@pytest.mark.parametrize("time_tile", [None, 3])
def test_mixed_stack_kernel_bit_exact(h_sizes, time_tile):
    """Fused stack kernel == per-layer oracle for uniform and heterogeneous
    hidden sizes under per-layer/per-gate formats (padded lanes masked)."""
    sf = _mixed_formats()
    luts = make_lut_pair(64)
    qps, qxs = _mixed_stack_setup(h_sizes, sf)
    seq_ref, hs_ref, cs_ref = _stack_oracle(qps, qxs, sf, luts)
    seq, (hs, cs) = lstm_forward(qps, qxs, backend="pallas_fxp", fmt=sf,
                                 luts=luts, return_sequence=True,
                                 return_state="all", block_b=3,
                                 time_tile=time_tile, interpret=True)
    np.testing.assert_array_equal(np.asarray(seq), np.asarray(seq_ref))
    for li in range(len(h_sizes)):
        np.testing.assert_array_equal(np.asarray(hs[li]),
                                      np.asarray(hs_ref[li]),
                                      err_msg=f"layer {li} h ({h_sizes})")
        np.testing.assert_array_equal(np.asarray(cs[li]),
                                      np.asarray(cs_ref[li]),
                                      err_msg=f"layer {li} c ({h_sizes})")


def test_mixed_stack_kernel_nonzero_state_and_no_luts():
    """Hetero-H + mixed formats with nonzero per-layer initial state, and
    the luts=None (full-precision activations) path."""
    sf = _mixed_formats()
    h_sizes = (10, 6)
    rng = np.random.default_rng(9)
    qps, qxs = _mixed_stack_setup(h_sizes, sf, key=9)
    h0 = [jnp.asarray(rng.integers(-200, 200, (3, h)), jnp.int32)
          for h in h_sizes]
    c0 = [jnp.asarray(rng.integers(-200, 200, (3, h)), jnp.int32)
          for h in h_sizes]
    for luts in (make_lut_pair(64), None):
        seq_ref, hs_ref, cs_ref = qxs, [], []
        from repro.core import fxp as fxp_mod
        from repro.core.lstm import lstm_layer_fxp
        seq_ref = qxs
        for li, qp in enumerate(qps):
            seq_ref, (h, c) = lstm_layer_fxp(
                qp, seq_ref, sf[li], luts, qh0=h0[li], qc0=c0[li],
                return_sequence=True)
            hs_ref.append(h)
            cs_ref.append(c)
            if li + 1 < len(qps):
                seq_ref = fxp_mod.fxp_convert(seq_ref, sf[li].data,
                                              sf[li + 1].data)
        seq, (hs, cs) = lstm_forward(qps, qxs, backend="pallas_fxp", fmt=sf,
                                     luts=luts, h0=h0, c0=c0,
                                     return_sequence=True, return_state="all",
                                     block_b=3, interpret=True)
        np.testing.assert_array_equal(np.asarray(seq), np.asarray(seq_ref))
        for li in range(len(h_sizes)):
            np.testing.assert_array_equal(np.asarray(hs[li]),
                                          np.asarray(hs_ref[li]))
            np.testing.assert_array_equal(np.asarray(cs[li]),
                                          np.asarray(cs_ref[li]))


def test_hetero_h_stack_no_fallback_in_fxp_and_pallas():
    """A hetero-H stack under ONE global format: both fxp backends agree
    (the dispatcher routes multi-layer pallas_fxp through the fused stack
    kernel even when hidden sizes differ — the old fallback is gone)."""
    fmt = FxpFormat(8, 16)
    from repro.core.fxp import StackFormats
    sf = StackFormats.uniform(fmt, 2)
    qps, qxs = _mixed_stack_setup((12, 5), sf, key=13)
    luts = make_lut_pair(64)
    seq_a, (hs_a, cs_a) = lstm_forward(qps, qxs, backend="fxp", fmt=fmt,
                                       luts=luts, return_sequence=True,
                                       return_state="all")
    seq_b, (hs_b, cs_b) = lstm_forward(qps, qxs, backend="pallas_fxp",
                                       fmt=fmt, luts=luts,
                                       return_sequence=True,
                                       return_state="all", block_b=3,
                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(seq_a), np.asarray(seq_b))
    for li in range(2):
        np.testing.assert_array_equal(np.asarray(hs_a[li]), np.asarray(hs_b[li]))
        np.testing.assert_array_equal(np.asarray(cs_a[li]), np.asarray(cs_b[li]))
