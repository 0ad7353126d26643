"""Cross-backend equivalence property suite (ISSUE 2).

One API, four datapaths: every ``lstm_forward`` backend must agree on every
shape.  Two contract classes:

* float backends (``sequential``, ``fused``) agree to float tolerance;
* fxp backends (``fxp``, ``pallas_fxp`` — un-tiled *and* time-tiled) are
  *integer-equal*, pairwise, including ``n_seq >> time_tile`` (the
  acceptance criterion is n_seq at least 8x the tile), ragged tails, and
  hidden sizes that are not a multiple of any TPU tile (the ROADMAP
  tile-alignment item — padding logic must not leak into the integers).

The deterministic sweep below always runs (tier-1); the hypothesis sweep at
the bottom widens it to randomly-drawn shapes/formats and is marked ``slow``
(skipped automatically when hypothesis is not installed, see
``_hypothesis_compat``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core.fxp import FxpFormat, quantize
from repro.core.lstm import (LSTM_BACKENDS, GRUParams, LSTMParams,
                             gru_forward, init_gru_params, init_lstm_params,
                             lstm_forward)
from repro.core.lut import make_lut_pair

RNG = np.random.default_rng(42)

FLOAT_BACKENDS = ("sequential", "fused")
FXP_BACKENDS = ("fxp", "pallas_fxp")


def _setup(n_in, n_h, t, b, key=0):
    params = init_lstm_params(jax.random.PRNGKey(key), n_in, n_h)
    xs = jnp.asarray(RNG.normal(size=(b, t, n_in)).astype(np.float32))
    return params, xs


def _quantized(params, xs, fmt):
    qp = LSTMParams(w=quantize(params.w, fmt), b=quantize(params.b, fmt))
    return qp, quantize(xs, fmt)


def _fxp_outputs(qp, qxs, fmt, luts, time_tile=None, return_sequence=False):
    """(backend label -> output) for every fxp datapath variant."""
    outs = {
        "fxp": lstm_forward(qp, qxs, backend="fxp", fmt=fmt, luts=luts,
                            return_sequence=return_sequence),
        "pallas_fxp": lstm_forward(qp, qxs, backend="pallas_fxp", fmt=fmt,
                                   luts=luts, block_b=2,
                                   return_sequence=return_sequence),
    }
    if time_tile is not None:
        outs[f"pallas_fxp/tt{time_tile}"] = lstm_forward(
            qp, qxs, backend="pallas_fxp", fmt=fmt, luts=luts, block_b=2,
            time_tile=time_tile, return_sequence=return_sequence)
    return outs


def _assert_int_equal_pairwise(outs: dict):
    names = list(outs)
    ref_name = names[0]
    ref = jax.tree.leaves(outs[ref_name])
    for name in names[1:]:
        for a, b in zip(ref, jax.tree.leaves(outs[name])):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"{ref_name} != {name}")


# ---------------------------------------------------------------------------
# Deterministic sweep (tier-1): shapes chosen to hit the acceptance criteria
# ---------------------------------------------------------------------------

# (n_seq, n_h, batch, time_tile): 8x-tile long sequence, ragged tails,
# batch-1, H not a multiple of any MXU/VPU tile width.
FXP_SHAPES = [
    (32, 20, 3, 4),      # n_seq = 8 x time_tile (acceptance criterion)
    (33, 20, 3, 4),      # + ragged tail (33 % 4 != 0)
    (17, 33, 2, 5),      # H=33: not a multiple of 8/128; ragged tail
    (9, 10, 1, None),    # un-tiled, batch 1
    (12, 8, 4, 12),      # tile == n_seq (degenerate tiling)
]


@pytest.mark.parametrize("n_seq,n_h,b,tile", FXP_SHAPES)
@pytest.mark.parametrize("frac,total", [(8, 16), (6, 12)])
def test_fxp_backends_integer_equal(n_seq, n_h, b, tile, frac, total):
    fmt = FxpFormat(frac, total)
    params, xs = _setup(2, n_h, n_seq, b)
    qp, qxs = _quantized(params, xs, fmt)
    luts = make_lut_pair(64)
    _assert_int_equal_pairwise(_fxp_outputs(qp, qxs, fmt, luts, tile))


@pytest.mark.parametrize("n_seq,n_h,b,tile", [(32, 20, 3, 4), (17, 33, 2, 5)])
def test_fxp_backends_integer_equal_with_sequence(n_seq, n_h, b, tile):
    """return_sequence=True: per-step hidden states are also integer-equal
    (the inter-layer traffic of stacked models rides on these)."""
    fmt = FxpFormat(8, 16)
    params, xs = _setup(2, n_h, n_seq, b)
    qp, qxs = _quantized(params, xs, fmt)
    luts = make_lut_pair(64)
    outs = _fxp_outputs(qp, qxs, fmt, luts, tile, return_sequence=True)
    _assert_int_equal_pairwise(outs)
    seq, (h, _) = outs["fxp"]
    assert seq.shape == (b, n_seq, n_h)
    np.testing.assert_array_equal(np.asarray(seq[:, -1]), np.asarray(h))


def test_fxp_backends_integer_equal_without_luts():
    """Fig. 6's sweep quantises data but not activations (luts=None)."""
    fmt = FxpFormat(8, 16)
    params, xs = _setup(2, 20, 32, 3)
    qp, qxs = _quantized(params, xs, fmt)
    _assert_int_equal_pairwise(_fxp_outputs(qp, qxs, fmt, None, time_tile=4))


@pytest.mark.parametrize("n_seq,n_h,b", [(7, 20, 3), (26, 33, 2)])
def test_float_backends_allclose_pairwise(n_seq, n_h, b):
    params, xs = _setup(2, n_h, n_seq, b)
    outs = {be: lstm_forward(params, xs, backend=be) for be in FLOAT_BACKENDS}
    for be in FLOAT_BACKENDS[1:]:
        for a, o in zip(outs[FLOAT_BACKENDS[0]], outs[be]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(o),
                                       atol=1e-5, err_msg=be)


def test_all_six_backends_one_shape():
    """The full backend matrix (all four backends) on one shape: every
    backend produces the right shape; float family allclose, fxp family
    integer-equal."""
    fmt = FxpFormat(8, 16)
    params, xs = _setup(2, 20, 16, 3)
    qp, qxs = _quantized(params, xs, fmt)
    luts = make_lut_pair(128)
    outs = {}
    for be in LSTM_BACKENDS:
        if be in FXP_BACKENDS:
            outs[be] = lstm_forward(
                qp, qxs, backend=be, fmt=fmt, luts=luts, block_b=2,
                time_tile=4 if be == "pallas_fxp" else None)
        else:
            outs[be] = lstm_forward(params, xs, backend=be)
        h, c = outs[be]
        assert h.shape == (3, 20) and c.shape == (3, 20), be
    for a, o in zip(outs["sequential"], outs["fused"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(o), atol=1e-5)
    _assert_int_equal_pairwise({be: outs[be] for be in FXP_BACKENDS})


def test_time_tiled_multi_layer_stack_integer_equal():
    """Stacked layers through the tiled kernel: inter-layer sequences flow
    through the time-tiled path and the result still matches the simulator."""
    fmt = FxpFormat(8, 16)
    params, xs = _setup(2, 12, 24, 3)
    p2 = init_lstm_params(jax.random.PRNGKey(7), 12, 12)
    qp1, qxs = _quantized(params, xs, fmt)
    qp2 = LSTMParams(w=quantize(p2.w, fmt), b=quantize(p2.b, fmt))
    luts = make_lut_pair(64)
    a = lstm_forward([qp1, qp2], qxs, backend="fxp", fmt=fmt, luts=luts)
    b = lstm_forward([qp1, qp2], qxs, backend="pallas_fxp", fmt=fmt,
                     luts=luts, block_b=2, time_tile=3)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


def _stack_params(n_layers, n_in, n_h, fmt, key=0):
    qps = []
    for li in range(n_layers):
        p = init_lstm_params(jax.random.PRNGKey(key + li),
                             n_in if li == 0 else n_h, n_h)
        qps.append(LSTMParams(w=quantize(p.w, fmt), b=quantize(p.b, fmt)))
    return qps


# (L, n_seq, n_h, b, time_tile): stacked depth x ragged tails x odd H
STACK_SHAPES = [
    (2, 24, 12, 3, 4),
    (2, 17, 33, 2, 5),     # H=33 not tile-aligned, ragged tail
    (3, 16, 10, 2, None),  # un-tiled 3-deep stack
]


@pytest.mark.parametrize("n_layers,n_seq,n_h,b,tile", STACK_SHAPES)
def test_stacked_all_layer_state_integer_equal(n_layers, n_seq, n_h, b, tile):
    """return_state="all": every layer's (h, c) integer-equal between the
    fxp simulator and the fused multi-layer Pallas kernel (which keeps the
    inter-layer hidden sequence in VMEM)."""
    fmt = FxpFormat(8, 16)
    qps = _stack_params(n_layers, 2, n_h, fmt)
    xs = jnp.asarray(RNG.normal(size=(b, n_seq, 2)).astype(np.float32))
    qxs = quantize(xs, fmt)
    luts = make_lut_pair(64)
    outs = {
        be: lstm_forward(qps, qxs, backend=be, fmt=fmt, luts=luts, block_b=2,
                         time_tile=tile if be == "pallas_fxp" else None,
                         return_sequence=True, return_state="all")
        for be in FXP_BACKENDS
    }
    _assert_int_equal_pairwise(outs)
    seq, (hs, cs) = outs["fxp"]
    assert len(hs) == len(cs) == n_layers
    assert seq.shape == (b, n_seq, n_h)
    np.testing.assert_array_equal(np.asarray(seq[:, -1]), np.asarray(hs[-1]))


@pytest.mark.parametrize("n_layers,n_seq,n_h,b,tile", STACK_SHAPES)
@pytest.mark.parametrize("backend", FXP_BACKENDS)
def test_stacked_chunked_continuation_integer_equal(n_layers, n_seq, n_h, b,
                                                    tile, backend):
    """The tentpole contract: two half-sequence calls with carried ALL-layer
    state are integer-equal to one full call — exactly what the fleet engine
    relies on to serve stacked models in chunks."""
    fmt = FxpFormat(8, 16)
    qps = _stack_params(n_layers, 2, n_h, fmt, key=3)
    xs = jnp.asarray(RNG.normal(size=(b, n_seq, 2)).astype(np.float32))
    qxs = quantize(xs, fmt)
    luts = make_lut_pair(64)
    kw = dict(backend=backend, fmt=fmt, luts=luts, block_b=2,
              time_tile=tile if backend == "pallas_fxp" else None)

    seq_full, (hs_full, cs_full) = lstm_forward(
        qps, qxs, return_sequence=True, return_state="all", **kw)

    cut = n_seq // 2
    seq_a, (hs_a, cs_a) = lstm_forward(
        qps, qxs[:, :cut], return_sequence=True, return_state="all", **kw)
    seq_b, (hs_b, cs_b) = lstm_forward(
        qps, qxs[:, cut:], h0=hs_a, c0=cs_a,
        return_sequence=True, return_state="all", **kw)

    np.testing.assert_array_equal(
        np.concatenate([np.asarray(seq_a), np.asarray(seq_b)], axis=1),
        np.asarray(seq_full))
    for li in range(n_layers):
        np.testing.assert_array_equal(np.asarray(hs_b[li]),
                                      np.asarray(hs_full[li]),
                                      err_msg=f"layer {li} h")
        np.testing.assert_array_equal(np.asarray(cs_b[li]),
                                      np.asarray(cs_full[li]),
                                      err_msg=f"layer {li} c")


@pytest.mark.parametrize("tile", [None, 4])
def test_heterogeneous_h_stack_fallback_integer_equal(tile):
    """ROADMAP open item: stacks with MIXED hidden sizes cannot fuse into
    ``lstm_sequence_fxp_stack_pallas`` (its state buffer is (L, B, H)) and
    must fall back to layer-by-layer — that fallback path must stay
    integer-equal to ``lstm_layer_fxp`` chained per layer, tiled or not."""
    from repro.core.lstm import lstm_layer_fxp

    fmt = FxpFormat(8, 16)
    sizes = [(2, 12), (12, 8), (8, 20)]     # H = 12 -> 8 -> 20
    qps = []
    for li, (n_in, n_h) in enumerate(sizes):
        p = init_lstm_params(jax.random.PRNGKey(11 + li), n_in, n_h)
        qps.append(LSTMParams(w=quantize(p.w, fmt), b=quantize(p.b, fmt)))
    xs = jnp.asarray(RNG.normal(size=(3, 14, 2)).astype(np.float32))
    qxs = quantize(xs, fmt)
    luts = make_lut_pair(64)

    # oracle: the readable per-layer simulator, chained by hand
    seq_ref = qxs
    hs_ref, cs_ref = [], []
    for qp in qps:
        seq_ref, (qh, qc) = lstm_layer_fxp(qp, seq_ref, fmt, luts,
                                           return_sequence=True)
        hs_ref.append(qh)
        cs_ref.append(qc)

    for backend in FXP_BACKENDS:
        seq, (hs, cs) = lstm_forward(
            qps, qxs, backend=backend, fmt=fmt, luts=luts, block_b=2,
            time_tile=tile if backend == "pallas_fxp" else None,
            return_sequence=True, return_state="all")
        np.testing.assert_array_equal(np.asarray(seq), np.asarray(seq_ref),
                                      err_msg=f"{backend} top h_seq")
        for li in range(len(qps)):
            np.testing.assert_array_equal(
                np.asarray(hs[li]), np.asarray(hs_ref[li]),
                err_msg=f"{backend} layer {li} h")
            np.testing.assert_array_equal(
                np.asarray(cs[li]), np.asarray(cs_ref[li]),
                err_msg=f"{backend} layer {li} c")
        assert [h.shape[-1] for h in hs] == [12, 8, 20], backend


def test_stacked_state_accepts_stacked_array():
    """h0/c0 may be one (L, B, H) array instead of per-layer lists."""
    fmt = FxpFormat(8, 16)
    qps = _stack_params(2, 2, 10, fmt, key=5)
    xs = jnp.asarray(RNG.normal(size=(2, 8, 2)).astype(np.float32))
    qxs = quantize(xs, fmt)
    rng = np.random.default_rng(0)
    h0 = jnp.asarray(rng.integers(-40, 40, (2, 2, 10)), jnp.int32)
    c0 = jnp.asarray(rng.integers(-40, 40, (2, 2, 10)), jnp.int32)
    a = lstm_forward(qps, qxs, backend="fxp", fmt=fmt,
                     h0=h0, c0=c0, return_state="all")
    bk = lstm_forward(qps, qxs, backend="fxp", fmt=fmt,
                      h0=[h0[0], h0[1]], c0=[c0[0], c0[1]],
                      return_state="all")
    _assert_int_equal_pairwise({"stacked-array": a, "per-layer-list": bk})
    # a (B, H) single-layer-convention array must NOT be mistaken for a
    # stacked (L, ...) one when B == L: the rank check rejects it loudly
    with pytest.raises(ValueError, match="per-layer h0/c0"):
        lstm_forward(qps, qxs, backend="fxp", fmt=fmt, h0=h0[0], c0=c0[0])


def test_return_state_top_is_backward_compatible():
    """Default return_state="top" keeps the historical (h_T, c_T) contract,
    equal to the last element of the "all" lists."""
    fmt = FxpFormat(8, 16)
    qps = _stack_params(2, 2, 10, fmt, key=6)
    xs = jnp.asarray(RNG.normal(size=(2, 8, 2)).astype(np.float32))
    qxs = quantize(xs, fmt)
    h_top, c_top = lstm_forward(qps, qxs, backend="fxp", fmt=fmt)
    hs, cs = lstm_forward(qps, qxs, backend="fxp", fmt=fmt,
                          return_state="all")
    np.testing.assert_array_equal(np.asarray(h_top), np.asarray(hs[-1]))
    np.testing.assert_array_equal(np.asarray(c_top), np.asarray(cs[-1]))
    with pytest.raises(ValueError, match="return_state"):
        lstm_forward(qps, qxs, backend="fxp", fmt=fmt, return_state="bottom")


def test_time_tile_validation():
    fmt = FxpFormat(8, 16)
    params, xs = _setup(2, 8, 6, 2)
    qp, qxs = _quantized(params, xs, fmt)
    with pytest.raises(ValueError, match="time_tile"):
        lstm_forward(qp, qxs, backend="pallas_fxp", fmt=fmt, time_tile=0)


# ---------------------------------------------------------------------------
# GRU rows (ISSUE 8): the same contracts through the cell-generic datapath
# ---------------------------------------------------------------------------


def _gru_setup(n_in, n_h, t, b, key=0):
    params = init_gru_params(jax.random.PRNGKey(key), n_in, n_h)
    xs = jnp.asarray(RNG.normal(size=(b, t, n_in)).astype(np.float32))
    return params, xs


def _gru_quantized(params, xs, fmt):
    qp = GRUParams(w=quantize(params.w, fmt), b=quantize(params.b, fmt))
    return qp, quantize(xs, fmt)


def _gru_fxp_outputs(qp, qxs, fmt, luts, time_tile=None,
                     return_sequence=False):
    outs = {
        "fxp": gru_forward(qp, qxs, backend="fxp", fmt=fmt, luts=luts,
                           return_sequence=return_sequence),
        "pallas_fxp": gru_forward(qp, qxs, backend="pallas_fxp", fmt=fmt,
                                  luts=luts, block_b=2,
                                  return_sequence=return_sequence),
    }
    if time_tile is not None:
        outs[f"pallas_fxp/tt{time_tile}"] = gru_forward(
            qp, qxs, backend="pallas_fxp", fmt=fmt, luts=luts, block_b=2,
            time_tile=time_tile, return_sequence=return_sequence)
    return outs


@pytest.mark.cells
@pytest.mark.parametrize("n_seq,n_h,b,tile", FXP_SHAPES)
@pytest.mark.parametrize("frac,total", [(8, 16), (6, 12)])
def test_gru_fxp_backends_integer_equal(n_seq, n_h, b, tile, frac, total):
    fmt = FxpFormat(frac, total)
    params, xs = _gru_setup(2, n_h, n_seq, b)
    qp, qxs = _gru_quantized(params, xs, fmt)
    luts = make_lut_pair(64)
    _assert_int_equal_pairwise(_gru_fxp_outputs(qp, qxs, fmt, luts, tile))


@pytest.mark.cells
@pytest.mark.parametrize("n_seq,n_h,b,tile", [(32, 20, 3, 4), (17, 33, 2, 5)])
def test_gru_fxp_backends_integer_equal_with_sequence(n_seq, n_h, b, tile):
    fmt = FxpFormat(8, 16)
    params, xs = _gru_setup(2, n_h, n_seq, b)
    qp, qxs = _gru_quantized(params, xs, fmt)
    luts = make_lut_pair(64)
    outs = _gru_fxp_outputs(qp, qxs, fmt, luts, tile, return_sequence=True)
    _assert_int_equal_pairwise(outs)
    seq, h = outs["fxp"]
    assert seq.shape == (b, n_seq, n_h)
    np.testing.assert_array_equal(np.asarray(seq[:, -1]), np.asarray(h))


@pytest.mark.cells
@pytest.mark.parametrize("n_seq,n_h,b", [(7, 20, 3), (26, 33, 2)])
def test_gru_float_backends_allclose_pairwise(n_seq, n_h, b):
    params, xs = _gru_setup(2, n_h, n_seq, b)
    outs = {be: gru_forward(params, xs, backend=be)
            for be in FLOAT_BACKENDS}
    for be in FLOAT_BACKENDS[1:]:
        np.testing.assert_allclose(
            np.asarray(outs[FLOAT_BACKENDS[0]]), np.asarray(outs[be]),
            atol=1e-5, err_msg=be)


@pytest.mark.cells
@pytest.mark.parametrize("backend", FXP_BACKENDS)
def test_gru_stacked_chunked_continuation_integer_equal(backend):
    """Single-state chunked serving: two half-sequence calls with carried
    all-layer h are integer-equal to one full call (the fleet-engine
    contract, GRU edition — no c to carry)."""
    fmt = FxpFormat(8, 16)
    n_h, n_seq, b = 12, 24, 3
    qps = []
    for li in range(2):
        p = init_gru_params(jax.random.PRNGKey(3 + li),
                            2 if li == 0 else n_h, n_h)
        qps.append(GRUParams(w=quantize(p.w, fmt), b=quantize(p.b, fmt)))
    xs = jnp.asarray(RNG.normal(size=(b, n_seq, 2)).astype(np.float32))
    qxs = quantize(xs, fmt)
    luts = make_lut_pair(64)
    kw = dict(backend=backend, fmt=fmt, luts=luts, block_b=2,
              time_tile=4 if backend == "pallas_fxp" else None)

    seq_full, hs_full = gru_forward(qps, qxs, return_sequence=True,
                                    return_state="all", **kw)
    cut = n_seq // 2
    seq_a, hs_a = gru_forward(qps, qxs[:, :cut], return_sequence=True,
                              return_state="all", **kw)
    seq_b, hs_b = gru_forward(qps, qxs[:, cut:], h0=hs_a,
                              return_sequence=True, return_state="all", **kw)

    np.testing.assert_array_equal(
        np.concatenate([np.asarray(seq_a), np.asarray(seq_b)], axis=1),
        np.asarray(seq_full))
    for li in range(2):
        np.testing.assert_array_equal(np.asarray(hs_b[li]),
                                      np.asarray(hs_full[li]),
                                      err_msg=f"layer {li} h")


# ---------------------------------------------------------------------------
# Hypothesis sweep (slow tier): randomly drawn shapes x formats x tiles
# ---------------------------------------------------------------------------

pytestmark_note = "hypothesis sweeps ride the slow tier; see scripts/ci.sh"

if HAVE_HYPOTHESIS:
    from hypothesis import HealthCheck

    _SWEEP = dict(
        n_seq=st.integers(1, 40),
        n_h=st.integers(1, 36),
        n_in=st.integers(1, 5),
        b=st.integers(1, 4),
        frac=st.integers(4, 12),
        tile=st.sampled_from([None, 1, 3, 4, 8]),
        depth=st.sampled_from([64, 256]),
    )
    _SETTINGS = settings(max_examples=30, deadline=None,
                         suppress_health_check=[HealthCheck.too_slow])
else:  # the stub's @given skips the test before a strategy is drawn
    _SWEEP = dict(n_seq=None, n_h=None, n_in=None, b=None, frac=None,
                  tile=None, depth=None)
    _SETTINGS = settings()


@pytest.mark.slow
@_SETTINGS
@given(**_SWEEP)
def test_property_fxp_backends_integer_equal(n_seq, n_h, n_in, b, frac, tile, depth):
    fmt = FxpFormat(frac, 16)
    rng = np.random.default_rng(n_seq * 1000 + n_h * 10 + b)
    params = init_lstm_params(jax.random.PRNGKey(frac), n_in, n_h)
    xs = jnp.asarray(rng.normal(size=(b, n_seq, n_in)).astype(np.float32))
    qp, qxs = _quantized(params, xs, fmt)
    luts = make_lut_pair(depth)
    _assert_int_equal_pairwise(_fxp_outputs(qp, qxs, fmt, luts, tile))


@pytest.mark.slow
@pytest.mark.cells
@_SETTINGS
@given(**_SWEEP)
def test_property_gru_fxp_backends_integer_equal(n_seq, n_h, n_in, b, frac,
                                                 tile, depth):
    fmt = FxpFormat(frac, 16)
    rng = np.random.default_rng(n_seq * 999 + n_h * 11 + b)
    params = init_gru_params(jax.random.PRNGKey(frac), n_in, n_h)
    xs = jnp.asarray(rng.normal(size=(b, n_seq, n_in)).astype(np.float32))
    qp, qxs = _gru_quantized(params, xs, fmt)
    luts = make_lut_pair(depth)
    _assert_int_equal_pairwise(_gru_fxp_outputs(qp, qxs, fmt, luts, tile))


@pytest.mark.slow
@_SETTINGS
@given(**{k: _SWEEP[k] for k in ("n_seq", "n_h", "n_in", "b")})
def test_property_float_backends_allclose(n_seq, n_h, n_in, b):
    rng = np.random.default_rng(n_seq + 97 * n_h)
    params = init_lstm_params(jax.random.PRNGKey(1), n_in, n_h)
    xs = jnp.asarray(rng.normal(size=(b, n_seq, n_in)).astype(np.float32))
    ref = lstm_forward(params, xs, backend="fused")
    out = lstm_forward(params, xs, backend="sequential")
    for a, o in zip(ref, out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(o), atol=1e-5)
