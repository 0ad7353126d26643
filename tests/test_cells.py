"""Cell-generic datapath suite (ISSUE 8).

Two contract classes:

* **API surface** — ``lstm_forward`` is now a shim over
  ``recurrent_forward(LSTM_CELL, ...)``; its public signature, the
  ``LSTMParams`` field set and the ``LSTM_BACKENDS`` tuple are pinned here
  so the refactor stays invisible to existing callers.

* **GRU exactness** — the fxp GRU is integer-equal to
  ``kernels.ref.gru_sequence_fxp_ref`` through every face of the stack:
  the simulator, PTQ (``quantize_lstm_model``), QAT -> freeze, and the
  backend dispatcher (an unregistered backend refuses loudly, the
  single-state cell rejects ``c0``).

Everything here is fast; the wide randomly-drawn GRU sweeps live in
``test_backend_equiv.py`` on the slow tier.
"""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cell import CELL_SPECS, GRU_CELL, LSTM_CELL, CellSpec, cell_spec
from repro.core.fxp import FxpFormat, quantize
from repro.core.lstm import (LSTM_BACKENDS, RECURRENT_BACKENDS, GRUParams,
                             LSTMParams, gru_forward, gru_layer_fxp,
                             init_gru_params, init_recurrent_params,
                             lstm_forward, recurrent_forward)
from repro.core.lut import make_lut_pair
from repro.core.quantize import (model_cell_kind, quantize_lstm_model,
                                 quantized_lstm_forward)
from repro.kernels.ref import gru_sequence_fxp_ref

pytestmark = pytest.mark.cells

RNG = np.random.default_rng(88)
FMT = FxpFormat(8, 16)


# ---------------------------------------------------------------------------
# API-surface guard: the refactor must be invisible to lstm_forward callers
# ---------------------------------------------------------------------------

# the committed public signature of lstm_forward — parameter names in order.
# If this test fails, the change is an API break, not a refactor.
LSTM_FORWARD_PARAMS = (
    "params", "xs", "backend", "fmt", "luts", "h0", "c0",
    "return_sequence", "return_state", "num_layers", "interpret",
    "block_b", "time_tile",
)


def test_lstm_forward_signature_is_unchanged():
    sig = inspect.signature(lstm_forward)
    assert tuple(sig.parameters) == LSTM_FORWARD_PARAMS
    # everything after xs stays keyword-only
    for name in LSTM_FORWARD_PARAMS[2:]:
        assert sig.parameters[name].kind is inspect.Parameter.KEYWORD_ONLY, name
    # defaults that existing callers rely on
    assert sig.parameters["backend"].default == "fused"
    assert sig.parameters["return_state"].default == "top"
    assert sig.parameters["return_sequence"].default is False


def test_lstm_public_types_are_unchanged():
    assert [f.name for f in dataclasses.fields(LSTMParams)] == ["w", "b"]
    assert LSTM_BACKENDS == ("sequential", "fused", "fxp", "pallas_fxp")
    assert RECURRENT_BACKENDS == LSTM_BACKENDS


def test_lstm_forward_shim_equals_recurrent_forward():
    p = init_recurrent_params("lstm", jax.random.PRNGKey(0), 2, 10)
    xs = jnp.asarray(RNG.normal(size=(3, 7, 2)).astype(np.float32))
    a = lstm_forward(p, xs, backend="fused")
    b = recurrent_forward(LSTM_CELL, p, xs, backend="fused")
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# CellSpec registry
# ---------------------------------------------------------------------------

def test_cell_spec_registry():
    assert cell_spec("lstm") is LSTM_CELL
    assert cell_spec("gru") is GRU_CELL
    assert cell_spec(GRU_CELL) is GRU_CELL        # pass-through for specs
    assert set(CELL_SPECS) == {"lstm", "gru"}
    with pytest.raises(ValueError, match="cell"):
        cell_spec("elman")


def test_cell_spec_geometry():
    assert LSTM_CELL.gates == ("i", "f", "g", "o")
    assert LSTM_CELL.activations == ("sigmoid", "sigmoid", "tanh", "sigmoid")
    assert LSTM_CELL.state_arity == 2
    assert GRU_CELL.gates == ("r", "z", "n")
    assert GRU_CELL.activations == ("sigmoid", "sigmoid", "tanh")
    assert GRU_CELL.state_arity == 1
    for spec in CELL_SPECS.values():
        assert isinstance(spec, CellSpec)
        assert len(spec.gates) == len(spec.activations) == spec.n_gates


def test_model_cell_kind_infers_from_param_class():
    lp = init_recurrent_params("lstm", jax.random.PRNGKey(0), 2, 4)
    gp = init_recurrent_params("gru", jax.random.PRNGKey(0), 2, 4)
    assert isinstance(gp, GRUParams)
    assert model_cell_kind(lp) == "lstm"
    assert model_cell_kind(gp) == "gru"
    assert model_cell_kind([gp, gp]) == "gru"
    # the stacked-gate width encodes the gate count: 4H vs 3H
    assert lp.w.shape[1] == 4 * 4 and gp.w.shape[1] == 3 * 4


# ---------------------------------------------------------------------------
# GRU exactness vs the textbook ref kernel
# ---------------------------------------------------------------------------

def _gru_fixture(n_in=3, n_h=10, t=12, b=2, key=0):
    p = init_gru_params(jax.random.PRNGKey(key), n_in, n_h)
    qp = GRUParams(w=quantize(p.w, FMT), b=quantize(p.b, FMT))
    xs = jnp.asarray(RNG.normal(size=(b, t, n_in)).astype(np.float32))
    return qp, quantize(xs, FMT)


@pytest.mark.parametrize("lut_depth", [None, 64])
def test_gru_layer_fxp_matches_ref(lut_depth):
    qp, qxs = _gru_fixture()
    luts = make_lut_pair(lut_depth) if lut_depth else None
    h_seq, qh = gru_layer_fxp(qp, qxs, FMT, luts, return_sequence=True)
    kw = dict(frac_bits=FMT.frac_bits, total_bits=FMT.total_bits,
              return_sequence=True)
    if luts is not None:
        sig_t, sig_s = luts["sigmoid"]
        tanh_t, tanh_s = luts["tanh"]
        kw.update(sig_table=sig_t, tanh_table=tanh_t,
                  sig_bounds=sig_s.bounds, tanh_bounds=tanh_s.bounds)
    h_seq_ref, qh_ref = gru_sequence_fxp_ref(qxs, qp.w, qp.b, None, **kw)
    np.testing.assert_array_equal(np.asarray(h_seq), np.asarray(h_seq_ref))
    np.testing.assert_array_equal(np.asarray(qh), np.asarray(qh_ref))


def test_gru_ptq_model_integer_equal_across_backends():
    from repro.models.lstm_model import init_traffic_model
    params = init_traffic_model(jax.random.PRNGKey(1), 1, 10,
                                num_layers=2, cell="gru")
    qm = quantize_lstm_model(params, FMT, 64)
    assert qm.cell == "gru"
    xs = jnp.asarray(RNG.normal(size=(4, 9, 1)).astype(np.float32))
    a = quantized_lstm_forward(qm, xs, backend="fxp")
    b = quantized_lstm_forward(qm, xs, backend="pallas_fxp")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gru_qat_freeze_parity():
    """QAT eval forward == freeze -> fxp integers, GRU edition (exact float
    equality: both sides live on the quantised grid)."""
    from repro.models.lstm_model import init_traffic_model
    from repro.qat import freeze, qat_traffic_forward
    params = init_traffic_model(jax.random.PRNGKey(2), 1, 8,
                                num_layers=2, cell="gru")
    xs = jnp.asarray(RNG.normal(size=(3, 7, 1)).astype(np.float32))
    pred_qat = qat_traffic_forward(params, xs, FMT, make_lut_pair(64))
    qm = freeze(params, FMT, 64)
    for backend in ("fxp", "pallas_fxp"):
        pred = quantized_lstm_forward(qm, xs, backend=backend)
        np.testing.assert_array_equal(np.asarray(pred_qat), np.asarray(pred),
                                      err_msg=backend)


# ---------------------------------------------------------------------------
# Dispatcher contracts: loud refusals, single-state geometry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cell", ["lstm", "gru"])
def test_gru_float_pallas_backends_refuse(cell):
    """The float Pallas backends are gone: ``"pallas_seq"`` is an unknown
    name for every cell kind, and the refusal names the four backends."""
    p = init_recurrent_params(cell, jax.random.PRNGKey(0), 2, 8)
    xs = jnp.asarray(RNG.normal(size=(2, 5, 2)).astype(np.float32))
    with pytest.raises(ValueError, match="unknown backend 'pallas_seq'") as e:
        recurrent_forward(cell, p, xs, backend="pallas_seq")
    for name in ("sequential", "fused", "fxp", "pallas_fxp"):
        assert repr(name) in str(e.value), name


def test_gru_rejects_c0():
    qp, qxs = _gru_fixture(n_h=8)
    with pytest.raises(ValueError, match="c0"):
        recurrent_forward("gru", qp, qxs, backend="fxp", fmt=FMT,
                          c0=jnp.zeros((2, 8), jnp.int32))


def test_gru_forward_single_state_shapes():
    qp, qxs = _gru_fixture(n_h=8)
    qh = recurrent_forward("gru", qp, qxs, backend="fxp", fmt=FMT)
    assert qh.shape == (2, 8)                 # bare h, no (h, c) tuple
    seq, qh2 = recurrent_forward("gru", qp, qxs, backend="fxp", fmt=FMT,
                                 return_sequence=True)
    assert seq.shape == (2, 12, 8)
    np.testing.assert_array_equal(np.asarray(seq[:, -1]), np.asarray(qh2))
    np.testing.assert_array_equal(np.asarray(qh), np.asarray(qh2))
