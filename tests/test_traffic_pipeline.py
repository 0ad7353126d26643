"""End-to-end traffic pipeline: data properties, training convergence, and
the paper's PTQ experiment trends (Fig. 6 / Table 1 directions)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fxp import FxpFormat
from repro.core.quantize import quantize_lstm_model, quantized_lstm_forward
from repro.data.traffic import (PEMS_TOTAL_POINTS, make_pems_like_series,
                                make_traffic_dataset, make_windows, normalize)
from repro.models.lstm_model import evaluate_mse, train_traffic_model


@pytest.fixture(scope="module")
def trained():
    data = make_traffic_dataset(seed=0)
    params, history = train_traffic_model(data, epochs=8)
    return data, params, history


def test_series_shape_and_stats():
    s = make_pems_like_series(seed=0)
    assert len(s) == PEMS_TOTAL_POINTS == 8064        # paper: 4 weeks @ 5 min
    assert 3.0 <= s.min() and s.max() <= 80.0         # freeway speeds (mph)
    # rush-hour structure: weekday midday mean < overnight mean
    day = s[: 288 * 5].reshape(5, 288)
    assert day[:, 96:120].mean() < day[:, 12:48].mean()


def _series_loop_reference(seed, n_points):
    """The per-sensor, one-draw-per-step generator the vectorised fleet
    generator replaced, kept as its reference."""
    from repro.data.traffic import PEMS_POINTS_PER_DAY as day

    rng = np.random.default_rng(seed)
    t = np.arange(n_points)
    tod, dow = (t % day) / day, (t // day) % 7
    free_flow = 65.0 + 3.0 * np.sin(2 * np.pi * t / (day * 7))

    def gauss(x, mu, sig):
        return np.exp(-0.5 * ((x - mu) / sig) ** 2)

    weekday = (dow < 5).astype(np.float64)
    speed = (free_flow - weekday * (22.0 * gauss(tod, 8.0 / 24, 1.2 / 24)
                                    + 28.0 * gauss(tod, 17.5 / 24, 1.6 / 24))
             - 6.0 * gauss(tod, 13.0 / 24, 2.5 / 24) * (1.0 - weekday))
    noise = np.zeros(n_points)
    for i in range(1, n_points):
        noise[i] = 0.85 * noise[i - 1] + rng.normal(0.0, 1.1)
    speed = speed + noise
    for _ in range(max(1, n_points // 2000)):
        start = rng.integers(0, n_points - 60)
        depth = rng.uniform(15.0, 35.0)
        dur = rng.integers(6, 30)
        speed[start : start + dur] -= depth * np.exp(-np.arange(dur) / (dur / 3.0))
    return np.clip(speed, 3.0, 80.0)


@pytest.mark.parametrize("n_points", [PEMS_TOTAL_POINTS, 500])
def test_fleet_series_equal_per_sensor_loop(n_points):
    from repro.data.traffic import make_pems_like_fleet

    seeds = [0, 3, 17]
    fleet = make_pems_like_fleet(seeds, n_points)
    assert fleet.shape == (3, n_points)
    for row, seed in zip(fleet, seeds):
        np.testing.assert_array_equal(row, _series_loop_reference(seed, n_points))
        np.testing.assert_array_equal(row, make_pems_like_series(seed, n_points))


def test_fleet_windows_ragged_and_seeded():
    from repro.data.traffic import make_fleet_windows

    a, b = make_fleet_windows(5, seed=1, block=2), make_fleet_windows(5, seed=1)
    assert [w.shape for w in a] == [w.shape for w in b]
    for wa, wb in zip(a, b):
        np.testing.assert_array_equal(wa, wb)
        assert 6 <= len(wa) <= 18 and wa.shape[1:] == (1,)
        assert wa.dtype == np.float32 and 0.0 <= wa.min() and wa.max() <= 1.0


def test_windowing():
    s = np.arange(20, dtype=np.float64)
    x, y = make_windows(s, n_seq=6)
    assert x.shape == (14, 6, 1) and y.shape == (14, 1)
    np.testing.assert_array_equal(x[0, :, 0], np.arange(6))
    assert y[0, 0] == 6


def test_split_is_chronological_3_to_1():
    data = make_traffic_dataset(seed=0)
    assert abs(data.n_train / (data.n_train + data.n_test) - 0.75) < 0.01


def test_training_converges(trained):
    data, params, history = trained
    # epoch-0 mean already includes most of the convergence (batch-1 SGD);
    # require further improvement plus a strong absolute bound
    assert history[-1] < history[0]
    assert evaluate_mse(params, data.x_test, data.y_test) < 0.005  # [0,1] units


def test_fig6_trend_monotone_then_plateau(trained):
    data, params, _ = trained
    xs, ys = jnp.asarray(data.x_test), jnp.asarray(data.y_test)
    mses = {}
    for fb in (4, 6, 8, 12):
        qm = quantize_lstm_model(params, FxpFormat(fb, 16), None)
        mses[fb] = float(jnp.mean((quantized_lstm_forward(qm, xs) - ys) ** 2))
    assert mses[4] > mses[6] > mses[8] * 0.999          # improves to 8
    assert mses[8] < 1.15 * mses[12]                    # plateau at 8 (paper)


def test_table1_trend_lut_depth(trained):
    data, params, _ = trained
    xs, ys = jnp.asarray(data.x_test), jnp.asarray(data.y_test)
    fmt = FxpFormat(8, 16)
    mses = {}
    for depth in (64, 128, 256):
        qm = quantize_lstm_model(params, fmt, depth)
        mses[depth] = float(jnp.mean((quantized_lstm_forward(qm, xs) - ys) ** 2))
    qm0 = quantize_lstm_model(params, fmt, None)
    fp_act = float(jnp.mean((quantized_lstm_forward(qm0, xs) - ys) ** 2))
    assert mses[64] > mses[128] > mses[256]             # paper Table 1 direction
    assert mses[256] < 1.25 * fp_act                    # 256 ~ full precision


def test_stacked_traffic_model_trains_and_quantises():
    """num_layers=2 flows through the whole pipeline: training (fused
    backend over the param list), PTQ (per-layer), and the bitstream-exact
    quantised forward — the model the stacked fleet engine serves."""
    from repro.models.lstm_model import init_traffic_model, traffic_forward

    data = make_traffic_dataset(seed=0)
    params, history = train_traffic_model(data, epochs=2, num_layers=2,
                                          hidden_size=10)
    assert isinstance(params["lstm"], list) and len(params["lstm"]) == 2
    assert history[-1] < history[0]              # the stack still learns
    xs = jnp.asarray(data.x_test[:16])
    assert traffic_forward(params, xs).shape == (16, 1)

    qm = quantize_lstm_model(params, FxpFormat(8, 16), 256)
    assert len(qm.lstm) == 2
    pred = quantized_lstm_forward(qm, xs)
    assert pred.shape == (16, 1)
    # fxp and the fused multi-layer Pallas stack kernel are integer-equal,
    # so the dequantised predictions are bitwise identical
    pred_k = quantized_lstm_forward(qm, xs, backend="pallas_fxp")
    np.testing.assert_array_equal(np.asarray(pred), np.asarray(pred_k))

    # the legacy single-layer cell path refuses stacked params loudly
    with pytest.raises(ValueError, match="single-layer"):
        traffic_forward(params, xs, cell=lambda *a, **k: None)
