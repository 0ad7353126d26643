"""Traffic-speed data pipeline (paper §5.1).

The paper uses one randomly-selected sensor series from PeMS-4W (5-minute
sampling over four weeks = 8064 points), split 3:1 train/test, windows of 6
historical points predicting the next point.

The zenodo archive is not reachable from this offline container, so
``make_pems_like_series`` synthesises a statistically-matched series: freeway
speeds with a free-flow plateau, weekday AM/PM rush-hour congestion dips,
weekend flattening, AR(1) measurement noise, and sporadic incident drops —
the canonical structure of PeMS loop-detector speed data.  The experiment
*trends* the paper reports (Fig. 6 fractional-bit plateau, Table 1 LUT-depth
convergence) are properties of the quantiser and model, not of which series
is used; DESIGN.md §4 records this substitution.
"""

from __future__ import annotations

import dataclasses

import numpy as np

__all__ = [
    "PEMS_POINTS_PER_DAY",
    "make_pems_like_series",
    "make_pems_like_fleet",
    "make_fleet_windows",
    "normalize",
    "make_windows",
    "train_test_split",
    "TrafficDataset",
    "make_traffic_dataset",
]

PEMS_POINTS_PER_DAY = 288  # 5-minute sampling
PEMS_WEEKS = 4
PEMS_TOTAL_POINTS = PEMS_POINTS_PER_DAY * 7 * PEMS_WEEKS  # 8064, as in the paper


def make_pems_like_series(seed: int = 0, n_points: int = PEMS_TOTAL_POINTS) -> np.ndarray:
    """Synthetic single-sensor freeway speed series in mph."""
    return make_pems_like_fleet([seed], n_points)[0]


def make_pems_like_fleet(seeds, n_points: int = PEMS_TOTAL_POINTS) -> np.ndarray:
    """``(len(seeds), n_points)`` series, row ``i`` bit-identical to
    ``make_pems_like_series(seeds[i], n_points)``.  The AR(1) noise runs
    vectorised over sensors (the same float64 operations in the same order
    per sensor), so a deployment-scale fleet is made in seconds."""
    rngs = [np.random.default_rng(s) for s in seeds]
    t = np.arange(n_points)
    tod = (t % PEMS_POINTS_PER_DAY) / PEMS_POINTS_PER_DAY  # time of day in [0,1)
    dow = (t // PEMS_POINTS_PER_DAY) % 7                   # day of week

    free_flow = 65.0 + 3.0 * np.sin(2 * np.pi * t / (PEMS_POINTS_PER_DAY * 7))

    def gauss(x, mu, sig):
        return np.exp(-0.5 * ((x - mu) / sig) ** 2)

    am_dip = 22.0 * gauss(tod, 8.0 / 24, 1.2 / 24)
    pm_dip = 28.0 * gauss(tod, 17.5 / 24, 1.6 / 24)
    weekday = (dow < 5).astype(np.float64)
    # weekends keep a mild midday slowdown
    weekend_dip = 6.0 * gauss(tod, 13.0 / 24, 2.5 / 24) * (1.0 - weekday)
    speed = free_flow - weekday * (am_dip + pm_dip) - weekend_dip

    # AR(1) measurement noise (loop detectors are noisy but correlated);
    # time-major so each step updates one contiguous row of sensors
    noise = np.zeros((n_points, len(rngs)))
    for j, rng in enumerate(rngs):
        noise[1:, j] = rng.normal(0.0, 1.1, size=n_points - 1)
    for i in range(1, n_points):
        noise[i] = 0.85 * noise[i - 1] + noise[i]
    speed = (speed[:, None] + noise).T

    # sporadic incidents: sharp dips with exponential recovery
    n_incidents = max(1, n_points // 2000)
    for row, rng in zip(speed, rngs):
        for _ in range(n_incidents):
            start = rng.integers(0, n_points - 60)
            depth = rng.uniform(15.0, 35.0)
            dur = rng.integers(6, 30)
            rec = np.exp(-np.arange(dur) / (dur / 3.0))
            row[start : start + dur] -= depth * rec

    return np.clip(speed, 3.0, 80.0)


def make_fleet_windows(n_sensors: int, seed: int = 0,
                       block: int = 1024) -> list[np.ndarray]:
    """Ragged recent histories for a PeMS-like sensor fleet (sensors report
    unevenly in the wild): sensor ``s`` is series ``s``, normalised, and
    reports 6..18 consecutive 5-minute points from a random offset in
    [100, 200).  ``seed`` draws the offsets and lengths.  Returns one
    ``(n_s, 1)`` float32 window per sensor; series are made ``block`` at a
    time to bound memory."""
    rng = np.random.default_rng(seed)
    out = []
    for first in range(0, n_sensors, block):
        for series in make_pems_like_fleet(
                range(first, min(first + block, n_sensors))):
            norm, _, _ = normalize(series)
            lo = int(rng.integers(100, 200))
            n = int(rng.integers(6, 19))
            out.append(norm[lo : lo + n][:, None].astype(np.float32))
    return out


def normalize(series: np.ndarray) -> tuple[np.ndarray, float, float]:
    """Min-max to [0, 1] (keeps everything inside the (8,16) fixed-point
    range with ample integer headroom, as the paper's PTQ assumes)."""
    lo, hi = float(series.min()), float(series.max())
    return (series - lo) / (hi - lo), lo, hi


def make_windows(series: np.ndarray, n_seq: int = 6) -> tuple[np.ndarray, np.ndarray]:
    """Sliding windows: X[k] = series[k : k+n_seq], y[k] = series[k+n_seq].

    Returns X: (N, n_seq, 1), y: (N, 1).
    """
    n = len(series) - n_seq
    idx = np.arange(n)[:, None] + np.arange(n_seq)[None, :]
    x = series[idx][..., None].astype(np.float32)
    y = series[np.arange(n) + n_seq][:, None].astype(np.float32)
    return x, y


def train_test_split(x: np.ndarray, y: np.ndarray, ratio: float = 0.75):
    """Chronological 3:1 split (paper §5.1)."""
    n_train = int(len(x) * ratio)
    return (x[:n_train], y[:n_train]), (x[n_train:], y[n_train:])


@dataclasses.dataclass
class TrafficDataset:
    x_train: np.ndarray
    y_train: np.ndarray
    x_test: np.ndarray
    y_test: np.ndarray
    lo: float
    hi: float

    @property
    def n_train(self) -> int:
        return len(self.x_train)

    @property
    def n_test(self) -> int:
        return len(self.x_test)


def make_traffic_dataset(seed: int = 0, n_seq: int = 6) -> TrafficDataset:
    series = make_pems_like_series(seed)
    norm, lo, hi = normalize(series)
    x, y = make_windows(norm, n_seq)
    (xt, yt), (xv, yv) = train_test_split(x, y)
    return TrafficDataset(xt, yt, xv, yv, lo, hi)
