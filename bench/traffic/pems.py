"""The benchmark's own copy of the PeMS-like traffic generator, and the one
general request generator that every traffic mix file feeds.

``make_pems_like_fleet`` and ``normalize`` are copied from the program's
``repro.data.traffic`` (same float64 operations in the same order), so a
later change to the program cannot move the yardstick.  Each sensor's
series is the synthetic 4-week, 5-minute PeMS-4W speed series of its index.

``build_requests`` turns one traffic file (a JSON dict of parameters) and a
seed into a fixed list of requests: which sensor, which window of its
series, and, for an open loop, when it is due.  Every seed gets the same
multiset of lengths and of inter-arrival gaps, in another order, so the
seed changes which sensors and windows are served but not how much work a
window holds.
"""

from __future__ import annotations

import dataclasses

import numpy as np

PEMS_POINTS_PER_DAY = 288  # 5-minute sampling
PEMS_TOTAL_POINTS = PEMS_POINTS_PER_DAY * 7 * 4  # 8064: four weeks


def make_pems_like_fleet(seeds, n_points: int = PEMS_TOTAL_POINTS) -> np.ndarray:
    """``(len(seeds), n_points)`` synthetic freeway speed series in mph:
    free-flow plateau, weekday rush-hour dips, weekend flattening, AR(1)
    measurement noise and sporadic incident drops."""
    rngs = [np.random.default_rng(s) for s in seeds]
    t = np.arange(n_points)
    tod = (t % PEMS_POINTS_PER_DAY) / PEMS_POINTS_PER_DAY
    dow = (t // PEMS_POINTS_PER_DAY) % 7

    free_flow = 65.0 + 3.0 * np.sin(2 * np.pi * t / (PEMS_POINTS_PER_DAY * 7))

    def gauss(x, mu, sig):
        return np.exp(-0.5 * ((x - mu) / sig) ** 2)

    am_dip = 22.0 * gauss(tod, 8.0 / 24, 1.2 / 24)
    pm_dip = 28.0 * gauss(tod, 17.5 / 24, 1.6 / 24)
    weekday = (dow < 5).astype(np.float64)
    weekend_dip = 6.0 * gauss(tod, 13.0 / 24, 2.5 / 24) * (1.0 - weekday)
    speed = free_flow - weekday * (am_dip + pm_dip) - weekend_dip

    noise = np.zeros((n_points, len(rngs)))
    for j, rng in enumerate(rngs):
        noise[1:, j] = rng.normal(0.0, 1.1, size=n_points - 1)
    for i in range(1, n_points):
        noise[i] = 0.85 * noise[i - 1] + noise[i]
    speed = (speed[:, None] + noise).T

    n_incidents = max(1, n_points // 2000)
    for row, rng in zip(speed, rngs):
        for _ in range(n_incidents):
            start = rng.integers(0, n_points - 60)
            depth = rng.uniform(15.0, 35.0)
            dur = rng.integers(6, 30)
            rec = np.exp(-np.arange(dur) / (dur / 3.0))
            row[start : start + dur] -= depth * rec

    return np.clip(speed, 3.0, 80.0)


def normalize(series: np.ndarray) -> np.ndarray:
    """Min-max to [0, 1] over the whole series, per row."""
    lo = series.min(axis=-1, keepdims=True)
    hi = series.max(axis=-1, keepdims=True)
    return (series - lo) / (hi - lo)


def quantize(x: np.ndarray, frac_bits: int, total_bits: int) -> np.ndarray:
    """float -> fixed-point int32: round half up in float32, saturate."""
    q = np.floor(np.asarray(x, np.float32) * np.float32(1 << frac_bits)
                 + np.float32(0.5))
    lim = 1 << (total_bits - 1)
    return np.clip(q, -lim, lim - 1).astype(np.int32)


def sensor_windows(n_sensors: int, first: int, last: int,
                   block: int = 1024) -> np.ndarray:
    """``(n_sensors, last - first)`` float32: points ``[first, last)`` of each
    sensor's normalised series, made ``block`` sensors at a time."""
    out = np.empty((n_sensors, last - first), np.float32)
    for lo in range(0, n_sensors, block):
        hi = min(lo + block, n_sensors)
        out[lo:hi] = normalize(make_pems_like_fleet(range(lo, hi)))[:, first:last]
    return out


@dataclasses.dataclass
class Requests:
    """A fixed request list: sensor, window start (relative to the first
    stored point) and length of each, the stored quantised points, and for
    an open loop each request's due time in seconds from the start."""

    sensor: np.ndarray       # (n,) int
    start: np.ndarray        # (n,) int, offset into ``points``' columns
    length: np.ndarray       # (n,) int
    points: np.ndarray       # (n_sensors, n_points) int32, quantised
    due_s: np.ndarray | None  # (n,) float64, open loop only

    def __len__(self) -> int:
        return len(self.sensor)

    def qxs(self, k: int) -> np.ndarray:
        """Request ``k``'s ``(T, 1)`` int32 input window (a fresh array)."""
        s, a = self.sensor[k], self.start[k]
        return self.points[s, a : a + self.length[k]][:, None].copy()


def build_requests(traffic: dict, n_sensors: int, seed: int,
                   n_requests: int, frac_bits: int, total_bits: int) -> Requests:
    """``n_requests`` requests of one traffic mix, drawn from ``seed``.

    Sensors are taken round-robin in a seeded order, every one before any
    twice.  Each request is a window of ``length_min..length_max`` points
    from a seeded offset in ``[offset_min, offset_max)``; the lengths cycle
    evenly over their range before the seeded shuffle.  An open loop
    (``rate_per_s``) gets inter-arrival gaps that are the exponential
    distribution's quantiles at that rate, shuffled: Poisson-like arrivals
    with the same total time for every seed.
    """
    rng = np.random.default_rng(seed)
    lo_len, hi_len = int(traffic["length_min"]), int(traffic["length_max"])
    lo_off, hi_off = int(traffic["offset_min"]), int(traffic["offset_max"])
    order = rng.permutation(n_sensors)
    sensor = order[np.arange(n_requests) % n_sensors]
    start = rng.integers(0, hi_off - lo_off, n_requests)
    length = lo_len + np.arange(n_requests) % (hi_len - lo_len + 1)
    length = rng.permutation(length)
    points = quantize(sensor_windows(n_sensors, lo_off, hi_off + hi_len),
                      frac_bits, total_bits)
    due = None
    if "rate_per_s" in traffic:
        u = (np.arange(n_requests) + 0.5) / n_requests
        gaps = rng.permutation(-np.log1p(-u) / float(traffic["rate_per_s"]))
        due = np.cumsum(gaps) - gaps[0]
    return Requests(sensor, start, length, points, due)
