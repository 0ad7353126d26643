"""Each traffic mix is deterministic in the seed, has the stated lengths and,
for an open loop, the stated mean rate."""

import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench.traffic import pems  # noqa: E402

MIXES = sorted(p.stem for p in (ROOT / "bench/traffic").glob("*.json"))
N_SENSORS = 24


def mix(name):
    return json.loads((ROOT / "bench/traffic" / f"{name}.json").read_text())


def build(name, seed, n=390):
    return pems.build_requests(mix(name), N_SENSORS, seed, n, 8, 16)


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_in_seed(name):
    a, b, c = build(name, 2**31 + 5), build(name, 2**31 + 5), build(name, 6)
    for k in ("sensor", "start", "length"):
        np.testing.assert_array_equal(getattr(a, k), getattr(b, k))
    np.testing.assert_array_equal(a.points, b.points)
    assert not np.array_equal(a.sensor, c.sensor)
    if a.due_s is not None:
        np.testing.assert_array_equal(a.due_s, b.due_s)
    assert all(np.array_equal(a.qxs(k), b.qxs(k)) for k in range(0, len(a), 37))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_windows_and_sensors(name):
    t = mix(name)
    r = build(name, 11)
    lo, hi = t["length_min"], t["length_max"]
    assert r.length.min() == lo and r.length.max() == hi
    counts = np.bincount(r.length)[lo:hi + 1]
    assert counts.max() - counts.min() <= 1          # every length alike
    assert r.start.min() >= 0 and r.start.max() < t["offset_max"] - t["offset_min"]
    # every sensor before any twice, in a seeded order
    assert sorted(r.sensor[:N_SENSORS]) == list(range(N_SENSORS))
    for k in range(0, len(r), 29):
        q = r.qxs(k)
        assert q.shape == (r.length[k], 1) and q.dtype == np.int32
        assert 0 <= q.min() and q.max() <= 256      # normalised, (8,16)


@pytest.mark.parametrize("name", [m for m in MIXES if "rate_per_s" in mix(m)])
def test_open_loop_mean_rate(name):
    rate = mix(name)["rate_per_s"]
    a, b = build(name, 1, 4000), build(name, 2, 4000)
    for r in (a, b):
        assert r.due_s[0] == 0 and np.all(np.diff(r.due_s) >= 0)
        assert abs((len(r) - 1) / r.due_s[-1] / rate - 1) < 0.02
    # the same gaps for every seed, in another order
    np.testing.assert_allclose(np.sort(np.diff(a.due_s)[1:]).sum(),
                               np.sort(np.diff(b.due_s)[1:]).sum(), rtol=0.02)


def test_generator_copy_matches_the_programs():
    from repro.data.traffic import make_pems_like_fleet

    np.testing.assert_array_equal(pems.make_pems_like_fleet(range(3), 600),
                                  make_pems_like_fleet(range(3), 600))
