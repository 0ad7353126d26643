"""bench/work.py against hand counts, and the peaks table's refusal of an
unknown device kind."""

import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import work  # noqa: E402

L1 = json.loads((ROOT / "bench/configs/pems_l1.json").read_text())
L2 = json.loads((ROOT / "bench/configs/pems_l2.json").read_text())


def test_ops_per_timestep_hand_count():
    # L=1: F = 1 + 20.  matmul 2*21*4*20 = 3360; bias 80; gate shifts 3*80 =
    # 240; five lookups per unit 100; tail 14*20 = 280.
    assert work.ops_per_timestep(L1) == 3360 + 80 + 240 + 100 + 280 == 4060
    # L=2 adds a layer with F = 20 + 20: 2*40*4*20 = 6400, plus the same 700.
    assert work.ops_per_timestep(L2) == 4060 + 6400 + 700 == 11160


def test_bytes_hand_count():
    assert work.bytes_per_timestep(L1) == 4 * (1 + 20) == 84
    assert work.bytes_per_timestep(L2) == 84            # top layer's h only
    assert work.state_bytes_per_slot(L1) == 2 * 2 * 20 * 4 == 320
    assert work.state_bytes_per_slot(L2) == 640
    assert work.weight_bytes(L1) == 4 * (21 * 80 + 80) == 7040
    assert work.weight_bytes(L2) == 7040 + 4 * (40 * 80 + 80) == 20160


def test_call_work_counts_occupied_slots_only():
    ops, nbytes = work.call_work(L1, occupied=1000, t_step=4)
    assert ops == 1000 * 4 * 4060
    assert nbytes == 1000 * (4 * 84 + 320) + 7040
    assert work.call_work(L1, 0, 4) == (0, 7040)


def test_peaks_known_kind_and_refusal():
    from bench import run

    v5e = run.load_peaks("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        run.load_peaks("TPU v9 imaginary")
