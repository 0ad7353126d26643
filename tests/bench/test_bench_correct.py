"""The comparison that decides ``correct``, driven through the harness on
the CPU at a small size (the kernel in interpret mode): sound runs pass, the
control fails, and each fault planted under the timed path fails."""

import pathlib
import sys
import time

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402

SMALL = {"config": {"n_sensors": 24},
         "traffic": {"slots_per_chip": 8, "backlog_requests": 96}}


def spec_with(cell):
    """``BENCHMARK.json``, listing ``cell`` (``<config>.<traffic>`` on one
    chip) where it does not list it yet: a cell whose files are under
    ``bench/`` but that is not yet proven on the chip."""
    spec = run.load_spec()
    if cell not in {w["name"] for w in spec["workloads"]}:
        config, traffic = cell.split(".", 1)
        if config not in {c["name"] for c in spec["configs"]}:
            spec["configs"].append({"name": config,
                                    "file": f"bench/configs/{config}.json"})
        spec["workloads"].append({"name": cell, "config": config,
                                  "traffic": traffic, "chips": 1})
    return spec


def small_run(cell, seconds=1.0, **kw):
    parts = run.cell_parts(spec_with(cell), cell)
    sizes = {k: dict(v) for k, v in SMALL.items()}
    if "rate_per_s" in parts["traffic"]:
        sizes["traffic"].update(rate_per_s=24.0, lead_in_s=0.3)
    return run.run_cell(parts, 2**31 + 3, seconds, False, jax.devices()[:1],
                        sizes=sizes, t_process=time.perf_counter(), **kw)


@pytest.mark.parametrize("cell", ["pems_l1.backlog6", "pems_l2.backlog6",
                                  "pems_l1.ragged_open"])
def test_sound_run_is_correct_and_control_is_not(cell):
    res = small_run(cell, control=True)
    assert res["correct"], res["checks"]
    assert res["checks"]["compared"]["value"] > 0
    assert res["checks"]["mismatched_ints"]["value"] == 0
    # the reference in bfloat16 in the program's place fails the same check
    ctl = res["control_checks"]
    assert ctl["mismatched_ints"]["value"] > 0 and ctl["max_int_gap"]["value"] > 0
    assert list(res)[-1] == "checks"


def _state_unchanged(step):
    def f(ws, bs, x, qh, qc, mask):
        seq, _, _ = step(ws, bs, x, qh, qc, mask)
        return seq, qh, qc
    return f


def _half_batch_left_out(step):
    def f(ws, bs, x, qh, qc, mask):
        keep = np.arange(mask.shape[0]) < mask.shape[0] // 2
        return step(ws, bs, x * keep[:, None, None], qh, qc, mask & keep)
    return f


def _answer_altered(step):
    def f(ws, bs, x, qh, qc, mask):
        seq, h, c = step(ws, bs, x, qh, qc, mask)
        return seq.at[0, -1, 0].add(1), h, c
    return f


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch_left_out,
                                   _answer_altered])
def test_planted_fault_is_not_correct(fault, monkeypatch):
    build = run.build_system

    def broken(*a, **k):
        queue, eng, cls = build(*a, **k)
        eng._step = fault(eng._step)
        return queue, eng, cls

    monkeypatch.setattr(run, "build_system", broken)
    res = small_run("pems_l1.backlog6")
    assert not res["correct"]
    assert res["checks"]["mismatched_ints"]["value"] > 0
