"""The fused fixed-point kernels compile for TPU v5e.

Interpret-mode passes say nothing about Mosaic, the TPU kernel compiler: it
refuses what the interpreter runs (an int32 x int32 MXU dot, an unaligned
slice, too much VMEM).  These tests compile the served kernels at real widths
for a described ``v5e:2x2`` host, with no chip attached, and check that each
program holds the compiled kernel (a ``tpu_custom_call``).  The topology is
described only inside the ``topo`` fixture, so collecting this file never
loads the TPU library; without a describable topology every test skips.
"""

import json
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.fxp import fmt_from_dict
from repro.kernels.lstm_fxp_seq import (DEFAULT_BLOCK_B,
                                        gru_sequence_fxp_stack_pallas,
                                        lstm_sequence_fxp_stack_pallas)
from repro.parallel.sharding import fleet_slot_specs

SLOTS = 1024        # a realistic per-chip fleet: tiles over the batch grid
DEPTH = 256         # the paper's LUT depth
MIXED = pathlib.Path(__file__).parent / "golden" / "lstm_mixed_golden.json"


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _stack_args(sharding, *, B, T, n_in, hs, n_gates, arity):
    """Shapes of one stack-kernel call: inputs, per-layer weights/biases,
    per-layer state lists and the two LUT tables."""
    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    fan_in = [n_in] + hs[:-1]
    ws = [s((f + h, n_gates * h)) for f, h in zip(fan_in, hs)]
    bs = [s((n_gates * h,)) for h in hs]
    state = [[s((B, h)) for h in hs] for _ in range(arity)]
    luts = [s((DEPTH,), jnp.float32), s((DEPTH,), jnp.float32)]
    return s((B, T, n_in)), ws, bs, state, luts


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("cell,hs,T,time_tile", [
    ("lstm", [20], 8, None), ("lstm", [20, 20], 8, None),
    ("gru", [20], 8, None), ("lstm", [20], 12, 8)],
    ids=["lstm_h20", "lstm_stack2", "gru_h20", "lstm_h20_time_tiled"])
def test_fleet_kernel_compiles_for_v5e(one_chip, cell, hs, T, time_tile):
    """The paper's width (input 1, H=20) at the engine's default block and
    the largest ``t_step`` bucket, for the 1-layer LSTM, the 2-layer stack
    and the GRU; and time-tiled with a ragged tail (Mosaic takes a time
    block only as a multiple of 8 or the whole sequence)."""
    arity = 2 if cell == "lstm" else 1
    qxs, ws, bs, state, (sig, tanh) = _stack_args(
        one_chip, B=SLOTS, T=T, n_in=1, hs=hs,
        n_gates=4 if cell == "lstm" else 3, arity=arity)
    kernel = (lstm_sequence_fxp_stack_pallas if cell == "lstm"
              else gru_sequence_fxp_stack_pallas)

    def fn(qxs, ws, bs, state, sig, tanh):
        return kernel(qxs, ws, bs, *state, sig, tanh, return_sequence=True,
                      block_b=DEFAULT_BLOCK_B, time_tile=time_tile,
                      interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, qxs, ws, bs, state, sig, tanh)


def test_mixed_precision_stack_compiles_for_v5e(one_chip):
    """The hetero-H (10/6) stack under the mixed-precision golden's
    per-layer/per-gate formats: every static shift and padded lane."""
    g = json.loads(MIXED.read_text())
    formats = fmt_from_dict(g["fmt"])
    n_in = len(g["stack"]["qxs"][0][0])
    qxs, ws, bs, (h0, c0), (sig, tanh) = _stack_args(
        one_chip, B=SLOTS, T=8, n_in=n_in, hs=g["stack"]["h_sizes"],
        n_gates=4, arity=2)

    def fn(qxs, ws, bs, h0, c0, sig, tanh):
        return lstm_sequence_fxp_stack_pallas(
            qxs, ws, bs, h0, c0, sig, tanh, formats=formats,
            return_sequence=True, block_b=DEFAULT_BLOCK_B, interpret=False)

    assert "tpu_custom_call" in _compiled_text(fn, qxs, ws, bs, h0, c0, sig,
                                               tanh)


def test_slot_sharded_kernel_compiles_for_four_chips(topo):
    """The engine's slot-sharded step: a ``shard_map`` of the stack kernel
    over a 4-device mesh with the fleet's slot specs, each device running
    the kernel on its own slot block."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    specs = fleet_slot_specs("data")

    def s(shape, spec, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    L, H = 2, 20
    args = (s((SLOTS, 8, 1), specs["x"]),
            [s((1 + H, 4 * H), specs["params"]), s((2 * H, 4 * H), specs["params"])],
            [s((4 * H,), specs["params"])] * L,
            s((L, SLOTS, H), specs["state"]), s((L, SLOTS, H), specs["state"]),
            s((DEPTH,), P(), jnp.float32), s((DEPTH,), P(), jnp.float32))

    def body(qxs, ws, bs, qh, qc, sig, tanh):
        seq, hs, cs = lstm_sequence_fxp_stack_pallas(
            qxs, ws, bs, qh, qc, sig, tanh, return_sequence=True,
            block_b=DEFAULT_BLOCK_B, interpret=False)
        return seq, hs, cs

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(specs["x"], specs["params"], specs["params"],
                  specs["state"], specs["state"], P(), P()),
        out_specs=(specs["seq"], specs["state"], specs["state"]),
        check_vma=False)
    text = _compiled_text(fn, *args)
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text
