"""Lookup-table activation functions (paper contribution C3).

The paper replaces full-precision ``sigmoid``/``tanh`` with lookup tables of
depth 64/128/256, instantiated once per function and shared by every
consumer.  Table 1 of the paper shows depth 256 recovers the full-precision
MSE.  This module is the pure-jnp reference implementation (also the
quantisation-simulator path); the served kernel
(``repro.kernels.lstm_fxp_seq``) holds both tables in VMEM and gathers from
them in-kernel with the same index scheme.

Index scheme (matches a BRAM-addressed LUT): the input range ``[lo, hi)`` is
split into ``depth`` equal bins; an input is clamped into range and mapped to
``idx = floor((x - lo) / step)``; the table stores the function sampled at
bin midpoints (midpoint sampling halves the worst-case error vs. left-edge
sampling).  Out-of-range inputs clamp to the first/last entry, which for
sigmoid/tanh equals the saturated value to within the table resolution.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = [
    "LutSpec",
    "build_table",
    "lut_apply",
    "lut_apply_fxp",
    "lut_sigmoid",
    "lut_tanh",
    "lut_gelu",
    "lut_silu",
    "make_lut_pair",
    "DEFAULT_RANGES",
]

# Input ranges chosen so the clamped tails are within one LSB of the true
# asymptote: |sigmoid(±8) - {0,1}| < 4e-4, |tanh(±4) - ±1| < 1.4e-3.
DEFAULT_RANGES = {
    "sigmoid": (-8.0, 8.0),
    "tanh": (-4.0, 4.0),
    "gelu": (-8.0, 8.0),
    "silu": (-8.0, 8.0),
}

_FNS: dict[str, Callable[[jax.Array], jax.Array]] = {
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


@dataclasses.dataclass(frozen=True)
class LutSpec:
    fn: str = "sigmoid"
    depth: int = 256
    lo: float | None = None
    hi: float | None = None

    def __post_init__(self):
        if self.fn not in _FNS:
            raise ValueError(f"unknown LUT function {self.fn!r}")
        if self.depth < 2:
            raise ValueError("LUT depth must be >= 2")

    @property
    def bounds(self) -> tuple[float, float]:
        lo, hi = DEFAULT_RANGES[self.fn]
        return (self.lo if self.lo is not None else lo, self.hi if self.hi is not None else hi)

    @property
    def step(self) -> float:
        lo, hi = self.bounds
        return (hi - lo) / self.depth


def build_table(spec: LutSpec, dtype=jnp.float32) -> jax.Array:
    """Sample ``spec.fn`` at the ``depth`` bin midpoints."""
    lo, _ = spec.bounds
    mids = lo + (jnp.arange(spec.depth, dtype=jnp.float32) + 0.5) * spec.step
    return _FNS[spec.fn](mids).astype(dtype)


def lut_indices(x: jax.Array, spec: LutSpec) -> jax.Array:
    lo, _ = spec.bounds
    idx = jnp.floor((jnp.asarray(x, jnp.float32) - lo) / spec.step).astype(jnp.int32)
    return jnp.clip(idx, 0, spec.depth - 1)


def lut_apply(x: jax.Array, table: jax.Array, spec: LutSpec) -> jax.Array:
    """Evaluate the LUT: clamp, index, gather.  Shape-preserving."""
    return jnp.take(table, lut_indices(x, spec), axis=0)


def lut_apply_fxp(q: jax.Array, table: jax.Array, spec: LutSpec, fmt,
                  out_fmt=None) -> jax.Array:
    """Apply a LUT to fixed-point inputs, returning fixed point.

    The FPGA addresses the LUT with the top bits of the fixed-point value; we
    reproduce that by dequantising for the index computation only (exact — it
    is integer arithmetic either way) and re-quantising the table output.
    This is THE fxp-LUT semantics: ``core.lstm.lstm_cell_fxp`` (the bitstream
    spec), the Pallas kernels' reference, and the QAT fake-quant ops
    (``repro.qat.fakequant.fake_lut_act``) all evaluate exactly this.
    ``fmt``: a ``repro.core.fxp.FxpFormat`` describing the *input* integers;
    ``out_fmt`` (default ``fmt``) is the format of the returned integers —
    in the mixed-precision datapath the gate pre-activation arrives at its
    own gate format while the activation output lands at the layer's data
    format.
    """
    from repro.core import fxp as fxp_mod

    x = fxp_mod.dequantize(q, fmt)
    y = lut_apply(x, table, spec)
    return fxp_mod.quantize(y, fmt if out_fmt is None else out_fmt)


@partial(jax.jit, static_argnames=("depth",))
def lut_sigmoid(x: jax.Array, depth: int = 256) -> jax.Array:
    spec = LutSpec("sigmoid", depth)
    return lut_apply(x, build_table(spec), spec)


@partial(jax.jit, static_argnames=("depth",))
def lut_tanh(x: jax.Array, depth: int = 256) -> jax.Array:
    spec = LutSpec("tanh", depth)
    return lut_apply(x, build_table(spec), spec)


@partial(jax.jit, static_argnames=("depth",))
def lut_gelu(x: jax.Array, depth: int = 256) -> jax.Array:
    """Beyond-paper: the paper's C3 applied to transformer MLP activations."""
    spec = LutSpec("gelu", depth)
    # gelu is unbounded above; LUT stores gelu on the range and we add the
    # identity passthrough for x > hi (gelu(x) ~= x there).
    lo, hi = spec.bounds
    y = lut_apply(x, build_table(spec), spec)
    return jnp.where(x >= hi, x, y)


@partial(jax.jit, static_argnames=("depth",))
def lut_silu(x: jax.Array, depth: int = 256) -> jax.Array:
    spec = LutSpec("silu", depth)
    lo, hi = spec.bounds
    y = lut_apply(x, build_table(spec), spec)
    return jnp.where(x >= hi, x, y)


def make_lut_pair(depth: int = 256) -> dict[str, tuple[jax.Array, LutSpec]]:
    """The paper instantiates exactly one sigmoid table and one tanh table
    and shares them across all gates and time steps — this returns that pair."""
    out = {}
    for fn in ("sigmoid", "tanh"):
        spec = LutSpec(fn, depth)
        out[fn] = (build_table(spec), spec)
    return out


def max_table_error(spec: LutSpec, n_probe: int = 65536) -> float:
    """Worst-case |LUT - exact| over the in-range domain (used by tests and
    the Table-1 benchmark to bound accuracy loss analytically)."""
    lo, hi = spec.bounds
    xs = jnp.linspace(lo, hi - 1e-6, n_probe)
    exact = _FNS[spec.fn](xs)
    approx = lut_apply(xs, build_table(spec), spec)
    return float(jnp.max(jnp.abs(exact - approx)))
