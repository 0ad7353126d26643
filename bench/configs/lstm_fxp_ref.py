"""Plain reference of the fixed-point LSTM stack the ``pems_*`` configurations
run (Qian, Ling, Schiele, arXiv:2310.16842, Fig. 1 and Sec. 3-5).

Written from the paper's datapath and nothing else: numpy only, int64
accumulators, one timestep and one layer at a time.  It imports nothing
of the program and takes only what the benchmark made: the seeded integer
weights and the activation tables.

Per layer and timestep, with ``x`` fractional bits and ``y`` total bits:

* ``z = sat((min([x_t, h] @ W + (b << x), 2**31 - 1 - half) + half) >> x)``
  for the stacked gates ``i, f, g, o`` (``half = 1 << (x - 1)``);
* each activation is a table lookup: the integer is read as a real number,
  binned into ``depth`` equal bins over ``[lo, hi)`` (clamped), and the
  table entry is quantised back with round-half-up in float32;
* ``c' = sat(r(f * c) + r(i * g))`` and ``h' = r(o * tanh_lut(c'))``, where
  ``r`` is the same rounding shift by ``x`` with saturation.

Layer ``l + 1`` takes layer ``l``'s fresh ``h`` at the same timestep.

``lowp=True`` is the control: the same datapath with every operand of the
gate products and every table entry first rounded to bfloat16, which is
what a matrix unit at its default precision does to float32 operands.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

INT32_MAX = (1 << 31) - 1
GATES = 4  # i, f, g, o


def make_tables(cfg: dict) -> dict:
    """float32 sigmoid and tanh tables, sampled at the bin midpoints."""
    depth = int(cfg["lut_depth"])
    out = {}
    for fn, f in (("sigmoid", lambda v: 1.0 / (1.0 + np.exp(-v))),
                  ("tanh", np.tanh)):
        lo, hi = (float(v) for v in cfg["lut_ranges"][fn])
        mids = lo + (np.arange(depth, dtype=np.float64) + 0.5) * (hi - lo) / depth
        out[fn] = f(mids).astype(np.float32)
    return out


def _bf16(a: np.ndarray) -> np.ndarray:
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


class Datapath:
    """The integer operations of one configuration."""

    def __init__(self, cfg: dict, tables: dict, lowp: bool = False):
        self.x = int(cfg["frac_bits"])
        self.y = int(cfg["total_bits"])
        self.qmin = -(1 << (self.y - 1))
        self.qmax = (1 << (self.y - 1)) - 1
        self.lowp = lowp
        self.luts = {}
        for fn in ("sigmoid", "tanh"):
            lo, hi = (float(v) for v in cfg["lut_ranges"][fn])
            table = _bf16(tables[fn]) if lowp else np.asarray(tables[fn], np.float32)
            self.luts[fn] = (table, lo, (hi - lo) / len(table))

    def sat(self, v):
        return np.clip(v, self.qmin, self.qmax)

    def shift(self, acc, s):
        half = 1 << (s - 1)
        return self.sat((np.minimum(acc, INT32_MAX - half) + half) >> s)

    def mul(self, a, b):
        return self.shift(a.astype(np.int64) * b.astype(np.int64), self.x)

    def dot(self, a, w):
        if self.lowp:
            return np.rint(_bf16(a).astype(np.float64) @ _bf16(w).astype(np.float64)
                           ).astype(np.int64)
        return a.astype(np.int64) @ w.astype(np.int64)

    def act(self, q, fn):
        table, lo, step = self.luts[fn]
        v = q.astype(np.float32) * np.float32(2.0 ** -self.x)
        idx = np.floor((v - np.float32(lo)) / np.float32(step)).astype(np.int64)
        t = table[np.clip(idx, 0, len(table) - 1)]
        return self.sat(np.floor(t * np.float32(1 << self.x) + np.float32(0.5)
                                 ).astype(np.int64))

    def cell(self, w, b, x_t, h, c):
        """One LSTM cell step on ``(N, n_in)`` inputs and ``(N, H)`` state."""
        H = h.shape[-1]
        acc = self.dot(np.concatenate([x_t, h], axis=-1), w) \
            + (b.astype(np.int64) << self.x)
        z = self.shift(acc, self.x)
        i = self.act(z[:, 0:H], "sigmoid")
        f = self.act(z[:, H:2 * H], "sigmoid")
        g = self.act(z[:, 2 * H:3 * H], "tanh")
        o = self.act(z[:, 3 * H:4 * H], "sigmoid")
        c = self.sat(self.mul(f, c) + self.mul(i, g))
        h = self.mul(o, self.act(c, "tanh"))
        return h, c


def forward(dp: Datapath, ws, bs, qxs: np.ndarray):
    """``qxs: (N, T, n_in)`` int -> ``(h_seq (N, T, H), qh (L, N, H),
    qc (L, N, H))``, every stream from zero state, all int64."""
    n, steps, _ = qxs.shape
    H = ws[0].shape[1] // GATES
    hs = [np.zeros((n, H), np.int64) for _ in ws]
    cs = [np.zeros((n, H), np.int64) for _ in ws]
    seq = np.zeros((n, steps, H), np.int64)
    for t in range(steps):
        inp = qxs[:, t].astype(np.int64)
        for l, (w, b) in enumerate(zip(ws, bs)):
            hs[l], cs[l] = dp.cell(w, b, inp, hs[l], cs[l])
            inp = hs[l]
        seq[:, t] = inp
    return seq, np.stack(hs), np.stack(cs)
