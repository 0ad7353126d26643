"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

This is the paper's core insight re-derived for the modern recurrent family
(DESIGN.md §5): an SSD layer is a gated recurrence just like the LSTM cell,
and its throughput bottleneck has the same fix —

* C1 (gate parallelism)  → within a chunk the recurrence is re-associated
  into three dense matmuls (score = C Bᵀ ⊙ L decay mask, intra = score·X,
  inter = decay·C·h) that all hit the MXU;
* C2 (pipelined update)  → the inter-chunk state update streams behind the
  intra-chunk matmuls in the same kernel invocation;
* C5 (state residency)   → the running state ``h (P, N)`` lives in VMEM
  scratch across the *sequential* chunk grid dimension — it never visits
  HBM between chunks, exactly like h/C in the FPGA's BRAM.

Grid: (batch, heads, n_chunks) with the chunk axis sequential ("arbitrary"
dimension semantics on TPU).  Oracle: ``ref.ssd_chunk_scan_ref`` (the exact
O(T) recurrence) — the kernel must match it for every chunk size.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_chunk_scan_pallas"]


def _ssd_kernel(x_ref, alog_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref, hstate):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        hstate[...] = h0_ref[0, 0].astype(jnp.float32)

    xq = x_ref[0, 0].astype(jnp.float32)      # (Q, P)
    aq = alog_ref[0, 0].astype(jnp.float32)   # (Q,)
    bq = b_ref[0, 0].astype(jnp.float32)      # (Q, N)
    cq = c_ref[0, 0].astype(jnp.float32)      # (Q, N)
    h = hstate[...]                           # (P, N) carried in VMEM

    q = xq.shape[0]
    acum = jnp.cumsum(aq)                     # inclusive per-step log decay

    # --- intra-chunk: re-associated recurrence as masked attention (C1) ----
    seg = acum[:, None] - acum[None, :]       # decay from step s to t
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    L = jnp.where(row >= col, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(
        cq, bq, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * L                                      # (Q, Q)
    y = jax.lax.dot_general(
        scores, xq, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # (Q, P)

    # --- inter-chunk: contribution of the carried state (C5) ---------------
    y = y + jnp.exp(acum)[:, None] * jax.lax.dot_general(
        cq, h, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                          # (Q,N)·(P,N)ᵀ -> (Q, P)

    # --- state update, streams behind the matmuls (C2) ---------------------
    a_sum = acum[-1]
    wgt = jnp.exp(a_sum - acum)                # (Q,)
    h_new = jnp.exp(a_sum) * h + jax.lax.dot_general(
        xq * wgt[:, None], bq, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                          # (P, N)

    hstate[...] = h_new
    y_ref[0, 0] = y.astype(y_ref.dtype)
    hout_ref[0, 0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan_pallas(
    x: jax.Array,       # (B, T, H, P)
    a_log: jax.Array,   # (B, T, H), log decay <= 0
    b: jax.Array,       # (B, T, H, N)
    c: jax.Array,       # (B, T, H, N)
    h0: jax.Array | None = None,   # (B, H, P, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    B, T, H, P = x.shape
    N = b.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), x.dtype)

    # head-major layout so each (batch, head) program streams its chunks
    xt = jnp.moveaxis(x, 1, 2)          # (B, H, T, P)
    at = jnp.moveaxis(a_log, 1, 2)      # (B, H, T)
    bt = jnp.moveaxis(b, 1, 2)          # (B, H, T, N)
    ct = jnp.moveaxis(c, 1, 2)          # (B, H, T, N)

    pad_t = (-T) % chunk
    if pad_t:  # zero padding is exact: decay 1, b=c=0 => state & y unaffected
        xt = jnp.pad(xt, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        at = jnp.pad(at, ((0, 0), (0, 0), (0, pad_t)))
        bt = jnp.pad(bt, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
        ct = jnp.pad(ct, ((0, 0), (0, 0), (0, pad_t), (0, 0)))
    Tp = T + pad_t
    n_chunks = Tp // chunk

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        )
    scratch = [pltpu.VMEM((P, N), jnp.float32)]

    y, h_fin = pl.pallas_call(
        _ssd_kernel,
        grid=(B, H, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((1, 1, chunk), lambda i, j, k: (i, j, k)),
            pl.BlockSpec((1, 1, chunk, N), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((1, 1, P, N), lambda i, j, k: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda i, j, k: (i, j, k, 0)),
            pl.BlockSpec((1, 1, P, N), lambda i, j, k: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tp, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), x.dtype),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        **kwargs,
    )(xt, at, bt, ct, h0)
    return jnp.moveaxis(y[:, :, :T], 2, 1), h_fin
