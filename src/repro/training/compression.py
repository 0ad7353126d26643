"""Gradient compression for the slow inter-pod links (paper C4 on the wire).

Cross-pod gradient reduction at 2+ pods moves |params| bytes per step over
data-centre links an order of magnitude slower than intra-pod ICI.  We
quantise each gradient leaf to int8 with per-block (256) max-abs scales,
psum the int8 payload and the scales separately, and dequantise — 4x fewer
bytes than fp32 (2x vs bf16) at <1% relative error on the mean (tested).

Error behaviour: quantisation noise is zero-mean and averages down across
pods; the scales themselves are reduced exactly.  An optional error-feedback
buffer (residual carried to the next step) is provided for accuracy-critical
runs.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


__all__ = ["compressed_pmean", "compressed_pmean_with_feedback"]

_BLOCK = 256


def _quantize_leaf(g: jax.Array):
    flat = g.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % _BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, _BLOCK)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1), 1e-20) / 127.0
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_leaf(q: jax.Array, scale: jax.Array, shape, size):
    flat = (q.astype(jnp.float32) * scale[:, None]).reshape(-1)
    return flat[:size].reshape(shape)


def compressed_pmean(grads: Any, axis_name: str) -> Any:
    """Mean of ``grads`` across ``axis_name`` with an int8 wire format.

    Implementation: all-gather the int8 payloads and their per-block scales
    (ring all-gather wire bytes ~= n_pods x N x 1 B, vs 8 x N B for an fp32
    all-reduce — a 4x saving at 2 pods), dequantise each pod's contribution
    with its OWN scale, and average locally.  The only error is each pod's
    quantisation noise (~0.4 % relative), zero-mean across pods."""
    n = jax.lax.axis_size(axis_name)

    def leaf(g):
        q, scale = _quantize_leaf(g)
        q_all = jax.lax.all_gather(q, axis_name)          # (n, nblk, B) int8
        s_all = jax.lax.all_gather(scale, axis_name)      # (n, nblk) f32
        summed = jnp.sum(q_all.astype(jnp.float32) * s_all[..., None], axis=0)
        flat = summed.reshape(-1)[: g.size].reshape(g.shape)
        return (flat / n).astype(g.dtype)

    return jax.tree.map(leaf, grads)


def compressed_pmean_with_feedback(grads: Any, residuals: Any, axis_name: str):
    """Error-feedback variant: the local quantisation error is added to the
    next step's gradient (Karimireddy et al., 2019) — eliminates bias
    accumulation for long runs.  Returns (mean_grads, new_residuals)."""
    n = jax.lax.axis_size(axis_name)

    def leaf(g, r):
        g_fb = g.astype(jnp.float32) + r
        q, scale = _quantize_leaf(g_fb)
        local_hat = _dequantize_leaf(q, scale, g.shape, g.size)
        new_r = g_fb - local_hat
        q_all = jax.lax.all_gather(q, axis_name)
        s_all = jax.lax.all_gather(scale, axis_name)
        summed = jnp.sum(q_all.astype(jnp.float32) * s_all[..., None], axis=0)
        g_hat = (summed.reshape(-1)[: g.size].reshape(g.shape)) / n
        return g_hat.astype(g.dtype), new_r

    flat_g, tree = jax.tree.flatten(grads)
    flat_r = jax.tree.leaves(residuals)
    out = [leaf(g, r) for g, r in zip(flat_g, flat_r)]
    return (jax.tree.unflatten(tree, [o[0] for o in out]),
            jax.tree.unflatten(tree, [o[1] for o in out]))
