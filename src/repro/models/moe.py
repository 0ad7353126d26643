"""Mixture-of-Experts: router + two dispatch implementations.

* ``moe_dense_sort`` — single-device path: tokens sorted by expert, grouped
  matmul via ``jax.lax.ragged_dot`` (full AD support), unsort, weighted
  combine.  No token dropping.  This is also the correctness oracle for the
  distributed path.

* ``moe_expert_parallel`` — the at-scale path, written for use *inside*
  ``shard_map``: experts are sharded over the ``model`` mesh axis; each
  device routes its local tokens, packs them into per-target-shard capacity
  buffers (capacity_factor dropping, as GShard/Switch), ``all_to_all``s them
  across the model axis, runs the local grouped matmul (ragged_dot over its
  resident experts), ``all_to_all``s results back, and combines at the
  origin.  Everything is differentiable, so the same code serves train and
  serve steps.

Router: softmax → top-k → renormalised top-k weights, plus the standard
load-balance auxiliary loss (fraction-of-tokens × mean-router-prob × E).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp


__all__ = [
    "MoEWeights",
    "router_topk",
    "moe_dense_sort",
    "moe_expert_parallel",
    "moe_expert_parallel_gathered",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MoEWeights:
    """router: (d, E); gate/up: (E, d, f); down: (E, f, d).

    Registered as a dataclass pytree so tree paths carry field NAMES —
    the name-based sharding rules (parallel/sharding.py) and checkpoint
    leaf naming depend on that."""

    router: jax.Array
    w_gate: jax.Array | None
    w_up: jax.Array
    w_down: jax.Array


def router_topk(x: jax.Array, router_w: jax.Array, top_k: int):
    """x: (T, d) -> (weights (T,k), experts (T,k) int32, aux_loss scalar)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                 # (T, E)
    top_w, top_e = jax.lax.top_k(probs, top_k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # load-balance aux: E * sum_e f_e * p_e
    n_experts = router_w.shape[-1]
    occupancy = jnp.zeros((n_experts,), jnp.float32).at[top_e.reshape(-1)].add(1.0)
    f_e = occupancy / (x.shape[0] * top_k)
    p_e = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(f_e * p_e)
    return top_w, top_e.astype(jnp.int32), aux


def _expert_ffn(x_sorted: jax.Array, gs: jax.Array, w: MoEWeights, act: Callable):
    """Grouped FFN over tokens sorted by expert; gs: (E_local,) group sizes."""
    up = jax.lax.ragged_dot(x_sorted, w.w_up, gs)
    if w.w_gate is not None:
        up = act(jax.lax.ragged_dot(x_sorted, w.w_gate, gs)) * up
    else:
        up = act(up)
    return jax.lax.ragged_dot(up, w.w_down, gs)


def moe_dense_sort(x: jax.Array, w: MoEWeights, top_k: int, act: Callable):
    """x: (T, d) -> (y (T, d), aux).  Dropless single-device dispatch."""
    t, d = x.shape
    n_experts = w.w_up.shape[0]
    top_w, top_e, aux = router_topk(x, w.router, top_k)

    flat_e = top_e.reshape(-1)                      # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t), top_k)       # token index per copy
    flat_w = top_w.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    xs = x[flat_t[order]]                           # (T*k, d) sorted by expert
    gs = jnp.bincount(flat_e, length=n_experts).astype(jnp.int32)

    ys = _expert_ffn(xs, gs, w, act)

    y = jnp.zeros((t, d), ys.dtype)
    y = y.at[flat_t[order]].add(ys * flat_w[order][:, None])
    return y.astype(x.dtype), aux


def moe_expert_parallel(
    x: jax.Array,            # (T_local, d) — this device's tokens
    w: MoEWeights,           # expert leaves already sharded: (E_local, ...)
    top_k: int,
    act: Callable,
    *,
    axis_name: str = "model",
    capacity_factor: float = 1.25,
):
    """Expert-parallel MoE for use inside shard_map.  See module docstring."""
    t_loc, d = x.shape
    e_local = w.w_up.shape[0]
    n_shards = jax.lax.axis_size(axis_name)
    n_experts = e_local * n_shards

    # --- route (router weights are replicated across the axis) -------------
    top_w, top_e, aux = router_topk(x, w.router, top_k)
    # NOTE: aux is per-device here; the caller pmean-s it across the mesh.

    m = t_loc * top_k
    flat_e = top_e.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t_loc), top_k)
    flat_w = top_w.reshape(-1)
    shard = flat_e // e_local                        # target model-shard
    local_e = flat_e % e_local

    # --- pack into per-target capacity buffers -----------------------------
    cap = int(max(8, -(-m * capacity_factor // n_shards)))  # ceil, >= 8
    order = jnp.argsort(shard, stable=True)
    shard_s = shard[order]
    starts = jnp.searchsorted(shard_s, jnp.arange(n_shards))
    pos = jnp.arange(m) - starts[shard_s]
    keep = pos < cap                                  # capacity dropping
    slot = jnp.where(keep, shard_s * cap + pos, n_shards * cap)

    x_send = jnp.zeros((n_shards * cap, d), x.dtype).at[slot].set(
        x[flat_t[order]], mode="drop")
    e_send = jnp.zeros((n_shards * cap,), jnp.int32).at[slot].set(
        local_e[order], mode="drop")

    # --- exchange over the model axis --------------------------------------
    x_recv = jax.lax.all_to_all(
        x_send.reshape(n_shards, cap, d), axis_name, split_axis=0, concat_axis=0, tiled=True
    ).reshape(n_shards * cap, d)
    e_recv = jax.lax.all_to_all(
        e_send.reshape(n_shards, cap), axis_name, split_axis=0, concat_axis=0, tiled=True
    ).reshape(n_shards * cap)

    # --- local grouped matmul over resident experts ------------------------
    order2 = jnp.argsort(e_recv, stable=True)
    inv2 = jnp.argsort(order2, stable=True)
    gs = jnp.bincount(e_recv, length=e_local).astype(jnp.int32)
    ys = _expert_ffn(x_recv[order2], gs, w, act)
    y_recv = ys[inv2]

    # --- reply + origin-side combine ----------------------------------------
    y_back = jax.lax.all_to_all(
        y_recv.reshape(n_shards, cap, d), axis_name, split_axis=0, concat_axis=0, tiled=True
    ).reshape(n_shards * cap, d)

    y_copy = y_back[jnp.clip(slot, 0, n_shards * cap - 1)]
    contrib = y_copy * (flat_w[order] * keep)[:, None]
    y = jnp.zeros((t_loc, d), contrib.dtype).at[flat_t[order]].add(contrib)
    return y.astype(x.dtype), aux


def moe_expert_parallel_gathered(
    x: jax.Array,            # (T_local, d) — sharded over data axes only,
    #                          replicated across the model axis
    w: MoEWeights,           # experts sharded over the model axis (E_local)
    top_k: int,
    act: Callable,
    *,
    axis_name: str = "model",
    capacity_factor: float = 2.0,
):
    """Decode-path EP (for use inside shard_map): token counts are tiny
    (one per sequence), so instead of an all_to_all scatter the tokens stay
    replicated across the model axis; every shard selects the copies routed
    to its resident experts, runs the local grouped matmul, and the partial
    results are psum-combined.  Communication = one psum of (T_local, d)."""
    t_loc, d = x.shape
    e_local = w.w_up.shape[0]
    n_shards = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)

    top_w, top_e, aux = router_topk(x, w.router, top_k)
    # NOTE: aux is per-device here; the caller pmean-s it across the mesh.

    m = t_loc * top_k
    flat_e = top_e.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(t_loc), top_k)
    flat_w = top_w.reshape(-1)
    mine = (flat_e // e_local) == my
    local_e = flat_e % e_local

    cap = int(max(8, -(-m * capacity_factor // n_shards)))
    pos = jnp.cumsum(mine) - 1
    keep = mine & (pos < cap)
    slot = jnp.where(keep, pos, cap)

    x_sel = jnp.zeros((cap + 1, d), x.dtype).at[slot].set(x[flat_t], mode="drop")
    e_sel = jnp.full((cap + 1,), e_local, jnp.int32).at[slot].set(local_e, mode="drop")
    # sort the capacity buffer by local expert (sentinel e_local sorts last)
    order = jnp.argsort(e_sel, stable=True)
    inv = jnp.argsort(order, stable=True)
    gs = jnp.bincount(e_sel, length=e_local).astype(jnp.int32)
    ys = _expert_ffn(x_sel[order], gs, w, act)[inv]

    y_copy = ys[slot]                                  # (m, d), garbage if !keep
    contrib = y_copy * (flat_w * keep)[:, None]
    y = jnp.zeros((t_loc, d), contrib.dtype).at[flat_t].add(contrib)
    y = jax.lax.psum(y, axis_name)
    return y.astype(x.dtype), aux
