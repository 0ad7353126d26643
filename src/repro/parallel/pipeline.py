"""GPipe-style pipeline parallelism over the ``pod`` axis.

The multi-pod mesh's ``pod`` axis defaults to data parallelism; this module
is the alternative: each pod holds a *slice of the layer stack* (stage) and
microbatches stream through via ``collective_permute``.  For models whose
parameters do not fit even FSDP-sharded in one pod, PP over pods trades the
per-layer FSDP all-gathers (which cross the slow inter-pod links) for
point-to-point boundary activations — the canonical reason real 1000+-node
deployments pipeline across pods.

Implementation: ``shard_map`` manual over the stage axis; the GPipe schedule
runs ``n_micro + n_stages - 1`` ticks; stage s processes microbatch ``t - s``
at tick ``t``.  Backward flows through the same ppermutes by AD (GPipe
semantics: full forward then full backward; bubble fraction
``(n_stages-1)/(n_micro+n_stages-1)``).  The roofline accounting counts the
boundary ppermute bytes.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["pipeline_apply", "split_stages"]


def split_stages(stacked_params, n_stages: int):
    """Reshape stacked-layer params (L, ...) -> (n_stages, L/n_stages, ...)."""
    def leaf(x):
        L = x.shape[0]
        assert L % n_stages == 0, f"layers {L} % stages {n_stages} != 0"
        return x.reshape(n_stages, L // n_stages, *x.shape[1:])
    return jax.tree.map(leaf, stacked_params)


def pipeline_apply(
    layer_fn: Callable,          # (layer_params, x) -> x
    staged_params,               # (n_stages, L/stage, ...) pytree
    x: jax.Array,                # (n_micro, mb, ...) microbatched input
    mesh: Mesh,
    axis_name: str = "pod",
):
    """Run the staged stack over microbatches with the GPipe schedule.
    Returns (n_micro, mb, ...) outputs (valid on every device after the
    final gather)."""
    n_stages = mesh.shape[axis_name]
    n_micro = x.shape[0]
    ticks = n_micro + n_stages - 1

    def stage_body(params_stage, xs):
        # shard_map keeps the sharded stage dim as size 1 — strip it:
        # (1, L/stage, ...) -> (L/stage, ...)
        params_stage = jax.tree.map(lambda a: a[0], params_stage)
        sid = jax.lax.axis_index(axis_name)

        def run_stage(h):
            def body(carry, lp):
                return layer_fn(lp, carry), None
            h, _ = jax.lax.scan(body, h, params_stage)
            return h

        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)           # activation in flight
        outs = jnp.zeros((n_micro, *mb_shape), xs.dtype)

        def tick(t, state):
            buf, outs = state
            mb_idx = t - sid
            # stage 0 ingests microbatch t; others use what arrived
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            h_in = jnp.where(sid == 0, feed, buf)
            h_out = run_stage(h_in)
            # last stage records its (valid) microbatch output
            valid = (mb_idx >= 0) & (mb_idx < n_micro)
            outs = jnp.where(
                (sid == n_stages - 1) & valid,
                jax.lax.dynamic_update_index_in_dim(
                    outs, h_out, jnp.clip(mb_idx, 0, n_micro - 1), 0),
                outs)
            # shift the pipe: stage s -> s+1 (ring; wraparound ignored)
            sent = jax.lax.ppermute(
                h_out, axis_name,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return sent, outs

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # rotate so device 0 holds the LAST stage's outputs; returning a
        # stage-sharded (not "replicated") output keeps the backward
        # cotangent on a single path (a replicated out_spec splits it 1/n).
        outs = jax.lax.ppermute(
            outs, axis_name,
            [((n_stages - 1 + i) % n_stages, i) for i in range(n_stages)])
        return outs[None]

    stacked = jax.shard_map(
        stage_body,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(axis_name),
        check_vma=False,
    )(staged_params, x)
    return stacked[0]
