"""Train-step factory: pjit-able, donation-friendly, microbatched.

``make_train_step(model, ctx, opt, schedule)`` builds the canonical step:

    grads = grad(loss)(params, batch)          # data/model sharding via GSPMD
    grads = clip_by_global_norm(grads)
    params, opt_state = opt.update(...)

Options:
  * ``accum_steps`` — gradient-accumulation microbatching (sequential scan
    over batch slices; the standard memory lever at scale).
  * ``grad_compression`` — int8-quantised cross-pod gradient mean: the step
    is wrapped in a shard_map that is *manual* over the ``pod`` axis and
    auto (GSPMD) over data/model, so the inter-pod reduction — the slowest
    link in a multi-pod system — moves 4x fewer bytes (paper C4 applied to
    the wire).  See training/compression.py.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import RunContext
from repro.training.compression import compressed_pmean
from repro.training.optimizer import Optimizer, OptState, clip_by_global_norm

__all__ = ["TrainState", "init_train_state", "make_train_step"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: OptState


def init_train_state(model, key, opt: Optimizer) -> TrainState:
    params = model.init(key)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      opt_state=opt.init(params))


def make_train_step(
    model,
    ctx: RunContext,
    opt: Optimizer,
    schedule: Callable,
    *,
    accum_steps: int = 1,
    max_grad_norm: float = 1.0,
    grad_compression: bool = False,
    param_shardings=None,
):
    """Returns ``step(state, batch) -> (state, metrics)``; jit it with the
    state/batch shardings from the launch layer and donate ``state``."""

    def loss_fn(params, batch):
        loss, parts = model.loss(params, batch, ctx)
        return loss, parts

    def compute_grads(params, batch):
        if accum_steps == 1:
            (loss, parts), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            return loss, parts, grads

        def micro(carry, mb):
            loss_sum, grads_sum = carry
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
            return (loss_sum + loss,
                    jax.tree.map(lambda a, b: (a + b).astype(a.dtype),
                                 grads_sum, g)), None

        micro_batch = jax.tree.map(
            lambda x: x.reshape(accum_steps, x.shape[0] // accum_steps, *x.shape[1:]),
            batch,
        )
        # accumulate in the param dtype: an fp32 accumulator doubles the
        # largest state buffer at 1T-param scale (grads are averaged over
        # only `accum_steps` microbatches, so bf16 accumulation is safe)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
        (loss_sum, grads), _ = jax.lax.scan(micro, (jnp.zeros(()), zeros), micro_batch)
        inv = 1.0 / accum_steps
        return loss_sum * inv, {}, jax.tree.map(lambda g: g * inv, grads)

    def step_body(state: TrainState, batch):
        loss, parts, grads = compute_grads(state.params, batch)
        if param_shardings is not None:
            # pin dgrads to the parameter layout BEFORE the optimizer math —
            # EP/shard_map cotangents exit with different specs and the
            # moment update would otherwise run replicated (kimi: TBs)
            grads = jax.tree.map(
                lambda g, s: g if s is None else
                jax.lax.with_sharding_constraint(g, s),
                grads, param_shardings)
        if grad_compression:
            grads = compressed_pmean(grads, "pod")
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = schedule(state.step)
        params, opt_state = opt.update(grads, state.opt_state, state.params, lr)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr,
                   **{k: v for k, v in parts.items()}}
        return TrainState(state.step + 1, params, opt_state), metrics

    if not grad_compression:
        return step_body

    # manual over 'pod' (so the int8 pmean is explicit), auto elsewhere.
    mesh = ctx.mesh
    assert mesh is not None and "pod" in mesh.axis_names, \
        "grad_compression needs a multi-pod mesh"

    def sharded_step(state, batch):
        return jax.shard_map(
            step_body,
            mesh=mesh,
            in_specs=(P(), P("pod")),    # state replicated over pods, batch split
            out_specs=(P(), P()),
            axis_names={"pod"},
            check_vma=False,
        )(state, batch)

    return sharded_step
