"""Sharding rules: logical parameter/activation axes → mesh axes.

The production mesh is ``("data", "model")`` single-pod or
``("pod", "data", "model")`` multi-pod (launch/mesh.py).  Parallelism map:

* DP   — batch over ``pod``+``data``.
* FSDP — parameters and optimizer state additionally sharded over the
  ``fsdp_axes`` (default ``data``; kimi-scale configs add ``pod``); XLA
  inserts the per-layer all-gathers.
* TP   — attention heads / ffn columns / vocab over ``model``.
* EP   — MoE experts over ``model`` via shard_map all_to_all (models/moe.py).
* SP   — long-context decode shards the KV/sequence dim over ``data``
  (batch=1 cells), with flash-decoding partial-softmax combine.

Rules are name-based over the param pytree paths, so every architecture in
the zoo shares one rule set; per-arch overrides are config fields.  All
rules check divisibility and fall back to replication on that axis.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["RunContext", "constrain", "param_pspec", "param_shardings",
           "logical_rules", "fleet_slot_specs", "fleet_mesh"]


@dataclasses.dataclass(frozen=True)
class RunContext:
    """Everything the model forward needs to know about distribution."""

    mesh: Mesh | None = None
    dp_axes: tuple = ("data",)          # batch axes ("pod","data") multi-pod
    tp_axis: str | None = "model"
    fsdp_axes: tuple = ("data",)        # param-sharding axes
    ep: bool = False                    # expert-parallel shard_map MoE
    seq_axis: str | None = None         # sequence sharding for long-context
    use_pallas: bool = False
    remat: str = "none"                 # none | full | dots
    zero1: bool = False                 # ZeRO-1: shard only optimizer state
    #   over the FSDP axes; params replicate over them (TP still applies).
    #   Right call when params/TP fit HBM: one grad all-reduce + one update
    #   all-gather per STEP instead of per-layer-per-microbatch gathers.

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        return int(
            jax.numpy.prod(jax.numpy.array([self.mesh.shape[a] for a in self.dp_axes]))
        )

    def axis_size(self, name: str | None) -> int:
        if self.mesh is None or name is None:
            return 1
        return self.mesh.shape[name]


def constrain(x: jax.Array, ctx: RunContext, spec: P) -> jax.Array:
    """with_sharding_constraint that degrades to identity without a mesh and
    drops axes that don't divide the corresponding dim."""
    if ctx.mesh is None:
        return x
    cleaned = []
    for dim, axes in enumerate(spec):
        if axes is None:
            cleaned.append(None)
            continue
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        size = 1
        for a in axes_t:
            size *= ctx.mesh.shape[a]
        cleaned.append(axes if x.shape[dim] % size == 0 else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, P(*cleaned)))


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# (regex over '/'-joined pytree path) -> logical spec template.
# Templates use tokens: F = fsdp axes, T = tp axis, E = expert (tp) axis,
# None = replicated.  Applied left-to-right over the param's dims.
_RULES: list[tuple[str, tuple]] = [
    (r"embed$",                ("T", "F")),        # (V, d)
    (r"lm_head$",              ("F", "T")),        # (d, V)
    (r"(wq|wk|wv)$",           ("F", "T")),        # (d, heads*hd)
    (r"wo$",                   ("T", "F")),        # (heads*hd, d)
    # MoE rules MUST precede the generic MLP rules (same leaf names)
    (r"moe/router$",           (None, None)),      # (d, E) tiny, replicated
    (r"moe/(w_gate|w_up)$",    ("E", "F", None)),  # (E, d, f)
    (r"moe/w_down$",           ("E", None, "F")),  # (E, f, d)
    (r"(w_gate|w_up)$",        ("F", "T")),        # (d, f)
    (r"w_down$",               ("T", "F")),        # (f, d)
    (r"(w_z|w_x)$",            ("F", "T")),        # mamba in-proj columns
    (r"(w_b|w_c|w_dt)$",       ("F", None)),       # small state projections
    (r"out_proj$",             ("T", "F")),        # (d_in, d)
    (r"conv_[wxbc].*$",        (None, None)),
    # int8-quantised Adam moments (_Q8: q (nblocks, 256), scale (nblocks,)):
    # shard the block dim over FSDP axes like the parameter it mirrors
    (r"/q$",                   ("F", None)),
    (r"/scale$",               ("F",)),
    (r"(norm|scale|bias|a_log|d_skip|dt_bias|q_norm|k_norm|conv_b)$", (None,)),
    (r"frontend.*$",           (None, None)),
]


def logical_rules() -> list[tuple[str, tuple]]:
    return list(_RULES)


# ---------------------------------------------------------------------------
# Fleet-serving rules: slot-axis data parallelism for SensorFleetEngine
# ---------------------------------------------------------------------------


def fleet_slot_specs(data_axis: str = "data") -> dict[str, P]:
    """PartitionSpecs for the fleet engine's slot-sharded step.

    The engine's batched step is pure data parallelism over the *slot* axis
    (independent sensor streams never interact), so every operand either
    shards its slot dim over ``data_axis`` or replicates:

    ========== =========================== ==========================
    key        operand                     spec
    ========== =========================== ==========================
    ``x``      inputs ``(slots, t, n_in)`` ``P(data, None, None)``
    ``state``  carry ``(L, slots, H)``     ``P(None, data, None)``
    ``mask``   lane mask ``(slots,)``      ``P(data)``
    ``seq``    output ``(slots, t, H)``    ``P(data, None, None)``
    ``params`` quantised weights/biases    ``P()`` (replicated)
    ========== =========================== ==========================

    Because the slot dim is block-partitioned, slot ``s`` of ``S`` lives on
    device ``s * D // S`` of ``D`` for the engine's whole lifetime — the
    placement invariant that keeps per-stream ``h``/``c`` carry on one device
    across join/leave churn (``serving/lstm_engine.py``).
    """
    return {
        "x": P(data_axis, None, None),
        "state": P(None, data_axis, None),
        "mask": P(data_axis),
        "seq": P(data_axis, None, None),
        "params": P(),
    }


def fleet_mesh(devices=None, data_axis: str = "data") -> Mesh:
    """A 1-D mesh over ``devices`` (default: all local) for slot sharding."""
    import numpy as np

    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.array(devices), (data_axis,))


def _resolve(template: tuple, ctx: RunContext, shape: tuple) -> P:
    out = []
    for dim, tok in enumerate(template[: len(shape)]):
        if tok is None:
            out.append(None)
            continue
        axes = {"F": ctx.fsdp_axes, "T": (ctx.tp_axis,), "E": (ctx.tp_axis,)}[tok]
        axes = tuple(a for a in axes if a is not None)
        size = 1
        for a in axes:
            size *= ctx.axis_size(a)
        if size > 1 and shape[dim] % size == 0:
            out.append(axes if len(axes) > 1 else axes[0])
        else:
            out.append(None)
    while len(out) < len(shape):
        out.append(None)
    return P(*out)


def param_pspec(path: str, shape: tuple, ctx: RunContext) -> P:
    """Sharding spec for one parameter.  Stacked-layer leading dims (the
    scan axis, named 'blocks/<i>/...') stay unsharded — rules apply to the
    trailing dims."""
    # int8-quantised Adam moments (_Q8) are shape-preserving: ``q`` shards
    # exactly like its parameter; ``scale`` (last dim = block count) uses the
    # parent rule with the last dim forced replicated when it no longer
    # divides.  Strip the /q|/scale suffix and recurse on the parent path.
    m = re.match(r"(opt_state/[mv]/.*)/(q|scale)$", path)
    if m:
        return param_pspec(m.group(1), shape, ctx)
    n_stack = 0
    if re.search(r"blocks/", path):
        n_stack = 1  # leading repeat axis from stacking
    body = shape[n_stack:]
    for pat, template in _RULES:
        if re.search(pat, path):
            if ctx.zero1 and not path.startswith("opt_state"):
                template = tuple(None if t == "F" else t for t in template)
            spec = _resolve(template, ctx, body)
            return P(*([None] * n_stack), *spec)
    return P(*([None] * len(shape)))


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):        # DictKey
            parts.append(str(p.key))
        elif hasattr(p, "name"):     # GetAttrKey (registered dataclasses)
            parts.append(str(p.name))
        elif hasattr(p, "idx"):      # SequenceKey
            parts.append(str(p.idx))
        else:
            parts.append(str(p).strip("."))
    return "/".join(parts)


def param_shardings(shapes: Any, ctx: RunContext) -> Any:
    """Map a pytree of ShapeDtypeStructs/arrays to NamedShardings."""
    if ctx.mesh is None:
        return jax.tree.map(lambda _: None, shapes)

    def leaf(path, x):
        return NamedSharding(ctx.mesh, param_pspec(_path_str(path), x.shape, ctx))

    return jax.tree_util.tree_map_with_path(leaf, shapes)
