"""The model's operations and bytes, from the configuration's shapes alone.

This is the algorithm's work, counted the same whatever kernel implements
it: masked or padded lanes, bf16 pieces of an integer product and one-hot
gathers are the kernel's choices and are not counted.  Only occupied slots
count.

Per layer with ``F = n_in + H`` inputs to the stacked gates (``G = 4``):

* operations per sensor-timestep: ``2*F*G*H`` for the gate matmul, ``G*H``
  bias adds and ``3*G*H`` for the gates' rounding shift (add half, shift,
  saturate), five table lookups per unit (``i, f, g, o, tanh(c)``) at one
  operation each, and the tail: ``f*c`` and ``i*g`` and ``o*tanh(c)`` each a
  multiply with its rounding shift (4 operations), plus the saturating add
  of ``c`` (2): ``14*H``;
* bytes per sensor-timestep: the input read and the top layer's ``h``
  written, int32 as served;
* bytes per engine call and occupied slot: every layer's ``h`` and ``c``
  read and written once;
* bytes per engine call: every layer's weights and bias read once.
"""

from __future__ import annotations

GATES = 4
WORD = 4  # int32, as served


def layer_widths(cfg: dict) -> list[tuple[int, int]]:
    """``(F, H)`` per layer."""
    H = int(cfg["hidden_size"])
    n_in = int(cfg["input_size"])
    return [((n_in if l == 0 else H) + H, H) for l in range(int(cfg["num_layers"]))]


def ops_per_timestep(cfg: dict) -> int:
    ops = 0
    for F, H in layer_widths(cfg):
        ops += 2 * F * GATES * H + GATES * H + 3 * GATES * H + 5 * H + 14 * H
    return ops


def bytes_per_timestep(cfg: dict) -> int:
    return WORD * (int(cfg["input_size"]) + int(cfg["hidden_size"]))


def state_bytes_per_slot(cfg: dict) -> int:
    """Carry read and written once per call: ``h`` and ``c`` of every layer."""
    return 2 * 2 * int(cfg["num_layers"]) * int(cfg["hidden_size"]) * WORD


def weight_bytes(cfg: dict) -> int:
    return sum(WORD * (F * GATES * H + GATES * H) for F, H in layer_widths(cfg))


def call_work(cfg: dict, occupied: int, t_step: int) -> tuple[int, int]:
    """``(operations, bytes)`` of one engine call that advances ``occupied``
    slots by ``t_step`` timesteps."""
    ops = occupied * t_step * ops_per_timestep(cfg)
    nbytes = (occupied * (t_step * bytes_per_timestep(cfg) + state_bytes_per_slot(cfg))
              + weight_bytes(cfg))
    return ops, nbytes
