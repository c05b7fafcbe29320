"""Operations and bytes of a dropless routed expert layer's grouped
products, from shapes: the least the algorithm needs for one call.

An expert is ``w_down(silu(w_gate x) * w_up x)``: three matrices of
``hidden_size x width``.  For ``pairs`` chosen (token, expert) pairs that
fall on ``touched`` distinct experts:

- operations: 2 per multiply-add over the three products of every pair;
- bytes: the three matrices of every expert that got a token, read once;
  each pair's input row read and output row written (``hidden_size``
  values each); the ``width``-wide intermediates are counted as staying on
  the chip (the least).

The shared expert is not a grouped product and is not counted here.  A
share of the roofline that reads over 100% is a bug in these counts, never
a fast kernel.
"""

from __future__ import annotations

from typing import Dict


def expert_products_flops(*, pairs: float, hidden_size: int,
                          width: int) -> float:
    return 2.0 * 3 * hidden_size * width * pairs


def expert_products_bytes(*, pairs: float, touched: float, hidden_size: int,
                          width: int, weight_itemsize: int,
                          activation_itemsize: int) -> float:
    return touched * 3 * hidden_size * width * weight_itemsize \
        + pairs * 2 * hidden_size * activation_itemsize


def expert_products_seconds(shape: Dict, pairs: float, touched: float,
                            peaks: Dict) -> Dict[str, float]:
    """The least time one layer's grouped products can take on a device of
    ``peaks``: the larger of the two bounds, and which it is."""
    d, f = shape["hidden_size"], shape["moe_intermediate_size"]
    t_flops = expert_products_flops(pairs=pairs, hidden_size=d, width=f) \
        / peaks["bf16_flops_per_s"]
    t_bytes = expert_products_bytes(
        pairs=pairs, touched=touched, hidden_size=d, width=f,
        weight_itemsize=shape["weight_itemsize"],
        activation_itemsize=shape["activation_itemsize"]) \
        / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops > t_bytes else "bytes"}
