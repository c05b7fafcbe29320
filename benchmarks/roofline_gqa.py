"""Operations and bytes of paged grouped-query attention, from shapes and
the program's own counters: the least one call (one layer, one serve tick)
needs, whatever implements it.

A call has ``walked`` cache positions read over all its slots (the model's
``attn_positions_walked`` counter: a slot's blocks from the first any live
lane may see to the last it wrote, times the block size) and ``lanes`` live
lanes (``lanes_live``: each slot's ``n_new``):

- bytes: the K row and the V row of every walked position, read once
  (``num_kv_heads x head_dim`` values of ``kv_itemsize`` each, twice); each
  live lane's query row in and output row out (``num_heads x head_dim`` of
  ``activation_itemsize``, twice).  Scores and probabilities are counted as
  staying on the chip.
- operations: every walked row scored and weighted by ONE lane's heads (2
  a multiply-add, two products: ``4 x num_heads x head_dim`` a row).  A
  prefilling slot's further lanes do more; the counters do not pair lanes
  with rows, so they are left out (the least).  At 16 lanes a slot the call
  would still do 128 operations a byte against the chip's 240: the bound is
  bytes either way.

A share of the roofline that reads over 100% is a bug in these counts,
never a fast kernel.
"""

from __future__ import annotations

from typing import Dict


def attention_flops(*, walked: float, num_heads: int, head_dim: int) -> float:
    return 4.0 * num_heads * head_dim * walked


def attention_bytes(*, walked: float, lanes: float, num_heads: int,
                    num_kv_heads: int, head_dim: int, kv_itemsize: int,
                    activation_itemsize: int) -> float:
    return walked * 2 * num_kv_heads * head_dim * kv_itemsize \
        + lanes * 2 * num_heads * head_dim * activation_itemsize


def attention_seconds(shape: Dict, walked: float, lanes: float,
                      peaks: Dict) -> Dict[str, float]:
    """The least time one layer's call can take on a device of ``peaks``:
    the larger of the two bounds, and which it is.  ``shape`` is the
    configuration's ``attention_layer``."""
    t_flops = attention_flops(
        walked=walked, num_heads=shape["num_heads"],
        head_dim=shape["head_dim"]) / peaks["bf16_flops_per_s"]
    t_bytes = attention_bytes(
        walked=walked, lanes=lanes, num_heads=shape["num_heads"],
        num_kv_heads=shape["num_kv_heads"], head_dim=shape["head_dim"],
        kv_itemsize=shape["kv_itemsize"],
        activation_itemsize=shape["activation_itemsize"]) \
        / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops > t_bytes else "bytes"}
