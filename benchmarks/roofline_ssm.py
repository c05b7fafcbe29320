"""Operations and bytes of a state-space (Mamba-2) layer's scan, from
shapes: the least the recurrence needs for one serve tick, whatever
implements it.

Per layer a tick has ``slots`` request slots whose state *advanced* (they
fed at least one token) and ``lanes`` live tokens over all of them.  The
scan is the short causal convolution over the ``conv_channels`` of
``xBC``, the recurrence ``S_t = a_t S_{t-1} + dt_t x_t B_t^T`` and the
read-out ``y_t = S_t C_t + D x_t`` (``heads`` heads of ``head_dim``,
``d_state`` columns):

- bytes: each advanced slot's state read once and written once
  (``heads x head_dim x d_state`` of ``state_itemsize``) and its
  ``d_conv - 1`` kept convolution rows likewise; each live lane's ``xBC``
  row read and ``y`` row written (``conv_itemsize`` an element: the
  activations' width) and its ``heads`` steps read (4 bytes each).  A slot
  that did not advance costs nothing: its state need not be touched.
- operations: a live lane's state update (a decay and a multiply-add an
  element: 3) and read-out (a multiply-add: 2) over ``heads x head_dim x
  d_state`` elements, and its convolution (a multiply-add a tap and
  channel).

A share of the roofline that reads over 100% is a bug in these counts,
never a fast scan.
"""

from __future__ import annotations

from typing import Dict


def scan_flops(*, lanes: float, heads: int, head_dim: int, d_state: int,
               conv_channels: int, d_conv: int) -> float:
    return lanes * (5.0 * heads * head_dim * d_state
                    + 2.0 * d_conv * conv_channels)


def scan_bytes(*, slots: float, lanes: float, heads: int, head_dim: int,
               d_state: int, conv_channels: int, d_conv: int,
               state_itemsize: int, conv_itemsize: int) -> float:
    per_slot = heads * head_dim * d_state * state_itemsize \
        + (d_conv - 1) * conv_channels * conv_itemsize
    per_lane = (conv_channels + heads * head_dim) * conv_itemsize \
        + heads * 4
    return 2.0 * slots * per_slot + lanes * per_lane


def scan_seconds(shape: Dict, slots: float, lanes: float,
                 peaks: Dict) -> Dict[str, float]:
    """The least time one layer's scan can take on a device of ``peaks``:
    the larger of the two bounds, and which it is.  ``shape`` is the
    configuration's ``ssm_layer``."""
    dims = {k: shape[k] for k in ("heads", "head_dim", "d_state",
                                  "conv_channels", "d_conv")}
    t_flops = scan_flops(lanes=lanes, **dims) / peaks["bf16_flops_per_s"]
    t_bytes = scan_bytes(slots=slots, lanes=lanes,
                         state_itemsize=shape["state_itemsize"],
                         conv_itemsize=shape["conv_itemsize"], **dims) \
        / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_flops, t_bytes),
            "bound": "flops" if t_flops > t_bytes else "bytes"}
