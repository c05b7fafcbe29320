"""Kernels: device time of the operations under the routed expert layer's
scopes (`moe_route`, `moe_dispatch`, `moe_experts`, `moe_combine`:
transformer/expert_parallel.py) and `shared_expert` (models/xing4.py), and
of the grouped-product kernels XLA makes of `lax.ragged_dot`
(`%ragged-dot-*`, which carry no path), over device-busy time, in %."""
from benchmarks import scope_time


def compute(run):
    return scope_time.scope_pct(("moe_route", "moe_dispatch", "moe_experts",
                                 "moe_combine", "shared_expert"),
                                ("%ragged-dot",))
