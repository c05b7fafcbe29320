"""Kernels: device time of the operations under the `paged_gqa_attention`
scope (ops/attention.py: the paged grouped-query kernel with the relayout of
its query and output rows, or the XLA form's scores and products) over
device-busy time, in %.  The projections, head norms, rotation, gate and
output product are `gqa_attention`'s, the writes `kv_write`'s: not in it."""
from benchmarks import scope_time


def compute(run):
    return scope_time.scope_pct(("paged_gqa_attention",))
