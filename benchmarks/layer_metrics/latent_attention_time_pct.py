"""Kernels: device time of the operations under `latent_attention`
(models/xing4.py: absorb, scores, softmax, weighted sum, un-absorb, output
projection; the cache's copy-on-write, write and gather are
`kv_relayout_time_pct`'s) over device-busy time, in %."""
from benchmarks import scope_time


def compute(run):
    return scope_time.scope_pct(("latent_attention",))
