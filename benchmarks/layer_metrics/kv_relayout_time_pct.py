"""Kernels: device time of the operations under the paged cache's `kv_cow`,
`kv_write` and `kv_gather` scopes (models/bert.py: copy-on-write, the K/V
scatter through the block table, each slot's view gathered back out) over
device-busy time, in %.  Attention itself (`paged_attention`) is not in it."""
from benchmarks import program_trace


def compute(run):
    return program_trace.scope_pct(program_trace.of_run(),
                                   ("kv_cow", "kv_write", "kv_gather"))
