"""Kernels: device time of the operations under the train step's
`optimizer` scope (the fused optimizer's Pallas kernels and what XLA runs
round them) over device-busy time, in %."""
from benchmarks import program_trace


def compute(run):
    return program_trace.scope_pct(program_trace.of_run(), ("optimizer",))
