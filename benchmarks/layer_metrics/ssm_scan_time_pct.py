"""Kernels: device time of the operations under the `ssm_scan` scope (inside
a Mamba-2 mixer: softplus, the causal convolution, the chunked recurrence
and the per-slot state's read and write; ops/ssd.py) over device-busy time,
in %.  A program without the scope gives nothing."""
from benchmarks import scope_time


def compute(run):
    return scope_time.scope_pct(("ssm_scan",))
