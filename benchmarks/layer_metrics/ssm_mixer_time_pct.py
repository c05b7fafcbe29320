"""Model: device time of the operations under a state-space (Mamba-2)
mixer's `ssm_mixer` scope (models/granite_hybrid.py: the in and out
projections, the convolution, the scan, the gated norm) over device-busy
time, in %.  A program without the scope gives nothing."""
from benchmarks import scope_time


def compute(run):
    return scope_time.scope_pct(("ssm_mixer",))
