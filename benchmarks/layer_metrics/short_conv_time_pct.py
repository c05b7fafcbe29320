"""Model: device time of the operations under a gated short convolution's
`short_conv` scope (models/lfm2.py: `W_in`, both gates, the taps over the
slot's kept rows with their read and write, `W_out`) over device-busy
time, in %.  A program without the scope gives nothing."""
from benchmarks import scope_time


def compute(run):
    return scope_time.scope_pct(("short_conv",))
