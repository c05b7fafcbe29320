"""Model: device time of the operations under `hc_mix` (models/xing4.py:
the hyper-connection units' coefficients, their Sinkhorn iterations and the
mixing of the residual streams) over device-busy time, in %."""
from benchmarks import scope_time


def compute(run):
    return scope_time.scope_pct(("hc_mix",))
