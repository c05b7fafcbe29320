"""Model: device time of the operations under the `lane_pack` scope (the
moves between the tick's packed rows and its `[slots, lanes]` form in
models/granite_hybrid.py: the maps, each Mamba layer's unpack of the scan's
operands and pack of its output, each attention layer's unpack of q and pack
of o, the head's pick) over device-busy time, in %.  A program without the
scope gives nothing."""
from benchmarks import scope_time


def compute(run):
    return scope_time.scope_pct(("lane_pack",))
