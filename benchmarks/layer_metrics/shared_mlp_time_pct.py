"""Model: device time of the operations under the `shared_mlp` scope (the
dense SwiGLU MLP every layer of models/granite_hybrid.py has) over
device-busy time, in %.  A program without the scope gives nothing."""
from benchmarks import scope_time


def compute(run):
    return scope_time.scope_pct(("shared_mlp",))
