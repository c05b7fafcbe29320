"""Model: device time of the operations under the `mtp` scope (the
next-token module of models/pangu_moe.py whole: the projection of embedding
and hidden state, its one expert layer — whose `latent_attention` and
`moe_*` scopes nest under it — and its head on the lane the next draft is
read from) over device-busy time, in %: what drafting costs a tick beside
the second lane.  A program without the scope gives nothing."""
from benchmarks import scope_time


def compute(run):
    return scope_time.scope_pct(("mtp",))
