"""Loop: 95th percentile of the time between consecutive steps finishing,
in ms.  The window never blocks on the step just dispatched; it blocks on
the loss of the step two behind, so the instants at which those fetches
return are one step apart in steady state (host clock)."""
from benchmarks import harness


def compute(run):
    at = run.facts.get("step_done_at") or []
    gaps = [b - a for a, b in zip(at, at[1:])]
    if len(gaps) < 20:
        return None
    return harness.quantile(gaps, 95) * 1e3
