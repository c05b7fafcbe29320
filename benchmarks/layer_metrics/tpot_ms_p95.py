"""Serve engine: nearest-rank 95th percentile over the window's requests of
`(t_finish - t_first_token) / (n - 1)`, in ms.  A failed or unfinished
request counts with the drain's end as its instant.  The tail beside the
judged median (`tpot_ms_p50`): one stalled tick lifts it for every request
then alive, so it is recorded, not judged (PERF.md section 2)."""
from benchmarks import harness


def compute(run):
    values = run.facts.get("tpot_ms")
    return harness.quantile(values, 95) if values else None
