"""Serve engine: nearest-rank 95th percentile over the window's requests of
`(t_finish - t_first_token) / (n - 1)`, in ms: per request.  A failed or
unfinished request counts with the drain's end as its instant.  The tail
beside the judged median (`tpot_ms_p50`), which since PR 30 is taken per gap
between two tokens, over all gaps of all requests: one stalled tick lifts a
request's mean for every request then alive and is one gap in some ten
thousand, so this tail reads the host's stalls and the median does not; it
is recorded, not judged (PERF.md section 2)."""
from benchmarks import harness


def compute(run):
    values = run.facts.get("tpot_ms")
    return harness.quantile(values, 95) if values else None
