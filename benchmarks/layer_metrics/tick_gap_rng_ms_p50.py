"""Serve engine: the part of `tick_device_gap_ms_p50` under `engine.rng`
(median over the traced gaps, ms): the chip idle while the host splits the
key (the split's two small programs themselves count as busy)."""
from benchmarks import handoff_trace


def compute(run):
    return handoff_trace.gap_ms_p50(handoff_trace.of_run(), "engine.rng")
