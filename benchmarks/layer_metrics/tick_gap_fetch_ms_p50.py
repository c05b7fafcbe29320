"""Serve engine: the part of `tick_device_gap_ms_p50` under all the
`engine.fetch` spans of a gap (median over the traced gaps, ms): the tail
from the end of the tick's program to its last output on the host."""
from benchmarks import handoff_trace


def compute(run):
    return handoff_trace.gap_ms_p50(handoff_trace.of_run(), "engine.fetch")
