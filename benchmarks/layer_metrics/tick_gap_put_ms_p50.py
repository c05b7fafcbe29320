"""Serve engine: the part of `tick_device_gap_ms_p50` under all the
`engine.put` spans of a gap (median over the traced gaps, ms): the chip
waiting for the step's arrays to be handed to the runtime one by one."""
from benchmarks import handoff_trace


def compute(run):
    return handoff_trace.gap_ms_p50(handoff_trace.of_run(), "engine.put")
