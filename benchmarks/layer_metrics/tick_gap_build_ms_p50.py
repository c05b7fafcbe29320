"""Serve engine: the part of `tick_device_gap_ms_p50` under `engine.build`
(median over the traced gaps, ms): the chip waiting while the host fills the
step's per-slot numpy arrays, before any hand-off."""
from benchmarks import handoff_trace


def compute(run):
    return handoff_trace.gap_ms_p50(handoff_trace.of_run(), "engine.build")
