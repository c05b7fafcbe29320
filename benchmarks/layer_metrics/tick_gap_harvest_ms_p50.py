"""Serve engine: the part of `tick_device_gap_ms_p50` that lies under the
finished tick's `engine.sync` (its tail: the program has ended, the tokens
are on their way to the host), `engine.harvest` and `engine.gauges` spans
(median over the traced gaps, ms)."""
from benchmarks import program_trace


def compute(run):
    return program_trace.gap_ms_p50(program_trace.of_run(),
                                    program_trace.HARVEST_PHASES)
