"""Serve engine: the part of `tick_device_gap_ms_p50` that lies under the
next tick's `engine.admit`, `engine.marshal` and `engine.enqueue` spans
(median over the traced gaps, ms): the chip waiting for the host to admit,
build the step's arguments and hand it over."""
from benchmarks import program_trace


def compute(run):
    return program_trace.gap_ms_p50(program_trace.of_run(),
                                    program_trace.DISPATCH_PHASES)
