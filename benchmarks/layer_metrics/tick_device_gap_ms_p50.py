"""Serve engine: median over the traced ticks of the time the chip stands
idle between the end of one tick's program and the start of the next, in ms
(device trace; the two small RNG programs in between count as busy).  What
the host adds to every tick: `tick_gap_dispatch_ms_p50` and
`tick_gap_harvest_ms_p50` split it over the engine's own phases."""
from benchmarks import program_trace


def compute(run):
    return program_trace.gap_ms_p50(program_trace.of_run())
