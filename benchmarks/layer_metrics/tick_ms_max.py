"""Serve engine: the longest wall time of one ``engine.step()`` in the
window, in ms (harness clock round the call).  The device's part of a tick
is fixed (one compiled program), so what this reads above `tick_ms_p50` is
a stall on the host; the run's notes give the three longest with their
instants, beside the interpreter's collector pauses."""


def compute(run):
    ticks = run.facts.get("ticks")
    if not ticks:
        return None
    return max(t[1] for t in ticks) * 1e3
