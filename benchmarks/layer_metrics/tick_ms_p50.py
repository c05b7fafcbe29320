"""Serve engine: median wall time of one ``engine.step()`` in the window,
in ms (harness clock round the call; the engine syncs on its sampled tokens
inside it, so this spans the device's work)."""
import statistics


def compute(run):
    ticks = run.facts.get("ticks")
    if not ticks:
        return None
    return statistics.median(t[1] for t in ticks) * 1e3
