"""Serve engine: live slots after each tick over the pool's slots, mean over
the window's ticks, in %."""


def compute(run):
    ticks = run.facts.get("ticks")
    if not ticks:
        return None
    return 100.0 * sum(t[2] for t in ticks) / len(ticks) / run.facts["slots"]
