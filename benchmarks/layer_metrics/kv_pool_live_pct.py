"""Serve engine: tokens whose keys and values are live in the paged cache
after each tick, over the tokens the pool has room for (blocks x block
size), mean over the window's ticks, in %.  It says how much of the arena
that the cell holds in memory the traffic fills."""


def compute(run):
    ticks, room = run.facts.get("ticks"), run.facts.get("pool_tokens")
    if not ticks or not room:
        return None
    return 100.0 * sum(t[3] for t in ticks) / len(ticks) / room
