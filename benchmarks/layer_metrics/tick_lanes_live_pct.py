"""Serve engine: the share of a tick's dense rows that carry a token, in %:
live lanes (the model's `lanes_live` counter, each slot's `n_new`) summed
over the slots, over slots x lanes of the tick's one `[slots, lanes]`
program (the traffic file's engine: lanes = block_size), mean over the
window's ticks.  A decoding slot uses 1 of its lanes, a prefilling one up
to all; the rest are multiplied through every dense product all the same.
A program without the counter gives nothing."""


def compute(run):
    got = (run.facts.get("counted") or {}).get("lanes_live")
    eng = run.traffic.get("engine") or {}
    rows = eng.get("slots", 0) * eng.get("block_size", 0)
    return 100.0 * got["routed"] / rows if got and rows else None
