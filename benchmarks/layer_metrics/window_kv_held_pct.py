"""Serve engine: tokens whose rows stand in the window arena after each tick
(the engine's `window_tokens_held` gauge, `[1, slots]`: a slot's fill less
what the pool has handed back) over the tokens the same slots hold in the
full arena (`full_tokens_held`: their fills), both means over the window's
ticks, in %.  100 with nothing released; the lower, the more of the long
requests' cache the window layers gave back while they ran.  Beside it, in
the run's notes: how many window blocks the pool handed back, in how many
ticks (the engine's `window_blocks_released` counter).  A program without
window leaves logs none of them and gives nothing."""
from benchmarks import harness


def compute(run):
    counted = run.facts.get("counted") or {}
    held, full = (counted.get(k) for k in ("window_tokens_held",
                                           "full_tokens_held"))
    if not held or not full or not full["routed"]:
        return None
    back = counted.get("window_blocks_released")
    harness.note(
        "window blocks handed back while their requests ran: " + (
            f"{back['routed'] * back['ticks']:.0f} in {back['ticks']} of "
            f"{full['ticks']} ticks" if back else
            f"none in {full['ticks']} ticks"))
    return 100.0 * held["routed"] / full["routed"]
