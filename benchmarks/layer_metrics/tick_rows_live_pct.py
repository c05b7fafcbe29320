"""Serve engine: the share of the rows the tick's token-wise products ran on
that carry a token, in %: live lanes (the model's `lanes_live` counter, each
slot's `n_new`) summed over the slots, over the model's `rows_dense` counter
(the rows of its residual stream: `ops/lane_pack.py`'s packed rows), mean
over the window's ticks.  `tick_lanes_live_pct` divides the same lanes by the
tick's slots x lanes, which the program no longer multiplies.  Beside it, in
the run's notes: how many prefill chunks the engine's row budget left
waiting, in how many ticks (its `prefill_chunks_deferred` counter).  A
program without the `rows_dense` counter gives nothing."""
from benchmarks import harness


def compute(run):
    counted = run.facts.get("counted") or {}
    lanes, rows = counted.get("lanes_live"), counted.get("rows_dense")
    if not lanes or not rows or not rows["routed"]:
        return None
    waited = counted.get("prefill_chunks_deferred")
    harness.note(
        f"rows of the tick's token-wise products: {rows['routed']:.0f}, "
        f"{lanes['routed']:.1f} of them live a tick; prefill chunks left "
        "waiting for rows: " + (
            f"{waited['routed'] * waited['ticks']:.0f} in {waited['ticks']} "
            f"of {lanes['ticks']} ticks" if waited else
            f"none in {lanes['ticks']} ticks"))
    return 100.0 * lanes["routed"] / rows["routed"]
