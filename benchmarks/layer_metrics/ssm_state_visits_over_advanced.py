"""Kernels: per-slot states the state-space scan's kernel fetched and wrote
(the model's `ssm_state_visits` counter, `[mamba layers, slots]`: 1 where
`ops/ssd.py`'s `ssd_chunk` visited a slot's state in a layer) over the
states that advanced (`ssm_slots_advanced`, the slots that fed a token),
both summed over layers and slots, means over the window's ticks.  1.0:
only the states that advanced cross memory, once in and once out; the
slots' count over the advanced ones (about 2 in this cell) would be every
slot's state moved whatever advanced.  A program without the counter (the
scan's XLA form, or the parent of the PR that added it) gives nothing."""


def compute(run):
    counted = run.facts.get("counted") or {}
    visits, moved = (counted.get(k) for k in ("ssm_state_visits",
                                              "ssm_slots_advanced"))
    if not visits or not moved or not moved["routed"]:
        return None
    return visits["routed"] / moved["routed"]
