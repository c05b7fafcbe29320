"""XLA step of a serving tick: its share of the bandwidth roofline, in %.
The bytes a tick must read (every weight once, and the keys and values of
the tokens live in the cache at that tick: roofline.decode_tick_bytes) over
the HBM peak, over the device-busy time per tick from the trace.  A tick
with prompt chunks in it does more arithmetic than this counts; it is still
far from compute-bound at these widths."""
from benchmarks import roofline


def compute(run):
    t, ticks = run.trace, run.facts.get("ticks")
    if not t or not t["busy_s"] or not ticks or not t["main_module_runs"] \
            or "serving_bytes" not in run.config:
        return None
    ticks_traced = t["main_module_runs"]    # runs of the tick's program
    need = sum(roofline.decode_tick_bytes(run.config, k[3])
               for k in ticks) / len(ticks)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] \
        / (t["busy_s"] / ticks_traced)
