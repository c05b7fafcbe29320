"""Kernels: the state-space scan's share of its roofline, in %: the least
time a tick's scans can take (roofline_ssm: the larger of operations over
the bf16 peak and bytes over the HBM peak, with the state and convolution
rows of every slot that *advanced* read once and written once, and the
live lanes' rows), summed over the Mamba layers, over the device time per
traced tick under the `ssm_scan` scope.  Slots advanced and lanes live are
the model's own counters (`ssm_slots_advanced [layers, slots]`,
`lanes_live [1, slots]`), means over the window's ticks; the same work is
counted whatever implements the scan."""
from benchmarks import program_trace, roofline_ssm, scope_time


def compute(run):
    t, shape = run.trace, run.config.get("ssm_layer")
    got = run.facts.get("counted") or {}
    moved, lanes = got.get("ssm_slots_advanced"), got.get("lanes_live")
    if not t or not t["main_module_runs"] or not shape or not moved \
            or not lanes:
        return None
    under, _ = scope_time.scope_seconds(program_trace.of_run(),
                                        ("ssm_scan",))
    if not under:
        return None
    least = roofline_ssm.scan_seconds(shape, moved["routed"],
                                      lanes["routed"], run.peaks)["seconds"]
    return 100.0 * shape["layers"] * least \
        / (under / t["main_module_runs"])
