"""Kernels: the grouped expert products' share of their roofline, in %: the
least time a tick's routed pairs can take (roofline_moe: the larger of
operations over the bf16 peak and bytes over the HBM peak, with the weights
of every expert that got a live token read once), summed over the expert
layers, over the device time per traced tick of the grouped products: the
operations under `moe_experts` and the `%ragged-dot-*` kernels XLA makes
of `lax.ragged_dot` (they carry no path, so no scope reaches them)."""
from benchmarks import program_trace, roofline_moe, scope_time


def compute(run):
    t, shape = run.trace, run.config.get("expert_layer")
    got = (run.facts.get("counted") or {}).get("expert_load")
    if not t or not t["main_module_runs"] or not shape or not got:
        return None
    under, _ = scope_time.scope_seconds(program_trace.of_run(),
                                        ("moe_experts",),
                                        ("%ragged-dot",))
    if not under:
        return None
    least = roofline_moe.expert_products_seconds(
        shape, got["routed"], got["touched"], run.peaks)["seconds"]
    return 100.0 * shape["layers"] * least \
        / (under / t["main_module_runs"])
