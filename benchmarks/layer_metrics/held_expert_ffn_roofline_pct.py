"""Kernels: `expert_ffn_roofline_pct` for a chip that holds a share of the
routed experts, in %: the least time a tick's grouped products can take
(roofline_moe: the larger of operations over the bf16 peak and bytes over
the HBM peak) over the pairs routed to the experts HELD here and the held
experts that got a live token — the model's `expert_load_held [layers,
held]` counter; `expert_load` counts over the router's whole width, 16
times the work of a share of 16 in 256 — summed over the expert layers (the
next-token module's among them), over the device time per traced tick of
the grouped products: the operations under `moe_experts` and the
`%ragged-dot-*` kernels XLA makes of `lax.ragged_dot`.  A program without
the counter gives nothing."""
from benchmarks import program_trace, roofline_moe, scope_time


def compute(run):
    t, shape = run.trace, run.config.get("expert_layer")
    got = (run.facts.get("counted") or {}).get("expert_load_held")
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
