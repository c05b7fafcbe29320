"""Kernels: paged grouped-query attention's share of its roofline, in %: the
least time a tick's calls can take (roofline_gqa: the larger of operations
over the bf16 peak and bytes over the HBM peak, with the K and V rows of
every position walked read once and the live lanes' query and output rows),
summed over the attention layers, over the device time per traced tick
under the `paged_gqa_attention` scope.  Positions walked and lanes live are
the model's own counters (`attn_positions_walked [layers, slots]`,
`lanes_live [1, slots]`), means over the window's ticks.  Over 100 is a bug
in the counts.  A program without the scope or the counters gives
nothing."""
from benchmarks import program_trace, roofline_gqa, scope_time


def compute(run):
    t, shape = run.trace, run.config.get("attention_layer")
    got = run.facts.get("counted") or {}
    walked, lanes = got.get("attn_positions_walked"), got.get("lanes_live")
    if not t or not t["main_module_runs"] or not shape or not walked \
            or not lanes:
        return None
    under, _ = scope_time.scope_seconds(program_trace.of_run(),
                                        ("paged_gqa_attention",))
    if not under:
        return None
    least = roofline_gqa.attention_seconds(shape, walked["routed"],
                                           lanes["routed"],
                                           run.peaks)["seconds"]
    return 100.0 * shape["layers"] * least \
        / (under / t["main_module_runs"])
