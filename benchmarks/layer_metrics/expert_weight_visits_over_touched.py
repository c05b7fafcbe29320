"""Kernels: (expert, row tile) visits the grouped-matmul kernel makes a
product (the model's `expert_weight_visits` counter, `[layers, E]`: row
tiles visited for each expert, 0 for an untouched one) over the experts
that got a live token (`expert_load`'s `touched`), both means over the
window's ticks and the expert layers.  1.0: every touched expert's weights
cross the MXU once a product; 1.3: groups straddling row tiles cost 30%
more passes.  A program without the counter (the XLA form, or the parent
of the PR that added it) gives nothing."""


def compute(run):
    counted = run.facts.get("counted") or {}
    visits, load = (counted.get(k) for k in ("expert_weight_visits",
                                             "expert_load"))
    if not visits or not load or not load["touched"]:
        return None
    return visits["routed"] / load["touched"]
