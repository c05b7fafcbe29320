"""Model: experts that got a live token, over the experts of a layer, mean
over the window's ticks and the expert layers, in % (the model's
`expert_load` counter, `[layers, E]` a tick, reduced by the runner to
`touched`; the configuration's `expert_layer.num_experts`).  At 100 a tick
streams every expert's weights whoever is live, so its length does not
follow the seed's arrivals; each untouched expert is 1 / E of a layer's
expert bytes not read that tick.  The runner keeps means only, so the
least over ticks is not here: the shortfall from 100 bounds it (one
tick-layer with k experts idle lowers the mean by 100 k / (E x ticks x
layers)).  A program without the counter gives nothing."""


def compute(run):
    got = (run.facts.get("counted") or {}).get("expert_load")
    experts = (run.config.get("expert_layer") or {}).get("num_experts")
    return 100.0 * got["touched"] / experts if got and experts else None
