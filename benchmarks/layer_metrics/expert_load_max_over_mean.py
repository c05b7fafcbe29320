"""Model: per expert layer and tick, the live tokens routed to the fullest
expert over the mean over the experts; mean over the window's ticks and
the layers (1.0 = perfectly even).  From the engine's own counter (the
model's `expert_load`, passed through the runner's `facts`)."""


def compute(run):
    got = (run.facts.get("counted") or {}).get("expert_load")
    return got["max_over_mean"] if got else None
