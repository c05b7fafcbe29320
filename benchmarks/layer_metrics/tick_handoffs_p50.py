"""Serve engine: median over the traced ticks of the hand-offs between the
tick's host thread and the runtime: the `engine.rng`, `engine.put`,
`engine.enqueue` and `engine.fetch` events inside one `engine.tick` (the
engine counts the same as it makes them: the tick's `handoffs=`).  Nothing
from a program without the child spans."""
from benchmarks import handoff_trace


def compute(run):
    return handoff_trace.handoffs_p50(handoff_trace.of_run())
