"""Model: device time of the operations under `mlm_head` (transform,
LayerNorm, vocabulary logits) and `loss` (softmax cross-entropy), forward,
rematerialized and backward alike, over device-busy time, in %."""
from benchmarks import program_trace


def compute(run):
    return program_trace.scope_pct(program_trace.of_run(),
                                   ("mlm_head", "loss"))
