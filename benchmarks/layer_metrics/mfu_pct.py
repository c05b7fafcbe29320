"""Model: model FLOP/s utilisation, in % of the chip's bf16 peak: items a
step per chip, over the steady time of a step (the mean distance between
steps finishing, before the profiler starts), times the analytic FLOPs an
item needs forward and backward (roofline.py; recomputed work does not
count)."""


def compute(run):
    at = run.facts.get("step_done_at") or []
    if len(at) < 3:
        return None
    step_s = (at[-1] - at[0]) / (len(at) - 1)
    rate = run.facts["items_per_step"] / run.facts["chips"] / step_s
    return 100.0 * rate * run.facts["flops_per_item"] \
        / run.peaks["bf16_flops_per_s"]
