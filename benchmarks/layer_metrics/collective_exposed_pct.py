"""Collectives: device time of collective operations on the XLA Ops line
(where operations do not overlap, so this is time in which that chip
computed nothing else) over the traced window, in %.  Only a cell on
several chips has anything to read."""


def compute(run):
    t = run.trace
    if not t or run.facts["chips"] < 2 or not t["window_s"]:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
