"""Kernels: device time of all Pallas kernels (custom calls on the XLA Ops
line) over the device-busy time of the traced window, in %."""


def compute(run):
    t = run.trace
    if not t or not t["busy_s"] or not t["pallas_s"]:
        return None
    return 100.0 * t["pallas_s"] / t["busy_s"]
