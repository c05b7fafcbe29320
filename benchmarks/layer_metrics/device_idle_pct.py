"""Device: share of the traced window in which no operation ran on the
chip, in % (1 - union of the device's operation intervals over the window,
mean over the chips used).  One reader for `device_idle_pct.train` and
`device_idle_pct.serve`, which differ only in the end-to-end metric they
move."""


def compute(run):
    t = run.trace
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
