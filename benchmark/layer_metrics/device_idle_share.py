"""Device idle share of the traced ticks, in %: 1 - busy / window, busy
the union of the device's op intervals, the mean over the cell's chips."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0 or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
