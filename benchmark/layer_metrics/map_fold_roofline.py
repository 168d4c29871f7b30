"""Share of its roofline the map fold kernel reached in the traced ticks,
in %: the HBM bytes its calls must move (kernel_bytes.py, from the cell's
shapes) over peak HBM bandwidth, divided by its measured device time. No
int32 VPU peak is published for the v5e, so the bound is the bytes."""


def read(run):
    t = run.trace
    k = t and t["kernels"].get("map_fold")
    if not k or k["time_s"] <= 0:
        return None
    moved = k["count"] * run.kernel_bytes["map_fold"]
    return 100.0 * moved / run.peaks["hbm_bytes_per_s"] / k["time_s"]
