"""The busiest chip's share of the cell's device time: 100 x the largest
per-chip busy seconds over their sum across the cell's chips (profiler
trace). 100 / chips is even load; 100 means one chip did all the work."""


def read(run):
    t = run.trace
    if t is None:
        return None
    busy = list(t["busy_s_per_device"].values())
    if sum(busy) <= 0:
        return None
    return 100.0 * max(busy) / sum(busy)
