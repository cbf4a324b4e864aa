"""The kernels' share of the HBM roofline: the bytes the window's answers
need (each request's input read once and its outputs written once, at the
request's own size) over peak HBM bandwidth times the time the chips were
busy (profiler trace; peak from peaks.json by device kind)."""


def read(run):
    t = run.trace
    if t is None or t["busy_s"] <= 0:
        return None
    done = run.completed_in_window()
    if not done:
        return None
    chips = len(t["busy_s_per_device"])
    least_s = run.ideal_bytes(done) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (t["busy_s"] * chips)
