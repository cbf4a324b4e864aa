"""Share of the window in which the busiest service's dispatch thread was at
work: the union of its ``dispatch`` spans (pad, launch, copy back, crop and
the replies it sends) over the window, for each replica, and the largest of
them; with one replica, its own. Near 100 a dispatch thread sets the pace."""
from chipbench.stages import covered_shares


def read(run):
    shares = covered_shares(run, "dispatch")
    return 100.0 * max(shares.values()) if shares else None
