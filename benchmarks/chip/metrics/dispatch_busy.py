"""Share of the window in which the service's dispatch thread was at work:
the union of its ``dispatch`` spans (pad, launch, copy back, crop and the
replies it sends) over the window. Near 100 the dispatch thread sets the
pace."""
from chipbench.stages import covered_share


def read(run):
    share = covered_share(run, "dispatch")
    return None if share is None else 100.0 * share
