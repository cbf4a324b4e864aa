"""95th percentile of the service's queue wait over the window's requests:
the obs ``queue`` spans that carry the benchmark's trace ids."""
import numpy as np

from chipbench.spans import queue_waits_ms


def read(run):
    waits = queue_waits_ms(run)
    return float(np.percentile(waits, 95)) if waits else None
