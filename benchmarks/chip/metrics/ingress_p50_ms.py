"""What the ingress hops add to the median: client p50 less the p50 of each
request's time inside the service, from the start of its ``queue`` span to
the end of the ``dispatch`` span whose ``trace_ids`` hold it."""
import numpy as np

from chipbench.spans import service_times_ms


def read(run):
    inside = service_times_ms(run)
    lat = run.latencies_ms()
    if not inside or not lat:
        return None
    return float(np.percentile(lat, 50) - np.percentile(inside, 50))
