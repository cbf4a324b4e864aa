"""Median client latency from when each request was due, over every request
due in the window (host clock); a failed request misses."""
import numpy as np


def read(run):
    lat = run.latencies_ms()
    return float(np.percentile(lat, 50)) if lat else None
