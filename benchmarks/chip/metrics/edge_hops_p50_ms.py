"""Median time an answered request of the window spends outside the worker
host: client latency from when it was sent less its ``ingress`` span, which
leaves the client, the edge host, the frontier and the links, both ways."""
import numpy as np

from chipbench.stages import ingress_by_request


def read(run):
    ms = [(r.done - r.sent) * 1e3 - ev["dur"] / 1e3
          for r, ev in ingress_by_request(run)
          if r.error is None and r.done is not None]
    return float(np.percentile(ms, 50)) if ms else None
