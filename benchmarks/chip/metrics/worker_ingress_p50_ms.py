"""Median time the worker host spends on a request of the window outside
the service: its ``ingress`` span's ``recv`` stage (decoding the frame's
tensor and plan, then submitting) plus its ``reply`` stage (encoding the
answer and sending it)."""
import numpy as np

from chipbench.stages import ingress_by_request


def read(run):
    ms = [sum(ev["args"].get("stages", {}).get(k, 0.0) for k in ("recv", "reply")) / 1e3
          for _, ev in ingress_by_request(run)]
    return float(np.percentile(ms, 50)) if ms else None
