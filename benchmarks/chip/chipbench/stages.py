"""The worker host's ``ingress`` spans and the service's ``dispatch`` spans
(Chrome trace events, perf_counter microseconds), joined to the benchmark's
requests by the trace ids it passed in. A program without these spans gives
empty results, never an error."""
from __future__ import annotations

from chipbench import xtrace
from chipbench.spans import TRACE_BASE


def ingress_by_request(run) -> list[tuple[object, dict]]:
    """(record, ingress event) for each request due in the window that has
    an ``ingress`` span."""
    if not run.spans:
        return []
    by_trace = {ev["args"]["trace_id"]: ev for ev in run.spans
                if ev.get("name") == "ingress" and ev.get("ph") == "X"
                and "trace_id" in ev.get("args", {})}
    return [(r, by_trace[TRACE_BASE + r.index]) for r in run.window_records()
            if TRACE_BASE + r.index in by_trace]


def covered_share(run, name: str) -> float | None:
    """Share of the window covered by the union of the spans called
    ``name``; None where there are none."""
    if not run.spans:
        return None
    evs = [(name, ev["ts"], ev["dur"]) for ev in run.spans
           if ev.get("name") == name and ev.get("ph") == "X"]
    if not evs:
        return None
    t0, t1 = run.t0 * 1e6, run.t1 * 1e6
    return xtrace.busy_ns(evs, t0, t1) / (t1 - t0)  # a union in the events' unit
