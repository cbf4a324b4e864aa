"""The service's obs spans (Chrome trace events, perf_counter microseconds)
joined to the benchmark's requests by the trace ids it passed in."""
from __future__ import annotations

TRACE_BASE = 1 << 40  # the benchmark's trace ids: TRACE_BASE + request index


def _window_ids(run) -> set[int]:
    return {TRACE_BASE + r.index for r in run.window_records()}


def queue_waits_ms(run) -> list[float]:
    if not run.spans:
        return []
    ids = _window_ids(run)
    return [ev["dur"] / 1e3 for ev in run.spans
            if ev.get("name") == "queue" and ev.get("ph") == "X"
            and ev.get("args", {}).get("trace_id") in ids]


def service_times_ms(run) -> list[float]:
    if not run.spans:
        return []
    ids = _window_ids(run)
    start, end = {}, {}
    for ev in run.spans:
        if ev.get("ph") != "X":
            continue
        args = ev.get("args", {})
        if ev.get("name") == "queue" and args.get("trace_id") in ids:
            start[args["trace_id"]] = ev["ts"]
        elif ev.get("name") == "dispatch":
            for t in args.get("trace_ids") or ():
                if t in ids:
                    end[t] = max(end.get(t, 0.0), ev["ts"] + ev["dur"])
    return [(end[t] - start[t]) / 1e3 for t in start if t in end]
