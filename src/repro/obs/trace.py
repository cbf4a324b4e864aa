"""Per-request tracing: spans over the serving pipeline, Chrome-trace export.

A *trace* is one request's journey: a trace ID is minted at ``submit()``
(process-unique, so a request keeps its identity across shard failover
hops) and every span recorded on its behalf carries it. Spans mark layer
boundaries — worker ingress, queue wait, group dispatch, retry/backoff,
bisection, router hops — with (plan, bucket, dtype, batch, shard) context
in their args. Each span has a process-unique id and, where one caused it,
the id of its ``parent`` (a worker's ``queue`` span is the child of its
``ingress`` span); both export as args ``span_id`` and ``parent_id``.

Spans cross threads (a queue span opens on the submitting thread and closes
on the batcher worker), so the API is explicit ``begin()``/``end()`` handles
plus a ``span()`` context manager for same-thread scopes. ``end()`` is
exactly-once by construction: a handle leaves the open set when it closes,
and closing it again raises — the invariant the trace-completeness chaos
test asserts.

Finished spans land in a bounded ring buffer (oldest dropped, drop count
kept) and export as Chrome trace-event JSON — ``chrome_trace()`` emits
``{"traceEvents": [...]}`` with complete (``"ph": "X"``) events, loadable
directly in Perfetto (ui.perfetto.dev) or chrome://tracing. Timestamps are
``time.perf_counter()`` microseconds, one timebase across every tracer in
the process, so router and shard spans interleave correctly on one
timeline. :func:`validate_chrome_trace` is the schema check CI runs against
exported documents.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from contextlib import contextmanager

_ids = itertools.count(1)
_ids_lock = threading.Lock()
_span_ids = itertools.count(1)  # next() on a count is atomic under the GIL


def new_trace_id() -> int:
    """Process-unique trace ID: one per request, minted at submit and
    threaded through every hop (shards must not re-mint)."""
    with _ids_lock:
        return next(_ids)


class Span:
    """An open span handle. Closed by ``Tracer.end`` (or the ``span()``
    context manager) exactly once."""

    __slots__ = ("name", "trace", "id", "parent", "t0", "t1", "tid", "attrs")

    def __init__(self, name: str, trace, tid: int, attrs: dict, parent=None):
        self.name = name
        self.trace = trace
        self.id = next(_span_ids)
        self.parent = parent
        self.t0 = time.perf_counter()
        self.t1 = None
        self.tid = tid
        self.attrs = attrs


class Tracer:
    """One tracer per service (the router gets its own). ``pid`` labels the
    process lane in the exported trace — shard index for shard services,
    ``"router"`` for the router."""

    def __init__(self, ring: int = 8192, pid="0", name: str = "service"):
        self.pid = str(pid)
        self.name = name
        self._lock = threading.Lock()
        self._done: collections.deque = collections.deque(maxlen=ring)
        self._open: set[Span] = set()
        self.dropped = 0
        self.spans_begun = 0
        self.spans_ended = 0

    # ------------------------------------------------------------- recording
    def begin(self, name: str, trace=None, parent: Span | None = None,
              **attrs) -> Span:
        span = Span(name, trace, threading.get_ident(), attrs,
                    parent.id if parent is not None else None)
        with self._lock:
            self._open.add(span)
            self.spans_begun += 1
        return span

    def end(self, span: Span, **attrs) -> None:
        """Close a span exactly once; closing twice (or closing a handle
        this tracer never began) raises."""
        with self._lock:
            try:
                self._open.remove(span)
            except KeyError:
                raise RuntimeError(
                    f"span {span.name!r} already ended (or foreign to this tracer)"
                ) from None
            span.t1 = time.perf_counter()
            if attrs:
                span.attrs.update(attrs)
            if len(self._done) == self._done.maxlen:
                self.dropped += 1
            self._done.append(span)
            self.spans_ended += 1

    @contextmanager
    def span(self, name: str, trace=None, **attrs):
        s = self.begin(name, trace, **attrs)
        try:
            yield s
        finally:
            self.end(s)

    def instant(self, name: str, trace=None, **attrs) -> None:
        """Zero-duration marker (exported as ``"ph": "i"``)."""
        s = Span(name, trace, threading.get_ident(), attrs)
        s.t1 = s.t0
        with self._lock:
            if len(self._done) == self._done.maxlen:
                self.dropped += 1
            self._done.append(s)

    # ------------------------------------------------------------- reading
    def open_count(self) -> int:
        with self._lock:
            return len(self._open)

    def finished(self) -> list[Span]:
        with self._lock:
            return list(self._done)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "spans_begun": self.spans_begun,
                "spans_ended": self.spans_ended,
                "open": len(self._open),
                "buffered": len(self._done),
                "dropped": self.dropped,
            }

    def chrome_events(self) -> list[dict]:
        events = [{
            "name": "process_name",
            "ph": "M",
            "pid": self.pid,
            "tid": 0,
            "ts": 0,
            "args": {"name": self.name},
        }]
        for s in self.finished():
            args = {k: _jsonable(v) for k, v in s.attrs.items()}
            if s.trace is not None:
                args["trace_id"] = s.trace
            args["span_id"] = s.id
            if s.parent is not None:
                args["parent_id"] = s.parent
            ev = {
                "name": s.name,
                "cat": "serve",
                "ph": "X" if s.t1 > s.t0 else "i",
                "ts": round(s.t0 * 1e6, 3),
                "pid": self.pid,
                "tid": s.tid,
                "args": args,
            }
            if ev["ph"] == "X":
                ev["dur"] = round((s.t1 - s.t0) * 1e6, 3)
            else:
                ev["s"] = "t"  # instant scope: thread
            events.append(ev)
        return events


def _jsonable(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (tuple, list)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    return str(v)


def chrome_trace(tracers) -> dict:
    """Merge any number of tracers into one Chrome trace-event document
    (Perfetto- and chrome://tracing-loadable)."""
    events: list[dict] = []
    for t in tracers:
        if t is not None:
            events.extend(t.chrome_events())
    events.sort(key=lambda e: e["ts"])
    return {"traceEvents": events, "displayTimeUnit": "ms"}


_PHASES = {"X", "i", "M"}


def validate_chrome_trace(doc) -> list[str]:
    """Structural check against the Chrome trace-event format (the subset
    this exporter emits). Returns a list of problems — empty means valid.
    CI runs this over the chaos-replay export."""
    errors: list[str] = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["document must be a dict with a 'traceEvents' list"]
    events = doc["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be a list"]
    for i, ev in enumerate(events):
        where = f"event[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        name, ph = ev.get("name"), ev.get("ph")
        if not isinstance(name, str) or not name:
            errors.append(f"{where}: missing/empty 'name'")
        if ph not in _PHASES:
            errors.append(f"{where} ({name}): bad phase {ph!r}")
        for field in ("pid", "tid"):
            if not isinstance(ev.get(field), (int, str)):
                errors.append(f"{where} ({name}): missing '{field}'")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errors.append(f"{where} ({name}): bad 'ts' {ts!r}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"{where} ({name}): 'X' event needs 'dur' >= 0")
        if "args" in ev and not isinstance(ev["args"], dict):
            errors.append(f"{where} ({name}): 'args' must be an object")
    return errors


__all__ = [
    "new_trace_id",
    "Span",
    "Tracer",
    "chrome_trace",
    "validate_chrome_trace",
]
