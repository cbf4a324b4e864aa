"""Frontier: the front-tier router over a fleet of worker processes.

The in-process ``ShardedMorphService`` routes (plan, bucket, dtype) groups
across per-device shards; the frontier applies the *same* discipline one
level up, across worker **processes**:

* **affinity** — a group token hashes (crc32) to one worker, so
  micro-batches keep coalescing across process boundaries: every request
  for a given (plan, bucket, dtype) lands on the same worker's batcher,
  exactly as it would land on the same shard in-process. The frontier
  buckets with its own ladder, which must match the workers' (the default
  on both sides) for the affinity to align with worker-side batching.
* **health** — the per-worker breaker/slow-mark state machine is the
  extracted :class:`HealthTracker` (serve/morph/health.py), the identical
  code the shard router runs. Worker-level errors (``InjectedFault``,
  ``ExecutorError``, a worker-side ``ShardUnavailable``) count toward the
  breaker; a lost TCP connection is ``mark_dead`` — immediately open,
  because a vanished process is definitive in a way one failed request is
  not. Recovery is the standard half-open probe: after
  ``probe_interval_s`` one request is let through, and the link
  reconnects lazily, so a restarted worker on the same address rejoins.
* **reroute** — on worker death every in-flight request the dead
  connection was carrying fails over: ``Connection`` resolves them all
  with ``ConnectionLost``, the frontier's done-callbacks re-``_attempt``
  on the survivors (same hash over the healthy subset — deterministic),
  and the caller's future resolves with the rerouted result. Zero lost
  futures is a structural property, not a retry loop.
* **stats/traces** — ``stats()`` merges worker ``metrics_snapshot()``s
  with the registry merge semantics (ingress/stats.py) into one
  fleet-wide view; ``export_trace()`` stitches worker Chrome traces onto
  the frontier timeline using per-link clock offsets, so one trace ID
  minted here is followable from the frontier hop span into the owning
  worker's ingress/queue/dispatch spans.

``serve()`` wraps the frontier in a :class:`WorkerHost` — the frontier
speaks the same protocol it consumes, so clients connect to one address
and the whole stack is recursively composed.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future

import numpy as np

from repro.obs import MetricsRegistry, Observability, new_trace_id
from repro.serve.ingress import proto
from repro.serve.ingress.client import Connection
from repro.serve.ingress.stats import (
    fleet_stats,
    merge_process_traces,
    merge_worker_metrics,
)
from repro.serve.ingress.worker import WorkerHost
from repro.serve.morph.buckets import DEFAULT_BUCKETS, choose_bucket
from repro.serve.morph.health import HealthTracker
from repro.serve.morph.plans import Plan, single_op_plan
from repro.serve.morph.resilience import (
    DeadlineExceeded,
    ExecutorError,
    FailoverPolicy,
    InjectedFault,
    ServiceClosed,
    ShardUnavailable,
)
from repro.serve.morph.tenancy import PRIORITY_NORMAL

# Failures that indict the *worker* (move its breaker / reroute the
# request). ConnectionLost is the process-death signal and ServiceClosed
# is the worker announcing its own drain — both are definitive (mark_dead),
# unlike a single failed request; a worker-side ShardUnavailable means
# that worker's whole internal router gave up, so for this group the
# worker is as good as down. Everything else is about the request and
# propagates typed without penalizing the worker. Note the asymmetry with
# the in-process router, which treats ServiceClosed as final: one process
# closing IS the end of its shards, but a fleet outlives any one worker's
# shutdown, so the frontier moves the traffic instead of spreading the
# goodbye to callers.
WORKER_LEVEL_ERRORS = (
    proto.ConnectionLost, ServiceClosed, InjectedFault, ExecutorError,
    ShardUnavailable,
)


class WorkerLink:
    """Frontier-side handle on one worker address: a lazily (re)connected
    :class:`Connection` plus the measured clock offset."""

    def __init__(self, index: int, address: tuple[str, int]):
        self.index = index
        self.address = (address[0], int(address[1]))
        self._lock = threading.Lock()
        self.conn: Connection | None = None

    def ensure(self) -> Connection:
        """The live connection, reconnecting if the previous one died —
        which is how a half-open probe of a restarted worker succeeds.
        Raises :class:`ConnectionLost` when the worker is unreachable."""
        with self._lock:
            if self.conn is not None and not self.conn.closed:
                return self.conn
            try:
                self.conn = Connection(self.address)
                self.conn.ping()  # liveness + clock offset in one round trip
            except OSError as exc:
                self.conn = None
                raise proto.ConnectionLost(
                    f"worker {self.index} at {self.address} unreachable: {exc}"
                ) from None
            return self.conn

    @property
    def clock_offset_s(self) -> float | None:
        c = self.conn
        return c.clock_offset_s if c is not None else None

    def close(self) -> None:
        with self._lock:
            if self.conn is not None:
                self.conn.close()
                self.conn = None


class _RequestCtx:
    __slots__ = ("tried",)

    def __init__(self):
        self.tried: set[int] = set()


class Frontier:
    """Route ingress traffic across worker processes. Service-shaped: the
    submit/run/stats/close surface matches ``MorphService``, which is what
    lets ``WorkerHost`` serve a frontier without knowing it is one."""

    def __init__(self, workers, *, buckets=DEFAULT_BUCKETS,
                 failover: FailoverPolicy = FailoverPolicy(),
                 default_deadline_ms: float | None = None,
                 obs=None, connect: bool = True):
        if not workers:
            raise ValueError("Frontier needs at least one worker address")
        self.links = [WorkerLink(i, a) for i, a in enumerate(workers)]
        self.buckets = buckets
        self.failover = failover
        self.default_deadline_ms = default_deadline_ms
        self.tracker = HealthTracker(len(self.links), failover, noun="worker")
        self.metrics = MetricsRegistry()
        self._obs = (
            Observability(obs, self.metrics, pid="frontier", name="frontier")
            if obs is not None and obs.enabled
            else None
        )
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._inflight = 0
        self._requests_ok = 0
        self._closed = False
        if connect:
            for link in self.links:
                try:
                    link.ensure()
                except proto.ConnectionLost:
                    self.tracker.mark_dead(link.index)

    # ------------------------------------------------------------- routing
    @staticmethod
    def _token(plan_name: str, bucket, dtype_str: str) -> bytes:
        return f"{plan_name}|{bucket}|{dtype_str}".encode()

    # ---------------------------------------------------------- submission
    def submit(self, img, op: str = "erode", se=(3, 3), **kw) -> Future:
        return self.submit_plan(img, single_op_plan(op, se), **kw)

    def submit_plan(self, img, plan, *, deadline_ms: float | None = None,
                    tag: str | None = None, tenant: str | None = None,
                    priority: int = PRIORITY_NORMAL,
                    _trace: int | None = None) -> Future:
        with self._lock:
            if self._closed:
                raise ServiceClosed("frontier is closed")
            self._inflight += 1
        try:
            spec = proto.plan_to_wire(plan)
            plan_name = (
                plan.name if isinstance(plan, Plan) else str(spec.get("name"))
            )
            img = np.asarray(img)
            if img.ndim != 2:
                raise ValueError(
                    "the service takes single (H, W) images; submit each "
                    "image of a batch separately"
                )
            bucket = choose_bucket(img.shape[0], img.shape[1], self.buckets)
            token = self._token(plan_name, bucket, img.dtype.str)
            if deadline_ms is None:
                deadline_ms = self.default_deadline_ms
            deadline_at = (
                time.monotonic() + deadline_ms / 1e3
                if deadline_ms is not None else None
            )
            if _trace is not None:
                trace = _trace
            else:
                # minted HERE: the ID every hop span, worker ingress span,
                # and queue span carries — across process boundaries
                trace = new_trace_id() if self._obs is not None else None
            outer: Future = Future()
            outer.add_done_callback(self._request_done)
            self._attempt(outer, img, spec, plan_name, token, deadline_at,
                          tag, tenant, priority, trace, frozenset(),
                          _RequestCtx())
            return outer
        except BaseException:
            with self._lock:
                self._inflight -= 1
                self._idle.notify_all()
            raise

    def _request_done(self, fut: Future) -> None:
        with self._lock:
            self._inflight -= 1
            if fut.exception() is None:
                self._requests_ok += 1
            self._idle.notify_all()

    def _resolve(self, outer: Future, *, exc=None, result=None) -> None:
        # attempts are strictly sequential (no hedging at this tier yet),
        # so the future resolves exactly once by construction
        if exc is not None:
            outer.set_exception(exc)
        else:
            outer.set_result(result)

    def _attempt(self, outer: Future, img, spec: dict, plan_name: str,
                 token: bytes, deadline_at: float | None, tag, tenant,
                 priority: int, trace, excluded: frozenset,
                 ctx: _RequestCtx) -> None:
        deadline_ms = None
        if deadline_at is not None:
            deadline_ms = (deadline_at - time.monotonic()) * 1e3
            if deadline_ms <= 0:
                self._resolve(outer, exc=DeadlineExceeded(
                    "deadline expired during worker failover", plan=plan_name
                ))
                return
        try:
            idx, was_probe = self.tracker.pick(token, excluded)
        except ShardUnavailable as exc:
            if self._obs is not None:
                self._obs.instant(
                    "unroutable", trace=trace, plan=plan_name,
                    excluded=sorted(excluded),
                )
            self._resolve(outer, exc=exc)
            return
        ctx.tried.add(idx)
        tracer = self._obs.tracer if self._obs is not None else None
        hop = (
            tracer.begin("hop", trace=trace, worker=idx, probe=was_probe,
                         plan=plan_name, attempt=len(excluded))
            if tracer is not None else None
        )
        t0 = time.monotonic()

        def worker_failed(exc: BaseException) -> None:
            if isinstance(exc, (proto.ConnectionLost, ServiceClosed)):
                # a dead process — or one announcing its drain — is
                # definitive; don't wait for a failure threshold
                self.tracker.mark_dead(idx)
            else:
                self.tracker.record_failure(idx, was_probe)
            nxt = excluded | {idx}
            if self._obs is not None:
                self._obs.instant(
                    "failover", trace=trace, worker=idx,
                    error=type(exc).__name__,
                    exhausted=len(nxt) >= len(self.links),
                )
            if len(nxt) < len(self.links):
                self._attempt(outer, img, spec, plan_name, token,
                              deadline_at, tag, tenant, priority, trace,
                              nxt, ctx)
            else:
                self._resolve(outer, exc=exc)

        try:
            fut = self.links[idx].ensure().submit_plan(
                img, spec, deadline_ms=deadline_ms, tag=tag, tenant=tenant,
                priority=priority, trace=trace,
            )
        except proto.ConnectionLost as exc:
            if hop is not None:
                tracer.end(hop, error=type(exc).__name__)
            worker_failed(exc)
            return

        def done(f) -> None:
            exc = f.exception()
            if hop is not None:
                tracer.end(hop, error=type(exc).__name__ if exc else None)
            if exc is None:
                self.tracker.record_success(idx, was_probe)
                self.tracker.observe_latency(
                    idx, (time.monotonic() - t0) * 1e3
                )
                self._resolve(outer, result=f.result())
            elif isinstance(exc, WORKER_LEVEL_ERRORS):
                worker_failed(exc)
            else:  # request-level: typed, final, worker not indicted
                self._resolve(outer, exc=exc)

        fut.add_done_callback(done)

    # -------------------------------------------------------- conveniences
    def run(self, img, op: str = "erode", se=(3, 3), **kw):
        return self.submit(img, op, se, **kw).result()

    def run_plan(self, img, plan, **kw):
        return self.submit_plan(img, plan, **kw).result()

    def run_batch(self, imgs, plan, **kw) -> list:
        futures = [self.submit_plan(im, plan, **kw) for im in imgs]
        return [f.result() for f in futures]

    # ------------------------------------------------------------- metrics
    def _worker_rpcs(self, mtype: str) -> list[dict | None]:
        """One control-plane RPC per worker; dead workers contribute None
        (the fleet view must not require every process alive)."""
        out: list[dict | None] = []
        for link in self.links:
            try:
                out.append(link.ensure().rpc(mtype))
            except (proto.ConnectionLost, proto.ServeError, OSError,
                    TimeoutError):
                out.append(None)
        return out

    def metrics_snapshot(self) -> dict:
        snaps = [
            (r.get("metrics") or {}) for r in self._worker_rpcs("stats") if r
        ]
        snaps.append(self.metrics.snapshot())
        return merge_worker_metrics(snaps)

    def stats(self) -> dict:
        replies = self._worker_rpcs("stats")
        merged = merge_worker_metrics(
            [(r.get("metrics") or {}) for r in replies if r]
        )
        with self._lock:
            requests_ok = self._requests_ok
        return fleet_stats(
            merged,
            health=self.tracker.snapshot(),
            counters={
                "requests": requests_ok,
                "reroutes": self.tracker.reroutes,
                "failovers": self.tracker.trips,
            },
            per_worker=[r.get("stats") if r else None for r in replies],
        )

    def export_trace(self) -> dict | None:
        """The fleet-wide Chrome trace: frontier events + every reachable
        worker's, clock-shifted onto this process's timebase; None when
        tracing is off at the frontier."""
        if self._obs is None or self._obs.tracer is None:
            return None
        worker_traces = []
        for link, reply in zip(self.links, self._worker_rpcs("trace")):
            if reply is not None:
                worker_traces.append(
                    (reply.get("trace"), link.clock_offset_s)
                )
        return merge_process_traces(
            self._obs.tracer.chrome_events(), worker_traces
        )

    def open_spans(self) -> int:
        """Frontier + reachable-worker open span count (the post-drain
        zero the bench asserts)."""
        total = (
            self._obs.tracer.open_count()
            if self._obs is not None and self._obs.tracer is not None else 0
        )
        for reply in self._worker_rpcs("trace"):
            if reply is not None:
                total += int(reply.get("open_spans") or 0)
        return total

    # ------------------------------------------------------------ lifecycle
    def serve(self, host: str = "127.0.0.1", port: int = 0) -> WorkerHost:
        """Expose this frontier over the ingress protocol (clients dial
        one address; the stack composes recursively)."""
        return WorkerHost(self, host=host, port=port)

    def flush(self, timeout: float | None = None) -> bool:
        deadline = (
            time.monotonic() + timeout if timeout is not None else None
        )
        with self._lock:
            while self._inflight > 0:
                remaining = (
                    deadline - time.monotonic()
                    if deadline is not None else None
                )
                if remaining is not None and remaining <= 0:
                    return False
                self._idle.wait(remaining)
            return True

    def close(self, *, close_workers: bool = False,
              timeout: float = 30.0) -> None:
        """Stop routing (in-flight requests drain first). The frontier
        does not own worker lifecycles by default; ``close_workers`` asks
        each reachable worker host to drain-then-close too."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self.flush(timeout)
        if close_workers:
            for link in self.links:
                try:
                    link.ensure().rpc("shutdown", timeout=timeout)
                except (proto.ConnectionLost, proto.ServeError, OSError,
                        TimeoutError):
                    pass
        for link in self.links:
            link.close()

    def __enter__(self) -> "Frontier":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


__all__ = ["Frontier", "WorkerLink", "WORKER_LEVEL_ERRORS"]
