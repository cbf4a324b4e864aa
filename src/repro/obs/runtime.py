"""ObsConfig and the Observability runtime the serving tier hangs hooks on.

``ObsConfig`` is the single gate (ISSUE 7): ``ServiceConfig.obs = None``
(the default) means no ``Observability`` object is ever constructed and
every hook site in the service/batcher/router/worker host is one ``is
None`` check — the same zero-overhead-off contract as ``FaultInjector``.
With a config present, the runtime owns:

* a :class:`~repro.obs.trace.Tracer` (per-request spans, Chrome export);
* *stages*: timed same-thread sections inside a span (``recv``, ``pad``,
  ``launch``, ``d2h``, ``reply``, ``tile.*``), summed per name into the
  span's ``stages`` arg in microseconds and, with ``jax_profiler`` on,
  mirrored into the profiler's trace as ``morph_serve:<stage>``
  annotations carrying the plan in their metadata, so a device profile
  names what the host was doing in each idle gap. Stages never nest on a
  thread and outer spans are not mirrored, so the innermost stage is the
  only host annotation open at any instant;
* ``BoundedIter`` iters-used/budget as first-class histograms (the
  counters in ``ServiceStats`` only give the mean; reconstruction-depth
  *distribution* is what the wavefront ROADMAP item needs).

The metrics registry itself is NOT gated: it is the always-on substrate
``stats()`` is built from (plain int adds under existing locks — the
pre-obs counters under another name). Only the per-request/per-dispatch
extras above sit behind the gate.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

from repro.obs.metrics import MetricsRegistry, POW2_BUCKETS
from repro.obs.trace import Tracer, chrome_trace, new_trace_id


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability knobs. Constructing one and passing it as
    ``ServiceConfig.obs`` turns the instrumented paths on; ``None`` keeps
    the serving tier exactly as fast as before this module existed."""

    trace: bool = True            # per-request spans + Chrome export
    trace_ring: int = 8192        # finished spans kept per tracer
    jax_profiler: bool = False    # stages as jax.profiler.TraceAnnotation

    @property
    def enabled(self) -> bool:
        return self.trace or self.jax_profiler


class Observability:
    """Per-service observability runtime. Every public hook is safe to call
    from any thread; hooks are no-ops for the features the config leaves
    off, so call sites only ever test the service's single ``_obs is not
    None`` gate."""

    def __init__(self, config: ObsConfig, registry: MetricsRegistry, *,
                 pid="0", name: str = "service"):
        self.config = config
        self.registry = registry
        self.tracer = (
            Tracer(ring=config.trace_ring, pid=pid, name=name)
            if config.trace else None
        )
        self._annotation = None
        if config.jax_profiler:
            import jax.profiler

            self._annotation = jax.profiler.TraceAnnotation
        # the span whose stage this thread is in: a span begun there (the
        # queue span of a request submitted during an ingress span's recv
        # stage) takes it as parent
        self._inside = threading.local()
        self._h_iters_used = registry.histogram("bounded_iter.used", POW2_BUCKETS)
        self._h_iters_budget = registry.histogram(
            "bounded_iter.budget", POW2_BUCKETS)

    # -------------------------------------------------------- request spans
    def begin(self, name: str, trace=None, **attrs):
        """Open a cross-thread span (None when tracing is off); close it
        with :meth:`end`."""
        if self.tracer is None:
            return None
        return self.tracer.begin(name, trace=trace, **attrs)

    def end(self, span, **attrs) -> None:
        if span is not None:
            self.tracer.end(span, **attrs)

    @contextlib.contextmanager
    def stage(self, span, name: str, plan: str | None = None):
        """Time a same-thread section of ``span`` (which may be None):
        its microseconds add to ``span.attrs["stages"][name]`` and, with
        ``jax_profiler`` on, it is a ``morph_serve:<name>`` annotation in
        the profiler's trace with ``plan`` in its metadata."""
        ann = None
        if self._annotation is not None:
            ann = self._annotation(f"morph_serve:{name}", plan=plan)
            ann.__enter__()
        self._inside.span = span
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self._inside.span = None
            if ann is not None:
                ann.__exit__(None, None, None)
            if span is not None:
                stages = span.attrs.setdefault("stages", {})
                stages[name] = stages.get(name, 0.0) + dt * 1e6

    def request_submitted(self, req, plan_name: str, bucket, dtype: str) -> None:
        """Mint the request's trace ID (unless a router hop already did) and
        open its queue-wait span, the child of the span whose stage this
        thread is in (the worker host's ``ingress``), if any."""
        if req.trace is None:
            req.trace = new_trace_id()
        if self.tracer is not None:
            req.qspan = self.tracer.begin(
                "queue", trace=req.trace,
                parent=getattr(self._inside, "span", None),
                plan=plan_name, bucket=bucket, dtype=dtype,
            )

    def request_dequeued(self, req, **attrs) -> None:
        """Close the queue span (idempotent: retries re-enter the executor
        but the queue wait ended at first dispatch)."""
        span = getattr(req, "qspan", None)
        if span is not None:
            req.qspan = None
            self.tracer.end(span, **attrs)

    def request_failed(self, req, exc: BaseException) -> None:
        """A request failing before/without dispatch still closes its queue
        span, so chaos traces account for every span exactly once."""
        self.request_dequeued(req, error=type(exc).__name__)

    # ---------------------------------------------------------- group spans
    def group_span(self, name: str, reqs, **attrs):
        """Span covering one dispatched group; args carry every member's
        trace ID so per-request journeys reconstruct from group events."""
        if self.tracer is None:
            return contextlib.nullcontext()
        attrs["trace_ids"] = [r.trace for r in reqs]
        attrs["n"] = len(reqs)
        return self.tracer.span(name, **attrs)

    def instant(self, name: str, reqs=None, **attrs) -> None:
        if self.tracer is None:
            return
        if reqs is not None:
            attrs["trace_ids"] = [r.trace for r in reqs]
        self.tracer.instant(name, **attrs)

    def record_bounded(self, used: int, budget: int) -> None:
        self._h_iters_used.observe(used)
        self._h_iters_budget.observe(budget)

    # -------------------------------------------------------------- reading
    def export_trace(self) -> dict:
        return chrome_trace([self.tracer])

    def snapshot(self) -> dict:
        return {
            "trace": self.tracer.snapshot() if self.tracer is not None else None,
            "jax_profiler": self.config.jax_profiler,
        }


def now_s() -> float:
    """The serving tier's duration clock (monotonic, high resolution).
    Durations everywhere use this; wall-clock time is reserved for
    checkpoint metadata (see checkpoint/manager.py)."""
    return time.perf_counter()


__all__ = ["ObsConfig", "Observability", "now_s"]
