"""End-to-end serving observability (ISSUE 7): one metrics vocabulary,
per-request tracing, and timed host stages for the morphology serving
tier.

    from repro.obs import ObsConfig
    from repro.serve.morph import MorphService, ServiceConfig

    with MorphService(ServiceConfig(obs=ObsConfig())) as svc:
        svc.run(img, "erode", (5, 5))
        json.dump(svc.export_trace(), open("trace.json", "w"))
        # -> load trace.json at ui.perfetto.dev

Three layers (DESIGN.md §12):

* ``metrics`` — counters / gauges / fixed-bucket histograms with explicit
  by-type merge semantics; the serving stats surfaces are views over one
  :class:`MetricsRegistry` per service, and the sharded router's stats are
  a :func:`merge_snapshots` over its shards.
* ``trace`` — trace IDs minted at submit, spans across worker ingress /
  queue wait / dispatch / retry / bisection / failover hops, each with
  its parent span, exported as Chrome trace-event JSON.
* ``runtime`` — :class:`ObsConfig` (off by default; ``None`` costs one
  ``is None`` check per hook site) and the :class:`Observability` object
  holding the tracer and the stages (timed sections of a span, mirrored
  into the ``jax.profiler`` trace as ``morph_serve:<stage>``).
"""
from repro.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS_MS,
    POW2_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    cache_stats,
    hit_rate,
    merge_snapshots,
    quantile_from_snapshot,
)
from repro.obs.runtime import (
    Observability,
    ObsConfig,
    now_s,
)
from repro.obs.trace import (
    Span,
    Tracer,
    chrome_trace,
    new_trace_id,
    validate_chrome_trace,
)

__all__ = [
    "DEFAULT_LATENCY_BUCKETS_MS",
    "POW2_BUCKETS",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "cache_stats",
    "hit_rate",
    "merge_snapshots",
    "quantile_from_snapshot",
    "Observability",
    "ObsConfig",
    "now_s",
    "Span",
    "Tracer",
    "chrome_trace",
    "new_trace_id",
    "validate_chrome_trace",
]
