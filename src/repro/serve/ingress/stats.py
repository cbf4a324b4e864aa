"""Cross-process stats: fleet metrics merge + multi-process Chrome traces.

Workers serve their ``metrics_snapshot()`` over the wire as plain JSON —
which is exactly what a registry snapshot already is, so the existing
by-type merge semantics (``repro.obs.merge_snapshots``: counters sum,
gauges by mode, histograms add bucket counts) apply to decoded frames
unchanged. ``fleet_stats`` builds the frontier's one fleet-wide ``stats()``
view from those merged snapshots, mirroring the sharded router's schema
(health list, merged quantiles, per-node detail riding along) so tooling
written against one tier reads the other.

Traces are the one thing that does *not* merge as-is: every process
timestamps spans with its own ``time.perf_counter()``, and two processes'
perf_counter bases are unrelated. The frontier therefore measures a clock
offset per worker on its control-plane ping (NTP-style midpoint estimate,
see ``Connection.ping``) and :func:`merge_process_traces` shifts each
worker's event timestamps by it before merging — so a frontier-minted
trace ID's spans line up on one timeline: ``hop`` on the frontier lane,
ingress/queue/dispatch spans on the worker lanes, microseconds apart the
way they really were. Negative shifted timestamps clamp to zero (the
Chrome trace format rejects negative ``ts``; sub-microsecond offset error
near the epoch is noise, not signal).
"""
from __future__ import annotations

from repro.obs import MetricsRegistry, cache_stats, quantile_from_snapshot


def merge_worker_metrics(snapshots: list[dict]) -> dict:
    """Fleet-wide registry view: the same ``merge_snapshots`` the sharded
    router uses, applied to wire-decoded worker snapshots."""
    return MetricsRegistry.merge([s for s in snapshots if s])


def fleet_stats(merged: dict, *, health: list[dict], counters: dict,
                per_worker: list[dict]) -> dict:
    """The frontier's ``stats()`` dict from a merged fleet snapshot —
    schema-aligned with ``ShardedMorphService.stats()`` (workers for
    shards) so dashboards and benchmarks read both tiers identically."""

    def value(name: str):
        m = merged.get(name)
        return m["value"] if m is not None else 0

    lat = merged.get("latency_ms")
    out = {
        "workers": len(health),
        "healthy_workers": sum(h["state"] == "closed" for h in health),
        "slow_workers": sum(h["state"] == "slow" for h in health),
        "health": health,
        "batches": value("batches"),
        "tiled_requests": value("tiled_requests"),
        "rle_requests": value("rle_requests"),
        "p50_ms": quantile_from_snapshot(lat, 0.50) if lat else 0.0,
        "p99_ms": quantile_from_snapshot(lat, 0.99) if lat else 0.0,
        "cache": cache_stats(
            value("cache.size"), value("cache.hits"),
            value("cache.misses"), value("cache.evictions"),
        ),
        "resilience": {
            k: value(f"batcher.{k}")
            for k in ("rejected_overloaded", "rejected_quota",
                      "shed_brownout", "deadline_expired", "retries",
                      "bisections", "request_failures")
        },
        "per_worker": per_worker,
    }
    # per-tenant counters merge by name across workers; rebuild the map
    tenants: dict[str, dict] = {}
    for name, m in merged.items():
        if not name.startswith("tenant."):
            continue
        t, event = name[len("tenant."):].rsplit(".", 1)
        if t != "_":
            tenants.setdefault(t, {})[event] = m["value"]
    out["resilience"]["tenants"] = tenants
    out.update(counters)
    return out


def shift_events(events: list[dict], offset_s: float) -> list[dict]:
    """Worker trace events re-based onto the frontier clock: ``ts`` (and
    nothing else) moves by ``-offset_s`` where ``offset_s`` is the
    worker-minus-frontier clock offset. Metadata events (``ph: "M"``,
    ``ts`` 0) stay put — they label lanes, not moments."""
    shifted = []
    for ev in events:
        if ev.get("ph") == "M":
            shifted.append(ev)
            continue
        ev = dict(ev)
        ev["ts"] = max(0.0, round(ev.get("ts", 0.0) - offset_s * 1e6, 3))
        shifted.append(ev)
    return shifted


def merge_process_traces(
    local_events: list[dict],
    worker_traces: list[tuple[dict | None, float | None]],
) -> dict:
    """One Chrome-trace document spanning processes: the frontier's own
    events plus each worker's, shifted by that worker's measured clock
    offset (workers whose offset was never measured shift by 0 — better a
    skewed lane than a dropped one)."""
    events = list(local_events)
    for doc, offset_s in worker_traces:
        if not doc:
            continue
        events.extend(shift_events(doc.get("traceEvents", []), offset_s or 0.0))
    events.sort(key=lambda e: e["ts"])
    return {"traceEvents": events, "displayTimeUnit": "ms"}


__all__ = ["merge_worker_metrics", "fleet_stats", "shift_events",
           "merge_process_traces"]
