"""Observability suite (ISSUE 7): metrics registry, tracing, profiling.

Three layers of guarantees:

* **Metrics** — counters/gauges/histograms merge by type with explicit
  semantics (sum / mode / bucket-add); histogram quantiles track
  ``np.percentile`` to within a bucket width; the stats surfaces keep a
  frozen key schema across ``MorphService`` and ``ShardedMorphService``
  (dashboards parse these dicts — key drift is an API break).
* **Tracing** — span handles close exactly once (double-end raises), the
  export is schema-valid Chrome trace-event JSON, and a chaos replay of the
  ISSUE 6 fault scenarios (failing shard + poison request) produces a trace
  containing the full resilience vocabulary — queue, dispatch, retry,
  bisect, hop, failover — with zero spans left open; dispatch spans carry
  their timed stages, and every launch counts the pixels it answered
  against the pixels it launched.
* **Gating** — ``obs=None`` (the default) constructs no observability
  runtime at all: the off path is structurally the pre-obs service.

Runs on logical shards (one CPU device repeated), so the suite is tier-1.
"""
import threading

import jax
import numpy as np
import pytest

from repro.obs import (
    DEFAULT_LATENCY_BUCKETS_MS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    ObsConfig,
    Tracer,
    cache_stats,
    chrome_trace,
    hit_rate,
    merge_snapshots,
    new_trace_id,
    quantile_from_snapshot,
    validate_chrome_trace,
)
from repro.serve.morph import (
    FaultPlan,
    MorphService,
    PoisonedRequest,
    RetryPolicy,
    ServeError,
    ServiceConfig,
    get_plan,
    single_op_plan,
)
from repro.shard import ShardedMorphService

RNG = np.random.default_rng(23)


def rand(h=40, w=50):
    return RNG.integers(0, 255, (h, w), dtype=np.uint8)


def cfg(**kw):
    kw.setdefault("buckets", ((64, 64),))
    kw.setdefault("window_ms", 1.0)
    return ServiceConfig(**kw)


# ------------------------------------------------------------------ metrics
def test_counter_gauge_histogram_basics():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.snapshot() == {"type": "counter", "value": 5}
    g = Gauge(mode="max")
    g.set(3.5)
    assert g.snapshot()["value"] == 3.5
    with pytest.raises(ValueError):
        Gauge(mode="average")
    h = Histogram((1.0, 10.0, 100.0))
    h.observe_many([0.5, 5.0, 50.0, 500.0])
    s = h.snapshot()
    assert s["counts"] == [1, 1, 1, 1]
    assert s["count"] == 4 and s["min"] == 0.5 and s["max"] == 500.0
    with pytest.raises(ValueError):
        Histogram(())
    with pytest.raises(ValueError):
        Histogram((5.0, 5.0))


def test_registry_names_are_typed():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    with pytest.raises(TypeError):
        reg.gauge("a")
    snap = reg.snapshot()
    assert snap == {"a": {"type": "counter", "value": 0}}


def test_merge_by_type():
    def make(vals, mode="sum"):
        reg = MetricsRegistry()
        reg.counter("n").inc(vals[0])
        reg.gauge("g", mode=mode).set(vals[1])
        reg.histogram("h", (10.0, 20.0)).observe(vals[2])
        return reg.snapshot()

    merged = merge_snapshots([make((1, 5.0, 3.0)), make((2, 7.0, 15.0))])
    assert merged["n"]["value"] == 3
    assert merged["g"]["value"] == 12.0  # sum mode
    assert merged["h"]["counts"] == [1, 1, 0]
    assert merged["h"]["count"] == 2
    assert merged["h"]["min"] == 3.0 and merged["h"]["max"] == 15.0
    # max-mode gauges take the worst shard
    m2 = merge_snapshots([make((0, 5.0, 1.0), "max"), make((0, 2.0, 1.0), "max")])
    assert m2["g"]["value"] == 5.0
    # a metric missing from some shards merges over those that have it
    partial = merge_snapshots([make((1, 1.0, 1.0)), {}])
    assert partial["n"]["value"] == 1


def test_merge_conflicts_raise():
    a = MetricsRegistry()
    a.counter("m")
    b = MetricsRegistry()
    b.gauge("m")
    with pytest.raises(ValueError, match="conflicting"):
        merge_snapshots([a.snapshot(), b.snapshot()])
    c = MetricsRegistry()
    c.histogram("h", (1.0, 2.0))
    d = MetricsRegistry()
    d.histogram("h", (1.0, 3.0))
    with pytest.raises(ValueError, match="bounds"):
        merge_snapshots([c.snapshot(), d.snapshot()])
    e = MetricsRegistry()
    e.gauge("g", mode="sum")
    f = MetricsRegistry()
    f.gauge("g", mode="max")
    with pytest.raises(ValueError, match="modes"):
        merge_snapshots([e.snapshot(), f.snapshot()])


def test_histogram_quantiles_track_percentile():
    rng = np.random.default_rng(3)
    samples = rng.lognormal(mean=1.0, sigma=1.0, size=4000)  # ms-ish spread
    h = Histogram(DEFAULT_LATENCY_BUCKETS_MS)
    h.observe_many(samples)
    snap = h.snapshot()
    for q in (0.5, 0.9, 0.99):
        est = quantile_from_snapshot(snap, q)
        exact = float(np.percentile(samples, q * 100))
        # within one bucket width of the exact answer
        hi = next(
            (b for b in DEFAULT_LATENCY_BUCKETS_MS if b >= exact),
            snap["max"],
        )
        lo = max(
            (b for b in DEFAULT_LATENCY_BUCKETS_MS if b < exact),
            default=snap["min"],
        )
        assert lo - 1e-9 <= est <= hi + 1e-9, (q, est, exact)
    # tails clamp to observed data
    assert quantile_from_snapshot(snap, 0.0) >= snap["min"]
    assert quantile_from_snapshot(snap, 1.0) <= snap["max"]
    assert quantile_from_snapshot(Histogram((1.0,)).snapshot(), 0.5) == 0.0


def test_shared_cache_arithmetic():
    assert hit_rate(0, 0) == 0.0
    assert hit_rate(3, 1) == 0.75
    s = cache_stats(2, 3, 1, 0)
    assert s == {"size": 2, "hits": 3, "misses": 1, "evictions": 0,
                 "hit_rate": 0.75}


# ------------------------------------------------------------------ tracing
def test_span_ends_exactly_once():
    t = Tracer()
    s = t.begin("work", trace=7, plan="erode")
    t.end(s, ok=True)
    with pytest.raises(RuntimeError, match="already ended"):
        t.end(s)
    assert t.open_count() == 0
    snap = t.snapshot()
    assert snap["spans_begun"] == snap["spans_ended"] == 1
    done = t.finished()[0]
    assert done.trace == 7 and done.attrs["ok"] is True


def test_ring_buffer_bounds_memory():
    t = Tracer(ring=4)
    for i in range(10):
        with t.span("s", trace=i):
            pass
    assert len(t.finished()) == 4
    assert t.dropped == 6
    assert [s.trace for s in t.finished()] == [6, 7, 8, 9]


def test_trace_ids_unique_across_threads():
    ids = []
    lock = threading.Lock()

    def mint():
        got = [new_trace_id() for _ in range(200)]
        with lock:
            ids.extend(got)

    threads = [threading.Thread(target=mint) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert len(ids) == len(set(ids))


def test_chrome_export_is_schema_valid():
    t = Tracer(pid="3", name="shard-3")
    with t.span("dispatch", trace=1, plan="erode", bucket=(64, 64)):
        pass
    t.instant("failover", trace=1, shard=2)
    doc = chrome_trace([t, None])  # None tracers are skipped
    assert validate_chrome_trace(doc) == []
    names = [e["name"] for e in doc["traceEvents"]]
    assert "process_name" in names and "dispatch" in names
    x = next(e for e in doc["traceEvents"] if e["name"] == "dispatch")
    assert x["ph"] == "X" and x["dur"] >= 0 and x["pid"] == "3"
    assert x["args"]["trace_id"] == 1 and x["args"]["bucket"] == [64, 64]
    inst = next(e for e in doc["traceEvents"] if e["name"] == "failover")
    assert inst["ph"] == "i" and inst["s"] == "t"


def test_validator_rejects_malformed_documents():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{}]}) != []
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": "0", "tid": 1, "ts": 1.0},  # no dur
        {"name": "y", "ph": "Q", "pid": "0", "tid": 1, "ts": 1.0},  # bad ph
        {"name": "z", "ph": "i", "pid": "0", "tid": 1, "ts": -5},   # bad ts
    ]}
    errs = validate_chrome_trace(bad)
    assert len(errs) == 3


# ------------------------------------------------------------- stats schema
SERVICE_STATS_KEYS = {
    "requests", "batches", "tiled_requests", "rle_requests", "repr",
    "bounded_iter", "img_per_s", "p50_ms", "p99_ms", "mean_batch",
    "occupancy", "cache", "backend", "interpret", "window_ms",
    "effective_window_ms", "adaptive_window", "resilience", "obs",
}
ROUTER_STATS_KEYS = {
    "shards", "healthy_shards", "slow_shards", "health", "requests",
    "batches", "tiled_requests", "rle_requests", "repr", "img_per_s",
    "p50_ms", "p99_ms", "cache", "bounded_iter", "resilience",
    "effective_window_ms", "backend", "interpret", "obs", "per_shard",
}
REPR_KEYS = {"dense", "rle", "density_p50"}
CACHE_KEYS = {"size", "hits", "misses", "evictions", "hit_rate"}
BOUNDED_KEYS = {"executions", "iters_used", "iters_budget", "saved_frac"}
BATCHER_COUNTERS = {
    "rejected_overloaded", "rejected_quota", "shed_brownout",
    "deadline_expired", "retries", "bisections", "request_failures",
}


def test_service_stats_schema_frozen():
    with MorphService(cfg()) as svc:
        svc.run(rand(), "erode", (3, 3))
        st = svc.stats()
    assert set(st) == SERVICE_STATS_KEYS
    assert set(st["cache"]) == CACHE_KEYS
    assert set(st["bounded_iter"]) == BOUNDED_KEYS
    assert set(st["repr"]) == REPR_KEYS
    assert set(st["resilience"]) == BATCHER_COUNTERS | {
        "max_queue", "faults", "brownout", "tenants",
    }
    assert st["requests"] == 1
    assert st["obs"] is None  # off by default
    assert st["p50_ms"] > 0.0


def test_router_stats_schema_frozen_and_consistent():
    devices = [jax.devices()[0]] * 3
    with ShardedMorphService(cfg(), devices=devices) as svc:
        for _ in range(6):
            svc.run(rand(), "erode", (3, 3))
        st = svc.stats()
    assert set(st) == ROUTER_STATS_KEYS
    assert set(st["cache"]) == CACHE_KEYS
    assert set(st["bounded_iter"]) == BOUNDED_KEYS
    assert set(st["repr"]) == REPR_KEYS
    assert set(st["resilience"]) == BATCHER_COUNTERS | {
        "reroutes", "rewarms", "failovers", "hedges", "hedge_wins",
        "hedge_delay_ms", "brownout_level", "tenants",
    }
    assert set(st["per_shard"][0]) == SERVICE_STATS_KEYS
    # the by-type merge must agree with summing the per-shard views
    assert st["requests"] == sum(p["requests"] for p in st["per_shard"]) == 6
    assert st["cache"]["misses"] == sum(
        p["cache"]["misses"] for p in st["per_shard"]
    )
    assert st["cache"]["hit_rate"] == pytest.approx(
        hit_rate(st["cache"]["hits"], st["cache"]["misses"])
    )
    # merged latency histogram yields a real cross-shard quantile
    assert st["p99_ms"] >= st["p50_ms"] > 0.0


def test_metrics_snapshot_merges_by_registry():
    devices = [jax.devices()[0]] * 2
    with ShardedMorphService(cfg(), devices=devices) as svc:
        svc.run(rand(), "erode", (3, 3))
        merged = svc.metrics_snapshot()
    assert merged["requests"]["value"] == 1
    assert merged["latency_ms"]["type"] == "histogram"
    assert merged["latency_ms"]["count"] == 1
    assert merged["window.effective_ms"]["mode"] == "max"


# ------------------------------------------------------------------- gating
def test_obs_off_is_structurally_absent():
    with MorphService(cfg()) as svc:
        svc.run(rand(), "erode", (3, 3))
        assert svc._obs is None
        assert svc._batcher._obs is None
        assert svc.export_trace() is None
        assert svc.stats()["obs"] is None
        # obs gates spans and stages only: the launch counters stay on
        snap = svc.metrics_snapshot()
        assert snap["executor.pixels_valid"]["value"] == 40 * 50
        assert not any(k.startswith("bounded_iter.used") for k in snap)
    devices = [jax.devices()[0]] * 2
    with ShardedMorphService(cfg(), devices=devices) as svc:
        assert svc._obs is None
        assert svc.export_trace() is None


def test_obs_config_enabled_flag():
    assert ObsConfig().enabled
    assert not ObsConfig(trace=False).enabled
    assert ObsConfig(trace=False, jax_profiler=True).enabled
    with MorphService(cfg(obs=ObsConfig(trace=False))) as svc:
        assert svc._obs is None
    # stages without spans: the profiler annotations alone
    with MorphService(cfg(obs=ObsConfig(trace=False, jax_profiler=True))) as svc:
        svc.run(rand(), "erode", (3, 3))
        assert svc._obs.tracer is None and svc.export_trace() is None


# -------------------------------------------------------- enabled pipeline
def test_single_service_trace_and_profile():
    with MorphService(cfg(obs=ObsConfig())) as svc:
        for _ in range(4):
            svc.run(rand(), "erode", (3, 3))
        svc.flush(10)
        st = svc.stats()
        doc = svc.export_trace()
        assert svc._obs.tracer.open_count() == 0
    assert validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"queue", "dispatch"} <= names and "executor" not in names
    # every request minted a distinct trace id, carried by its queue span
    qids = [
        e["args"]["trace_id"] for e in doc["traceEvents"]
        if e["name"] == "queue"
    ]
    assert len(qids) == 4 and len(set(qids)) == 4
    # the dispatch span carries the launch's args and its stage split, the
    # durations the per-key executor profile used to time a second time
    dispatches = [e for e in doc["traceEvents"] if e["name"] == "dispatch"]
    assert len(dispatches) == 4
    for ev in dispatches:
        args = ev["args"]
        assert args["plan"] == "erode" and args["bucket"] == [64, 64]
        assert args["dtype"] == "uint8" and args["batch"] == 1
        assert set(args["stages"]) == {"pad", "launch", "d2h"}
        assert 0 < sum(args["stages"].values()) <= ev["dur"]
    # the cold first launch paid the compile: its launch stage is the longest
    launches = [e["args"]["stages"]["launch"] for e in dispatches]
    assert launches[0] == max(launches)
    assert st["obs"]["trace"]["open"] == 0
    assert "profiled_keys" not in st["obs"]


def test_span_ids_parents_and_stages_export():
    t = Tracer()
    outer = t.begin("ingress", trace=7)
    inner = t.begin("queue", trace=7, parent=outer)
    outer.attrs["stages"] = {"recv": 12.5}
    t.end(inner)
    t.end(outer)
    evs = {e["name"]: e for e in t.chrome_events() if e["ph"] == "X"}
    assert evs["queue"]["args"]["parent_id"] == evs["ingress"]["args"]["span_id"]
    assert "parent_id" not in evs["ingress"]["args"]
    assert evs["ingress"]["args"]["stages"] == {"recv": 12.5}
    assert inner.id != outer.id


FRAME = (600, 800)
PAGE = (3508, 2480)  # A4 at 300 dpi, portrait


@pytest.mark.parametrize("shape,n,plan", [
    (FRAME, 5, single_op_plan("erode", (3, 3))),  # one (608, 896) batch of 8 slots
    (PAGE, 1, "document_cleanup"),  # 35 tiles in one grid program, no dummies
], ids=["frames_batch_of_5", "a4_page"])
def test_launch_counters_are_exact(shape, n, plan):
    """Pixels answered against pixels launched (slots times bucket or tile
    extent), and real tiles per launch, counted with obs off."""
    c = ServiceConfig(window_ms=2000.0, adaptive_window=False)
    with MorphService(c) as svc:
        futs = [svc.submit_plan(rand(*shape), plan) for _ in range(n)]
        for f in futs:
            f.result(timeout=300)
        snap = {k: v["value"] for k, v in svc.metrics_snapshot().items()
                if v["type"] == "counter"}
    if shape == FRAME:
        assert snap["batches"] == 1
        launched, tiles, launches = 8 * 608 * 896, 0, 0
    else:
        gh, gw = get_plan("document_cleanup").halo()
        launched, tiles, launches = 35 * (512 + 2 * gh) * (512 + 2 * gw), 35, 1
    assert snap["executor.pixels_valid"] == n * shape[0] * shape[1]
    assert snap["executor.pixels_launched"] == launched
    assert snap["tiled.tiles"] == tiles
    assert snap["tiled.launches"] == launches


def test_submit_rejection_leaves_no_open_spans():
    """Admission rejects before the queue span (or the RLE density probe)
    exists, so shed requests cost nothing in the tracer — but they stay
    observable through the admission counters, and nothing leaks."""
    c = cfg(obs=ObsConfig(), max_queue=1, window_ms=50.0)
    with MorphService(c) as svc:
        futs = []
        rejected = 0
        for _ in range(8):
            try:
                futs.append(svc.submit(rand(), "erode", (3, 3)))
            except ServeError:
                rejected += 1
        for f in futs:
            f.result()
        svc.flush(10)
        assert rejected > 0
        assert svc._obs.tracer.open_count() == 0
        errs = [
            e for e in svc.export_trace()["traceEvents"]
            if e["name"] == "queue" and e["args"].get("error")
        ]
        assert errs == []  # never opened, nothing to error-close
        assert svc.stats()["resilience"]["rejected_overloaded"] == rejected


# ----------------------------------------------------- chaos trace replay
def test_chaos_trace_is_complete():
    """Replay the ISSUE 6 chaos scenario with tracing on: the primary shard
    fails every dispatch (InjectedFault -> retry -> breaker -> failover) and
    one request is poisoned (bisect isolates it on the survivor). The
    exported trace must be schema-valid, contain the whole resilience span
    vocabulary, and close every span exactly once."""
    n = 4
    plan = single_op_plan("erode", (3, 3))
    import zlib

    primary = zlib.crc32(
        f"{plan.name}|{(64, 64)}|{np.dtype(np.uint8).str}".encode()
    ) % n
    c = cfg(
        window_ms=30.0,  # coalesce the whole cohort into one group
        max_batch=8,
        retry=RetryPolicy(max_retries=1, backoff_ms=0.5, backoff_cap_ms=2.0),
        faults=FaultPlan(
            fail_shard=primary, fail_after=0, fail_for=None,
            poison_tags=frozenset({"bad"}),
        ),
        obs=ObsConfig(),
    )
    devices = [jax.devices()[0]] * n
    imgs = [rand(60, 60) for _ in range(8)]
    with ShardedMorphService(c, devices=devices) as svc:
        futs = [
            svc.submit_plan(img, plan, tag="bad" if i == 3 else None)
            for i, img in enumerate(imgs)
        ]
        outcomes = []
        for f in futs:
            try:
                f.result(timeout=120)
                outcomes.append("ok")
            except PoisonedRequest:
                outcomes.append("poison")
            except ServeError as e:  # pragma: no cover - diagnostic
                outcomes.append(type(e).__name__)
        svc.flush(30)
        doc = svc.export_trace()
        stats = svc.stats()
        # exactly-once accounting: nothing left open on any tracer
        assert svc._obs.tracer.open_count() == 0
        for s in svc.shards:
            assert s._obs.tracer.open_count() == 0
    assert outcomes.count("ok") == 7
    assert outcomes[3] == "poison"
    assert validate_chrome_trace(doc) == []
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"queue", "dispatch", "retry", "bisect", "hop",
            "failover"} <= names, names
    # the failing primary tripped its breaker and traffic moved
    assert stats["resilience"]["failovers"] >= 1
    assert stats["resilience"]["retries"] >= 1
    assert stats["resilience"]["bisections"] >= 1
    # one trace id per request, threaded through router hops unchanged:
    # every queue span's id also appears on at least one hop span
    hops = {
        e["args"]["trace_id"] for e in doc["traceEvents"]
        if e["name"] == "hop"
    }
    queued = {
        e["args"]["trace_id"] for e in doc["traceEvents"]
        if e["name"] == "queue"
    }
    assert queued <= hops
    assert len(queued) == 8
    # spans begun == spans ended on every lane (the balance the open_count
    # checks above prove, restated from the exported snapshots)
    trace_stats = stats["obs"]["trace"]
    assert trace_stats["spans_begun"] == trace_stats["spans_ended"]
