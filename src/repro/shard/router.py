"""ShardedMorphService: route shape buckets across per-device shards.

The serving engine (PR 2) runs one ``MorphService`` per host; this router
scales it across a device mesh. Each mesh device gets its own full
``MorphService`` — batcher thread, bucket ladder, executable cache — pinned
to that device (``ServiceConfig.device``), and requests route by a stable
hash of ``(plan, bucket, dtype)``:

* every (plan, bucket) group lands on exactly one shard, so micro-batching
  coalesces exactly as on a single service (scattering a group would
  fragment its batches and multiply compiles);
* distinct groups spread across shards, so a diverse traffic mix keeps all
  devices busy while each device holds only its own groups' executables —
  the aggregate cache is N times the single-service VMEM/HBM budget, which
  is the point of sharding the engine.

Failure handling (ISSUE 6; vocabulary in serve/morph/resilience.py): each
shard carries a consecutive-failure **circuit breaker**
(``ServiceConfig.failover``). Shard-level failures (``InjectedFault``,
``ExecutorError``) trip it after ``failure_threshold`` consecutive hits;
while open, the shard's groups **reroute deterministically** to survivors —
the same crc32 hashed over the healthy subset, so a given (plan, bucket,
dtype) group keeps landing on one survivor and its batching stays coherent
— and the router **rewarms** the survivor's executable cache in the
background so rerouted traffic doesn't pay the compile in-line. After
``probe_interval_s`` one live request is let through as a **half-open
probe**: success closes the breaker (the shard's groups return home),
failure re-opens it. A request that fails on a shard is transparently
resubmitted to the next healthy shard (its caller future resolves with the
rerouted result); request-level failures (deadline, poison, overload)
propagate typed to the caller and never move the breaker. ``stats()``
surfaces per-shard health and the reroute/rewarm/probe counters.

Gray-failure defense (ISSUE 9): a shard that is *slow but alive* never
trips the error-driven breaker, so two further mechanisms cover it.
**Slow-state health** — every successful attempt feeds a per-shard
residence-latency EWMA; a shard whose EWMA exceeds
``failover.slow_factor`` times the peer median (and ``slow_min_ms``)
is marked ``"slow"``: new traffic routes away exactly like a reroute,
but the breaker does not move and the shard is never declared dead — a
trickle probe (one request per ``slow_probe_interval_s``) keeps its EWMA
fresh so recovery (below ``slow_exit_factor`` x median) is observable.
**Hedged dispatch** (``ServiceConfig.hedge``) — after a p99-derived delay
read from the *peer* shards' latency histograms (the shard the request is
riding on is excluded, so a gray shard's own slow completions can't
inflate the trigger that is supposed to rescue requests stuck on it), a
still-unresolved request is resubmitted to the next healthy shard; first
result wins, the caller's
future resolves exactly once (a per-request lock arbitrates the race),
and the router's own ``requests`` count ticks once per caller request no
matter how many shards raced on it. Both are driven by the replayable
chaos harness via ``FaultPlan``'s gray clauses (``latency_after`` /
``latency_every``).

Tiled (oversized) traffic routes the same way; each shard's device-side
tile gather (serve/morph/tiling.py) keeps it off the host. For one giant
image where *latency* matters more than engine throughput, use
``repro.shard.to_sharded`` directly — that is mesh parallelism inside a
single computation, not across the request stream.

``stats()`` merges per-shard engines by metric type (``repro.obs``):
counters sum, gauges apply their declared mode (cache sizes add, the
adaptive window takes the worst shard), histograms add bucket counts so the
merged p50/p99 are true cross-shard quantiles — and the full per-shard list
rides along. With ``ServiceConfig.obs`` set, the router also traces: one
trace ID is minted per request and threaded through every failover hop
(each hop is a span on the router's ``"router"`` lane; shard-side queue/
dispatch/retry spans carry the same ID), and ``export_trace()``
merges the router and all shard tracers onto one Chrome-trace timeline.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import zlib
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import (
    MetricsRegistry,
    Observability,
    cache_stats,
    chrome_trace,
    new_trace_id,
    quantile_from_snapshot,
)
from repro.serve.morph.buckets import choose_bucket
from repro.serve.morph.health import HealthTracker
from repro.serve.morph.plans import Plan, get_plan, single_op_plan
from repro.serve.morph.resilience import (
    DeadlineExceeded,
    ExecutorError,
    InjectedFault,
    ServeError,
    ShardUnavailable,
)
from repro.serve.morph.service import MorphService, ServiceConfig
from repro.serve.morph.tenancy import PRIORITY_NORMAL

# Failures that indict the *shard* (move its breaker); everything else —
# deadline, poison, overload, closed — is about the request or the caller
# and propagates without penalizing the shard that reported it.
SHARD_LEVEL_ERRORS = (InjectedFault, ExecutorError)


class _RequestCtx:
    """Per-caller-request arbitration state: exactly-once resolution of the
    outer future across the primary chain and any hedges, plus the hedge
    timer and the set of shards already racing on this request."""

    __slots__ = ("lock", "resolved", "hedges", "timer", "tried")

    def __init__(self):
        self.lock = threading.Lock()
        self.resolved = False
        self.hedges = 0
        self.timer: threading.Timer | None = None
        self.tried: set[int] = set()


class ShardedMorphService:
    """Mesh-sharded morphology serving. Use as a context manager:

        with ShardedMorphService() as svc:          # one shard per device
            fut = svc.submit(img, op="erode", se=(5, 5))
            outs = svc.run_plan(img2, "document_cleanup")
    """

    def __init__(self, config: ServiceConfig | None = None, *,
                 mesh=None, devices=None):
        if mesh is not None and devices is not None:
            raise ValueError("pass mesh or devices, not both")
        if mesh is not None:
            devices = list(mesh.devices.flat)
        elif devices is None:
            devices = jax.devices()
        if not devices:
            raise ValueError("ShardedMorphService needs at least one device")
        self.config = config or ServiceConfig()
        self.failover = self.config.failover
        self.devices = tuple(devices)
        self.shards = tuple(
            MorphService(dataclasses.replace(
                self.config,
                device=d,
                shard=i,  # labels the shard's trace lane and error context
                # shard-scoped fault clauses apply only to their shard
                faults=(self.config.faults.scoped(i)
                        if self.config.faults is not None else None),
            ))
            for i, d in enumerate(self.devices)
        )
        obs_cfg = self.config.obs
        self._obs = (
            Observability(obs_cfg, MetricsRegistry(), pid="router", name="router")
            if obs_cfg is not None and obs_cfg.enabled
            else None
        )
        # breaker + slow-state machinery shared with the ingress frontier
        # (serve/morph/health.py). The router's own counters share the
        # tracker's lock — the pre-extraction code had exactly one health
        # lock, and keeping that invariant means no new lock-ordering to
        # reason about. Methods below never call a self-locking tracker
        # method while holding _hlock.
        self._tracker = HealthTracker(
            len(self.shards), self.failover, noun="shard"
        )
        self._hlock = self._tracker.lock
        self._health = self._tracker.nodes
        # groups seen (token -> (plan, bucket, dtype)), for failover rewarm
        self._groups: dict[bytes, tuple[Plan, tuple | None, str]] = {}
        self._rewarmed: set[tuple[int, bytes]] = set()
        self.rewarms = 0
        # hedging (ISSUE 9): counters + the cached peer-quantile delays
        self.hedges = 0
        self.hedge_wins = 0
        self._requests_ok = 0  # caller requests resolved with a result —
        # ticks once per request however many shards raced on it, which is
        # what keeps stats()["requests"] single-count under hedging
        # hedge-delay cache, keyed by the excluded (hedge-target) shard:
        # exclude -> (delay_ms, computed_at)
        self._hedge_delay: dict[int | None, tuple[float, float]] = {}
        self._hedge_delay_last_ms = 0.0

    @property
    def reroutes(self) -> int:
        return self._tracker.reroutes

    @property
    def failovers(self) -> int:
        """Breaker trips observed at routing level."""
        return self._tracker.trips

    # ------------------------------------------------------------- routing
    @staticmethod
    def _token(plan: Plan, bucket, dtype_str: str) -> bytes:
        return f"{plan.name}|{bucket}|{dtype_str}".encode()

    def _route(self, plan: Plan, img: np.ndarray) -> MorphService:
        """The shard a request routes to right now (stable while health is
        stable); kept for tests/benchmarks that pin a group's primary."""
        bucket = choose_bucket(img.shape[0], img.shape[1], self.config.buckets)
        idx, _ = self._pick(self._token(plan, bucket, img.dtype.str), frozenset())
        return self.shards[idx]

    def _healthy(self, i: int) -> bool:
        return self._health[i].state == "closed"

    def _pick(self, token: bytes, excluded: frozenset) -> tuple[int, bool]:
        """Deterministic shard choice for a group token — the breaker/
        slow-state machine lives in :class:`HealthTracker` (shared with the
        ingress frontier). Raises :class:`ShardUnavailable` when nothing is
        routable."""
        return self._tracker.pick(token, excluded)

    def _record_success(self, idx: int, was_probe: bool) -> None:
        self._tracker.record_success(idx, was_probe)

    # ------------------------------------------------- slow-state (gray)
    def _observe_latency(self, idx: int, ms: float) -> None:
        """Feed one successful attempt's residence latency (submit to
        resolution, queue wait included — that is what the caller feels)
        into the shard's EWMA; the tracker re-scores every shard against
        the peer median. Errors never reach here: the breaker owns those."""
        self._tracker.observe_latency(idx, ms)

    # --------------------------------------------------------- hedging
    def _hedge_delay_s(self, exclude: int | None = None) -> float:
        """The hedge trigger delay: the configured quantile of the latency
        histograms merged over every shard EXCEPT ``exclude`` — the shard
        the request is currently riding on, i.e. the hedge target. The
        exclusion is the fix for the survivor-bias debt (ROADMAP, PR 9):
        the merged histogram includes the gray shard's own slow
        completions, so the moment one shard degrades, the merged p99
        climbs toward that shard's latency and the hedge that was supposed
        to rescue its requests never fires before they finish the slow
        way. Measured against healthy peers only, the delay stays at the
        fleet's actual service quantile and the gray shard's requests
        hedge out. Clamped to the policy's bounds and cached per excluded
        shard for ``refresh_s`` (the merge walks every peer registry)."""
        policy = self.config.hedge
        now = time.monotonic()
        delay_ms, at = self._hedge_delay.get(exclude, (0.0, 0.0))
        if now - at < policy.refresh_s and at > 0.0:
            return delay_ms / 1e3
        snaps = [
            s.metrics_snapshot()
            for i, s in enumerate(self.shards) if i != exclude
        ]
        lat = (
            MetricsRegistry.merge(snaps).get("latency_ms") if snaps else None
        )
        q = quantile_from_snapshot(lat, policy.quantile) if lat else 0.0
        delay_ms = min(max(q, policy.min_delay_ms), policy.max_delay_ms)
        self._hedge_delay[exclude] = (delay_ms, now)
        self._hedge_delay_last_ms = delay_ms
        return delay_ms / 1e3

    def _resolve(self, ctx: _RequestCtx, outer: Future, *,
                 exc: BaseException | None = None, result=None) -> bool:
        """Resolve the caller's future exactly once across every racing
        attempt; returns True for the attempt that won."""
        with ctx.lock:
            if ctx.resolved:
                return False
            ctx.resolved = True
            timer, ctx.timer = ctx.timer, None
        if timer is not None:
            timer.cancel()
        if exc is not None:
            outer.set_exception(exc)
        else:
            with self._hlock:
                self._requests_ok += 1
            outer.set_result(result)
        return True

    def _hedge(self, ctx: _RequestCtx, outer: Future, img, plan: Plan,
               token: bytes, deadline_at: float | None, tag: str | None,
               tenant: str | None, priority: int, trace: int | None) -> None:
        """Timer body: the primary chain is still unresolved after the
        hedge delay — race a duplicate on the next healthy shard."""
        with ctx.lock:
            if ctx.resolved:
                return
            ctx.hedges += 1
            ctx.timer = None
        with self._hlock:
            self.hedges += 1
        if self._obs is not None:
            self._obs.instant(
                "hedge", trace=trace, plan=plan.name, tried=sorted(ctx.tried)
            )
        self._attempt(outer, img, plan, token, deadline_at, tag,
                      frozenset(ctx.tried), trace, ctx=ctx, hedge=True,
                      tenant=tenant, priority=priority)

    def _record_failure(self, idx: int, was_probe: bool) -> list:
        """Count a shard-level failure; on breaker trip, return the rewarm
        work ((survivor, plan, bucket, dtype) tuples) to run outside the
        lock."""
        tripped = self._tracker.record_failure(idx, was_probe)
        rewarm: list = []
        if tripped and self.failover.rewarm:
            with self._hlock:
                rewarm = self._rewarm_targets(idx)
        return rewarm

    # ------------------------------------------------------------- rewarm
    def _rewarm_targets(self, dead: int) -> list:
        """Under _hlock: every known bucketed group whose primary is the
        dead shard, paired with the survivor it will deterministically
        reroute to."""
        n = len(self.shards)
        survivors = [i for i in range(n) if i != dead and self._healthy(i)]
        out = []
        for token, (plan, bucket, dtype_str) in self._groups.items():
            if bucket is None:  # tiled groups compile per tile grid; skip
                continue
            h = zlib.crc32(token)
            if h % n != dead or not survivors:
                continue
            target = survivors[h % len(survivors)]
            if (target, token) not in self._rewarmed:
                self._rewarmed.add((target, token))
                out.append((target, plan, bucket, dtype_str))
        return out

    def _rewarm_async(self, targets: list) -> None:
        """Compile a rerouted group's executable on its survivor off the
        routing path, so the first rerouted request doesn't pay the compile
        in-line. Batch bucket 1 — the smallest real executable; larger
        batch buckets compile on demand as coalescing resumes."""
        if not targets:
            return

        def warm():
            for idx, plan, bucket, dtype_str in targets:
                try:
                    svc = self.shards[idx]
                    with svc._device_scope():
                        fn = svc._executor_for(plan, bucket, np.dtype(dtype_str), 1)
                        fn(
                            jnp.zeros((1, *bucket), np.dtype(dtype_str)),
                            jnp.zeros((1, 4), np.int32),
                        )
                    with self._hlock:
                        self.rewarms += 1
                except Exception:  # noqa: BLE001 — warm is advisory only
                    pass

        threading.Thread(target=warm, name="shard-rewarm", daemon=True).start()

    # ---------------------------------------------------------- submission
    def submit(self, img, op: str = "erode", se=(3, 3), **kw):
        return self.submit_plan(img, single_op_plan(op, se), **kw)

    def submit_plan(self, img, plan: "str | Plan", *,
                    deadline_ms: float | None = None, tag: str | None = None,
                    tenant: str | None = None,
                    priority: int = PRIORITY_NORMAL,
                    _trace: int | None = None):
        plan = get_plan(plan)
        img = np.asarray(img)
        if img.ndim != 2:
            raise ValueError("the service takes single (H, W) images; submit "
                             "each image of a batch separately")
        bucket = choose_bucket(img.shape[0], img.shape[1], self.config.buckets)
        token = self._token(plan, bucket, img.dtype.str)
        with self._hlock:
            self._groups.setdefault(token, (plan, bucket, img.dtype.str))
        if deadline_ms is None:
            deadline_ms = self.config.default_deadline_ms
        deadline_at = (
            time.monotonic() + deadline_ms / 1e3 if deadline_ms is not None else None
        )
        outer: Future = Future()
        # one trace ID per caller request, minted here so it survives every
        # failover hop and hedge (shards see it via _trace and must not
        # re-mint — which is also what keeps per-request obs single-count).
        # An ingress worker host passes the frontier's ID through `_trace`,
        # so a trace spans processes the same way it spans hops.
        if _trace is not None:
            trace = _trace
        else:
            trace = new_trace_id() if self._obs is not None else None
        ctx = _RequestCtx()
        self._attempt(outer, img, plan, token, deadline_at, tag, frozenset(),
                      trace, ctx=ctx, tenant=tenant, priority=priority)
        return outer

    def _attempt(self, outer: Future, img, plan: Plan, token: bytes,
                 deadline_at: float | None, tag: str | None,
                 excluded: frozenset, trace: int | None = None, *,
                 ctx: _RequestCtx, hedge: bool = False,
                 tenant: str | None = None,
                 priority: int = PRIORITY_NORMAL) -> None:
        """Route one attempt; the done callback reroutes shard-level
        failures to the next survivor until every shard has been tried, so
        the caller's future always resolves — with the rerouted result or a
        typed error. A ``hedge`` attempt is opportunistic: only a result
        may resolve the caller (through ``_resolve``, exactly once); its
        failures still feed shard health but neither recurse nor resolve —
        the primary chain stays authoritative for errors."""
        deadline_ms = None
        if deadline_at is not None:
            deadline_ms = (deadline_at - time.monotonic()) * 1e3
            if deadline_ms <= 0:
                if not hedge:
                    self._resolve(ctx, outer, exc=DeadlineExceeded(
                        "deadline expired during failover", plan=plan.name))
                return
        try:
            idx, was_probe = self._pick(token, excluded)
        except ShardUnavailable as exc:
            if self._obs is not None:
                self._obs.instant(
                    "unroutable", trace=trace, plan=plan.name,
                    excluded=sorted(excluded), error=type(exc).__name__,
                )
            if not hedge:
                self._resolve(ctx, outer, exc=exc)
            return
        ctx.tried.add(idx)
        # the hop span covers shard submit through future resolution — its
        # duration is this attempt's full shard-side residence time
        tracer = self._obs.tracer if self._obs is not None else None
        hop = (
            tracer.begin("hop", trace=trace, shard=idx, probe=was_probe,
                         plan=plan.name, attempt=len(excluded), hedge=hedge)
            if tracer is not None else None
        )
        t0 = time.monotonic()
        try:
            fut = self.shards[idx].submit_plan(
                img, plan, deadline_ms=deadline_ms, tag=tag, _trace=trace,
                tenant=tenant, priority=priority,
            )
        except ServeError as exc:
            if hop is not None:
                tracer.end(hop, error=type(exc).__name__)
            # submit-time rejection (Overloaded, QuotaExceeded, brownout,
            # ServiceClosed): back-pressure or shutdown, not a shard fault —
            # shedding load is the point, don't spread the spill. Resolve
            # the caller's future (this path may run inside a done callback,
            # where a raise would vanish into the futures machinery and hang
            # the caller).
            if was_probe:
                with self._hlock:
                    self._health[idx].probing = False
            if not hedge:
                self._resolve(ctx, outer, exc=exc)
            return

        def done(f, idx=idx, was_probe=was_probe, hop=hop, t0=t0):
            exc = f.exception()
            if hop is not None:
                tracer.end(hop, error=type(exc).__name__ if exc else None)
            if exc is None:
                self._record_success(idx, was_probe)
                self._observe_latency(idx, (time.monotonic() - t0) * 1e3)
                if self._resolve(ctx, outer, result=f.result()) and hedge:
                    with self._hlock:
                        self.hedge_wins += 1
            elif isinstance(exc, SHARD_LEVEL_ERRORS):
                rewarm = self._record_failure(idx, was_probe)
                self._rewarm_async(rewarm)
                nxt = excluded | {idx}
                if self._obs is not None:
                    self._obs.instant(
                        "failover", trace=trace, shard=idx,
                        error=type(exc).__name__, hedge=hedge,
                        exhausted=len(nxt) >= len(self.shards),
                    )
                if hedge:
                    return  # health recorded; the primary chain owns errors
                if len(nxt) < len(self.shards):
                    self._attempt(outer, img, plan, token, deadline_at, tag,
                                  nxt, trace, ctx=ctx, tenant=tenant,
                                  priority=priority)
                else:
                    self._resolve(ctx, outer, exc=exc)
            else:  # request-level failure: typed, final, shard not indicted
                if not hedge:
                    self._resolve(ctx, outer, exc=exc)

        fut.add_done_callback(done)
        # arm (or re-arm, for multi-hedge policies) the hedge timer once a
        # real attempt is in flight and a second shard exists to race on
        policy = self.config.hedge
        if (
            policy.enabled
            and len(self.shards) > 1
            and ctx.hedges < policy.max_hedges
        ):
            with ctx.lock:
                if ctx.resolved or ctx.timer is not None:
                    return
                timer = threading.Timer(
                    # the delay excludes THIS attempt's shard: a hedge is
                    # scored against the peers it would run on, never
                    # against the (possibly gray) shard it rescues from
                    self._hedge_delay_s(exclude=idx), self._hedge,
                    args=(ctx, outer, img, plan, token, deadline_at, tag,
                          tenant, priority, trace),
                )
                timer.daemon = True
                ctx.timer = timer
            timer.start()

    def submit_expr(self, img, expr, name: str | None = None, **kw):
        from repro.morph.plan_compile import to_plan

        policy = self.shards[0].policy
        return self.submit_plan(img, to_plan(expr, name=name, policy=policy), **kw)

    def run(self, img, op: str = "erode", se=(3, 3), **kw):
        return self.submit(img, op, se, **kw).result()

    def run_plan(self, img, plan: "str | Plan", **kw):
        return self.submit_plan(img, plan, **kw).result()

    def run_expr(self, img, expr, name: str | None = None, **kw):
        return self.submit_expr(img, expr, name, **kw).result()

    def run_batch(self, imgs, plan: "str | Plan", **kw) -> list:
        futures = [self.submit_plan(im, plan, **kw) for im in imgs]
        return [f.result() for f in futures]

    # ------------------------------------------------------------- metrics
    def metrics_snapshot(self) -> dict:
        """The by-type merge of every shard's registry snapshot — the raw
        form ``stats()`` derives its aggregates from."""
        return MetricsRegistry.merge(
            [s.metrics_snapshot() for s in self.shards]
        )

    def stats(self) -> dict:
        per = [s.stats() for s in self.shards]
        merged = self.metrics_snapshot()

        def value(name: str):
            # merged counter or gauge scalar (0 before first registration)
            m = merged.get(name)
            return m["value"] if m is not None else 0

        # one merge rule per metric type replaces the old hand-coded
        # key-by-key sums: counters summed, the cache-size gauge summed, the
        # window gauge max'd, latency histograms added bucket-wise — so the
        # merged p50/p99 are real cross-shard quantiles, not the worst
        # shard's local estimate.
        cache = cache_stats(
            value("cache.size"), value("cache.hits"),
            value("cache.misses"), value("cache.evictions"),
        )
        iters_used = value("bounded_iter.iters_used")
        iters_budget = value("bounded_iter.iters_budget")
        bounded = {
            "executions": value("bounded_iter.executions"),
            "iters_used": iters_used,
            "iters_budget": iters_budget,
            "saved_frac": (
                1.0 - iters_used / iters_budget if iters_budget else 0.0
            ),
        }
        resilience = {
            k: value(f"batcher.{k}")
            for k in ("rejected_overloaded", "rejected_quota", "shed_brownout",
                      "deadline_expired", "retries", "bisections",
                      "request_failures")
        }
        # worst shard's active brownout level (the gauge merges with max)
        resilience["brownout_level"] = value("brownout.level")
        # per-tenant counters merge by name across shards; rebuild the map
        tenants: dict[str, dict] = {}
        for name, m in merged.items():
            if not name.startswith("tenant."):
                continue
            t, event = name[len("tenant."):].rsplit(".", 1)
            if t != "_":  # the anonymous tenant stays out of the map
                tenants.setdefault(t, {})[event] = m["value"]
        resilience["tenants"] = tenants
        with self._hlock:
            health = [h.snapshot() for h in self._health]
            resilience.update(
                reroutes=self.reroutes,
                rewarms=self.rewarms,
                failovers=self.failovers,
                hedges=self.hedges,
                hedge_wins=self.hedge_wins,
                hedge_delay_ms=self._hedge_delay_last_ms,
            )
            requests_ok = self._requests_ok
        lat = merged.get("latency_ms")
        dens = merged.get("rle.density")
        return {
            "shards": len(self.shards),
            "healthy_shards": sum(h["state"] == "closed" for h in health),
            "slow_shards": sum(h["state"] == "slow" for h in health),
            "health": health,
            # the router's own resolved-with-a-result count: one tick per
            # caller request however many shards raced on it under hedging
            # (per-shard "requests" counters still count shard-side work)
            "requests": requests_ok,
            "batches": value("batches"),
            "tiled_requests": value("tiled_requests"),
            "rle_requests": value("rle_requests"),
            "repr": {
                "dense": value("repr.dense"),
                "rle": value("repr.rle"),
                "density_p50": (
                    quantile_from_snapshot(dens, 0.50) if dens else 0.0
                ),
            },
            "img_per_s": sum(p["img_per_s"] for p in per),
            "p50_ms": quantile_from_snapshot(lat, 0.50) if lat else 0.0,
            "p99_ms": quantile_from_snapshot(lat, 0.99) if lat else 0.0,
            "cache": cache,
            "bounded_iter": bounded,
            "resilience": resilience,
            "effective_window_ms": merged["window.effective_ms"]["value"],
            "backend": per[0]["backend"],
            "interpret": per[0]["interpret"],
            "obs": self._obs.snapshot() if self._obs is not None else None,
            "per_shard": per,
        }

    def export_trace(self) -> dict | None:
        """Router + all shard tracers merged onto one Chrome-trace timeline
        (every tracer timestamps with the same process clock); None when
        tracing is off."""
        if self._obs is None or self._obs.tracer is None:
            return None
        tracers = [self._obs.tracer] + [
            s._obs.tracer for s in self.shards if s._obs is not None
        ]
        return chrome_trace(tracers)

    # ------------------------------------------------------------ lifecycle
    def flush(self, timeout: float | None = None) -> bool:
        return all(s.flush(timeout) for s in self.shards)

    def close(self) -> None:
        """Idempotent: each shard's close() joins an already-drained
        batcher on repeat calls."""
        for s in self.shards:
            s.close()

    def __enter__(self) -> "ShardedMorphService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
