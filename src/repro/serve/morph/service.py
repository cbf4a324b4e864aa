"""MorphService: the async front door over the fused morphology kernels.

Mirrors the LM engine (serve/engine.py) one layer up: where that engine
batches decode steps over a KV cache, this one batches single-image
morphology requests into (B, H, W) stacks. A request flows:

    submit(img, op/plan)
      -> bucket  (buckets.py: pad up to a fixed (H, W) ladder)   } cache-
      -> batch   (batcher.py: coalesce within a deadline window) } friendly
      -> execute (plans.py executor from the LRU executable cache)
      -> crop + resolve the Future

Images too large for the ladder take the tiled route (tiling.py): one
program per tile grid, gather, passes and stitch on device, from the same
executable cache. The cache is keyed on ``(plan, shape, dtype,
batch-bucket, policy.cache_token(), backend, interpret)`` for buckets and
on the grid ``(ny, nx)`` and tile extent for pages, with hit/miss/eviction
counters; batch sizes are bucketed to powers of two so B-variance cannot
silently multiply compiles.

Observability (ISSUE 7, ``repro.obs``): every counter/latency surface here
is a view over one :class:`~repro.obs.MetricsRegistry` per service —
``stats()`` derives its dict from registry metrics, and the sharded router
merges registries by metric type instead of re-aggregating stats dicts.
Passing ``ServiceConfig(obs=ObsConfig())`` additionally turns on
per-request tracing (trace ID minted at submit, spans over queue wait and
dispatch, exported via :meth:`MorphService.export_trace` as Chrome
trace-event JSON) and the dispatch span's timed stages (``pad``,
``launch``, ``d2h``; ``tile.gather``, ``tile.launch``, ``tile.stitch`` on
the tiled route); ``obs=None`` (default) costs one ``is None`` check per
hook. Every launch, traced or not, adds the pixels it answered and the
pixels it launched (batch slots times bucket or tile extent) to the
``executor.pixels_valid`` / ``executor.pixels_launched`` counters, and a
tiled page's program call its ``ny*nx`` tiles to ``tiled.tiles`` and one
to ``tiled.launches``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dispatch import DispatchPolicy, resolve_interpret
from repro.obs import (
    MetricsRegistry,
    Observability,
    ObsConfig,
    POW2_BUCKETS,
    cache_stats,
    chrome_trace,
    quantile_from_snapshot,
)
from repro.morph import cost_model_for
from repro.rle import estimate_run_density, lower_rle, plan_rle_eligible
from repro.serve.morph.batcher import MicroBatcher
from repro.serve.morph.buckets import (
    DEFAULT_BUCKETS,
    check_buckets,
    choose_bucket,
    crop_from_bucket,
    valid_rect,
)
from repro.serve.morph.resilience import (
    DeadlineExceeded,
    ExecutorError,
    FaultInjector,
    FaultPlan,
    FailoverPolicy,
    HedgePolicy,
    RetryPolicy,
    ServeError,
)
from repro.serve.morph.tenancy import (
    BrownoutPolicy,
    PRIORITY_NORMAL,
    TenantQuota,
)
from repro.morph.plan_compile import to_plan
from repro.serve.morph.plans import (
    Plan,
    build_executor,
    check_backend,
    get_plan,
    single_op_plan,
)
from repro.serve.morph.tiling import build_grid_executor, run_grid, tile_counts


_NULL = contextlib.nullcontext()

# Run-density histogram bounds (runs per pixel): log-spaced over the range
# the representation gate discriminates on — 0.1% (deep-RLE territory)
# through 50% (checkerboard worst case).
DENSITY_BUCKETS: tuple[float, ...] = (
    0.0005, 0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5,
)


def _round_up_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class ExecutableCache:
    """LRU over built (jitted) plan executors, with observable counters.

    One entry == one compile of one executable (keys include the padded
    batch size), so ``misses`` is exactly the compile count the service has
    paid — the number the bucket ladder exists to keep small. Counters are
    registry metrics (``cache.*``) so shard merges sum them by type.
    """

    def __init__(self, max_size: int = 128, registry: MetricsRegistry | None = None):
        self.max_size = max_size
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()
        reg = registry if registry is not None else MetricsRegistry()
        self._hits = reg.counter("cache.hits")
        self._misses = reg.counter("cache.misses")
        self._evictions = reg.counter("cache.evictions")
        self._size = reg.gauge("cache.size", mode="sum")

    @property
    def hits(self) -> int:
        return self._hits.value

    @property
    def misses(self) -> int:
        return self._misses.value

    @property
    def evictions(self) -> int:
        return self._evictions.value

    def get(self, key, builder):
        with self._lock:
            if key in self._entries:
                self._hits.inc()
                self._entries.move_to_end(key)
                return self._entries[key]
            self._misses.inc()
        value = builder()  # build outside the lock; benign duplicate on race
        with self._lock:
            self._entries[key] = value
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_size:
                self._entries.popitem(last=False)
                self._evictions.inc()
            self._size.set(len(self._entries))
        return value

    def snapshot(self) -> dict:
        with self._lock:
            return cache_stats(
                len(self._entries), self.hits, self.misses, self.evictions
            )


class ServiceStats:
    """Rolling serving metrics: throughput, latency quantiles, occupancy.

    Latencies and batch sizes are fixed-bucket registry histograms
    (``latency_ms``, ``batch_size``): p50/p99 read off the histogram, which
    is what makes the sharded router's cross-shard quantiles well-defined
    (bucket counts add; percentiles never would). Only the throughput
    timestamps stay a rolling deque — img/s needs real arrival times.
    """

    def __init__(self, window: int = 4096, registry: MetricsRegistry | None = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self._lock = threading.Lock()
        self._done_ts = collections.deque(maxlen=window)
        self._requests = self.registry.counter("requests")
        self._batches = self.registry.counter("batches")
        self._tiled = self.registry.counter("tiled_requests")
        self._latency = self.registry.histogram("latency_ms")
        self._batch_sizes = self.registry.histogram("batch_size", POW2_BUCKETS)
        # convergence telemetry from BoundedIter plans (reconstruction):
        # budget is the fixed-trace iteration cap, used what actually ran
        # before the predicated scan converged (interp.py) — the gap is
        # work the convergence-aware serving path reclaims.
        self._bounded_execs = self.registry.counter("bounded_iter.executions")
        self._iters_used = self.registry.counter("bounded_iter.iters_used")
        self._iters_budget = self.registry.counter("bounded_iter.iters_budget")
        # representation gate (repro.rle): one counter per representation
        # decision plus the measured run-density histogram, so the gate's
        # behavior over a traffic mix is auditable from stats()/the registry
        self._rle = self.registry.counter("rle_requests")
        self._repr_dense = self.registry.counter("repr.dense")
        self._repr_rle = self.registry.counter("repr.rle")
        self._density = self.registry.histogram("rle.density", DENSITY_BUCKETS)

    @property
    def requests(self) -> int:
        return self._requests.value

    def record_batch(self, latencies_s) -> None:
        now = time.monotonic()
        with self._lock:
            self._requests.inc(len(latencies_s))
            self._batches.inc()
            self._batch_sizes.observe(len(latencies_s))
            self._latency.observe_many([l * 1e3 for l in latencies_s])
            self._done_ts.extend([now] * len(latencies_s))

    def record_tiled(self, latencies_s) -> None:
        """Tiled requests never ride the batcher's stacks — count their
        latency/throughput but keep them out of the occupancy metrics."""
        now = time.monotonic()
        with self._lock:
            self._requests.inc(len(latencies_s))
            self._tiled.inc(len(latencies_s))
            self._latency.observe_many([l * 1e3 for l in latencies_s])
            self._done_ts.extend([now] * len(latencies_s))

    def record_repr(self, use_rle: bool, density: float) -> None:
        """One representation-gate decision (at submit, before execution)."""
        with self._lock:
            (self._repr_rle if use_rle else self._repr_dense).inc()
            self._density.observe(density)

    def record_rle(self, latencies_s) -> None:
        """RLE-routed requests execute per request on exact-shape run
        buffers — like the tiled route, they never ride the batcher's
        stacks, so they stay out of the occupancy metrics."""
        now = time.monotonic()
        with self._lock:
            self._requests.inc(len(latencies_s))
            self._rle.inc(len(latencies_s))
            self._latency.observe_many([l * 1e3 for l in latencies_s])
            self._done_ts.extend([now] * len(latencies_s))

    def record_bounded(self, used: int, budget: int) -> None:
        with self._lock:
            self._bounded_execs.inc()
            self._iters_used.inc(int(used))
            self._iters_budget.inc(int(budget))

    def snapshot(self, max_batch: int) -> dict:
        with self._lock:
            ts = list(self._done_ts)
            lat = self._latency.snapshot()
            sizes = self._batch_sizes.snapshot()
            # copy under the lock: used/budget must come from one
            # record_bounded or the derived ratio can tear
            bounded_execs = self._bounded_execs.value
            iters_used = self._iters_used.value
            iters_budget = self._iters_budget.value
            density = self._density.snapshot()
        span = (ts[-1] - ts[0]) if len(ts) > 1 else 0.0
        mean_batch = sizes["sum"] / sizes["count"] if sizes["count"] else 0.0
        return {
            "requests": self._requests.value,
            "batches": self._batches.value,
            "tiled_requests": self._tiled.value,
            "rle_requests": self._rle.value,
            "repr": {
                "dense": self._repr_dense.value,
                "rle": self._repr_rle.value,
                "density_p50": quantile_from_snapshot(density, 0.50),
            },
            "bounded_iter": {
                "executions": bounded_execs,
                "iters_used": iters_used,
                "iters_budget": iters_budget,
                "saved_frac": (
                    1.0 - iters_used / iters_budget if iters_budget else 0.0
                ),
            },
            "img_per_s": (len(ts) - 1) / span if span > 0 else 0.0,
            "p50_ms": quantile_from_snapshot(lat, 0.50),
            "p99_ms": quantile_from_snapshot(lat, 0.99),
            "mean_batch": float(mean_batch),
            "occupancy": float(mean_batch) / max_batch,
        }


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    buckets: tuple[tuple[int, int], ...] = DEFAULT_BUCKETS
    max_batch: int = 64
    window_ms: float = 2.0
    # Load-aware deadline window (ROADMAP item): window_ms becomes the MAX;
    # the effective window shrinks toward min_window_ms when dispatches
    # drain below the batcher's low-water mark and grows back toward
    # window_ms under sustained pressure. stats()["effective_window_ms"]
    # reports the current value.
    adaptive_window: bool = True
    min_window_ms: float = 0.0
    tile_interior: tuple[int, int] = (512, 512)
    max_tiles_per_launch: int = 16
    backend: str = "auto"  # "kernel" (fused Pallas) | "jnp" | "auto"
    # Representation gate (repro.rle): boolean requests on run-domain-
    # lowerable plans are probed for run density and routed to RLE when the
    # cost model says runs beat pixels. False = always dense (A/B baseline).
    rle_gate: bool = True
    policy: DispatchPolicy | None = None
    interpret: bool | None = None
    cache_size: int = 128
    stats_window: int = 4096
    # Pin this service's dispatches to one jax device — how the sharded
    # router (repro.shard.router) runs each shard's batcher under its own
    # mesh slot. None = the process default device.
    device: Any = None
    # This service's shard index under a sharded router (labels trace
    # lanes and error context); None for a standalone service.
    shard: int | None = None
    # --- resilience (resilience.py) ---------------------------------------
    # Admission bound on outstanding (queued + in-flight) requests; submit()
    # raises Overloaded past it. None = unbounded (the pre-resilience mode).
    max_queue: int | None = 1024
    # Deadline applied to every request that doesn't pass its own
    # deadline_ms to submit_plan(); None = no deadline.
    default_deadline_ms: float | None = None
    # Retry-with-backoff then bisect for failed dispatch groups.
    retry: RetryPolicy = RetryPolicy()
    # --- tenancy + graduated overload (tenancy.py, ISSUE 9) ---------------
    # Per-tenant admission quotas and fair-share weights; tenants not in
    # the map get DEFAULT_QUOTA (unbounded, weight 1.0). None = single-
    # tenant behavior (the map only matters once submit passes tenant=).
    tenants: "dict[str, TenantQuota] | None" = None
    # Brownout ladder: widen window -> shed low priority (typed
    # BrownoutShed) -> shed all, driven by queue depth + dispatch-latency
    # EWMA. Defaults on: with the default thresholds level 3 can never
    # fire before max_queue itself, so single-tenant behavior is unchanged.
    # None disables the ladder entirely.
    brownout: BrownoutPolicy | None = BrownoutPolicy()
    # Hedged dispatch policy — read by ShardedMorphService (a lone service
    # has no second shard to hedge to), default off.
    hedge: HedgePolicy = HedgePolicy()
    # Circuit breaker / reroute rules — read by ShardedMorphService, inert
    # for a standalone service.
    failover: FailoverPolicy = FailoverPolicy()
    # Deterministic fault injection; None (default) adds zero overhead.
    faults: FaultPlan | None = None
    # Observability (repro.obs): spans and stages; None (default) adds
    # zero overhead, same contract as ``faults``.
    obs: ObsConfig | None = None


@dataclasses.dataclass
class _Request:
    key: tuple
    img: np.ndarray
    plan: Plan
    bucket: tuple[int, int] | None  # None -> tiled route
    future: Future
    t_submit: float
    deadline: float | None = None  # absolute monotonic seconds
    tag: str | None = None  # caller label; fault injection poisons by tag
    tenant: str | None = None  # tenancy: quota + fair-share identity
    priority: int = PRIORITY_NORMAL  # priority class (lower = more important)
    trace: int | None = None  # obs: request trace ID (minted at submit)
    qspan: Any = None  # obs: open queue-wait span handle


class MorphService:
    """Async morphology serving engine. Use as a context manager:

        with MorphService() as svc:
            fut = svc.submit(img, op="erode", se=(5, 5))
            clean = svc.run_plan(img2, "document_cleanup")["clean"]
    """

    def __init__(self, config: ServiceConfig | None = None):
        self.config = config or ServiceConfig()
        check_buckets(self.config.buckets)
        self.policy = self.config.policy or DispatchPolicy.calibrated()
        self.interpret = resolve_interpret(self.config.interpret, self.policy)
        if self.config.backend == "auto":
            # Compiled Mosaic -> fused megakernel; interpret mode (CPU CI,
            # laptops) -> the pure-XLA separable path, which is bit-exact
            # and far faster than interpreting Pallas.
            self.backend = "jnp" if self.interpret else "kernel"
        else:
            # fail loudly at construction, not inside the batcher thread
            self.backend = check_backend(self.config.backend)
        self.metrics = MetricsRegistry()
        self.cache = ExecutableCache(self.config.cache_size, registry=self.metrics)
        # RLE route caches: structural eligibility per plan (one graph walk)
        # and the host lowering per plan. Plain dicts — host lowerings are a
        # closure over numpy ops, not a compiled artifact worth LRU pressure.
        self._rle_eligible: dict = {}
        self._rle_exec: dict = {}
        self._stats = ServiceStats(self.config.stats_window, registry=self.metrics)
        # launch occupancy: pixels answered against pixels launched, and
        # tiles per launch on the tiled route (the dispatch thread is their
        # only writer)
        self._px_valid = self.metrics.counter("executor.pixels_valid")
        self._px_launched = self.metrics.counter("executor.pixels_launched")
        self._tiles = self.metrics.counter("tiled.tiles")
        self._tile_launches = self.metrics.counter("tiled.launches")
        faults = self.config.faults
        self._injector = (
            FaultInjector(faults) if faults is not None and faults.enabled else None
        )
        obs_cfg = self.config.obs
        shard = self.config.shard
        # the open dispatch span, while the batcher's one dispatch thread
        # executes a group (None when tracing is off)
        self._dspan = None
        self._obs = (
            Observability(
                obs_cfg,
                self.metrics,
                pid="0" if shard is None else str(shard),
                name="service" if shard is None else f"shard-{shard}",
            )
            if obs_cfg is not None and obs_cfg.enabled
            else None
        )
        self._batcher = MicroBatcher(
            self._execute_group,
            max_batch=self.config.max_batch,
            window_s=self.config.window_ms / 1e3,
            adaptive=self.config.adaptive_window,
            min_window_s=self.config.min_window_ms / 1e3,
            max_queue=self.config.max_queue,
            retry=self.config.retry,
            tenants=self.config.tenants,
            brownout=self.config.brownout,
            registry=self.metrics,
            obs=self._obs,
        )

    # ------------------------------------------------------------ submission
    def submit(self, img, op: str = "erode", se=(3, 3), **kw) -> Future:
        """Single-op request; resolves to the cropped result array."""
        return self.submit_plan(img, single_op_plan(op, se), **kw)

    def submit_plan(
        self,
        img,
        plan: "str | Plan",
        *,
        deadline_ms: float | None = None,
        tag: str | None = None,
        tenant: str | None = None,
        priority: int = PRIORITY_NORMAL,
        _trace: int | None = None,
    ) -> Future:
        """Plan request; resolves to an array (single-output plans) or a
        ``{name: array}`` dict (plans with named outputs).

        ``deadline_ms`` (default ``config.default_deadline_ms``) bounds how
        long the request may wait: expired requests fail with a typed
        :class:`DeadlineExceeded` instead of occupying the executor, and an
        urgent request pulls its whole group's dispatch forward. ``tag`` is
        a caller label carried on the request (fault injection poisons by
        tag; it never affects routing or batching). ``tenant``/``priority``
        feed admission (quotas, the brownout ladder) and weighted-fair
        dispatch order — see tenancy.py. ``_trace`` is internal: the
        sharded router threads one trace ID through failover hops."""
        plan = get_plan(plan)
        img = np.asarray(img)
        if img.ndim != 2:
            raise ValueError("the service takes single (H, W) images; submit "
                             "each image of a batch separately")
        if deadline_ms is None:
            deadline_ms = self.config.default_deadline_ms
        deadline = None
        if deadline_ms is not None:
            if deadline_ms <= 0:
                raise DeadlineExceeded(
                    f"deadline_ms={deadline_ms} already expired at submit",
                    plan=plan.name,
                )
            deadline = time.monotonic() + deadline_ms / 1e3
        # Admission (queue bound, tenant quota, brownout) is charged BEFORE
        # any routing work: the RLE density probe scans the whole image, and
        # an overloaded service must shed at the door, not after paying a
        # per-request O(H*W) probe for a request it then rejects.
        self._batcher.reserve(tenant, priority)
        try:
            if self._route_rle(img, plan):
                # content-gated representation choice: run-domain execution
                # on exact shapes — no bucket padding, no tiling
                key, bucket = ("rle", plan, img.dtype.str), None
            else:
                bucket = choose_bucket(
                    img.shape[0], img.shape[1], self.config.buckets
                )
                if bucket is None:
                    gh, gw = plan.halo()
                    ext = (self.config.tile_interior[0] + 2 * gh,
                           self.config.tile_interior[1] + 2 * gw)
                    key = ("tiled", plan, ext, img.dtype.str)
                else:
                    key = ("bucket", plan, bucket, img.dtype.str)
            req = _Request(key, img, plan, bucket, Future(), time.monotonic(),
                           deadline=deadline, tag=tag, tenant=tenant,
                           priority=priority, trace=_trace)
            if self._obs is not None:
                self._obs.request_submitted(req, plan.name, bucket,
                                            img.dtype.str)
            try:
                self._batcher.enqueue(req)
            except ServeError as exc:
                # rejected after the span opened (close() raced us): the
                # queue span must still close exactly once
                if self._obs is not None:
                    self._obs.request_failed(req, exc)
                raise
        except BaseException:
            self._batcher.release(tenant)  # slot never reached the queue
            raise
        return req.future

    def submit_expr(self, img, expr, name: str | None = None, **kw) -> Future:
        """Morphology-expression request (``repro.morph``): any graph over
        ``Var("x")`` — including ``BoundedIter`` reconstruction chains — is
        compiled into a plan and served; equal expressions share one cached
        executable. Plan compilation honors the service's policy (notably
        ``opt_level`` — a ``DispatchPolicy(opt_level=0)`` service really
        serves the raw graph)."""
        return self.submit_plan(
            img, to_plan(expr, name=name, policy=self.policy), **kw
        )

    def run(self, img, op: str = "erode", se=(3, 3), **kw):
        return self.submit(img, op, se, **kw).result()

    def run_plan(self, img, plan: "str | Plan", **kw):
        return self.submit_plan(img, plan, **kw).result()

    def run_expr(self, img, expr, name: str | None = None, **kw):
        return self.submit_expr(img, expr, name, **kw).result()

    def run_batch(self, imgs, plan: "str | Plan", **kw) -> list:
        """Synchronous convenience: submit all, wait for all, keep order."""
        futures = [self.submit_plan(im, plan, **kw) for im in imgs]
        return [f.result() for f in futures]

    # ---------------------------------------------------------- RLE routing
    def _route_rle(self, img: np.ndarray, plan: Plan) -> bool:
        """The per-request representation gate: structural eligibility
        (boolean dtype + run-domain-lowerable plan, cached per plan), then
        a measured run-density probe against the cost model's
        representation axis. Every probed request records its decision and
        density so the gate's behavior is auditable from stats()."""
        if not self.config.rle_gate or img.dtype != np.bool_:
            return False
        ok = self._rle_eligible.get(plan)
        if ok is None:
            ok = self._rle_eligible[plan] = plan_rle_eligible(plan)
        if not ok:
            return False
        density = estimate_run_density(img)
        use_rle = cost_model_for(self.policy).rle_wins(
            int(density * img.size), img.size
        )
        self._stats.record_repr(use_rle, density)
        return use_rle

    def _rle_executor(self, plan: Plan):
        key = (plan, self.policy.cache_token())
        fn = self._rle_exec.get(key)
        if fn is None:
            fn = self._rle_exec[key] = lower_rle(
                dict(plan.outputs), mode="host", policy=self.policy
            )
        return fn

    def _expire_mid_group(self, r) -> bool:
        """Serial routes (RLE, tiled) execute one request at a time, so a
        late group member's deadline can lapse while its batch-mates run —
        fail it typed instead of executing work nobody is waiting for.
        Returns True when the request was expired."""
        if r.deadline is None or r.deadline > time.monotonic():
            return False
        exc = DeadlineExceeded(
            "deadline passed mid-group before execution", plan=r.plan.name
        )
        self.metrics.counter("batcher.deadline_expired").inc()
        if self._obs is not None:
            self._obs.request_failed(r, exc)
        if not r.future.done():
            r.future.set_exception(exc)
        return True

    def _execute_rle(self, reqs: list) -> None:
        for r in reqs:
            if r.future.done():
                continue  # already served before a batch-mate failed a retry
            if self._expire_mid_group(r):
                continue
            if self._injector is not None:
                self._injector.before_dispatch([r])
            try:
                outs = self._rle_executor(r.plan)(r.img)
            except ServeError:
                raise
            except Exception as exc:
                raise ExecutorError(
                    f"rle executor failed: {type(exc).__name__}: {exc}",
                    plan=r.plan.name,
                    dtype=np.dtype(r.img.dtype).name,
                    batch=1,
                ) from exc
            names = r.plan.output_names()
            # record before resolving: a caller returning from result()
            # must observe its own request in stats()
            self._stats.record_rle([time.monotonic() - r.t_submit])
            if not r.future.done():
                r.future.set_result(outs["out"] if names == ("out",) else outs)

    # ------------------------------------------------------------- execution
    def _executor_key(self, plan: Plan, shape: tuple[int, int], dtype, batch: int):
        return (
            plan,
            shape,
            np.dtype(dtype).str,
            batch,
            self.policy.cache_token(),
            self.backend,
            self.interpret,
        )

    def _executor_for(self, plan: Plan, shape: tuple[int, int], dtype, batch: int):
        key = self._executor_key(plan, shape, dtype, batch)

        def build():
            return build_executor(
                plan,
                backend=self.backend,
                policy=self.policy,
                interpret=self.interpret,
                with_aux=True,
            )

        return self.cache.get(key, build)

    def _device_scope(self):
        if self.config.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.config.device)

    def _execute_group(self, key, reqs: list) -> None:
        obs = self._obs
        if obs is not None:
            for r in reqs:
                obs.request_dequeued(r)  # queue wait ends here (idempotent)
            span = obs.group_span(
                "dispatch", reqs, kind=key[0], plan=key[1].name,
                shard=self.config.shard,
            )
        else:
            span = _NULL
        with span as self._dspan, self._device_scope():
            try:
                if key[0] == "tiled":
                    self._execute_tiled(reqs)
                elif key[0] == "rle":
                    self._execute_rle(reqs)
                else:
                    self._execute_bucketed(key, reqs)
            finally:
                self._dspan = None

    def _stage(self, name: str, plan: str):
        """A timed stage of the open dispatch span (see
        ``Observability.stage``); nothing when obs is off."""
        obs = self._obs
        return obs.stage(self._dspan, name, plan) if obs is not None else _NULL

    def _count_device(self, outs: dict) -> None:
        """Count the dispatch under the device its outputs landed on
        (``device.<id>.dispatches``): what shows, from the metrics, that a
        pinned shard's work stays on its own chip."""
        for d in next(iter(outs.values())).devices():
            self.metrics.counter(f"device.{d.id}.dispatches").inc()

    def _record_aux(self, aux: dict) -> None:
        budget = int(aux["iters_budget"])
        if budget:
            self._stats.record_bounded(int(aux["iters_used"]), budget)
            if self._obs is not None:
                self._obs.record_bounded(int(aux["iters_used"]), budget)

    def _execute_bucketed(self, key, reqs: list) -> None:
        _, plan, bucket, _ = key
        if self._injector is not None:
            self._injector.before_dispatch(reqs)
        bb = min(_round_up_pow2(len(reqs)), self.config.max_batch)
        dtype = reqs[0].img.dtype
        if self._dspan is not None:
            self._dspan.attrs.update(bucket=bucket, dtype=np.dtype(dtype).name,
                                     batch=bb)
        valid = 0
        with self._stage("pad", plan.name):
            batch = np.zeros((bb, *bucket), dtype=dtype)
            rects = np.zeros((bb, 4), dtype=np.int32)
            for i, r in enumerate(reqs):
                h, w = r.img.shape
                batch[i, :h, :w] = r.img  # rows past len(reqs) keep an empty rect
                rects[i] = valid_rect(h, w)
                valid += h * w
        try:
            with self._stage("launch", plan.name):
                execute = self._executor_for(plan, bucket, dtype, bb)
                outs, aux = execute(jnp.asarray(batch), jnp.asarray(rects))
                self._count_device(outs)
            with self._stage("d2h", plan.name):
                # np.asarray blocks until the device is done
                outs = {k: np.asarray(v) for k, v in outs.items()}
        except ServeError:
            raise
        except Exception as exc:
            raise ExecutorError(
                f"executor failed: {type(exc).__name__}: {exc}",
                plan=plan.name,
                bucket=bucket,
                dtype=np.dtype(dtype).name,
                batch=bb,
            ) from exc
        self._px_valid.inc(valid)
        self._px_launched.inc(bb * bucket[0] * bucket[1])
        self._record_aux(aux)
        names = plan.output_names()
        # record stats before resolving futures: a caller returning from
        # result() must observe its own request in stats()
        now = time.monotonic()
        self._stats.record_batch([now - r.t_submit for r in reqs])
        for i, r in enumerate(reqs):
            h, w = r.img.shape
            cropped = {
                name: crop_from_bucket(outs[name][i], h, w) for name in names
            }
            if not r.future.done():
                r.future.set_result(
                    cropped["out"] if names == ("out",) else cropped
                )

    def _grid_executor_for(self, plan: Plan, grid: tuple[int, int], dtype):
        """The tiled route's one program per tile grid, keyed on the grid
        and the extended tile shape, never the page's exact shape."""
        gh, gw = plan.halo()
        th, tw = self.config.tile_interior
        cap = self.config.max_tiles_per_launch
        key = ("grid", plan, grid, (th + 2 * gh, tw + 2 * gw),
               np.dtype(dtype).str, cap, self.policy.cache_token(),
               self.backend, self.interpret)

        def build():
            # the bucketed route's executor, traced inside the grid program
            # (built here, not cached: the grid program is the one compile)
            execute = build_executor(
                plan,
                backend=self.backend,
                policy=self.policy,
                interpret=self.interpret,
                with_aux=True,
            )
            return build_grid_executor(plan, execute, grid, (th, tw),
                                       max_tiles_per_launch=cap)

        return self.cache.get(key, build)

    def _execute_tiled(self, reqs: list) -> None:
        for r in reqs:
            if r.future.done():
                continue  # already served before a batch-mate failed a retry
            if self._expire_mid_group(r):
                continue
            if self._injector is not None:
                self._injector.before_dispatch([r])
            h, w = r.img.shape
            ny, nx = tile_counts(h, w, self.config.tile_interior)
            gh, gw = r.plan.halo()
            ext = (self.config.tile_interior[0] + 2 * gh,
                   self.config.tile_interior[1] + 2 * gw)
            if self._dspan is not None:
                self._dspan.attrs.update(bucket=ext,
                                         dtype=np.dtype(r.img.dtype).name)

            def execute(grid, page, rects):
                fn = self._grid_executor_for(r.plan, grid, page.dtype)
                outs, aux = fn(page, rects)
                self._count_device(outs)
                return outs, aux

            try:
                outs, aux = run_grid(
                    r.img,
                    r.plan,
                    execute,
                    tile_interior=self.config.tile_interior,
                    stage=lambda name: self._stage(name, r.plan.name),
                )
            except ServeError:
                raise
            except Exception as exc:
                raise ExecutorError(
                    f"tiled executor failed: {type(exc).__name__}: {exc}",
                    plan=r.plan.name,
                    bucket=ext,
                    dtype=np.dtype(r.img.dtype).name,
                    batch=ny * nx,
                ) from exc
            self._tiles.inc(ny * nx)
            self._tile_launches.inc()
            self._px_valid.inc(h * w)
            self._px_launched.inc(ny * nx * ext[0] * ext[1])
            self._record_aux(aux)
            names = r.plan.output_names()
            # record before resolving: a caller returning from result()
            # must observe its own request in stats()
            self._stats.record_tiled([time.monotonic() - r.t_submit])
            if not r.future.done():
                r.future.set_result(outs["out"] if names == ("out",) else outs)

    # -------------------------------------------------------------- lifecycle
    def metrics_snapshot(self) -> dict:
        """Registry snapshot with the point-in-time gauges refreshed — the
        unit the sharded router merges by metric type."""
        self.metrics.gauge("window.effective_ms", mode="max").set(
            self._batcher.window_s * 1e3
        )
        return self.metrics.snapshot()

    def stats(self) -> dict:
        snap = self._stats.snapshot(self.config.max_batch)
        snap["cache"] = self.cache.snapshot()
        snap["backend"] = self.backend
        snap["interpret"] = self.interpret
        snap["window_ms"] = self.config.window_ms
        snap["effective_window_ms"] = self._batcher.window_s * 1e3
        snap["adaptive_window"] = self.config.adaptive_window
        resilience = self._batcher.counters()
        resilience["max_queue"] = self.config.max_queue
        resilience["faults"] = (
            self._injector.snapshot() if self._injector is not None else None
        )
        snap["resilience"] = resilience
        snap["obs"] = self._obs.snapshot() if self._obs is not None else None
        return snap

    def export_trace(self) -> dict | None:
        """Chrome trace-event JSON of the finished spans (Perfetto-loadable);
        None when tracing is off."""
        if self._obs is None or self._obs.tracer is None:
            return None
        return chrome_trace([self._obs.tracer])

    def flush(self, timeout: float | None = None) -> bool:
        return self._batcher.flush(timeout)

    def close(self) -> None:
        """Drain in-flight requests and stop the batcher. Idempotent: a
        second close() (or a close() racing __exit__) is a no-op join."""
        self._batcher.close()

    def __enter__(self) -> "MorphService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
