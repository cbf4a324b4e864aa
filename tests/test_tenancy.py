"""Tenant-aware overload control suite (ISSUE 9).

Drives the three new admission/ordering mechanisms plus the two satellite
fixes that ride with them:

* per-tenant quotas reject with the typed ``QuotaExceeded`` (an
  ``Overloaded`` that names the tenant) and release on completion;
* start-time fair queuing never starves a positive-weight tenant —
  asserted deterministically and as a hypothesis property with the
  analytic SFQ gap bound;
* the brownout ladder degrades in steps (widen window -> shed low
  priority typed -> shed all) with hysteresis, driven by queue depth and
  the dispatch-latency EWMA, and surfaces its level in ``stats()``;
* retry backoff is capped at the group's remaining deadline slack, so a
  retried request fails fast with ``DeadlineExceeded`` instead of
  sleeping past its deadline and dispatching anyway;
* RLE-routed requests honor admission control, quotas, and per-request
  deadlines (the ``("rle", plan, dtype)`` regression).
"""
import math
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from repro.data.images import synth_sparse_masks
from repro.serve.morph import (
    BrownoutController,
    BrownoutPolicy,
    BrownoutShed,
    DeadlineExceeded,
    FairScheduler,
    FaultPlan,
    InjectedFault,
    MicroBatcher,
    MorphService,
    Overloaded,
    PRIORITY_HIGH,
    PRIORITY_LOW,
    PRIORITY_NORMAL,
    Plan,
    QuotaExceeded,
    RetryPolicy,
    ServiceConfig,
    Step,
    TenantQuota,
)
from repro.serve.morph.tenancy import effective_weight

RNG = np.random.default_rng(23)


def rand(h=40, w=50, dtype=np.uint8):
    return RNG.integers(0, 255, (h, w), dtype=dtype)


def cfg(**kw):
    kw.setdefault("buckets", ((64, 64),))
    kw.setdefault("window_ms", 1.0)
    kw.setdefault("retry", RetryPolicy(max_retries=0, backoff_ms=0.5))
    return ServiceConfig(**kw)


class Req:
    """Raw batcher-level request double (same shape test_resilience uses,
    plus the tenancy fields)."""

    def __init__(self, key="k", deadline=None, tenant=None,
                 priority=PRIORITY_NORMAL):
        self.key = key
        self.future = Future()
        self.deadline = deadline
        self.tenant = tenant
        self.priority = priority


# ------------------------------------------------------------------- quotas
def test_tenant_quota_validates():
    with pytest.raises(ValueError):
        TenantQuota(max_outstanding=0)
    with pytest.raises(ValueError):
        TenantQuota(weight=0.0)
    with pytest.raises(ValueError):
        TenantQuota(weight=-1.0)


def test_quota_exceeded_is_typed_and_tenant_scoped():
    """A tenant at its max_outstanding sheds alone — typed, non-retryable,
    naming the tenant — while other tenants keep flowing through the same
    queue; completed requests return the slots."""
    c = cfg(window_ms=150.0, max_batch=8,
            tenants={"free": TenantQuota(max_outstanding=2)})
    img = rand()
    with MorphService(c) as svc:
        held = [svc.submit(img, tenant="free") for _ in range(2)]
        with pytest.raises(QuotaExceeded) as ei:
            svc.submit(img, tenant="free")
        assert ei.value.tenant == "free"
        assert isinstance(ei.value, Overloaded)
        assert not ei.value.retryable
        # the shared queue is nowhere near full: other tenants unaffected
        gold = svc.submit(img, tenant="gold")
        anon = svc.submit(img)
        st = svc.stats()["resilience"]
        assert st["rejected_quota"] == 1
        assert st["tenants"]["free"]["rejected_quota"] == 1
        assert st["tenants"]["free"]["outstanding"] == 2
        for f in (*held, gold, anon):
            assert f.result(timeout=60) is not None
        # completion released the quota: the tenant is admitted again
        assert svc.submit(img, tenant="free").result(timeout=60) is not None


def test_unknown_tenant_gets_default_quota():
    with MorphService(cfg(tenants={"vip": TenantQuota(weight=8.0)})) as svc:
        out = svc.run(rand(), "erode", (3, 3), tenant="stranger")
        assert out is not None


# ------------------------------------------------- weighted-fair scheduling
def _simulate(tenants, priorities, rounds):
    """All tenants permanently backlogged, one single-member group each;
    dispatch the scheduler's top pick each round. Returns the dispatch
    sequence of tenant names."""
    fs = FairScheduler(tenants)
    names = list(tenants)
    seq = []
    for _ in range(rounds):
        items = [
            (0.0, t, [(t, priorities[t])]) for t in names
        ]
        winner = fs.order(items)[0]
        fs.account([(winner, priorities[winner])])
        seq.append(winner)
    return seq


def test_fair_ordering_tracks_weights():
    tenants = {"a": TenantQuota(weight=3.0), "b": TenantQuota(weight=1.0)}
    seq = _simulate(tenants, {"a": PRIORITY_NORMAL, "b": PRIORITY_NORMAL}, 200)
    na, nb = seq.count("a"), seq.count("b")
    assert nb > 0  # never starved
    assert 2.0 <= na / nb <= 4.0  # ~3:1 share


def test_priority_folds_into_share_not_strict_tiers():
    """High priority gets a larger share (the boost), but low priority is
    still dispatched — priority must not become a starvation tier."""
    tenants = {"hi": TenantQuota(), "lo": TenantQuota()}
    seq = _simulate(tenants, {"hi": PRIORITY_HIGH, "lo": PRIORITY_LOW}, 200)
    nh, nl = seq.count("hi"), seq.count("lo")
    assert nl > 0
    assert nh > nl  # boost = 4x weight for HIGH vs LOW


def _gap_bound(weights, t):
    """SFQ liveness bound: between two dispatches of backlogged tenant t,
    every other tenant u fits at most ceil(w_u/w_t) + 1 dispatches."""
    return 1 + sum(
        math.ceil(w / weights[t]) + 1 for u, w in weights.items() if u != t
    )


def test_no_starvation_deterministic():
    tenants = {
        "whale": TenantQuota(weight=10.0),
        "mid": TenantQuota(weight=2.0),
        "min": TenantQuota(weight=0.25),
    }
    prios = {t: PRIORITY_NORMAL for t in tenants}
    seq = _simulate(tenants, prios, 400)
    weights = {
        t: effective_weight(q, PRIORITY_NORMAL) for t, q in tenants.items()
    }
    for t in tenants:
        bound = _gap_bound(weights, t)
        last = -1
        for i, name in enumerate(seq):
            if name != t:
                continue
            assert i - last <= bound, (t, i - last, bound)
            last = i
        assert last >= 0, f"{t} never dispatched"


def test_idle_tenant_reenters_at_floor_not_with_credit():
    """A tenant that sat idle while others were served cannot burst ahead:
    its tag re-enters at the floor, not at its stale virtual time."""
    fs = FairScheduler({"busy": TenantQuota(), "idle": TenantQuota()})
    for _ in range(50):
        fs.account([("busy", PRIORITY_NORMAL)])
    step = 1.0 / effective_weight(TenantQuota(), PRIORITY_NORMAL)
    assert fs.tag("idle") == pytest.approx(fs.tag("busy") - step)


def test_no_starvation_property():
    """Hypothesis: for arbitrary positive weights and priority classes,
    every backlogged tenant is dispatched within the analytic gap bound."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    weights_st = st.lists(
        st.floats(0.1, 16.0, allow_nan=False), min_size=2, max_size=5
    )
    prios_st = st.lists(st.integers(0, 2), min_size=5, max_size=5)

    @settings(max_examples=40, deadline=None)
    @given(ws=weights_st, ps=prios_st)
    def prop(ws, ps):
        tenants = {
            f"t{i}": TenantQuota(weight=w) for i, w in enumerate(ws)
        }
        prios = {f"t{i}": ps[i] for i in range(len(ws))}
        eff = {
            t: effective_weight(q, prios[t]) for t, q in tenants.items()
        }
        bounds = {t: _gap_bound(eff, t) for t in tenants}
        # enough rounds that even the lightest tenant must appear
        rounds = max(bounds.values()) + 10
        seq = _simulate(tenants, prios, rounds)
        for t in tenants:
            bound = bounds[t]
            last = -1
            for i, name in enumerate(seq):
                if name == t:
                    assert i - last <= bound
                    last = i
            assert last >= 0
            assert len(seq) - last <= bound  # still live at the end

    prop()


def test_batcher_fair_order_under_flood():
    """End-to-end through MicroBatcher: a flooding tenant cannot starve a
    light one — the light tenant's requests complete interleaved, not
    parked behind the whole flood."""
    order = []
    entered, release = threading.Event(), threading.Event()

    def execute(key, reqs):
        if key == "gate":
            entered.set()
            release.wait(timeout=30)
        for r in reqs:
            if key != "gate":
                order.append(r.tenant)
            r.future.set_result(True)

    # max_batch > group size, so no group fills. The worker is held inside
    # a first dispatch while the flood queues behind close(); the drain
    # then makes the whole flood due at once, and dispatch order is the
    # scheduler's alone, not a race between window timers
    b = MicroBatcher(execute, max_batch=4, window_s=0.05,
                     tenants={"whale": TenantQuota(weight=1.0),
                              "shrimp": TenantQuota(weight=1.0)},
                     retry=RetryPolicy(max_retries=0))
    closer = threading.Thread(target=b.close)
    try:
        b.submit(Req(key="gate"))
        assert entered.wait(timeout=30)
        reqs = []
        # distinct keys -> one group per request
        for i in range(20):
            reqs.append(Req(key=f"w{i}", tenant="whale"))
        for i in range(4):
            reqs.append(Req(key=f"s{i}", tenant="shrimp"))
        for r in reqs:
            b.submit(r)
        closer.start()
        deadline = time.monotonic() + 30
        while not b._closed and time.monotonic() < deadline:
            time.sleep(0.001)
        release.set()
        closer.join(timeout=30)
        for r in reqs:
            assert r.future.result(timeout=30)
    finally:
        release.set()
        b.close()
    # equal weights: shrimp's 4 must all land within the first ~half of the
    # dispatch order, not after the 20-deep whale flood
    last_shrimp = max(i for i, t in enumerate(order) if t == "shrimp")
    assert last_shrimp < 16, order


# ----------------------------------------------------------- brownout ladder
def test_brownout_policy_validates():
    with pytest.raises(ValueError):
        BrownoutPolicy(enter_widen=0.8, enter_shed=0.5)
    with pytest.raises(ValueError):
        BrownoutPolicy(hysteresis=-0.1)


def test_brownout_ladder_levels_and_hysteresis():
    p = BrownoutPolicy(enter_widen=0.5, enter_shed=0.75, enter_global=0.95,
                       hysteresis=0.10)
    c = BrownoutController(p, max_queue=100)
    assert c.update(49) == 0 and c.window_multiplier() == 1.0
    assert c.update(50) == 1 and c.window_multiplier() == p.window_widen
    assert not c.sheds(PRIORITY_LOW)
    assert c.update(75) == 2
    assert c.sheds(PRIORITY_LOW) and not c.sheds(PRIORITY_NORMAL)
    assert c.update(95) == 3
    assert c.sheds(PRIORITY_HIGH)  # level 3 sheds everything
    # hysteresis: level 3 holds until below enter_global - hysteresis
    assert c.update(86) == 3
    assert c.update(84) == 2
    # and level 1 holds at 41 (exit 0.40) but releases at 39
    assert c.update(41) == 1
    assert c.update(39) == 0
    assert c.transitions >= 5


def test_brownout_latency_ewma_escalates_one_level():
    p = BrownoutPolicy(latency_ms=10.0, latency_alpha=1.0)
    c = BrownoutController(p, max_queue=100)
    assert c.update(10) == 0
    c.observe_latency(50.0)
    assert c.update(10) == 1  # queue says 0, latency says worse
    assert c.snapshot()["latency_ewma_ms"] == 50.0
    c.observe_latency(1.0)
    assert c.update(10) == 0


def test_brownout_sheds_low_priority_typed():
    """With the worker pinned, queue depth climbs into level 2: low
    priority sheds with BrownoutShed while normal priority is admitted
    until the global bound, and stats() reports the active level."""
    import threading

    release = threading.Event()

    def execute(key, reqs):
        release.wait(30)
        for r in reqs:
            r.future.set_result(True)

    b = MicroBatcher(
        execute, max_batch=1, window_s=0.0, max_queue=10,
        brownout=BrownoutPolicy(enter_widen=0.15, enter_shed=0.3,
                                enter_global=0.9, hysteresis=0.05),
        retry=RetryPolicy(max_retries=0),
    )
    try:
        reqs = [Req(key=f"k{i}") for i in range(4)]
        for r in reqs:
            b.submit(r)  # outstanding climbs to 4 (>= 0.3 * 10)
        with pytest.raises(BrownoutShed) as ei:
            b.submit(Req(key="low", priority=PRIORITY_LOW))
        assert ei.value.level >= 2
        assert ei.value.priority == PRIORITY_LOW
        assert isinstance(ei.value, Overloaded)
        ok = Req(key="norm", priority=PRIORITY_NORMAL)
        b.submit(ok)  # normal class still admitted at level 2
        counters = b.counters()
        assert counters["shed_brownout"] == 1
        assert counters["brownout"]["level"] >= 2
        release.set()
        for r in reqs:
            assert r.future.result(timeout=30)
        assert ok.future.result(timeout=30)
    finally:
        release.set()
        b.close()


def test_brownout_service_integration_levels_in_stats():
    c = cfg(max_queue=10, window_ms=200.0, max_batch=1,
            brownout=BrownoutPolicy(enter_widen=0.15, enter_shed=0.3,
                                    enter_global=0.9, hysteresis=0.05),
            faults=FaultPlan(latency_ms=40.0))
    img = rand()
    with MorphService(c) as svc:
        accepted = [svc.submit(img) for _ in range(4)]
        with pytest.raises(BrownoutShed):
            svc.submit(img, priority=PRIORITY_LOW)
        st = svc.stats()["resilience"]
        assert st["brownout"]["level"] >= 2
        assert st["shed_brownout"] == 1
        for f in accepted:
            assert f.result(timeout=60) is not None


def test_default_brownout_cannot_preempt_max_queue_cliff():
    """The default ladder thresholds must leave single-tenant behavior
    untouched: everything rejected under default config is plain
    Overloaded at the max_queue cliff, not a BrownoutShed."""
    p = BrownoutPolicy()  # defaults: enter_global=0.95
    c = BrownoutController(p, max_queue=4)
    # no integer outstanding below max_queue=4 reaches frac 0.95
    for n in range(4):
        c.update(n)
        assert not c.sheds(PRIORITY_NORMAL)


# ------------------------------------- satellite: backoff capped by deadline
def test_retry_backoff_capped_at_deadline_slack():
    """A retried group whose backoff would sleep past the deadline fails
    fast with DeadlineExceeded instead — and well before the configured
    backoff elapses."""
    calls = []

    def execute(key, reqs):
        calls.append(time.monotonic())
        raise InjectedFault("flaky")

    b = MicroBatcher(
        execute, max_batch=4, window_s=0.0,
        retry=RetryPolicy(max_retries=3, backoff_ms=1000.0,
                          backoff_cap_ms=1000.0, bisect=False),
    )
    try:
        t0 = time.monotonic()
        r = Req(deadline=t0 + 0.08)
        b.submit(r)
        with pytest.raises(DeadlineExceeded):
            r.future.result(timeout=30)
        elapsed = time.monotonic() - t0
        # uncapped: first backoff alone is 1s; capped: ~80ms of slack
        assert elapsed < 0.8, elapsed
        assert len(calls) == 1  # never re-dispatched past the deadline
        assert b.counters()["deadline_expired"] == 1
    finally:
        b.close()


def test_retry_redrops_expired_members_before_sleeping():
    """Mixed group: the member with slack survives the retry, the expired
    member fails typed — the retry never rides an already-dead request."""
    attempts = []

    def execute(key, reqs):
        attempts.append([r.name for r in reqs])
        if len(attempts) == 1:
            raise InjectedFault("first dispatch dies")
        for r in reqs:
            r.future.set_result(True)

    b = MicroBatcher(
        execute, max_batch=4, window_s=0.0,
        retry=RetryPolicy(max_retries=2, backoff_ms=60.0,
                          backoff_cap_ms=60.0, bisect=False),
    )
    try:
        now = time.monotonic()
        short = Req(deadline=now + 0.03)
        long_ = Req(deadline=now + 30.0)
        short.name, long_.name = "short", "long"
        short.key = long_.key = "same-group"
        b.submit(short)
        b.submit(long_)
        assert long_.future.result(timeout=30)
        with pytest.raises(DeadlineExceeded):
            short.future.result(timeout=30)
        # the retry dispatched only the live member
        assert attempts[-1] == ["long"]
    finally:
        b.close()


# ------------------------------------ satellite: RLE route admission (S1)
RLE_PLAN = Plan("mask_open_t", (Step("opening", (3, 3)),))


def sparse_mask(seed=0):
    return synth_sparse_masks(1, 128, 128, run_density=0.005, seed=seed)[0]


def test_rle_route_honors_max_queue():
    """RLE-routed requests bypass bucketing, not admission: past max_queue
    they shed typed — and the rejected request never reaches the density
    probe (no repr decision is recorded for it)."""
    c = cfg(max_queue=1, window_ms=300.0)
    with MorphService(c) as svc:
        first = svc.submit_plan(sparse_mask(0), RLE_PLAN)
        with pytest.raises(Overloaded):
            svc.submit_plan(sparse_mask(1), RLE_PLAN)
        st = svc.stats()
        # admission rejected BEFORE the probe: one decision recorded, not two
        assert st["repr"]["rle"] + st["repr"]["dense"] == 1
        assert st["resilience"]["rejected_overloaded"] == 1
        assert first.result(timeout=60) is not None


def test_rle_route_honors_tenant_quota():
    c = cfg(window_ms=300.0,
            tenants={"free": TenantQuota(max_outstanding=1)})
    with MorphService(c) as svc:
        first = svc.submit_plan(sparse_mask(0), RLE_PLAN, tenant="free")
        with pytest.raises(QuotaExceeded):
            svc.submit_plan(sparse_mask(1), RLE_PLAN, tenant="free")
        assert first.result(timeout=60) is not None
    assert isinstance(first.result(), np.ndarray)


def test_rle_route_honors_mid_group_deadline():
    """Serial RLE execution: a group member whose deadline lapses while an
    earlier member runs fails typed instead of executing anyway."""
    c = cfg(window_ms=40.0, faults=FaultPlan(latency_ms=120.0))
    with MorphService(c) as svc:
        r1 = svc.submit_plan(sparse_mask(0), RLE_PLAN)
        r2 = svc.submit_plan(sparse_mask(1), RLE_PLAN, deadline_ms=60.0)
        assert r1.result(timeout=60) is not None
        with pytest.raises(DeadlineExceeded):
            r2.result(timeout=60)
        assert svc.stats()["resilience"]["deadline_expired"] >= 1


def test_rle_route_respects_fair_order_fields():
    """tenant/priority ride the RLE group key path end to end (smoke: the
    per-tenant dispatch counters tick for RLE-routed work)."""
    c = cfg(window_ms=1.0)
    with MorphService(c) as svc:
        out = svc.run_plan(sparse_mask(0), RLE_PLAN, tenant="gold",
                           priority=PRIORITY_HIGH)
        assert out is not None
        st = svc.stats()
        assert st["rle_requests"] == 1
        assert st["resilience"]["tenants"]["gold"]["dispatched"] == 1
