"""Front-tier ingress benchmark: QPS/SLO load against a live worker fleet.

Spawns 2 (``--smoke``) or 4 real worker *processes* (``spawn_worker``: the
same ``python -m repro.serve.ingress.worker`` entry point production would
run), routes through an in-process :class:`Frontier`, and measures the
ingress tier end to end:

* **bit_exact** — for every plan in the mix, the remote result is compared
  bit-for-bit against a direct in-process ``MorphService`` (the acceptance
  gate: the wire adds a process boundary, not a numerics boundary);
* **qps_slo** — an open-loop, paced multi-tenant load generator: tenant
  "gold" (PRIORITY_HIGH) and "free" (PRIORITY_LOW) interleave at a fixed
  offered QPS (calibrated to ~60% of measured healthy throughput, so the
  numbers mean sustained service, not queue growth). Reports sustained QPS
  and per-class p99 against SLOs set at 1.5x the healthy calibration p99
  (floored at 25 ms), the same bar the resilience bench uses;
* **typed_errors** — deadline misses, per-tenant quota floods, and a
  drain-then-reject worker shutdown each come back as the *same* typed
  exception a local caller gets (``DeadlineExceeded``, ``QuotaExceeded``
  with its ``.tenant``, ``ServiceClosed``), reconstructed client-side from
  the wire;
* **worker_kill** — SIGKILL the hash-owner worker with a burst in flight:
  every future must resolve with the bit-exact result via survivors (zero
  lost futures), the fleet ``stats()`` must still merge, and the exported
  cross-process Chrome trace must validate with zero open spans.

Emits ``benchmarks/results/BENCH_router.json`` (rendered by report.py) and
the merged multi-process trace next to it.

    REPRO_PALLAS_INTERPRET=1 PYTHONPATH=src \\
        python -m benchmarks.bench_router [--smoke]
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import time
import zlib

import jax
import numpy as np

from benchmarks.common import p99_ms
from repro import core
from repro.obs import ObsConfig, validate_chrome_trace
from repro.serve.ingress import Connection, Frontier, spawn_worker
from repro.serve.morph import (
    DeadlineExceeded,
    FailoverPolicy,
    MorphService,
    PRIORITY_HIGH,
    PRIORITY_LOW,
    QuotaExceeded,
    ServeError,
    ServiceClosed,
    ServiceConfig,
    single_op_plan,
)

RESULTS = os.path.join(
    os.path.dirname(__file__), "results", "BENCH_router.json"
)
TRACE_OUT = os.path.join(
    os.path.dirname(__file__), "results", "BENCH_router_trace.json"
)

OPS = ("erode", "dilate", "opening", "closing", "gradient")
SE = (3, 3)
BUCKET = (64, 64)
PLANS = {op: single_op_plan(op, SE) for op in OPS}
REF = {op: getattr(core, op) for op in OPS}


def synth_requests(n: int, seed: int):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256,
                     (40 + 8 * int(rng.integers(0, 4)),
                      48 + 8 * int(rng.integers(0, 3))),
                     dtype=np.uint8)
        for _ in range(n)
    ]


def owner(plan_name: str, n: int) -> int:
    token = f"{plan_name}|{BUCKET}|{np.dtype(np.uint8).str}".encode()
    return zlib.crc32(token) % n


def busiest_owner(n: int) -> int:
    """The worker owning the most plan groups — killing it guarantees the
    chaos sits in the traffic path."""
    counts = collections.Counter(owner(PLANS[op].name, n) for op in OPS)
    return counts.most_common(1)[0][0]


def worker_config(smoke: bool) -> dict:
    return {
        "buckets": [list(BUCKET)],
        "window_ms": 2.0,
        "max_batch": 16,
        "obs": True,
        "interpret": bool(smoke),
        # gold/free are weighted classes for the QPS phase; quota_probe is
        # a deliberately tiny budget the typed-errors phase floods
        "tenants": {
            "gold": {"weight": 4.0},
            "free": {"weight": 1.0},
            "quota_probe": {"max_outstanding": 2},
        },
    }


def submit_timed(front, im, plan, sink, ref, **kw):
    t0 = time.perf_counter()
    fut = front.submit_plan(im, plan, **kw)

    def done(f, t0=t0, ref=ref):
        sink.append((time.perf_counter() - t0, f, ref))

    fut.add_done_callback(done)
    return fut


# ------------------------------------------------------------------ phases
def phase_bit_exact(front, imgs) -> dict:
    """Every plan in the mix: remote-through-the-fleet vs direct."""
    with MorphService(ServiceConfig(buckets=(BUCKET,))) as direct:
        checked = 0
        for op in OPS:
            for im in imgs:
                remote = np.asarray(front.run_plan(im, PLANS[op]))
                local = np.asarray(direct.run_plan(im, PLANS[op]))
                np.testing.assert_array_equal(remote, local)
                ref = np.asarray(REF[op](im, SE))
                np.testing.assert_array_equal(remote, ref)
                checked += 1
    print(f"bit_exact          {checked} remote results == direct == kernel "
          f"reference, {len(OPS)} plans")
    return {"plans": list(OPS), "checked": checked, "mismatches": 0}


def phase_qps_slo(front, imgs, *, n_requests: int) -> dict:
    """Open-loop paced load, two tenant classes interleaved 1:1."""
    # calibration: unpaced burst measures healthy capacity and p99
    calib: list = []
    t0 = time.perf_counter()
    futs = [
        submit_timed(front, im, PLANS[OPS[i % len(OPS)]], calib,
                     np.asarray(REF[OPS[i % len(OPS)]](im, SE)))
        for i, im in enumerate(imgs)
    ]
    for f in futs:
        f.result(timeout=300)
    healthy_img_s = len(imgs) / (time.perf_counter() - t0)
    healthy_p99 = p99_ms([lat for lat, _, _ in calib])
    slo_gold = max(1.5 * healthy_p99, 25.0)
    slo = {"gold": slo_gold, "free": 2.0 * slo_gold}
    classes = {"gold": PRIORITY_HIGH, "free": PRIORITY_LOW}

    qps = max(20.0, min(0.6 * healthy_img_s, 1000.0))
    per = {t: [] for t in classes}
    stream = []
    for i in range(n_requests):
        im = imgs[i % len(imgs)]
        op = OPS[i % len(OPS)]
        tenant = "gold" if i % 2 == 0 else "free"
        stream.append((im, op, tenant))
    t_start = time.perf_counter()
    futs = []
    for i, (im, op, tenant) in enumerate(stream):
        target = t_start + i / qps
        now = time.perf_counter()
        if target > now:
            time.sleep(target - now)
        futs.append(submit_timed(
            front, im, PLANS[op], per[tenant], np.asarray(REF[op](im, SE)),
            tenant=tenant, priority=classes[tenant],
        ))
    completed = shed = 0
    for f in futs:
        try:
            f.result(timeout=300)
            completed += 1
        except ServeError:
            shed += 1  # typed, never hung
    wall = time.perf_counter() - t_start
    assert all(f.done() for f in futs), "hung futures in the load phase"
    assert completed + shed == n_requests, "lost futures in the load phase"
    rows = {}
    for tenant, sink in per.items():
        lats, ok = [], 0
        for lat, f, ref in sink:
            if f.exception() is None:
                np.testing.assert_array_equal(np.asarray(f.result()), ref)
                lats.append(lat)
                if lat * 1e3 <= slo[tenant]:
                    ok += 1
        rows[tenant] = {
            "priority": classes[tenant],
            "submitted": len(sink),
            "completed": len(lats),
            "p99_ms": round(p99_ms(lats), 2) if lats else None,
            "slo_ms": round(slo[tenant], 2),
            "slo_attained": round(ok / len(sink), 3) if sink else None,
        }
        print(f"tenant {tenant:5s}       p99={rows[tenant]['p99_ms']} ms  "
              f"slo<={rows[tenant]['slo_ms']} ms  "
              f"attained={rows[tenant]['slo_attained']}")
    out = {
        "healthy_img_s": round(healthy_img_s, 2),
        "healthy_p99_ms": round(healthy_p99, 2),
        "offered_qps": round(qps, 2),
        "sustained_qps": round(completed / wall, 2),
        "requests": n_requests,
        "completed": completed,
        "shed_typed": shed,
        "classes": rows,
    }
    print(f"qps_slo            offered={out['offered_qps']}/s  "
          f"sustained={out['sustained_qps']}/s  "
          f"completed={completed}/{n_requests}")
    return out


def phase_typed_errors(front, addrs, imgs, smoke: bool) -> dict:
    """Every rejection crosses the wire as the same typed exception."""
    out: dict = {}
    # deadline: straight to a worker with an already-expired deadline —
    # the worker raises at submit, the client reconstructs from the frame
    with Connection(addrs[0]) as conn:
        try:
            conn.submit_plan(imgs[0], PLANS["erode"], deadline_ms=0).result(60)
            raise AssertionError("expired deadline did not fail")
        except DeadlineExceeded:
            out["deadline"] = {"typed": True}
    # quota: flood the 2-slot quota_probe tenant through the frontier; the
    # worker sheds typed and the frontier propagates, .tenant intact
    futs = [
        front.submit_plan(imgs[i % len(imgs)], PLANS["erode"],
                          tenant="quota_probe")
        for i in range(24)
    ]
    quota_hits = completed = 0
    for f in futs:
        try:
            f.result(timeout=300)
            completed += 1
        except QuotaExceeded as exc:
            assert exc.tenant == "quota_probe", exc.tenant
            quota_hits += 1
    assert quota_hits >= 1, "quota flood never tripped QuotaExceeded"
    out["quota"] = {"typed": quota_hits, "completed": completed,
                    "tenant": "quota_probe"}
    # drain-then-reject: a dedicated slow worker is told to shut down with
    # requests in flight — accepted work drains to results, late work gets
    # ServiceClosed over the wire, and nothing sees a dropped connection
    cfgd = dict(worker_config(smoke))
    cfgd["faults"] = {"latency_ms": 150.0}
    cfgd.pop("interpret")  # resolved for the CPU backend below
    # drain semantics need no chip, and every chip already has its worker
    proc, addr = spawn_worker(cfgd, worker_id=9, env={"JAX_PLATFORMS": "cpu"})
    closed_hits = late_results = 0
    try:
        with Connection(addr) as conn:
            held = [conn.submit_plan(im, PLANS["erode"]) for im in imgs[:4]]
            conn.rpc("shutdown")
            deadline = time.monotonic() + 30
            while closed_hits == 0 and time.monotonic() < deadline:
                try:
                    conn.submit_plan(imgs[0], PLANS["erode"]).result(60)
                    late_results += 1  # raced the closing flag; accepted
                except ServiceClosed:
                    closed_hits += 1
            for f in held:  # accepted-before-drain work always completes
                assert isinstance(np.asarray(f.result(60)), np.ndarray)
    finally:
        proc.wait(timeout=60)
    assert closed_hits >= 1, "shutdown never surfaced typed ServiceClosed"
    out["service_closed"] = {"typed": closed_hits,
                             "raced_accepted": late_results,
                             "drained": len(imgs[:4])}
    print(f"typed_errors       DeadlineExceeded=1  "
          f"QuotaExceeded={quota_hits}  ServiceClosed={closed_hits} "
          f"(all reconstructed client-side)")
    return out


def phase_worker_kill(front, procs, n_workers: int, imgs) -> dict:
    """SIGKILL the busiest owner with a burst in flight; zero lost
    futures, bit-exact reroutes, merged stats, schema-valid trace."""
    victim = busiest_owner(n_workers)
    sink: list = []
    futs = []
    for i, im in enumerate(imgs):
        op = OPS[i % len(OPS)]
        futs.append(submit_timed(front, im, PLANS[op], sink,
                                 np.asarray(REF[op](im, SE))))
    procs[victim].kill()
    completed = 0
    for f in futs:
        f.result(timeout=300)  # any raise here is a lost/failed future
        completed += 1
    for _, f, ref in sink:
        np.testing.assert_array_equal(np.asarray(f.result()), ref)
    assert completed == len(imgs), "futures lost during worker kill"
    stats = front.stats()
    assert stats["healthy_workers"] == n_workers - 1, stats["health"]
    assert stats["per_worker"][victim] is None
    assert sum(1 for p in stats["per_worker"] if p) == n_workers - 1
    doc = front.export_trace()
    errors = validate_chrome_trace(doc)
    pids = sorted({e.get("pid") for e in doc["traceEvents"]})
    open_spans = front.open_spans()
    os.makedirs(os.path.dirname(TRACE_OUT), exist_ok=True)
    with open(TRACE_OUT, "w") as f:
        json.dump(doc, f)
    out = {
        "victim": victim,
        "requests": len(imgs),
        "completed": completed,
        "healthy_workers": stats["healthy_workers"],
        "fleet_requests": stats["requests"],
        "fleet_p99_ms": round(stats["p99_ms"], 2),
        "reroutes": stats["reroutes"],
        "trace_events": len(doc["traceEvents"]),
        "trace_pids": pids,
        "trace_validation_errors": len(errors),
        "open_spans": open_spans,
        "trace_file": os.path.relpath(TRACE_OUT),
    }
    print(f"worker_kill        victim={victim}  completed={completed}/"
          f"{len(imgs)}  healthy={out['healthy_workers']}/{n_workers}  "
          f"trace: {out['trace_events']} events over pids {pids}, "
          f"{len(errors)} schema errors, {open_spans} open spans")
    return out


# -------------------------------------------------------------------- driver
def run(smoke: bool = False) -> dict:
    # the workers hold the chips, one each (spawn_worker pins them); this
    # process computes every reference on the CPU and never takes one
    jax.config.update("jax_platforms", "cpu")
    n_workers = 2 if smoke else 4
    n_bitexact = 4 if smoke else 12
    n_calib = 40 if smoke else 120
    n_load = 160 if smoke else 600
    n_kill = 48 if smoke else 96

    procs, addrs = [], []
    try:
        for i in range(n_workers):
            proc, addr = spawn_worker(worker_config(smoke), worker_id=i)
            procs.append(proc)
            addrs.append(addr)
        with Frontier(addrs, buckets=(BUCKET,), obs=ObsConfig(),
                      failover=FailoverPolicy(probe_interval_s=600.0)
                      ) as front:
            out = {
                "workers": n_workers,
                "smoke": smoke,
                "bucket": list(BUCKET),
                "bit_exact": phase_bit_exact(
                    front, synth_requests(n_bitexact, seed=3)),
                "qps_slo": phase_qps_slo(
                    front, synth_requests(n_calib, seed=5),
                    n_requests=n_load),
                "typed_errors": phase_typed_errors(
                    front, addrs, synth_requests(8, seed=7), smoke),
                "worker_kill": phase_worker_kill(
                    front, procs, n_workers, synth_requests(n_kill, seed=9)),
            }
    finally:
        for p in procs:
            p.kill()
            p.wait(timeout=60)
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {RESULTS}")
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--smoke", action="store_true",
                   help="2 workers, short streams, interpret kernels (CI)")
    out = run(smoke=p.parse_args().smoke)
    ok = True
    if out["bit_exact"]["mismatches"]:
        ok = False
        print("FAIL: remote results diverged from direct service")
    q = out["qps_slo"]
    gold = q["classes"]["gold"]
    if gold["p99_ms"] is None or gold["p99_ms"] > gold["slo_ms"]:
        ok = False
        print(f"FAIL: gold p99 {gold['p99_ms']} ms exceeds its SLO "
              f"{gold['slo_ms']} ms")
    free = q["classes"]["free"]
    if free["p99_ms"] is not None and free["p99_ms"] > free["slo_ms"]:
        print(f"WARNING: free p99 {free['p99_ms']} ms exceeds its SLO "
              f"{free['slo_ms']} ms")
    if q["sustained_qps"] < 0.8 * q["offered_qps"]:
        ok = False
        print(f"FAIL: sustained {q['sustained_qps']}/s fell below 80% of "
              f"offered {q['offered_qps']}/s")
    te = out["typed_errors"]
    if not (te["deadline"]["typed"] and te["quota"]["typed"]
            and te["service_closed"]["typed"]):
        ok = False
        print("FAIL: a rejection class did not reconstruct typed")
    k = out["worker_kill"]
    if k["completed"] != k["requests"]:
        ok = False
        print(f"FAIL: {k['requests'] - k['completed']} futures lost in the "
              f"worker-kill reroute")
    if k["trace_validation_errors"] or k["open_spans"]:
        ok = False
        print(f"FAIL: fleet trace invalid ({k['trace_validation_errors']} "
              f"schema errors, {k['open_spans']} open spans)")
    if len(k["trace_pids"]) < 2:
        ok = False
        print(f"FAIL: trace does not span processes (pids {k['trace_pids']})")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
