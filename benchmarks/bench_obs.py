"""Observability overhead benchmark + chaos trace validation (ISSUE 7).

Two claims to hold the obs subsystem to:

* **Disabled is free.** ``ServiceConfig(obs=None)`` (the default) must run
  the BENCH_serve traffic mix at the same img/s as before the subsystem
  existed — every hook site is one ``is None`` check. Measured as an A/A
  ratio between two disabled passes (the noise floor) reported next to it.
* **Enabled is cheap.** ``obs=ObsConfig()`` (spans and their stages)
  must cost <= ~5% on the same mix — spans are two ``perf_counter`` calls
  and a deque append per pipeline stage.

Plus the acceptance scenario: a chaos replay (one shard's dispatches
failing, one poison request, on logical shards) with obs enabled must
export Chrome trace-event JSON that passes ``validate_chrome_trace``,
contains the full resilience span vocabulary (queue / dispatch / retry /
hop / failover), and closes every span exactly once.

Emits ``benchmarks/results/BENCH_obs.json`` and the chaos trace itself as
``benchmarks/results/trace_obs_chaos.json`` (drop it into ui.perfetto.dev).

    PYTHONPATH=src python -m benchmarks.bench_obs [--quick|--smoke]

``--smoke`` is the CI gate: quick sizes, and a nonzero exit if the disabled
path regresses past the noise gate or the chaos trace fails validation.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import zlib

import jax
import numpy as np

from benchmarks.bench_serve import synth_requests
from benchmarks.common import latency_summary
from repro.obs import ObsConfig, validate_chrome_trace
from repro.serve.morph import MorphService, ServiceConfig
from repro.serve.morph.plans import single_op_plan
from repro.serve.morph.resilience import FaultPlan, RetryPolicy, ServeError
from repro.shard import ShardedMorphService

RESULTS = os.path.join(os.path.dirname(__file__), "results", "BENCH_obs.json")
TRACE_OUT = os.path.join(
    os.path.dirname(__file__), "results", "trace_obs_chaos.json"
)

# The chaos span vocabulary the exported trace must contain (the router
# adds "hop"/"failover"; the batcher adds "retry"; "bisect" appears only
# when a poison hides inside a multi-request group).
REQUIRED_CHAOS_SPANS = {
    "queue", "dispatch", "retry", "hop", "failover",
}


# --------------------------------------------------------------- overhead
def _serve_pass(
    streams, bucket, max_batch: int, obs: ObsConfig | None
) -> tuple[float, dict]:
    """One BENCH_serve-style serving pass; returns (img/s, latency summary)."""
    cfg = ServiceConfig(
        buckets=(bucket,), max_batch=max_batch, window_ms=2.0, obs=obs
    )
    n = sum(len(s) for s in streams)
    with MorphService(cfg) as svc:
        svc.run_batch(streams[0], "document_cleanup")  # warm the cache
        latencies: list[float] = []
        t0 = time.perf_counter()
        for imgs in streams:
            pairs = [
                (time.perf_counter(), svc.submit_plan(img, "document_cleanup"))
                for img in imgs
            ]
            for t_sub, f in pairs:
                f.result()
                latencies.append(time.perf_counter() - t_sub)
        wall = time.perf_counter() - t0
    return n / wall, latency_summary(latencies)


def bench_overhead(quick: bool = False, repeats: int = 3) -> list[dict]:
    h, w = (64, 96) if quick else (160, 224)
    bucket = (64, 128) if quick else (192, 256)
    levels = (8,) if quick else (8, 64)
    rounds = 2 if quick else 3
    rows = []
    for n in levels:
        streams = [
            synth_requests(n, h, w, jitter=16, seed=1000 * n + r)
            for r in range(rounds)
        ]
        modes = {
            "off_a": None,
            "off_b": None,  # A/A: the noise floor the "free" claim is read against
            "on": ObsConfig(),
        }
        best: dict[str, tuple[float, dict]] = {}
        for _ in range(repeats):
            for name, obs in modes.items():
                ips, lat = _serve_pass(streams, bucket, min(64, n), obs)
                if name not in best or ips > best[name][0]:
                    best[name] = (ips, lat)
        off_ips = max(best["off_a"][0], best["off_b"][0])
        on_ips = best["on"][0]
        row = {
            "concurrency": n,
            "rounds": rounds,
            "repeats": repeats,
            "off_img_s": round(off_ips, 2),
            "on_img_s": round(on_ips, 2),
            # disabled-path A/A ratio: ~1.0 up to measurement noise
            "disabled_aa_ratio": round(
                best["off_a"][0] / best["off_b"][0], 4
            ) if best["off_b"][0] else None,
            # enabled overhead: how much slower spans and stages make it
            "enabled_overhead": round(off_ips / on_ips, 4) if on_ips else None,
            "off_p99_ms": round(best["off_a"][1]["p99_ms"], 2),
            "on_p99_ms": round(best["on"][1]["p99_ms"], 2),
        }
        rows.append(row)
        print(
            f"concurrency={n:3d}  off={off_ips:8.1f} img/s  "
            f"on={on_ips:8.1f} img/s  A/A={row['disabled_aa_ratio']}  "
            f"enabled={row['enabled_overhead']}x"
        )
    return rows


# ------------------------------------------------------------ chaos trace
def bench_chaos_trace(n_shards: int = 4) -> dict:
    """The acceptance scenario: one shard's dispatches fail (breaker trips,
    traffic fails over), one request is poisoned (fails alone, typed), obs
    on — then the exported trace must validate and balance."""
    plan = single_op_plan("erode", (3, 3))
    bucket = (64, 64)
    primary = zlib.crc32(
        f"{plan.name}|{bucket}|{np.dtype(np.uint8).str}".encode()
    ) % n_shards
    cfg = ServiceConfig(
        buckets=(bucket,),
        window_ms=0.0,
        max_batch=8,
        retry=RetryPolicy(max_retries=1, backoff_ms=0.5, backoff_cap_ms=2.0),
        faults=FaultPlan(
            fail_shard=primary, fail_after=0, fail_for=None,
            poison_tags=frozenset({"poison"}),
        ),
        obs=ObsConfig(),
    )
    rng = np.random.default_rng(7)
    imgs = [
        rng.integers(0, 256, (64, 64), dtype=np.uint8) for _ in range(24)
    ]
    devices = [jax.devices()[0]] * n_shards  # logical shards; CPU-safe
    completed = failed = 0
    with ShardedMorphService(cfg, devices=devices) as svc:
        futs = [
            svc.submit_plan(img, plan, tag="poison" if i == 5 else None)
            for i, img in enumerate(imgs)
        ]
        for f in futs:
            try:
                f.result(timeout=120)
                completed += 1
            except ServeError:
                failed += 1
        svc.flush(30)
        stats = svc.stats()
        doc = svc.export_trace()
        open_spans = svc._obs.tracer.open_count() + sum(
            s._obs.tracer.open_count() for s in svc.shards
        )
    errors = validate_chrome_trace(doc)
    names = {e["name"] for e in doc["traceEvents"]}
    missing = sorted(REQUIRED_CHAOS_SPANS - names)
    os.makedirs(os.path.dirname(TRACE_OUT), exist_ok=True)
    with open(TRACE_OUT, "w") as f:
        json.dump(doc, f)
    summary = {
        "shards": n_shards,
        "requests": len(imgs),
        "completed": completed,
        "failed_typed": failed,
        "events": len(doc["traceEvents"]),
        "span_names": sorted(names - {"process_name"}),
        "missing_spans": missing,
        "open_spans": open_spans,
        "validation_errors": len(errors),
        "failovers": stats["resilience"]["failovers"],
        "retries": stats["resilience"]["retries"],
        "trace_file": os.path.relpath(TRACE_OUT, os.path.dirname(__file__)),
    }
    print(
        f"chaos trace: {summary['events']} events, spans={summary['span_names']}, "
        f"open={open_spans}, validation_errors={len(errors)}"
    )
    if errors:
        for e in errors[:5]:
            print("  validation:", e)
    return summary


def run(quick: bool = False) -> dict:
    overhead = bench_overhead(quick=quick, repeats=2 if quick else 3)
    chaos = bench_chaos_trace()
    out = {"overhead": overhead, "chaos_trace": chaos}
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {RESULTS}")
    return out


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--quick", action="store_true", help="small sizes")
    p.add_argument("--smoke", action="store_true",
                   help="CI gate: quick sizes + hard asserts on the chaos "
                        "trace and the disabled path")
    args = p.parse_args()
    out = run(quick=args.quick or args.smoke)
    chaos = out["chaos_trace"]
    worst_enabled = max(
        (r["enabled_overhead"] or 0.0) for r in out["overhead"]
    )
    if worst_enabled > 1.05:
        print(f"WARNING: enabled-obs overhead {worst_enabled}x above the 1.05x bar")
    if args.smoke:
        # hard gates (loose enough for noisy CI hosts; the trace checks are
        # exact): the chaos trace must validate, balance, and cover the
        # resilience vocabulary; the disabled path must stay near the A/A
        # noise floor.
        ok = (
            chaos["validation_errors"] == 0
            and chaos["open_spans"] == 0
            and not chaos["missing_spans"]
            and all(
                r["disabled_aa_ratio"] is not None
                and 0.5 <= r["disabled_aa_ratio"] <= 2.0
                for r in out["overhead"]
            )
        )
        if not ok:
            print("SMOKE FAILED:", json.dumps(chaos, indent=2))
            return 1
        print("smoke ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
