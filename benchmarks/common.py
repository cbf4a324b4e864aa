"""Shared benchmark utilities: timing, latency summaries, workload generation.

Latency percentiles go through the serving tier's histogram
(``repro.obs.Histogram``) rather than ``np.percentile``, so a benchmark's
reported p50/p99 quantizes exactly as the live ``stats()`` surface does —
one estimator, comparable numbers.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.morphology import CONFIG as MORPH
from repro.obs import DEFAULT_LATENCY_BUCKETS_MS, Histogram, quantile_from_snapshot

# every benchmark imports this module: share one persistent compile cache
enable_compile_cache()


def time_fn(fn, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall-time (seconds) of fn(*args) with block_until_ready."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def time_fn_amortized(fn, *args, reps: int = 5) -> float:
    """Mean wall-time (seconds) over one blocking sweep of ``reps`` calls —
    the cheap estimator for already-warm compiled fns (one sync at the end
    instead of per call)."""
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def latency_summary(latencies_s) -> dict:
    """p50/p99/mean (milliseconds) of per-request latencies given in
    seconds, estimated from the obs latency histogram."""
    h = Histogram(DEFAULT_LATENCY_BUCKETS_MS)
    h.observe_many([t * 1e3 for t in latencies_s])
    snap = h.snapshot()
    return {
        "n": h.count,
        "mean_ms": h.mean(),
        "p50_ms": quantile_from_snapshot(snap, 0.50),
        "p99_ms": quantile_from_snapshot(snap, 0.99),
    }


def p99_ms(latencies_s) -> float:
    return latency_summary(latencies_s)["p99_ms"]


def paper_image(seed: int = 0) -> jnp.ndarray:
    """The paper's experimental input: 800x600 u8 gray image."""
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(0, 256, (MORPH.height, MORPH.width), dtype=np.uint8)
    )


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}")
