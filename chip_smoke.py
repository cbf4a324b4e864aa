#!/usr/bin/env python3
"""Smoke run of the served morphology path on TPU, checked bit for bit.

    python chip_smoke.py             # one chip: the served path, one process
    python chip_smoke.py --chips 4   # four chips: the sharded paths only

One chip: a default ``MorphService`` (fused Pallas kernels, compiled) sits
behind ``WorkerHost -> Frontier -> Frontier.serve()`` and an
``IngressClient`` sends it the paper's deployment (``configs/morphology.py``:
800x600 u8, rectangular SEs 3 to 121) as concurrent requests that batch,
one A4 page at 300 dpi that takes the tiled route, and two binary masks:
a dense one for the kernels and a sparse one for the run-length gate.

Four chips: ``to_sharded`` of an opening over an 8192x8192 u8 image on a
four-device mesh, against the one-device ``lower_xla`` result; then a
``ShardedMorphService`` whose traffic reaches every shard, each of which
must have dispatched on its own device only.

Every result is compared with ``morph2d_naive`` (single operators) or
``lower_xla`` (plans). The script exits non-zero, without its final line,
when JAX finds no TPU, when a request fails, or when any result differs.
The earlier lines report the device, the cost-table source, compile seconds,
cache hits and wall time per phase; they are a smoke record, not a
benchmark. The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import zlib

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

SEED = 0
PAPER_HW = (600, 800)  # configs/morphology.py: 800x600 u8
PAPER_SES = (3, 15, 121)  # both ends and the middle of the paper's SE sweep
A4_HW = (3508, 2480)  # ISO 216 A4 at 300 dpi, portrait: taller than any bucket
SHARDED_HW = (8192, 8192)
SHARDED_SE = (15, 15)
REQUEST_TIMEOUT_S = 900.0


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, read off
    ``jax.monitoring`` (a compile served from the cache still counts its
    retrieval time, so a warm cache shows as fewer seconds)."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def reading(self) -> tuple[float, int, int]:
        with self._lock:
            return self.seconds, self.compiles, self.cache_hits


def run_phase(name: str, meter: CompileMeter, fn):
    s0, c0, h0 = meter.reading()
    t0 = time.perf_counter()
    detail = fn()
    wall = time.perf_counter() - t0
    s1, c1, h1 = meter.reading()
    print(f"phase {name}: wall {wall} s, {c1 - c0} compiles, compile {s1 - s0} s, "
          f"persistent-cache hits {h1 - h0}; {detail}", flush=True)


def check_equal(got, want, what: str) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if got.dtype != want.dtype or got.shape != want.shape:
        raise SystemExit(f"{what}: got {got.dtype}{got.shape}, "
                         f"reference {want.dtype}{want.shape}")
    bad = int(np.count_nonzero(got != want))
    if bad:
        raise SystemExit(f"{what}: {bad} of {got.size} pixels differ from the reference")


def check_outputs(got, want, what: str) -> None:
    if isinstance(want, dict):
        if not isinstance(got, dict) or set(got) != set(want):
            raise SystemExit(f"{what}: outputs {sorted(got)} != {sorted(want)}")
        for k in want:
            check_equal(got[k], want[k], f"{what}[{k}]")
    else:
        check_equal(got, want, what)


class References:
    """The references every served result is held to, run on the default
    device: ``morph2d_naive`` for single operators (the gradient widened as
    every gradient path is), ``lower_xla`` for plans."""

    def __init__(self):
        import jax

        from repro.core import morph2d_naive
        from repro.core.types import widened_sub
        from repro.morph import lower_xla

        self._naive = jax.jit(morph2d_naive, static_argnames=("se", "op"))
        self._sub = widened_sub
        self._lower_xla = lower_xla

    def naive(self, x, se, op):
        if max(se) > 15:
            # a rectangle's min is the min of its rows' mins: two 1-D naive
            # passes, since the direct form unrolls w_h * w_w slices (14,641
            # at SE 121) and takes minutes to trace and compile
            return self._naive(self._naive(x, se=(se[0], 1), op=op),
                               se=(1, se[1]), op=op)
        return self._naive(x, se=se, op=op)

    def __call__(self, x, plan):
        if len(plan.steps) == 1 and plan.steps[0].op in ("erode", "dilate", "gradient"):
            op, se = plan.steps[0].op, plan.steps[0].se
            if op == "gradient":
                return self._sub(self.naive(x, se, "max"), self.naive(x, se, "min"))
            return self.naive(x, se, "min" if op == "erode" else "max")
        outs = self._lower_xla(dict(plan.outputs))(x)
        return outs["out"] if plan.output_names() == ("out",) else dict(outs)


def check_kernel_service(svc) -> None:
    """The chip path runs compiled kernels: no interpreter, no jnp path."""
    if svc.backend != "kernel" or svc.interpret is not False:
        raise SystemExit(f"service backend={svc.backend} interpret="
                         f"{svc.interpret}; the chip path needs compiled kernels")


# ------------------------------------------------------------------ one chip
def smoke_one_chip(meter: CompileMeter) -> None:
    from repro.morph.opt.cost import cost_model_for
    from repro.serve.ingress import Frontier, IngressClient, WorkerHost
    from repro.serve.morph import MorphService, ServiceConfig, get_plan, single_op_plan

    rng = np.random.default_rng(SEED)
    refs = References()
    svc = MorphService(ServiceConfig())
    check_kernel_service(svc)
    print(f"service: backend={svc.backend} interpret={svc.interpret} "
          f"cost_table={cost_model_for(svc.policy).source}", flush=True)
    host = WorkerHost(svc)
    front = edge = client = None
    try:
        front = Frontier([host.address])
        edge = front.serve()
        client = IngressClient(edge.address)

        def serve_and_check(jobs, label):
            futs = [(im, plan, client.submit_plan(im, plan)) for im, plan in jobs]
            for i, (im, plan, fut) in enumerate(futs):
                got = fut.result(timeout=REQUEST_TIMEOUT_S)
                check_outputs(got, refs(im, plan), f"{label} #{i} {plan.name}")
            return len(futs)

        def paper():
            imgs = [rng.integers(0, 256, PAPER_HW, dtype=np.uint8) for _ in range(4)]
            plans = [single_op_plan(op, (w, w)) for w in PAPER_SES
                     for op in ("erode", "dilate")]
            plans += [single_op_plan("gradient", (5, 5)), get_plan("document_cleanup")]
            # grouped by plan, all in flight at once: each group batches
            n = serve_and_check([(im, p) for p in plans for im in imgs], "paper")
            s = svc.stats()
            if s["batches"] >= s["requests"]:
                raise SystemExit(f"no dispatch batched more than one request "
                                 f"({s['requests']} requests, {s['batches']} batches)")
            return (f"{n} requests over {len(plans)} plans, {s['batches']} dispatches, "
                    f"mean batch {s['mean_batch']}, all bit-exact")

        def page():
            tiled0 = svc.stats()["tiled_requests"]
            im = rng.integers(0, 256, A4_HW, dtype=np.uint8)
            serve_and_check([(im, single_op_plan("erode", (15, 15)))], "a4")
            tiled = svc.stats()["tiled_requests"] - tiled0
            if tiled != 1:
                raise SystemExit(f"the A4 page took {tiled} tiled routes, not 1")
            return f"{A4_HW[1]}x{A4_HW[0]} u8 erode 15x15 on the tiled route, bit-exact"

        def masks():
            r0 = svc.stats()["repr"]
            dense = rng.random(PAPER_HW) < 0.5
            sparse = np.zeros(PAPER_HW, dtype=bool)
            sparse[100:180, 200:420] = True
            sparse[350:360, 50:700] = True
            serve_and_check([(dense, single_op_plan("erode", (5, 5))),
                             (sparse, single_op_plan("dilate", (9, 9)))], "mask")
            r1 = svc.stats()["repr"]
            routed = (r1["dense"] - r0["dense"], r1["rle"] - r0["rle"])
            if routed != (1, 1):
                raise SystemExit(f"masks routed dense/rle = {routed}, expected (1, 1)")
            return "dense bool mask on the kernels, sparse mask on RLE, both bit-exact"

        run_phase("paper_800x600_u8", meter, paper)
        run_phase("a4_page_tiled", meter, page)
        run_phase("bool_masks", meter, masks)
        s = svc.stats()
        print(f"service totals: {s['requests']} requests, executable cache "
              f"{s['cache']}", flush=True)
    finally:
        if client is not None:
            client.close()
        if edge is not None:
            edge.close()  # closes the frontier it serves
        elif front is not None:
            front.close()
        host.close()  # drains and closes the service


# --------------------------------------------------------------- four chips
def smoke_four_chips(meter: CompileMeter, devices) -> None:
    import jax

    from repro.morph import X, lower_xla
    from repro.serve.morph import ServiceConfig, single_op_plan
    from repro.serve.morph.buckets import choose_bucket
    from repro.shard import ShardedMorphService, image_mesh, to_sharded

    rng = np.random.default_rng(SEED)
    refs = References()

    def sharded_lowering():
        x = rng.integers(0, 256, SHARDED_HW, dtype=np.uint8)
        expr = X.opening(SHARDED_SE)
        got = to_sharded(expr, image_mesh(len(devices)))(x)
        spread = len(got.sharding.device_set)
        want = jax.jit(lower_xla(expr))(jax.device_put(x, devices[0]))
        check_equal(got, want, "to_sharded opening")
        return (f"opening {SHARDED_SE} over {SHARDED_HW[1]}x{SHARDED_HW[0]} u8 on "
                f"{spread} devices, bit-exact against one-device lower_xla")

    def sharded_service():
        shapes = [(100, 120), (200, 250), (500, 500)]  # three buckets
        ops = ("erode", "dilate", "opening", "closing", "gradient")
        cfg = ServiceConfig()
        jobs = [(rng.integers(0, 256, hw, dtype=np.uint8), single_op_plan(op, (5, 5)))
                for hw in shapes for op in ops for _ in range(2)]
        # the router's group token (shard/router.py): crc32 over plan, bucket, dtype
        owners = {
            zlib.crc32(f"{p.name}|{choose_bucket(*im.shape, cfg.buckets)}|"
                       f"{im.dtype.str}".encode()) % len(devices)
            for im, p in jobs
        }
        if len(owners) != len(devices):
            raise SystemExit(f"traffic reaches shards {sorted(owners)} only")
        with ShardedMorphService(cfg, devices=devices) as svc:
            for shard in svc.shards:
                check_kernel_service(shard)
            futs = [(im, p, svc.submit_plan(im, p)) for im, p in jobs]
            for i, (im, p, fut) in enumerate(futs):
                check_outputs(fut.result(timeout=REQUEST_TIMEOUT_S), refs(im, p),
                              f"sharded #{i} {p.name}")
            placed = []
            for shard in svc.shards:
                seen = {k for k in shard.metrics_snapshot() if k.startswith("device.")}
                own = f"device.{shard.config.device.id}.dispatches"
                if seen != {own}:
                    raise SystemExit(f"shard {shard.config.shard} dispatched on "
                                     f"{sorted(seen)}, expected only {own}")
                placed.append(shard.config.device.id)
        return (f"{len(jobs)} requests, every shard served on its own device "
                f"{placed}, all bit-exact")

    run_phase("to_sharded_8192_u8", meter, sharded_lowering)
    run_phase("sharded_service", meter, sharded_service)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path on one chip; 4: the sharded paths")
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401 — the package lives in src/ next to this file
    except ImportError as e:
        raise SystemExit(f"chip_smoke: cannot import the repro package ({e}); "
                         "run it from a checkout of the repository")
    import jax

    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                         f"TPU devices, JAX found {len(devices)}")
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        smoke_four_chips(meter, devices[:4])
    else:
        smoke_one_chip(meter)
    seconds, compiles, hits = meter.reading()
    print(f"total: wall {time.perf_counter() - t0} s, {compiles} compiles, "
          f"compile {seconds} s, persistent-cache hits {hits}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
