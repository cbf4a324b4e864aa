"""One run of one cell: set-up, a measured window on the served path, the
check against the plain reference, and one JSON line.

The cell, its configuration, its traffic mix and its metrics are all found by
name: ``BENCHMARK.json`` at the checkout's root names them, and the files are
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``metrics/<metric>.py`` under this benchmark's directory (a metric named
``base.suffix`` may share the reader ``metrics/<base>.py``).

The entry the window drives is the same in every cell, in one process that
holds the cell's chips::

    IngressClient -> Frontier.serve() -> Frontier -> WorkerHost -> service

over loopback sockets, where the service is ``MorphService(ServiceConfig())``.
A configuration that states ``"replicas": N`` lays the cell out over its
chips: N services, replica i pinned to chip i, each behind a worker host of
its own, and one frontier over the N hosts. A traced run (``--trace 1``)
turns the services' spans and profiler annotations on and records a profiler
trace of the window.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import heapq
import importlib.util
import json
import os
import queue
import resource
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench import reference, traffic as gen, xtrace
from chipbench.spans import TRACE_BASE

BENCH_REL = os.path.join("benchmarks", "chip")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
LATE_S = 60.0  # how long past the window's close an answer may take
WARM_THREADS = 16  # executors compiled or loaded at once where there are replicas


class BenchError(SystemExit):
    """A run that cannot produce a result: exits non-zero, prints no line."""

    def __init__(self, msg: str):
        super().__init__(f"benchmark: {msg}")


# ------------------------------------------------------------------ loading
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise BenchError(f"no BENCHMARK.json at {root}")
    return load_json(path)


def cell_spec(manifest: dict, workload: str, root: str = ROOT) -> dict:
    """The cell with its configuration, traffic mix and metric entries."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, entry["file"]))
    bench_dir = os.path.join(root, BENCH_REL)
    mix = load_json(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    return {
        "cell": cell,
        "config": config,
        "traffic": mix,
        "bench_dir": bench_dir,
        "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
        "per_layer": [m for m in manifest["per_layer"] if mine(m)],
    }


def load_reader(name: str, bench_dir: str):
    """``read(run)`` from ``metrics/<name>.py``, else ``metrics/<base>.py``."""
    for stem in (name, name.split(".", 1)[0]):
        path = os.path.join(bench_dir, "metrics", stem + ".py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(f"chipbench_metric_{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise BenchError(f"no reader for metric {name!r} under {bench_dir}/metrics")


def peak_for(device_kind: str, bench_dir: str) -> dict:
    table = load_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json")
    return table[device_kind]


# -------------------------------------------------------------- the program
def import_program(root: str = ROOT):
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the program is not in this checkout ({src}/repro is missing)")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401


def enable_cache(root: str = ROOT) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless the
    environment names one; programs of every size are kept, and none is
    evicted: a four-chip cell's programs (one set per chip, up to 4 MB each)
    outgrow a cache capped at a few hundred MB, which would leave every run
    compiling in set-up."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def chip_devices(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def server_plan(kind: dict):
    from repro.serve.morph import get_plan, single_op_plan

    if "plan" in kind:
        return get_plan(kind["plan"])
    return single_op_plan(kind["op"], tuple(kind["se"]))


@dataclasses.dataclass
class Stack:
    services: list
    hosts: list
    frontier: object
    edge: object
    client: object
    served_by: dict  # trace id -> the replica that took the request (replicas only)

    def trace_events(self) -> list[dict]:
        """Every service's finished spans; in one process they share a clock.
        (``Frontier.export_trace`` would need tracing on at the frontier too,
        which adds its own spans and annotations to every traced cell.)"""
        return [ev for svc in self.services
                for ev in (svc.export_trace() or {}).get("traceEvents", [])]

    def close(self) -> None:
        for part in (self.client, self.edge, *self.hosts):
            if part is not None:
                part.close()  # the edge closes its frontier; a host its service


class Attributed:
    """A replica as its worker host sees it: notes which replica took each
    request, by the trace id the benchmark sent, and is the service in all
    else. The check draws its sample from every replica's answers by it."""

    def __init__(self, service, index: int, served_by: dict):
        self._service, self._index, self._served_by = service, index, served_by

    def submit_plan(self, img, plan, **kw):
        self._served_by[kw.get("_trace")] = self._index
        return self._service.submit_plan(img, plan, **kw)

    def __getattr__(self, name):
        return getattr(self._service, name)


def replicas_of(config: dict) -> int:
    return int(config.get("replicas", 1))


def counter(snapshot: dict, name: str) -> float:
    m = snapshot.get(name)
    return m["value"] if m is not None else 0


def open_stack(config: dict, traced: bool, ring: int, devices) -> Stack:
    """The served stack. With one replica the service takes the process's
    default device; with N, replica i is pinned to ``devices[i]`` (to the one
    device there is, where fewer are given, as on the CPU)."""
    from repro.obs import ObsConfig
    from repro.serve.ingress import Frontier, IngressClient, WorkerHost
    from repro.serve.morph import MorphService, ServiceConfig

    kw = dict(config.get("service", {}))
    if "buckets" in kw:
        kw["buckets"] = tuple(tuple(b) for b in kw["buckets"])
    if "tile_interior" in kw:
        kw["tile_interior"] = tuple(kw["tile_interior"])
    n = replicas_of(config)
    services, hosts, served_by = [], [], {}
    frontier = edge = client = None
    try:
        for i in range(n):
            own = dict(kw)
            if traced:
                own["obs"] = ObsConfig(jax_profiler=True, trace_ring=ring)
            if n > 1:
                own.update(device=devices[i % len(devices)], shard=i)
            services.append(MorphService(ServiceConfig(**own)))
            hosts.append(WorkerHost(services[-1] if n == 1 else
                                    Attributed(services[-1], i, served_by)))
        frontier = Frontier([h.address for h in hosts])
        edge = frontier.serve()
        client = IngressClient(edge.address)
    except BaseException:
        if edge is not None:
            edge.close()
        elif frontier is not None:
            frontier.close()
        for h in hosts:
            h.close()
        raise
    return Stack(services, hosts, frontier, edge, client, served_by)


def warm_executors(stack: Stack, kinds: list[dict], config: dict) -> int:
    """Build and run, on zeros, every executor a bucketed request of this cell
    can reach, on every replica: each kind's plan at its bucket, at every
    power-of-two batch up to ``max_batch``. With replicas, several at once.
    Returns how many."""
    import jax.numpy as jnp
    from repro.serve.morph.buckets import choose_bucket

    h, w = int(config["image"]["height"]), int(config["image"]["width"])
    dtype = np.dtype(config["image"]["dtype"])
    jobs = []
    for svc in stack.services:
        for kind in kinds:
            plan = server_plan(kind)
            bucket = choose_bucket(h, w, svc.config.buckets)
            if bucket is None:
                continue  # tiled: warmed by serving a whole request
            b = 1
            while b <= svc.config.max_batch:
                jobs.append((svc, plan, bucket, b))
                b *= 2

    def warm(job) -> None:
        svc, plan, bucket, b = job
        fn = svc._executor_for(plan, bucket, dtype, b)
        with svc._device_scope():
            outs, _ = fn(jnp.zeros((b, *bucket), dtype), jnp.zeros((b, 4), jnp.int32))
            for v in outs.values():
                v.block_until_ready()

    if len(stack.services) == 1:
        for job in jobs:
            warm(job)
    elif jobs:
        with ThreadPoolExecutor(min(len(jobs), WARM_THREADS)) as pool:
            list(pool.map(warm, jobs))
    return len(jobs)


def annotate(name: str, on: bool):
    """A host span in the profiler's trace, where the run is traced."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def as_outputs(result) -> dict:
    if isinstance(result, dict):
        return {k: np.asarray(v) for k, v in result.items()}
    return {"out": np.asarray(result)}


# -------------------------------------------------------------- the window
@dataclasses.dataclass
class Record:
    index: int
    kind: int
    due: float  # perf_counter: when the request was due
    sent: float = 0.0
    done: float | None = None
    error: str | None = None


class Driver:
    """Sends the cell's requests through the client and keeps, for the check,
    the answers of the ``sample`` finished requests with the lowest draws from
    each replica that answered any: a replica that takes a small share, such
    as a reroute target, is compared all the same."""

    def __init__(self, stack: Stack, kinds, plans, pool, seed: int, sample: int,
                 traced: bool = False):
        self.client = stack.client
        self.served_by = stack.served_by
        self.traced = traced
        self.kinds = kinds
        self.plans = plans
        self.pool = pool
        self.seed = seed
        self.sample = sample
        self.records: list[Record] = []
        self.done_q: queue.Queue = queue.Queue()
        self._lock = threading.Lock()
        self._heaps: dict = {}  # replica -> [(-draw, index, outputs)]: the kept answers
        self.seq: np.ndarray | None = None

    def kind_of(self, index: int) -> int:
        if index >= len(self.seq):
            self.seq = gen.kind_sequence(len(self.kinds), 2 * index + 1, self.seed)
        return int(self.seq[index])

    def send(self, index: int, due: float) -> None:
        kind = self.kind_of(index)
        img = gen.stamp(self.pool[index % len(self.pool)], index)
        rec = Record(index, kind, due)
        self.records.append(rec)
        rec.sent = time.perf_counter()
        try:
            with annotate("bench:submit", self.traced):
                fut = self.client.submit_plan(img, self.plans[kind],
                                              trace=TRACE_BASE + index)
        except Exception as exc:  # noqa: BLE001 — a refused send is a failed request
            rec.done, rec.error = time.perf_counter(), f"{type(exc).__name__}: {exc}"
            self.done_q.put(index)
            return
        fut.add_done_callback(lambda f, rec=rec: self._finished(rec, f))

    def _finished(self, rec: Record, fut) -> None:
        t = time.perf_counter()
        exc = fut.exception()
        if exc is not None:
            rec.error = f"{type(exc).__name__}: {exc}"
        else:
            draw = gen.check_priority(self.seed, rec.index)
            replica = self.served_by.get(TRACE_BASE + rec.index, 0)
            with self._lock:
                heap = self._heaps.setdefault(replica, [])
                if len(heap) < self.sample:
                    heapq.heappush(heap, (-draw, rec.index, fut.result()))
                elif -heap[0][0] > draw:
                    heapq.heapreplace(heap, (-draw, rec.index, fut.result()))
        rec.done = t
        self.done_q.put(rec.index)

    def kept(self) -> list[tuple[int, object]]:
        with self._lock:
            return sorted((i, r) for heap in self._heaps.values() for _, i, r in heap)

    def kept_per_replica(self) -> dict[int, int]:
        with self._lock:
            return {k: len(heap) for k, heap in sorted(self._heaps.items())}

    def outstanding(self) -> int:
        return sum(1 for r in self.records if r.done is None)

    def drain(self, until: float) -> None:
        while self.outstanding() and time.perf_counter() < until:
            try:
                self.done_q.get(timeout=0.05)
            except queue.Empty:
                pass

    def closed_loop(self, in_flight: int, seconds: float) -> tuple[float, float]:
        self.seq = gen.kind_sequence(len(self.kinds), in_flight + int(seconds * 5000),
                                     self.seed)
        t0 = time.perf_counter()
        t_end = t0 + seconds
        for i in range(in_flight):
            self.send(i, time.perf_counter())
        nxt = in_flight
        while True:
            try:
                self.done_q.get(timeout=0.05)
            except queue.Empty:
                if time.perf_counter() >= t_end:
                    break
                continue
            now = time.perf_counter()
            if now >= t_end:
                break
            self.send(nxt, now)
            nxt += 1
        return t0, t_end

    def open_loop(self, due: np.ndarray, seconds: float) -> tuple[float, float]:
        self.seq = gen.kind_sequence(len(self.kinds), len(due), self.seed)
        t0 = time.perf_counter() + 0.01
        for i, d in enumerate(due):
            at = t0 + float(d)
            wait = at - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            self.send(i, at)
        t_end = max(t0 + seconds, time.perf_counter())
        return t0, t_end


# ----------------------------------------------------------------- the run
@dataclasses.dataclass
class Run:
    """What a metric reader reads: the window's requests on the host clock,
    the service's counters, the compile meter and, in a traced run, the
    trace reduction and the service's spans."""

    spec: dict
    t0: float
    t1: float
    setup_s: float
    records: list
    kinds: list
    counters0: dict
    counters1: dict
    compiles_in_window: int
    peak: dict
    trace: dict | None = None
    spans: list | None = None

    def window_records(self) -> list:
        """Requests due inside the window."""
        return [r for r in self.records if self.t0 <= r.due < self.t1]

    def latencies_ms(self) -> list[float]:
        """From due to answer, for each request due in the window. A failed
        or missing answer misses every limit: it counts as answered when the
        run stopped waiting, a minute past the window's close."""
        give_up = self.t1 + LATE_S
        return [((r.done if r.error is None and r.done is not None else give_up)
                 - r.due) * 1e3 for r in self.window_records()]

    def completed_in_window(self) -> list:
        return [r for r in self.records if r.error is None and r.done is not None
                and self.t0 <= r.done < self.t1]

    def ideal_bytes(self, recs) -> int:
        """Input and output bytes each request needs at its own size."""
        cfg = self.spec["config"]
        px = int(cfg["image"]["height"]) * int(cfg["image"]["width"])
        item = np.dtype(cfg["image"]["dtype"]).itemsize
        per_kind = [ideal_bytes(k, px, item, cfg.get("plans", {})) for k in self.kinds]
        return sum(per_kind[r.kind] for r in recs)

    def counter_delta(self, name: str) -> float:
        return counter(self.counters1, name) - counter(self.counters0, name)


def out_itemsize(op: str, in_item: int) -> int:
    """Bytes per pixel of an operator's answer: a gradient is widened."""
    return 2 * in_item if op == "gradient" else in_item


def ideal_bytes(kind: dict, pixels: int, item: int, plans: dict) -> int:
    """The least HBM traffic a request needs: its input read once, each of its
    outputs written once, at the request's own size."""
    if "plan" in kind:
        outs = [np.dtype(s["astype"]).itemsize if "astype" in s
                else out_itemsize(s["op"], item)
                for s in plans[kind["plan"]] if "save_as" in s]
    else:
        outs = [out_itemsize(kind["op"], item)]
    return pixels * (item + sum(outs))


def host_usage(ru0, ru1) -> str:
    """The process's CPU time, page faults and context switches between two
    ``getrusage`` readings."""
    return (f"user {ru1.ru_utime - ru0.ru_utime} s, sys {ru1.ru_stime - ru0.ru_stime} s, "
            f"minor faults {ru1.ru_minflt - ru0.ru_minflt}, major faults "
            f"{ru1.ru_majflt - ru0.ru_majflt}, voluntary switches "
            f"{ru1.ru_nvcsw - ru0.ru_nvcsw}, involuntary {ru1.ru_nivcsw - ru0.ru_nivcsw}, "
            f"cpus allowed {len(os.sched_getaffinity(0))}")


def answers_per_bin(run: Run, width: float) -> list[int]:
    """Answers that arrived inside the window, counted in bins of ``width`` s."""
    bins = [0] * max(1, int(np.ceil((run.t1 - run.t0) / width)))
    for r in run.completed_in_window():
        bins[min(len(bins) - 1, int((r.done - run.t0) // width))] += 1
    return bins


def check_answers(driver: Driver, kinds, plans, pool, make=None):
    """Compare each kept answer with the reference. ``make`` stands in for the
    served answer (the control); returns (wrong pixels, answers compared)."""
    wrong = compared = 0
    for index, result in driver.kept():
        kind = kinds[driver.kind_of(index)]
        img = gen.stamp(pool[index % len(pool)], index)
        got = make(img, kind, plans) if make is not None else as_outputs(result)
        wrong += reference.mismatches(got, reference.expected(img, kind, plans))
        compared += 1
    return wrong, compared


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, control: bool = False) -> dict:
    import jax

    from chipbench.compile_meter import CompileMeter

    config, mix, cell = spec["config"], spec["traffic"], spec["cell"]
    chips = int(cell["chips"])
    if replicas_of(config) > chips:
        raise BenchError(f"the configuration asks for {replicas_of(config)} replicas, "
                         f"the cell has {chips} chips")
    if require_tpu:
        devices = chip_devices(chips)
    else:
        devices = jax.devices()[:chips]
    dev0 = devices[0]
    peak = peak_for(dev0.device_kind, spec["bench_dir"]) if require_tpu else {}
    meter = CompileMeter()
    readers = {m["name"]: load_reader(m["name"], spec["bench_dir"])
               for m in (spec["per_layer"] if trace else spec["end_to_end"])}

    kinds = gen.request_kinds(config)
    plans_ref = config.get("plans", {})
    server_plans = [server_plan(k) for k in kinds]
    pool = gen.image_pool(config, int(mix.get("image_pool", 16)), seed)
    open_loop = mix["loop"] == "open"
    due = (gen.open_loop_due(float(mix["rate_per_s"]), seconds, seed)
           if open_loop else None)
    ring = 4 * (len(due) if open_loop else int(seconds * 20_000)) + 4096
    stack = open_stack(config, trace, ring, devices)
    try:
        warmed = warm_executors(stack, kinds, config)
        # every kind once through the whole stack: sockets, crops, tiles
        warm_futs = [stack.client.submit_plan(gen.stamp(pool[i % len(pool)], 10**9 + i), p)
                     for i, p in enumerate(server_plans)]
        for f in warm_futs:
            f.result(timeout=600)
        driver = Driver(stack, kinds, server_plans, pool, seed, int(mix["check_sample"]),
                        traced=trace)
        logdir = None
        if trace:
            logdir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(logdir)
        # the fleet's view: the replicas' counters summed, by metric type
        counters0, fleet0 = stack.frontier.metrics_snapshot(), stack.frontier.stats()
        setup_s = time.perf_counter() - t_start
        s_c, n_c, n_h = meter.reading()
        print(f"set-up: {setup_s} s, {warmed} executors warmed, {n_c} programs "
              f"obtained ({n_h} from the persistent cache), compile {s_c} s",
              file=sys.stderr, flush=True)
        ru0 = resource.getrusage(resource.RUSAGE_SELF)
        with annotate("bench:window", trace):
            if open_loop:
                t0, t1 = driver.open_loop(due, seconds)
            else:
                t0, t1 = driver.closed_loop(int(mix["in_flight"]), seconds)
            counters1, fleet1 = stack.frontier.metrics_snapshot(), stack.frontier.stats()
        ru1 = resource.getrusage(resource.RUSAGE_SELF)
        in_window = meter.compiles_between(t0, t1)
        driver.drain(t1 + LATE_S)
        trace_red = spans = None
        if trace:
            jax.profiler.stop_trace()
            raw = xtrace.load_xplane(logdir)
            shutil.rmtree(logdir, ignore_errors=True)
            names = [f"{xtrace.DEVICE_PREFIX}{d.id}" for d in devices]
            trace_red = xtrace.reduce(raw, names)
            spans = stack.trace_events()
        mem = [d.memory_stats() or {} for d in devices] if require_tpu else []
        memory_peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    finally:
        stack.close()

    run = Run(spec, t0, t1, setup_s, driver.records, kinds, counters0,
              counters1, in_window, peak, trace_red, spans)
    # ---- the check, once the program's state is freed
    tc = time.perf_counter()
    window = run.window_records()
    failed = sum(1 for r in window if r.error is not None or r.done is None)
    errors = sorted({r.error for r in window if r.error})[:3]
    wrong, compared = check_answers(driver, kinds, plans_ref, pool,
                                    reference.control if control else None)
    checks = {
        "wrong_pixels": {"value": wrong, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
        "compared_answers": {"value": compared, "limit": ">= 1"},
    }
    correct = wrong == 0 and failed == 0 and compared >= 1
    check_s = time.perf_counter() - tc

    metrics = {}
    for m in (spec["per_layer"] if trace else spec["end_to_end"]):
        v = readers[m["name"]](run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    lateness = [r.sent - r.due for r in window]
    print(f"window: {len(window)} requests due, {len(run.completed_in_window())} "
          f"answered inside it, {in_window} programs obtained inside it; generator lateness "
          f"max {max(lateness, default=0.0)} s, mean "
          f"{sum(lateness) / len(lateness) if lateness else 0.0} s; check {check_s} s",
          file=sys.stderr)
    per_replica = [(b or {}).get("requests", 0) - (a or {}).get("requests", 0)
                   for a, b in zip(fleet0["per_worker"], fleet1["per_worker"])]
    print(f"replicas: requests in the window {per_replica}; frontier reroutes "
          f"{fleet1['reroutes']}, failovers {fleet1['failovers']}; answers compared "
          f"{driver.kept_per_replica()}", file=sys.stderr)
    print(f"host over the window: {host_usage(ru0, ru1)}; answers per 5 s "
          f"{answers_per_bin(run, 5.0)}", file=sys.stderr)
    if trace_red is not None:
        print(f"busy s per chip: {trace_red['busy_s_per_device']}", file=sys.stderr)
    if errors:
        print(f"errors: {errors}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    out = {
        "correct": bool(correct),
        "attempted": len(window),
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": dev0.platform,
            "kind": dev0.device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak),
        },
    }
    if trace_red is not None:
        out["device"]["busy_s"] = trace_red["busy_s"]
        out["device"]["window_s"] = trace_red["window_s"]
        out["breakdown"] = {"device_ops": trace_red["device_ops"],
                            "idle_gaps": trace_red["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None, *, t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="compare the reference one precision down in place of "
                         "the served answers (it has to come out not correct)")
    args = ap.parse_args(argv)
    spec = cell_spec(load_manifest(), args.workload)
    import_program()
    enable_cache()
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace), t_start=t_start,
                   control=args.control)
    print(json.dumps(out), flush=True)
    return 0
