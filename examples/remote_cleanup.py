"""End-to-end driver #5 — the serving tier as a deployable fleet: document
cleanup over the ingress wire protocol.

Spawns two real worker *processes* (the same ``python -m
repro.serve.ingress.worker`` entry point production would run), routes
through a :class:`Frontier` (crc32 plan/bucket/dtype affinity, per-worker
breakers), and exposes the whole fleet on one client address via
``Frontier.serve()`` — the recursive composition::

    IngressClient -> WorkerHost(Frontier) -> Connection -> WorkerHost(worker)

Every remote result is compared bit-for-bit against a direct in-process
``MorphService``: the wire adds a process boundary, not a numerics
boundary. The fleet-wide ``stats()`` at the end is merged from each
worker's metrics registry over the same protocol.

On a TPU host each worker holds one chip (``spawn_worker`` pins worker
``i`` to chip ``i``), so this process computes its reference on the CPU
and never takes a chip itself.

    PYTHONPATH=src python examples/remote_cleanup.py
"""
import time

import jax
import numpy as np

from repro.serve.ingress import Frontier, IngressClient, spawn_worker
from repro.serve.morph import MorphService, ServiceConfig

jax.config.update("jax_platforms", "cpu")  # the chips belong to the workers

BUCKET = (128, 128)
rng = np.random.default_rng(0)
imgs = [rng.integers(0, 256, (100 + 4 * i, 120), dtype=np.uint8)
        for i in range(8)]

# ------------------------------------------------------------ reference path
with MorphService(ServiceConfig(buckets=(BUCKET,))) as direct:
    refs = [direct.run_plan(im, "document_cleanup") for im in imgs]

# ---------------------------------------------------------------- the fleet
workers = []
try:
    for i in range(2):
        workers.append(spawn_worker(
            {"buckets": [list(BUCKET)], "window_ms": 2.0}, worker_id=i,
        ))
    addrs = [addr for _, addr in workers]
    print(f"fleet: 2 worker processes at {addrs}")

    with Frontier(addrs, buckets=(BUCKET,)) as front:
        edge = front.serve()  # one address for clients, same protocol
        try:
            with IngressClient(edge.address) as client:
                client.run_plan(imgs[0], "document_cleanup")  # warm
                t0 = time.perf_counter()
                futures = [client.submit_plan(im, "document_cleanup")
                           for im in imgs]
                results = [f.result() for f in futures]
                dt = time.perf_counter() - t0
                stats = client.stats()
        finally:
            edge.close()

    for got, ref in zip(results, refs):
        for k in ref:
            np.testing.assert_array_equal(got[k], np.asarray(ref[k]))
    print(f"remote : {dt*1e3:.1f} ms for {len(imgs)} requests "
          f"({len(imgs)/dt:.1f} img/s) — bit-exact vs the direct service")
    print(f"fleet  : {stats['workers']} workers "
          f"({stats['healthy_workers']} healthy), "
          f"{stats['requests']} routed requests, "
          f"p99 {stats['p99_ms']:.1f} ms, "
          f"cache hit rate {stats['cache']['hit_rate']}")
finally:
    for proc, _ in workers:
        proc.kill()
        proc.wait(timeout=60)
