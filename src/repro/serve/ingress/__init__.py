"""Front-tier ingress (ISSUE 10): the serving tier as a deployable
multi-process service.

    worker processes:  python -m repro.serve.ingress.worker --config '{…}'
    frontier:          Frontier([(host, port), …]).serve()
    clients:           IngressClient(frontier_address).submit_plan(img, plan)

Four layers, one protocol:

* ``proto``    — versioned length-prefixed JSON + raw-tensor framing;
  the typed ``ServeError`` family round-trips losslessly, so a remote
  ``QuotaExceeded`` is the same exception (type, message, ``.tenant``)
  a local caller catches;
* ``worker``   — a ``MorphService``/``ShardedMorphService`` (or a
  ``Frontier``) behind a stdlib socket server, with drain-then-reject
  shutdown: ``close()`` mid-request surfaces ``ServiceClosed``, never a
  dropped connection;
* ``frontier`` — crc32 (plan, bucket, dtype) affinity routing across
  workers, per-worker breakers/slow marks (the shard router's state
  machine, extracted to serve/morph/health.py), deterministic reroute on
  worker death with zero lost futures;
* ``stats``    — fleet-wide metrics merge (the registry's cross-process
  semantics applied to wire snapshots) and Chrome traces stitched across
  processes via per-worker clock offsets.

``benchmarks/bench_router.py`` drives a multi-tenant QPS/SLO load mix
against a live 2–4 process fleet; ``examples/remote_cleanup.py`` is the
minimal end-to-end fleet walkthrough.
"""
from repro.serve.ingress.client import Connection, IngressClient
from repro.serve.ingress.frontier import (
    WORKER_LEVEL_ERRORS,
    Frontier,
    WorkerLink,
)
from repro.serve.ingress.proto import (
    MAGIC,
    PROTOCOL_VERSION,
    ConnectionLost,
    ProtocolError,
    decode_error,
    decode_result,
    decode_tensor,
    encode_error,
    encode_frame,
    encode_result,
    encode_tensor,
    frame_buffers,
    plan_from_wire,
    plan_to_wire,
    read_frame,
    send_frame,
)
from repro.serve.ingress.stats import (
    fleet_stats,
    merge_process_traces,
    merge_worker_metrics,
    shift_events,
)
from repro.serve.ingress.worker import (
    READY_SENTINEL,
    WorkerHost,
    config_from_json,
    spawn_worker,
)

__all__ = [
    "PROTOCOL_VERSION",
    "MAGIC",
    "ProtocolError",
    "ConnectionLost",
    "frame_buffers",
    "encode_frame",
    "send_frame",
    "read_frame",
    "encode_tensor",
    "decode_tensor",
    "encode_result",
    "decode_result",
    "encode_error",
    "decode_error",
    "plan_to_wire",
    "plan_from_wire",
    "Connection",
    "IngressClient",
    "WorkerHost",
    "READY_SENTINEL",
    "config_from_json",
    "spawn_worker",
    "Frontier",
    "WorkerLink",
    "WORKER_LEVEL_ERRORS",
    "merge_worker_metrics",
    "fleet_stats",
    "shift_events",
    "merge_process_traces",
]
