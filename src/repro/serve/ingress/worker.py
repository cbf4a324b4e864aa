"""Worker host: a morphology service behind a socket, speaking proto.py.

``WorkerHost`` wraps any service-like object — a :class:`MorphService`, a
:class:`ShardedMorphService`, or the ingress :class:`Frontier` itself
(which is how the frontier exposes its own client port: the ingress stack
is ``client -> WorkerHost(Frontier) -> Connection -> WorkerHost(service)``,
one protocol everywhere) — behind a stdlib TCP listener. No framework, no
new dependencies: one accept thread, one reader thread per connection,
responses written by whichever thread resolves the future, serialized per
connection by a write lock so frames never interleave.

Remote requests are *real* requests: ``tenant``, ``priority``,
``deadline_ms``, ``tag``, and the frontier-minted ``trace`` ID all thread
from the wire into ``service.submit_plan``, so quotas, brownout, hedging,
deadline scheduling, and tracing apply to ingress traffic exactly as they
do in-process, and every typed rejection rides back as the same exception
type via ``proto.encode_error``.

Shutdown is **drain-then-reject** (ISSUE 10 satellite): ``close()``

1. flips the host to *closing* — submits that arrive from here on are
   answered with a typed :class:`ServiceClosed` frame (never a dropped
   connection, which a client could not tell from a crash);
2. waits until every already-accepted submit has written its response
   (the service stays open, so in-flight work completes normally);
3. closes the service (idempotent batcher drain), then the sockets.

So every outstanding client future resolves exactly once: accepted work
with its result, late work with ``ServiceClosed``, and only a genuinely
killed worker ever surfaces :class:`ConnectionLost`. ``kill()`` is that
crash, for chaos tests: sockets drop with no drain and no typed goodbye.

Where the wrapped service has observability on (its ``_obs``), each submit
is an ``ingress`` span in the service's own tracer, carrying the request's
trace ID from the header: it opens once the frame is decoded and closes
once the reply bytes are sent, and times two stages, ``recv`` (decoding
the tensor and plan, then ``submit_plan``, on the connection's reader
thread) and ``reply`` (encoding the result and ``sendall``, on whichever
thread resolves the future). A :class:`MorphService`'s ``queue`` span is
its child (``Observability.request_submitted`` runs inside ``recv``).

The module is also the subprocess entry point::

    python -m repro.serve.ingress.worker --config '{"max_batch": 16}'

which prints ``INGRESS_WORKER_READY <host> <port>`` once serving;
:func:`spawn_worker` wraps the Popen + handshake for benchmarks/tests.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import glob
import json
import os
import socket
import subprocess
import sys
import threading
import time

from repro.compile_cache import enable_compile_cache
from repro.serve.ingress import proto
from repro.serve.morph.resilience import FaultPlan, ServiceClosed
from repro.serve.morph.service import MorphService, ServiceConfig
from repro.serve.morph.tenancy import PRIORITY_NORMAL, TenantQuota

READY_SENTINEL = "INGRESS_WORKER_READY"
_NULL = contextlib.nullcontext()


def _open_spans(service) -> int:
    """Open-span count across a service-like object's tracers (0 when obs
    is off) — the number the acceptance gate asserts is zero post-drain."""
    if hasattr(service, "open_spans"):
        return service.open_spans()
    total = 0
    obs = getattr(service, "_obs", None)
    if obs is not None and getattr(obs, "tracer", None) is not None:
        total += obs.tracer.open_count()
    for s in getattr(service, "shards", ()):
        o = getattr(s, "_obs", None)
        if o is not None and getattr(o, "tracer", None) is not None:
            total += o.tracer.open_count()
    return total


class WorkerHost:
    """Serve one service-like object over the ingress protocol.

    ``service`` may be passed ready-made (the frontier does this; tests
    wrap pre-configured services); otherwise one ``MorphService(config)``
    is constructed and owned. ``worker_id`` labels health/stats responses
    so a frontier can tell its workers apart in merged views.
    """

    def __init__(self, service=None, *, config: ServiceConfig | None = None,
                 host: str = "127.0.0.1", port: int = 0,
                 worker_id: int | None = None):
        self.service = service if service is not None else MorphService(
            config or ServiceConfig()
        )
        self.worker_id = worker_id
        # the wrapped service's observability runtime (None when off)
        self._obs = getattr(self.service, "_obs", None)
        self._lock = threading.Lock()
        self._drained = threading.Condition(self._lock)
        self._closing = False
        self._closed = threading.Event()
        self._outstanding = 0  # accepted submits whose response isn't written
        self.requests = 0
        self._conns: set[socket.socket] = set()
        self._listener = socket.create_server((host, port))
        self.address: tuple[str, int] = self._listener.getsockname()[:2]
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="ingress-accept", daemon=True
        )
        self._accept_thread.start()

    # ------------------------------------------------------------ connections
    def _accept_loop(self) -> None:
        while True:
            try:
                conn, _ = self._listener.accept()
            except OSError:
                return  # listener closed
            with self._lock:
                if self._closing:
                    conn.close()
                    continue
                self._conns.add(conn)
            threading.Thread(
                target=self._serve_conn, args=(conn,),
                name="ingress-conn", daemon=True,
            ).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        rfile = conn.makefile("rb")
        wlock = threading.Lock()

        def send(header: dict, payload: bytes = b"") -> None:
            buf = proto.encode_frame(header, payload)
            try:
                with wlock:
                    conn.sendall(buf)
            except OSError:
                pass  # client went away; its futures died with it

        try:
            while True:
                try:
                    frame = proto.read_frame(rfile)
                except proto.ProtocolError as exc:
                    # the bad frame was consumed; answer typed and keep going
                    send(proto.error_message(None, exc)[0])
                    continue
                except (proto.ConnectionLost, OSError, ValueError):
                    return
                if frame is None:
                    return  # clean EOF
                self._dispatch(frame[0], frame[1], send)
        finally:
            rfile.close()
            conn.close()
            with self._lock:
                self._conns.discard(conn)

    # -------------------------------------------------------------- messages
    def _dispatch(self, header: dict, payload: bytes, send) -> None:
        mtype = header.get("type")
        rid = header.get("id")
        if mtype == "submit":
            self._handle_submit(header, payload, send)
        elif mtype == "stats":
            send({
                "type": "stats_result", "id": rid,
                "worker": self.worker_id,
                "metrics": self.service.metrics_snapshot(),
                "stats": self.service.stats(),
            })
        elif mtype == "health":
            with self._lock:
                closing, requests = self._closing, self.requests
            send({
                "type": "health_result", "id": rid,
                "worker": self.worker_id,
                "t": header.get("t"),
                "t_local": time.perf_counter(),
                "closing": closing,
                "requests": requests,
            })
        elif mtype == "trace":
            doc = (
                self.service.export_trace()
                if hasattr(self.service, "export_trace") else None
            )
            send({
                "type": "trace_result", "id": rid,
                "worker": self.worker_id,
                "trace": doc,
                "open_spans": _open_spans(self.service),
                "clock": time.perf_counter(),
            })
        elif mtype == "shutdown":
            # ack first (the requester's RPC must resolve), then drain in
            # the background — drain waits on responses, including this one
            send({"type": "shutdown_result", "id": rid})
            threading.Thread(
                target=self.close, name="ingress-shutdown", daemon=True
            ).start()
        else:
            send(proto.error_message(
                rid, proto.ProtocolError(f"unknown message type {mtype!r}")
            )[0])

    def _handle_submit(self, header: dict, payload: bytes, send) -> None:
        rid = header.get("id")
        with self._lock:
            if self._closing:
                # drain-then-reject: late submits get the same typed error
                # a local caller gets after close(), not a dead socket
                send(proto.error_message(rid, ServiceClosed(
                    "worker host is draining for shutdown"
                ))[0])
                return
            self._outstanding += 1
            self.requests += 1

        obs = self._obs
        span = None
        plan_name = None
        if obs is not None:
            span = obs.begin("ingress", trace=header.get("trace"),
                             worker=self.worker_id)
            plan_name = (header.get("plan") or {}).get("name")

        def stage(name: str):
            return obs.stage(span, name, plan_name) if obs is not None else _NULL

        def reply(message) -> None:
            # the response is written BEFORE the outstanding count drops:
            # close() waiting on zero therefore waits for the bytes, which
            # is what "every client future resolves" means on the wire
            try:
                with stage("reply"):
                    send(*message())
            finally:
                if span is not None:
                    obs.end(span)
                with self._lock:
                    self._outstanding -= 1
                    self._drained.notify_all()

        try:
            with stage("recv"):
                plan = proto.plan_from_wire(header.get("plan") or {})
                img = proto.decode_tensor(header.get("tensor") or {}, payload)
                fut = self.service.submit_plan(
                    img, plan,
                    deadline_ms=header.get("deadline_ms"),
                    tag=header.get("tag"),
                    tenant=header.get("tenant"),
                    priority=header.get("priority", PRIORITY_NORMAL),
                    _trace=header.get("trace"),
                )
        except BaseException as exc:  # noqa: BLE001 — typed over the wire
            reply(lambda: proto.error_message(rid, exc))
            return

        def done(f) -> None:
            exc = f.exception()
            if exc is None:
                reply(lambda: proto.result_message(rid, f.result()))
            else:
                reply(lambda: proto.error_message(rid, exc))

        fut.add_done_callback(done)

    # ------------------------------------------------------------- lifecycle
    def _close_listener(self) -> None:
        # shutdown() before close(): on Linux, close() alone does not wake
        # a thread blocked in accept() — the stuck syscall keeps the socket
        # description (and the LISTEN port) alive after the fd is gone.
        # shutdown() fails accept() with EINVAL, so the thread exits and
        # the port is actually released.
        try:
            self._listener.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._listener.close()
        except OSError:
            pass

    def close(self, timeout: float = 60.0) -> None:
        """Drain-then-reject shutdown; idempotent (later calls wait for the
        first to finish)."""
        with self._lock:
            first = not self._closing
            self._closing = True
        if not first:
            self._closed.wait(timeout)
            return
        # 1) no new connections
        self._close_listener()
        # 2) drain: every accepted submit writes its response (the service
        #    is still open, so in-flight work completes normally)
        deadline = time.monotonic() + timeout
        with self._lock:
            while self._outstanding > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._drained.wait(remaining)
        # 3) the service itself (drains its batcher; idempotent)
        self.service.close()
        # 4) sockets — clients have all their responses by now
        with self._lock:
            conns = list(self._conns)
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            c.close()
        self._closed.set()

    def kill(self) -> None:
        """Abrupt death for chaos tests: drop every socket with no drain
        and no typed goodbye — in-flight remote callers see
        :class:`ConnectionLost`, exactly like a SIGKILL'd process."""
        with self._lock:
            self._closing = True
            conns = list(self._conns)
        self._close_listener()
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            c.close()
        self.service.close()
        self._closed.set()

    def wait_closed(self, timeout: float | None = None) -> bool:
        return self._closed.wait(timeout)

    def __enter__(self) -> "WorkerHost":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ------------------------------------------------------------- configuration
def config_from_json(d: dict) -> ServiceConfig:
    """A ServiceConfig from a JSON-safe dict (the subprocess handshake).
    Only wire-expressible knobs are mapped; unknown keys are ignored, the
    same additive-evolution rule the protocol itself follows."""
    kw: dict = {}
    if "buckets" in d:
        kw["buckets"] = tuple((int(h), int(w)) for h, w in d["buckets"])
    for k in ("max_batch", "cache_size", "shard"):
        if d.get(k) is not None:
            kw[k] = int(d[k])
    for k in ("window_ms", "default_deadline_ms"):
        if d.get(k) is not None:
            kw[k] = float(d[k])
    if "max_queue" in d:
        kw["max_queue"] = None if d["max_queue"] is None else int(d["max_queue"])
    for k in ("backend",):
        if d.get(k) is not None:
            kw[k] = d[k]
    for k in ("rle_gate", "adaptive_window"):
        if d.get(k) is not None:
            kw[k] = bool(d[k])
    if d.get("interpret") is not None:
        kw["interpret"] = bool(d["interpret"])
    if d.get("tenants"):
        kw["tenants"] = {
            name: TenantQuota(
                max_outstanding=q.get("max_outstanding"),
                weight=float(q.get("weight", 1.0)),
            )
            for name, q in d["tenants"].items()
        }
    if d.get("brownout") is False:
        kw["brownout"] = None
    if d.get("faults"):
        kw["faults"] = FaultPlan(**d["faults"])
    if d.get("obs"):
        from repro.obs import ObsConfig
        kw["obs"] = ObsConfig()
    return ServiceConfig(**kw)


def _local_tpu_chips() -> int:
    """TPU chips on this host, counted from their device nodes: the parent
    must not load the TPU runtime to ask, or it would hold every chip."""
    return len(glob.glob("/dev/accel[0-9]*")) or len(
        glob.glob("/dev/vfio/[0-9]*")
    )


def _free_port(host: str = "127.0.0.1") -> int:
    with socket.socket() as s:
        s.bind((host, 0))
        return s.getsockname()[1]


def chip_env(worker_id: int, env: dict) -> dict:
    """Environment that gives worker ``worker_id`` TPU chip ``worker_id``
    and no other, before the child imports JAX; empty when the child will
    not hold a chip (no chips here, or ``JAX_PLATFORMS`` leaves TPU out).

    A chip belongs to one process at a time, so a worker id past the last
    chip is refused rather than left to fail or hang on the chip's lock.
    """
    platforms = env.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return {}
    chips = _local_tpu_chips()
    if chips == 0:
        return {}
    if not 0 <= worker_id < chips:
        raise ValueError(
            f"ingress worker {worker_id} would hold a TPU chip, but this "
            f"host has {chips}: one chip-holding worker per chip, ids "
            f"0..{chips - 1}"
        )
    port = _free_port()
    return {
        "TPU_VISIBLE_CHIPS": str(worker_id),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_PORT": str(port),
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
    }


_STDERR_TAIL_LINES = 40


def spawn_worker(config: dict | None = None, *, worker_id: int = 0,
                 host: str = "127.0.0.1", env: dict | None = None,
                 timeout: float = 120.0):
    """Launch a worker subprocess and wait for its READY handshake.
    Returns ``(Popen, (host, port))``. The child inherits this process's
    environment (plus ``PYTHONPATH`` pointing at this repro checkout, so
    callers don't have to re-derive it, and on a TPU host the variables
    that give it chip ``worker_id`` alone, see :func:`chip_env`). A
    failed start raises with the tail of the child's stderr."""
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    child_env = dict(os.environ)
    if env:
        child_env.update(env)
    child_env.update(chip_env(worker_id, child_env))
    pp = child_env.get("PYTHONPATH", "")
    if src_root not in pp.split(os.pathsep):
        child_env["PYTHONPATH"] = (
            f"{src_root}{os.pathsep}{pp}" if pp else src_root
        )
    cfg = dict(config or {})
    cfg.setdefault("shard", worker_id)  # labels the worker's trace lane
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.serve.ingress.worker",
         "--host", host, "--config", json.dumps(cfg),
         "--worker-id", str(worker_id)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, env=child_env,
    )
    # drain stderr for the worker's whole life (a full pipe would block
    # it), keeping the last lines for the error below
    tail: collections.deque = collections.deque(maxlen=_STDERR_TAIL_LINES)
    drain = threading.Thread(
        target=tail.extend, args=(proc.stderr,),
        name=f"ingress-worker-{worker_id}-stderr", daemon=True,
    )
    drain.start()

    def failed(what: str) -> RuntimeError:
        proc.kill()
        proc.wait(timeout=30)
        drain.join(timeout=5)
        detail = "".join(tail).rstrip()
        return RuntimeError(
            f"ingress worker {worker_id} {what} (returncode {proc.returncode})"
            + (f"; stderr tail:\n{detail}" if detail else "")
        )

    deadline = time.monotonic() + timeout
    while True:
        line = proc.stdout.readline()
        if not line:
            raise failed("exited before READY")
        if line.startswith(READY_SENTINEL):
            _, h, p = line.split()
            return proc, (h, int(p))
        if time.monotonic() > deadline:
            raise failed("READY timeout")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="morphology ingress worker")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--config", default="{}",
                    help="JSON ServiceConfig subset (see config_from_json)")
    ap.add_argument("--worker-id", type=int, default=None)
    ap.add_argument("--sharded", action="store_true",
                    help="wrap a ShardedMorphService over all local devices")
    args = ap.parse_args(argv)
    enable_compile_cache()
    cfg = config_from_json(json.loads(args.config))
    if args.sharded:
        from repro.shard.router import ShardedMorphService
        service = ShardedMorphService(cfg)
    else:
        service = MorphService(cfg)
    host = WorkerHost(
        service, host=args.host, port=args.port, worker_id=args.worker_id
    )
    print(f"{READY_SENTINEL} {host.address[0]} {host.address[1]}", flush=True)
    try:
        while not host.wait_closed(1.0):
            pass
    except KeyboardInterrupt:
        host.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
