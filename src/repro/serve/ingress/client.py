"""Ingress client: futures over the wire, typed errors reconstructed.

:class:`Connection` is the low-level unit — one socket, one reader thread,
a request-id -> Future map. Submits return immediately with a
``concurrent.futures.Future`` that the reader thread resolves when the
matching ``result``/``error`` frame arrives, so the remote API is
shape-identical to the local one: ``submit_plan(img, plan) -> Future``
resolving to an array or ``{name: array}`` dict, and every failure is the
*same* typed exception a local caller would catch (``QuotaExceeded`` with
its ``.tenant``, ``DeadlineExceeded``, ``ServiceClosed``, …) rebuilt by
``proto.decode_error``. A dead transport fails every outstanding future
exactly once with :class:`ConnectionLost` — no future ever hangs on a
vanished worker.

:class:`IngressClient` pools ``Connection``s round-robin (one socket
serializes frame writes; several keep a multi-MB image upload from
head-of-line-blocking everyone else) and adds the synchronous conveniences
(``run``, ``run_plan``, ``run_batch``, ``stats``) mirroring the service
API. The frontier's per-worker links are plain ``Connection``s too — one
transport implementation for every hop of the ingress stack.
"""
from __future__ import annotations

import itertools
import socket
import threading
import time
from concurrent.futures import Future

import numpy as np

from repro.serve.ingress import proto
from repro.serve.morph.plans import single_op_plan
from repro.serve.morph.tenancy import PRIORITY_NORMAL


class Connection:
    """One protocol connection. Thread-safe: submits may come from any
    thread; the dedicated reader thread resolves futures."""

    def __init__(self, address: tuple[str, int], *,
                 connect_timeout: float = 10.0):
        self.address = (address[0], int(address[1]))
        self.sock = socket.create_connection(
            self.address, timeout=connect_timeout
        )
        self.sock.settimeout(None)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._rfile = self.sock.makefile("rb")
        self._wlock = threading.Lock()
        self._lock = threading.Lock()
        self._pending: dict[int, Future] = {}
        self._ids = itertools.count(1)
        self.closed = False
        # worker perf_counter minus the midpoint of our send/recv clocks,
        # measured by ping(); the frontier uses it to shift worker trace
        # timestamps onto its own timebase
        self.clock_offset_s: float | None = None
        self._reader = threading.Thread(
            target=self._read_loop, name="ingress-reader", daemon=True
        )
        self._reader.start()

    # --------------------------------------------------------------- reading
    def _read_loop(self) -> None:
        while True:
            try:
                frame = proto.read_frame(self._rfile)
            except Exception as exc:  # noqa: BLE001 — transport is dead
                self._fail_all(self._as_lost(exc))
                return
            if frame is None:
                self._fail_all(proto.ConnectionLost(
                    f"connection to {self.address} closed by peer"
                ))
                return
            header, payload = frame
            rid = header.get("id")
            with self._lock:
                fut = self._pending.pop(rid, None)
            if fut is None:
                continue  # response for a request nobody waits on anymore
            mtype = header.get("type")
            if mtype == "error":
                fut.set_exception(proto.decode_error(header.get("error") or {}))
            elif mtype == "result":
                fut.set_result(
                    proto.decode_result(header.get("result") or {}, payload)
                )
            else:
                fut.set_result(header)  # raw RPC (stats/health/trace/…)

    @staticmethod
    def _as_lost(exc: BaseException) -> proto.ConnectionLost:
        if isinstance(exc, proto.ConnectionLost):
            return exc
        lost = proto.ConnectionLost(f"transport error: {exc}")
        lost.__cause__ = exc
        return lost

    def _fail_all(self, exc: BaseException) -> None:
        with self._lock:
            self.closed = True
            pending, self._pending = self._pending, {}
        for fut in pending.values():
            # each future was popped exactly once; set_exception is safe
            fut.set_exception(exc)
        try:
            self.sock.close()
        except OSError:
            pass

    # --------------------------------------------------------------- writing
    def _register(self) -> tuple[int, Future]:
        with self._lock:
            if self.closed:
                raise proto.ConnectionLost(
                    f"connection to {self.address} is closed"
                )
            rid = next(self._ids)
            fut: Future = Future()
            self._pending[rid] = fut
        return rid, fut

    def _send(self, rid: int, header: dict, payload=b"") -> None:
        try:
            with self._wlock:
                proto.send_frame(self.sock, header, payload)
        except OSError as exc:
            with self._lock:
                self._pending.pop(rid, None)
            raise self._as_lost(exc) from None

    # ------------------------------------------------------------------- API
    def submit_plan(self, img, plan, *, deadline_ms: float | None = None,
                    tag: str | None = None, tenant: str | None = None,
                    priority: int = PRIORITY_NORMAL,
                    trace: int | None = None) -> Future:
        """Submit one image; the Future resolves with the decoded result
        or raises the reconstructed typed error."""
        spec = plan if isinstance(plan, dict) else proto.plan_to_wire(plan)
        rid, fut = self._register()
        header, payload = proto.submit_message(
            rid, spec, np.asarray(img), deadline_ms=deadline_ms, tag=tag,
            tenant=tenant, priority=priority, trace=trace,
        )
        self._send(rid, header, payload)
        return fut

    def rpc(self, mtype: str, *, timeout: float = 30.0, **fields) -> dict:
        """Synchronous control-plane round trip (stats/health/trace/…)."""
        rid, fut = self._register()
        self._send(rid, {"type": mtype, "id": rid, **fields})
        return fut.result(timeout)

    def ping(self, *, timeout: float = 30.0) -> dict:
        """Health round trip; as a side effect measures the peer clock
        offset (NTP-style: the peer's clock is read at the midpoint of our
        send/receive timestamps, the unbiased estimate for a symmetric
        link — and loopback is as symmetric as links get)."""
        t0 = time.perf_counter()
        h = self.rpc("health", timeout=timeout, t=t0)
        t1 = time.perf_counter()
        if h.get("t_local") is not None:
            self.clock_offset_s = h["t_local"] - (t0 + t1) / 2.0
        return h

    def close(self) -> None:
        self._fail_all(proto.ConnectionLost(
            f"connection to {self.address} closed locally"
        ))

    def __enter__(self) -> "Connection":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class IngressClient:
    """Application-facing handle on an ingress endpoint (a worker host or
    a frontier — same protocol either way)."""

    def __init__(self, address: tuple[str, int], *, pool: int = 2,
                 connect_timeout: float = 10.0):
        if pool < 1:
            raise ValueError("pool must be >= 1")
        self._conns = [
            Connection(address, connect_timeout=connect_timeout)
            for _ in range(pool)
        ]
        self._rr = itertools.count()

    def _conn(self) -> Connection:
        n = len(self._conns)
        start = next(self._rr)
        for i in range(n):
            c = self._conns[(start + i) % n]
            if not c.closed:
                return c
        raise proto.ConnectionLost("every pooled connection is closed")

    # ------------------------------------------------------------ data plane
    def submit(self, img, op: str = "erode", se=(3, 3), **kw) -> Future:
        return self.submit_plan(img, single_op_plan(op, se), **kw)

    def submit_plan(self, img, plan, **kw) -> Future:
        return self._conn().submit_plan(img, plan, **kw)

    def run(self, img, op: str = "erode", se=(3, 3), **kw):
        return self.submit(img, op, se, **kw).result()

    def run_plan(self, img, plan, **kw):
        return self.submit_plan(img, plan, **kw).result()

    def run_batch(self, imgs, plan, **kw) -> list:
        futures = [self.submit_plan(im, plan, **kw) for im in imgs]
        return [f.result() for f in futures]

    # --------------------------------------------------------- control plane
    def stats(self, *, timeout: float = 30.0) -> dict:
        return self._conn().rpc("stats", timeout=timeout).get("stats") or {}

    def metrics_snapshot(self, *, timeout: float = 30.0) -> dict:
        return self._conn().rpc("stats", timeout=timeout).get("metrics") or {}

    def health(self, *, timeout: float = 30.0) -> dict:
        return self._conn().ping(timeout=timeout)

    def export_trace(self, *, timeout: float = 30.0) -> dict | None:
        return self._conn().rpc("trace", timeout=timeout).get("trace")

    def shutdown_server(self, *, timeout: float = 30.0) -> None:
        """Ask the remote host to drain and close (its drain-then-reject
        shutdown; this client's outstanding futures resolve first)."""
        self._conn().rpc("shutdown", timeout=timeout)

    def close(self) -> None:
        for c in self._conns:
            c.close()

    def __enter__(self) -> "IngressClient":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


__all__ = ["Connection", "IngressClient"]
