"""Ingress suite (ISSUE 10): wire protocol, worker hosts, frontier routing.

Three layers, pinned from the outside in:

* **proto** — framing round-trips; frozen message schemas (a key-set change
  is a protocol change and must show up here); tensor dtypes incl. bool;
  the typed error family round-trips losslessly (hypothesis over every
  wire error); version skew — unknown fields are ignored, an unknown
  version byte is answered with a typed ``ProtocolError`` on a surviving
  connection, never a drop;
* **worker** — a live ``WorkerHost`` serves bit-exact results; typed
  rejections (``UnknownPlan``, ``DeadlineExceeded``, ``QuotaExceeded``
  with its ``.tenant``) reconstruct client-side; drain-then-reject
  ``close()`` resolves every outstanding future exactly once with a result
  or ``ServiceClosed`` — never ``ConnectionLost``;
* **frontier** — crc32 affinity lands every (plan, bucket, dtype) group on
  its hash-owner worker; a killed worker's in-flight requests reroute with
  zero lost futures; a *gracefully* closing worker's traffic moves without
  callers ever seeing its ``ServiceClosed``; fleet ``stats()`` merges
  worker registries and ``export_trace()`` stitches a schema-valid
  multi-process timeline with zero open spans.

Everything runs on in-process ``WorkerHost``s over loopback sockets (real
frames, real reader threads) so the suite is tier-1; the one true
multi-*process* test (``spawn_worker`` fleet) is marked ``slow`` and runs
in the ingress CI job.
"""
import concurrent.futures
import socket
import threading
import time
import zlib

import numpy as np
import pytest

from repro.serve.ingress import proto
from repro.serve.ingress.client import Connection, IngressClient
from repro.serve.ingress.frontier import Frontier
from repro.serve.ingress.stats import merge_process_traces, shift_events
from repro.serve.ingress.worker import WorkerHost, config_from_json, spawn_worker
from repro.serve.morph import (
    DeadlineExceeded,
    FailoverPolicy,
    FaultPlan,
    MorphService,
    QuotaExceeded,
    ServeError,
    ServiceClosed,
    ServiceConfig,
    TenantQuota,
    UnknownPlan,
    get_plan,
    single_op_plan,
)

RNG = np.random.default_rng(23)


def rand(h=40, w=50, dtype=np.uint8):
    return RNG.integers(0, 255, (h, w), dtype=dtype)


def svc_cfg(**kw):
    kw.setdefault("buckets", ((64, 64),))
    kw.setdefault("window_ms", 1.0)
    return ServiceConfig(**kw)


ERODE3 = single_op_plan("erode", (3, 3))
DILATE3 = single_op_plan("dilate", (3, 3))


def owner(plan, n, bucket=(64, 64), dtype=np.uint8):
    """The crc32 hash-owner index for a group, mirroring the frontier."""
    name = plan if isinstance(plan, str) else plan.name
    token = f"{name}|{bucket}|{np.dtype(dtype).str}".encode()
    return zlib.crc32(token) % n


def poll_until(pred, timeout=20.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


# =========================================================== proto: framing
def test_frame_round_trip_header_and_payload():
    import io

    header = {"type": "submit", "id": 7, "nested": {"a": [1, 2]}}
    payload = bytes(range(256)) * 3
    buf = proto.encode_frame(header, payload)
    rfile = io.BytesIO(buf + proto.encode_frame({"type": "x"}))
    h1, p1 = proto.read_frame(rfile)
    assert h1 == header and p1 == payload
    h2, p2 = proto.read_frame(rfile)
    assert h2 == {"type": "x"} and p2 == b""
    assert proto.read_frame(rfile) is None  # clean EOF at a boundary


def test_frame_eof_mid_frame_is_connection_lost():
    import io

    buf = proto.encode_frame({"type": "submit", "id": 1}, b"abc")
    with pytest.raises(proto.ConnectionLost):
        proto.read_frame(io.BytesIO(buf[:3]))  # inside the prefix
    with pytest.raises(proto.ConnectionLost):
        proto.read_frame(io.BytesIO(buf[:-1]))  # inside the body


def test_frame_bad_magic_and_bad_lengths_are_protocol_errors():
    import io

    with pytest.raises(proto.ProtocolError):
        proto.read_frame(io.BytesIO(b"NOPE" + b"\x00" * 9))
    bad = proto._FRAME.pack(proto.MAGIC, proto.PROTOCOL_VERSION,
                            proto.MAX_HEADER + 1, 0)
    with pytest.raises(proto.ProtocolError):
        proto.read_frame(io.BytesIO(bad))


def test_unknown_version_rejected_after_frame_is_consumed():
    """The skew rule: the unparseable frame is consumed in full, the error
    is typed, and the *next* frame on the stream still reads — a v2 peer
    cannot wedge a v1 reader."""
    import io

    hdr = b'{"type": "submit"}'
    v2 = proto._FRAME.pack(proto.MAGIC, 2, len(hdr), 0) + hdr
    stream = io.BytesIO(v2 + proto.encode_frame({"type": "health", "id": 9}))
    with pytest.raises(proto.ProtocolError, match="version 2"):
        proto.read_frame(stream)
    h, _ = proto.read_frame(stream)
    assert h == {"type": "health", "id": 9}


def test_unknown_header_fields_are_ignored():
    """Additive evolution: decoders read with .get, so headers from a
    newer peer with extra fields parse into the same results."""
    meta, payload = proto.encode_tensor(rand())
    meta["compression"] = "zstd-someday"  # future field
    np.testing.assert_array_equal(proto.decode_tensor(meta, payload),
                                  proto.decode_tensor(dict(meta), payload))
    d = proto.encode_error(DeadlineExceeded("late"))
    d["severity"] = "page"  # future field
    assert isinstance(proto.decode_error(d), DeadlineExceeded)


# ==================================================== proto: frozen schemas
def test_frozen_message_schemas():
    """Key sets are the wire contract; a change here is a protocol rev."""
    h, _ = proto.submit_message(7, {"name": "document_cleanup"},
                                np.zeros((4, 4), np.uint8))
    assert set(h) == {"type", "id", "plan", "tensor", "deadline_ms", "tag",
                      "tenant", "priority", "trace"}
    assert set(h["tensor"]) == {"dtype", "shape"}

    h, _ = proto.result_message(7, {"out": np.zeros((2, 2), np.uint8)})
    assert set(h) == {"type", "id", "result"}
    assert set(h["result"]) == {"kind", "outputs"}
    assert set(h["result"]["outputs"][0]) == {"dtype", "shape", "name"}

    h, _ = proto.error_message(7, QuotaExceeded("over", tenant="free"))
    assert set(h) == {"type", "id", "error"}
    assert set(h["error"]) == {"name", "message", "retryable", "context",
                               "extra"}
    # context-free errors omit "extra" entirely (absent, not empty)
    h, _ = proto.error_message(None, proto.ProtocolError("bad"))
    assert set(h["error"]) == {"name", "message", "retryable", "context"}


def test_plan_wire_round_trip():
    spec = proto.plan_to_wire(ERODE3)
    rebuilt = proto.plan_from_wire(spec)
    assert rebuilt == ERODE3  # frozen dataclass equality: steps and all
    assert proto.plan_from_wire({"name": "document_cleanup"}) == \
        "document_cleanup"  # bare names resolve on the worker
    assert proto.plan_to_wire("document_cleanup") == {
        "name": "document_cleanup"
    }
    with pytest.raises(proto.ProtocolError):
        proto.plan_from_wire({})


# ===================================================== proto: tensor dtypes
@pytest.mark.parametrize("dtype", [
    np.bool_, np.uint8, np.uint16, np.int32, np.int64, np.float32,
    np.float64,
])
def test_tensor_round_trip_dtypes(dtype):
    if dtype is np.bool_:
        arr = RNG.integers(0, 2, (13, 17)).astype(np.bool_)
    elif np.issubdtype(dtype, np.floating):
        arr = RNG.random((13, 17)).astype(dtype)
    else:
        arr = RNG.integers(0, 100, (13, 17)).astype(dtype)
    meta, payload = proto.encode_tensor(arr)
    out = proto.decode_tensor(meta, payload)
    assert out.dtype == arr.dtype and out.shape == arr.shape
    np.testing.assert_array_equal(out, arr)


def test_result_round_trip_dict_and_bare_array():
    d = {"edges": rand(8, 9), "mask": rand(8, 9).astype(np.bool_)}
    meta, payload = proto.encode_result(d)
    out = proto.decode_result(meta, payload)
    assert set(out) == set(d)
    for k in d:
        np.testing.assert_array_equal(out[k], d[k])
        assert out[k].dtype == d[k].dtype
    arr = rand(5, 6)
    meta, payload = proto.encode_result(arr)
    out = proto.decode_result(meta, payload)
    assert isinstance(out, np.ndarray)  # bare in, bare out
    np.testing.assert_array_equal(out, arr)


def test_tensor_short_payload_is_protocol_error():
    meta, payload = proto.encode_tensor(rand())
    with pytest.raises(proto.ProtocolError):
        proto.decode_tensor(meta, payload[:-1])


# ================================================= proto: typed error family
def _build_error(name, message, ctx, extra):
    cls = proto.WIRE_ERRORS[name]
    kw = dict(ctx)
    if name == "QuotaExceeded":
        kw["tenant"] = extra
    elif name == "BrownoutShed":
        kw.update(level=3, priority=0)
    elif name == "PoisonedRequest":
        kw["tag"] = extra
    return cls(message, **kw)


def _assert_error_round_trips(exc):
    import json

    wire = json.loads(json.dumps(proto.encode_error(exc),
                                 default=proto._json_default))
    got = proto.decode_error(wire)
    assert type(got) is type(exc)
    assert str(got) == str(exc)  # incl. the composed [ctx] suffix
    assert got.retryable == exc.retryable
    for f in proto._CONTEXT_FIELDS + proto._EXTRA_FIELDS:
        assert getattr(got, f, None) == getattr(exc, f, None), f


@pytest.mark.parametrize("name", sorted(proto.WIRE_ERRORS))
def test_error_round_trip_every_wire_type(name):
    """Deterministic sweep: every wire error, with and without context,
    reconstructs losslessly through real JSON."""
    _assert_error_round_trips(_build_error(name, "plain message", {}, "t1"))
    _assert_error_round_trips(_build_error(
        name, "with context",
        {"plan": "document_cleanup", "bucket": (64, 64), "dtype": "|u1",
         "batch": 3, "shard": 2},
        "gold",
    ))


def test_error_round_trip_all_wire_types_hypothesis():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    names = sorted(proto.WIRE_ERRORS)
    ctx = st.fixed_dictionaries({}, optional={
        "plan": st.sampled_from(["document_cleanup", "erode3x3"]),
        "bucket": st.tuples(st.integers(1, 4096), st.integers(1, 4096)),
        "dtype": st.sampled_from(["|u1", "|b1", "<f4"]),
        "batch": st.integers(1, 64),
        "shard": st.integers(0, 7),
    })

    @settings(deadline=None, max_examples=120)
    @given(name=st.sampled_from(names), message=st.text(max_size=60),
           context=ctx, extra=st.text(min_size=1, max_size=12))
    def check(name, message, context, extra):
        _assert_error_round_trips(_build_error(name, message, context, extra))

    check()


def test_unknown_error_name_degrades_to_serveerror():
    got = proto.decode_error({
        "name": "FutureFancyError", "message": "from a newer server",
        "retryable": True, "context": {"plan": "p"},
    })
    assert type(got) is ServeError
    assert got.retryable is True  # the newer peer's verdict, as data
    assert got.plan == "p"
    # and a non-ServeError on the wire names its class in the message
    d = proto.encode_error(ValueError("boom"))
    assert d["name"] == "ServeError" and "ValueError" in d["message"]


# ======================================================== worker: round trip
def test_worker_serves_bit_exact_results():
    imgs = [rand(40 + i, 50) for i in range(6)]
    with MorphService(svc_cfg()) as direct:
        refs = [direct.run_plan(im, "document_cleanup") for im in imgs]
    with WorkerHost(config=svc_cfg(), worker_id=0) as host:
        with IngressClient(host.address) as client:
            outs = [client.run_plan(im, "document_cleanup") for im in imgs]
            stats = client.stats()
            health = client.health()
    for got, ref in zip(outs, refs):
        assert set(got) == set(ref)
        for k in ref:
            np.testing.assert_array_equal(got[k], np.asarray(ref[k]))
            assert got[k].dtype == np.asarray(ref[k]).dtype
    assert stats["requests"] >= len(imgs)
    assert health["worker"] == 0 and health["closing"] is False
    assert host.requests == len(imgs)


def test_worker_reconstructs_typed_errors():
    cfg = svc_cfg(tenants={"free": TenantQuota(max_outstanding=1)},
                  faults=FaultPlan(latency_ms=80.0), window_ms=20.0)
    with WorkerHost(config=cfg) as host:
        with Connection(host.address) as conn:
            with pytest.raises(UnknownPlan):
                conn.submit_plan(rand(), "no_such_plan").result(30)
            with pytest.raises(DeadlineExceeded):
                conn.submit_plan(rand(), ERODE3, deadline_ms=0).result(30)
            # fill the free tenant's single slot (held by the 80 ms fault),
            # then overflow it — same connection, so ordering is the wire's
            first = conn.submit_plan(rand(), ERODE3, tenant="free")
            with pytest.raises(QuotaExceeded) as ei:
                conn.submit_plan(rand(), ERODE3, tenant="free").result(30)
            assert ei.value.tenant == "free"
            assert isinstance(first.result(60), np.ndarray)


def test_worker_answers_unknown_message_and_version_typed():
    """Skew over a real socket: garbage message types and future version
    bytes get typed replies and the connection keeps serving."""
    with WorkerHost(config=svc_cfg()) as host:
        s = socket.create_connection(host.address)
        rfile = s.makefile("rb")
        try:
            hdr = b'{"type": "submit", "id": 3}'
            s.sendall(proto._FRAME.pack(proto.MAGIC, 2, len(hdr), 0) + hdr)
            s.sendall(proto.encode_frame({"type": "frobnicate", "id": 4}))
            s.sendall(proto.encode_frame({"type": "health", "id": 5}))
            h1, _ = proto.read_frame(rfile)
            assert h1["type"] == "error" and h1["id"] is None
            exc = proto.decode_error(h1["error"])
            assert isinstance(exc, proto.ProtocolError)
            assert "version 2" in str(exc)
            h2, _ = proto.read_frame(rfile)
            assert h2["type"] == "error" and h2["id"] == 4
            assert isinstance(proto.decode_error(h2["error"]),
                              proto.ProtocolError)
            h3, _ = proto.read_frame(rfile)
            assert h3["type"] == "health_result" and h3["id"] == 5
        finally:
            s.close()


def test_worker_ignores_unknown_submit_fields():
    with WorkerHost(config=svc_cfg()) as host:
        with Connection(host.address) as conn:
            img = rand()
            header, payload = proto.submit_message(
                None, proto.plan_to_wire(ERODE3), img
            )
            header["routing_hints"] = {"zone": "us-east1-b"}  # future field
            rid, fut = conn._register()
            header["id"] = rid
            conn._send(rid, header, payload)
            assert isinstance(fut.result(30), np.ndarray)


# =============================================== worker: drain-then-reject
def test_close_resolves_every_future_exactly_once():
    """The ISSUE 10 shutdown satellite: close() mid-request drains accepted
    work to results and answers late work with typed ServiceClosed; no
    future resolves twice, none hangs, and none sees ConnectionLost."""
    cfg = svc_cfg(faults=FaultPlan(latency_ms=120.0), window_ms=1.0)
    resolved = []
    rlock = threading.Lock()

    def track(fut):
        with rlock:
            resolved.append(fut)

    with WorkerHost(config=cfg) as host:
        conn = Connection(host.address)
        early = [conn.submit_plan(rand(40 + i, 50), ERODE3)
                 for i in range(6)]
        # "accepted" means read off the socket and admitted, not merely in
        # the TCP buffer — wait for that before closing, so the early/late
        # split below is deterministic
        assert poll_until(lambda: host.requests == len(early), timeout=10)
        closer = threading.Thread(target=host.close)
        closer.start()
        # once the closing flag is up, every further submit must be
        # rejected typed — never raced into the batcher, never dropped
        assert poll_until(lambda: host._closing, timeout=10)
        late = [conn.submit_plan(rand(), ERODE3) for _ in range(6)]
        for f in early + late:
            f.add_done_callback(track)
        closer.join(timeout=60)
        assert not closer.is_alive()
        assert host.wait_closed(10)

    # every response was written before the host closed; the client's
    # reader thread may still be delivering the last ones
    _, not_done = concurrent.futures.wait(early + late, timeout=30)
    assert not not_done
    results, closed_errs = 0, 0
    for f in early + late:
        exc = f.exception(timeout=0)
        if exc is None:
            assert isinstance(f.result(), np.ndarray)
            results += 1
        else:
            assert isinstance(exc, ServiceClosed), exc
            assert not isinstance(exc, proto.ConnectionLost)
            closed_errs += 1
    assert results >= len(early)  # accepted work drained to real results
    assert closed_errs == len(late)  # post-flag work rejected typed
    assert len(resolved) == len(early) + len(late)  # exactly once each
    conn.close()


def test_shutdown_rpc_drains_remotely():
    with WorkerHost(config=svc_cfg()) as host:
        with IngressClient(host.address) as client:
            assert isinstance(client.run(rand(), "erode", (3, 3)),
                              np.ndarray)
            client.shutdown_server()
        assert host.wait_closed(30)
    # post-close dials are refused at the socket — the listener is gone
    with pytest.raises(OSError):
        socket.create_connection(host.address, timeout=2.0)


# ========================================================= frontier: routing
def two_hosts(cfgs=None):
    cfgs = cfgs or [svc_cfg(shard=i) for i in range(2)]
    return [WorkerHost(config=c, worker_id=i) for i, c in enumerate(cfgs)]


def test_frontier_affinity_and_bit_exact():
    """Every (plan, bucket, dtype) group lands on its crc32 owner — the
    cross-process extension of the shard router's affinity — and results
    are bit-exact vs a direct MorphService."""
    hosts = two_hosts()
    imgs = [rand(40 + i, 50) for i in range(4)]
    with MorphService(svc_cfg()) as direct:
        refs = {
            "erode": [np.asarray(direct.run_plan(im, ERODE3)) for im in imgs],
            "dilate": [np.asarray(direct.run_plan(im, DILATE3)) for im in imgs],
        }
    try:
        with Frontier([h.address for h in hosts],
                      buckets=((64, 64),)) as front:
            for plan, key in ((ERODE3, "erode"), (DILATE3, "dilate")):
                for im, ref in zip(imgs, refs[key]):
                    np.testing.assert_array_equal(
                        np.asarray(front.run_plan(im, plan)), ref
                    )
            stats = front.stats()
        # affinity: each plan's traffic went only to its hash owner
        expected = [0, 0]
        for plan in (ERODE3, DILATE3):
            expected[owner(plan, 2)] += len(imgs)
        assert [h.requests for h in hosts] == expected
        assert stats["workers"] == 2 and stats["healthy_workers"] == 2
        assert stats["requests"] == 2 * len(imgs)
    finally:
        for h in hosts:
            h.close()


def test_frontier_worker_kill_reroutes_zero_lost():
    """Chaos: SIGKILL-equivalent on the owner worker mid-flight. Every
    future resolves with the bit-exact result via the survivor; the dead
    worker reads open in fleet health; merged stats still compute."""
    victim = owner(ERODE3, 2)
    cfgs = [svc_cfg(shard=i) for i in range(2)]
    cfgs[victim] = svc_cfg(shard=victim, faults=FaultPlan(latency_ms=150.0))
    hosts = two_hosts(cfgs)
    imgs = [rand(40 + i, 50) for i in range(8)]
    with MorphService(svc_cfg()) as direct:
        refs = [np.asarray(direct.run_plan(im, ERODE3)) for im in imgs]
    try:
        with Frontier([h.address for h in hosts],
                      buckets=((64, 64),),
                      failover=FailoverPolicy(probe_interval_s=600.0)) as front:
            futs = [front.submit_plan(im, ERODE3) for im in imgs]
            hosts[victim].kill()  # no drain, no typed goodbye
            results = [f.result(timeout=120) for f in futs]
            for got, ref in zip(results, refs):
                np.testing.assert_array_equal(np.asarray(got), ref)
            # late traffic routes straight to the survivor
            late = np.asarray(front.run_plan(imgs[0], ERODE3))
            np.testing.assert_array_equal(late, refs[0])
            stats = front.stats()
        assert stats["health"][victim]["state"] == "open"
        assert stats["healthy_workers"] == 1
        assert stats["per_worker"][victim] is None  # dead, not required
        assert stats["per_worker"][1 - victim] is not None
        assert stats["requests"] == len(imgs) + 1
        assert hosts[1 - victim].requests >= len(imgs)
    finally:
        for h in hosts:
            h.kill() if not h._closed.is_set() else None


def test_frontier_graceful_worker_close_moves_traffic():
    """A worker announcing its drain (typed ServiceClosed) is a routing
    event, not a caller-visible failure: the frontier marks it dead and
    moves the group to the survivor — every caller gets a result."""
    victim = owner(ERODE3, 2)
    hosts = two_hosts()
    imgs = [rand(40 + i, 50) for i in range(6)]
    with MorphService(svc_cfg()) as direct:
        refs = [np.asarray(direct.run_plan(im, ERODE3)) for im in imgs]
    try:
        with Frontier([h.address for h in hosts],
                      buckets=((64, 64),),
                      failover=FailoverPolicy(probe_interval_s=600.0)) as front:
            np.testing.assert_array_equal(
                np.asarray(front.run_plan(imgs[0], ERODE3)), refs[0]
            )
            hosts[victim].close()  # graceful: drain-then-reject
            for im, ref in zip(imgs, refs):
                np.testing.assert_array_equal(
                    np.asarray(front.run_plan(im, ERODE3)), ref
                )
            assert front.stats()["health"][victim]["state"] == "open"
    finally:
        for h in hosts:
            h.close()


# =================================================== frontier: stats/traces
def test_frontier_merges_stats_and_cross_process_trace():
    from repro.obs import ObsConfig, validate_chrome_trace

    cfgs = [svc_cfg(shard=i, obs=ObsConfig()) for i in range(2)]
    hosts = two_hosts(cfgs)
    try:
        with Frontier([h.address for h in hosts], buckets=((64, 64),),
                      obs=ObsConfig()) as front:
            for i in range(4):
                front.run_plan(rand(40 + i, 50), ERODE3)
                front.run_plan(rand(40 + i, 50), DILATE3)
            stats = front.stats()
            doc = front.export_trace()
            open_spans = front.open_spans()
        assert stats["requests"] == 8
        assert stats["batches"] >= 1  # merged from worker registries
        assert stats["p99_ms"] > 0.0
        assert set(stats["cache"]) >= {"size", "hits", "misses"}
        assert "tenants" in stats["resilience"]
        assert validate_chrome_trace(doc) == []
        pids = {e.get("pid") for e in doc["traceEvents"]}
        assert "frontier" in pids and len(pids) >= 3  # both worker lanes
        # frontier-minted IDs must appear on worker-side spans: the trace
        # crosses the process boundary, not just the function boundary
        by_trace = {}
        for ev in doc["traceEvents"]:
            t = (ev.get("args") or {}).get("trace_id")
            if t is not None:
                by_trace.setdefault(t, set()).add(ev.get("pid"))
        assert any(len(p) >= 2 for p in by_trace.values()), by_trace
        assert open_spans == 0
    finally:
        for h in hosts:
            h.close()


def test_trace_shift_clamps_and_skips_metadata():
    evs = [{"ph": "M", "ts": 0, "pid": "0", "name": "process_name"},
           {"ph": "X", "ts": 5.0, "dur": 1.0, "pid": "0", "name": "s"}]
    out = shift_events(evs, offset_s=1.0)
    assert out[0]["ts"] == 0  # metadata untouched
    assert out[1]["ts"] == 0.0  # clamped, not negative
    doc = merge_process_traces(
        [{"ph": "X", "ts": 9.0, "dur": 1.0, "pid": "f", "name": "hop"}],
        [({"traceEvents": evs}, 0.0), (None, None)],
    )
    assert [e["ts"] for e in doc["traceEvents"]] == [0, 5.0, 9.0]  # sorted


def test_frontier_serve_composes_recursively():
    """client -> WorkerHost(Frontier) -> workers: one protocol end to end."""
    hosts = two_hosts()
    img = rand()
    with MorphService(svc_cfg()) as direct:
        ref = np.asarray(direct.run_plan(img, ERODE3))
    try:
        with Frontier([h.address for h in hosts],
                      buckets=((64, 64),)) as front:
            edge = front.serve()
            try:
                with IngressClient(edge.address) as client:
                    np.testing.assert_array_equal(
                        np.asarray(client.run_plan(img, ERODE3)), ref
                    )
                    stats = client.stats()
                assert stats["workers"] == 2  # fleet stats over the wire
            finally:
                edge.close()
    finally:
        for h in hosts:
            h.close()


# ===================================================== subprocess fleet (CI)
@pytest.mark.slow
def test_subprocess_fleet_round_trip_and_kill():
    """The real thing: two worker *processes*, spawned and handshaken,
    serving bit-exact results; killing one reroutes with zero lost
    futures. Slow (two interpreter boots + compiles); the ingress CI job
    runs it."""
    wcfg = {"buckets": [[64, 64]], "window_ms": 1.0, "interpret": True}
    procs, addrs = [], []
    try:
        for i in range(2):
            proc, addr = spawn_worker(dict(wcfg), worker_id=i)
            procs.append(proc)
            addrs.append(addr)
        imgs = [rand(40 + i, 50) for i in range(6)]
        with MorphService(svc_cfg(interpret=True)) as direct:
            refs = [np.asarray(direct.run_plan(im, ERODE3)) for im in imgs]
        with Frontier(addrs,
                      buckets=((64, 64),),
                      failover=FailoverPolicy(probe_interval_s=600.0)) as front:
            for im, ref in zip(imgs, refs):
                np.testing.assert_array_equal(
                    np.asarray(front.run_plan(im, ERODE3)), ref
                )
            victim = owner(ERODE3, 2)
            futs = [front.submit_plan(im, ERODE3) for im in imgs]
            procs[victim].kill()
            for f, ref in zip(futs, refs):
                np.testing.assert_array_equal(
                    np.asarray(f.result(timeout=120)), ref
                )
            assert front.stats()["healthy_workers"] >= 1
    finally:
        for p in procs:
            p.kill()
            p.wait(timeout=30)


def test_chip_env_one_chip_per_worker(monkeypatch):
    from repro.serve.ingress import worker

    monkeypatch.setattr(worker, "_local_tpu_chips", lambda: 2)
    env = worker.chip_env(1, {})
    assert env["TPU_VISIBLE_CHIPS"] == "1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_ADDRESSES"] == f"localhost:{env['TPU_PROCESS_PORT']}"
    with pytest.raises(ValueError, match="one chip-holding worker per chip"):
        worker.chip_env(2, {})
    # a worker kept off the TPU holds no chip, so any id is fine
    assert worker.chip_env(7, {"JAX_PLATFORMS": "cpu"}) == {}
    monkeypatch.setattr(worker, "_local_tpu_chips", lambda: 0)
    assert worker.chip_env(7, {}) == {}


def test_spawn_worker_failure_shows_stderr_tail():
    # the child raises while building its service; the parent's error
    # carries the child's own traceback instead of a bare "exited"
    with pytest.raises(RuntimeError, match="backend must be one of"):
        spawn_worker({"backend": "no-such-backend"}, worker_id=0,
                     env={"JAX_PLATFORMS": "cpu"}, timeout=120)


def test_config_from_json_maps_and_ignores_unknowns():
    cfg = config_from_json({
        "buckets": [[64, 64], [128, 128]], "max_batch": 4,
        "window_ms": 2.5, "tenants": {"gold": {"max_outstanding": 8,
                                               "weight": 4.0}},
        "brownout": False, "interpret": True,
        "a_future_knob": {"x": 1},  # ignored, like unknown wire fields
    })
    assert cfg.buckets == ((64, 64), (128, 128))
    assert cfg.max_batch == 4 and cfg.window_ms == 2.5
    assert cfg.tenants["gold"].max_outstanding == 8
    assert cfg.brownout is None and cfg.interpret is True
