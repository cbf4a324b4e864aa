"""Ingress wire protocol: versioned length-prefixed JSON + raw-tensor frames.

One frame is::

    !4s B I I         magic b"MRF1" | version | header_len | payload_len
    header_len bytes  UTF-8 JSON header (the message)
    payload_len bytes raw little-endian tensor bytes (may be empty)

The JSON header carries everything structured (message type, request id,
plan spec, tensor dtype/shape, tenancy fields, error payloads); the binary
payload carries only tensor data, so a 4 Mpx uint8 image costs 4 MB on the
wire, not 4 MB of base64. The version byte sits *outside* the JSON: a
reader can always finish framing a message it refuses to parse, reply with
a typed :class:`ProtocolError`, and keep the connection — which is what
the version-skew tests pin down. Skew rules:

* unknown **fields** in a known-version header are ignored (decoders read
  with ``.get``), so additive protocol evolution is free;
* an unknown **version** is rejected with a typed :class:`ProtocolError`
  after the frame is consumed — never by dropping the connection.

Message types (the frozen-schema tests snapshot these key sets):

* ``submit``    — plan spec + tensor meta (+ payload), ``deadline_ms``,
  ``tag``, ``tenant``, ``priority``, ``trace``;
* ``result``    — named output tensors, one after another in the payload;
* ``error``     — a :func:`encode_error` dict; :func:`decode_error`
  reconstructs the *same* typed exception client-side;
* ``stats`` / ``stats_result`` — a worker's ``metrics_snapshot()`` (the
  cross-process merge unit) plus its ``stats()`` view;
* ``health`` / ``health_result`` — liveness + the clock handshake
  (``t_local`` is the worker's ``perf_counter``) the frontier uses to
  shift worker trace timestamps onto its own timebase;
* ``trace`` / ``trace_result`` — a worker's Chrome-trace export + open
  span count;
* ``shutdown`` / ``shutdown_result`` — ask a worker host to drain and
  close (the remote handle on its drain-then-reject shutdown).

Payloads travel as buffers, never as joined or sliced ``bytes``: a frame
goes out as ``[prefix + header, *payload buffers]`` (:func:`frame_buffers`,
written by :func:`send_frame` with ``sendmsg``), where each buffer is a
byte view of a C-contiguous array's own memory (:func:`encode_tensor`
copies an array only when it is not contiguous); it comes in with its
payload ``readinto`` one fresh buffer (:func:`read_frame`), and
:func:`decode_tensor` / :func:`decode_result` return views of that buffer.
A page crosses a hop with no user-space copy beyond the socket's own.
Decoded arrays alias their frame's buffer, so buffers are never reused.

Error transport is lossless by construction: :func:`decode_error` rebuilds
the exception via ``cls.__new__`` + attribute restore instead of calling
``__init__``, so the message (which already embeds the ``[plan=…, …]``
context suffix composed at raise time) is not re-composed, and
``type(exc)``, ``str(exc)``, ``retryable``, the five context fields, and
the subtype extras (``tenant``, ``level``, ``priority``, ``tag``) all
round-trip bit-for-bit. Unknown error type names degrade to the base
:class:`ServeError` with ``retryable`` carried as data — old clients stay
correct against newer servers.
"""
from __future__ import annotations

import json
import struct

import numpy as np

from repro.serve.morph.plans import Plan, Step, UnknownPlan, get_plan
from repro.serve.morph.resilience import (
    BrownoutShed,
    DeadlineExceeded,
    ExecutorError,
    InjectedFault,
    Overloaded,
    PoisonedRequest,
    QuotaExceeded,
    ServeError,
    ServiceClosed,
    ShardUnavailable,
)

PROTOCOL_VERSION = 1
MAGIC = b"MRF1"

_FRAME = struct.Struct("!4sBII")
# sanity bounds: a corrupt length prefix must fail loudly, not allocate
MAX_HEADER = 16 << 20
MAX_PAYLOAD = 1 << 30


# --------------------------------------------------------------------- errors
class ProtocolError(ServeError):
    """The peer sent something this protocol version cannot parse: bad
    magic, an unknown version byte, or a structurally invalid message.
    Not retryable — resending the same bytes cannot help."""

    retryable = False


class ConnectionLost(ServeError):
    """The transport died with requests outstanding. Retryable: the
    morphology plans are pure functions of their input, so re-running a
    request whose first attempt may or may not have executed is sound."""

    retryable = True


# ------------------------------------------------------------------- framing
def frame_buffers(header: dict, payload=b"") -> list[memoryview]:
    """One wire frame as buffers: the prefix and header in one, then the
    payload's buffers as they are, none of them copied. ``payload`` is one
    bytes-like or a sequence of them (:func:`encode_result` gives one
    buffer an output)."""
    hdr = json.dumps(header, default=_json_default).encode()
    parts = payload if isinstance(payload, (list, tuple)) else (payload,)
    bufs = [memoryview(p) for p in parts]
    plen = sum(b.nbytes for b in bufs)
    head = _FRAME.pack(MAGIC, PROTOCOL_VERSION, len(hdr), plen) + hdr
    return [memoryview(head), *(b for b in bufs if b.nbytes)]


def encode_frame(header: dict, payload=b"") -> bytes:
    """One wire frame as bytes: the buffers of :func:`frame_buffers`,
    joined (for tests and hand-built frames; the served path sends the
    buffers with :func:`send_frame`)."""
    return b"".join(frame_buffers(header, payload))


def send_frame(sock, header: dict, payload=b"") -> None:
    """Write one frame to a connected socket with ``sendmsg`` over its
    buffers, resuming after a partial send. The caller holds the
    connection's write lock, so frames never interleave."""
    bufs = frame_buffers(header, payload)
    while bufs:
        sent = sock.sendmsg(bufs)
        while bufs and sent >= bufs[0].nbytes:
            sent -= bufs.pop(0).nbytes
        if sent:
            bufs[0] = bufs[0][sent:]


def read_frame(rfile) -> tuple[dict, memoryview] | None:
    """Read one frame from a buffered binary file-like. Returns ``(header,
    payload)``, the payload a ``memoryview`` of a fresh buffer that the
    frame's bytes were read into; ``None`` on clean EOF at a frame
    boundary. Raises :class:`ProtocolError` for bad magic/version/JSON (the
    offending frame is consumed first, so the connection survives and can
    carry the typed error back) and :class:`ConnectionLost` for EOF
    mid-frame."""
    prefix = rfile.read(_FRAME.size)
    if not prefix:
        return None  # clean EOF between frames
    if len(prefix) < _FRAME.size:
        raise ConnectionLost("EOF inside a frame prefix")
    magic, version, hlen, plen = _FRAME.unpack(prefix)
    if magic != MAGIC:
        # nothing after a framing desync can be trusted; no recovery
        raise ProtocolError(f"bad frame magic {magic!r}")
    if hlen > MAX_HEADER or plen > MAX_PAYLOAD:
        raise ProtocolError(f"frame lengths out of range ({hlen}, {plen})")
    hdr = rfile.read(hlen)
    if len(hdr) < hlen:
        raise ConnectionLost("EOF inside a frame header")
    # un-zeroed: readinto fills every byte. Decoded arrays alias it and
    # live on (queues, reroutes, results), so each frame gets its own.
    payload = memoryview(np.empty(plen, np.uint8))
    got = 0
    while got < plen:
        n = rfile.readinto(payload[got:])
        if not n:
            raise ConnectionLost("EOF inside a frame payload")
        got += n
    if version != PROTOCOL_VERSION:
        raise ProtocolError(
            f"unsupported protocol version {version} "
            f"(this side speaks {PROTOCOL_VERSION})"
        )
    try:
        header = json.loads(hdr)
    except ValueError as exc:
        raise ProtocolError(f"unparseable frame header: {exc}") from None
    if not isinstance(header, dict):
        raise ProtocolError("frame header must be a JSON object")
    return header, payload


def _json_default(v):
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, np.ndarray):
        return v.tolist()
    if isinstance(v, (set, frozenset)):
        return sorted(v)
    return str(v)


# ------------------------------------------------------------------- tensors
def _encode(arr) -> tuple[dict, memoryview, int]:
    """``(meta, buffer, bytes copied)`` for one array. The buffer is a
    flat byte view of the array's own memory where it is C-contiguous, else
    of one contiguous copy. ``dtype.str`` carries the byte order, so bool
    (``|b1``) and every multi-byte dtype reconstruct exactly."""
    arr = np.asarray(arr)
    copied = 0
    if not arr.flags.c_contiguous:
        arr = np.ascontiguousarray(arr)
        copied = arr.nbytes
    meta = {"dtype": arr.dtype.str, "shape": list(arr.shape)}
    return meta, memoryview(arr.reshape(-1).view(np.uint8)), copied


def encode_tensor(arr: np.ndarray) -> tuple[dict, memoryview]:
    """``(meta, buffer)`` for one array, the buffer a flat byte view of its
    memory (of one contiguous copy where it is not C-contiguous)."""
    meta, buf, _ = _encode(arr)
    return meta, buf


def _tensor_nbytes(meta: dict) -> int:
    shape = tuple(meta["shape"])
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(meta["dtype"]).itemsize


def decode_tensor(meta: dict, buf) -> np.ndarray:
    """The array ``meta`` describes, as a view of ``buf``'s bytes."""
    dt = np.dtype(meta["dtype"])
    shape = tuple(meta["shape"])
    n = _tensor_nbytes(meta)
    buf = memoryview(buf)
    if buf.nbytes < n:
        raise ProtocolError(
            f"tensor payload short: {buf.nbytes} bytes for {shape} {dt}"
        )
    return np.frombuffer(buf[:n], dtype=dt).reshape(shape)


def encode_result(result, metrics=None) -> tuple[dict, list[memoryview]]:
    """A service result — a bare array (single-output plans) or a
    ``{name: array}`` dict — as ``(meta, buffers)``, one buffer an output.
    The meta records which shape it was so the client-side API mirrors the
    local one exactly. Where ``metrics`` (a registry) is given, the
    payload's bytes go to ``ingress.payload_bytes_sent`` and those copied
    to make an output contiguous to ``ingress.payload_bytes_copied``."""
    if isinstance(result, dict):
        items = [(str(k), v) for k, v in result.items()]
        kind = "dict"
    else:
        items = [("out", result)]
        kind = "array"
    outputs, bufs, copied = [], [], 0
    for name, arr in items:
        meta, buf, n = _encode(arr)
        meta["name"] = name
        outputs.append(meta)
        bufs.append(buf)
        copied += n
    if metrics is not None:
        metrics.counter("ingress.payload_bytes_sent").inc(
            sum(b.nbytes for b in bufs)
        )
        metrics.counter("ingress.payload_bytes_copied").inc(copied)
    return {"kind": kind, "outputs": outputs}, bufs


def decode_result(meta: dict, payload):
    """The result ``meta`` describes, each output a view of ``payload``:
    one buffer, as :func:`read_frame` gives it, or :func:`encode_result`'s
    buffers, joined first."""
    if isinstance(payload, (list, tuple)):
        payload = b"".join(payload)
    buf = memoryview(payload)
    out, off = {}, 0
    for m in meta.get("outputs", ()):
        n = _tensor_nbytes(m)
        out[m["name"]] = decode_tensor(m, buf[off:off + n])
        off += n
    if meta.get("kind") == "array":
        return next(iter(out.values()))
    return out


# -------------------------------------------------------------------- errors
# Every typed exception a service can raise, by wire name. decode_error
# falls back to ServeError for names minted by a newer peer.
WIRE_ERRORS: dict[str, type] = {
    cls.__name__: cls
    for cls in (
        ServeError, Overloaded, QuotaExceeded, BrownoutShed,
        DeadlineExceeded, ServiceClosed, ExecutorError, PoisonedRequest,
        InjectedFault, ShardUnavailable, UnknownPlan,
        ProtocolError, ConnectionLost,
    )
}

_CONTEXT_FIELDS = ("plan", "bucket", "dtype", "batch", "shard")
_EXTRA_FIELDS = ("tenant", "level", "priority", "tag")


def encode_error(exc: BaseException) -> dict:
    """Any exception as a wire dict. Typed :class:`ServeError` subclasses
    keep their exact identity; anything else (a stray ValueError inside a
    handler) degrades to the base type with the original class named in
    the message — remote callers always get *a* typed error."""
    if isinstance(exc, ServeError):
        name = type(exc).__name__
        if name not in WIRE_ERRORS:
            name = "ServeError"
        message = exc.args[0] if exc.args else str(exc)
    else:
        name = "ServeError"
        message = f"{type(exc).__name__}: {exc}"
    d: dict = {
        "name": name,
        "message": message,
        "retryable": bool(getattr(exc, "retryable", False)),
        "context": {
            k: v for k in _CONTEXT_FIELDS
            if (v := getattr(exc, k, None)) is not None
        },
    }
    extra = {
        k: v for k in _EXTRA_FIELDS
        if (v := getattr(exc, k, None)) is not None
    }
    if extra:
        d["extra"] = extra
    return d


def decode_error(d: dict) -> ServeError:
    """The typed exception back from its wire dict. Reconstruction skips
    ``__init__`` (which would re-compose the ``[ctx]`` message suffix) and
    restores attributes directly, so ``str``, type, and every field match
    the original exactly."""
    cls = WIRE_ERRORS.get(d.get("name"), ServeError)
    exc = cls.__new__(cls)
    Exception.__init__(exc, d.get("message", ""))
    ctx = d.get("context") or {}
    for k in _CONTEXT_FIELDS:
        v = ctx.get(k)
        if k == "bucket" and isinstance(v, list):
            v = tuple(v)
        setattr(exc, k, v)
    for k in _EXTRA_FIELDS:
        if k in (d.get("extra") or {}):
            setattr(exc, k, d["extra"][k])
    if cls is ServeError and "retryable" in d:
        # unknown subtype from a newer peer: honor its retryability as data
        exc.retryable = bool(d["retryable"])
    return exc


# --------------------------------------------------------------------- plans
def plan_to_wire(plan) -> dict:
    """A plan reference as a wire spec: registered plans go by name (the
    worker resolves against its own registry — a miss comes back as a
    typed :class:`UnknownPlan`), step-built plans ship their steps.
    Expression-built plans have no wire form — register them on the worker
    and submit by name."""
    if isinstance(plan, str):
        return {"name": plan}
    plan = get_plan(plan)
    if plan.steps:
        return {
            "name": plan.name,
            "steps": [
                {"op": s.op, "se": [s.se[0], s.se[1]],
                 "save_as": s.save_as, "astype": s.astype}
                for s in plan.steps
            ],
        }
    return {"name": plan.name}


def plan_from_wire(spec: dict):
    """The worker-side resolution of a wire spec: explicit steps rebuild a
    :class:`Plan`; a bare name resolves against the worker's registry
    (so ``submit_plan`` raises :class:`UnknownPlan` typed)."""
    steps = spec.get("steps")
    if steps:
        return Plan(
            str(spec.get("name") or "wire_plan"),
            tuple(
                Step(s["op"], tuple(s["se"]),
                     save_as=s.get("save_as"), astype=s.get("astype"))
                for s in steps
            ),
        )
    name = spec.get("name")
    if not name:
        raise ProtocolError("plan spec needs 'name' or 'steps'")
    return name


# ------------------------------------------------------------------ messages
def submit_message(req_id: int, plan_spec: dict, arr: np.ndarray, *,
                   deadline_ms: float | None = None, tag: str | None = None,
                   tenant: str | None = None, priority: int = 0,
                   trace: int | None = None) -> tuple[dict, memoryview]:
    meta, payload = encode_tensor(arr)
    return (
        {
            "type": "submit",
            "id": req_id,
            "plan": plan_spec,
            "tensor": meta,
            "deadline_ms": deadline_ms,
            "tag": tag,
            "tenant": tenant,
            "priority": priority,
            "trace": trace,
        },
        payload,
    )


def result_message(req_id: int, result,
                   metrics=None) -> tuple[dict, list[memoryview]]:
    meta, payload = encode_result(result, metrics)
    return {"type": "result", "id": req_id, "result": meta}, payload


def error_message(req_id, exc: BaseException) -> tuple[dict, bytes]:
    return {"type": "error", "id": req_id, "error": encode_error(exc)}, b""


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
    "WIRE_ERRORS",
    "encode_error",
    "decode_error",
    "plan_to_wire",
    "plan_from_wire",
    "submit_message",
    "result_message",
    "error_message",
]
