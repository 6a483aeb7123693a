"""Fleet wire protocol: length-prefixed frames with zero-copy payloads.

One frame = ``<u32 header_len><u64 payload_len><u32 crc32><header
JSON><payload>`` (the CRC covers header + payload).
The header is tiny routing metadata (op, request id, model, encoding,
shape); the payload is the row data — and the whole design goal is that
the payload bytes are never copied or decoded at the dispatcher:

- client side: an Arrow RecordBatch is written to an IPC stream (Arrow's
  writer appends the column *buffers* verbatim — no per-value work), a
  numpy batch rides as its raw C-order bytes via ``memoryview``;
- dispatcher: reads the header, forwards the payload memoryview to the
  chosen replica socket untouched (``fleet.dispatch`` routes on header
  fields only);
- replica: ``decode_matrix`` reconstructs the batch *over* the received
  buffer — ``np.frombuffer`` for raw f32, ``pyarrow.ipc`` over a
  ``py_buffer`` view for Arrow (both zero-copy reads; the only copy on
  the whole path is the final columnar->row-major stack at the kernel
  boundary, exactly what the in-process engine pays in ``_as_batch``).

Arrow is optional (pyarrow is an optional dependency repo-wide): the
``arrow`` encoding is negotiated by the client helper and raises cleanly
when pyarrow is absent; ``raw`` always works.

**Integrity** (docs/reliability.md "Integrity & chaos"): every frame's
prefix carries a CRC-32 (``zlib.crc32``, C-speed) over header + payload,
verified by :func:`recv_frame` before the header is even JSON-decoded.  A
mismatch raises :class:`WireCorruptError` — a :class:`WireError` subclass,
so every existing caller already treats it as peer-gone and quarantines
the connection exactly like a ``drop_connection`` fault: the dispatcher
runs its replica-death path (in-flight batch reroutes), the replica exits
its serve loop.  Length prefixes are sanity-bounded (``MAX_HEADER`` /
``MAX_PAYLOAD``) so a garbage prefix can never make the reader allocate
an absurd buffer, and a header that fails to JSON-decode is a
:class:`WireError` too — garbage fails ONE connection, never the fleet.
The ``wire.frame`` fault seam in :func:`send_frame` injects deterministic
byte flips (``corrupt`` kind) after the CRC is computed, which is how the
chaos harness proves the detection end to end.

**Degraded links** (docs/reliability.md "Degraded networks"): the same
``wire.frame`` seam shapes outbound traffic — ``latency`` jitters each
frame from a seeded hash, ``throttle`` paces the write to a byte budget,
``blackhole_tx``/``partition`` silently swallow it (connection open, peer
starving: a half-open link).  The receive side has its own seam,
``wire.recv`` in :func:`recv_frame`, where ``blackhole_rx``/``partition``
consume a full frame without delivering it.  :func:`recv_frame` also
takes a cumulative per-frame ``budget_s`` — the clock starts at the first
prefix byte and covers every subsequent read, so a slow-loris peer
trickling one byte per idle-timeout interval can no longer hold an rx
slot indefinitely (it gets ``budget_s`` total, not per read).
"""
from __future__ import annotations

import ctypes
import json
import os
import socket
import struct
import time
import zlib
from typing import Any, Optional, Tuple

import numpy as np

# <u32 header_len> <u64 payload_len> <u32 crc32(header + payload)>
_PREFIX = struct.Struct("<IQI")

# sanity bounds on the two length prefixes: a corrupted/garbage prefix
# must fail the connection, not OOM the reader with one allocation
MAX_HEADER = 1 << 20          # 1 MiB of routing JSON is already absurd
MAX_PAYLOAD = 1 << 31         # 2 GiB of row data per frame

# payload encodings
RAW = "raw"      # C-order float32 bytes; header carries "shape"
ARROW = "arrow"  # Arrow IPC stream holding one RecordBatch

# replica -> dispatcher telemetry shipment (serving/replica.py
# ship_telemetry): header {"op": TELEMETRY, "label": ...}, payload = JSON
# bytes of telemetry.distributed.snapshot_payload().  Rides the same
# serialized connection as predicts; the dispatcher ingests it without
# touching the in-flight request.  Predict headers additionally carry a
# "trace" id the replica echoes into its span events, which is what lets
# one merged chrome://tracing file pair dispatcher and replica brackets
# per request (docs/observability.md).
TELEMETRY = "telemetry"

# replica -> dispatcher feedback-capture shipment (serving/replica.py, the
# online-learning loop's sample stream): header {"op": FEEDBACK, "model",
# "trace", "shape": [R, F], "oshape": [...]}, payload = the request's raw
# f32 feature rows followed by the raw f32 scores the replica served.
# Unsolicited like TELEMETRY — the dispatcher ingests it without touching
# the in-flight request (docs/online.md "Sampling & the join contract").
FEEDBACK = "feedback"

# dispatcher <-> replica application-level heartbeat (docs/reliability.md
# "Degraded networks"): the dispatcher sends {"op": PING, "seq": n} on a
# schedule; the replica's serve loop answers {"op": PONG, "seq": n}
# immediately.  Because the connection is serialized, a pong queued
# behind a long predict still proves the replica end-to-end alive —
# while a half-open replica (alive process, blackholed return path)
# never answers, which TCP keepalive cannot see.  Pongs feed the
# xtb_net_heartbeat_rtt_seconds histogram and the liveness deadline.
PING = "ping"
PONG = "pong"

# external label producer -> dispatcher (online/feedback.py label feed):
# header {"op": LABEL, "trace": <trace id>}, payload = raw f32 outcome
# values for that trace's rows.  Arrives on a dedicated label-feed
# connection (a hello frame with kind="label_feed" on the fleet's
# listener) and lands in the same bounded symmetric label join as the
# in-process ``label()`` API — same horizon, same counted drops, so a
# remote label pipeline gets no laxer loss accounting than a local one
# (docs/online.md "Sampling & the join contract").
LABEL = "label"


class WireError(RuntimeError):
    """Framing violation on a fleet socket (peer is gone or confused)."""


class WireCorruptError(WireError):
    """Frame CRC mismatch: the bytes on the wire are not the bytes that
    were sent.  Subclasses :class:`WireError` on purpose — corruption is
    handled as peer-gone (quarantine the connection), never by decoding
    the damaged frame."""


# payloads up to this ride in the header's sendall (one segment, one
# syscall).  Two sendalls on a small frame without TCP_NODELAY is the
# classic Nagle + delayed-ACK interaction: the second segment waits for
# the peer's (delayed, up to 40ms) ACK of the first — measured as the
# p99 cliff on the fleet's batch-1 request path.  configure() disables
# Nagle outright; the merge additionally halves small-frame syscalls.
_INLINE_PAYLOAD = 1 << 16


def configure(sock: socket.socket) -> socket.socket:
    """Fleet socket options: TCP_NODELAY (frames are self-contained
    request/response units — buffering them for coalescing only adds
    latency).  Both ends call this on every fleet connection."""
    try:
        sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    except OSError as e:
        # a non-TCP transport (tests pair unix sockets) rejects the
        # option — expected, not a resource event; anything else is
        # classified
        import errno as _errno

        if getattr(e, "errno", None) not in (
                _errno.ENOPROTOOPT, _errno.EOPNOTSUPP, _errno.EINVAL,
                getattr(_errno, "ENOTSUP", _errno.EOPNOTSUPP)):
            from ..reliability import resources as _resources

            _resources.note_os_error(e, "wire.configure")
    return sock


def send_frame(sock: socket.socket, header: dict,
               payload: Optional[Any] = None, *,
               peer: Optional[Any] = None) -> None:
    """Write one frame.  ``payload`` may be bytes/bytearray/memoryview —
    a large one is handed to the kernel as-is (no intermediate concat
    copy of the row data); small ones merge into the prefix+header write
    (one syscall beats one copy at that size).  The prefix CRC covers
    header + payload (~GB/s, a fraction of what the kernel copy costs).
    ``peer`` names the far end (replica label / rank) for link-scoped
    fault matching — a ``partition`` spec cuts only the links whose peer
    hashes onto the wrong side."""
    from ..reliability import faults as _faults

    hdr = json.dumps(header, separators=(",", ":")).encode()
    body = memoryview(payload) if payload is not None else memoryview(b"")
    if body.ndim != 1 or body.itemsize != 1:
        body = body.cast("B")
    crc = zlib.crc32(body, zlib.crc32(hdr))
    prefix = _PREFIX.pack(len(hdr), len(body), crc)
    head = prefix + hdr
    spec = _faults.maybe_inject("wire.frame", rank=peer)
    if spec is not None:
        if spec.kind == "corrupt":
            # deterministic damage AFTER the CRC was computed, scoped to
            # the header+payload region the CRC covers: the receiver must
            # detect it (WireCorruptError) and quarantine the connection.
            # (A flip in the length prefix itself is indistinguishable
            # from a stalled or insane peer — the MAX_* bounds and
            # callers' timeouts own that case.)
            sock.sendall(prefix
                         + _faults.corrupt_bytes(hdr + bytes(body), spec))
            return
        if spec.kind == "blackhole_tx" or (
                spec.kind == "partition"
                and _faults.partition_blocks(spec, peer)):
            # half-open link, outbound side: the bytes vanish but the
            # connection stays up — the peer sees silence, never EOF.
            # Detection is the application's job (heartbeat deadline,
            # per-link budget), which is the point.
            return
        if spec.kind == "throttle":
            time.sleep(_faults.throttle_seconds(
                spec, len(head) + len(body)))
    if len(body) and len(body) <= _INLINE_PAYLOAD:
        sock.sendall(head + bytes(body))
        return
    sock.sendall(head)
    if len(body):
        sock.sendall(body)


_NATIVE = None  # None = unresolved; False = disabled/unavailable; CDLL = ready


def _native_lib():
    """The native rx library (native/xtb_wire.cc via utils/native), or
    None for the pure-Python frame path.  ``XGBOOST_TPU_WIRE_NATIVE=0``
    is the kill switch (default on when the library loads); resolved
    once per process."""
    global _NATIVE
    if _NATIVE is None:
        if os.environ.get("XGBOOST_TPU_WIRE_NATIVE", "1").strip().lower() \
                in ("", "0", "false", "off", "no"):
            _NATIVE = False
        else:
            from ..utils.native import load_wire

            _NATIVE = load_wire() or False
    return _NATIVE or None


class _NativeReader:
    """Frame source backed by libxtb_wire: :func:`recv_frame` reads the
    whole frame — prefix, header, payload, CRC verify — in two native
    calls (one GIL release each) instead of per-chunk interpreter reads.
    Under a sharded dispatcher the GIL *reacquire* per read is the
    convoy cost this removes; the thread takes the GIL back only to
    JSON-decode the tiny header.  Only created for sockets in plain
    blocking mode at a frame boundary; the socket stays owned by the
    caller."""
    __slots__ = ("sock", "fd")

    def __init__(self, sock: socket.socket):
        self.sock = sock  # keeps the fd alive for the reader's lifetime
        self.fd = sock.fileno()

    def close(self) -> None:
        self.sock = None


def reader(sock: socket.socket):
    """Buffered frame source for a long-lived fleet connection.  A frame
    is 3+ reads (prefix, header, payload); on a raw socket each is a
    syscall AND a GIL release/reacquire — and under a many-threaded
    dispatcher the reacquire, not the syscall, is the cost (profiled at
    ~ms under convoy).  A ``BufferedReader`` usually serves the prefix
    and header out of the buffer: one GIL event per frame instead of
    three.  Safe to create any time the stream is at a frame boundary
    (``makefile`` shares the fd — no dup, no double-buffering).

    When the native wire library is available (utils/native.load_wire;
    ``XGBOOST_TPU_WIRE_NATIVE=0`` forces it off) and the socket is in
    plain blocking mode, the source is a :class:`_NativeReader` instead:
    one GIL release covers the whole frame read and the CRC verify,
    under the identical frame contract (bounds, cumulative slow-loris
    budget, CRC semantics, fault seams stay Python-side)."""
    if _native_lib() is not None and sock.gettimeout() is None:
        return _NativeReader(sock)
    return sock.makefile("rb", buffering=1 << 16)


def _recv_exact(stream, n: int,
                deadline: Optional[float] = None) -> memoryview:
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    readinto = getattr(stream, "readinto", None)
    while got < n:
        r = (readinto(view[got:]) if readinto is not None
             else stream.recv_into(view[got:], n - got))
        if not r:
            raise WireError("connection closed mid-frame")
        got += r
        # the slow-loris bound: every partial read is a checkpoint
        # against the frame's CUMULATIVE deadline, so a peer drip-feeding
        # one byte per idle-timeout interval exhausts one budget instead
        # of resetting it on each byte
        if deadline is not None and got < n \
                and time.monotonic() >= deadline:
            raise WireError(
                f"frame read exceeded its cumulative deadline with "
                f"{n - got} of {n} bytes outstanding (slow-loris bound)")
    return memoryview(buf)


def recv_frame(stream, *, budget_s: Optional[float] = None,
               peer: Optional[Any] = None) -> Tuple[dict, memoryview]:
    """Read one frame -> (header, payload view) from a socket or a
    :func:`reader` stream.  Raises WireError on EOF at a frame boundary
    too (callers treat any WireError as peer-gone); length-prefix
    violations and CRC mismatches (:class:`WireCorruptError`) are
    WireErrors as well, so a poisoned connection fails itself, not the
    fleet, and damaged bytes are never JSON-decoded.

    ``budget_s`` bounds one frame's total read wall: the clock starts
    when the first prefix byte arrives (idle time between frames is
    free) and a frame still incomplete at the deadline is a WireError —
    the slow-loris bound.  It needs at least a trickle to check against
    (each arriving chunk is a checkpoint); a peer sending *nothing* is
    the idle-timeout/heartbeat layer's case, not this one.  ``peer``
    scopes rx-side fault matching (``wire.recv`` seam), where
    ``blackhole_rx``/``partition`` consume a frame without delivering
    it — the half-open link's inbound side."""
    from ..reliability import faults as _faults

    if isinstance(stream, _NativeReader):
        return _recv_frame_native(stream, budget_s=budget_s, peer=peer)
    while True:
        spec = _faults.maybe_inject("wire.recv", rank=peer)
        first = _recv_exact(stream, 1)
        deadline = (time.monotonic() + budget_s) if budget_s is not None \
            else None
        rest = _recv_exact(stream, _PREFIX.size - 1, deadline)
        hlen, plen, crc = _PREFIX.unpack(bytes(first) + bytes(rest))
        if hlen > MAX_HEADER:
            raise WireError(f"unreasonable header length {hlen}")
        if plen > MAX_PAYLOAD:
            raise WireError(f"unreasonable payload length {plen}")
        hdr_bytes = _recv_exact(stream, hlen, deadline)
        payload = _recv_exact(stream, plen, deadline) if plen \
            else memoryview(b"")
        if zlib.crc32(payload, zlib.crc32(hdr_bytes)) != crc:
            from ..reliability import integrity as _integrity

            _integrity.corrupt_detected("wire")
            raise WireCorruptError(
                f"frame CRC mismatch ({hlen}B header, {plen}B payload): "
                "corrupted in transit — quarantining the connection")
        if spec is not None and (
                spec.kind == "blackhole_rx"
                or (spec.kind == "partition"
                    and _faults.partition_blocks(spec, peer))):
            # half-open link, inbound side: the kernel delivered the
            # frame, the application never sees it.  Loop for the next
            # frame — the connection stays alive and silent.
            continue
        try:
            header = json.loads(bytes(hdr_bytes))
        except ValueError as e:
            raise WireError(f"undecodable frame header: {e}") from e
        if not isinstance(header, dict):
            raise WireError(f"frame header is {type(header).__name__}, "
                            "expected a JSON object")
        return header, payload


def _native_raise(rc: int, what: str) -> None:
    """Map a libxtb_wire return code onto the same WireError classes the
    Python reader raises (CRC handled at the call site — it also bumps
    the integrity counter)."""
    if rc in (1, -1):
        raise WireError("connection closed mid-frame")
    if rc == -2:
        raise WireError(
            f"frame {what} read exceeded its cumulative deadline "
            "(slow-loris bound)")
    raise WireError(f"socket read failed during frame {what} (rc={rc})")


def _recv_frame_native(rd: "_NativeReader", *,
                       budget_s: Optional[float] = None,
                       peer: Optional[Any] = None) -> Tuple[dict, memoryview]:
    """:func:`recv_frame` over a :class:`_NativeReader`: the byte loop
    (prefix read, body read, CRC) runs in libxtb_wire under ONE GIL
    release per call; every policy decision — length bounds, the
    ``wire.recv`` fault seam with its blackhole re-loop, corruption
    accounting, error classification — stays here so both paths are
    observably identical."""
    from ..reliability import faults as _faults

    lib = _native_lib()
    while True:
        spec = _faults.maybe_inject("wire.recv", rank=peer)
        hlen = ctypes.c_uint()
        plen = ctypes.c_ulonglong()
        crc = ctypes.c_uint()
        deadline = ctypes.c_double()
        rc = lib.xtb_wire_read_prefix(
            rd.fd, float(budget_s) if budget_s is not None else 0.0,
            ctypes.byref(hlen), ctypes.byref(plen), ctypes.byref(crc),
            ctypes.byref(deadline))
        if rc != 0:
            _native_raise(rc, "prefix")
        hl, pl = int(hlen.value), int(plen.value)
        if hl > MAX_HEADER:
            raise WireError(f"unreasonable header length {hl}")
        if pl > MAX_PAYLOAD:
            raise WireError(f"unreasonable payload length {pl}")
        buf = bytearray(hl + pl)
        rc = lib.xtb_wire_read_body(
            rd.fd, (ctypes.c_ubyte * len(buf)).from_buffer(buf), len(buf),
            deadline.value, crc.value)
        if rc == -6:
            from ..reliability import integrity as _integrity

            _integrity.corrupt_detected("wire")
            raise WireCorruptError(
                f"frame CRC mismatch ({hl}B header, {pl}B payload): "
                "corrupted in transit — quarantining the connection")
        if rc != 0:
            _native_raise(rc, "body")
        if spec is not None and (
                spec.kind == "blackhole_rx"
                or (spec.kind == "partition"
                    and _faults.partition_blocks(spec, peer))):
            # half-open link, inbound side — same contract as the Python
            # reader: the frame was consumed, the application never sees
            # it, the connection stays alive and silent
            continue
        view = memoryview(buf)
        try:
            header = json.loads(bytes(view[:hl]))
        except ValueError as e:
            raise WireError(f"undecodable frame header: {e}") from e
        if not isinstance(header, dict):
            raise WireError(f"frame header is {type(header).__name__}, "
                            "expected a JSON object")
        return header, view[hl:]


# ---------------------------------------------------------------- encoding
def encode_raw(X: np.ndarray) -> Tuple[dict, memoryview]:
    """(header fields, payload) for a numpy batch — zero-copy when ``X``
    is already C-contiguous float32."""
    X = np.ascontiguousarray(X, np.float32)
    if X.ndim == 1:
        X = X[None, :]
    return ({"enc": RAW, "shape": list(X.shape)},
            memoryview(X).cast("B"))


def encode_arrow(batch) -> Tuple[dict, memoryview]:
    """(header fields, payload) for a pyarrow RecordBatch/Table: one IPC
    stream, column buffers appended without per-value work."""
    import pyarrow as pa

    if isinstance(batch, pa.Table):
        batch = batch.combine_chunks().to_batches()[0] if batch.num_rows \
            else pa.RecordBatch.from_pydict(
                {n: [] for n in batch.schema.names}, schema=batch.schema)
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, batch.schema) as writer:
        writer.write_batch(batch)
    buf = sink.getvalue()
    return ({"enc": ARROW, "shape": [batch.num_rows, batch.num_columns]},
            memoryview(buf))


def label_feed(host: str, port: int, label: str = "labeler",
               timeout: Optional[float] = 30.0) -> socket.socket:
    """Open a label-feed channel to a fleet listener
    (``ServingFleet.label_endpoint()``): connect, configure, and send
    the ``kind="label_feed"`` hello that routes this connection to the
    fleet's label rx loop instead of replica bookkeeping.  ``timeout``
    bounds the connect AND every later send on the socket — a
    black-holed route is a detected fault, not a wedged producer.  The
    caller owns the socket (close it when the producer is done)."""
    sock = configure(socket.create_connection((host, int(port)),
                                              timeout=timeout))
    send_frame(sock, {"op": "hello", "kind": "label_feed",
                      "label": label})
    return sock


def send_label(sock: socket.socket, trace: str, y, *,
               peer: Optional[Any] = None) -> None:
    """One ``op="label"`` frame on a label-feed channel: the outcome
    values for ``trace``'s rows, float32 raw — joined driver-side by the
    online loop's FeedbackHub (docs/online.md)."""
    arr = np.ascontiguousarray(np.asarray(y, np.float32).reshape(-1))
    send_frame(sock, {"op": LABEL, "trace": trace,
                      "shape": [int(arr.shape[0])]},
               memoryview(arr).cast("B"), peer=peer)


def decode_matrix(header: dict, payload) -> np.ndarray:
    """Reconstruct the (R, F) float32 batch over the received buffer.

    ``raw``: a zero-copy ``np.frombuffer`` view.  ``arrow``: zero-copy IPC
    read; float32 null-free columns are stacked straight off the Arrow
    buffers, anything else (other dtypes, nulls, dictionary categoricals)
    goes through the same semantics as ``data/arrow.py`` ingestion."""
    enc = header.get("enc", RAW)
    if enc == RAW:
        R, F = (int(x) for x in header["shape"])
        return np.frombuffer(payload, np.float32).reshape(R, F)
    if enc == ARROW:
        from ..data.arrow import ipc_batch_to_dense
        return ipc_batch_to_dense(payload)
    raise WireError(f"unknown payload encoding {enc!r}")
