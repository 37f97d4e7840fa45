"""Length-prefixed frame protocol for the out-of-process serving stack.

One wire format shared by all three socket seams: router <-> replica
worker process (:mod:`.remote` / :mod:`.worker`), client <-> ingress
(:mod:`.ingress`), and the chaos harness that drives them. Two
design constraints shape it:

* **A torn frame must be discarded, never mis-parsed.** Every frame
  starts with a fixed magic + two length words; the reader either
  receives the WHOLE frame or raises :class:`ConnectionClosed` — a
  worker that dies mid-``sendall`` leaves a truncated tail that reads
  as EOF-inside-a-frame, not as a smaller frame with garbage bits. A
  wrong magic or an absurd length raises :class:`FrameError`
  immediately (a desynchronized or hostile peer is cut off, not
  guessed at).

* **No pickled code over the socket.** Payloads are a JSON header plus
  a raw binary section for numpy buffers — nested lists/tuples/dicts
  with ndarray leaves round-trip exactly (dtype, shape, bits), and the
  decoder can never execute anything. The ingress accepts these frames
  from arbitrary network clients; ``pickle.loads`` there would be a
  remote-code-execution hole, so the private router<->worker seam pays
  the same (tiny) encoding cost for one shared, safe codec.

Frame layout::

    MAGIC (4 bytes, b"MXS1") | header_len u32 BE | body_len u32 BE
    | header (UTF-8 JSON)    | body (concatenated ndarray buffers)

The header is a dict with a ``kind`` field (``hello`` / ``submit`` /
``result`` / ``health`` / ``stop`` / ``bye``); ndarrays anywhere in it
are hoisted into the body section and referenced by index. Typed
errors cross the wire as ``{"ok": false, "etype": ..., "error": ...}``
result frames; :func:`encode_error` / :func:`decode_error` map the
serving stack's exception types (:class:`~.router.ServerOverloaded`,
:class:`~.router.FailoverExhausted`, ...) to stable wire names so
backpressure stays TYPED across process boundaries.
"""
from __future__ import annotations

import json
import socket
import struct
from typing import Any, Dict, Optional, Tuple

import numpy as np

from ..base import MXNetError

__all__ = [
    "FrameError", "ConnectionClosed", "send_frame", "recv_frame",
    "reader", "pack_frame", "FrameWriter",
    "encode_payload", "decode_payload", "encode_error",
    "decode_error", "MAGIC", "MAX_FRAME_BYTES",
]

MAGIC = b"MXS1"
_HEADER = struct.Struct("!4sII")
# per-call nonblocking send flag for the FrameWriter inline fast path
# (Linux/BSD; None disables the fast path, everything coalesces through
# the writer thread as before)
_MSG_DONTWAIT = getattr(socket, "MSG_DONTWAIT", None)
# sanity cap: one frame carries one sample or one sliced result row set,
# not a dataset — a length past this is a desynchronized/hostile peer
MAX_FRAME_BYTES = 256 << 20


class FrameError(MXNetError):
    """The byte stream is not a valid frame (bad magic, absurd length,
    malformed header). The connection is unusable — callers close it."""


class ConnectionClosed(FrameError):
    """EOF — cleanly between frames or (a dying peer's half-written
    frame) in the middle of one. Either way the partial bytes are
    discarded, never parsed."""


# ---------------------------------------------------------------------------
# payload codec: JSON header + hoisted ndarray buffers (no pickle)
# ---------------------------------------------------------------------------

def encode_payload(obj) -> Tuple[bytes, bytes]:
    """Encode ``obj`` (JSON-able scalars + list/tuple/dict containers +
    ndarray/np-scalar leaves) into ``(header_json, body)``."""
    blobs = []

    def enc(o):
        if isinstance(o, np.ndarray):
            arr = np.ascontiguousarray(o)
            blobs.append(arr)
            return {"__nd__": [len(blobs) - 1, arr.dtype.str,
                               list(arr.shape)]}
        if isinstance(o, np.generic):
            return {"__np__": [o.dtype.str, o.item()]}
        if isinstance(o, dict):
            return {"__d__": [[enc(k), enc(v)] for k, v in o.items()]}
        if isinstance(o, tuple):
            return {"__t__": [enc(x) for x in o]}
        if isinstance(o, list):
            return {"__l__": [enc(x) for x in o]}
        if o is None or isinstance(o, (bool, int, float, str)):
            return {"__v__": o}
        raise FrameError(
            f"cannot encode {type(o).__name__} for the serving wire "
            "(JSON scalars, list/tuple/dict, numpy only)")

    data = enc(obj)
    header = json.dumps(
        {"data": data,
         "blobs": [[b.dtype.str, list(b.shape)] for b in blobs]},
        separators=(",", ":")).encode("utf-8")
    body = b"".join(b.tobytes() for b in blobs)
    return header, body


def decode_payload(header: bytes, body: bytes):
    """Inverse of :func:`encode_payload`. Raises :class:`FrameError` on
    anything malformed — a bad frame is rejected, not guessed at."""
    try:
        meta = json.loads(header.decode("utf-8"))
        blob_meta = meta["blobs"]
        arrays = []
        off = 0
        for dtype_str, shape in blob_meta:
            dt = np.dtype(dtype_str)
            n = int(np.prod(shape, dtype=np.int64)) if shape else 1
            nbytes = dt.itemsize * n
            chunk = body[off:off + nbytes]
            if len(chunk) != nbytes:
                raise ValueError("body shorter than its blob table")
            arrays.append(np.frombuffer(chunk, dtype=dt).reshape(shape)
                          .copy())
            off += nbytes

        def dec(o):
            if not isinstance(o, dict) or len(o) != 1:
                raise ValueError(f"untagged node {o!r}")
            tag, v = next(iter(o.items()))
            if tag == "__v__":
                return v
            if tag == "__nd__":
                return arrays[v[0]]
            if tag == "__np__":
                return np.dtype(v[0]).type(v[1])
            if tag == "__d__":
                return {dec(k): dec(val) for k, val in v}
            if tag == "__t__":
                return tuple(dec(x) for x in v)
            if tag == "__l__":
                return [dec(x) for x in v]
            raise ValueError(f"unknown tag {tag!r}")

        return dec(meta["data"])
    except FrameError:
        raise
    except Exception as e:  # noqa: BLE001 - any malformation is typed
        raise FrameError(f"malformed wire payload: {e}") from e


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------

def pack_frame(payload: Dict[str, Any]) -> bytes:
    """Serialize ``payload`` into one complete frame's bytes."""
    header, body = encode_payload(payload)
    return _HEADER.pack(MAGIC, len(header), len(body)) + header + body


def send_frame(sock: socket.socket, payload: Dict[str, Any]) -> None:
    """Serialize ``payload`` (a dict with a ``kind`` field; ndarrays
    anywhere inside) and write one frame. Callers serialize concurrent
    senders with their own lock — a frame must hit the stream whole."""
    sock.sendall(pack_frame(payload))


class FrameWriter:
    """Coalescing write side for a long-lived frame stream, with an
    opportunistic inline fast path.

    ``send()`` never blocks on the peer. When the stream is IDLE —
    writer thread asleep, nothing queued, socket buffer has room — the
    caller encodes and writes the frame itself in one GIL hold: no
    writer-thread wakeup, no futex round trip, no handoff. On a
    contended interpreter those two thread hops per frame were the
    dominant per-request cost of the out-of-process serving path (wall
    time in ``submit`` ~20x its CPU time, all GIL handoffs). When the fast path is NOT clear —
    a send already in progress, queued frames, a full socket buffer,
    or a stalled peer — the payload is enqueued and the dedicated
    writer thread encodes + drains everything queued in one
    ``sendall``. Properties the hot paths rely on:

    * Frames from one caller thread hit the stream in ``send()``
      order: the fast path runs only when nothing is queued ahead,
      and queued frames only ever drain behind the in-progress
      inline write (the io lock serializes actual socket writes).
    * Under streaming load the kernel sees a few large writes instead
      of a syscall per frame (the symmetric half of :func:`reader`).
    * The caller — the router's single dispatch thread, a worker's
      result callbacks — never blocks on the peer's socket: the
      inline path writes only what ``select`` says fits right now
      (the unsent tail is handed to the writer thread); a stalled
      peer stalls the writer thread, not the dispatcher.
      Consequence: ndarrays inside ``payload`` are captured by
      REFERENCE and must not be mutated after ``send()``.

    A send after the connection died raises :class:`ConnectionClosed`
    (the reader side owns *reporting* the death — first signal wins
    there); a payload the codec rejects poisons the stream and closes
    the writer (every later ``send`` raises — the stack only feeds it
    frames built from already-validated parts). ``close(flush=True)``
    drains what is queued, then stops.
    """

    def __init__(self, sock: socket.socket, name: str = "wire-writer"):
        import threading

        self._sock = sock
        self._cond = threading.Condition()
        self._buf: list = []
        self._tail = b""        # unsent remainder of an inline write
        self._io = threading.Lock()     # serializes socket writes
        self._closed = False
        self._poisoned = False  # closed BY a codec failure: later
        #                         sends raise FrameError (a caller can
        #                         tell "peer died" from "this stream
        #                         can never speak again" and die loud)
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    def _raise_closed(self) -> None:
        if self._poisoned:
            # NOT ConnectionClosed: the socket may be perfectly
            # healthy — an earlier payload the codec rejected poisoned
            # the stream, and a worker swallowing this as "peer went
            # away" would zombie (read submits forever, answer none)
            raise FrameError(
                "frame writer was poisoned by an unencodable payload; "
                "this stream can no longer send")
        raise ConnectionClosed(
            "frame writer is closed (connection died or close() was "
            "called)")

    def send(self, payload: Dict[str, Any]) -> None:
        # inline fast path: only when we win the io lock WITHOUT
        # waiting (the caller must not block) and nothing is queued
        # ahead (order preservation)
        if self._io.acquire(blocking=False):
            try:
                with self._cond:
                    if self._closed:
                        self._raise_closed()
                    clear = not self._buf and not self._tail
                if clear and self._send_inline(payload):
                    return
            finally:
                self._io.release()
        # fallback: enqueue for the writer thread (coalesced drain)
        with self._cond:
            if self._closed:
                self._raise_closed()
            self._buf.append(payload)
            self._cond.notify()

    def _send_inline(self, payload: Dict[str, Any]) -> bool:
        """Holding ``_io`` with a clear queue: write what fits without
        blocking. True = fully handled (sent, or tail handed to the
        writer thread); False = socket has no room at all — enqueue."""
        if _MSG_DONTWAIT is None:
            return False            # platform without per-call nonblock
        try:
            data = pack_frame(payload)
        except Exception:   # noqa: BLE001 - unencodable payload
            # caller bug; nothing partial was sent, but poison the
            # writer so later frames cannot silently reorder around
            # the failure (same contract as the writer-thread path)
            with self._cond:
                self._closed = True
                self._poisoned = True
                self._buf = []
                self._cond.notify()
            raise
        try:
            # per-call nonblocking: a blocking send() loops in-kernel
            # until the WHOLE buffer is copied, and fd-level O_NONBLOCK
            # would break the peer-direction reader sharing this fd
            n = self._sock.send(data, _MSG_DONTWAIT)
        except BlockingIOError:
            return False            # no room at all right now
        except (OSError, ValueError):   # ValueError: fd already closed
            with self._cond:
                self._closed = True
                self._buf = []
                self._cond.notify()
            raise ConnectionClosed(
                "frame writer is closed (connection died or close() "
                "was called)")
        if n < len(data):
            with self._cond:
                self._tail = data[n:]
                self._cond.notify()
        return True

    def _run(self) -> None:
        while True:
            with self._cond:
                while not self._buf and not self._tail \
                        and not self._closed:
                    self._cond.wait()
                closed = self._closed
            # take the io lock BEFORE popping: an inline sender who
            # saw the queue empty must not write between our pop and
            # our sendall (frames would reorder around the drain)
            with self._io:
                with self._cond:
                    buf, self._buf = self._buf, []
                    tail, self._tail = self._tail, b""
                if buf or tail:
                    try:
                        data = tail + b"".join(pack_frame(p)
                                               for p in buf)
                    except Exception:   # noqa: BLE001 - unencodable
                        # payload = a caller bug; the stream position
                        # is still clean (nothing partial was sent)
                        # but frames after the bad one would be
                        # silently reordered — poison the writer
                        with self._cond:
                            self._closed = True
                            self._poisoned = True
                            self._buf = []
                        raise
                    try:
                        self._sock.sendall(data)
                    except OSError:
                        with self._cond:
                            self._closed = True
                            self._buf = []
                        return
            if closed:
                with self._cond:
                    if not self._buf and not self._tail:
                        return

    def close(self, flush: bool = True, timeout: float = 5.0) -> None:
        with self._cond:
            if not flush:
                self._buf = []
                self._tail = b""
            self._closed = True
            self._cond.notify()
        self._thread.join(timeout)


def _recv_exact(sock, n: int, started: bool) -> bytes:
    """Read exactly ``n`` bytes from a socket OR a buffered file-like
    (``reader()``). EOF raises :class:`ConnectionClosed`; ``started``
    only flavors the message (mid-frame vs between frames)."""
    read = getattr(sock, "read", None)
    if read is not None:
        # BufferedReader.read(n) blocks until n bytes or EOF — one
        # python call, and back-to-back frames amortize the recv
        # syscalls (the throughput seam: a syscall per header is 3+
        # syscalls per frame; buffered it is a fraction of one)
        try:
            buf = read(n)
        except OSError as e:
            raise ConnectionClosed(f"connection lost mid-read: {e}") \
                from e
        if buf is None or len(buf) < n:
            raise ConnectionClosed(
                "peer closed mid-frame (half-written frame discarded)"
                if started or buf else "peer closed the connection")
        return buf
    chunks = []
    got = 0
    while got < n:
        try:
            chunk = sock.recv(min(n - got, 1 << 20))
        except OSError as e:
            raise ConnectionClosed(f"connection lost mid-read: {e}") \
                from e
        if not chunk:
            raise ConnectionClosed(
                "peer closed mid-frame (half-written frame discarded)"
                if started or got else "peer closed the connection")
        chunks.append(chunk)
        got += len(chunk)
        started = True
    return b"".join(chunks)


def reader(sock: socket.socket, bufsize: int = 1 << 16):
    """A buffered read side for ``recv_frame`` — use in every
    long-lived reader loop: streamed frames then cost a fraction of a
    syscall each instead of 3+. The socket itself stays usable for
    (unbuffered) sends; closing the socket unblocks the reader."""
    return sock.makefile("rb", buffering=bufsize)


def recv_frame(sock) -> Dict[str, Any]:
    """Read one whole frame from a socket or a :func:`reader` stream
    and decode it. Raises :class:`ConnectionClosed` on EOF (clean or
    mid-frame) and :class:`FrameError` on a corrupt stream."""
    raw = _recv_exact(sock, _HEADER.size, started=False)
    magic, hlen, blen = _HEADER.unpack(raw)
    if magic != MAGIC:
        raise FrameError(
            f"bad frame magic {magic!r} (desynchronized or non-protocol "
            "peer)")
    if hlen + blen > MAX_FRAME_BYTES:
        raise FrameError(
            f"frame of {hlen + blen} bytes exceeds the "
            f"{MAX_FRAME_BYTES}-byte cap")
    header = _recv_exact(sock, hlen, started=True)
    body = _recv_exact(sock, blen, started=True) if blen else b""
    payload = decode_payload(header, body)
    if not isinstance(payload, dict) or "kind" not in payload:
        raise FrameError(f"frame payload has no 'kind': {payload!r}")
    return payload


# ---------------------------------------------------------------------------
# typed errors on the wire
# ---------------------------------------------------------------------------

def _error_registry():
    # resolved lazily: wire is imported by worker subprocesses before
    # the full serving package, and router imports server — keep the
    # import graph shallow until an error actually crosses the wire
    from ..fault import FaultInjected
    from .kvcache import CacheFull, Preempted
    from .router import FailoverExhausted, ServerOverloaded
    from .server import TenantThrottled

    return {
        "overloaded": ServerOverloaded,
        "failover_exhausted": FailoverExhausted,
        "fault_injected": FaultInjected,
        "preempted": Preempted,
        "kvcache_full": CacheFull,
        "throttled": TenantThrottled,
        "mxnet_error": MXNetError,
    }


def encode_error(exc: BaseException) -> Tuple[str, str]:
    """``(etype, message)`` wire form of ``exc`` — the most specific
    registered type wins, anything unknown degrades to ``internal``."""
    reg = _error_registry()
    for name in ("overloaded", "failover_exhausted", "fault_injected",
                 "preempted", "kvcache_full", "throttled"):
        if isinstance(exc, reg[name]):
            return name, str(exc)
    if isinstance(exc, MXNetError):
        return "mxnet_error", str(exc)
    return "internal", f"{type(exc).__name__}: {exc}"


def decode_error(etype: str, message: str) -> MXNetError:
    """Reconstruct the typed exception for a wire error. ``FaultInjected``
    carries site/hit structure that does not cross the wire — it comes
    back as a plain :class:`MXNetError` naming the injection."""
    reg = _error_registry()
    cls = reg.get(etype)
    if cls is None or etype == "fault_injected":
        return MXNetError(message)
    return cls(message)


def parse_hostport(addr: str) -> Tuple[str, int]:
    """``host:port`` -> ``(host, port)`` with a typed error on junk."""
    host, _, port = addr.rpartition(":")
    if not host or not port.isdigit():
        raise MXNetError(f"expected host:port, got {addr!r}")
    return host, int(port)


def connect(host: str, port: int,
            timeout: Optional[float] = None) -> socket.socket:
    """TCP connect with TCP_NODELAY (frames are small and latency-bound;
    Nagle would batch a submit behind the previous result's ACK)."""
    sock = socket.create_connection((host, port), timeout=timeout)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return sock
