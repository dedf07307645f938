"""Storage-plane wire format: length-prefixed JSON header + raw binary body.

Replaces the reference's internal protocol — hand-built JSON with Base64
fragment payloads over hand-parsed HTTP (StorageNode.java:629-642,657-773) —
which inflates replication traffic ~33% and breaks on escaped quotes
(SURVEY.md §2.5(6), S14). Frame layout::

    magic   u32  0x44465301  ("DFS\\x01")
    hdr_len u32  big-endian
    body_len u64 big-endian
    header  hdr_len bytes of UTF-8 JSON (op, params, chunk table …)
    body    body_len raw bytes (chunk data, concatenated)

Chunk batches put (digest, length) pairs in the header and concatenate the
raw chunk bytes in the body — zero encoding overhead.

Since round 9 the header MAY carry an OPTIONAL ``trace`` field —
``{"t": <trace32hex>, "s": <span16hex>, "f": <sender node id>}`` — the
distributed-tracing context (docs/observability.md). Compatibility is
bidirectional by construction: receivers that predate the field ignore
unknown header keys, and receivers that understand it treat a frame
without (or with a malformed) ``trace`` exactly like one from an
untraced caller. The field never affects op semantics.

Since round 18 the header MAY also carry an OPTIONAL ``deadline``
field — the sender's REMAINING end-to-end budget in seconds (a JSON
number; docs/serve.md §deadlines). Remaining time, never absolute wall
time: the receiver starts its own countdown on arrival, so the hop
decrement is exactly the network flight time and no clock comparison
ever crosses processes. Same bidirectional compatibility contract as
``trace``: absent/malformed = an undeadlined caller (pre-r18 peer),
and the field never changes what an op DOES — only whether a receiver
may refuse to start work whose caller has already given up.

Round 10 makes the frame layer **zero-copy** (docs/wire.md):

- a body may be a *sequence of buffers* (``bytes | bytearray |
  memoryview``): :func:`send_msg` and the framed connections below write
  the prefix, header, and each buffer straight to the transport — never
  joining them into one bytes object. (``StreamWriter.writelines`` is
  the natural spelling, but CPython < 3.12's selector transport
  implements it as ``b"".join`` — exactly the copy being eliminated —
  so buffers are flushed as individual writes, which go straight to
  ``send(2)`` whenever the transport buffer is empty.)
- the receive side is :class:`asyncio.BufferedProtocol` based
  (:class:`FrameConnection` / :class:`FrameServerProtocol`): the kernel
  copies each frame ONCE into a per-frame buffer via ``recv_into`` —
  no StreamReader byte-buffer shuffling (which measured ~3 passes over
  every body) — and :func:`unpack_chunks` hands out read-only
  memoryview slices of it instead of per-chunk copies.

Round 16 adds two dedup/index-plane metadata ops (docs/index.md),
carried in the same frame shape: ``get_filter`` replies with the
peer-existence filter meta in the header and the raw blocked-bloom
bytes as the body (a binary payload like chunk data — never Base64),
and ``filter_delta`` replies header-only with the digests added since
a (generation, version) cursor or ``resync: true``. Both are optional:
peers that predate the ops answer "unknown op", which the filter sync
loop treats as "no filter plane" — compatibility is bidirectional like
the ``trace`` field.

Round 19 adds ``get_filters`` (docs/client.md): a BATCHED filter fetch
for external smart clients — one node replies with its own filter plus
every peer-filter replica it gossips, as a meta table in the header
(node id, generation, version, capacity, bits/key, age, blob length)
and the raw blobs concatenated in table order as the body. Optional
like the r16 ops: an old server answers "unknown op" and the client
degrades to per-peer ``get_filter`` or plain probing.

The stream-based :func:`send_msg` / :func:`read_msg` remain the
compatibility surface (tests, tooling, pre-r10 interop): the bytes on
the wire are identical.
"""

from __future__ import annotations

import asyncio
import json
import struct
from typing import Sequence, Union

MAGIC = 0x44465301
_PREFIX = struct.Struct(">IIQ")
PREFIX_LEN = _PREFIX.size
MAX_HEADER = 64 * 1024 * 1024
MAX_BODY = 8 * 1024 * 1024 * 1024

# ---------------------------------------------------------------------
# The internal-op contract, as data. One entry per op the storage plane
# speaks: the request header fields a client may send and the reply
# header fields a handler may produce — beyond the envelope the
# transport owns (`op`, optional `trace`, the optional remaining-budget
# `deadline`, the ring-epoch pair `repoch`/`rfp` on placement-bearing
# ops, and `ok`/`error` plus the `ringEpoch`/`ring` refusal pair on
# every reply). `body` notes the binary payload direction for humans;
# the checker does not model it.
#
# dfslint DFS010 (docs/lint.md) statically extracts the op set from the
# client call sites (comm/rpc.py + the runtime's raw sends) and the
# handler table (node/runtime.py `_dispatch`) and fails the gate when
# the three disagree: an op sent but unhandled, handled but missing
# here, documented here but unhandled, or a request/reply field read by
# one side and never produced by the other. Editing ANY side of the
# wire therefore means editing all three, in one PR — the drift this
# table exists to make impossible.
OP_SPECS = {
    "store_chunks": {"request": ["fileId", "chunks"],
                     "reply": ["digests"],
                     "body": "request: chunk payloads (scatter-gather)"},
    # residentOk (optional, placement's probes and pre-ack rounds only):
    # the store's resident set may answer instead of a stat a digest
    # (store/cas.py has); without it — the repair cycle, who_has, an
    # older caller — the answer is a look at the disk
    "has_chunks": {"request": ["digests", "residentOk"],
                   "reply": ["have"], "body": None},
    "get_chunk": {"request": ["digest"], "reply": [],
                  "body": "reply: chunk payload"},
    "get_chunks": {"request": ["digests"], "reply": ["chunks"],
                   "body": "reply: chunk payloads (table in header)"},
    "announce": {"request": ["manifest", "fresh"], "reply": [],
                 "body": None},
    "get_manifest": {"request": ["fileId"], "reply": ["manifest",
                                                      "mtime"],
                     "body": None},
    "delete": {"request": ["fileId"], "reply": [], "body": None},
    "delete_chunks": {"request": ["digests"],
                      "reply": ["removed", "refused"],
                      "body": None},
    "tombstones": {"request": [], "reply": ["tombs"], "body": None},
    "list_manifests": {"request": [], "reply": ["ids"], "body": None},
    "health": {"request": [], "reply": ["nodeId", "chunks", "files"],
               "body": None},
    "get_trace": {"request": ["traceId"], "reply": ["spans"],
                  "body": None},
    "get_doctor": {"request": [], "reply": ["doctor"], "body": None},
    "get_census": {"request": ["prefixes"], "reply": ["census"],
                   "body": None},
    "get_ring": {"request": [], "reply": ["ring", "previous",
                                          "migrating"],
                 "body": None},
    "propose_ring": {"request": ["ring"], "reply": ["epoch",
                                                    "installed"],
                     "body": None},
    "get_filter": {"request": [], "reply": ["filter"],
                   "body": "reply: blocked-bloom filter bytes"},
    "filter_delta": {"request": ["gen", "since"],
                     "reply": ["resync", "gen", "version", "adds"],
                     "body": None},
    "get_filters": {"request": [], "reply": ["filters"],
                    "body": "reply: concatenated filter blobs "
                            "(table in header)"},
}

# one payload buffer; a frame body is one of these or a sequence of them
Buffer = Union[bytes, bytearray, memoryview]


class WireError(RuntimeError):
    pass


def as_buffers(body: Buffer | Sequence[Buffer]) -> list[Buffer]:
    """Normalize a body argument to a flat buffer list (a single buffer
    becomes a one-element list; a sequence is taken as-is)."""
    if isinstance(body, (bytes, bytearray, memoryview)):
        return [body]
    return list(body)


def buffers_nbytes(body: Buffer | Sequence[Buffer]) -> int:
    if isinstance(body, (bytes, bytearray, memoryview)):
        return len(body)
    return sum(len(b) for b in body)


def encode_frame(header: dict, body: Buffer | Sequence[Buffer] = b""
                 ) -> tuple[bytes, list[Buffer], int]:
    """-> (prefix+header bytes, body buffer list, total frame length).
    The one place a frame is laid out, shared by every send path — so
    byte accounting (``total``) is by construction what the socket
    carries."""
    h = json.dumps(header, separators=(",", ":")).encode()
    bufs = as_buffers(body)
    body_len = sum(len(b) for b in bufs)
    head = _PREFIX.pack(MAGIC, len(h), body_len) + h
    return head, bufs, len(head) + body_len


def frame_size(header: dict, body_len: int) -> int:
    """Exact on-wire size of a frame with this header and body length."""
    h = json.dumps(header, separators=(",", ":")).encode()
    return PREFIX_LEN + len(h) + body_len


def _decode_header(raw: Buffer) -> dict:
    """Parse + validate a frame header; any malformation is a
    :class:`WireError` (a peer sending garbage must fail the frame, not
    leak a JSONDecodeError / AttributeError into op dispatch)."""
    try:
        # header-only copy (≤ a few KB): json.loads rejects memoryviews
        header = json.loads(bytes(raw))  # dfslint: ignore[DFS006]
    except (ValueError, UnicodeDecodeError) as e:
        raise WireError(f"bad frame header: {e}") from e
    if not isinstance(header, dict):
        raise WireError(
            f"bad frame header: want a JSON object, got {type(header).__name__}")
    return header


async def send_msg(writer: asyncio.StreamWriter, header: dict,
                   body: Buffer | Sequence[Buffer] = b"") -> int:
    """Write one frame; returns the frame's total on-wire byte count.
    ``body`` may be a single buffer or a sequence of buffers — buffers
    are written individually (vectored send, no join; see module
    docstring for the writelines caveat)."""
    head, bufs, total = encode_frame(header, body)
    writer.write(head)
    for b in bufs:
        if len(b):
            writer.write(b)
    await writer.drain()
    return total


async def read_msg(reader: asyncio.StreamReader) -> tuple[dict, bytes]:
    try:
        prefix = await reader.readexactly(_PREFIX.size)
    except asyncio.IncompleteReadError as e:
        raise WireError("connection closed mid-frame") from e
    magic, hdr_len, body_len = _PREFIX.unpack(prefix)
    if magic != MAGIC:
        raise WireError(f"bad magic {magic:#x}")
    if hdr_len > MAX_HEADER or body_len > MAX_BODY:
        raise WireError("frame too large")
    try:
        header = _decode_header(await reader.readexactly(hdr_len))
        body = await reader.readexactly(body_len) if body_len else b""
    except asyncio.IncompleteReadError as e:
        raise WireError("connection closed mid-frame") from e
    return header, body


def pack_chunks(chunks: Sequence[tuple[str, Buffer]]
                ) -> tuple[list[dict], list[Buffer]]:
    """[(digest, data)] → (header chunk table, body buffer list).
    The buffers are the callers' own objects — NOT joined; they flow to
    the transport as a scatter-gather body (docs/wire.md ownership
    rules: the caller must not mutate them until the send completes)."""
    table = [{"digest": d, "length": len(b)} for d, b in chunks]
    return table, [b for _, b in chunks]


def unpack_chunks(table: list[dict], body: Buffer
                  ) -> list[tuple[str, memoryview]]:
    """Chunk table + body → [(digest, payload view)]. Payloads are
    READ-ONLY memoryview slices of ``body`` — zero-copy; they pin the
    body buffer for as long as any of them is referenced."""
    mv = body if isinstance(body, memoryview) else memoryview(body)
    if not mv.readonly:
        mv = mv.toreadonly()
    out: list[tuple[str, memoryview]] = []
    off = 0
    for entry in table:
        try:
            ln = int(entry["length"])
            digest = entry["digest"]
        except (TypeError, ValueError, KeyError) as e:
            # malformed table entry is as recoverable as corrupt bytes —
            # callers catch WireError and fall back to other replicas
            raise WireError(f"malformed chunk table entry: {e!r}") from e
        if ln < 0 or off + ln > len(mv):
            raise WireError("chunk table overruns body")
        out.append((digest, mv[off:off + ln]))
        off += ln
    if off != len(mv):
        raise WireError("body has trailing bytes")
    return out


# --------------------------------------------------------------------- #
# zero-copy framed connections (BufferedProtocol)
# --------------------------------------------------------------------- #

class _FrameReceiver(asyncio.BufferedProtocol):
    """Shared receive machine: the transport ``recv_into``s directly
    into (a) a 16-byte prefix scratch, then (b) ONE per-frame
    ``bytearray(hdr_len + body_len)`` — a single kernel→frame copy per
    frame. ``_on_frame(header, body_view, frame_len)`` fires with a
    read-only view of the body; ``_on_broken(exc)`` fires once when the
    connection dies (malformed frame, EOF, reset).

    Subclasses get outbound flow control too: ``_write_frame`` +
    ``await _drain()`` honor ``pause_writing`` exactly like streams.
    """

    def __init__(self) -> None:
        self._transport: asyncio.Transport | None = None
        self._prefix = bytearray(PREFIX_LEN)
        self._pmv = memoryview(self._prefix)
        self._frame: bytearray | None = None
        self._fmv: memoryview | None = None
        self._hdr_len = 0
        self._got = 0
        self._broken: Exception | None = None
        self._send_paused = False
        self._drain_waiters: list[asyncio.Future] = []

    # ---- protocol callbacks ----

    def connection_made(self, transport) -> None:  # noqa: D401
        self._transport = transport

    def get_buffer(self, sizehint: int) -> memoryview:
        if self._frame is None:
            return self._pmv[self._got:]
        return self._fmv[self._got:]

    def buffer_updated(self, nbytes: int) -> None:
        if self._broken is not None:
            return   # dying transport may still deliver buffered bytes
        self._got += nbytes
        if self._frame is None:
            if self._got < PREFIX_LEN:
                return
            magic, hdr_len, body_len = _PREFIX.unpack(self._prefix)
            if magic != MAGIC:
                self._die(WireError(f"bad magic {magic:#x}"))
                return
            if hdr_len > MAX_HEADER or body_len > MAX_BODY:
                # validated BEFORE the allocation: an adversarial prefix
                # must not make the receiver allocate gigabytes
                self._die(WireError("frame too large"))
                return
            self._hdr_len = hdr_len
            self._got = 0
            if hdr_len + body_len == 0:
                self._deliver(bytearray())
                return
            self._frame = bytearray(hdr_len + body_len)
            self._fmv = memoryview(self._frame)
            return
        if self._got >= len(self._frame):
            frame, self._frame, self._fmv = self._frame, None, None
            self._got = 0
            self._deliver(frame)

    def _deliver(self, frame: bytearray) -> None:
        fv = memoryview(frame).toreadonly()
        try:
            header = _decode_header(fv[:self._hdr_len])
        # not silent: _die tears the connection down and propagates the
        # WireError to every waiter's future
        except WireError as e:  # dfslint: ignore[DFS007]
            self._die(e)
            return
        self._on_frame(header, fv[self._hdr_len:],
                       PREFIX_LEN + len(frame))

    def eof_received(self) -> bool:
        self._fail(WireError("connection closed mid-frame")
                   if (self._frame is not None or self._got)
                   else ConnectionResetError("connection closed"))
        return False     # let the transport close

    def connection_lost(self, exc: Exception | None) -> None:
        self._fail(exc if exc is not None
                   else ConnectionResetError("connection lost"))
        # wake writers parked in _drain so they see the failure
        self._send_paused = False
        for fut in self._drain_waiters:
            if not fut.done():
                fut.set_result(None)
        self._drain_waiters.clear()

    def pause_writing(self) -> None:
        self._send_paused = True

    def resume_writing(self) -> None:
        self._send_paused = False
        for fut in self._drain_waiters:
            if not fut.done():
                fut.set_result(None)
        self._drain_waiters.clear()

    # ---- shared plumbing ----

    def _die(self, exc: Exception) -> None:
        """Protocol violation: record the cause and drop the connection
        PROMPTLY — a malformed frame leaves the stream unparseable, so
        the only safe move is teardown (no hang, no desync)."""
        self._fail(exc)
        if self._transport is not None:
            self._transport.close()

    def _fail(self, exc: Exception) -> None:
        if self._broken is None:
            self._broken = exc
            self._on_broken(exc)

    def _write_frame(self, header: dict,
                     body: Buffer | Sequence[Buffer] = b"") -> int:
        """Vectored frame write (prefix+header, then each buffer as-is);
        returns the frame's on-wire size. Raises if the connection
        already failed."""
        head, bufs, total = encode_frame(header, body)
        self._write_encoded(head, bufs)
        return total

    def _write_encoded(self, head: bytes, bufs: Sequence[Buffer]) -> None:
        if self._broken is not None:
            raise self._broken
        if self._transport is None or self._transport.is_closing():
            raise ConnectionResetError("connection is closed")
        self._transport.write(head)
        for b in bufs:
            if len(b):
                self._transport.write(b)

    async def _drain(self) -> None:
        if self._broken is not None:
            raise self._broken
        if not self._send_paused:
            return
        fut = asyncio.get_running_loop().create_future()
        self._drain_waiters.append(fut)
        await fut
        if self._broken is not None:
            raise self._broken

    def close(self) -> None:
        if self._transport is not None:
            self._transport.close()

    @property
    def closed(self) -> bool:
        return (self._broken is not None or self._transport is None
                or self._transport.is_closing())

    # ---- subclass surface ----

    def _on_frame(self, header: dict, body: memoryview,
                  frame_len: int) -> None:
        raise NotImplementedError

    def _on_broken(self, exc: Exception) -> None:
        raise NotImplementedError


class FrameConnection(_FrameReceiver):
    """Client side of the storage plane: one pooled connection carrying
    strictly request→reply frames (the pool dials more connections for
    concurrency — see InternalClient). Replaces the StreamReader-based
    client path; the on-wire bytes are unchanged.

    Usage::

        conn = await FrameConnection.connect(host, port)
        nsent = await conn.send(header, bufs)     # vectored, drained
        resp, body, nrecv = await conn.reply()    # zero-copy body view
    """

    def __init__(self) -> None:
        super().__init__()
        self._waiter: asyncio.Future | None = None

    @classmethod
    async def connect(cls, host: str, port: int) -> "FrameConnection":
        loop = asyncio.get_running_loop()
        _, conn = await loop.create_connection(cls, host, port)
        return conn

    async def send(self, header: dict,
                   body: Buffer | Sequence[Buffer] = b"") -> int:
        """Write one request frame (returns its on-wire size) and
        register for its reply. One request may be outstanding per
        connection — the contract the pool's checkout/checkin already
        enforces."""
        if self._waiter is not None:
            raise RuntimeError("request already in flight on this "
                               "connection")
        # registered BEFORE the drain await: the reply may arrive while
        # the send is still draining
        self._waiter = asyncio.get_running_loop().create_future()
        try:
            n = self._write_frame(header, body)
            await self._drain()
        except BaseException:
            self._waiter = None
            raise
        return n

    async def reply(self) -> tuple[dict, memoryview, int]:
        """-> (response header, read-only body view, frame byte count).
        The body view borrows the per-frame receive buffer — it stays
        valid for as long as the caller references it."""
        fut = self._waiter
        if fut is None:
            raise RuntimeError("no request in flight")
        try:
            return await fut
        finally:
            self._waiter = None

    def send_torn(self, header: dict,
                  body: Buffer | Sequence[Buffer] = b"",
                  keep: float = 0.5) -> None:
        """CHAOS SEAM (dfs_tpu.chaos, docs/chaos.md): write only the
        first ``keep`` fraction of the whole frame — prefix and header
        included — then close, so the receiver sees a torn frame (cut
        mid-prefix, mid-header, or mid-body: "connection closed
        mid-frame" / torn teardown, the corruption the fuzz tests
        cover, now injectable on a live cluster). The budget is capped
        at total-1 bytes: a 'truncated' frame must NEVER arrive whole —
        an empty-body control op would otherwise be delivered (and
        executed) while the caller counts it failed. Never called
        outside fault injection; the connection is unusable afterwards
        by construction."""
        head, bufs, total = encode_frame(header, body)
        budget = min(max(0, int(total * keep)), total - 1)
        pieces: list[Buffer] = [head, *bufs]
        cut: list[Buffer] = []
        for b in pieces:
            if budget <= 0:
                break
            take = b[:budget] if len(b) > budget else b
            cut.append(take)
            budget -= len(take)
        self._write_encoded(cut[0] if cut else b"", cut[1:])
        self.close()

    def _on_frame(self, header: dict, body: memoryview,
                  frame_len: int) -> None:
        fut = self._waiter
        if fut is None or fut.done():
            # unsolicited frame: the connection is out of sync — drop it
            self._die(WireError("unsolicited frame"))
            return
        fut.set_result((header, body, frame_len))

    def _on_broken(self, exc: Exception) -> None:
        fut = self._waiter
        if fut is not None and not fut.done():
            fut.set_exception(exc)


class FrameServerProtocol(_FrameReceiver):
    """Server side: frames are served STRICTLY one at a time per
    connection — reading pauses while a frame is in service (the same
    backpressure the stream loop had), and ``get_buffer`` bounds every
    recv to the current frame, so a frame is never read ahead of the
    previous one's reply.

    ``handler(conn, header, body_view, frame_len)`` is awaited per
    frame; it replies via ``conn.send_frame(...)`` + ``await
    conn.drain()``. A handler exception tears the connection down (the
    node runtime's handler converts op errors to error replies itself,
    so anything reaching here is a protocol-level failure)."""

    def __init__(self, handler, on_connect=None, on_close=None) -> None:
        super().__init__()
        self._handler = handler
        self._on_connect = on_connect
        self._on_close = on_close
        self._task: asyncio.Task | None = None   # retained: DFS002

    def connection_made(self, transport) -> None:
        super().connection_made(transport)
        if self._on_connect is not None:
            self._on_connect(self)

    def _on_frame(self, header: dict, body: memoryview,
                  frame_len: int) -> None:
        self._transport.pause_reading()
        self._task = asyncio.get_running_loop().create_task(
            self._serve(header, body, frame_len))
        self._task.add_done_callback(self._served)

    async def _serve(self, header: dict, body: memoryview,
                     frame_len: int) -> None:
        await self._handler(self, header, body, frame_len)

    def _served(self, task: asyncio.Task) -> None:
        self._task = None
        if not task.cancelled() and task.exception() is not None:
            self._die(WireError(
                f"handler failed: {task.exception()!r}"))
            return
        if self._broken is None and self._transport is not None \
                and not self._transport.is_closing():
            self._transport.resume_reading()

    def send_frame(self, header: dict,
                   body: Buffer | Sequence[Buffer] = b"") -> int:
        return self._write_frame(header, body)

    def send_encoded(self, head: bytes, bufs: Sequence[Buffer]) -> None:
        """Write a frame the caller already laid out via
        :func:`encode_frame` (so the header is encoded exactly once —
        the node runtime needs the reply's byte count for its span
        BEFORE sending)."""
        self._write_encoded(head, bufs)

    async def drain(self) -> None:
        await self._drain()

    def _on_broken(self, exc: Exception) -> None:
        # an in-service frame's task is NOT cancelled: ops complete (and
        # fail at the reply write) exactly like the pre-r10 stream loop
        # — a peer hanging up must not abort a half-applied op that the
        # handler would have finished atomically
        if self._on_close is not None:
            self._on_close(self)
