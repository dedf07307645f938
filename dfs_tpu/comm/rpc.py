"""Async client for the internal storage plane.

Fulfils the roles of the reference's outbound peer calls
(HttpURLConnection at StorageNode.java:226-259, 313-350, 471-483) with the
same reliability envelope — per-attempt connect timeouts and bounded retries
(reference: 2 s / 3 attempts, StorageNode.java:208,229-230) — but over the
binary wire format and with a persistent per-peer connection pool (the
reference opens a fresh connection per call and pays Base64 inflation).

Ops mirror the reference's internal API one-to-one:
- store_chunks   ⇔ POST /internal/storeFragments (StorageNode.java:265-293),
  including the hash-echo verification contract (:248-257): the receiver
  recomputes sha256 of every chunk it wrote and echoes the digests.
- announce       ⇔ POST /internal/announceFile  (StorageNode.java:299-311)
- get_chunk      ⇔ GET  /internal/getFragment   (StorageNode.java:489-515)
- get_manifest   — new: manifest fetch fallback (the reference silently loses
  manifests announced while a node was down, SURVEY.md §5.3)
- health         ⇔ GET /status (StorageNode.java:71-74)
"""

from __future__ import annotations

import asyncio
import random
import time
from typing import Any, Callable

from dfs_tpu.comm.wire import (Buffer, FrameConnection, WireError,
                               buffers_nbytes, pack_chunks, unpack_chunks)
from dfs_tpu.config import PeerAddr
from dfs_tpu.utils import deadline
from dfs_tpu.utils.aio import gather_abort_siblings


class RpcError(RuntimeError):
    """Base for storage-plane call failures."""


class RpcUnreachable(RpcError):
    """Transport-level failure: connect/read timed out for every attempt.
    The only error class that should count as evidence a peer is *dead*."""


class RpcRemoteError(RpcError):
    """The peer was reachable and answered with an application-level error
    (e.g. chunk not found). Says nothing about peer liveness."""


class DeadlineExpired(RpcError):
    """The caller's end-to-end deadline ran out before (or between)
    attempts — the work is dead, so no frame is sent and no retry is
    paid (docs/serve.md §deadlines). An RpcError, NOT RpcUnreachable:
    an expired budget says nothing about peer liveness, and the retry
    loop's application-error fast path stops on it by construction."""


class RingEpochMismatch(RpcRemoteError):
    """The peer refused a placement-bearing op because our ring epochs
    differ (docs/membership.md). Carries the peer's epoch and (when the
    peer is ahead) its full ring map, so the stale side can refresh and
    retry without an extra round-trip — the client's ring-aware retry
    (:meth:`InternalClient.call`) does exactly that."""

    def __init__(self, msg: str, epoch: int, ring: dict | None) -> None:
        super().__init__(msg)
        self.epoch = epoch
        self.ring = ring


# placement-bearing ops: the sender's ring epoch rides the header so a
# stale side answers RingEpochMismatch and refreshes instead of
# mis-placing. Metadata/diagnosis ops carry no epoch — they must work
# exactly while the cluster is converging.
_EPOCH_OPS = frozenset({"store_chunks", "get_chunk", "get_chunks",
                        "has_chunks"})


class RetryBudget:
    """Per-peer token bucket gating RETRY attempts (first attempts are
    always free). Pre-r13 every failing call to a partitioned peer paid
    its full retry envelope independently — N concurrent callers times
    ``retries`` attempts is a retry STORM aimed at a link that is
    already sick, and the cluster-wide cost of one partition scaled
    with load instead of with time. The bucket makes retries a shared,
    rate-limited resource per peer: roughly ``refill_per_s`` retries
    per second steady-state with ``capacity`` of burst; beyond that,
    calls fail fast after their first attempt (journaled as
    ``retry_budget_exhausted``) — so a partition costs one budget, not
    a storm, and the health monitor / handoff machinery (which already
    handle a dead peer) take over immediately.

    Single-threaded by design: touched only from the owning event loop
    (the client is loop-affine like its connection pool)."""

    CAPACITY = 10.0
    REFILL_PER_S = 0.5

    def __init__(self, capacity: float = CAPACITY,
                 refill_per_s: float = REFILL_PER_S) -> None:
        self.capacity = float(capacity)
        self.refill_per_s = float(refill_per_s)
        self._tokens: dict[Any, tuple[float, float]] = {}
        self._exhausted: dict[Any, int] = {}

    def take(self, peer) -> bool:
        """Consume one retry token for ``peer``; False = budget empty
        (the caller must fast-fail instead of retrying)."""
        now = time.monotonic()
        tokens, last = self._tokens.get(peer, (self.capacity, now))
        tokens = min(self.capacity,
                     tokens + (now - last) * self.refill_per_s)
        if tokens >= 1.0:
            self._tokens[peer] = (tokens - 1.0, now)
            return True
        self._tokens[peer] = (tokens, now)
        self._exhausted[peer] = self._exhausted.get(peer, 0) + 1
        return False

    def stats(self) -> dict:
        """/metrics ``retryBudget``: remaining tokens + exhaustion
        counts per peer (ids as strings — JSON keys)."""
        now = time.monotonic()
        tokens = {
            str(p): round(min(self.capacity,
                              t + (now - last) * self.refill_per_s), 2)
            for p, (t, last) in sorted(self._tokens.items(),
                                       key=lambda kv: str(kv[0]))}
        return {"capacity": self.capacity,
                "refillPerS": self.refill_per_s,
                "tokens": tokens,
                "exhausted": {str(p): n for p, n in
                              sorted(self._exhausted.items(),
                                     key=lambda kv: str(kv[0]))}}


def slice_payloads(items: list, max_bytes: int,
                   size: Callable[..., int] = lambda it: len(it[1])
                   ) -> list[list]:
    """Split (digest, payload) lists into <= max_bytes slices (always
    at least one item per slice) so no single RPC carries unbounded
    bytes — the receiver hash-echoes a whole call before replying.
    ``max_bytes`` is required: placement passes its ``slice_bytes``
    (instance-scalable) so a default here cannot silently drift.
    ``size`` weighs an item that is not such a pair (the smart client
    batches bare digests by their chunks' lengths)."""
    out: list[list] = []
    cur: list = []
    total = 0
    for it in items:
        n = size(it)
        if cur and total + n > max_bytes:
            out.append(cur)
            cur, total = [], 0
        cur.append(it)
        total += n
    if cur:
        out.append(cur)
    return out


class InternalClient:
    """Storage-plane RPC client with a per-peer persistent-connection
    pool. The server side keeps framed connections open across requests
    (runtime._serve_internal_frame serves frame after frame until the
    connection dies), so reconnecting per call — the reference's
    behavior, and this client's until round 3 — paid a connect
    round-trip on every has_chunks/store/fetch. Since round 10 each
    pooled connection is a zero-copy :class:`FrameConnection`
    (BufferedProtocol receive, scatter-gather send — docs/wire.md)."""

    _MAX_IDLE_PER_PEER = 4

    def __init__(self, connect_timeout_s: float = 2.0,
                 request_timeout_s: float = 10.0, retries: int = 3,
                 coalesce_fetches: bool = False, obs=None,
                 chaos=None, ring=None) -> None:
        self.connect_timeout_s = connect_timeout_s
        self.request_timeout_s = request_timeout_s
        self.retries = retries
        # Membership seam (dfs_tpu.ring.manager.RingManager): when set,
        # placement-bearing calls carry the ring epoch and a
        # RingEpochMismatch reply triggers the converge-and-retry path
        # (adopt the peer's newer map, or push ours to a stale peer).
        # None (standalone tools) = the pre-r14 wire exactly.
        self._ring = ring
        # Observability hook (dfs_tpu.obs): when set, every call records
        # per-peer per-op client metrics, opens an `rpc.<op>` span, and
        # attaches the trace context to the wire header so the peer's
        # server span parents to it. None (the pre-r09 behavior, and
        # what standalone tools get) changes nothing on the wire.
        self._obs = obs
        # Chaos seam (dfs_tpu.chaos): when set, every call first asks
        # the injector about partitions / link latency / drops /
        # truncation. None (the default everywhere outside an enabled
        # ChaosConfig) is one branch per call.
        self._chaos = chaos
        # retry storms: retries (never first attempts) draw from a
        # per-peer token bucket; exhaustion -> fast-fail (see RetryBudget)
        self.retry_budget = RetryBudget()
        # decorrelated-jitter backoff draws; independent of the chaos
        # layer's deterministic decision stream on purpose (backoff
        # timing is not part of the fault schedule)
        self._backoff_rng = random.Random()
        self._pool: dict[tuple[str, int], list[FrameConnection]] = {}
        # Per-(peer, digest) single-flight for get_chunk: with the
        # serving tier on, concurrent readers racing to the SAME replica
        # for the SAME immutable chunk collapse into one wire transfer
        # (a failure reaches the coalesced callers and clears — see
        # serve.singleflight). Off by default: identical call behavior.
        self._flight = None
        if coalesce_fetches:
            from dfs_tpu.serve.singleflight import SingleFlight

            self._flight = SingleFlight()

    def _checkout(self, peer: PeerAddr) -> FrameConnection | None:
        """Pop a live pooled connection, or None to signal a fresh dial."""
        pool = self._pool.get((peer.host, peer.internal_port))
        while pool:
            conn = pool.pop()
            if conn.closed:
                conn.close()
                continue
            return conn
        return None

    def _checkin(self, peer: PeerAddr, conn: FrameConnection) -> None:
        pool = self._pool.setdefault((peer.host, peer.internal_port), [])
        if len(pool) < self._MAX_IDLE_PER_PEER and not conn.closed:
            pool.append(conn)
        else:
            conn.close()

    def close(self) -> None:
        """Drop every pooled connection (node shutdown)."""
        for pool in self._pool.values():
            for conn in pool:
                conn.close()
        self._pool.clear()

    # bulk transfers budget extra time per byte on top of the base
    # request timeout: a 32 MiB store slice plus its server-side hash
    # echo blew a flat 10 s budget on a contended 1-core host (every
    # peer "timed out", failing a 2 GiB upload below quorum). 1 MB/s is
    # the assumed worst-case effective bandwidth — GiB-class ingest on
    # one core measured 4-8 MB/s end to end (the receiver creates one
    # file per chunk; fs metadata dominates) with multi-second writeback
    # stalls on top
    _BULK_BYTES_PER_S = 1024 * 1024

    def _bulk_timeout(self, n_bytes: int) -> float:
        return self.request_timeout_s + n_bytes / self._BULK_BYTES_PER_S

    async def _request(self, conn: FrameConnection, header: dict, body,
                       timeout_s: float | None = None,
                       acct: dict | None = None) -> tuple[dict, memoryview]:
        t = self.request_timeout_s if timeout_s is None \
            else max(self.request_timeout_s, timeout_s)
        nsent = await asyncio.wait_for(conn.send(header, body), timeout=t)
        if acct is not None:
            acct["out"] += nsent
        resp, rbody, nrecv = await asyncio.wait_for(conn.reply(), timeout=t)
        if acct is not None:
            acct["in"] += nrecv
        return resp, rbody

    async def _call_once(self, peer: PeerAddr, header: dict,
                         body,
                         timeout_s: float | None = None,
                         acct: dict | None = None
                         ) -> tuple[dict, memoryview]:
        rem = deadline.remaining()
        if rem is not None:
            if rem <= 0:
                # expired work must never reach the wire (or, on the
                # receiving side, a worker thread)
                raise DeadlineExpired(
                    f"peer {peer.node_id}: deadline expired before send")
            # remaining budget rides the OPTIONAL `deadline` header
            # field, re-stamped per attempt so every hop (and every
            # retry) carries what is actually left — the hop decrement
            # falls out of sending REMAINING time, not absolute time.
            # Pre-r18 peers ignore unknown header fields (the `trace`
            # compatibility contract, comm/wire.py).
            header["deadline"] = round(rem, 4)
        chaos = self._chaos
        if chaos is not None:
            op = str(header.get("op"))
            # partition: fail before dialing (one-way — only THIS
            # side's sends die); delay/drop: link faults before the
            # frame goes out. All raise OSError subclasses, so the
            # retry/budget/backoff machinery below handles injected
            # faults exactly like real ones.
            chaos.check_partition(peer.node_id, op)
            await chaos.before_rpc(peer.node_id, op)
        conn = self._checkout(peer)
        reused = conn is not None
        if conn is None:
            conn = await asyncio.wait_for(
                FrameConnection.connect(peer.host, peer.internal_port),
                timeout=self.connect_timeout_s)
        if chaos is not None and chaos.truncate_now(peer.node_id,
                                                    str(header.get("op"))):
            # torn frame: prefix promises the full body, half arrives,
            # connection closes — the receiver's mid-frame teardown
            # path (wire fuzz coverage) exercised on a live cluster
            try:
                conn.send_torn(header, body)
            finally:
                conn.close()
            raise ConnectionResetError(
                f"chaos: truncated frame to node {peer.node_id}")
        try:
            resp, rbody = await self._request(conn, header, body,
                                              timeout_s, acct)
        except (ConnectionError, asyncio.IncompleteReadError, WireError):
            # disconnect-class only: a pooled connection the server closed
            # while idle surfaces as reset/EOF on the first frame, and is
            # not evidence the peer is down — retry ONCE on a fresh dial.
            # A request TIMEOUT must NOT take this path: the peer may
            # still be processing, and a silent resend would duplicate
            # work and double the health monitor's fast-fail budget.
            conn.close()
            if not reused:
                raise
            conn = await asyncio.wait_for(
                FrameConnection.connect(peer.host, peer.internal_port),
                timeout=self.connect_timeout_s)
            try:
                resp, rbody = await self._request(conn, header, body,
                                                  timeout_s, acct)
            except BaseException:
                conn.close()
                raise
        except BaseException:
            conn.close()
            raise
        # request/response completed: the connection is still in frame
        # sync even for an application-level error — pool it either way
        self._checkin(peer, conn)
        if not resp.get("ok", False):
            re = resp.get("ringEpoch")
            if isinstance(re, int) and not isinstance(re, bool):
                # structured membership refusal: carry the peer's epoch
                # (+ map) so call()'s converge-and-retry path can fix
                # the stale side without an extra round-trip
                raise RingEpochMismatch(
                    f"peer {peer.node_id} error: {resp.get('error')}",
                    epoch=re, ring=resp.get("ring")
                    if isinstance(resp.get("ring"), dict) else None)
            raise RpcRemoteError(
                f"peer {peer.node_id} error: {resp.get('error')}")
        return resp, rbody

    async def call(self, peer: PeerAddr, header: dict,
                   body: Buffer | list[Buffer] = b"",
                   retries: int | None = None,
                   timeout_s: float | None = None
                   ) -> tuple[dict, memoryview]:
        """Bounded-retry call (reference: 3 attempts, StorageNode.java:208).
        ``body`` may be one buffer or a buffer list — it rides the wire
        as a scatter-gather frame, never joined. The returned body is a
        read-only view of the reply frame (zero-copy). ``retries``
        overrides the default — the node runtime passes 1 for peers its
        health monitor believes are dead (fast-fail probe). ``timeout_s``
        raises (never lowers) the per-attempt budget — bulk ops pass a
        size-derived value (:meth:`_bulk_timeout`).

        With an obs hook: opens an ``rpc.<op>`` span, propagates the
        trace context in the header's optional ``trace`` field (peers
        that predate the field ignore it), and records per-peer per-op
        count/latency/bytes/errors into the client RPC table — byte
        counts are FRAME sizes (prefix + header + body), what the
        socket actually carried, summed across retry attempts."""
        if self._ring is not None \
                and header.get("op") in _EPOCH_OPS \
                and "repoch" not in header:
            # placement-bearing op: stamp the sender's ring epoch AND
            # map fingerprint so a stale side — including one holding a
            # DIFFERENT map at the same epoch (racing admins) — answers
            # RingEpochMismatch instead of silently mis-placing
            # (docs/membership.md)
            header["repoch"] = self._ring.epoch
            header["rfp"] = self._ring.current.fingerprint
        obs = self._obs
        if obs is None:
            return await self._call_converging(peer, header, body,
                                               retries, timeout_s)
        op = str(header.get("op"))
        with obs.span(f"rpc.{op}", peer=peer.node_id) as sp:
            # attach INSIDE the span: the rpc span's own id is what the
            # peer's server span must parent to
            tr = obs.wire_trace()
            if tr is not None:
                header["trace"] = tr
            t0 = time.perf_counter()
            acct = {"out": 0, "in": 0}
            failed = True
            try:
                resp, rbody = await self._call_converging(
                    peer, header, body, retries, timeout_s, acct)
                failed = False
                sp.bytes = acct["out"] + acct["in"]
                return resp, rbody
            finally:
                obs.rpc_client.record(
                    peer.node_id, op, time.perf_counter() - t0,
                    bytes_out=acct["out"], bytes_in=acct["in"],
                    error=failed)

    async def _call_converging(self, peer: PeerAddr, header: dict,
                               body, retries: int | None,
                               timeout_s: float | None,
                               acct: dict | None = None
                               ) -> tuple[dict, memoryview]:
        """``_call_retrying`` plus the one-shot epoch-convergence path:
        a RingEpochMismatch reply means the two sides disagree on
        membership — the LOWER epoch refreshes (we adopt the peer's
        newer map straight from the refusal; a stale peer gets ours
        pushed via ``propose_ring``) and the original call retries
        exactly once at the converged epoch. A second mismatch (racing
        epoch bumps) propagates as the application error it is — the
        caller's normal retry machinery picks it up later."""
        try:
            return await self._call_retrying(peer, header, body, retries,
                                             timeout_s, acct)
        except RingEpochMismatch as e:
            ring = self._ring
            if ring is None:
                raise
            ring.note_epoch_mismatch()
            # the (epoch, fingerprint) total order decides who is
            # stale: adopt() installs the peer's map iff it beats
            # ours — otherwise OURS wins and the peer gets it pushed.
            # Covers racing same-epoch maps, not just lagging epochs.
            adopted = False
            if e.ring is not None:
                try:
                    adopted = ring.adopt(e.ring,
                                         source=f"mismatch:"
                                                f"{peer.node_id}")
                except ValueError:
                    raise e from None   # malformed map from the peer
            if not adopted:
                # peer's map lost (or was absent): teach it ours
                await self._call_retrying(
                    peer, {"op": "propose_ring",
                           "ring": ring.current.to_dict()},
                    b"", 1, None, acct)
            header["repoch"] = ring.epoch
            header["rfp"] = ring.current.fingerprint
            return await self._call_retrying(peer, header, body, retries,
                                             timeout_s, acct)

    # decorrelated-jitter backoff bounds (Brooker, "Exponential Backoff
    # And Jitter"): sleep_n = min(CAP, uniform(BASE, 3 * sleep_{n-1})).
    # Jitter decorrelates the N callers a partition makes fail at the
    # same instant; the cap bounds a single call's worst-case stall.
    _BACKOFF_BASE_S = 0.05
    _BACKOFF_CAP_S = 0.5

    async def _call_retrying(self, peer: PeerAddr, header: dict,
                             body, retries: int | None,
                             timeout_s: float | None,
                             acct: dict | None = None
                             ) -> tuple[dict, memoryview]:
        attempts = retries if retries is not None else self.retries
        op = header.get("op")
        last: Exception | None = None
        prev_sleep = self._BACKOFF_BASE_S
        for attempt in range(attempts):
            if attempt:
                # retries draw from the per-peer budget; an empty
                # bucket means this peer is already eating a storm —
                # fail fast and let the health/handoff machinery (which
                # already handles a dead peer) take over
                if not self.retry_budget.take(peer.node_id):
                    if self._obs is not None:
                        self._obs.event("retry_budget_exhausted",
                                        peer=peer.node_id, op=str(op),
                                        attempt=attempt,
                                        cause=type(last).__name__
                                        if last else None)
                    raise RpcUnreachable(
                        f"peer {peer.node_id} retry budget exhausted "
                        f"after {attempt} attempt(s): "
                        f"{type(last).__name__}: {last}")
                if self._obs is not None:
                    self._obs.rpc_client.retry(peer.node_id, str(op))
                    # journal the retry (flight recorder): a retry storm
                    # on one peer is the classic early sign of a sick
                    # link, and the per-call metrics only keep totals,
                    # not WHEN
                    self._obs.event("rpc_retry", peer=peer.node_id,
                                    op=str(op), attempt=attempt,
                                    cause=type(last).__name__ if last
                                    else None)
            try:
                return await self._call_once(peer, header, body, timeout_s,
                                             acct)
            except RpcError:
                raise  # application-level error: retrying won't help
            # not silent: the retry is metered (rpc_client.retry) and
            # journaled (rpc_retry) at the top of the next attempt, and
            # the terminal failure emits rpc_unreachable + raises
            except (OSError, asyncio.TimeoutError, RuntimeError) as e:
                last = e
                if attempt + 1 < attempts:
                    prev_sleep = min(
                        self._BACKOFF_CAP_S,
                        self._backoff_rng.uniform(self._BACKOFF_BASE_S,
                                                  3.0 * prev_sleep))
                    rem = deadline.remaining()
                    if rem is not None \
                            and rem < prev_sleep + self.connect_timeout_s:
                        # the remaining budget cannot cover the backoff
                        # plus even a connect — another attempt is pure
                        # waste aimed at a caller that will be gone
                        if self._obs is not None:
                            self._obs.event("deadline_shed",
                                            where="rpc_retry",
                                            peer=peer.node_id,
                                            op=str(op), attempt=attempt)
                        raise DeadlineExpired(
                            f"peer {peer.node_id} {op}: deadline cannot "
                            f"cover another attempt ({rem:.3f}s left): "
                            f"{type(e).__name__}: {e}") from e
                    await asyncio.sleep(prev_sleep)
        if self._obs is not None:
            self._obs.event("rpc_unreachable", peer=peer.node_id,
                            op=str(op), attempts=attempts,
                            cause=type(last).__name__)
        raise RpcUnreachable(
            f"peer {peer.node_id} unreachable after {attempts} attempts: "
            f"{type(last).__name__}: {last}")   # TimeoutError strs empty

    # ---- typed ops ----

    async def store_chunks(self, peer: PeerAddr, file_id: str,
                           chunks: list[tuple[str, Buffer]]) -> list[str]:
        """Send chunks; returns the receiver's recomputed digests (hash echo,
        reference contract StorageNode.java:248-257). Caller verifies.
        Payloads go out as a scatter-gather body — the caller's buffers
        are written as-is, never joined (docs/wire.md)."""
        table, bufs = pack_chunks(chunks)
        resp, _ = await self.call(
            peer, {"op": "store_chunks", "fileId": file_id, "chunks": table},
            bufs, timeout_s=self._bulk_timeout(buffers_nbytes(bufs)))
        return list(resp.get("digests", []))

    async def store_chunks_windowed(
            self, peer: PeerAddr, file_id: str,
            slices: list[list[tuple[str, bytes]]], window: int = 2,
            on_slice=None) -> int:
        """Send payload slices with up to ``window`` concurrently in
        flight to ONE peer, over pooled connections (each in-flight
        slice rides its own connection — the pool dials as needed and
        keeps up to ``_MAX_IDLE_PER_PEER`` warm). Strictly-serial slice
        sending left the wire idle while the receiver ran its hash-echo
        pass over the previous slice; windowing overlaps transfer of
        slice N+1 with the peer verifying slice N.

        ``on_slice(part, echoed)`` runs as each slice completes
        (completion order) — the caller verifies the hash echo and does
        per-slice accounting there; an exception it raises cancels the
        remaining in-flight slices and propagates (so a mismatch fails
        the peer exactly like the serial path did). Returns the peak
        number of slices that were actually in flight at once."""
        window = max(1, window)
        if window == 1 or len(slices) <= 1:
            for part in slices:
                echoed = await self.store_chunks(peer, file_id, part)
                if on_slice is not None:
                    on_slice(part, echoed)
            return 1 if slices else 0
        sem = asyncio.Semaphore(window)
        inflight = 0
        peak = 0

        async def one(part: list[tuple[str, bytes]]) -> None:
            nonlocal inflight, peak
            async with sem:
                inflight += 1
                peak = max(peak, inflight)
                try:
                    echoed = await self.store_chunks(peer, file_id, part)
                finally:
                    inflight -= 1
                if on_slice is not None:
                    on_slice(part, echoed)

        await gather_abort_siblings(*(one(p) for p in slices))
        return peak

    async def has_chunks(self, peer: PeerAddr, digests: list[str], *,
                         resident_ok: bool = False,
                         retries: int | None = None) -> set[str]:
        """Which of ``digests`` the peer says it holds. ``resident_ok``
        lets the peer's resident set answer instead of a look at the
        disk (store/cas.py ``has``): placement's probes and pre-ack
        rounds pass it, because what they are told is re-counted before
        any ack; the repair cycle and the read path's who-has sweep do
        not, and their look at the disk heals the set."""
        header = {"op": "has_chunks", "digests": digests}
        if resident_ok:
            header["residentOk"] = True
        resp, _ = await self.call(peer, header, retries=retries)
        return set(resp.get("have", []))

    async def announce(self, peer: PeerAddr, manifest_json: str,
                       fresh: bool = False) -> None:
        """``fresh=True`` marks an announce coming straight from an upload
        in progress — receivers clear any tombstone for the file id (a new
        upload resurrects deleted content on purpose). Replayed/stale
        announces leave it unset and bounce off tombstones."""
        await self.call(peer, {"op": "announce", "manifest": manifest_json,
                               "fresh": fresh})

    async def get_chunk(self, peer: PeerAddr, digest: str) -> memoryview:
        """Fetch one chunk; the result is a read-only view of the reply
        frame (zero-copy — callers that need to retain it independently
        of other references copy explicitly, e.g. the serve cache)."""
        if self._flight is None:
            _, body = await self.call(
                peer, {"op": "get_chunk", "digest": digest})
            return body
        key = (peer.host, peer.internal_port, digest)
        leader, fut = self._flight.claim(key)
        if not leader:
            # raises whatever RpcError the leader rejected with — never
            # the leader's own CancelledError (converted below), so a
            # coalesced caller whose request is alive falls back to the
            # next replica like any failed fetch. The wait gets its own
            # span: a coalesced caller's trace must show WHERE its
            # latency went (waiting on another flight, not the wire).
            if self._obs is not None:
                with self._obs.span("rpc.get_chunk.wait",
                                    peer=peer.node_id):
                    return await self._flight.wait(fut)
            return await self._flight.wait(fut)
        try:
            _, body = await self.call(
                peer, {"op": "get_chunk", "digest": digest})
        except BaseException as e:
            exc = e if isinstance(e, RpcError) else RpcRemoteError(
                f"coalesced fetch aborted: {type(e).__name__}: {e}")
            self._flight.reject(key, exc)
            raise
        self._flight.resolve(key, body)
        return body

    async def get_chunks(self, peer: PeerAddr, digests: list[str],
                         retries: int | None = None,
                         expect_bytes: int = 0
                         ) -> list[tuple[str, memoryview]]:
        """Batched fetch: returns (digest, payload view) for every
        requested chunk the peer holds (missing ones are absent — no
        error). Payloads are read-only slices of the ONE reply frame —
        zero-copy; referencing any of them pins the frame buffer.
        ``retries`` as in :meth:`call` (callers pass 1 for known-dead
        peers); ``expect_bytes`` sizes the timeout for the expected
        response payload."""
        resp, body = await self.call(
            peer, {"op": "get_chunks", "digests": digests},
            retries=retries, timeout_s=self._bulk_timeout(expect_bytes))
        return unpack_chunks(resp.get("chunks", []), body)

    # readdir+stat budget of a peer's CAS walk: a census over 2 GiB on
    # the chip machine's disk ran past the flat request timeout and read
    # as "2 peer(s) unreachable" (PERF.md, PR 21)
    _CENSUS_CHUNKS_PER_S = 1000

    async def get_census(self, peer: PeerAddr,
                         prefixes: list[str] | None = None,
                         retries: int | None = None,
                         expect_chunks: int = 0) -> dict | None:
        """Census inventory of one peer (docs/observability.md): the
        bucketed CAS summary, or — with ``prefixes`` — member digest
        lists for exactly those buckets (the census drill-down; the
        receiver caps each list). Callers pass ``retries=1``: the
        census is partial-on-dead by contract, so a dead peer must cost
        one fast probe, not the full retry envelope. ``expect_chunks``
        (how many chunks the peer may have to walk) raises the
        per-attempt budget like a bulk transfer's byte count does."""
        header: dict = {"op": "get_census"}
        if prefixes:
            header["prefixes"] = list(prefixes)
        resp, _ = await self.call(
            peer, header, retries=retries,
            timeout_s=self.request_timeout_s
            + expect_chunks / self._CENSUS_CHUNKS_PER_S)
        census = resp.get("census")
        return census if isinstance(census, dict) else None

    async def get_filter(self, peer: PeerAddr,
                         retries: int | None = None
                         ) -> tuple[dict | None, memoryview]:
        """Full peer-existence filter snapshot (docs/index.md):
        (meta, filter-bytes view) — meta None when the peer runs no
        filter plane (pre-r16 build or filters off). The body view is
        zero-copy; callers that retain the filter past the reply frame
        copy explicitly (runtime ``_filter_fetch_full``)."""
        resp, body = await self.call(peer, {"op": "get_filter"},
                                     retries=retries)
        meta = resp.get("filter")
        return (meta if isinstance(meta, dict) else None), body

    async def get_filters(self, peer: PeerAddr,
                          retries: int | None = None
                          ) -> list[tuple[dict, memoryview]]:
        """Batched existence-filter fetch (docs/client.md): every
        filter replica the peer holds — its OWN filter first, then its
        replicas of the other nodes' — as (meta, filter-bytes view)
        pairs. Each meta carries nodeId/gen/version/capacity/bitsPerKey/
        ageS/length; the blobs ride concatenated in table order in one
        reply body. Lets an external smart client learn the whole
        cluster's existence summaries from ONE peer. Empty on a peer
        with no filter plane; pre-r19 peers answer unknown-op (an
        RpcRemoteError — callers degrade to probing)."""
        resp, body = await self.call(peer, {"op": "get_filters"},
                                     retries=retries)
        out: list[tuple[dict, memoryview]] = []
        off = 0
        for meta in resp.get("filters", []):
            ln = int(meta.get("length", 0))
            out.append((meta, body[off:off + ln]))
            off += ln
        return out

    async def filter_delta(self, peer: PeerAddr, gen: int, since: int,
                           retries: int | None = None) -> dict:
        """Incremental filter update from (generation, version): the
        reply carries ``adds`` (digests since ``since``) or
        ``resync: true`` when the replica must refetch the full filter
        — generation moved, unknown cursor, or the peer's add log no
        longer reaches back (at-least-once, like propose_ring)."""
        resp, _ = await self.call(
            peer, {"op": "filter_delta", "gen": gen, "since": since},
            retries=retries)
        return resp

    async def get_manifest(self, peer: PeerAddr, file_id: str
                           ) -> tuple[str | None, float | None]:
        """-> (manifest json or None, origin mtime or None). The mtime is
        the peer's on-disk write time — adopters must preserve it (LWW
        against tombstones)."""
        resp, _ = await self.call(peer, {"op": "get_manifest", "fileId": file_id})
        return resp.get("manifest"), resp.get("mtime")

    async def health(self, peer: PeerAddr) -> dict[str, Any]:
        resp, _ = await self.call(peer, {"op": "health"})
        return resp
