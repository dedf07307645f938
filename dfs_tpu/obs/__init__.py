"""Request-scoped distributed tracing + unified metrics (docs/observability.md).

The reference system has only ``printf`` logging (SURVEY.md §5.5); this
node until round 9 had three disconnected metric registries and zero
request correlation across nodes — a slow multi-peer download (gather →
``_fetch_chunk`` → peer get → singleflight wait) was undiagnosable. This
package is the Dapper-shaped fix (Sigelman et al., 2010; Canopy, Kaldor
et al., SOSP 2017): cheap ALWAYS-ON trace contexts propagated on every
hop, collected in a bounded per-node ring, stitched post-hoc.

Three pieces:

- **Trace context** — a ``(trace_id, span_id)`` pair carried in a
  :mod:`contextvars` variable, so every async hop of a request (placement
  tasks, the async CAS pool await, singleflight waiters, admission queue
  waits) inherits it without plumbing. It crosses processes as the
  ``X-Dfs-Trace: <trace32hex>-<span16hex>`` HTTP header (api/http.py) and
  as an OPTIONAL ``trace`` field ``{"t","s","f"}`` in the storage-plane
  JSON wire header (comm/rpc.py) — old peers ignore the field, new peers
  tolerate its absence (backward compatible by construction).
- **Span collection** — :meth:`Observability.span` records finished
  spans (name, ids, wall start, ``m0`` = CLOCK_MONOTONIC ns at open,
  duration, peer, bytes, error) into a bounded ring
  (``ObsConfig.trace_ring`` entries; 0 disables tracing entirely and
  the context var is never even read). Served at
  ``GET /trace?traceId=…`` and stitched cluster-wide by
  :mod:`dfs_tpu.obs.stitch` + the ``trace <id>`` CLI subcommand.
  ``m0`` is one clock for every process of a host, the clock a device
  profile's session start is stamped with (benchmarks/owner.py), so a
  span can be laid beside a device op.
- **Span totals** — every finished span also lands in a bounded
  per-name table (count, seconds, self seconds), served as
  ``/metrics`` ``obs.spans``: where a layer's time goes, summed where
  the work happens. Self time is the span's duration minus the union
  of its direct children's intervals in the same process.
- **Unified metrics** — :class:`RpcStats` (per-peer per-op RPC
  count/latency/bytes/errors/retries, client and server side) and the
  Prometheus text exposition (:mod:`dfs_tpu.obs.prom`) flattening every
  registry, histogram buckets included, at ``GET /metrics?format=prom``.

Cost discipline: with ``trace_ring=0`` every tracing call is one ``is
None`` branch; with it on, an untraced call path (no inbound context,
not an entry point) pays one ContextVar read. OBS_r09.json holds the
measured hot-read overhead (≤2% vs ``trace_ring=0``).
"""

from __future__ import annotations

import contextlib
import contextvars
import os
import threading
import time
from collections import deque

from dfs_tpu.utils.logging import capped_key
from dfs_tpu.utils.trace import LatencyRecorder

# the current (trace_id, span_id) of this task/thread, or None when the
# request was never traced. ContextVar semantics give the propagation
# for free: asyncio.create_task / asyncio.to_thread copy the context, so
# placement windows and worker-thread hops inherit the ids.
_ctx: contextvars.ContextVar[tuple[str, str] | None] = \
    contextvars.ContextVar("dfs_trace_ctx", default=None)

TRACE_HEX = 32   # 16 random bytes
SPAN_HEX = 16    # 8 random bytes


def new_trace_id() -> str:
    return os.urandom(TRACE_HEX // 2).hex()


def new_span_id() -> str:
    return os.urandom(SPAN_HEX // 2).hex()


def current() -> tuple[str, str] | None:
    """(trace_id, span_id) active in this context, or None."""
    return _ctx.get()


_HEX = frozenset("0123456789abcdef")


def is_id(s, n: int) -> bool:
    """Exactly ``n`` lowercase hex chars — the canonical id form
    (os.urandom().hex()). Strict charset on purpose: int(s, 16) also
    accepts '0x'/sign/underscore forms that would let malformed ids
    slip into rings and wire fields."""
    return isinstance(s, str) and len(s) == n and set(s) <= _HEX


def parse_http_trace(value: str | None) -> tuple[str, str] | None:
    """``X-Dfs-Trace`` header value ``<trace>-<span>`` -> (trace_id,
    parent_span_id), or None for absent/malformed (never raises — a bad
    header must not fail the request it rides on)."""
    if not value:
        return None
    t, sep, s = value.strip().partition("-")
    if sep and is_id(t, TRACE_HEX) and is_id(s, SPAN_HEX):
        return t, s
    return None


def parse_wire_trace(field) -> tuple[str, str, int | None] | None:
    """Wire-header ``trace`` field ``{"t","s"[,"f"]}`` -> (trace_id,
    parent_span_id, sender node id or None). None for absent/malformed
    — pre-r09 peers simply never send the field."""
    if not isinstance(field, dict):
        return None
    t, s = field.get("t"), field.get("s")
    if not (is_id(t, TRACE_HEX) and is_id(s, SPAN_HEX)):
        return None
    f = field.get("f")
    return t, s, (f if isinstance(f, int) and not isinstance(f, bool)
                  else None)


class Span:
    """Mutable annotations a caller may set while its span is open."""

    __slots__ = ("bytes", "err")

    def __init__(self) -> None:
        self.bytes = 0
        self.err: str | None = None


# shared by every no-op path; its annotations are written and discarded
_NULL_SPAN = Span()


class RpcStats:
    """Per-(peer, op) RPC counters: calls, errors, retries, bytes
    out/in, total seconds. One instance per direction (client / server).
    Key cardinality is capped — a hostile or buggy peer label stream
    folds into ``("_overflow", "_overflow")`` instead of growing
    ``/metrics`` unboundedly (same discipline as Counters)."""

    _MAX_KEYS = 256
    # recency window behind snapshot()'s recentSeconds/recentCount: the
    # doctor's slow_peer rule reads WINDOWED means, so a peer that spent
    # an hour dead (accumulating ~75ms connect-timeout "calls" in the
    # lifetime table) is not diagnosed slow forever after it recovers —
    # the same no-latching rationale as shed_storm/loop_lag. The per-key
    # sample is bounded: at extreme call rates the window simply covers
    # the most recent _RECENT_MAX calls.
    RECENT_WINDOW_S = 60.0
    _RECENT_MAX = 512

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # (peer, op) -> [count, errors, retries, bytes_out, bytes_in, s]
        self._m: dict[tuple, list] = {}
        # (peer, op) -> [deque[(monotonic ts, seconds, error)],
        # rolling sum, rolling count, ok-only sum, ok-only count] for
        # the window — sums maintained on append and expiry so
        # snapshot() never scans a deque under the lock the data
        # plane's record() takes. The all-samples pair feeds the
        # doctor's slow_peer rule (timeouts make a peer slow ON
        # PURPOSE); the ok-only pair feeds the hedge delay (a fast
        # error reply is not "what a healthy fetch takes").
        self._recent: dict[tuple, list] = {}
        self._overflow_warned = False

    def _row(self, peer, op) -> tuple[tuple, list]:
        key = capped_key(self._m, (peer, op), self._MAX_KEYS, self,
                         "RpcStats", ("_overflow", "_overflow"))
        row = self._m.get(key)
        if row is None:
            row = self._m[key] = [0, 0, 0, 0, 0, 0.0]
        return key, row

    def record(self, peer, op: str, seconds: float, bytes_out: int = 0,
               bytes_in: int = 0, error: bool = False) -> None:
        now = time.monotonic()
        with self._lock:
            key, row = self._row(peer, op)
            row[0] += 1
            if error:
                row[1] += 1
            row[3] += bytes_out
            row[4] += bytes_in
            row[5] += seconds
            ent = self._recent.get(key)
            if ent is None:
                ent = self._recent[key] = [deque(), 0.0, 0, 0.0, 0]
            ent[0].append((now, seconds, error))
            ent[1] += seconds
            ent[2] += 1
            if not error:
                ent[3] += seconds
                ent[4] += 1
            self._expire(ent, now)

    def _expire(self, ent: list, now: float) -> None:
        """Drop window-expired (and over-bound) samples, keeping the
        rolling sums exact. Lock held by the caller."""
        dq = ent[0]
        cutoff = now - self.RECENT_WINDOW_S
        while dq and (dq[0][0] < cutoff or len(dq) > self._RECENT_MAX):
            _, s, err = dq.popleft()
            ent[1] -= s
            ent[2] -= 1
            if not err:
                ent[3] -= s
                ent[4] -= 1
        if ent[2] == 0:
            ent[1] = 0.0   # re-zero float drift at every empty window
        if ent[4] == 0:
            ent[3] = 0.0

    def retry(self, peer, op: str) -> None:
        with self._lock:
            _, row = self._row(peer, op)
            row[2] += 1

    def recent_best_mean(self, op: str) -> float | None:
        """The LOWEST per-peer windowed mean of SUCCESSFUL calls for
        ``op`` — "what a healthy replica currently takes". Successful
        only: a live peer answering fast *errors* (a 1 ms chunk-miss
        reply during placement skew) would otherwise collapse the best
        mean — and with it the hedge delay — to the floor, tripping a
        hedge on nearly every remote fetch. And the BEST replica's
        mean, not the primary's own: seeding from the primary is
        self-referential — its slow samples would push its own hedge
        delay past its slowness and disable hedging exactly when it is
        needed (observed live in r18 bring-up: three reads against a
        250 ms-slow replica walked the delay 59→177→300 ms and the
        third read never hedged). O(peers) under the lock, called once
        per remote fetch."""
        now = time.monotonic()
        best: float | None = None
        with self._lock:
            for (p, o), ent in self._recent.items():
                if o != op:
                    continue
                self._expire(ent, now)
                if ent[4] == 0:
                    continue
                mean = ent[3] / ent[4]
                if best is None or mean < best:
                    best = mean
        return best

    def snapshot(self) -> dict:
        """JSON /metrics shape: '<peer>:<op>' -> counters dict.
        ``recentSeconds``/``recentCount`` cover RECENT_WINDOW_S."""
        now = time.monotonic()
        with self._lock:
            out = {}
            for (p, o), r in sorted(self._m.items(),
                                    key=lambda kv: str(kv[0])):
                ent = self._recent.get((p, o))
                if ent is not None:
                    self._expire(ent, now)
                    rs, rc = ent[1], ent[2]
                else:
                    rs, rc = 0.0, 0
                out[f"{p}:{o}"] = {"count": r[0], "errors": r[1],
                                   "retries": r[2], "bytesOut": r[3],
                                   "bytesIn": r[4],
                                   "seconds": round(r[5], 6),
                                   "recentSeconds": round(rs, 6),
                                   "recentCount": rc}
            return out

    def rows(self) -> list[tuple[str, str, list]]:
        """(peer, op, [count, errors, retries, bytes_out, bytes_in, s])
        rows for the Prometheus exposition."""
        with self._lock:
            return [(str(p), str(o), list(r))
                    for (p, o), r in sorted(self._m.items(),
                                            key=lambda kv: str(kv[0]))]


def _span_dict(r: tuple) -> dict:
    tid, sid, parent, name, node, t_wall, dur, peer, nbytes, err, m0 = r
    d = {"t": tid, "s": sid, "p": parent, "name": name, "node": node,
         "t0": round(t_wall, 6), "d": round(dur, 6), "m0": m0}
    if peer is not None:
        d["peer"] = peer
    if nbytes:
        d["bytes"] = nbytes
    if err:
        d["err"] = err
    return d


class _Open:
    """What one OPEN span knows of its direct children in this process,
    for its self time: each child registers ``[start, end]`` (clock ns,
    ``end`` None while it runs) when it opens. ``covered`` is the part
    of the children's union that is already final — below ``floor`` —
    so a span with thousands of short children (a per-chunk read under
    a streamed download) holds its open children and a number, not
    every interval it ever had. Every access is under the owning
    Observability's lock."""

    __slots__ = ("floor", "covered", "kids", "fold_at")
    _FOLD_AT = 256

    def __init__(self, start: int) -> None:
        self.floor = start      # no interval counts below this
        self.covered = 0        # ns of [span start, floor) under a child
        self.kids: list[list] = []
        self.fold_at = self._FOLD_AT

    def add(self, kid: list) -> None:
        if len(self.kids) >= self.fold_at:
            self.fold(kid[0], final=False)
            self.fold_at = max(self._FOLD_AT, 2 * len(self.kids))
        self.kids.append(kid)

    def fold(self, now: int, final: bool) -> None:
        """Union the children's intervals into ``covered`` up to the
        earliest start of a child still open (``now`` when none is, or
        when the span itself closes: a child that outlives its parent
        is clipped to the parent's end). A later child starts at
        ``now`` or after and the open ones reach at least ``now``, so
        nothing below that cut can change, and the closed intervals
        above it lie inside an open child's."""
        live = [] if final else [k for k in self.kids if k[1] is None]
        cut = min([k[0] for k in live], default=now)
        hi = self.floor
        for s, e in sorted((k[0], now if k[1] is None else k[1])
                           for k in self.kids):
            s, e = max(s, hi), min(e, cut)
            if e > s:
                self.covered += e - s
                hi = e
        self.floor = cut
        self.kids = live


class Observability:
    """One node's observability state: span ring + RPC metric tables +
    the shared :class:`LatencyRecorder`, plus (since r11) the diagnosis
    hooks — the flight-recorder journal, the tail-retention store that
    pins slow/errored traces across ring churn, and the sentinel gauge
    surface. Constructed unconditionally by the node runtime;
    ``ObsConfig(trace_ring=0)`` turns every tracing path into a
    constant-time no-op while the metric tables stay live.
    """

    # traces the tail store tracks at once; oldest forgotten first (its
    # already-pinned spans stay until the span-count bound evicts them)
    _MAX_INTERESTING = 128
    # distinct span names the totals table holds; further names fold
    # into "_overflow" (span names are code literals plus allowlisted
    # routes and wire ops, so the cap is a guard, not a budget)
    _MAX_SPAN_NAMES = 256

    def __init__(self, cfg, node_id: int,
                 latency: LatencyRecorder | None = None,
                 journal=None, clock_ns=time.monotonic_ns) -> None:
        self.cfg = cfg
        self.node_id = node_id
        self.latency = latency if latency is not None else LatencyRecorder()
        self._ring: deque | None = deque(maxlen=cfg.trace_ring) \
            if cfg.trace_ring > 0 else None
        # the one clock of every span: CLOCK_MONOTONIC in ns, shared by
        # every process of the host (tests inject their own)
        self._now = clock_ns
        # span id -> _Open for every span open in this process, and the
        # per-name totals [count, seconds, self seconds]; both live and
        # die with the ring (never touched when tracing is off)
        self._open: dict[str, _Open] = {}
        self._totals: dict[str, list] = {}
        self._overflow_warned = False
        # tail retention (Dapper's tail-sampling lesson): spans of
        # slow/errored traces are COPIED here and survive main-ring
        # eviction — bounded by span count, FIFO. None = feature off.
        self._tail: deque | None = deque() \
            if cfg.tail_keep > 0 and self._ring is not None else None
        self._tail_ids: set[str] = set()
        self._interesting: dict[str, None] = {}   # insertion-ordered
        # flight recorder (obs/journal.py) — None when journaling is off
        # or the owner (tests, standalone tools) never attached one
        self.journal = journal
        # set by the node runtime when sentinels run; stats() surfaces it
        self.sentinel = None
        self._lock = threading.Lock()
        self.rpc_client = RpcStats()
        self.rpc_server = RpcStats()

    @property
    def enabled(self) -> bool:
        return self._ring is not None

    # ---- lifecycle events (flight recorder) --------------------------- #

    def event(self, etype: str, **fields) -> None:
        """Record one lifecycle event in the journal, stamped with the
        active trace id. No-op without a journal; never blocks (the
        journal writer is a bounded-queue thread)."""
        j = self.journal
        if j is None:
            return
        cur = _ctx.get() if self._ring is not None else None
        j.emit(etype, fields, trace=cur[0] if cur is not None else None)

    # ---- propagation carriers ---------------------------------------- #

    def wire_trace(self) -> dict | None:
        """The ``trace`` field to attach to an outbound wire header —
        {"t","s","f"} naming the CURRENT span as the peer's parent —
        or None (tracing off / caller untraced): the field is simply
        omitted, which is also what a pre-r09 node sends."""
        cur = _ctx.get() if self._ring is not None else None
        if cur is None:
            return None
        return {"t": cur[0], "s": cur[1], "f": self.node_id}

    # ---- span recording ---------------------------------------------- #

    def _traced(self, name, tid, sid, parent, peer, latency_name):
        tok = _ctx.set((tid, sid))
        sp = Span()
        t_wall = time.time()
        m0 = self._now()
        kid = [m0, None]
        with self._lock:
            mine = self._open[sid] = _Open(m0)
            up = self._open.get(parent)
            if up is not None:      # the parent is open in this process
                up.add(kid)
        err = None
        try:
            yield sp
        except BaseException as e:
            err = type(e).__name__
            raise
        finally:
            _ctx.reset(tok)
            m1 = self._now()
            dur = (m1 - m0) / 1e9
            if latency_name is not None:
                # traced observations carry their trace id as the
                # bucket's OpenMetrics exemplar (/metrics?format=prom)
                self.latency.record(latency_name, dur, exemplar=tid)
            rec = (tid, sid, parent, name, self.node_id,
                   t_wall, dur, peer, sp.bytes, err or sp.err, m0)
            ring = self._ring
            with self._lock:
                kid[1] = m1
                del self._open[sid]
                mine.fold(m1, final=True)
                key = capped_key(self._totals, name, self._MAX_SPAN_NAMES,
                                 self, "span totals", "_overflow")
                row = self._totals.get(key)
                if row is None:
                    row = self._totals[key] = [0, 0.0, 0.0]
                row[0] += 1
                row[1] += dur
                row[2] += (m1 - m0 - mine.covered) / 1e9
                ring.append(rec)
                if self._tail is not None:
                    self._tail_note(rec)

    # ---- tail retention (lock held by caller) ------------------------- #

    def _tail_note(self, rec: tuple) -> None:
        """Pin spans of outlier traces. A span that is slow (>=
        slow_span_s) or errored marks its whole trace interesting: the
        trace's spans already in the main ring are copied into the tail
        store, and every later span of the trace lands there too — so
        the one request worth diagnosing survives the churn of the
        thousand ordinary ones that follow it (Dapper's tail lesson)."""
        tid = rec[0]
        if tid not in self._interesting:
            if not (rec[9] or rec[6] >= self.cfg.slow_span_s):
                return
            while len(self._interesting) >= self._MAX_INTERESTING:
                del self._interesting[next(iter(self._interesting))]
            self._interesting[tid] = None
            # sweep earlier spans of this trace out of the mortal ring
            for r in self._ring:
                if r[0] == tid and r[1] != rec[1]:
                    self._tail_pin(r)
        self._tail_pin(rec)

    def _tail_pin(self, rec: tuple) -> None:
        if rec[1] in self._tail_ids:
            return
        while len(self._tail) >= self.cfg.tail_keep:
            old = self._tail.popleft()
            self._tail_ids.discard(old[1])
        self._tail.append(rec)
        self._tail_ids.add(rec[1])

    @contextlib.contextmanager
    def span(self, name: str, peer=None, latency: bool = False):
        """Child span of the current context. Without an active context
        (or with tracing off) this is a no-op — except that
        ``latency=True`` still records the duration into the shared
        LatencyRecorder under ``name`` (the pre-r09 ``/metrics`` latency
        surface keeps its keys regardless of tracing state)."""
        cur = _ctx.get() if self._ring is not None else None
        if cur is None:
            if not latency:
                yield _NULL_SPAN
                return
            t0 = time.perf_counter()
            try:
                yield _NULL_SPAN
            finally:
                self.latency.record(name, time.perf_counter() - t0)
            return
        yield from self._traced(name, cur[0], new_span_id(), cur[1],
                                peer, name if latency else None)

    @contextlib.contextmanager
    def request_span(self, name: str,
                     incoming: tuple[str, str] | None = None, peer=None,
                     latency: bool = False):
        """Entry-point span (HTTP layer): adopts (trace_id, parent) from
        an inbound ``X-Dfs-Trace`` carrier, or roots a fresh trace —
        always-on tracing means every request is traceable, not only the
        ones a client asked about. ``latency=True`` records the span's
        duration under ``name`` — traced requests tag the bucket they
        land in with their trace id (the OpenMetrics exemplar the
        ``/metrics?format=prom`` exposition serves), and the name stays
        a bounded-cardinality histogram key even with tracing off (the
        HTTP layer only passes allowlisted route names)."""
        if self._ring is None:
            if not latency:
                yield _NULL_SPAN
                return
            t0 = time.perf_counter()
            try:
                yield _NULL_SPAN
            finally:
                self.latency.record(name, time.perf_counter() - t0)
            return
        if incoming is not None:
            tid, parent = incoming
        else:
            tid, parent = new_trace_id(), None
        yield from self._traced(name, tid, new_span_id(), parent, peer,
                                name if latency else None)

    @contextlib.contextmanager
    def server_span(self, name: str,
                    incoming: tuple[str, str, int | None] | None,
                    peer=None):
        """Storage-plane server span: ``incoming`` is
        :func:`parse_wire_trace` output. A frame without a trace field
        (pre-r09 peer, or an untraced caller) roots a fresh trace."""
        if self._ring is None:
            yield _NULL_SPAN
            return
        if incoming is not None:
            tid, parent = incoming[0], incoming[1]
            if peer is None:
                peer = incoming[2]
        else:
            tid, parent = new_trace_id(), None
        yield from self._traced(name, tid, new_span_id(), parent, peer,
                                None)

    # ---- query ------------------------------------------------------- #

    def _select(self, want) -> list[dict]:
        """Finished spans still resident that ``want(record)`` — main
        ring plus the tail-retention store (outlier traces outlive ring
        churn there), deduped by span id, ordered by wall start."""
        if self._ring is None:
            return []
        with self._lock:
            rows = [r for r in self._ring if want(r)]
            if self._tail is not None:
                have = {r[1] for r in rows}
                rows.extend(r for r in self._tail
                            if want(r) and r[1] not in have)
        rows.sort(key=lambda r: r[5])
        return [_span_dict(r) for r in rows]

    def spans_for(self, trace_id: str) -> list[dict]:
        """Finished spans of one trace still resident."""
        return self._select(lambda r: r[0] == trace_id)

    def spans_between(self, since_ns: int, until_ns: int) -> list[dict]:
        """Finished spans still resident that were open at some moment
        of ``[since_ns, until_ns]`` on the spans' clock (``m0``): what
        this process was doing while, say, a device sat idle."""
        return self._select(
            lambda r: r[10] <= until_ns
            and r[10] + int(r[6] * 1e9) >= since_ns)

    def span_totals(self) -> dict[str, dict]:
        """name -> {count, seconds, selfSeconds} over every span this
        process has finished (``/metrics`` ``obs.spans``; a reader takes
        deltas). Spans of one name may overlap (slices of one batch in
        flight together), so ``seconds`` is span-seconds, not wall."""
        with self._lock:
            return {name: {"count": r[0], "seconds": round(r[1], 6),
                           "selfSeconds": round(r[2], 6)}
                    for name, r in sorted(self._totals.items())}

    def stats(self) -> dict:
        """JSON ``/metrics`` ``obs`` section. The ``traceRing`` /
        ``slowSpanS`` / ``tailKeep`` keys mirror ObsConfig fields;
        ``journal`` / ``sentinel`` carry the flight-recorder and sampler
        sub-sections (dfslint DFS005 checks the field⇄key mapping)."""
        with self._lock:
            tail_spans = len(self._tail) if self._tail is not None else 0
        return {"traceRing": self.cfg.trace_ring,
                "slowSpanS": self.cfg.slow_span_s,
                "tailKeep": self.cfg.tail_keep,
                "ringSpans": len(self._ring)
                if self._ring is not None else 0,
                "tailSpans": tail_spans,
                # the per-name totals exist only with the ring
                **({"spans": self.span_totals()}
                   if self._ring is not None else {}),
                "journal": self.journal.stats()
                if self.journal is not None else {"enabled": False},
                "sentinel": self.sentinel.stats()
                if self.sentinel is not None else {"enabled": False},
                "rpcClient": self.rpc_client.snapshot(),
                "rpcServer": self.rpc_server.snapshot()}


__all__ = ["Observability", "RpcStats", "Span", "current", "is_id",
           "new_span_id", "new_trace_id", "parse_http_trace",
           "parse_wire_trace"]
