"""The asyncio node runtime (L4/L2) — the reference's StorageNode re-designed.

One process per node, two listeners:
- external HTTP API (dfs_tpu.api.http) — /status /files /upload /download,
  capability parity with StorageNode.java:71-89;
- internal binary storage plane (this module) — store_chunks / announce /
  get_chunk / get_manifest / health / has_chunks (+ the r16 dedup/index
  ops get_filter / filter_delta, docs/index.md), replacing the
  reference's /internal/* HTTP+Base64 endpoints (StorageNode.java:92-105).

Deliberate upgrades over the reference, per SURVEY.md §2.5 / §5.3:
- write-quorum instead of write-all: the reference aborts the entire upload if
  any single peer is unreachable (StorageNode.java:218-221); here a chunk
  succeeds once ``write_quorum`` replicas hold it, and under-replicated chunks
  are queued for background repair.
- transfer dedup: peers are asked which digests they already have
  (``has_chunks``) and only missing bytes travel — re-uploading a file, or
  uploading a near-duplicate, moves almost nothing (north-star dedup index).
- hash-echo verification is kept: receivers recompute sha256 of everything
  they store and the sender verifies the echo (StorageNode.java:248-257).
- concurrency: replication to all peers and chunk fetches during download run
  concurrently (asyncio.gather) instead of the reference's sequential per-peer
  loops (StorageNode.java:195-224, 422-449).
"""

from __future__ import annotations

import asyncio
import contextlib
import errno
import functools
import math
import threading
import time
import types
from collections import deque
from typing import Collection, Mapping, Sequence

from dfs_tpu.comm.rpc import (DeadlineExpired, InternalClient, RpcError,
                              RpcRemoteError, RpcUnreachable)
from dfs_tpu.comm.wire import (FrameServerProtocol, WireError, encode_frame,
                               pack_chunks, unpack_chunks)
from dfs_tpu.config import NodeConfig
from dfs_tpu.fragmenter.base import get_fragmenter
from dfs_tpu.meta.manifest import (ChunkRef, EcInfo, Manifest, StripeRef,
                                   ec_stripe_groups, stripe_shard_len)
from dfs_tpu.node.health import HealthMonitor
from dfs_tpu.obs import Observability, Span, parse_wire_trace
from dfs_tpu.ring import RingMap
from dfs_tpu.ring.manager import RingManager
from dfs_tpu.serve import BatchPrefetcher, ServingTier
from dfs_tpu.store.aio import AsyncChunkStore
from dfs_tpu.store.cas import NodeStore
from dfs_tpu.utils import deadline
from dfs_tpu.utils.hashing import (is_hex_digest, sha256_hex,
                                   sha256_many_hex, sha256_new)
from dfs_tpu.utils.aio import create_logged_task, gather_abort_siblings
from dfs_tpu.utils.logging import Counters, Stopwatches, get_logger
from dfs_tpu.utils.trace import LatencyRecorder


def _spanned(name: str):
    """Run an async method of the node inside ``self.obs.span(name)``:
    one span per call under the caller's trace (a no-op when the caller
    is untraced), whichever upload path makes the call."""
    def deco(fn):
        @functools.wraps(fn)
        async def wrapper(self, *args, **kwargs):
            with self.obs.span(name):
                return await fn(self, *args, **kwargs)
        return wrapper
    return deco


class UploadError(RuntimeError):
    """Maps to HTTP 500 'Replication failed' (StorageNode.java:176) by
    default; raisers may pin a different code via ``status`` (resume
    validation -> 400, resume-missing-chunks -> 409) so the HTTP layer
    never classifies by matching message text."""

    def __init__(self, msg: str, status: int = 500) -> None:
        super().__init__(msg)
        self.status = status


class NotFoundError(KeyError):
    """Maps to HTTP 404 (StorageNode.java:408-411)."""


class DownloadError(RuntimeError):
    """Maps to HTTP 500 'Could not retrieve fragment…' / 'File corrupted'
    (StorageNode.java:443-446, 453-458)."""


class RangeNotSatisfiable(DownloadError):
    """A byte range past EOF — maps to HTTP 416 with the file size."""

    def __init__(self, size: int) -> None:
        super().__init__(f"range not satisfiable (size {size})")
        self.size = size


class DeadlineExceeded(DownloadError):
    """The caller's end-to-end deadline expired during a read — maps to
    HTTP 503 + Retry-After (the same answer the admission gate gives an
    expired arrival), never a 500: the cluster is healthy, the budget
    is gone, and a 500 would invite the immediate no-backoff retry the
    Retry-After discipline exists to prevent. Also distinct so the
    fetch walks can STOP at expiry instead of touring every remaining
    candidate and counting each refusal as a remote miss."""


def ec_placement_map(manifest: Manifest, ring) -> Mapping[str, tuple[int, ...]]:
    """digest -> candidate holder nodes for every shard (data + parity)
    of an erasure-coded manifest. Derived from the manifest plus the
    membership ring alone, so any node can locate any shard. ``ring``
    is a :class:`~dfs_tpu.ring.RingMap` — or a plain node-id list,
    which compiles to the static epoch-0 map (the pre-r14 call shape;
    tests and benches still use it). A digest appearing in several
    stripes (dedup within the file) gets the union of its slots'
    holders. Memoized per (manifest layout, ring identity): rebuilding
    measured ~30 ms per gather on a 32 MiB manifest, and a degraded
    read runs two gathers. The key is a cheap layout fingerprint, not
    the manifest object — hashing a frozen dataclass walks every
    ChunkRef, which would cost as much as the rebuild; stripe endpoints
    pin the ec_k re-upload case where the same file_id maps to a
    different stripe layout."""
    if not isinstance(ring, RingMap):
        ring = RingMap.static(list(ring))
    ec = manifest.ec
    assert ec is not None
    key = (manifest.file_id, ec.k, len(manifest.chunks), len(ec.stripes),
           ec.stripes[0].p if ec.stripes else "",
           ec.stripes[-1].q if ec.stripes else "", ring.key)
    hit = _EC_PLACEMENT_CACHE.get(key)
    if hit is None:
        hit = _ec_placement_build(manifest, ring)
        if len(_EC_PLACEMENT_CACHE) >= 64:
            _EC_PLACEMENT_CACHE.pop(next(iter(_EC_PLACEMENT_CACHE)))
        _EC_PLACEMENT_CACHE[key] = hit
    return hit


_EC_PLACEMENT_CACHE: dict = {}


def _ec_placement_build(manifest: Manifest, ring: RingMap
                        ) -> Mapping[str, tuple[int, ...]]:
    ec = manifest.ec
    assert ec is not None
    pl: dict[str, list[int]] = {}
    groups = ec_stripe_groups(manifest.chunks, ec.k)
    for s, (st, grp) in enumerate(zip(ec.stripes, groups)):
        # one ring walk per stripe: holders for all k data shards + P/Q
        holders = ring.ec_stripe_nodes(manifest.file_id, s, len(grp) + 2)
        for j, c in enumerate(grp):
            pl.setdefault(c.digest, []).append(holders[j])
        pl.setdefault(st.p, []).append(holders[len(grp)])
        pl.setdefault(st.q, []).append(holders[len(grp) + 1])
    # read-only view over tuple values: the map is cached and shared by
    # every reader of this (manifest, membership) pair — a caller
    # mutating it would corrupt placement for all subsequent reads, so
    # violations fail loudly instead of silently.
    return types.MappingProxyType(
        {d: tuple(dict.fromkeys(v)) for d, v in pl.items()})


def ec_shard_items(manifest: Manifest) -> list[tuple[str, int]]:
    """(digest, byte length) of every shard an EC manifest references —
    data chunks at their true length, parity at the stripe's padded
    shard length."""
    ec = manifest.ec
    assert ec is not None
    out = [(c.digest, c.length) for c in manifest.chunks]
    for st in ec.stripes:
        out.append((st.p, st.shard_len))
        out.append((st.q, st.shard_len))
    return out


# storage-plane ops the internal admission gate bounds: the ones that
# move/hash chunk payloads. Everything else (health, has_chunks,
# tombstones, list/get_manifest, announce, delete) is cheap metadata
# whose timeliness other subsystems depend on — see _handle_internal.
# The same set decides which UNTRACED inbound ops still root a fresh
# trace (heavy work stays diagnosable; probe noise stays out of the
# span ring).
_HEAVY_OPS = frozenset({"store_chunks", "get_chunk", "get_chunks"})

# annotation sink for inbound ops that record no span (untraced cheap
# ops) — writes are discarded, same contract as obs._NULL_SPAN
_NULL_OBS_SPAN = Span()


class ByteBudget:
    """Counting BYTE semaphore for cross-thread ingest backpressure.

    The streaming-upload credit gate originally bounded chunk COUNT
    (256), which bounds memory only as well as the chunk-size config
    does: a stream of max-size chunks under a large ``max_chunk`` could
    buffer ~1 GiB of produced-but-unconsumed payloads, silently breaking
    the bounded-memory ingest contract. This gate charges actual payload
    bytes instead.

    A single chunk larger than the whole budget is admitted when nothing
    else is outstanding (otherwise it could never proceed — the classic
    byte-semaphore deadlock); the budget is then simply oversubscribed
    by that one chunk until it is consumed.
    """

    def __init__(self, budget: int) -> None:
        self.budget = max(1, int(budget))
        self._out = 0
        self._cv = threading.Condition()

    def acquire(self, n: int, timeout: float | None = None) -> bool:
        """Block until ``n`` bytes fit under the budget (or the gate is
        empty); False on timeout. Called from the fragmenter thread."""
        with self._cv:
            ok = self._cv.wait_for(
                lambda: self._out + n <= self.budget or self._out == 0,
                timeout)
            if ok:
                self._out += n
            return ok

    def release(self, n: int) -> None:
        with self._cv:
            self._out = max(0, self._out - n)
            self._cv.notify_all()

    @property
    def outstanding(self) -> int:
        with self._cv:
            return self._out


class _TrustLedger:
    """Filter-credited replica copies awaiting pre-ack verification.

    When placement trusts a peer-filter POSITIVE (skipping both the
    has_chunks probe and the transfer — the re-upload fast path,
    docs/index.md), the copy it credited is a bloom ``maybe``, not a
    fact. Every trusted (peer, digest, length) lands here, and
    ``StorageNodeServer._verify_trusted`` confirms the whole ledger
    with ONE has_chunks round per peer BEFORE the manifest write acks
    the upload — so a false positive can delay an ack (it gets healed
    by a real transfer first), never weaken one. Event-loop-only, like
    the placement bookkeeping it extends."""

    def __init__(self) -> None:
        self.by_peer: dict[int, dict[str, int]] = {}

    def credit(self, peer: int, digest: str, length: int) -> None:
        self.by_peer.setdefault(peer, {})[digest] = length

    def __bool__(self) -> bool:
        return bool(self.by_peer)


def _config_fingerprint(cfg: NodeConfig) -> str:
    """sha256 over the SHARED config surface — everything that should be
    identical across a healthy cluster. Node-local identity fields
    (node_id, data_root, sidecar_port) are excluded so the doctor's
    config_drift rule compares policy, not identity."""
    import dataclasses as _dc
    import json as _json

    d = _dc.asdict(cfg)
    for local in ("node_id", "data_root", "sidecar_port"):
        d.pop(local, None)
    return sha256_hex(_json.dumps(d, sort_keys=True,
                                  default=str).encode())


class StorageNodeServer:
    def __init__(self, cfg: NodeConfig) -> None:
        self.cfg = cfg
        # fsync-before-ack durability (DurabilityConfig, docs/chaos.md):
        # chunk puts and manifest saves barrier file + directory before
        # returning, on the CAS worker threads / to_thread — the loop
        # never blocks on an fsync
        self.store = NodeStore(cfg.data_root, cfg.node_id,
                               fsync=cfg.durability.fsync)
        self.counters = Counters()
        self.latency = LatencyRecorder()
        # flight recorder (obs/journal.py): crash-safe on-disk lifecycle
        # journal under the node's data root — built before the
        # Observability hub so every subsystem's obs.event() lands in it
        journal = None
        if cfg.obs.journal_bytes > 0:
            from dfs_tpu.obs.journal import Journal

            journal = Journal(self.store.root / "journal", cfg.node_id,
                              total_bytes=cfg.obs.journal_bytes,
                              segment_bytes=cfg.obs.journal_segment_bytes)
        # observability: trace-context propagation + span ring + RPC
        # metric tables (dfs_tpu.obs). Built FIRST — the client, CAS
        # tier, and serving tier all take it as their tracing hook.
        self.obs = Observability(cfg.obs, cfg.node_id,
                                 latency=self.latency, journal=journal)
        # config fingerprint over the SHARED fields (node-local identity
        # excluded) — the doctor's config_drift rule compares these
        # across nodes
        self._config_hash = _config_fingerprint(cfg)
        self._started_at = time.time()
        # fault injection (dfs_tpu.chaos, docs/chaos.md): None unless
        # ChaosConfig.enabled — every seam below is one None check, so
        # a chaos-less node runs byte-identical code paths. Built right
        # after obs so injected faults journal trace-stamped.
        self.chaos = None
        if cfg.chaos.enabled:
            from dfs_tpu.chaos import ChaosInjector

            self.chaos = ChaosInjector(cfg.chaos, cfg.node_id,
                                       obs=self.obs)
            # disk faults ride the ChunkStore hook: it runs on the CAS
            # worker threads, so ENOSPC/EIO/slow-disk injection covers
            # the AsyncChunkStore tier and every sync caller alike
            self.store.chunks.fault = self.chaos.store_hook()
        # dedup/index plane (dfs_tpu.index, docs/index.md): None unless
        # IndexConfig.enabled — a zero-knob node keeps the stat-per-
        # digest existence paths byte-identical. Built after obs (the
        # LSI journals index_rebuild/index_compact through it);
        # OPENED in start(), before the servers listen. (The
        # mid-compaction kill -9 coverage drives the DigestIndex.hook
        # seam directly — tests/test_index.py, bench_dedup_index.py —
        # rather than the CRASH_POINTS registry, whose every entry
        # must fire on a default-config upload.)
        self.index = None
        self._filter_sync_task: asyncio.Task | None = None
        if cfg.index.enabled:
            from dfs_tpu.index import IndexPlane

            self.index = IndexPlane(cfg.index, self.store.root)
            self.index.lsi.on_event = self.obs.event
            # the ChunkStore seam: every put/delete feeds the LSI from
            # the CAS worker threads; has() answers from it first
            self.store.chunks.index = self.index
        # elastic membership (dfs_tpu.ring, docs/membership.md): the
        # epoch-versioned placement map + migration window + rebalance
        # credits. Built after obs (epoch changes journal) and before
        # the client (placement-bearing RPCs carry the epoch). The
        # default config compiles a STATIC epoch-0 ring byte-identical
        # to the pre-r14 cyclic placement.
        self.ring = RingManager(cfg, self.store.root, obs=self.obs)
        self.ring.on_change = self._on_ring_change
        self._repair_lock = asyncio.Lock()
        # async CAS tier: every event-loop chunk put/get routes through a
        # bounded thread pool (store/aio.py) — the loop never blocks on
        # chunk file I/O and disk concurrency is explicit
        self.cas = AsyncChunkStore(self.store.chunks,
                                   workers=cfg.ingest.cas_io_threads,
                                   obs=self.obs)
        # streaming-ingest flush size: config-driven, kept as an instance
        # attribute so tests/benches can still scale it per node
        self._STREAM_FLUSH_BYTES = cfg.ingest.flush_bytes
        if cfg.sidecar_port:
            # delegate chunk+hash to a sidecar process (north-star shape:
            # device init/compiles never block the serving loop)
            from dfs_tpu.sidecar.service import SidecarFragmenter

            self.fragmenter = SidecarFragmenter(cfg.sidecar_port)
        else:
            self.fragmenter = get_fragmenter(
                cfg.fragmenter, cdc_params=cfg.cdc,
                fixed_parts=cfg.fixed_parts, frag=cfg.frag)
        self.client = InternalClient(cfg.connect_timeout_s,
                                     cfg.request_timeout_s, cfg.retries,
                                     coalesce_fetches=cfg.serve.cache_bytes
                                     > 0, obs=self.obs,
                                     chaos=self.chaos, ring=self.ring)
        self.health = HealthMonitor(cfg.cluster, cfg.node_id, self.client,
                                    probe_interval_s=cfg.health_probe_s,
                                    obs=self.obs)
        # write-path stall attribution (time blocked on credits vs
        # replication vs disk) + pipeline-depth peaks — /metrics "ingest"
        self.ingest_stalls = Stopwatches()
        # runtime stall sentinel (obs/sentinel.py): loop-lag, CAS-pool
        # backlog and credit-stall sampling → journal incidents; None
        # when sampled off. Registered on obs so /metrics "obs" and the
        # doctor snapshot carry its gauges.
        self.sentinel = None
        if cfg.obs.sentinel_interval_s > 0:
            from dfs_tpu.obs.sentinel import Sentinel

            self.sentinel = Sentinel(self.obs, cas=self.cas,
                                     stalls=self.ingest_stalls,
                                     interval_s=cfg.obs.sentinel_interval_s,
                                     lag_s=cfg.obs.sentinel_lag_s)
            self.obs.sentinel = self.sentinel
        # read-path serving tier: hot-chunk cache + single-flight +
        # admission gates + readahead. Default config = every component
        # off, and the node runs the historical code paths exactly.
        self.serve = ServingTier(cfg.serve, obs=self.obs)
        # hot/cold tiering plane (dfs_tpu.tier, docs/tiering.md): None
        # unless TierConfig.enabled — the default node never touches a
        # ledger, never scans, and serves byte-identical paths. Built
        # after serve (the read path feeds the ledger) and after ring
        # (demotion reuses ring-walk EC stripe placement).
        self.tier = None
        self._tier_task: asyncio.Task | None = None
        self._tier_promoting: set[str] = set()  # file ids mid-promotion
        # cold files whose surplus replicas are CONFIRMED reclaimed,
        # keyed to the ring epoch the confirmation was computed under
        # (an epoch bump moves ownership — re-judge)
        self._tier_surplus_done: dict[str, int] = {}
        if cfg.tier.enabled:
            from dfs_tpu.tier import TierPlane

            self.tier = TierPlane(cfg.tier, self.store.root / "tier",
                                  obs=self.obs)
        # similarity compression plane (dfs_tpu.sim, docs/similarity.md):
        # None unless SimConfig.enabled — the default node's put/get
        # paths stay byte-identical (the ChunkStore sim seam is one None
        # check). Built after chaos so the sim.* crash points fire on
        # the real delta write / GC / re-materialize paths.
        self.sim = None
        if cfg.sim.enabled:
            from dfs_tpu.sim import SimPlane

            self.sim = SimPlane(cfg.sim, self.store.root / "sim")
            if self.chaos is not None:
                self.sim.crash = self.chaos.maybe_crash
            self.store.chunks.sim = self.sim
        # census/capacity plane (docs/observability.md): the embedded
        # metrics-history ring a background sampler feeds — trend data
        # for GET /metrics/history and the doctor's capacity_trend
        # rule. None = sampling off (census queries still answer).
        self.history = None
        if cfg.census.history_interval_s > 0:
            from dfs_tpu.obs.history import MetricsHistory

            self.history = MetricsHistory(
                cfg.census.history_interval_s, cfg.census.history_slots,
                cfg.census.history_coarse_every,
                cfg.census.history_coarse_slots)
        self._history_task: asyncio.Task | None = None
        self._ring_catchup_task: asyncio.Task | None = None
        # last coordinator census summary (doctor snapshot material)
        self._last_census: dict | None = None
        self._disk_pressure = False
        self.log = get_logger("node", cfg.node_id)
        self.under_replicated: set[str] = set()  # digests needing repair
        self._internal_server: asyncio.AbstractServer | None = None
        self._http_server: asyncio.AbstractServer | None = None
        self._inbound: set[FrameServerProtocol] = set()  # live peer conns

    # ------------------------------------------------------------------ #
    # lifecycle
    # ------------------------------------------------------------------ #

    async def start(self) -> None:
        from dfs_tpu.api.http import make_http_handler

        addr = self.cfg.self_addr
        # boot-time crash recovery (docs/chaos.md): BEFORE the servers
        # listen — so nothing can be in flight — reclaim every
        # crash-leaked temp file (all from the previous life) and run
        # the aged orphan GC, reconciling a crash between CAS put and
        # manifest write with the same path aborted streams already use
        if self.index is not None:
            # open (or rebuild from the CAS walk — the chunk files are
            # ground truth) BEFORE the boot sweep and the servers: the
            # sweep's orphan GC feeds deletes through the ChunkStore
            # seam, and deletes noted into an UNOPENED index would be
            # overwritten by the WAL replay — the swept chunks coming
            # back as phantom "present" answers. Off the loop: a
            # rebuild reads the whole catalog's names.
            info = await asyncio.to_thread(self.index.open_or_rebuild,
                                           self.store.chunks.digests)
            if info["rebuilt"]:
                self.log.warning("digest index rebuilt from CAS walk "
                                 "(%d entries): %s", info["entries"],
                                 info["reason"])
        swept = await asyncio.to_thread(self.store.boot_sweep)
        if swept["tmps"] or swept["orphans"]:
            self.obs.event("boot_sweep", **swept)
            self.log.info("boot sweep: %d temp(s), %d aged orphan(s)",
                          swept["tmps"], swept["orphans"])
        # the internal plane is a BufferedProtocol server (comm/wire.py):
        # each inbound frame lands in ONE recv_into buffer and is served
        # by _serve_internal_frame — no StreamReader byte shuffling on
        # the hot receive path (docs/wire.md)
        loop = asyncio.get_running_loop()
        self._internal_server = await loop.create_server(
            lambda: FrameServerProtocol(self._serve_internal_frame,
                                        on_connect=self._inbound.add,
                                        on_close=self._inbound.discard),
            addr.host, addr.internal_port)
        self._http_server = await asyncio.start_server(
            make_http_handler(self), addr.host, addr.port)
        if self.cfg.health_probe_s > 0:
            self.health.start()
        if self.sentinel is not None:
            self.sentinel.start()
        if self.history is not None:
            self._history_task = create_logged_task(
                self._history_loop(), self.log, "census-history")
        if self.tier is not None and self.cfg.tier.scan_interval_s > 0:
            # demotion worker: started HERE (not a CLI periodic) so
            # in-process test nodes run it too; scan_interval_s == 0
            # leaves scans manual (POST /tier) for determinism
            self._tier_task = create_logged_task(
                self._tier_loop(), self.log, "tier-scan")
        if self._peers():
            # membership catch-up: a (re)started node may have slept
            # through epoch bumps (or lost its ring.json) — one cheap
            # get_ring round adopts the highest epoch any peer holds,
            # and a resumed migration picks up where the crash left it.
            # Best-effort: the epoch-on-RPC gossip is the backstop.
            self._ring_catchup_task = create_logged_task(
                self._ring_catchup(), self.log, "ring-catchup")
        if self.index is not None \
                and self.index.local_filter is not None \
                and self.cfg.index.filter_sync_s > 0 and self._peers():
            # peer-existence filter gossip (docs/index.md): replicate
            # every peer's filter on the configured cadence — deltas
            # when the generation holds, full resync when it moved
            self._filter_sync_task = create_logged_task(
                self._filter_sync_loop(), self.log, "filter-sync")
        # flight-recorder boot record: the config this life ran with is
        # the first question of every post-mortem
        self.obs.event("boot", configHash=self._config_hash,
                       http=addr.port, internal=addr.internal_port,
                       fragmenter=self.fragmenter.name)
        self.log.info("node %d up: http=%d internal=%d",
                      self.cfg.node_id, addr.port, addr.internal_port)

    async def stop(self) -> None:
        if self._history_task is not None:
            self._history_task.cancel()
            self._history_task = None
        if self._ring_catchup_task is not None:
            self._ring_catchup_task.cancel()
            self._ring_catchup_task = None
        if self._filter_sync_task is not None:
            self._filter_sync_task.cancel()
            self._filter_sync_task = None
        if self._tier_task is not None:
            self._tier_task.cancel()
            self._tier_task = None
        if self.tier is not None:
            # parting ledger snapshot (atomic write, off the loop) —
            # best-effort: losing it only under-counts heat
            with contextlib.suppress(OSError):
                await asyncio.to_thread(self.tier.snapshot_ledger)
        if self.sentinel is not None:
            self.sentinel.stop()
        self.health.stop()
        self.client.close()   # drop pooled peer connections
        self.cas.close()      # async CAS tier workers (non-blocking)
        if self.sim is not None:
            # band-log close + dir fsync (losing buffered adds is the
            # safe direction — missed dedup, never wrong bytes)
            await asyncio.to_thread(self.sim.close)
        if self.index is not None:
            # flush the WAL buffer + close run fds; off the loop (file
            # I/O). In-flight CAS jobs racing the close lose only
            # buffered PUT records — the safe divergence direction.
            await asyncio.to_thread(self.index.close)
        # Peers keep POOLED connections into this node open indefinitely;
        # Server.wait_closed() (3.12+) waits for every live handler, so
        # idle inbound connections must be torn down explicitly or stop()
        # deadlocks on a peer that simply hasn't spoken lately.
        for srv in (self._internal_server, self._http_server):
            if srv is None:
                continue
            srv.close()
            closed = asyncio.ensure_future(srv.wait_closed())
            while not closed.done():
                # sweep AFTER close(), and again until the wait returns:
                # a connection the listener accepted just before close()
                # attaches to the server (and lands in _inbound) a loop
                # turn later — invisible to one sweep taken up front,
                # and then wait_closed() never returns
                for w in list(self._inbound):
                    w.close()
                await asyncio.wait({closed}, timeout=0.2)
            await closed
        if self.obs.journal is not None:
            # last: every subsystem above may still emit during teardown;
            # close() drains the bounded queue on the writer thread and
            # can block seconds on a sick disk (put timeout + join), so
            # it must not run on the loop — other nodes may share it
            await asyncio.to_thread(self.obs.journal.close)

    # ------------------------------------------------------------------ #
    # internal storage plane (server side)
    # ------------------------------------------------------------------ #

    # ------------------------------------------------------------------ #
    # membership plane (dfs_tpu.ring, docs/membership.md)
    # ------------------------------------------------------------------ #

    def _on_ring_change(self) -> None:
        """RingManager install hook: kick an immediate rebalance walk
        (repair_once IS the rebalancer — its manifest walk + bounded
        pushes now run against the new epoch's owner map) instead of
        waiting out the periodic repair interval."""
        try:
            asyncio.get_running_loop()
        # absence-as-result: "no running loop" just means this install
        # happened at boot, before start() — the first periodic repair
        # cycle runs the same walk
        except RuntimeError:  # dfslint: ignore[DFS007]
            return
        create_logged_task(self._rebalance_kick(), self.log,
                           "rebalance-kick")

    async def _rebalance_kick(self) -> None:
        # the kick may have been spawned from inside a deadlined RPC's
        # dispatch (epoch adoption off a placement-bearing call):
        # create_task copied that context, and a rebalance walk must
        # not inherit a request's dying budget
        deadline.clear()
        try:
            await self.repair_once()
        except Exception as e:  # noqa: BLE001 — next periodic repair
            # retries; the kick must not die loudly mid-migration
            self.log.warning("rebalance kick failed: %s", e)

    async def _ring_catchup(self) -> None:
        best: dict | None = None
        for peer in self._peers():
            try:
                resp, _ = await self.client.call(
                    peer, {"op": "get_ring"}, retries=1)
            # not silent: catch-up is best-effort by contract — the
            # epoch-on-RPC gossip converges a node this round misses
            except RpcError:  # dfslint: ignore[DFS007]
                continue
            ring = resp.get("ring")
            if isinstance(ring, dict) \
                    and isinstance(ring.get("epoch"), int) \
                    and ring["epoch"] > self.ring.epoch \
                    and (best is None or ring["epoch"] > best["epoch"]):
                best = ring
        if best is not None:
            try:
                self.ring.adopt(best, source="catchup")
            except ValueError as e:
                self.log.warning("ring catch-up rejected peer map: %s", e)

    async def ring_admin(self, action: str, node_id: int | None = None,
                         weight: float | None = None) -> dict:
        """Admin membership change (POST /ring): build the epoch+1 map,
        install locally, push it to every cluster peer (best-effort —
        a peer that misses the push converges via the epoch-on-RPC
        gossip), and return the new map + per-peer push results. The
        rebalancer kicks off via the install hook on every node."""
        cur = self.ring.current
        weights = {m.node_id: m.weight for m in cur.members}
        if action == "add":
            if node_id is None:
                raise ValueError("add needs nodeId")
            if node_id not in {p.node_id for p in self.cfg.cluster.peers}:
                raise ValueError(
                    f"node {node_id} is not in the cluster address "
                    "book (boot every process with it in --nodes/"
                    "--cluster-config first)")
            if weights.get(node_id, 0) > 0:
                raise ValueError(f"node {node_id} is already a ring "
                                 "member")
            if weight is None:
                # capacity-derived default (r20): weight the joiner by
                # its disk headroom relative to the median member, so
                # `ring add` without an explicit --weight stops
                # assigning a small disk the same share as a big one.
                # An explicit weight always wins; any probe failure
                # falls back to the old 1.0.
                weight = await self._derive_add_weight(
                    node_id, [m for m, w in weights.items() if w > 0])
            weights[node_id] = float(weight)
        elif action == "drain":
            if node_id is None or node_id not in weights:
                raise ValueError(f"node {node_id} is not a ring member")
            weights[node_id] = 0.0
        elif action == "remove":
            if node_id is None or node_id not in weights:
                raise ValueError(f"node {node_id} is not a ring member")
            del weights[node_id]
            if not weights:
                raise ValueError("cannot remove the last ring member")
        elif action == "reweight":
            if node_id is None or node_id not in weights:
                raise ValueError(f"node {node_id} is not a ring member")
            if weight is None:
                raise ValueError("reweight needs weight")
            weights[node_id] = float(weight)
        else:
            raise ValueError(f"unknown ring action {action!r} "
                            "(add/drain/remove/reweight)")
        if not any(w > 0 for w in weights.values()):
            raise ValueError("change would leave no active member")
        new = self.ring.propose_next(weights)
        self.ring.install(new, source=f"admin:{action}")
        ring_dict = new.to_dict()

        async def push(peer) -> tuple[int, bool]:
            try:
                await self.client.call(
                    peer, {"op": "propose_ring", "ring": ring_dict},
                    retries=2)
                return peer.node_id, True
            # not silent: surfaced per-peer in the admin reply AND the
            # peer converges later via the epoch-on-RPC gossip
            except RpcError:  # dfslint: ignore[DFS007]
                return peer.node_id, False

        pushed = dict(await asyncio.gather(
            *(push(p) for p in self._peers())))
        return {"action": action, "epoch": new.epoch,
                "ring": ring_dict, "pushed": pushed}

    _ADD_WEIGHT_MIN = 0.25    # capacity-derived weight clamp: a tiny
    _ADD_WEIGHT_MAX = 4.0     # disk still takes SOME share, a huge one
                              # never dominates the map on day one

    async def _derive_add_weight(self, node_id: int,
                                 members: list[int]) -> float:
        """Default weight for ``ring add`` (r20): the joiner's free
        disk bytes over the MEDIAN active member's, clamped to
        [0.25, 4.0]. Headroom comes from the census inventory's
        ``disk`` block (the ``df`` numbers) — self via the local
        statvfs, peers via one ``get_census`` round. Any failure —
        unreachable joiner, no members answering, zero medians —
        falls back to 1.0, the pre-r20 constant."""
        async def free_bytes(nid: int) -> float | None:
            try:
                if nid == self.cfg.node_id:
                    disk = await asyncio.to_thread(self._disk_usage)
                else:
                    resp, _ = await self.client.call(
                        self.cfg.cluster.peer(nid),
                        {"op": "get_census"}, retries=1)
                    disk = (resp.get("census") or {}).get("disk") or {}
                free = disk.get("freeBytes")
                return float(free) if isinstance(free, (int, float)) \
                    and free > 0 else None
            # not silent: a None row degrades to the 1.0 fallback below
            except (RpcError, KeyError):  # dfslint: ignore[DFS007]
                return None

        target = await free_bytes(node_id)
        if target is None:
            return 1.0
        frees = [f for f in await asyncio.gather(
            *(free_bytes(m) for m in members)) if f is not None]
        if not frees:
            return 1.0
        frees.sort()
        median = frees[len(frees) // 2]
        if median <= 0:
            return 1.0
        w = max(self._ADD_WEIGHT_MIN,
                min(self._ADD_WEIGHT_MAX, target / median))
        return round(w, 3)

    async def ring_status(self, cluster: bool = True) -> dict:
        """GET /ring: this node's membership view plus (cluster=True)
        every peer's epoch/migration state — partial on dead peers,
        like every diagnosis surface."""
        out = {"nodeId": self.cfg.node_id,
               "epoch": self.ring.epoch,
               "mode": "static" if self.ring.current.vnodes == 0
               else "hash",
               "vnodes": self.ring.current.vnodes,
               "members": self.ring.current.to_dict()["members"],
               "active": self.ring.current.active_ids(),
               "migrating": self.ring.migrating,
               "previousEpoch": self.ring.previous.epoch
               if self.ring.previous is not None else None,
               "rebalance": self.ring.rebalance_stats()}
        if not cluster:
            return out

        async def one(peer) -> tuple[int, dict | None]:
            try:
                resp, _ = await self.client.call(
                    peer, {"op": "get_ring"}, retries=1)
                ring = resp.get("ring") or {}
                return peer.node_id, {
                    "epoch": ring.get("epoch"),
                    "migrating": bool(resp.get("migrating"))}
            # not silent: a None row IS the partial-result signal
            except RpcError:  # dfslint: ignore[DFS007]
                return peer.node_id, None

        peers = dict(await asyncio.gather(
            *(one(p) for p in self._peers())))
        out["peers"] = {str(k): v for k, v in sorted(peers.items())}
        out["peersFailed"] = sum(1 for v in peers.values() if v is None)
        return out

    def ring_stats(self) -> dict:
        """``/metrics`` ``ring`` section. The vnodes/members/
        rebalanceCreditBytes keys mirror RingConfig fields (dfslint
        DFS005 checks the config ⇄ CLI ⇄ metrics mapping); the rest is
        live epoch + rebalance state."""
        r = self.cfg.ring
        return {"vnodes": r.vnodes,
                "members": r.members,
                "rebalanceCreditBytes": r.rebalance_credit_bytes,
                "epoch": self.ring.epoch,
                "mode": "static" if self.ring.current.vnodes == 0
                else "hash",
                "active": self.ring.current.active_ids(),
                "rebalance": self.ring.rebalance_stats()}

    # ------------------------------------------------------------------ #
    # dedup/index plane: filter gossip (dfs_tpu.index, docs/index.md)
    # ------------------------------------------------------------------ #

    async def _filter_sync_loop(self) -> None:
        """Replicate every peer's existence filter on the configured
        cadence (``IndexConfig.filter_sync_s``). The first round runs
        immediately — a freshly-booted node should start skipping
        probes as soon as its peers can be asked."""
        interval = self.cfg.index.filter_sync_s
        while True:
            try:
                await self._filter_sync_once()
            except Exception as e:  # noqa: BLE001 — the sync loop must
                # outlive one bad round; next tick retries
                self.log.warning("filter sync failed: %s", e)
            await asyncio.sleep(interval)

    async def _filter_sync_once(self) -> int:
        """One gossip round: per peer, a ``filter_delta`` from the
        replicated (generation, version) cursor — or a full
        ``get_filter`` resync when no replica exists yet, the
        generation moved, or the delta is unusable/corrupt (strict
        validation; at-least-once like propose_ring). Returns peers
        successfully synced."""
        plane = self.index
        if plane is None or plane.local_filter is None:
            return 0
        synced = 0
        for peer in self._peers():
            st = plane.peer_filters.state(peer.node_id)
            try:
                if st is None:
                    ok = await self._filter_fetch_full(peer)
                else:
                    resp = await self.client.filter_delta(
                        peer, st["gen"], st["version"], retries=1)
                    gen, version = resp.get("gen"), resp.get("version")
                    ok = (not resp.get("resync")
                          and isinstance(gen, int)
                          and isinstance(version, int)
                          and plane.peer_filters.apply_delta(
                              peer.node_id, gen, version,
                              resp.get("adds")))
                    if not ok:
                        # generation moved / corrupt or malformed
                        # delta: the replica cannot be patched — full
                        # resync, never a poisoned filter
                        ok = await self._filter_fetch_full(peer)
                if ok:
                    synced += 1
            # a LIVE peer that answers "unknown op" is a pre-r16 build
            # (or filters off): there is nothing to sync from it and
            # nothing is wrong — the probe path simply stays un-trimmed
            # for that peer. Not silent: the absent replica is visible
            # in /metrics index.peerFilters and the doctor's
            # index_stale ages.
            except RpcRemoteError:  # dfslint: ignore[DFS007]
                continue
            except RpcError:
                # transport failure: best-effort by contract (the probe
                # path degrades to probing); counted so habitual
                # failures surface
                self.counters.inc("filter_sync_failures")
        return synced

    async def _filter_fetch_full(self, peer) -> bool:
        """Full filter resync from one peer; False = the peer runs no
        filter plane (pre-r16 build or filters off) or sent garbage."""
        plane = self.index
        meta, body = await self.client.get_filter(peer, retries=1)
        if meta is None:
            return False
        try:
            # ownership copy ON PURPOSE: the replica outlives the reply
            # frame, and pinning the receive buffer for the filter's
            # lifetime would hold every frame it arrived in
            plane.peer_filters.apply_full(
                peer.node_id, meta, bytes(body))  # dfslint: ignore[DFS006]
        except (KeyError, TypeError, ValueError):
            self.counters.inc("filter_sync_failures")
            return False
        self.obs.event("filter_resync", peer=peer.node_id,
                       gen=meta.get("gen"), bytes=len(body))
        return True

    def index_stats(self) -> dict:
        """``/metrics`` ``index`` section. The enabled/memtableEntries/
        compactRuns/filterBitsPerKey/filterSyncS keys mirror
        IndexConfig fields (dfslint DFS005 checks the config ⇄ CLI ⇄
        metrics mapping); the live plane (LSI gauges, filter bytes,
        probe-skip counters) rides alongside when enabled."""
        c = self.cfg.index
        out = {"enabled": c.enabled,
               "memtableEntries": c.memtable_entries,
               "compactRuns": c.compact_runs,
               "filterBitsPerKey": c.filter_bits_per_key,
               "filterSyncS": c.filter_sync_s,
               "backgroundCompact": c.background_compact,
               "echoCacheEntries": c.echo_cache_entries}
        if self.index is not None:
            out.update(self.index.stats())
        return out

    async def _serve_internal_frame(self, conn, header: dict,
                                    body: memoryview,
                                    nbytes_in: int) -> None:
        """Serve ONE inbound storage-plane frame (the FrameServerProtocol
        awaits this per frame, strictly sequentially per connection —
        the same ordering the pre-r10 stream loop had). ``body`` is a
        read-only view of the frame's receive buffer (zero-copy all the
        way into CAS writes); ``nbytes_in`` is the frame's full on-wire
        size, which is what the RPC tables and span byte counts record
        (headers included — /metrics matches what the socket carried).

        Trace context off the wire: the OPTIONAL `trace` field names the
        caller's rpc span — this op's span (and every span it opens
        downstream: cas, admission waits) parents to it, which is what
        makes cluster stitching possible. Absent/malformed (pre-r09
        peers) roots a fresh trace — but only for the HEAVY ops: rooting
        every untraced health probe / background repair call would mint
        a steady stream of unqueryable single-span traces that evict
        client-tagged spans from the bounded ring (the same probe-noise
        reasoning that exempts cheap ops from the internal admission
        gate)."""
        op = header.get("op")
        tr = parse_wire_trace(header.get("trace"))
        # end-to-end deadline off the wire (docs/serve.md §deadlines):
        # the OPTIONAL `deadline` field carries the sender's REMAINING
        # budget — this hop starts its own countdown from it, so the
        # decrement across hops is exactly the flight time and no wall
        # clocks are ever compared. Absent/malformed (pre-r18 peer) =
        # no deadline, the historical service path byte-identical.
        budget = deadline.parse_wire(header.get("deadline"))
        dl_token = deadline.activate(budget) if budget is not None \
            else None
        t0 = time.perf_counter()
        try:
            with (self.obs.server_span(f"peer.{op}", tr)
                  if tr is not None or op in _HEAVY_OPS
                  else contextlib.nullcontext(_NULL_OBS_SPAN)) as sp:
                sp.bytes = nbytes_in
                try:
                    if self.chaos is not None:
                        # injected whole-node slowness (chaos
                        # serve_delay): inside the span so traces
                        # attribute the stall to this op, before the
                        # gate so probes feel it too — a slow node's
                        # health answers ARE slow
                        await self.chaos.before_serve(str(op))
                    gate = self.serve.admission.internal
                    if gate.enabled and op in _HEAVY_OPS:
                        # bounded storage-plane concurrency for the
                        # BULK ops only; a shed op surfaces to the
                        # peer as an application error
                        # (RpcRemoteError — live peer, not a death
                        # sign). Cheap O(1)/metadata ops — health
                        # above all — bypass the gate: a health
                        # probe queued behind multi-second transfers
                        # past the prober's timeout would make a
                        # merely BUSY node look dead and trigger
                        # repair churn.
                        async with gate.slot():
                            resp, rbody = await self._dispatch(header,
                                                               body)
                    else:
                        resp, rbody = await self._dispatch(header, body)
                # not silent: the error is returned to the peer in the
                # reply and recorded on the server span (sp.err)
                except Exception as e:  # noqa: BLE001  # dfslint: ignore[DFS007]
                    sp.err = type(e).__name__
                    resp, rbody = {"ok": False, "error": str(e)}, b""
                # reply encoded inside the span so sp.bytes carries the
                # real frame total; the buffers themselves are NOT
                # joined — they go to the transport one by one below
                head, bufs, nbytes_out = encode_frame(resp, rbody)
                sp.bytes = nbytes_in + nbytes_out
        finally:
            if dl_token is not None:
                deadline.restore(dl_token)
        self.obs.rpc_server.record(
            tr[2] if tr is not None and tr[2] is not None else "-",
            str(op), time.perf_counter() - t0,
            bytes_out=nbytes_out, bytes_in=nbytes_in,
            error=not resp.get("ok", False))
        try:
            conn.send_encoded(head, bufs)
            await conn.drain()
        except (ConnectionError, OSError, WireError):
            # peer went away mid-reply: nothing to salvage — but count
            # it (DFS007): a peer that habitually hangs up mid-reply is
            # a sick link this node would otherwise never surface
            self.counters.inc("peer_reply_aborted")
            conn.close()

    async def _dispatch(self, header: dict, body) -> tuple[dict, object]:
        op = header.get("op")
        if deadline.expired():
            # the caller's end-to-end budget ran out while this frame
            # sat in the admission queue (or in flight): dropping HERE
            # — before any CAS-pool job, hash pass, or payload write —
            # is the whole point of carrying deadlines on the wire.
            # Expired work must never reach a worker thread.
            self.counters.inc("deadline_drops")
            self.obs.event("deadline_shed", where="dispatch",
                           op=str(op))
            return {"ok": False, "error": "deadline expired"}, b""
        repoch = header.get("repoch")
        rfp = header.get("rfp")
        if isinstance(repoch, int) and not isinstance(repoch, bool) \
                and (repoch != self.ring.epoch
                     or (isinstance(rfp, str)
                         and rfp != self.ring.current.fingerprint)):
            # membership disagreement on a placement-bearing op —
            # lagging epoch OR a different map at the SAME epoch
            # (racing admins; the fingerprint tiebreak reconciles):
            # refuse WITH our epoch + map, so the stale side
            # (whichever it is) converges and retries instead of
            # silently mis-placing — see comm/rpc.py
            # RingEpochMismatch. Ops without the fields (pre-r14
            # peers, metadata ops) are served as-is.
            self.ring.note_epoch_mismatch()
            self.counters.inc("ring_epoch_mismatches")
            return {"ok": False,
                    "error": f"ring epoch mismatch (have "
                             f"{self.ring.epoch}, got {repoch})",
                    "ringEpoch": self.ring.epoch,
                    "ring": self.ring.current.to_dict()}, b""
        if op == "get_ring":
            # membership query (ring status / boot catch-up): cheap
            # metadata, ungated like health
            return {"ok": True, "ring": self.ring.current.to_dict(),
                    "previous": self.ring.previous.to_dict()
                    if self.ring.previous is not None else None,
                    "migrating": self.ring.migrating}, b""
        if op == "propose_ring":
            # epoch-versioned membership install (admin push / the
            # stale-peer refresh path). Idempotent: at-or-below-epoch
            # proposals answer ok with our state — gossip is
            # at-least-once.
            try:
                installed = self.ring.adopt(header.get("ring"),
                                            source="propose")
            except ValueError as e:
                return {"ok": False, "error": f"bad ring map: {e}"}, b""
            return {"ok": True, "epoch": self.ring.epoch,
                    "installed": installed}, b""
        if op == "store_chunks":
            # Hash echo: recompute every digest from the received bytes
            # (reference receiver contract, StorageNode.java:279-292).
            # The hash runs OFF the event loop and the file writes in
            # the bounded CAS write pool, as a batch (cas.put_many —
            # the coordinator's own copy takes the same road): inline
            # they occupied the loop for seconds under writeback
            # pressure (observed on a 2 GiB-corpus ingest), so the node
            # answered NOTHING and every peer cascaded into
            # "unreachable" — the same rule upload/download/scrub
            # already follow. A pair whose echo differs is left out.
            pairs = unpack_chunks(header.get("chunks", []), body)
            echoed = await asyncio.to_thread(
                sha256_many_hex, [b for _, b in pairs])
            sound = [(actual, data)
                     for (claimed, data), actual in zip(pairs, echoed)
                     if claimed == actual]
            results = await self.cas.put_many(sound, verify=False)
            stored = sum(results)
            dedup = len(sound) - stored
            nbytes = sum(len(data)
                         for (_, data), newly in zip(sound, results)
                         if newly)
            if stored:
                self.counters.inc("chunks_stored", stored)
                self.counters.inc("bytes_stored", nbytes)
            if dedup:
                self.counters.inc("dedup_hits", dedup)
            return {"ok": True, "digests": echoed}, b""
        if op == "has_chunks":
            digests = header.get("digests", [])
            # ONE job of the CAS latency lane for the whole probe list
            # (this used to ride the unbounded to_thread executor); with
            # the index plane on, each answer is a memtable/run hit
            # instead of a stat syscall (docs/index.md). With it off the
            # answer is a look at the disk, a stat a digest — unless
            # the CALLER sets `residentOk` (placement's probes and
            # pre-ack rounds only): then the store's resident set may
            # answer (store/cas.py has). A caller that does not send
            # the key — the repair cycle, who_has, an older peer — is
            # answered from the disk, and that look heals the set.
            mask = await self.cas.has_many(
                digests, resident_ok=bool(header.get("residentOk")))
            return {"ok": True,
                    "have": [d for d, h in zip(digests, mask) if h]}, b""
        if op == "get_filter":
            # peer-existence filter replication (docs/index.md): the
            # full filter snapshot — generation-stamped; cheap
            # metadata, ungated like get_ring. `filter: null` = this
            # node runs no filter plane (pre-r16 peer or filters off).
            if self.index is None or self.index.local_filter is None:
                return {"ok": True, "filter": None}, b""
            meta, body = self.index.local_filter.snapshot()
            return {"ok": True, "filter": meta}, body
        if op == "filter_delta":
            # incremental filter update: digests added since (gen,
            # version), or resync=True when the caller must refetch the
            # full filter — generation moved, version unknown, or the
            # add log no longer reaches back (at-least-once discipline,
            # same shape as propose_ring). Malformed cursors answer
            # resync, never an error: gossip must converge, not fail.
            if self.index is None or self.index.local_filter is None:
                return {"ok": True, "resync": True, "gen": -1,
                        "version": 0}, b""
            gen, since = header.get("gen"), header.get("since")
            if not isinstance(gen, int) or not isinstance(since, int) \
                    or isinstance(gen, bool) or isinstance(since, bool):
                return {"ok": True, "resync": True, "gen": -1,
                        "version": 0}, b""
            return {"ok": True,
                    **self.index.local_filter.delta(gen, since)}, b""
        if op == "get_filters":
            # batched filter fetch (docs/client.md): this node's own
            # filter PLUS every peer-filter replica it gossips, so an
            # external smart client learns the whole cluster's
            # existence summaries in one round trip. Meta table in the
            # header (blob lengths included), raw blobs concatenated in
            # table order as the body — the pack_chunks shape without
            # digests. Cheap metadata, ungated like get_filter; a node
            # with no filter plane answers an empty table.
            metas: list[dict] = []
            blobs: list[bytes] = []
            if self.index is not None \
                    and self.index.local_filter is not None:
                fmeta, blob = self.index.local_filter.snapshot()
                metas.append({"nodeId": self.cfg.node_id,
                              "gen": fmeta["gen"],
                              "version": fmeta["version"],
                              "capacity": fmeta["capacity"],
                              "bitsPerKey": fmeta["bitsPerKey"],
                              "ageS": 0.0, "length": len(blob)})
                blobs.append(blob)
                for _pid, pmeta, pblob in \
                        self.index.peer_filters.replicas():
                    metas.append({**pmeta, "length": len(pblob)})
                    blobs.append(pblob)
            return {"ok": True, "filters": metas}, blobs
        if op == "announce":
            m = Manifest.from_json(header["manifest"])
            if header.get("fresh"):
                self.store.manifests.clear_tombstone(m.file_id)
            # off-loop: with fsync durability the save is a disk barrier
            if await asyncio.to_thread(self.store.manifests.save, m):
                self.counters.inc("manifests_announced")
            else:
                self.counters.inc("announce_rejected_tombstoned")
            return {"ok": True}, b""
        if op == "tombstones":
            # ts=None means the .tomb vanished between the glob and the
            # read — a concurrent fresh re-upload cleared it. Advertising
            # it would invite peers to re-delete the acknowledged upload.
            ms = self.store.manifests
            tombs = [{"id": fid, "ts": ts} for fid in ms.tombstones()
                     if (ts := ms.tombstone_ts(fid)) is not None]
            return {"ok": True, "tombs": tombs}, b""
        if op == "list_manifests":
            return {"ok": True, "ids": self.store.manifests.ids()}, b""
        if op == "get_chunk":
            # off-loop via the bounded CAS pool: a cold read under
            # writeback pressure is a multi-ms (worst observed: multi-s)
            # syscall the serving loop must not eat inline
            if self.tier is not None:
                # storage-plane temperature feed (docs/tiering.md): a
                # holder serving a chunk to a peer's download IS read
                # demand — without this only the coordinating node's
                # ledger heats and every other scanner misclassifies
                self.tier.ledger.note_read(header["digest"])
            data = await self.cas.get(header["digest"])
            if data is None:
                return {"ok": False, "error": "chunk not found"}, b""
            return {"ok": True}, data
        if op == "get_chunks":
            # batched fetch: one frame returns every requested chunk this
            # node holds (the per-chunk op costs a full RPC round-trip per
            # chunk — the dominant cost of degraded reads at small chunk
            # sizes). Missing digests are simply absent from the table.
            # Reads ride the bounded CAS pool like every other chunk-file
            # touch — a burst of peer batched fetches must not stack
            # unbounded executor jobs.
            if self.tier is not None:
                # same storage-plane temperature feed as get_chunk
                for d in header.get("digests", []):
                    if isinstance(d, str):
                        self.tier.ledger.note_read(d)
            have = await self.cas.get_many(header.get("digests", []))
            table, bufs = pack_chunks(have)
            # buffer list straight from CAS reads to the socket — the
            # reply body is never joined (zero-copy data plane)
            return {"ok": True, "chunks": table}, bufs
        if op == "get_manifest":
            m = self.store.manifests.load(header["fileId"])
            return {"ok": True,
                    "manifest": None if m is None else m.to_json(),
                    "mtime": self.store.manifests.mtime(
                        header["fileId"])}, b""
        if op == "delete":
            # off-loop: tombstone write (an fsync barrier under the
            # default durability mode) + the delete-triggered GC sweep
            await asyncio.to_thread(self._forget_file, header["fileId"])
            return {"ok": True}, b""
        if op == "delete_chunks":
            # surplus-replica reclaim (r20 tiering): the demoting node
            # asks peers to drop chunk copies that the COLD manifest no
            # longer places on them. The receiver NEVER trusts the
            # caller's view — it re-derives its own expected set from
            # its own manifests + ring and refuses any digest it still
            # believes it owns. A stale peer (missed the demote
            # announce) therefore refuses — the safe direction; the
            # caller re-announces and retries on a later scan. Refused
            # wholesale mid-migration: the dual-read window may need
            # any replica.
            digests = header.get("digests", [])
            if not (isinstance(digests, list) and
                    all(isinstance(d, str) and len(d) == 64
                        for d in digests)):
                return {"ok": False, "error": "bad digests"}, b""
            if self.ring.migrating:
                return {"ok": True, "removed": [],
                        "refused": list(digests)}, b""

            def reclaim():
                expected = self._expected_digests_here(set(digests))
                removed, refused = [], []
                for d in digests:
                    if d in expected:
                        refused.append(d)
                    elif self.store.chunks.delete(d):
                        removed.append(d)
                    elif self.store.chunks.delta_pinned(d):
                        # delta base (similarity plane): resident deltas
                        # reconstruct through it — refused like an owned
                        # chunk; the caller retries after the dependents
                        # die or re-materialize
                        refused.append(d)
                return removed, refused

            removed, refused = await asyncio.to_thread(reclaim)
            self.serve.drop_cached(removed)
            if removed:
                self.counters.inc("tier_chunks_reclaimed", len(removed))
            return {"ok": True, "removed": removed,
                    "refused": refused}, b""
        if op == "get_trace":
            # span query for cross-node stitching (trace_spans below):
            # cheap metadata (bounded ring scan), ungated like health
            return {"ok": True, "spans": await self._own_spans(
                str(header.get("traceId", "")))}, b""
        if op == "get_doctor":
            # per-node diagnosis snapshot for the cluster doctor fan-out
            # (doctor_report below). Ungated like get_trace — diagnosis
            # must work exactly when the bulk gates are saturated; the
            # journal/disk reads inside run off-loop.
            return {"ok": True, "doctor": await self.doctor_snapshot()}, b""
        if op == "get_census":
            # bucketed CAS inventory for the cluster census fan-out
            # (census_report below); optional `prefixes` drills member
            # digest lists for mismatched buckets. Ungated like
            # get_doctor — data-health diagnosis must answer while the
            # bulk gates are saturated; the store scan runs on the
            # bounded CAS read pool, never the loop.
            prefixes = header.get("prefixes")
            if prefixes is not None and not (
                    isinstance(prefixes, list)
                    and all(isinstance(p, str) and len(p) ==
                            self.store.chunks.PREFIX_HEX
                            for p in prefixes)):
                return {"ok": False, "error": "bad prefixes"}, b""
            return {"ok": True,
                    "census": await self.census_inventory(prefixes)}, b""
        if op == "health":
            # counts must be O(1)/filename-only: every peer probes this
            # op every few seconds, and the full digests()+manifest-parse
            # scan measured ~40% of read throughput at a 175K-chunk
            # store. The count's one-time priming scan goes off-loop.
            return {"ok": True, "nodeId": self.cfg.node_id,
                    "chunks": await asyncio.to_thread(
                        self.store.chunks.count),
                    "files": len(self.store.manifests.ids())}, b""
        return {"ok": False, "error": f"unknown op {op!r}"}, b""

    # ------------------------------------------------------------------ #
    # upload (L4) — reference handleUpload, StorageNode.java:118-189
    # ------------------------------------------------------------------ #

    def _peers(self) -> list:
        return [p for p in self.cfg.cluster.peers
                if p.node_id != self.cfg.node_id]

    async def upload(self, data: bytes, name: str,
                     ec_k: int = 0) -> tuple[Manifest, dict]:
        # hashing + fragmentation run off the event loop: a multi-hundred-
        # MiB body would otherwise stall every concurrent request for the
        # full CPU pass (the reference is thread-per-connection so it
        # never noticed; an asyncio node must not block its loop)
        with self.obs.span("upload.hash_file", latency=True):
            file_id = await asyncio.to_thread(sha256_hex, data)
        if not name:
            name = f"file-{file_id[:8]}"  # reference default, StorageNode.java:133-135
        with self.obs.span("upload.fragment", latency=True):
            manifest = await asyncio.to_thread(
                self.fragmenter.manifest, data, name=name, file_id=file_id)

        stats = self._new_upload_stats()
        stats["bytes"] = len(data)
        seen: set[str] = set()
        batch: list[tuple[str, bytes]] = []
        view = memoryview(data).toreadonly()
        for c in manifest.chunks:
            if c.digest in seen:
                continue  # duplicate content within the file: place once
            seen.add(c.digest)
            # read-only VIEW per chunk, shared across every target —
            # pre-r10 this was a bytes slice per chunk (a full-corpus
            # copy before a byte hit the wire); views flow untouched
            # through CAS puts and scatter-gather peer sends
            batch.append((c.digest, view[c.offset:c.offset + c.length]))
        stats["uniqueChunks"] = len(seen)
        placement = None
        rf = None
        if ec_k:
            ids = self.ring.node_ids()
            if ec_k + 2 > len(ids):
                raise UploadError(
                    f"ec={ec_k} needs {ec_k + 2} nodes, ring has "
                    f"{len(ids)} active (shards of a stripe must land "
                    "on distinct nodes)", status=400)
            if ec_k > 255:
                # the Q coefficients live in GF(256)*'s order-255 group:
                # beyond k=255 they repeat and some double erasures
                # become uncorrectable — the any-2-lost guarantee fails
                raise UploadError("ec must be <= 255", status=400)
            with self.obs.span("upload.ec_encode", latency=True):
                manifest, parity = await asyncio.to_thread(
                    self._ec_extend, manifest, data, ec_k)
            for d, b in parity:
                # per-item seen check: P and Q can share a digest
                # (k=1 makes Q == P), and a lazy bulk-extend would
                # place it twice
                if d not in seen:
                    seen.add(d)
                    batch.append((d, b))
            stats["ecParityBytes"] = sum(len(b) for _, b in parity)
            placement = ec_placement_map(manifest, self.ring.current)
            rf = 1   # the parity IS the redundancy (any 2 shards may die)
        ledger = self._new_trust_ledger()
        await self._place_batch(file_id, batch, stats, rf=rf,
                                placement=placement, ledger=ledger)
        if ledger:
            # filter-credited copies confirmed BEFORE the ack
            await self._verify_trusted(file_id, ledger, stats, rf=rf,
                                       placement=placement)
        await self._finalize_upload(manifest)
        self.counters.inc("upload_bytes", len(data))
        return manifest, stats

    def _ec_extend(self, manifest: Manifest, data: bytes, k: int
                   ) -> tuple[Manifest, list[tuple[str, bytes]]]:
        """Compute P+Q parity per stripe of ``k`` data chunks (ops.ec;
        device encode when the node's fragmenter already runs on one) and
        return the EC manifest plus the parity (digest, payload) list.
        Runs in a worker thread — NumPy/encode work."""
        view = memoryview(data)
        src = {c.digest: view[c.offset:c.offset + c.length]
               for c in manifest.chunks}
        return self._ec_extend_from(manifest, src, k)

    def _ec_extend_from(self, manifest: Manifest,
                        chunk_bytes: Mapping[str, bytes], k: int
                        ) -> tuple[Manifest, list[tuple[str, bytes]]]:
        """:meth:`_ec_extend` with per-chunk payloads sourced from a
        digest map instead of one contiguous buffer — the shape tier
        demotion has (its bytes come from a ``_gather_chunks`` dict,
        never a whole-file assembly). Worker-thread code."""
        import dataclasses as _dc

        import numpy as np

        from dfs_tpu.ops import ec as ec_ops

        device = "tpu" in self.fragmenter.name
        stripes: list[StripeRef] = []
        parity: list[tuple[str, bytes]] = []
        for grp in ec_stripe_groups(manifest.chunks, k):
            pad = stripe_shard_len(grp)
            sh = np.zeros((len(grp), pad), dtype=np.uint8)
            for j, c in enumerate(grp):
                sh[j, :c.length] = np.frombuffer(
                    chunk_bytes[c.digest], dtype=np.uint8,
                    count=c.length)
            p, q = ec_ops.encode_pq(sh, device=device)
            pb, qb = p.tobytes(), q.tobytes()
            pd, qd = sha256_hex(pb), sha256_hex(qb)
            stripes.append(StripeRef(p=pd, q=qd, shard_len=pad))
            parity.append((pd, pb))
            parity.append((qd, qb))
        ec = EcInfo(k=k, stripes=tuple(stripes))
        return _dc.replace(manifest, ec=ec), parity

    # per-RPC payload cap for replication slices (see replicate() in
    # _place_batch); class-level so tests/benches can scale it per node
    _REPLICA_SLICE_BYTES = 8 * 1024 * 1024

    async def upload_stream(self, blocks, name: str) -> tuple[Manifest, dict]:
        """Bounded-memory PIPELINED ingest: ``blocks`` is an async
        iterator of byte blocks (e.g. an HTTP chunked-transfer body).
        The fragmenter's streaming walk runs in a worker thread
        consuming the blocks; finished chunks flow back and are
        placed/replicated in ~``ingest.flush_bytes`` batches as the
        stream arrives — at no point does the whole payload exist in
        node memory (the reference reads the entire body into one array,
        StorageNode.java:124). file_id stays sha256(whole stream),
        computed incrementally.

        Up to ``ingest.window`` placement batches stay in flight at once
        (docs/ingest.md): while batch N replicates over the network the
        fragmenter keeps chunking batch N+1 instead of stalling on its
        credits — replication latency was the dominant ingest cost the
        serial schedule paid in full (INGEST_r07.json: 2.66x). The first
        placement failure aborts the stream exactly like the serial
        path: reading stops, no manifest commits, already-placed chunks
        age out via GC. Per-batch stats are kept separately and merged
        in batch order, so the windowed schedule reports byte-identical
        stats to the serial one."""
        import queue as _queue

        loop = asyncio.get_running_loop()
        inq: _queue.Queue = _queue.Queue(maxsize=4)
        outq: asyncio.Queue = asyncio.Queue()
        hasher = sha256_new()
        frag_dead = threading.Event()
        aborted = threading.Event()
        # byte credits: the fragmenter thread blocks once this many
        # produced-but-unconsumed payload BYTES are outstanding, which
        # stops it draining inq, which blocks the feeder, which stops
        # reading the socket — TCP backpressure end to end. Without it a
        # fast client outruns slow replication and the 'bounded-memory'
        # contract silently fails. (Counting chunks instead of bytes —
        # the gate until round 7 — let max-size chunks oversubscribe the
        # budget by orders of magnitude.)
        credits = ByteBudget(self.cfg.ingest.credit_bytes)

        def feed_iter():
            while True:
                try:
                    b = inq.get(timeout=0.5)
                except _queue.Empty:
                    # abort must not depend on the end-of-stream sentinel
                    # arriving: the feeder's cancelled finally submits it
                    # through the shared to_thread pool, which can be
                    # saturated — a fragmenter parked in a bare get()
                    # would deadlock the abort path's gather forever
                    if aborted.is_set():
                        return
                    continue
                if b is None:
                    return
                yield b

        def on_chunk(digest: str, payload: bytes) -> None:
            t0 = time.perf_counter()
            while not credits.acquire(len(payload), timeout=0.5):
                if aborted.is_set():
                    raise RuntimeError("upload aborted")
            waited = time.perf_counter() - t0
            if waited > 0.001:   # stall attribution: chunking blocked on
                # unconsumed output (downstream placement is the
                # bottleneck); sub-ms lock noise is not a stall
                self.ingest_stalls.add("creditS", waited)
            loop.call_soon_threadsafe(outq.put_nowait, (digest, payload))

        def run_fragmenter():
            try:
                # to_thread copied the request's context: the span (the
                # owner seam as this node sees it, same name as the
                # whole-payload path's) parents to the request, and a
                # chip owner's spans hang under it
                with self.obs.span("upload.fragment", latency=True):
                    m = self.fragmenter.manifest_stream(
                        feed_iter(), name=name or "stream", store=on_chunk)
                loop.call_soon_threadsafe(outq.put_nowait, ("done", m))
            # not silent: surfaced to the async consumer via the
            # ("error", e) queue item, which re-raises on the loop
            except BaseException as e:  # dfslint: ignore[DFS007]
                loop.call_soon_threadsafe(outq.put_nowait, ("error", e))
            finally:
                frag_dead.set()

        def put_block(b) -> None:
            # bounded put that cannot deadlock: if the fragmenter thread
            # died it stopped draining inq, so give up instead of blocking
            # a worker thread (and the feeder await) forever
            while not frag_dead.is_set():
                try:
                    inq.put(b, timeout=0.5)
                    return
                except _queue.Full:
                    continue

        frag_task = asyncio.create_task(asyncio.to_thread(run_fragmenter))

        async def feeder() -> int:
            total = 0
            # the body's two waits, told apart: for the next block from
            # the socket (the client, or TCP backpressure) and for
            # put_block (the fragmenter side is not draining inq)
            body_wait = feed_wait = 0.0
            with self.obs.span("upload.body") as sp:
                try:
                    t = time.perf_counter()
                    async for b in blocks:
                        body_wait += time.perf_counter() - t
                        if aborted.is_set():
                            break    # placement failed: stop reading, do
                            # NOT drain the rest of the body into memory
                        total += len(b)
                        hasher.update(b)
                        t = time.perf_counter()
                        await asyncio.to_thread(put_block, b)
                        now = time.perf_counter()
                        feed_wait += now - t
                        t = now
                    else:       # the wait that found the body's end
                        body_wait += time.perf_counter() - t
                finally:
                    await asyncio.to_thread(put_block, None)
                    sp.bytes = total
                    self.ingest_stalls.add("bodyWaitS", body_wait)
                    self.ingest_stalls.add("feedWaitS", feed_wait)
            return total

        feed_task = asyncio.create_task(feeder())

        stats = self._new_upload_stats()
        seen: set[str] = set()
        batch: list[tuple[str, bytes]] = []
        pending = 0
        manifest: Manifest | None = None
        window = max(1, self.cfg.ingest.window)
        # (task, per-batch stats) in submission order — awaited FIFO so
        # stats merge deterministically and the FIRST failing batch is
        # the one that aborts the stream
        inflight: deque[tuple[asyncio.Task, dict]] = deque()

        async def drain_one() -> None:
            task, bstats = inflight[0]
            # removed only AFTER the await resolves: if THIS coroutine
            # is cancelled mid-await (client hung up), the still-running
            # placement must remain in `inflight` so the abort path
            # below cancels and reaps it — popping first leaked it
            await task
            inflight.popleft()
            self._merge_upload_stats(stats, bstats)

        ledger = self._new_trust_ledger()

        async def submit(b: list[tuple[str, bytes]]) -> None:
            if window == 1:     # serial placement: the historical
                # schedule, byte-identical behavior
                await self._place_batch("", b, stats, ledger=ledger)
                return
            while len(inflight) >= window:
                # stall attribution: the window is full — ingest is
                # blocked on placement (replication/disk), not chunking
                t0 = time.perf_counter()
                # surface a failure from ANY in-flight batch before
                # blocking: awaiting only the head would ride out a
                # slow batch A (dead-peer retries run tens of seconds)
                # while batch C's failure is already known — and then
                # replicate one more doomed batch
                for task, _ in inflight:
                    if task.done() and not task.cancelled() \
                            and task.exception() is not None:
                        await task          # re-raise: abort the stream
                if inflight[0][0].done():
                    await drain_one()       # FIFO merge
                else:
                    await asyncio.wait(
                        [t for t, _ in inflight if not t.done()],
                        return_when=asyncio.FIRST_COMPLETED)
                self.ingest_stalls.add("placementS",
                                       time.perf_counter() - t0)
            bstats = self._new_upload_stats()
            task = asyncio.create_task(
                self._place_batch("", b, bstats, ledger=ledger))
            # completion wakes the consume loop below via a sentinel: a
            # FAILED placement must abort the stream even while the
            # consumer is parked on outq behind a slow client — without
            # the wakeup, abort latency was coupled to body progress
            task.add_done_callback(
                lambda t: outq.put_nowait(("placed", t)))
            inflight.append((task, bstats))
            self.ingest_stalls.peak("placeWindow", len(inflight))

        # file_id is only known at stream end; batches placed before that
        # tag transfers with a placeholder (store_chunks ignores it)
        try:
            while manifest is None:
                # merge (and surface failures of) any placements that
                # already resolved, oldest first
                while inflight and inflight[0][0].done():
                    await drain_one()
                item = await outq.get()
                if item[0] == "placed":
                    task = item[1]
                    if not task.cancelled() and task.exception() \
                            is not None:
                        await task   # re-raise the placement failure
                        # NOW — reading the body stops immediately
                    continue         # success: head drain above merges
                if item[0] == "error" and isinstance(item[1], BaseException):
                    raise UploadError(f"fragmenter failed: {item[1]}")
                if item[0] == "done" and isinstance(item[1], Manifest):
                    manifest = item[1]
                    break
                digest, payload = item
                credits.release(len(payload))
                if digest in seen:
                    continue
                seen.add(digest)
                batch.append((digest, payload))
                pending += len(payload)
                if pending >= self._STREAM_FLUSH_BYTES:
                    await submit(batch)
                    batch, pending = [], 0
            if batch:
                await submit(batch)
            while inflight:        # tail drain: the stream is chunked,
                t0 = time.perf_counter()   # only placement remains
                await drain_one()
                self.ingest_stalls.add("placementS",
                                       time.perf_counter() - t0)
        except BaseException:
            aborted.set()                  # unblock fragmenter + feeder
            # the feeder may be parked in a socket read with no timeout
            # (a stalled client mid-body) — cancel it rather than wait
            # for the next block that may never come; its finally still
            # hands the fragmenter the end-of-stream sentinel
            feed_task.cancel()
            for task, _ in inflight:       # first failure aborts: stop
                task.cancel()              # sibling placements too
            await asyncio.gather(feed_task, frag_task,
                                 *(t for t, _ in inflight),
                                 return_exceptions=True)
            raise
        try:
            # re-raises body errors (malformed chunked framing -> 400);
            # nothing was finalized, so a truncated stream commits NO
            # manifest — its already-placed chunks are unreferenced and
            # the aged GC in the repair loop reclaims them
            total = await feed_task
        finally:
            await frag_task
        if stats["minCopies"] is None:     # zero-chunk (empty) stream
            stats["minCopies"] = self.cfg.cluster.replication_factor
        file_id = hasher.hexdigest()
        if not name:
            name = f"file-{file_id[:8]}"
        manifest = Manifest(file_id=file_id, name=name, size=total,
                            fragmenter=manifest.fragmenter,
                            chunks=manifest.chunks)
        stats["bytes"] = total
        stats["uniqueChunks"] = len(seen)
        if ledger:
            # every filter-credited copy across every placed batch is
            # confirmed in ONE has_chunks round per peer — before the
            # manifest write acks the stream (docs/index.md)
            await self._verify_trusted(file_id, ledger, stats)
        await self._finalize_upload(manifest)
        self.counters.inc("upload_bytes", total)
        return manifest, stats

    async def missing_digests(self, digests: list[str]) -> list[str]:
        """Which of ``digests`` the cluster holds NOwhere reachable —
        the resumable-upload probe (SURVEY §5.4: chunk-level resume falls
        out of the dedup index). Local CAS first — ONE batched
        ``has_many`` job of the CAS latency lane (this loop used to
        stat inline ON the event loop, one syscall per digest); the
        remainder is asked of each digest's replica set via batched
        has_chunks, with peer-filter-ruled-out digests never probed at
        all. Both take a resident answer (``residentOk``): this is
        placement asking, and what it is told is present is re-counted
        before any ack (``upload_resume`` fetches or 409s). Filter
        POSITIVES are still probed here on purpose: a
        bloom false positive answered as "cluster has it" would tell
        the client to skip bytes, and at bloom FP rates every large
        resume would then trip upload_resume's 409 fallback — the
        probe is cheaper than the fallback (docs/index.md)."""
        cand = [d for d in dict.fromkeys(digests) if is_hex_digest(d)]
        mask = await self.cas.has_many(cand, resident_ok=True)
        missing = [d for d, h in zip(cand, mask) if not h]
        if not missing:
            return []
        rf = self.cfg.cluster.replication_factor
        found: set[str] = set()
        by_peer: dict[int, list[str]] = {}
        for d in missing:
            # dual-read candidates: mid-rebalance the bytes may still
            # sit at previous-epoch owners only
            for t in self.ring.read_candidates(d, rf):
                if t != self.cfg.node_id:
                    by_peer.setdefault(t, []).append(d)
        plane = self.index
        if plane is not None and plane.local_filter is not None:
            trimmed: dict[int, list[str]] = {}
            for nid, ds in by_peer.items():
                if plane.peer_filters.state(nid) is None:
                    trimmed[nid] = ds       # no replica: probe as-is
                    continue
                keep = [d for d in ds
                        if plane.peer_filters.contains(nid, d)
                        is not False]
                plane.probes_skipped += len(ds) - len(keep)
                if keep:
                    trimmed[nid] = keep
                elif ds:
                    plane.probe_rpcs_skipped += 1
            by_peer = trimmed

        async def probe(nid: int, ds: list[str]) -> None:
            try:
                resp, _ = await self.client.call(
                    self.cfg.cluster.peer(nid),
                    {"op": "has_chunks", "digests": ds,
                     "residentOk": True}, retries=1)
                found.update(resp.get("have", []))
            except RpcError:
                # best-effort: an unanswered probe only makes the client
                # resend bytes the cluster already has — but count it
                # (DFS007): habitual probe failures silently erase the
                # resume/dedup win
                self.counters.inc("probe_failures")

        await asyncio.gather(*(probe(n, ds) for n, ds in by_peer.items()))
        return [d for d in missing if d not in found]

    async def upload_resume(self, table: list[tuple[int, int, str]],
                            name: str, file_id: str, size: int,
                            provided: dict[str, bytes]
                            ) -> tuple[Manifest, dict]:
        """Finalize an upload from a client-supplied chunk table plus
        ONLY the payloads the cluster lacked (client flow: GET /chunking
        -> chunk locally -> POST /missing -> POST /upload_resume). The
        interrupted-upload bytes already placed are never re-sent — the
        resume SURVEY §5.4 says should fall out of the dedup index.

        Integrity: every provided payload is hash-verified; chunks NOT
        provided must be locally present or fetchable from replicas
        (else UploadError lists them — client falls back to a full
        upload); the assembled stream must hash to ``file_id`` exactly
        like a regular upload's fileId = sha256(body)."""
        if not name:
            name = f"file-{file_id[:8]}"   # reference default naming
        # table sanity: contiguous tiling of [0, size)
        expect = 0
        for off, ln, dg in table:
            if off != expect or ln < 0 or not is_hex_digest(dg):
                raise UploadError("malformed chunk table", status=400)
            expect = off + ln
        if expect != size:
            raise UploadError("chunk table does not tile the stream",
                              status=400)

        hexes = await asyncio.to_thread(
            sha256_many_hex, list(provided.values()))
        for d, h in zip(provided, hexes):
            if d != h:
                raise UploadError(f"provided chunk {d[:12]}… hash mismatch",
                                  status=400)

        refs = [ChunkRef(index=i, offset=off, length=ln, digest=dg)
                for i, (off, ln, dg) in enumerate(table)]
        manifest = Manifest(file_id=file_id, name=name, size=size,
                            fragmenter=self.fragmenter.name,
                            chunks=tuple(refs))

        # assemble incrementally (batches) to verify the whole-stream
        # hash AND place everything; bytes come from `provided`, the
        # local CAS, or replicas
        stats = self._new_upload_stats()
        stats["bytes"] = sum(len(b) for b in provided.values())
        hasher = sha256_new()
        seen: set[str] = set()
        ledger = self._new_trust_ledger()
        batch: list = []
        bsize = 0
        for c in refs:
            batch.append(c)
            bsize += c.length
            if bsize >= self._FETCH_BATCH_BYTES or c is refs[-1]:
                got = dict(provided)
                need = [x for x in batch if x.digest not in got]
                if need:
                    # digest-verified like every read path: a rotten
                    # local copy of an interrupted upload's chunk heals
                    # from a replica instead of failing the resume with
                    # a client-blaming hash error forever
                    fetched = await self._fetch_verified(
                        manifest, need, strict=False)
                    got.update(fetched)
                absent = [x.digest for x in batch if x.digest not in got]
                if absent:
                    raise UploadError(
                        "resume missing chunks: "
                        + ",".join(d[:12] for d in absent), status=409)
                payloads = [got[x.digest] for x in batch]
                await asyncio.to_thread(
                    lambda ps=payloads: [hasher.update(p) for p in ps])
                place = [(x.digest, got[x.digest]) for x in batch
                         if x.digest not in seen]
                seen.update(d for d, _ in place)
                await self._place_batch(file_id, place, stats,
                                        ledger=ledger)
                batch, bsize = [], 0
        if hasher.hexdigest() != file_id:
            raise UploadError("resumed stream does not hash to fileId",
                              status=400)
        stats["uniqueChunks"] = len(seen)
        if stats["minCopies"] is None:
            stats["minCopies"] = self.cfg.cluster.replication_factor
        if ledger:
            await self._verify_trusted(file_id, ledger, stats)
        await self._finalize_upload(manifest)
        self.counters.inc("uploads_resumed")
        self.counters.inc("upload_bytes", size)
        return manifest, stats

    async def commit_manifest(self, table: list[tuple[int, int, str]],
                              name: str, file_id: str, size: int
                              ) -> tuple[Manifest, dict]:
        """Single-hop ingest commit (docs/client.md): the smart client
        already striped every payload directly to its ring owners with
        per-slice hash-echo verification; this ONE coordinator call
        turns that pre-staged state into an acked file. Ack semantics
        are unchanged from a regular upload — the manifest write is
        fsync-before-ack and nothing is acked until every chunk in the
        table is confirmed AT WRITE QUORUM by real ``has_chunks``
        rounds (a stale filter or a lying client cannot manufacture a
        phantom copy: the coordinator re-counts durable copies itself,
        and re-places anything below quorum through the normal batch
        path). Chunks held nowhere reachable raise a 409-class
        UploadError — the client falls back to a legacy full upload.

        ``file_id`` on this path is the client's claim of
        sha256(stream): the coordinator never saw the assembled bytes.
        Per-chunk digests WERE verified at store time (the owners
        hash-echo what they durably hold), and every read re-verifies
        each chunk against the manifest — so a wrong claim can only
        mis-name the file, never corrupt bytes (same trust model as
        the chunk table itself; documented in docs/client.md)."""
        if not name:
            name = f"file-{file_id[:8]}"   # reference default naming
        # table sanity: contiguous tiling of [0, size) — the same
        # contract as upload_resume
        expect = 0
        for off, ln, dg in table:
            if off != expect or ln < 0 or not is_hex_digest(dg):
                raise UploadError("malformed chunk table", status=400)
            expect = off + ln
        if expect != size:
            raise UploadError("chunk table does not tile the stream",
                              status=400)
        refs = [ChunkRef(index=i, offset=off, length=ln, digest=dg)
                for i, (off, ln, dg) in enumerate(table)]
        manifest = Manifest(file_id=file_id, name=name, size=size,
                            fragmenter=self.fragmenter.name,
                            chunks=tuple(refs))
        stats = self._new_upload_stats()
        stats["bytes"] = size

        ring = self.ring.current
        ids = ring.active_ids()
        rf = self.cfg.cluster.replication_factor
        quorum = min(self.cfg.write_quorum, rf, len(ids))
        plane = self.index
        cache = plane.echo_cache if plane is not None else None
        digests = list(dict.fromkeys(dg for _, _, dg in table))
        copies = {d: 0 for d in digests}
        # local holdings first (this node is an owner for its arc)
        mask = await self.cas.has_many(digests, resident_ok=True)
        for d, h in zip(digests, mask):
            if h:
                copies[d] += 1
        # one real has_chunks round per owner peer — first-party
        # evidence, the same pre-ack discipline as _verify_trusted
        by_peer: dict[int, list[str]] = {}
        for d in digests:
            for t in ring.owners(d, rf):
                if t != self.cfg.node_id:
                    by_peer.setdefault(t, []).append(d)

        async def probe(nid: int, ds: list[str]) -> set[str]:
            try:
                resp, _ = await self.client.call(
                    self.cfg.cluster.peer(nid),
                    {"op": "has_chunks", "digests": ds,
                     "residentOk": True},
                    retries=None if self.health.is_alive(nid) else 1)
                self.health.mark_alive(nid)
                return set(resp.get("have", []))
            except DeadlineExpired:
                raise
            except RpcError as e:
                if isinstance(e, RpcUnreachable):
                    self.health.mark_dead(nid)
                self.counters.inc("commit_probe_failures")
                return set()

        with self.obs.span("upload.commit_verify", latency=True):
            peers = sorted(by_peer)
            results = await asyncio.gather(
                *(probe(n, by_peer[n]) for n in peers))
        for nid, have in zip(peers, results):
            for d in by_peer[nid]:
                if d in have:
                    copies[d] += 1
                    if cache is not None:
                        cache.confirm(nid, d)
        confirmed = {d: n for d, n in copies.items() if n >= quorum}
        stats["dedupSkippedBytes"] = sum(
            ln for _, ln, dg in table if dg in confirmed)
        below = [d for d in digests if d not in confirmed]
        if below:
            # heal below-quorum chunks pre-ack: fetch the bytes (local
            # CAS, then any replica — the client may have reached SOME
            # owners) and re-place through the normal batch path, which
            # re-probes, transfers, and falls to handoff as needed.
            # Chunks absent everywhere 409 — the ack was never given.
            self.obs.event("commit_replace", chunks=len(below))
            need = [c for c in refs if c.digest in set(below)]
            dedup: set[str] = set()
            need = [c for c in need
                    if not (c.digest in dedup or dedup.add(c.digest))]
            fetched = await self._fetch_verified(manifest, need,
                                                 strict=False)
            absent = [c.digest for c in need if c.digest not in fetched]
            if absent:
                raise UploadError(
                    "commit missing chunks: "
                    + ",".join(d[:12] for d in absent), status=409)
            await self._place_batch(
                file_id, [(c.digest, fetched[c.digest]) for c in need],
                stats)
        stats["uniqueChunks"] = len(digests)
        batch_min = min((confirmed[d] for d in confirmed), default=rf)
        stats["minCopies"] = batch_min if stats["minCopies"] is None \
            else min(stats["minCopies"], batch_min)
        stats["degraded"] = stats["degraded"] or batch_min < rf
        await self._finalize_upload(manifest)
        self.counters.inc("uploads_committed")
        self.counters.inc("upload_bytes", size)
        return manifest, stats

    def dataplane_info(self) -> dict:
        """GET /dataplane (docs/client.md): one bootstrap call telling
        an external smart client everything it needs to run the data
        plane itself — the ring map (so it can compute owners), the
        peer address book (so it can dial their storage-plane ports),
        the replication policy (rf / write quorum), the fragmenter
        description (so its chunk boundaries match the cluster's
        bit-exactly), and the existence-filter state. Old servers 404
        this route; the client falls back to the coordinator path."""
        out = {"nodeId": self.cfg.node_id,
               "epoch": self.ring.epoch,
               "fingerprint": self.ring.current.fingerprint,
               "ring": self.ring.current.to_dict(),
               "migrating": self.ring.migrating,
               "replicationFactor": self.cfg.cluster.replication_factor,
               "writeQuorum": self.cfg.write_quorum,
               "peers": [{"nodeId": p.node_id, "host": p.host,
                          "port": p.port,
                          "internalPort": p.internal_port}
                         for p in self.cfg.cluster.peers],
               "filters": {"enabled": False}}
        try:
            out["chunking"] = {"fragmenter": self.fragmenter.name,
                               "describe": self.fragmenter.describe()}
        except NotImplementedError:
            out["chunking"] = None   # engine not resume-describable:
            # the client cannot reproduce boundaries — legacy path only
        if self.index is not None and self.index.local_filter is not None:
            fstats = self.index.local_filter.stats()
            out["filters"] = {
                "enabled": True,
                "generation": fstats["generation"],
                "version": fstats["version"],
                "peerAges": {str(p): round(a, 3) for p, a in
                             self.index.peer_filters.ages().items()}}
        return out

    @staticmethod
    def _new_upload_stats() -> dict:
        return {"bytes": 0, "uniqueChunks": 0, "transferredBytes": 0,
                "dedupSkippedBytes": 0, "minCopies": None,
                "handoffChunks": 0, "degraded": False}

    @staticmethod
    def _merge_upload_stats(into: dict, part: dict) -> None:
        """Fold one batch's placement stats into the stream totals.
        Every field is commutative (sum / min / or), so the windowed
        schedule reports exactly what the serial one would; merging in
        batch order anyway keeps the trace reproducible. ``bytes`` and
        ``uniqueChunks`` are stream-level — set by the caller at stream
        end, never by a batch."""
        into["transferredBytes"] += part["transferredBytes"]
        into["dedupSkippedBytes"] += part["dedupSkippedBytes"]
        into["handoffChunks"] += part["handoffChunks"]
        into["degraded"] = into["degraded"] or part["degraded"]
        if part["minCopies"] is not None:
            into["minCopies"] = part["minCopies"] \
                if into["minCopies"] is None \
                else min(into["minCopies"], part["minCopies"])

    @staticmethod
    def _slice_payloads(items: list[tuple[str, bytes]], max_bytes: int
                        ) -> list[list[tuple[str, bytes]]]:
        """Split (digest, payload) lists into <= max_bytes slices (always
        at least one item per slice) so no single RPC carries unbounded
        bytes — the receiver hash-echoes a whole call before replying.
        ``max_bytes`` is required: callers pass ``_REPLICA_SLICE_BYTES``
        (instance-scalable) so a default here cannot silently drift."""
        out: list[list[tuple[str, bytes]]] = []
        cur: list[tuple[str, bytes]] = []
        size = 0
        for d, b in items:
            if cur and size + len(b) > max_bytes:
                out.append(cur)
                cur, size = [], 0
            cur.append((d, b))
            size += len(b)
        if cur:
            out.append(cur)
        return out

    def _raise_if_disk_full(self, e: OSError) -> None:
        """ENOSPC graceful degradation (docs/chaos.md): a full local
        disk during placement is a capacity condition, not a crash —
        surface it as HTTP 507 (Insufficient Storage) with a journaled
        ``disk_pressure`` event instead of a 500 traceback. Reads and
        internal gets keep working (they never put); replication TO a
        full node already degrades via handoff. Anything that is not
        ENOSPC re-raises in the caller unchanged."""
        if e.errno != errno.ENOSPC:
            return
        self.counters.inc("disk_full_rejects")
        self.obs.event("disk_pressure", cause="enospc_put")
        raise UploadError("Insufficient storage: local CAS put failed "
                          "(ENOSPC)", status=507) from e

    @_spanned("upload.place")
    async def _place_batch(self, file_id: str,
                           batch: list[tuple[str, bytes]],
                           stats: dict, rf: int | None = None,
                           placement: Mapping[str, tuple[int, ...]] | None = None,
                           ledger: _TrustLedger | None = None
                           ) -> None:
        """Place one batch of unique (digest, payload) chunks: local puts
        for canonical ownership, concurrent replication with hash-echo
        verification, then sloppy-quorum handoff — failing loudly if any
        chunk ends below quorum. Shared by whole-payload upload (one
        batch) and streaming upload (a batch per ~32 MiB). ``rf``
        overrides the cluster replication factor (erasure-coded files
        place single copies — the parity is the redundancy) and
        ``placement`` pins digests to explicit holders (EC stripe
        placement) instead of the digest-derived replica set; the
        handoff ring then continues cyclically from the pinned holder.

        With the index plane on, each peer's replication pass consults
        that peer's existence filter first (docs/index.md): digests the
        filter RULES OUT skip the probe and transfer directly; filter
        POSITIVES are — when ``ledger`` is given — credited as trusted
        copies (probe and transfer both skipped; the caller MUST run
        :meth:`_verify_trusted` on the ledger before acking), except
        that a chunk nothing else would vouch for is put to one of its
        peers (``filter_credits``) or, with no ledger, probed as before
        minus the ruled-out payload."""
        if self.chaos is not None:
            self.chaos.maybe_crash("place.before_local_put")
        # placement snapshot: ONE ring map for the whole batch — a
        # concurrent epoch adoption must not split a batch between two
        # maps (the rebalancer reconciles whole batches placed under
        # either epoch; a half-and-half batch would satisfy neither)
        ring = self.ring.current
        ids = ring.active_ids()
        if self.index is not None and self.index.echo_cache is not None:
            # pin the echo cache to this batch's epoch: an adoption
            # since the last batch clears every session confirmation
            # (ownership moved — docs/client.md §filter freshness)
            self.index.echo_cache.note_epoch(ring.epoch)
        if rf is None:
            rf = self.cfg.cluster.replication_factor
        placement = placement or {}

        def primary_targets(digest: str) -> Sequence[int]:
            return placement.get(digest) \
                or ring.owners(digest, rf)

        def handoff_ring(digest: str) -> list[int]:
            pinned = placement.get(digest)
            if not pinned:
                return ring.owners(digest, len(ids))
            return ring.handoff_order(pinned)

        per_node: dict[int, list[tuple[str, bytes]]] = {}
        copies: dict[str, int] = {}
        payload_of: dict[str, bytes] = {}
        local_puts: list[tuple[str, bytes]] = []
        for digest, payload in batch:
            copies[digest] = 0
            payload_of[digest] = payload
            for target in primary_targets(digest):
                if target == self.cfg.node_id:
                    local_puts.append((digest, payload))
                    copies[digest] += 1
                else:
                    per_node.setdefault(target, []).append((digest, payload))

        async def put_local(items: list[tuple[str, bytes]],
                            count_dedup: bool = True) -> None:
            # local canonical copies through the async CAS tier: one
            # bounded-pool job for the whole list, OFF the event loop
            # (inline puts occupied it for the full writeback pass) and
            # overlapping peer replication instead of preceding it. A
            # failed put still fails the batch via the gather below.
            results = await self.cas.put_many(items, verify=False)
            nstored = nbytes = 0
            for (d, b), newly in zip(items, results):
                if newly:
                    nstored += 1
                    nbytes += len(b)
            if nstored:
                self.counters.inc("chunks_stored", nstored)
                self.counters.inc("bytes_stored", nbytes)
            if count_dedup and len(items) > nstored:
                self.counters.inc("dedup_hits", len(items) - nstored)

        # (peer, digest) pairs whose bytes are already accounted in
        # transferredBytes/dedupSkippedBytes: a chunk's bytes count at
        # most ONCE per peer across the primary and handoff passes, so
        # repeated handoff probes cannot double-count one transfer
        counted: set[tuple[int, str]] = set()

        def filter_credits() -> dict[int, set[str]]:
            """The filter positives each primary leg may credit
            unasked (docs/index.md §3), settled before a leg starts.
            A chunk this node does not own, which no leg would be sent
            or asked about, would be credited by every filter and
            stored nowhere this node can vouch for — and its payload
            leaves with the batch, so the pre-ack verify round could
            name two false positives but heal neither. The first leg
            that would credit such a chunk asks its peer instead, side
            by side with the other legs; the rest may credit it."""
            plane = self.index
            maybe: dict[int, set[str]] = {}
            if ledger is None or plane is None \
                    or plane.local_filter is None:
                return maybe
            cache = plane.echo_cache
            real = {d for d, _ in local_puts}
            for nid, wanted in per_node.items():
                if not self.health.is_alive(nid):
                    continue             # a corpse backs nothing
                if plane.peer_filters.state(nid) is None:
                    real.update(d for d, _ in wanted)    # all probed
                    continue
                for d, _ in wanted:
                    if cache is not None and cache.confirmed(nid, d) \
                            or plane.peer_filters.contains(nid, d) is False:
                        real.add(d)      # echo on record, or to be sent
                    else:
                        maybe.setdefault(nid, set()).add(d)
            for ds in maybe.values():
                asks = ds - real
                ds -= asks
                real |= asks
            return maybe

        async def replicate(node_id: int,
                            wanted: list[tuple[str, bytes]],
                            credit: Collection[str] = ()) -> None:
            # ``credit``: the filter positives this leg may take on
            # trust; a handoff leg is given none and asks about each
            peer = self.cfg.cluster.peer(node_id)
            # Known-dead peers get one fast probe instead of the full retry
            # envelope (health registry, SURVEY.md §5.3).
            retries = None if self.health.is_alive(node_id) else 1
            plane = self.index
            cache = plane.echo_cache if plane is not None else None
            trusted: set[str] = set()

            async def probe() -> tuple[list, set[str], list | None]:
                """This leg's existence check: what is still to be
                weighed after the echo cache, what the peer answered it
                has, and the payload slices staged meanwhile."""
                # echo-cache consult first (ISSUE 16 satellite): a
                # digest this peer hash-echo-confirmed THIS SESSION
                # under the current epoch is first-party evidence,
                # stronger than a bloom positive — credit the copy with
                # NO ledger entry, skipping the probe AND the pre-ack
                # verify round. Dead peers never qualify (same rule as
                # filter trust).
                remaining = wanted
                if cache is not None and retries is None:
                    echoed_skip = 0
                    remaining = []
                    for d, b in wanted:
                        if cache.confirmed(node_id, d):
                            echoed_skip += 1
                            copies[d] += 1
                            if (node_id, d) not in counted:
                                counted.add((node_id, d))
                                stats["dedupSkippedBytes"] += len(b)
                        else:
                            remaining.append((d, b))
                    if echoed_skip:
                        plane.echo_trusted += echoed_skip
                        plane.probes_skipped += echoed_skip
                filtered = False
                to_probe = remaining
                if plane is not None and plane.local_filter is not None \
                        and retries is None \
                        and plane.peer_filters.state(node_id) is not None:
                    filtered = True
                    ruled_out = 0
                    to_probe = []
                    for d, b in remaining:
                        if d in credit:
                            trusted.add(d)
                            copies[d] += 1
                            ledger.credit(node_id, d, len(b))
                            if (node_id, d) not in counted:
                                counted.add((node_id, d))
                                stats["dedupSkippedBytes"] += len(b)
                        elif plane.peer_filters.contains(
                                node_id, d) is False:
                            ruled_out += 1       # straight to transfer
                        else:
                            to_probe.append((d, b))
                    plane.probes_skipped += ruled_out + len(trusted)
                    plane.trusted += len(trusted)
                    if not to_probe and remaining:
                        plane.probe_rpcs_skipped += 1
                digests = [d for d, _ in to_probe]
                if plane is not None:
                    plane.place_considered += len(wanted)
                    plane.place_skipped += len(wanted) - len(digests)
                staged = None
                have: set[str] = set()
                if to_probe and not filtered:
                    # the has_chunks probe flies while the payload list
                    # is staged into bounded slices — fresh data rarely
                    # dedups, so the optimistic staging is usually
                    # final; a dedup hit restages only the missing
                    # remainder
                    call = asyncio.create_task(self.client.call(
                        peer, {"op": "has_chunks", "digests": digests,
                               "residentOk": True},
                        retries=retries))
                    try:
                        # staging runs on a worker thread so it is
                        # GENUINELY concurrent with the probe's RTT:
                        # the to_thread await yields the loop, which
                        # runs the probe task's send before (and while)
                        # the slicing executes — inline staging after
                        # create_task would still serialize ahead of
                        # the wire write
                        staged = await asyncio.to_thread(
                            self._slice_payloads, remaining,
                            self._REPLICA_SLICE_BYTES)
                        resp, _ = await call
                    except BaseException:
                        call.cancel()    # replicate cancelled/failed
                        raise            # first: don't orphan the probe
                    have = set(resp.get("have", []))
                elif to_probe:
                    # filter-trimmed probe: only what the filter could
                    # not rule out goes over the wire
                    resp, _ = await self.client.call(
                        peer, {"op": "has_chunks", "digests": digests,
                               "residentOk": True},
                        retries=retries)
                    have = set(resp.get("have", []))
                    for d in digests:
                        if d not in have:
                            # the filter said maybe, the peer says no:
                            # an OBSERVED false positive — counted, and
                            # overridden so a retry stops re-trusting
                            plane.peer_filters.note_fp(node_id, d)
                return remaining, have, staged

            try:
                # peer-filter consultation (docs/index.md): split this
                # peer's list into ruled-out (definitely absent —
                # transfer without probing), trusted (a filter
                # positive this leg was given to credit — probe AND
                # transfer skipped, verified pre-ack), and to-probe. A
                # dead peer's filter is never trusted (a stale summary
                # crediting copies on a corpse is exactly the phantom
                # the health registry exists to prevent); no replica of
                # the peer's filter = the pre-index path.
                with self.obs.span("upload.probe"):
                    remaining, have, staged = await probe()
                missing = [(d, b) for d, b in remaining
                           if d not in have and d not in trusted]
                for d, b in remaining:
                    if d in have:
                        # durable on the peer no matter what later
                        # slices do — credit the copy immediately
                        copies[d] += 1
                        if cache is not None:
                            cache.confirm(node_id, d)
                        if (node_id, d) not in counted:
                            counted.add((node_id, d))
                            stats["dedupSkippedBytes"] += len(b)
                            self.counters.inc("dedup_remote_hits")
                if missing:
                    # bounded RPCs: the receiver recomputes the hash echo
                    # of everything in one call before replying, so an
                    # unbounded payload turns into an unbounded server
                    # pass — a ~300 MB push under 1-core contention blew
                    # the request timeout and failed a whole 2 GiB-corpus
                    # upload below quorum; bounded slices keep each
                    # call's work (and any retry's re-send) small
                    slices = staged if staged is not None and not have \
                        else self._slice_payloads(
                            missing, self._REPLICA_SLICE_BYTES)

                    def make_on_slice(nid: int):
                        def on_slice(part: list[tuple[str, bytes]],
                                     echoed: list[str]) -> None:
                            # hash-echo verification per slice (reference
                            # contract, StorageNode.java:248-257) +
                            # per-slice crediting: a verified slice is
                            # durable on the peer even if a LATER slice
                            # fails — end-of-call crediting forgot
                            # delivered bytes on partial failure, and
                            # handoff re-transferred (and re-counted)
                            # them. The echo IS the session confirmation
                            # the echo cache keys on.
                            sent = {d for d, _ in part}
                            if sent & set(echoed) != sent:
                                raise RpcError(
                                    f"hash echo mismatch from node {nid}")
                            for d, b in part:
                                copies[d] += 1
                                if cache is not None:
                                    cache.confirm(nid, d)
                                if nid != node_id:
                                    # hedge-backup copy: durable but on
                                    # a non-canonical holder — queue it
                                    # for repair like a handoff copy
                                    self.under_replicated.add(d)
                                if (nid, d) not in counted:
                                    counted.add((nid, d))
                                    stats["transferredBytes"] += len(b)
                        return on_slice

                    # hedged write (ISSUE 16 satellite): under a hedge
                    # policy, race the slice train against a timer; if
                    # the primary stalls past the p~99 envelope, open a
                    # SECOND train to the next ring holder under the
                    # shared token budget. Content-addressed puts make
                    # the duplicate harmless — whichever copies land
                    # are real copies — and per-slice crediting under
                    # ``counted`` keeps the byte accounting exact.
                    backup_id = None
                    if self.serve.hedge is not None:
                        # first digest in the batch with a live third
                        # holder nominates the backup (the batch mixes
                        # owner sets; anchoring on missing[0] alone
                        # left whole trains unhedged on a coin flip)
                        for dg, _ in missing:
                            primaries = set(primary_targets(dg))
                            backup_id = next(
                                (t for t in handoff_ring(dg)
                                 if t != node_id
                                 and t != self.cfg.node_id
                                 and t not in primaries
                                 and self.health.is_alive(t)), None)
                            if backup_id is not None:
                                break
                    if backup_id is None:
                        peak = await self.client.store_chunks_windowed(
                            peer, file_id, slices,
                            window=self.cfg.ingest.slice_inflight,
                            on_slice=make_on_slice(node_id))
                        self.ingest_stalls.peak("sliceInflight", peak)
                    else:
                        await self._store_hedged(
                            node_id, backup_id, file_id, slices,
                            make_on_slice)
                self.health.mark_alive(node_id)
            except DeadlineExpired:
                # the caller's budget died, not the peer: abort the
                # upload as a 503-class refusal (see _place_batch's
                # gather) — swallowing it here would count every peer
                # as a replication failure and end in a quorum-fail 500
                # on a healthy cluster
                raise
            except RpcError as e:
                self.log.warning("replication to node %d failed: %s",
                                 node_id, e)
                self.counters.inc("replication_failures")
                if isinstance(e, RpcUnreachable):
                    # only transport-level exhaustion is liveness evidence;
                    # an application error came from a live peer
                    self.health.mark_dead(node_id)
                    if cache is not None:
                        # session confirmations were about THAT process;
                        # its successor re-earns them
                        cache.drop(node_id)

        with self.obs.span("upload.replicate", latency=True):
            try:
                credits = filter_credits()
                await gather_abort_siblings(
                    put_local(local_puts),
                    *(replicate(nid, w, credits.get(nid, ()))
                      for nid, w in per_node.items()))
            except OSError as e:
                self._raise_if_disk_full(e)
                raise
        if self.chaos is not None:
            self.chaos.maybe_crash("place.after_replicate")

        # Sloppy-quorum fallback (hinted handoff): chunks still below
        # quorum try the next nodes in their digest ring, so a dead
        # canonical target costs availability only when fewer than
        # ``write_quorum`` nodes in the WHOLE cluster are reachable. The
        # reference aborts the entire upload on ANY dead peer
        # (StorageNode.java:218-221); this keeps its >=2-copies durability
        # without its write-all fragility. Handoff copies are queued for
        # repair, which migrates them back to canonical placement.
        # Effective quorum: write_quorum can't exceed the copies placement
        # will ever make — rf (the policy) or the cluster size (a 1-node
        # cluster's single copy IS every copy in the world). Without the
        # clamp a legal `--nodes 1` deployment fails every upload.
        quorum = min(self.cfg.write_quorum, rf, len(ids))
        handoff: set[str] = set()
        next_try = {d: len(primary_targets(d))       # ring index per digest
                    for d in copies}
        with self.obs.span("upload.handoff", latency=True):
            while True:
                need = [d for d, n in copies.items() if n < quorum]
                if not need:
                    break
                groups: dict[int, list[tuple[str, bytes]]] = {}
                local_handoff: list[tuple[str, bytes]] = []
                progress = False
                for d in need:
                    order = handoff_ring(d)
                    if next_try[d] >= len(order):
                        continue                     # cluster exhausted
                    target = order[next_try[d]]
                    next_try[d] += 1
                    progress = True
                    handoff.add(d)
                    if target == self.cfg.node_id:
                        local_handoff.append((d, payload_of[d]))
                        copies[d] += 1   # local copy counts even on dedup
                    else:
                        groups.setdefault(target, []).append(
                            (d, payload_of[d]))
                if not progress:
                    break
                jobs = []
                if local_handoff:
                    # count_dedup=False: the handoff path never counted
                    # a local dedup hit (the copy was credited above)
                    jobs.append(put_local(local_handoff,
                                          count_dedup=False))
                jobs.extend(replicate(nid, w)
                            for nid, w in groups.items())
                if jobs:
                    try:
                        await gather_abort_siblings(*jobs)
                    except OSError as e:
                        self._raise_if_disk_full(e)
                        raise

        # Write-quorum policy (vs reference write-all abort, :218-221).
        failed = [d for d, n in copies.items() if n < quorum]
        if failed:
            # journaled: a quorum failure is the write path's loudest
            # lifecycle event and the HTTP 500 it becomes carries no
            # cluster state — the flight recorder keeps the evidence
            self.obs.event("quorum_fail", chunksBelow=len(failed),
                           quorum=quorum)
            raise UploadError(
                f"Replication failed: {len(failed)} chunks below quorum "
                f"{quorum}")
        for d, n in copies.items():
            if n < rf or d in handoff:
                self.under_replicated.add(d)
        batch_min = min(copies.values(), default=rf)
        stats["minCopies"] = batch_min if stats["minCopies"] is None \
            else min(stats["minCopies"], batch_min)
        stats["handoffChunks"] += len(handoff)
        stats["degraded"] = stats["degraded"] or bool(
            handoff or any(n < rf for n in copies.values()))

    async def _store_hedged(self, primary_id: int, backup_id: int,
                            file_id: str,
                            slices: list[list[tuple[str, bytes]]],
                            make_on_slice) -> None:
        """Hedged replication store (ISSUE 16 satellite, the write-side
        twin of :meth:`_hedged_get_chunks`): send the slice train to the
        primary; if it outlives the latency-derived hedge delay and the
        shared token bucket allows, open a SECOND train of the same
        slices to ``backup_id``. Content-addressed puts make the
        duplicate inherently safe — every hash-echo-verified slice is a
        real durable copy wherever it landed, credited through the
        caller's ``counted`` discipline — so unlike the read side there
        is no result to pick: success of EITHER train completes the
        call, and a loser cancelled mid-flight keeps the slices it
        already landed. Exceptions propagate only when both trains fail
        (the primary's error class, so the caller's health handling
        stays aimed at the peer it chose)."""
        hedge = self.serve.hedge
        window = self.cfg.ingest.slice_inflight

        async def issue(nid: int):
            return await self.client.store_chunks_windowed(
                self.cfg.cluster.peer(nid), file_id, slices,
                window=window, on_slice=make_on_slice(nid))

        task = asyncio.create_task(issue(primary_id))
        btask: asyncio.Task | None = None

        async def reap_on_cancel() -> None:
            # our caller was cancelled: the trains must die with it —
            # shield/asyncio.wait leave their tasks running detached
            # otherwise, and an unretrieved RpcError would log
            # 'exception was never retrieved' at GC
            task.cancel()
            if btask is not None:
                btask.cancel()
            await asyncio.gather(task,
                                 *([btask] if btask is not None
                                   else []),
                                 return_exceptions=True)

        delay = hedge.delay_s(
            self.obs.rpc_client.recent_best_mean("store_chunks"))
        try:
            peak = await asyncio.wait_for(asyncio.shield(task), delay)
            self.ingest_stalls.peak("sliceInflight", peak)
            return
        # absence-as-result: the timeout IS the hedge trigger — the
        # shielded primary keeps running and is raced below
        except asyncio.TimeoutError:  # dfslint: ignore[DFS007]
            pass                        # primary still in flight: hedge
        except asyncio.CancelledError:
            await reap_on_cancel()
            raise
        except BaseException:
            raise                       # primary failed fast — the
            # caller's RpcUnreachable/RpcError handling applies as-is
        if not hedge.take():
            try:
                peak = await task
            except asyncio.CancelledError:
                await reap_on_cancel()   # awaiting a Task does not
                raise                    # cancel it — reap explicitly
            self.ingest_stalls.peak("sliceInflight", peak)
            return
        hedge.note_fired()
        self.obs.event("hedge_fired", op="store_chunks",
                       primary=primary_id, backup=backup_id,
                       slices=len(slices), delayS=round(delay, 4))
        btask = asyncio.create_task(issue(backup_id))
        try:
            done, _ = await asyncio.wait(
                {task, btask}, return_when=asyncio.FIRST_COMPLETED)
        except asyncio.CancelledError:
            await reap_on_cancel()
            raise
        first, other = (task, btask) if task in done else (btask, task)
        first_id, other_id = (primary_id, backup_id) if first is task \
            else (backup_id, primary_id)
        ferr = first.exception()
        if ferr is None:
            other.cancel()
            try:
                await other
            except (asyncio.CancelledError, RpcError, WireError):  # dfslint: ignore[DFS007]
                pass    # reaped: the winner's train already landed
            if not other.cancelled() \
                    and isinstance(other.exception(), RpcUnreachable):
                self.health.mark_dead(other_id)
            if first_id == backup_id:
                hedge.note_won()
                self.obs.event("hedge_won", op="store_chunks",
                               primary=primary_id, backup=backup_id)
            else:
                self.ingest_stalls.peak("sliceInflight", first.result())
            return
        # first train failed: fall to the other side — no third train
        if isinstance(ferr, RpcUnreachable):
            self.health.mark_dead(first_id)
        try:
            await other
        except asyncio.CancelledError:
            await reap_on_cancel()       # the train must die with us
            raise
        except (RpcError, WireError) as e:
            # both failed: surface the PRIMARY's failure class so the
            # caller's diagnosis targets the peer it actually chose
            raise (ferr if first_id == primary_id else e) from None
        if other_id == backup_id:
            hedge.note_won()
            self.obs.event("hedge_won", op="store_chunks",
                           primary=primary_id, backup=backup_id)

    def _new_trust_ledger(self) -> _TrustLedger | None:
        """A trust ledger when the filter plane is on, else None (the
        pre-index placement path, probe per batch per peer)."""
        if self.index is not None and self.index.local_filter is not None:
            return _TrustLedger()
        return None

    async def _verify_trusted(self, file_id: str, ledger: _TrustLedger,
                              stats: dict, rf: int | None = None,
                              placement: Mapping[str, tuple[int, ...]]
                              | None = None) -> None:
        """Confirm every filter-credited copy with ONE real has_chunks
        round per peer — the pre-ack half of the probe-skipping
        placement (docs/index.md). Runs after the last batch placed and
        BEFORE the manifest write that acks the upload, so a bloom
        false positive (or a peer that died between trust and verify)
        costs a heal — re-fetching the bytes and re-placing them
        through the normal batch path — never an ack backed by a
        phantom copy. Observed FPs are counted (``index.filterFp``)
        and overridden per peer, so a deterministic bloom collision
        cannot wedge a retry loop into trusting the same phantom
        forever."""
        plane = self.index
        assert plane is not None
        unconfirmed: dict[str, int] = {}
        with self.obs.span("upload.verify_trusted", latency=True):
            for node_id, entries in sorted(ledger.by_peer.items()):
                peer = self.cfg.cluster.peer(node_id)
                digests = sorted(entries)
                try:
                    resp, _ = await self.client.call(
                        peer, {"op": "has_chunks", "digests": digests,
                               "residentOk": True})
                    self.health.mark_alive(node_id)
                except RpcError as e:
                    # the peer answered the filter sync but not the
                    # verify: every credit it granted is unconfirmed —
                    # NOT a false positive (the filter made no mistake;
                    # the peer is sick), so no FP count/override
                    if isinstance(e, RpcUnreachable):
                        self.health.mark_dead(node_id)
                        if plane.echo_cache is not None:
                            plane.echo_cache.drop(node_id)
                    self.counters.inc("index_verify_failures")
                    for d in digests:
                        stats["dedupSkippedBytes"] -= entries[d]
                        unconfirmed.setdefault(d, entries[d])
                    continue
                have = set(resp.get("have", []))
                for d in digests:
                    if d not in have:
                        plane.peer_filters.note_fp(node_id, d)
                        stats["dedupSkippedBytes"] -= entries[d]
                        unconfirmed.setdefault(d, entries[d])
                    elif plane.echo_cache is not None:
                        # the verify round is first-party evidence too:
                        # future re-uploads this session skip straight
                        # past both the probe and the verify
                        plane.echo_cache.confirm(node_id, d)
        if not unconfirmed:
            return
        # heal pre-ack: re-fetch the bytes (local CAS first — this node
        # is usually a holder — then any replica) and re-place through
        # the normal batch path with NO ledger: real holders dedup, the
        # phantom target receives an actual transfer (its FP override
        # stops the filter from re-trusting), dead targets fall to
        # handoff, and the quorum check re-runs for exactly these
        # digests. Bytes that survive nowhere reachable fail the upload
        # loudly — the ack was never given.
        self.obs.event("filter_fp_replace", chunks=len(unconfirmed))
        items: list[tuple[str, bytes]] = []
        local = dict(await self.cas.get_many(sorted(unconfirmed)))
        for d, ln in sorted(unconfirmed.items()):
            b = local.get(d)
            if b is None:
                try:
                    b = await self._fetch_chunk(d, ln)
                except DeadlineExceeded:
                    raise          # budget died: 503-class, never a
                    # "held nowhere reachable" 500
                except DownloadError:
                    raise UploadError(
                        f"filter-credited chunk {d[:12]}… held nowhere "
                        "reachable — retry the upload (the filter "
                        "override now forces a real transfer)")
            items.append((d, b))
        await self._place_batch(file_id, items, stats, rf=rf,
                                placement=placement)

    @_spanned("upload.commit")
    async def _finalize_upload(self, manifest: Manifest) -> None:
        # Manifest-last ordering (SURVEY.md §5.4), then best-effort announce
        # (reference: announce failure only logged, StorageNode.java:338-346).
        # A fresh upload clears tombstones (locally and via fresh=True at
        # peers): re-uploading deleted content must resurrect the
        # content-derived file id, not leave it permanently undownloadable.
        # The save runs off-loop: with fsync durability it is a disk
        # BARRIER (file + dir), and this is the write that acks the
        # upload — the one moment the loop must not eat a barrier.
        if self.chaos is not None:
            self.chaos.maybe_crash("upload.before_manifest")
        self.store.manifests.clear_tombstone(manifest.file_id)
        try:
            saved = await asyncio.to_thread(self.store.manifests.save,
                                            manifest)
        except OSError as e:
            self._raise_if_disk_full(e)
            raise
        if not saved:
            raise UploadError("manifest save refused (tombstone race)")
        if self.chaos is not None:
            self.chaos.maybe_crash("upload.after_manifest")
        mj = manifest.to_json()          # once, not once per recipient

        async def announce(peer) -> None:
            try:
                await self.client.announce(peer, mj, fresh=True)
            except RpcError as e:
                self.log.warning("announce to node %d failed: %s",
                                 peer.node_id, e)
                self.counters.inc("announce_failures")

        await asyncio.gather(*(announce(p) for p in self._peers()))
        self.counters.inc("uploads")

    # ------------------------------------------------------------------ #
    # download (L4) — reference handleDownload, StorageNode.java:399-461
    # ------------------------------------------------------------------ #

    async def _fetch_chunk(self, digest: str, length: int) -> bytes:
        # local read through the bounded CAS pool — never inline on the
        # event loop (same rule every other chunk-file touch follows)
        data = await self.cas.get(digest)
        if data is not None:
            return data
        rf = self.cfg.cluster.replication_factor
        # current-epoch owners first, then previous-epoch owners (the
        # dual-read migration window: mid-rebalance the bytes may not
        # have reached their new home yet — docs/membership.md)
        candidates = [t for t in self.ring.read_candidates(digest, rf)
                      if t != self.cfg.node_id]
        # try believed-alive replicas first; dead ones remain as last resort
        candidates.sort(key=lambda t: not self.health.is_alive(t))
        # then every OTHER peer in the ADDRESS BOOK (alive-first too),
        # not just active ring members: handoff copies and stale
        # placement can park bytes on a node that has since been
        # drained (weight 0) or removed from the ring — it is still
        # reachable and may hold the only surviving copy. A known-dead
        # peer ahead of a live holder would cost a connect timeout per
        # chunk, hence the alive-first sort.
        candidates += sorted(
            (t for t in self.cfg.cluster.sorted_ids()
             if t != self.cfg.node_id and t not in candidates),
            key=lambda t: not self.health.is_alive(t))
        if self.serve.hedge is not None:
            # hedged reads (docs/serve.md): same candidate walk, but a
            # primary that outlives its latency-derived hedge delay
            # races the NEXT replica — first verified answer wins
            return await self._fetch_chunk_hedged(digest, length,
                                                  candidates)
        for target in candidates:
            try:
                data = await self.client.get_chunk(
                    self.cfg.cluster.peer(target), digest)
                self.health.mark_alive(target)
            except DeadlineExpired as e:
                # the budget died, not the replicas: stop the walk —
                # touring the remaining candidates would count each
                # refusal as a remote miss (placement-skew evidence)
                # and waste exactly the work the deadline forbids
                raise DeadlineExceeded(str(e)) from e
            except RpcUnreachable:
                self.health.mark_dead(target)
                continue
            except RpcError:
                # live peer without the chunk — not a death signal, but
                # counted (DFS007): a ring walk that keeps missing is
                # placement skew the terminal DownloadError hides
                self.counters.inc("remote_chunk_misses")
                continue
            # Verify against the manifest digest before trusting a peer
            # (stronger than the reference, which only checks the whole file).
            if len(data) == length and sha256_hex(data) == digest:
                self.counters.inc("chunks_fetched_remote")
                if self.ring.is_prev_only(digest, target, rf):
                    # served through the dual-read window: the byte
                    # came from a previous-epoch owner mid-move
                    self.ring.note_dual_read_hit()
                return data
            self.log.warning("corrupt chunk %s from node %d",
                             digest[:12], target)
        raise DownloadError(f"Could not retrieve chunk {digest[:12]}…")

    async def _fetch_chunk_hedged(self, digest: str, length: int,
                                  candidates: list[int]) -> bytes:
        """The hedged-read walk of :meth:`_fetch_chunk` ("The Tail at
        Scale"): a primary replica that has not answered within
        ``HedgePolicy.delay_s`` of ITS OWN windowed mean latency races
        the next replica in the (dual-read/ring-aware) candidate order;
        the first VERIFIED answer wins, the loser is cancelled, and
        every hedge draws from the node's token bucket so hedging can
        never double cluster fetch load. The per-replica outcome
        handling (health marks, miss counters, digest verification) is
        the serial walk's, verbatim — a hedge changes WHEN the next
        replica is asked, never what counts as an answer. Coalesced
        readers (serve/rpc single-flight) share the leader's hedge
        decision by construction: the hedge fires inside the one flight
        they all await."""
        hedge = self.serve.hedge
        rf = self.cfg.cluster.replication_factor

        async def attempt(nid: int) -> bytes | None:
            """One replica's verified bytes, or None — miss, corrupt,
            or dead, with exactly the serial walk's bookkeeping."""
            try:
                data = await self.client.get_chunk(
                    self.cfg.cluster.peer(nid), digest)
                self.health.mark_alive(nid)
            except DeadlineExpired as e:
                raise DeadlineExceeded(str(e)) from e  # stop the walk
            except RpcUnreachable:
                self.health.mark_dead(nid)
                return None
            except RpcError:
                # live peer without the chunk — not a death signal (see
                # _fetch_chunk; counted for placement-skew visibility)
                self.counters.inc("remote_chunk_misses")
                return None
            if len(data) == length and sha256_hex(data) == digest:
                return data
            self.log.warning("corrupt chunk %s from node %d",
                             digest[:12], nid)
            return None

        def accept(data: bytes, src: int) -> bytes:
            self.counters.inc("chunks_fetched_remote")
            if self.ring.is_prev_only(digest, src, rf):
                self.ring.note_dual_read_hit()
            return data

        i = 0
        while i < len(candidates):
            nid = candidates[i]
            backup_id = candidates[i + 1] if i + 1 < len(candidates) \
                else None
            if backup_id is None:
                data = await attempt(nid)
                if data is not None:
                    return accept(data, nid)
                i += 1
                continue
            task = asyncio.create_task(attempt(nid))
            btask: asyncio.Task | None = None
            try:
                # delay seeded by the BEST replica's windowed mean, not
                # the primary's own (RpcStats.recent_best_mean: a slow
                # primary's samples would talk its own hedge out of
                # firing)
                delay = hedge.delay_s(
                    self.obs.rpc_client.recent_best_mean("get_chunk"))
                try:
                    data = await asyncio.wait_for(asyncio.shield(task),
                                                  delay)
                # absence-as-result: the timeout IS the hedge trigger —
                # the shielded primary keeps running, awaited below
                except asyncio.TimeoutError:  # dfslint: ignore[DFS007]
                    data = None
                if task.done():
                    # the primary answered (or failed fast) within the
                    # delay: no hedge — exactly the serial walk's step
                    if data is None:
                        data = task.result()
                    if data is not None:
                        return accept(data, nid)
                    i += 1
                    continue
                if not hedge.take():
                    # budget empty: wait the primary out (hedging must
                    # never become its own overload — the denial is
                    # counted and windowed for the doctor's
                    # hedge_storm)
                    data = await task
                    if data is not None:
                        return accept(data, nid)
                    i += 1
                    continue
                hedge.note_fired()
                self.obs.event("hedge_fired", digest=digest[:12],
                               primary=nid, backup=backup_id,
                               delayS=round(delay, 4))
                btask = asyncio.create_task(attempt(backup_id))
                done, _ = await asyncio.wait(
                    {task, btask}, return_when=asyncio.FIRST_COMPLETED)
                first, other = (task, btask) if task in done \
                    else (btask, task)
                first_id, other_id = (nid, backup_id) if first is task \
                    else (backup_id, nid)
                data = first.result()      # attempt() raises only
                # DeadlineExceeded (reaped by the handler below)
                src = first_id
                if data is None:
                    # first finisher missed/failed: the race collapses
                    # to waiting on the other — no third fetch issued
                    data = await other
                    src = other_id
                else:
                    other.cancel()         # loser cancelled
                    with contextlib.suppress(asyncio.CancelledError):
                        await other
            except (asyncio.CancelledError, DeadlineExceeded):
                # OUR caller was cancelled (client hung up mid-read) or
                # the deadline died mid-race: the racers must die with
                # it — shield/asyncio.wait leave their tasks running
                # detached otherwise, still transferring bytes for a
                # reader that is gone
                task.cancel()
                if btask is not None:
                    btask.cancel()
                await asyncio.gather(task,
                                     *([btask] if btask is not None
                                       else []),
                                     return_exceptions=True)
                raise
            if data is not None:
                if src == backup_id:
                    hedge.note_won()
                    self.obs.event("hedge_won", digest=digest[:12],
                                   primary=nid, backup=backup_id)
                return accept(data, src)
            i += 2                         # both replicas consumed
        raise DownloadError(f"Could not retrieve chunk {digest[:12]}…")

    async def _hedged_get_chunks(self, primary_id: int, backup_id: int,
                                 digests: list[str], expect: int
                                 ) -> tuple[list, int]:
        """Hedged batched fetch (docs/serve.md): issue ``get_chunks``
        to the primary; if it outlives its latency-derived hedge delay
        and the token bucket allows, race the SAME batch against the
        backup replica — first completed reply wins, loser cancelled.
        Returns ``(pairs, winner_id)``; exceptions propagate only when
        BOTH sides fail (attributed to the primary — the caller's
        health/error handling stays aimed at the peer it chose), so a
        hedge can only ever improve on the unhedged call."""
        hedge = self.serve.hedge

        async def issue(nid: int):
            return await self.client.get_chunks(
                self.cfg.cluster.peer(nid), digests,
                retries=None if self.health.is_alive(nid) else 1,
                expect_bytes=expect)

        task = asyncio.create_task(issue(primary_id))
        btask: asyncio.Task | None = None

        async def reap_on_cancel() -> None:
            """OUR caller was cancelled: the racers must die with it —
            shield/asyncio.wait leave their tasks running detached
            otherwise (up to two ~32 MiB transfers for a reader that
            is gone), and an unretrieved RpcError would log 'exception
            was never retrieved' at GC."""
            task.cancel()
            if btask is not None:
                btask.cancel()
            await asyncio.gather(task,
                                 *([btask] if btask is not None
                                   else []),
                                 return_exceptions=True)

        # best-replica seed, not the primary's own mean — see
        # RpcStats.recent_best_mean for the observed failure mode
        delay = hedge.delay_s(
            self.obs.rpc_client.recent_best_mean("get_chunks"))
        try:
            return await asyncio.wait_for(asyncio.shield(task),
                                          delay), primary_id
        # absence-as-result: the timeout IS the hedge trigger — the
        # shielded primary keeps running and is raced below
        except asyncio.TimeoutError:  # dfslint: ignore[DFS007]
            pass                        # primary still in flight: hedge
        except asyncio.CancelledError:
            await reap_on_cancel()
            raise
        except BaseException:
            raise                       # primary failed fast — the
            # caller's RpcUnreachable/RpcError handling applies as-is
        if not hedge.take():
            try:
                return await task, primary_id
            except asyncio.CancelledError:
                await reap_on_cancel()   # awaiting a Task does not
                raise                    # cancel it — reap explicitly
        hedge.note_fired()
        self.obs.event("hedge_fired", op="get_chunks",
                       primary=primary_id, backup=backup_id,
                       chunks=len(digests), delayS=round(delay, 4))
        btask = asyncio.create_task(issue(backup_id))
        try:
            done, _ = await asyncio.wait(
                {task, btask}, return_when=asyncio.FIRST_COMPLETED)
        except asyncio.CancelledError:
            await reap_on_cancel()
            raise
        first, other = (task, btask) if task in done else (btask, task)
        first_id, other_id = (primary_id, backup_id) if first is task \
            else (backup_id, primary_id)
        ferr = first.exception()
        if ferr is None:
            # loser cancelled; if it had already failed unreachable,
            # keep the evidence (the health registry would learn it
            # from the next probe anyway — this is just sooner)
            other.cancel()
            try:
                await other
            except (asyncio.CancelledError, RpcError, WireError):  # dfslint: ignore[DFS007]
                pass    # reaped: the winner's reply is the result
            if not other.cancelled() \
                    and isinstance(other.exception(), RpcUnreachable):
                self.health.mark_dead(other_id)
            if first_id == backup_id:
                hedge.note_won()
                self.obs.event("hedge_won", op="get_chunks",
                               primary=primary_id, backup=backup_id)
            return first.result(), first_id
        # first finisher failed: fall to the other side — no third RPC
        if isinstance(ferr, RpcUnreachable):
            self.health.mark_dead(first_id)
        try:
            got = await other
        except asyncio.CancelledError:
            await reap_on_cancel()       # the racer must die with us
            raise
        except (RpcError, WireError) as e:
            # both failed: surface the PRIMARY's failure class so the
            # caller's diagnosis targets the peer it actually chose
            raise (ferr if first_id == primary_id else e) from None
        if other_id == backup_id:
            hedge.note_won()
            self.obs.event("hedge_won", op="get_chunks",
                           primary=primary_id, backup=backup_id)
        return got, other_id

    _FETCH_BATCH_BYTES = 32 * 1024 * 1024
    _PROBE_SLICE_DIGESTS = 2048   # digests per repair has_chunks call

    async def _gather_chunks(self, manifest: Manifest | None,
                             chunks=None, strict: bool = True,
                             prefetched: dict[str, bytes] | None = None,
                             ec_fallback: bool = True
                             ) -> dict[str, bytes]:
        """Collect chunks (default: all of the manifest's): local first,
        then BATCHED remote fetches grouped by preferred replica holder
        (one RPC per ~32 MiB of chunks per peer — the per-chunk op costs
        a round-trip per chunk and dominated degraded reads), with the
        per-chunk replica-fallback path (:meth:`_fetch_chunk`) mopping up
        anything a peer turned out not to hold. Returns digest ->
        verified bytes; ``strict=False`` skips unrecoverable chunks
        instead of raising (repair's best-effort restore); ``prefetched``
        carries bytes the caller already read+verified (skips the local
        disk read)."""
        need: dict[str, int] = {}
        for c in (manifest.chunks if chunks is None else chunks):
            need.setdefault(c.digest, c.length)
        out: dict[str, bytes] = {}
        for d in list(need):
            b = (prefetched or {}).get(d)
            if b is not None:
                out[d] = b
                del need[d]
        if need:
            # local reads batched through the async CAS tier: one
            # bounded-pool job instead of one inline open/read per chunk
            # on the event loop
            for d, b in await self.cas.get_many(list(need)):
                out[d] = b
                del need[d]
        if not need:
            return out

        ring = self.ring
        rf = self.cfg.cluster.replication_factor
        # EC manifests pin shards to stripe-derived holders, not the
        # digest ring — group fetches by the real holder or every round
        # asks the wrong peers and falls through to the slow has_chunks
        # sweep. Mid-migration the PREVIOUS epoch's pinned holders join
        # the candidate walk (dual-read window).
        pref = ec_placement_map(manifest, ring.current) \
            if manifest is not None and manifest.ec is not None else {}
        pref_prev = ec_placement_map(manifest, ring.previous) \
            if pref and ring.previous is not None else {}

        def candidates_for(d: str) -> Sequence[int]:
            pinned = pref.get(d)
            if pinned:
                # pinned + the handoff continuation: a shard that
                # sloppy-quorum handoff placed on a non-pinned node is
                # findable by the batched rounds (the write side walked
                # this same order), not only by the cluster-wide sweep
                out = ring.handoff_order(pinned)
                prev_pin = pref_prev.get(d)
                if prev_pin:
                    out = list(dict.fromkeys(
                        list(out) + list(prev_pin)))
                return out
            # current owners + previous-epoch owners (dual-read window)
            return ring.read_candidates(d, rf)

        def group_remaining(exclude: set[int]) -> dict[int, list[str]]:
            """Missing digests grouped by their first believed-alive
            replica holder (excluding peers that just failed a batch)."""
            groups: dict[int, list[str]] = {}
            for d in need:
                if d in out:
                    continue
                cands = [t for t in candidates_for(d)
                         if t != self.cfg.node_id and t not in exclude]
                cands.sort(key=lambda t: not self.health.is_alive(t))
                if cands:
                    groups.setdefault(cands[0], []).append(d)
            return groups

        async def fetch_batches(node_id: int, digests: list[str]) -> None:
            peer = self.cfg.cluster.peer(node_id)
            batch: list[str] = []
            size = 0

            async def flush() -> None:
                nonlocal batch, size
                if not batch:
                    return
                # hedge target for this batch (docs/serve.md): the most
                # common next-replica among the batch's digests — for
                # the dominant case (one slow primary, ring-adjacent
                # replica sets) every digest agrees; digests the backup
                # happens to lack stay missing and the mop-up rounds
                # fetch them, exactly as for any partial reply
                backup_id = None
                if self.serve.hedge is not None:
                    votes: dict[int, int] = {}
                    for d in batch:
                        for t in candidates_for(d):
                            if t != node_id and t != self.cfg.node_id:
                                votes[t] = votes.get(t, 0) + 1
                                break
                    if votes:
                        backup_id = max(votes, key=votes.get)
                src = node_id
                try:
                    # known-dead peers get one fast probe, not the full
                    # retry envelope (same rule replication uses) — a
                    # degraded EC read would otherwise pay retries per
                    # batch for holders that died
                    if backup_id is not None:
                        got, src = await self._hedged_get_chunks(
                            node_id, backup_id, list(batch),
                            sum(need[d] for d in batch))
                    else:
                        got = await self.client.get_chunks(
                            peer, batch,
                            retries=None
                            if self.health.is_alive(node_id) else 1,
                            expect_bytes=sum(need[d] for d in batch))
                    self.health.mark_alive(src)
                except DeadlineExpired as e:
                    # the budget died, not the peer: abort the gather
                    # (503-class) instead of regrouping onto the next
                    # replica and polluting the miss/error counters
                    raise DeadlineExceeded(str(e)) from e
                except RpcUnreachable:
                    self.health.mark_dead(node_id)
                    got = []
                except (RpcError, WireError) as e:
                    # WireError: peer sent a malformed chunk table — as
                    # recoverable as corrupt bytes; other replicas serve.
                    # Counted (DFS007): a byzantine peer that keeps
                    # sending garbage must not stay invisible just
                    # because its replicas covered for it.
                    self.counters.inc("fetch_batch_errors")
                    self.log.warning("batched fetch from node %d failed:"
                                     " %s: %s", node_id,
                                     type(e).__name__, e)
                    got = []
                if got:
                    hexes = sha256_many_hex([b for _, b in got])
                    for (d, b), h in zip(got, hexes):
                        # verify against the requested digest before
                        # trusting a peer (per-chunk integrity, stronger
                        # than the reference's whole-file-only check);
                        # `d not in out` keeps a racing batch from
                        # double-counting a chunk another peer delivered
                        if (d in need and d not in out and h == d
                                and len(b) == need[d]):
                            out[d] = b
                            self.counters.inc("chunks_fetched_remote")
                            if ring.migrating and ring.is_prev_only(
                                    d, src, rf):
                                ring.note_dual_read_hit()
                batch, size = [], 0

            for d in digests:
                batch.append(d)
                size += need[d]
                if size >= self._FETCH_BATCH_BYTES:
                    await flush()
            await flush()

        # up to rf batched rounds: a dead/lacking peer's chunks regroup
        # onto the next replica in ring order instead of dropping straight
        # to one-RPC-per-chunk (which made degraded reads ~2x slower)
        tried: set[int] = set()
        for _ in range(rf):
            groups = group_remaining(tried)
            if not groups:
                break
            await asyncio.gather(*(fetch_batches(nid, ds)
                                   for nid, ds in groups.items()))
            tried.update(groups)

        # straggler mop-up stays BATCHED: up to rf more rounds, each
        # assigning every missing digest to exactly ONE replica candidate
        # (round r -> r-th candidate) so no chunk's bytes cross the wire
        # from two peers at once. The rounds above only ever ask a
        # digest's first-choice holder (and exclude a peer cluster-wide
        # once tried), so a peer that answered a batch but lacked a few
        # chunks leaves those here — previously a serial
        # one-RPC-per-chunk walk.
        for r in range(rf):
            missing = [d for d in need if d not in out]
            if not missing:
                break
            by_peer: dict[int, list[str]] = {}
            for d in missing:
                cands = [t for t in candidates_for(d)
                         if t != self.cfg.node_id]
                if cands:
                    by_peer.setdefault(cands[min(r, len(cands) - 1)],
                                       []).append(d)
            if not by_peer:
                break
            await asyncio.gather(*(fetch_batches(nid, ds)
                                   for nid, ds in by_peer.items()))

        # cluster-wide fallback: after a MEMBERSHIP CHANGE the mod-N
        # replica sets remap wholesale while the bytes still sit on the
        # old holders until repair migrates them. One cheap batched
        # has_chunks to every peer finds the actual holders, then one
        # batched fetch per claiming peer — no duplicate payload
        # transfer, and reads stay correct throughout a rebalance.
        missing = [d for d in need if d not in out]
        if missing:
            claims: dict[str, int] = {}

            async def who_has(nid: int) -> None:
                try:
                    resp, _ = await self.client.call(
                        self.cfg.cluster.peer(nid),
                        {"op": "has_chunks", "digests": missing},
                        retries=1)
                    for d in resp.get("have", []):
                        claims.setdefault(d, nid)
                except DeadlineExpired as e:
                    raise DeadlineExceeded(str(e)) from e
                except RpcError:
                    # best-effort sweep; counted (DFS007) — habitual
                    # probe failures silently shrink the replica set a
                    # degraded read can draw from
                    self.counters.inc("probe_failures")

            others = [p.node_id for p in self._peers()]
            await asyncio.gather(*(who_has(n) for n in others))
            groups2: dict[int, list[str]] = {}
            for d, nid in claims.items():
                groups2.setdefault(nid, []).append(d)
            if groups2:
                await asyncio.gather(*(fetch_batches(nid, ds)
                                       for nid, ds in groups2.items()))

        # terminal per-chunk path: only chunks NO reachable peer produced
        # valid bytes for reach here — walks candidates once more, then
        # raises (strict) or skips (repair's best-effort). EC manifests
        # skip the re-walk: the batched rounds + cluster-wide sweep above
        # already asked every peer, and the next stop is parity decode —
        # a per-chunk tour of dead holders measured ~0.5 s/chunk on a
        # degraded real-process cluster, pure waste before a decode.
        missing = [d for d in need if d not in out]
        is_ec = manifest is not None and manifest.ec is not None
        if missing and not is_ec:
            sem = asyncio.Semaphore(8)

            async def one(d: str) -> None:
                async with sem:
                    try:
                        out[d] = await self._fetch_chunk(d, need[d])
                    except DeadlineExceeded:
                        raise          # dead budget ends the read —
                        # never "chunk missing"
                    # not silent: the digest stays missing and the strict
                    # raise / best-effort skip below carries the failure
                    except DownloadError:  # dfslint: ignore[DFS007]
                        pass

            await asyncio.gather(*(one(d) for d in missing))
            missing = [d for d in need if d not in out]
        if missing and is_ec and ec_fallback:
            # no copy of the shard survives anywhere reachable — the
            # erasure parity exists exactly for this moment
            await self._ec_recover(manifest, set(missing), out)
            missing = [d for d in need if d not in out]
        if missing and strict:
            raise DownloadError(
                f"Could not retrieve chunk {missing[0][:12]}…")
        return out

    async def _ec_recover(self, manifest: Manifest, wanted: set[str],
                          out: dict[str, bytes]) -> None:
        """Rebuild lost shards of an EC manifest from their stripe-mates
        (ops.ec P+Q decode). The surviving shards of EVERY affected
        stripe are fetched in ONE batched gather (non-strict, decode
        disabled — no recursion), then each stripe decodes, digest-
        verifies, and adds its wanted bytes to ``out``. Lost parity
        shards are re-encoded from recovered data. Stripes beyond the
        two-erasure budget are skipped (the caller decides whether that
        is fatal). Batching matters: a per-stripe fetch loop measured
        ~0.8 s/stripe on a two-nodes-dead real-process cluster (every
        stripe re-paying the dead-holder probes) — 53 s for a 2 MB
        file; one gather amortizes the probing across all stripes."""
        import numpy as np

        from dfs_tpu.ops import ec as ec_ops

        ec = manifest.ec
        assert ec is not None
        groups = ec_stripe_groups(manifest.chunks, ec.k)
        affected = [
            (s, st, grp)
            for s, (st, grp) in enumerate(zip(ec.stripes, groups))
            if wanted.intersection([c.digest for c in grp]
                                   + [st.p, st.q])]
        # `wanted` digests were JUST proven unreachable by the caller's
        # gather — re-fetching them would repeat the dead-holder probes
        # and the cluster-wide sweep per degraded read
        fetch: dict[str, ChunkRef] = {}
        for s, st, grp in affected:
            for c in grp:
                if c.digest not in out and c.digest not in wanted:
                    fetch.setdefault(c.digest, ChunkRef(
                        index=0, offset=0, length=c.length,
                        digest=c.digest))
            for d in (st.p, st.q):
                if d not in out and d not in wanted:
                    fetch.setdefault(d, ChunkRef(
                        index=0, offset=0, length=st.shard_len, digest=d))
        have = dict(out)
        if fetch:
            got = await self._gather_chunks(
                manifest, chunks=list(fetch.values()), strict=False,
                ec_fallback=False)
            have.update(got)
        def padded(d: str, ln: int, shard_len: int) -> np.ndarray | None:
            # `out` first: a digest shared between stripes (in-file
            # dedup) may have been recovered by an earlier batch of
            # this very pass — the pre-fetch snapshot would still
            # count it lost and push the stripe past the P+Q budget
            b = out.get(d)
            if b is None:
                b = have.get(d)
            if b is None or len(b) != ln:
                return None
            if ln == shard_len:
                # common case (every shard except a stripe's tail):
                # zero-copy view — recover_stripes only reads its
                # inputs, and the padded-copy here measured a full
                # extra pass over the corpus per degraded read
                return np.frombuffer(b, dtype=np.uint8)
            arr = np.zeros(shard_len, dtype=np.uint8)
            arr[:ln] = np.frombuffer(b, dtype=np.uint8)
            return arr

        # All affected stripes decode in ONE vectorized batch
        # (ec_ops.recover_stripes) instead of a sequential per-stripe
        # loop — 1,398 host decodes for a 64 MiB two-dead-node read
        # measured 3x slower than a healthy read; the batch solve is one
        # xor/Horner pass over an [S, k, W] stack. A stripe whose budget
        # depends on a shard another stripe of this batch recovers
        # (in-file dedup) defers to the next round of the loop.
        pending = affected
        while pending:
            deferred = []
            inputs = []
            meta = []
            for s, st, grp in pending:
                data = [padded(c.digest, c.length, st.shard_len)
                        for c in grp]
                p = padded(st.p, st.shard_len, st.shard_len)
                q = padded(st.q, st.shard_len, st.shard_len)
                lost = sum(d is None for d in data) \
                    + (p is None) + (q is None)
                if lost > 2:
                    deferred.append((s, st, grp, lost))
                    continue
                inputs.append((data, p, q))
                meta.append((s, st, grp))
            recs = []
            if inputs:
                try:
                    recs = await asyncio.to_thread(
                        ec_ops.recover_stripes, inputs)
                except ValueError as e:
                    # fall back to per-stripe so one malformed stripe
                    # cannot sink the others (off-loop like the batch —
                    # thousands of inline decodes would stall the server)
                    self.log.warning("ec batch decode failed (%s); "
                                     "retrying per stripe", e)

                    def _per_stripe():
                        got = []
                        for data, p, q in inputs:
                            try:
                                got.append(
                                    ec_ops.recover_stripe(data, p, q))
                            except ValueError as e2:
                                got.append(None)
                                self.log.warning("ec decode failed: %s",
                                                 e2)
                        return got

                    recs = await asyncio.to_thread(_per_stripe)
            progress = False
            for (s, st, grp), rec in zip(meta, recs):
                if rec is None:
                    continue
                recovered = False
                for c, arr in zip(grp, rec):
                    if c.digest in wanted and c.digest not in out:
                        b = arr[:c.length].tobytes()
                        if sha256_hex(b) == c.digest:
                            out[c.digest] = b
                            recovered = True
                        else:
                            self.log.error(
                                "ec decode produced wrong digest for %s",
                                c.digest[:12])
                if (st.p in wanted and st.p not in out) \
                        or (st.q in wanted and st.q not in out):
                    full = np.stack([np.asarray(a) for a in rec])
                    pb, qb = ec_ops.encode_pq(full, device=False)
                    for d, b in ((st.p, pb.tobytes()),
                                 (st.q, qb.tobytes())):
                        if d in wanted and d not in out \
                                and sha256_hex(b) == d:
                            out[d] = b
                            recovered = True
                if recovered:
                    progress = True
                    self.counters.inc("ec_decodes")
            if not deferred:
                break
            if not progress:
                for s, st, grp, lost in deferred:
                    self.log.warning(
                        "ec stripe %d of %s: %d shards lost, beyond P+Q",
                        s, manifest.file_id[:12], lost)
                break
            pending = [(s, st, grp) for s, st, grp, _ in deferred]

    async def _resolve_manifest(self, file_id: str) -> Manifest:
        manifest = self.store.manifests.load(file_id)
        if manifest is None and self.store.manifests.is_tombstoned(file_id):
            # deleted — without this gate the peer fallback below would
            # happily serve the file from a node that slept through the
            # delete (the exact resurrection tombstones exist to prevent)
            raise NotFoundError(file_id)
        if manifest is None:
            # Manifest fallback from peers — fixes the reference's silent
            # manifest loss on nodes that were down during announce
            # (§5.3). Adoption preserves the ORIGIN mtime: stamping now
            # would make a stale adopted manifest postdate a legitimate
            # delete in the tombstone LWW comparison.
            for peer in self._peers():
                try:
                    mj, mt = await self.client.get_manifest(peer, file_id)
                # not silent: the next peer is tried, and a total miss
                # raises DownloadError("Unknown fileId") right below
                except RpcError:  # dfslint: ignore[DFS007]
                    continue
                if mj:
                    manifest = Manifest.from_json(mj)
                    await asyncio.to_thread(self.store.manifests.save,
                                            manifest, mt)
                    break
        if manifest is None:
            raise NotFoundError(file_id)
        return manifest

    async def download_range(self, file_id: str, first: int | None,
                             last: int | None
                             ) -> tuple[Manifest, list, int, int]:
        """Serve an HTTP-style byte range ((first, last) as parsed from a
        single-range ``bytes=`` header; either side may be open) — only
        the chunks overlapping it are gathered, the partial-read
        capability chunk-granular manifests buy (the reference can only
        assemble whole files, StorageNode.java:399-461). Range
        satisfiability is resolved HERE, against the resolved manifest,
        so exactly one clamp exists. Returns (manifest, parts, start,
        end) where ``parts`` is the range payload as an ordered BUFFER
        LIST (read-only views into the gathered chunks) — the HTTP layer
        writes them to the socket one by one; nothing joins them
        (docs/wire.md zero-copy discipline).

        The whole-file hash gate cannot apply to a partial read, so local
        chunk copies are digest-verified up front; a rotten one is
        evicted + queued for repair and the gather re-fetches it from a
        healthy replica (remote bytes are already verified in the
        gather). Raises :class:`RangeNotSatisfiable` past EOF."""
        manifest = await self._resolve_manifest(file_id)
        size = manifest.size
        if first is None:                   # suffix: last N bytes
            if not last:
                raise RangeNotSatisfiable(size)
            start, end = max(0, size - last), size
        else:
            start = first
            end = size if last is None else min(last + 1, size)
        if start >= size or start >= end:
            raise RangeNotSatisfiable(size)

        wanted = [c for c in manifest.chunks
                  if c.offset < end and c.offset + c.length > start]
        # local copies are verified ONCE, off the event loop, inside
        # _fetch_verified (the whole-file hash gate cannot apply to a
        # partial read, so per-chunk verification carries integrity)
        by_digest = await self._fetch_verified(manifest, wanted)
        parts = []
        for c in wanted:
            b = by_digest[c.digest]
            if not isinstance(b, memoryview):
                # slice via a view: a range over large chunks must not
                # copy each chunk's overlap (DFS006 copy discipline)
                b = memoryview(b)
            lo = max(0, start - c.offset)
            hi = min(c.length, end - c.offset)
            parts.append(b[lo:hi])
        self.counters.inc("range_downloads")
        return manifest, parts, start, end

    async def _fetch_verified(self, manifest: Manifest, chunks: list,
                              strict: bool = True) -> dict[str, bytes]:
        """Serving-tier front of :meth:`_fetch_verified_direct`. With the
        tier enabled (cfg.serve.cache_bytes > 0): hot digests come from
        the in-memory SIEVE cache; cold digests are CLAIMED per digest
        (single-flight) and every digest this caller wins is fetched in
        one batched direct gather — leadership never degrades the read
        into one-RPC-per-chunk — then verified bytes populate the cache
        and resolve the waiters. A leader failure rejects its claims
        (waiters of THIS flight see it; the next request re-leads — no
        poisoning). Default config: exactly the direct path."""
        if deadline.expired():
            # already-dead read: refuse BEFORE the cache scan, flight
            # claims, and above all the CAS pool — a request whose
            # caller gave up must not occupy a disk worker (checked per
            # batch, so a mid-download expiry stops the remaining
            # batches too). No deadline set = one ContextVar read.
            self.counters.inc("deadline_drops")
            self.obs.event("deadline_shed", where="fetch")
            raise DeadlineExceeded("deadline expired")
        if self.tier is not None:
            # temperature feed (docs/tiering.md): every requested digest
            # counts as one read — BEFORE the cache/flight split, so
            # cache hits and misses heat the ledger alike (temperature
            # is about demand, not about where the bytes came from)
            for c in chunks:
                self.tier.ledger.note_read(c.digest)
        serve = self.serve
        if not serve.read_path_enabled:
            return await self._fetch_verified_direct(manifest, chunks,
                                                     strict)
        length: dict[str, int] = {}
        for c in chunks:
            length.setdefault(c.digest, c.length)
        out: dict[str, bytes] = {}
        waits: dict[str, asyncio.Future] = {}
        mine: list[str] = []
        for d in length:
            b = serve.cache.get(d)
            if b is not None:
                out[d] = b
                continue
            leader, fut = serve.flight.claim(d)
            if leader:
                mine.append(d)
            else:
                waits[d] = fut
        if mine:
            refs = [ChunkRef(index=0, offset=0, length=length[d],
                             digest=d) for d in mine]
            try:
                got = await self._fetch_verified_direct(
                    manifest, refs, strict=False)
            except BaseException as e:
                # convert a cancelled leader (client hung up mid-read)
                # into a normal fetch failure for the waiters: their
                # requests are alive and must not inherit cancellation
                exc = e if isinstance(e, Exception) else DownloadError(
                    "origin fetch cancelled")
                for d in mine:
                    serve.flight.reject(d, exc)
                raise
            for d in mine:
                b = got.get(d)
                if b is None:
                    serve.flight.reject(d, DownloadError(
                        f"Could not retrieve chunk {d[:12]}…"))
                else:
                    serve.cache.put(d, b)
                    serve.flight.resolve(d, b)
                    out[d] = b
        failed_waits: list[str] = []
        if waits:
            # traced as ONE wait span (not per digest): what matters
            # post-hoc is how long this reader was parked behind other
            # flights, and a span per coalesced digest would dominate
            # the ring on hot files
            with self.obs.span("serve.flight.wait"):
                for d, fut in waits.items():
                    try:
                        out[d] = await serve.flight.wait(fut)
                    # not silent: the digest joins failed_waits and is
                    # re-fetched directly right below
                    except DownloadError:  # dfslint: ignore[DFS007]
                        failed_waits.append(d)
                    except asyncio.CancelledError:
                        if not fut.done():
                            raise            # WE were cancelled
                        failed_waits.append(d)  # the leader's flight died
        if failed_waits:
            # a rejected flight says nothing about THIS request: the
            # leader may simply have been cancelled (its client hung
            # up). Re-fetch directly — an innocent waiter must not 500
            # on a healthy cluster; for genuinely lost chunks this one
            # batched attempt is the same work the leader already paid.
            refs = [ChunkRef(index=0, offset=0, length=length[d],
                             digest=d) for d in failed_waits]
            got = await self._fetch_verified_direct(
                manifest, refs, strict=False)
            for d in failed_waits:
                b = got.get(d)
                if b is not None:
                    serve.cache.put(d, b)
                    out[d] = b
        missing = [d for d in length if d not in out]
        if missing and strict:
            raise DownloadError(
                f"Could not retrieve chunk {missing[0][:12]}…")
        return out

    async def _fetch_verified_direct(self, manifest: Manifest,
                                     chunks: list, strict: bool = True
                                     ) -> dict[str, bytes]:
        """Gather a slice of a manifest's chunks with local copies
        digest-verified first (heal-on-read: rotten local chunks are
        evicted + queued for repair and re-fetched from replicas, the
        same discipline range reads use)."""
        digests = list(dict.fromkeys(c.digest for c in chunks))
        local = await self.cas.get_many(digests)
        hexes = await asyncio.to_thread(
            sha256_many_hex, [b for _, b in local])
        good: dict[str, bytes] = {}
        for (d, b), h in zip(local, hexes):
            if h == d:
                good[d] = b
            else:
                self.store.chunks.delete(d)
                self.serve.drop_cached([d])
                self.under_replicated.add(d)
                self.log.warning("evicted corrupt local chunk %s on read",
                                 d[:12])
                self.obs.event("corrupt_chunk", digest=d[:12],
                               where="read")
        return await self._gather_chunks(manifest, chunks=chunks,
                                         prefetched=good, strict=strict)

    async def download_stream(self, file_id: str):
        """Streaming read: -> (manifest, async generator of chunk
        payloads in stream order). Chunks are gathered in ~32 MiB batches
        and yielded as they verify, so node memory stays ~one batch no
        matter the file size — the reference (and this node's download()
        until round 3) assembles the whole file in RAM
        (StorageNode.java:419,448). Integrity: every chunk is
        digest-verified (local AND remote); the reference's whole-file
        gate (sha256(assembled) == fileId, StorageNode.java:453-458) is
        kept by hashing incrementally and HOLDING BACK the final chunk —
        a corrupted assembly is truncated before its last byte, never
        silently completed. The first batch is fetched eagerly so
        unrecoverable-chunk failures surface before any byte is sent."""
        manifest = await self._resolve_manifest(file_id)
        # promotion trigger (docs/tiering.md): a cold file read hot
        # enough re-materializes replicated in the BACKGROUND — this
        # read itself reconstructs transparently via the EC decode path
        self._tier_maybe_promote(manifest)
        refs = list(manifest.chunks)
        batches: list[list] = []
        cur: list = []
        size = 0
        for c in refs:
            cur.append(c)
            size += c.length
            if size >= self._FETCH_BATCH_BYTES:
                batches.append(cur)
                cur, size = [], 0
        if cur:
            batches.append(cur)
        first = await self._fetch_verified(manifest, batches[0]) \
            if batches else {}

        async def gen():
            nonlocal first
            # bounded readahead (serving tier): with K > 0 the next K
            # batches fetch WHILE the current one drains to the socket,
            # so storage plane and socket stop serializing; memory stays
            # <= K+1 batches. K = 0 (default) keeps the strict
            # one-batch-at-a-time schedule. Built HERE, not before the
            # generator starts: batch 0 is already fetched above (eager
            # failure surfacing before the response head), and a body
            # that is closed before its first iteration must own no
            # in-flight fetch tasks (an unstarted generator's finally
            # never runs, so nothing else could cancel them).
            pre: BatchPrefetcher | None = None
            if self.serve.readahead_batches > 0 and len(batches) > 1:
                pre = BatchPrefetcher(
                    batches, lambda b: self._fetch_verified(manifest, b),
                    self.serve.readahead_batches, start=1)
                pre.prime()   # batches 1..K fetch while batch 0 drains
            hasher = sha256_new()
            held: bytes | None = None
            total = 0
            try:
                for i, batch in enumerate(batches):
                    if i:
                        got = await (pre.get(i) if pre is not None else
                                     self._fetch_verified(manifest, batch))
                    else:
                        got, first = first, None   # don't pin batch 0 for
                        # the whole download — peak stays ~one batch
                    payloads = [got[c.digest] for c in batch]
                    await asyncio.to_thread(
                        lambda ps=payloads: [hasher.update(p) for p in ps])
                    for b in payloads:
                        if held is not None:
                            total += len(held)
                            yield held
                        held = b
                if hasher.hexdigest() != file_id:
                    # mid-assembly corruption (e.g. a stale manifest):
                    # abort before the last byte — the client sees
                    # truncation, not a silently wrong file
                    raise DownloadError("File corrupted")
                if held is not None:
                    total += len(held)
                    yield held
                self.counters.inc("downloads")
                self.counters.inc("download_bytes", total)
            finally:
                if pre is not None:    # abandoned stream: stop fetching
                    await pre.close()

        return manifest, gen()

    async def download(self, file_id: str) -> tuple[Manifest, bytearray]:
        """Whole-file read for callers that want one bytes-like object.
        Since round 10 this is a thin accumulator over
        :meth:`download_stream` — ONE assembly path owns batching,
        per-chunk verification, and the whole-file hash gate (the
        streamed path's incremental hash + held-back final chunk is
        exactly the reference's sha256(assembled) == fileId check,
        StorageNode.java:453-458, surfaced before the last byte). The
        pre-r10 implementation gathered every chunk into a dict and
        joined it — two resident copies of the file plus a full-corpus
        memcpy; this keeps ONE growing buffer (returned as a bytearray —
        bytes-like for every comparison/hash/slice use) and no join."""
        manifest, gen = await self.download_stream(file_id)
        out = bytearray()
        with self.obs.span("download.gather", latency=True):
            async for part in gen:
                out += part
        return manifest, out

    # ------------------------------------------------------------------ #
    # listing (reference handleListFiles, StorageNode.java:364-393)
    # ------------------------------------------------------------------ #

    def ingest_stats(self) -> dict:
        """Write-path pipeline observability for /metrics: the configured
        bounds plus stall attribution — where ingest wall time went
        (chunking blocked on credits vs placement blocked on
        replication vs the disk tier's queue/busy split) and the peak
        pipeline depths actually reached."""
        ing = self.cfg.ingest
        return {"window": ing.window,
                "flushBytes": self._STREAM_FLUSH_BYTES,
                "creditBytes": ing.credit_bytes,
                "sliceInflight": ing.slice_inflight,
                "stalls": self.ingest_stalls.snapshot(),
                "cas": self.cas.stats()}

    def frag_stats(self) -> dict:
        """Fragmenter execution knobs for /metrics "frag" (DFS005: every
        FragmenterConfig field surfaces here) plus what is ACTUALLY
        running: the engine name ('auto' resolved once, at start;
        'sidecar:<engine>' when a chip owner does the work) and
        ``degraded`` — True once a sharded walk has fallen back to its
        single-device kernel (CPU rehearsal only: where the device was
        asked for, that is an error instead).
        The sharded fragmenters share the host engine's ``name`` on
        purpose (same strategy, same manifests), so the name alone
        cannot reveal that fallback — this flag is the operator's
        signal."""
        f = self.cfg.frag
        return {"devices": f.devices,
                "regionBytes": f.region_bytes,
                "stagingBuffers": f.staging_buffers,
                "engine": self.fragmenter.name,
                "degraded": bool(getattr(self.fragmenter,
                                         "_unavailable", False))}

    async def _own_spans(self, trace_id: str) -> list[dict]:
        """This node's spans of one trace, and its chip owner's when it
        delegates to one (the owner's ``Trace`` method): the owner is a
        node of the trace like any other, reached through the node it
        serves. An owner that does not answer leaves the node's own
        spans (``owner.*`` then simply miss from the tree)."""
        spans = self.obs.spans_for(trace_id)
        if self.cfg.sidecar_port:
            import grpc

            try:
                spans = spans + await asyncio.to_thread(
                    self.fragmenter.client.trace, traceId=trace_id)
            except grpc.RpcError as e:
                self.log.warning("owner did not answer Trace: %s", e)
                self.counters.inc("owner_trace_failures")
        return spans

    async def trace_spans(self, trace_id: str,
                          cluster: bool = True) -> dict:
        """Spans of one trace — local ring and this node's chip owner,
        plus (``cluster=True``) every peer's via the ``get_trace`` op,
        merged for the stitcher (GET /trace, CLI ``trace <id>``).
        Unreachable peers degrade the result to a partial trace
        (reported in ``peersFailed``), never an error: a stitch query
        must work exactly when something is wrong."""
        from dfs_tpu.obs.stitch import merge_spans

        lists: list[list[dict]] = [await self._own_spans(trace_id)]
        failed = 0
        peers = self._peers() if cluster else []

        async def one(peer) -> list[dict] | None:
            try:
                resp, _ = await self.client.call(
                    peer, {"op": "get_trace", "traceId": trace_id},
                    retries=1)
                spans = resp.get("spans")
                return spans if isinstance(spans, list) else []
            # not silent: None is counted into the report's peersFailed
            except RpcError:  # dfslint: ignore[DFS007]
                return None

        for got in await asyncio.gather(*(one(p) for p in peers)):
            if got is None:
                failed += 1
            else:
                lists.append(got)
        return {"traceId": trace_id,
                "slowSpanS": self.cfg.obs.slow_span_s,
                "spans": merge_spans(lists),
                "peersQueried": len(peers), "peersFailed": failed}

    # ------------------------------------------------------------------ #
    # cluster doctor (docs/observability.md)
    # ------------------------------------------------------------------ #

    def _disk_usage(self) -> dict:
        """Blocking statvfs under the node's data root — call via
        ``asyncio.to_thread`` (shared by the doctor snapshot, the
        census inventory, and the history sampler)."""
        import shutil

        try:
            u = shutil.disk_usage(self.store.root)
            return {"totalBytes": u.total, "freeBytes": u.free}
        # not silent: {} renders as unknown headroom in the report
        except OSError:  # dfslint: ignore[DFS007]
            return {}

    async def doctor_snapshot(self) -> dict:
        """This node's diagnosis snapshot: the per-node material the
        doctor rule table consumes — metric summaries, recent journal
        incidents, disk headroom, config fingerprint, wall clock. Every
        blocking read (journal tail, disk_usage, chunk count priming)
        runs off the event loop."""
        incidents: list[dict] = []
        if self.obs.journal is not None:
            tail = await asyncio.to_thread(self.obs.journal.tail, 0.0, 64)
            incidents = tail.get("events", [])
        obs_stats = self.obs.stats()
        return {
            "nodeId": self.cfg.node_id,
            "now": time.time(),
            "uptimeS": round(time.time() - self._started_at, 3),
            "configHash": self._config_hash,
            "chunks": await asyncio.to_thread(self.store.chunks.count),
            "files": len(self.store.manifests.ids()),
            "peersAlive": self.health.snapshot(),
            "underReplicated": len(self.under_replicated),
            "admission": self.serve.admission.stats(),
            # hedged-read counters incl. the 60 s fired/denied windows —
            # the doctor's hedge_storm evidence (docs/serve.md)
            "hedge": self.serve.hedge.stats()
            if self.serve.hedge is not None else {"enabled": False},
            "cache": self.serve.cache.stats()
            if self.serve.cache is not None else {"enabled": False},
            "ingestStalls": self.ingest_stalls.snapshot(),
            "cas": self.cas.stats(),
            "sentinel": obs_stats["sentinel"],
            "journal": obs_stats["journal"],
            "rpcClient": obs_stats["rpcClient"],
            "counters": self.counters.snapshot(),
            "incidents": incidents,
            "disk": await asyncio.to_thread(self._disk_usage),
            # trend material for the doctor's capacity_trend rule
            # (history-derived CAS growth slope) and the last census
            # this node coordinated — feeds the underreplication rule
            "capacity": self._capacity_summary(),
            "census": self._last_census,
            # dedup/index plane view: peer-filter replica ages — the
            # doctor's index_stale evidence (a node skipping probes on
            # weeks-old summaries is mis-placing trust, not saving RPCs)
            "index": {"enabled": False} if self.index is None else {
                "enabled": True,
                "syncS": self.cfg.index.filter_sync_s,
                "peerAgeS": {str(p): round(a, 3) for p, a in
                             sorted(self.index.peer_filters.ages()
                                    .items())}},
            # membership view: epoch + migration progress — the
            # doctor's epoch_mismatch and rebalance_stuck evidence
            "ring": {"epoch": self.ring.epoch,
                     "migrating": self.ring.migrating,
                     **{k: v for k, v in
                        self.ring.rebalance_stats().items()
                        if k in ("sinceProgressS", "bytesMoved",
                                 "dualReadHits")}},
            # tiering plane view: scan cadence + progress gauge — the
            # doctor's tier_stall evidence (a worker that stopped
            # completing scans leaves the cold tail undemoted silently)
            "tier": {"enabled": False} if self.tier is None else {
                "enabled": True,
                "scanIntervalS": self.cfg.tier.scan_interval_s,
                "sinceProgressS": round(
                    time.monotonic() - self.tier.last_progress_at, 3),
                "errors": self.tier.errors,
                "scans": self.tier.scans},
        }

    async def doctor_report(self, cluster: bool = True) -> dict:
        """The cluster doctor: fan out ``get_doctor`` to every peer
        (bounded — one fast attempt per peer, partial on dead peers,
        exactly like ``/trace``), then run the pathology rule table
        (obs/doctor.py) over the snapshots. A peer that cannot answer IS
        a finding (dead_peer), never an error — the doctor must work
        exactly when something is wrong."""
        from dfs_tpu.obs.doctor import diagnose

        snaps: dict[int, dict | None] = {
            self.cfg.node_id: await self.doctor_snapshot()}
        # clock_skew compares each snapshot's capture-time "now" against
        # the moment THIS coordinator received it — never against a
        # single post-fan-out timestamp, which one hung peer would drag
        # seconds past every fast answer and misdiagnose the whole live
        # cluster as skewed.
        snaps[self.cfg.node_id]["receivedAt"] = time.time()
        failed = 0
        peers = self._peers() if cluster else []

        async def one(peer) -> tuple[int, dict | None]:
            try:
                resp, _ = await self.client.call(
                    peer, {"op": "get_doctor"}, retries=1)
                d = resp.get("doctor")
                if isinstance(d, dict):
                    d["receivedAt"] = time.time()
                    return peer.node_id, d
                return peer.node_id, None
            # not silent: a None snapshot IS the dead_peer finding
            except RpcError:  # dfslint: ignore[DFS007]
                return peer.node_id, None

        for nid, snap in await asyncio.gather(*(one(p) for p in peers)):
            snaps[nid] = snap
            if snap is None:
                failed += 1
        now = time.time()
        findings = diagnose(snaps, coordinator_now=now)
        return {"coordinator": self.cfg.node_id, "now": now,
                "peersFailed": failed,
                "nodes": {str(k): v for k, v in sorted(snaps.items())},
                "findings": findings}

    # ------------------------------------------------------------------ #
    # cluster census & capacity plane (docs/observability.md)
    # ------------------------------------------------------------------ #

    # per-bucket digest-list cap for census drill-downs: bounds one
    # drill reply at DRILL_BUCKET_CAP x this many digests per node
    _CENSUS_LIST_CAP = 4096
    # disk_pressure journal event: fires crossing below 5% free, re-arms
    # above 10% (hysteresis — a disk hovering at the line must not spam
    # the flight recorder every sample)
    _DISK_PRESSURE_FRACTION = 0.05
    # counters the history sampler tracks (ingest/serve totals; rates
    # fall out of differencing adjacent buckets)
    _HISTORY_COUNTERS = ("http_requests", "uploads", "downloads",
                         "upload_bytes", "download_bytes",
                         "chunks_stored", "bytes_stored", "dedup_hits",
                         "replication_failures", "http_shed")

    async def _history_loop(self) -> None:
        interval = self.cfg.census.history_interval_s
        while True:
            await asyncio.sleep(interval)
            try:
                await self._history_sample_once()
            except Exception as e:  # noqa: BLE001 — sampler must outlive
                # one bad sample; the failure is logged, next tick retries
                self.log.warning("census history sample failed: %s", e)

    async def _history_sample_once(self) -> None:
        """One history tick: selected counters/gauges into the
        multi-resolution ring. Disk/CAS reads run off the loop; the
        CAS byte gauge is O(1) after its one priming scan
        (ChunkStore.bytes_total)."""
        h = self.history
        now = time.time()
        c = self.counters.snapshot()
        for k in self._HISTORY_COUNTERS:
            h.observe(f"counter.{k}", c.get(k, 0), now)
        h.observe("cas.pending", self.cas.pending, now)
        h.observe("ingest.creditS",
                  self.ingest_stalls.snapshot().get("creditS", 0.0), now)
        cache = self.serve.cache
        if cache is not None:
            cs = cache.stats()
            h.observe("cache.hits", cs["hits"], now)
            h.observe("cache.misses", cs["misses"], now)
            h.observe("cache.bytes", cs["bytes"], now)
        calls = secs = 0
        for _, _, row in self.obs.rpc_client.rows():
            calls += row[0]
            secs += row[5]
        h.observe("rpc.clientCalls", calls, now)
        h.observe("rpc.clientSeconds", secs, now)
        h.observe("capacity.casBytes",
                  await asyncio.to_thread(self.store.chunks.bytes_total),
                  now)
        h.observe("capacity.casChunks",
                  await asyncio.to_thread(self.store.chunks.count), now)
        disk = await asyncio.to_thread(self._disk_usage)
        if disk:
            h.observe("capacity.diskFreeBytes", disk["freeBytes"], now)
            h.observe("capacity.diskTotalBytes", disk["totalBytes"], now)
            frac = disk["freeBytes"] / max(1, disk["totalBytes"])
            if frac < self._DISK_PRESSURE_FRACTION:
                if not self._disk_pressure:
                    self._disk_pressure = True
                    self.obs.event("disk_pressure",
                                   freeBytes=disk["freeBytes"],
                                   totalBytes=disk["totalBytes"])
            elif frac >= 2 * self._DISK_PRESSURE_FRACTION:
                self._disk_pressure = False

    def _capacity_summary(self) -> dict:
        """History-derived capacity gauges + growth slope — the doctor
        snapshot's trend material (capacity_trend rule). Reads only
        the last sampled values: never a scan, safe on the loop."""
        h = self.history
        if h is None:
            return {"enabled": False}
        return {"enabled": True,
                "casBytes": h.last("capacity.casBytes"),
                "casChunks": h.last("capacity.casChunks"),
                "diskFreeBytes": h.last("capacity.diskFreeBytes"),
                "diskTotalBytes": h.last("capacity.diskTotalBytes"),
                "growthBytesPerS": h.trend("capacity.casBytes")}

    def durability_stats(self) -> dict:
        """``/metrics`` ``durability`` section. The ``mode`` key mirrors
        DurabilityConfig.mode (dfslint DFS005 checks the mapping);
        ``fsyncs`` counts the chunk files the store made durable (payload
        fsync'd, linked, directory fsync'd — one per file, before its put
        returned), ``dirBarriers`` the directory fsyncs that took: one
        per distinct directory of a batch, not one per file;
        ``resident*`` how often the store's resident set answered an
        existence check in place of a ``stat`` (index off)."""
        return {"mode": self.cfg.durability.mode,
                "fsyncs": self.store.chunks.fsync_count(),
                "dirBarriers": self.store.chunks.dir_barrier_count(),
                **self.store.chunks.resident_stats()}

    def chaos_stats(self) -> dict:
        """``/metrics`` ``chaos`` section: active knobs + per-kind
        injected-fault counters (dfs_tpu.chaos.ChaosInjector.stats);
        ``enabled: false`` for the default chaos-less node."""
        if self.chaos is None:
            return {"enabled": False}
        return self.chaos.stats()

    def census_stats(self) -> dict:
        """``/metrics`` ``census`` section. The history* / maxListed
        keys mirror CensusConfig fields (dfslint DFS005 checks the
        config ⇄ CLI ⇄ metrics mapping)."""
        c = self.cfg.census
        return {"historyIntervalS": c.history_interval_s,
                "historySlots": c.history_slots,
                "coarseEvery": c.history_coarse_every,
                "coarseSlots": c.history_coarse_slots,
                "maxListed": c.max_listed,
                "history": self.history.stats()
                if self.history is not None else {"enabled": False},
                "capacity": self._capacity_summary(),
                "lastCensus": self._last_census}

    async def census_inventory(self, prefixes=None) -> dict:
        """This node's census contribution: the bucketed CAS inventory
        (one bounded read-pool job), disk headroom, and the serve
        cache's bounded top-K temperature stats (ROADMAP item 3's
        demotion-policy seed). ``prefixes`` adds member digest lists
        for those buckets (the drill-down pass)."""
        inv = await self.cas.inventory(prefixes,
                                       list_cap=self._CENSUS_LIST_CAP)
        inv["nodeId"] = self.cfg.node_id
        inv["disk"] = await asyncio.to_thread(self._disk_usage)
        cache = self.serve.cache
        inv["cacheTemperature"] = cache.temperature() \
            if cache is not None else []
        return inv

    async def census_report(self, cluster: bool = True) -> dict:
        """The replication-health census (GET /census, CLI ``census`` /
        ``df``): fan out ``get_census`` summaries to every peer
        (bounded, partial on dead peers — the /trace /doctor
        discipline), compare each node's bucket summary against the
        expectation derived from this node's manifests, drill only the
        mismatched buckets, and emit the replication histogram plus
        bounded under-replicated / orphaned / over-replicated lists
        (obs/census.py). Data-health findings are journaled
        (census_underreplicated / census_orphan), stamped with the
        active trace id."""
        from dfs_tpu.obs import census as census_mod

        rf = self.cfg.cluster.replication_factor
        # epoch-aware expectation: bucket tables derive from the ring's
        # owner map; mid-migration the PREVIOUS epoch's owners join the
        # union expectation so a rebalance in flight reads as IN-FLIGHT
        # digests, not thousands of phantom under-/over-replication
        # findings (docs/membership.md)
        cur_ring = self.ring.current
        prev_ring = self.ring.previous
        manifests = await asyncio.to_thread(self.store.manifests.list)
        expected, cur_expected, lengths, logical = \
            await asyncio.to_thread(census_mod.expected_state_ring,
                                    manifests, cur_ring, prev_ring, rf)
        peers = self._peers() if cluster else []
        inventories: dict[int, dict | None] = {
            self.cfg.node_id: await self.census_inventory()}

        async def one(peer) -> tuple[int, dict | None]:
            try:
                inv = await self.client.get_census(
                    peer, retries=1, expect_chunks=len(lengths))
                return peer.node_id, inv if isinstance(inv, dict) else None
            # not silent: a None inventory IS the partial-result signal
            # (peersFailed + unknown copies in the report)
            except RpcError:  # dfslint: ignore[DFS007]
                return peer.node_id, None

        for nid, inv in await asyncio.gather(*(one(p) for p in peers)):
            inventories[nid] = inv
        failed = sum(1 for v in inventories.values() if v is None)

        # drill pass: only buckets whose summary mismatches expectation
        # move digest lists, capped per node (census_mod.DRILL_BUCKET_CAP)
        exp_by_node = await asyncio.to_thread(
            census_mod.summarize_expected, expected, lengths)
        drill_want: dict[int, list[str]] = {}
        for nid, inv in inventories.items():
            if inv is None:
                continue
            mism = census_mod.diff_buckets(
                exp_by_node.get(nid, {}), inv.get("buckets") or {})
            if mism:
                drill_want[nid] = mism[:census_mod.DRILL_BUCKET_CAP]

        async def drill(nid: int, want: list[str]
                        ) -> tuple[int, dict]:
            if nid == self.cfg.node_id:
                inv = await self.cas.inventory(
                    want, list_cap=self._CENSUS_LIST_CAP)
                return nid, inv.get("listed") or {}
            try:
                inv = await self.client.get_census(
                    self.cfg.cluster.peer(nid), prefixes=want, retries=1,
                    expect_chunks=len(lengths))
                return nid, (inv or {}).get("listed") or {}
            # not silent: an unanswered drill leaves its buckets in the
            # report's uncheckedBuckets count (build_report)
            except RpcError:  # dfslint: ignore[DFS007]
                return nid, {}

        drilled: dict[int, dict] = {}
        for nid, listed in await asyncio.gather(
                *(drill(n, w) for n, w in drill_want.items())):
            drilled[nid] = listed

        report = await asyncio.to_thread(
            census_mod.build_report, expected, lengths, inventories,
            drilled, self.cfg.census.max_listed, cur_expected)
        report["ringEpoch"] = cur_ring.epoch
        report["migrating"] = prev_ring is not None

        # capacity / df section: per-node and cluster byte accounting
        nodes_cap: dict[str, dict | None] = {}
        cluster_bytes = cluster_chunks = 0
        for nid in sorted(inventories):
            inv = inventories[nid]
            if inv is None:
                nodes_cap[str(nid)] = None
                continue
            disk = inv.get("disk") or {}
            nodes_cap[str(nid)] = {
                "casBytes": inv.get("bytes", 0),
                "casChunks": inv.get("chunks", 0),
                "diskFreeBytes": disk.get("freeBytes"),
                "diskTotalBytes": disk.get("totalBytes"),
                "cacheTemperature": inv.get("cacheTemperature") or []}
            cluster_bytes += inv.get("bytes", 0)
            cluster_chunks += inv.get("chunks", 0)
        unique_bytes = sum(lengths.values())
        report["capacity"] = {
            "nodes": nodes_cap,
            "clusterCasBytes": cluster_bytes,
            "clusterChunks": cluster_chunks,
            "logicalBytes": logical,
            "uniqueBytes": unique_bytes,
            "dedupRatio": round(logical / unique_bytes, 6)
            if unique_bytes else 0.0}
        report["coordinator"] = self.cfg.node_id
        report["now"] = time.time()
        report["peersFailed"] = failed

        # flight-recorder correlation: data-health incidents get dated,
        # trace-stamped journal entries (the `events` / doctor surface)
        if report["underReplicatedTotal"]:
            self.obs.event(
                "census_underreplicated",
                count=report["underReplicatedTotal"],
                sample=[f["digest"][:12]
                        for f in report["underReplicated"][:4]])
        if report["orphanedTotal"]:
            self.obs.event(
                "census_orphan", count=report["orphanedTotal"],
                sample=[f["digest"][:12]
                        for f in report["orphaned"][:4]])
        self._last_census = {"at": report["now"],
                             "underReplicated":
                             report["underReplicatedTotal"],
                             "orphaned": report["orphanedTotal"],
                             "overReplicated":
                             report["overReplicatedTotal"],
                             "peersFailed": failed}
        self.counters.inc("census_runs")
        return report

    def list_files(self) -> list[dict]:
        return [{"fileId": m.file_id, "name": m.name, "size": m.size,
                 "chunks": m.total_chunks, "fragmenter": m.fragmenter}
                for m in self.store.manifests.list()]

    # ------------------------------------------------------------------ #
    # delete + repair (new capabilities; absent in reference §2.5(5), §5.3)
    # ------------------------------------------------------------------ #

    def _forget_file(self, file_id: str, ts: float | None = None,
                     gc: bool = True) -> bool:
        """Tombstone a manifest AND drop its bytes from serving memory —
        the one delete sequence every path (user delete, the internal
        delete op, tombstone anti-entropy) must share. The manifest is
        loaded BEFORE tombstoning: the cache may hold chunks this node
        only ever fetched remotely (never in the local store), which the
        local GC's dead-list cannot name; correctness is unaffected
        either way — content addressing means cached bytes are never
        wrong, and the tombstone already blocks the file-level read.
        ``ts`` propagates an ORIGIN deletion time (anti-entropy);
        ``gc=False`` defers the orphan sweep to the caller (anti-entropy
        runs ONE sweep after applying a whole round of tombstones).
        With the cache off (default) the manifest load is skipped — the
        pre-serving-tier delete paths never paid that read."""
        m = self.store.manifests.load(file_id) \
            if self.serve.cache is not None else None
        found = self.store.manifests.delete(file_id, ts=ts)
        if gc:
            self.serve.drop_cached(self.store.gc())
        if m is not None:
            self.serve.drop_cached(m.all_digests())
        return found

    async def delete(self, file_id: str) -> bool:
        # tombstone persists; written off-loop (fsync barrier + GC)
        found = await asyncio.to_thread(self._forget_file, file_id)

        async def forget(peer) -> None:
            try:
                await self.client.call(peer, {"op": "delete", "fileId": file_id})
            except RpcError:
                # journaled (DFS007): the delete converges later via
                # tombstone anti-entropy, but "peer N kept serving a
                # deleted file for an hour" starts exactly here
                self.obs.event("delete_propagate_fail", peer=peer.node_id,
                               fileId=file_id[:12])

        # Best-effort immediate propagation; a node that is down right now
        # converges later via tombstone anti-entropy in repair_once.
        await asyncio.gather(*(forget(p) for p in self._peers()))
        return found

    async def _tombstone_antientropy(self) -> int:
        """Pull peers' tombstones and converge by last-writer-wins: a node
        that slept through a delete learns of it here BEFORE
        re-replicating, so its stale manifest can neither serve the file
        nor resurrect its chunks onto peers. Ordering matters the other
        way too — a peer that slept through a *re-upload* still holds a
        tombstone OLDER than our live manifest; applying it blindly would
        destroy an acknowledged upload cluster-wide, so stale tombstones
        are instead answered by re-announcing the newer manifest
        (fresh=True clears the peer's tombstone). Returns #applied."""
        known = set(self.store.manifests.tombstones())
        applied = 0
        for peer in self._peers():
            # no is_alive gate: a peer marked dead is exactly the one that
            # may have rejoined lagging; one cheap attempt probes it
            try:
                resp, _ = await self.client.call(
                    peer, {"op": "tombstones"}, retries=1)
                self.health.mark_alive(peer.node_id)
            except RpcError:
                # counted (DFS007): anti-entropy that silently fails
                # every cycle IS the cluster not converging
                self.counters.inc("antientropy_rpc_failures")
                continue
            for t in resp.get("tombs", []):
                fid, ts = t.get("id"), t.get("ts")
                # validate before applying: one malformed entry from a
                # skewed peer raising here would abort repair for every
                # cycle and silently stop the cluster converging
                if fid in known or not is_hex_digest(fid):
                    continue
                if ts is None:
                    # tombstone no longer exists on the peer (cleared by a
                    # concurrent fresh re-upload). Applying it with ts=None
                    # would re-stamp a FRESH local timestamp that postdates
                    # the re-uploaded manifest and propagate the deletion
                    # of an acknowledged upload cluster-wide. Skip it.
                    continue
                try:
                    ts = float(ts)
                    if not math.isfinite(ts):
                        continue   # NaN defeats every LWW comparison
                except (TypeError, ValueError):
                    continue
                local_mtime = self.store.manifests.mtime(fid)
                if local_mtime is not None and local_mtime > ts:
                    # our manifest postdates the delete: the tombstone is
                    # stale — resurrect the file on the lagging peer
                    m = self.store.manifests.load(fid)
                    if m is not None:
                        try:
                            await self.client.announce(peer, m.to_json(),
                                                       fresh=True)
                        except RpcError:
                            self.counters.inc("antientropy_rpc_failures")
                    continue
                # propagate with the ORIGIN timestamp (re-stamping would
                # let the tombstone's ts creep forward as it gossips);
                # one shared GC sweep runs after the whole round below.
                # Off-loop: the tombstone write is an fsync barrier
                # under the default durability mode.
                await asyncio.to_thread(self._forget_file, fid, ts, False)
                known.add(fid)
                applied += 1
        if applied:
            self.serve.drop_cached(self.store.gc())
            self.log.info("anti-entropy: applied %d tombstones", applied)
        return applied

    async def _manifest_antientropy(self) -> int:
        """Pull manifests this node is missing (announce is best-effort,
        exactly like the reference — StorageNode.java:338-346 — so a node
        that was down or timed out during an announce would otherwise
        stay silently ignorant of the file forever, SURVEY §3.4's noted
        hole). Tombstoned ids are skipped: deletes win over stale
        creates; the LWW path handles the re-upload case. Returns
        #manifests adopted."""
        known = set(self.store.manifests.ids())
        adopted = 0
        for peer in self._peers():
            try:
                resp, _ = await self.client.call(
                    peer, {"op": "list_manifests"}, retries=1)
                self.health.mark_alive(peer.node_id)
            except RpcError:
                self.counters.inc("antientropy_rpc_failures")
                continue
            for fid in resp.get("ids", []):
                if (fid in known or not is_hex_digest(fid)
                        or self.store.manifests.is_tombstoned(fid)):
                    continue
                try:
                    mj, mt = await self.client.get_manifest(peer, fid)
                except RpcError:
                    self.counters.inc("antientropy_rpc_failures")
                    continue
                if mj:
                    try:
                        m = Manifest.from_json(mj)
                    except (ValueError, KeyError):
                        continue          # corrupt peer manifest
                    # adoption preserves the ORIGIN mtime — see save();
                    # saved off-loop (fsync barrier under the default
                    # durability mode)
                    if m.file_id == fid and await asyncio.to_thread(
                            self.store.manifests.save, m, mt):
                        known.add(fid)
                        adopted += 1
        if adopted:
            self.log.info("anti-entropy: adopted %d manifests", adopted)
        return adopted

    async def repair_once(self) -> int:
        """Re-replicate chunks below replication factor — and, since
        r14, the ONLINE REBALANCER: after a ring epoch change the same
        manifest walk computes placement against the NEW owner map, so
        chunks stream to their new-epoch owners through the bounded
        async CAS tier + sliced pushes, under the ring's byte credits
        (``RingConfig.rebalance_credit_bytes``), with exactly one
        DESIGNATED mover per digest (the first alive previous-epoch
        owner) so a membership change moves each byte once, not once
        per node. When a full walk confirms every digest at its
        new-epoch owners, the migration window closes
        (``rebalance_done``) and reads stop consulting the previous
        map. Returns #chunks repaired/moved.

        Tombstone anti-entropy runs FIRST: repairing from a manifest whose
        file was deleted cluster-wide while this node slept would push the
        deleted chunks back onto peers. Manifest anti-entropy runs second
        (adopt creates this node missed), so the repair walk below also
        restores this node's canonical chunks for newly-adopted files."""
        async with self._repair_lock:
            # serialized: the periodic repair loop and the install-time
            # rebalance kick must not interleave two walks (their
            # confirmed-sets would cross-talk into a bogus
            # finish_migration)
            return await self._repair_once_locked()

    async def _repair_once_locked(self) -> int:
        await self._tombstone_antientropy()
        await self._manifest_antientropy()
        # placement snapshot for the WHOLE walk: epoch adoptions landing
        # mid-walk take effect next cycle (and block finish_migration
        # below — the identity check), never mid-computation
        cur = self.ring.current
        prev = self.ring.previous
        migrating = prev is not None
        rf = self.cfg.cluster.replication_factor
        need: dict[int, list[tuple[str, int]]] = {}
        chunk_len: dict[str, int] = {}
        own_missing: dict[str, int] = {}
        own_missing_ec: list[tuple[Manifest, list[ChunkRef]]] = []
        ec_digests: set[str] = set()
        # previous-epoch holders of EC shards (designated-mover order);
        # replicated digests compute theirs on demand (one ring walk)
        prev_ec_holders: dict[str, tuple[int, ...]] = {}

        def designated_mover(d: str) -> bool:
            """During a migration exactly ONE node streams a digest to
            its new owners: the first ALIVE previous-epoch holder (a
            dead mover's duty falls to the next; a digest no previous
            owner survives for is pushed best-effort by whoever holds
            a copy). Outside a migration every node pushes — the
            pre-r14 repair behavior."""
            if not migrating:
                return True
            holders = prev_ec_holders.get(d)
            if holders is None:
                holders = prev.owners(d, rf)
            for p in holders:
                if p == self.cfg.node_id:
                    return True
                if self.health.is_alive(p):
                    return False
            return True
        # One readdir snapshot of the local catalog, off the loop. It
        # serves BOTH sides of the walk below: the own-missing checks
        # (which previously paid a stat() per canonical digest) and the
        # stray detection — local copies of chunks this node is NOT a
        # canonical holder of (sloppy-quorum handoff leftovers, stale
        # placement), candidates for relocation-by-deletion once every
        # canonical holder is confirmed. Net cost vs pre-r13: one
        # listing replaces thousands of stats (gc at the end of this
        # cycle already re-lists for its own sweep, as before).
        local_digests = set(await asyncio.to_thread(
            self.store.chunks.digests))
        stray: dict[str, frozenset[int]] = {}
        for m in self.store.manifests.list():
            if m.ec is not None:
                # EC shards live at stripe-derived holders, one copy
                # each; a holder missing its shard regenerates it LOCALLY
                # via parity decode (the push loop below only relocates
                # surviving copies — it cannot invent lost bytes)
                pl = ec_placement_map(m, cur)
                pl_prev = ec_placement_map(m, prev) if migrating else {}
                miss: dict[str, int] = {}
                for d, ln in ec_shard_items(m):
                    chunk_len[d] = ln
                    ec_digests.add(d)
                    if migrating:
                        prev_ec_holders.setdefault(
                            d, tuple(pl_prev.get(d, ())))
                    for target in pl[d]:
                        if target != self.cfg.node_id:
                            need.setdefault(target, []).append((d, ln))
                        elif d not in local_digests:
                            miss[d] = ln
                if miss:
                    own_missing_ec.append(
                        (m, [ChunkRef(index=0, offset=0, length=ln,
                                      digest=d)
                             for d, ln in miss.items()]))
                continue
            for c in m.chunks:
                chunk_len[c.digest] = c.length
                targets = cur.owners(c.digest, rf)
                for target in targets:
                    if target != self.cfg.node_id:
                        need.setdefault(target, []).append(
                            (c.digest, c.length))
                    elif c.digest not in local_digests:
                        own_missing[c.digest] = c.length
                if self.cfg.node_id not in targets \
                        and c.digest in local_digests:
                    stray[c.digest] = frozenset(targets)

        repaired = 0
        # restore this node's OWN canonical copies first (lost to scrub
        # eviction or disk faults) — pushing to peers alone would leave
        # the local replica count permanently short. Batched via the same
        # grouped-fetch path downloads use (per-chunk RPCs measured ~7x
        # slower on the reconstruct bench).
        async def restore_local(got: dict[str, bytes]) -> int:
            # restored copies land through the async CAS tier: one
            # bounded-pool job for the whole batch, OFF the event loop —
            # inline puts here were the last chunk-file writes still
            # running on the loop (dfslint DFS001), and a post-outage
            # repair can restore most of a corpus in one pass
            items = list(got.items())
            stored = await self.cas.put_many(items, verify=False)
            nstored = nbytes = 0
            for (d, b), newly in zip(items, stored):
                if newly:
                    nstored += 1
                    nbytes += len(b)
                self.under_replicated.discard(d)
            if nstored:
                self.counters.inc("chunks_stored", nstored)
                self.counters.inc("bytes_stored", nbytes)
            return len(items)

        own_restored = True   # did every own-copy restore succeed?

        async def restore_missing(manifest: Manifest | None,
                                  refs: list[ChunkRef]
                                  ) -> tuple[int, bool]:
            """Pull this node's missing canonical copies in BOUNDED
            (~_FETCH_BATCH_BYTES) batches: memory stays one batch no
            matter the catalog size, and during a migration each batch
            is charged against the rebalance byte credits AND counted
            into bytesMoved — the JOINING node's pull is the dominant
            transfer of a `ring add` (every node already holds every
            manifest, so the new owner pulls its whole share), and an
            unmetered pull would void both the bandwidth bound and the
            moved-bytes accounting the r14 artifact gates. Progress
            also feeds the doctor's rebalance_stuck gauge."""
            n = 0
            ok = True
            batch: list[ChunkRef] = []
            size = 0

            async def flush() -> None:
                nonlocal n, ok, batch, size
                if not batch:
                    return
                if migrating:
                    self.ring.note_credit_stall(
                        await self.ring.credits.acquire(size))
                got = await self._gather_chunks(manifest, chunks=batch,
                                                strict=False)
                n += await restore_local(got)
                ok = ok and {r.digest for r in batch} <= set(got)
                if migrating and got:
                    self.ring.note_moved(
                        sum(len(b) for b in got.values()), pushes=0)
                batch, size = [], 0

            for r in refs:
                batch.append(r)
                size += r.length
                if size >= self._FETCH_BATCH_BYTES:
                    await flush()
            await flush()
            return n, ok

        if own_missing:
            refs = [ChunkRef(index=0, offset=0, length=ln, digest=d)
                    for d, ln in own_missing.items()]
            n_restored, ok = await restore_missing(None, refs)
            repaired += n_restored
            own_restored = ok
        # EC shards this node should hold: gather WITH the manifest so
        # the parity-decode fallback can rebuild bytes that survive
        # nowhere (a replicated chunk in that state is simply gone)
        for m, refs in own_missing_ec:
            n_restored, ok = await restore_missing(m, refs)
            repaired += n_restored
            own_restored = own_restored and ok
        verified: set[str] = set()
        # digest -> canonical holders CONFIRMED to hold it this cycle
        # (has_chunks answer or push hash-echo) — the relocation pass
        # below deletes a local stray copy only when every canonical
        # holder is in this set, so a copy is never deleted on faith
        confirmed: dict[str, set[int]] = {}
        plane = self.index
        for node_id, wanted in need.items():
            peer = self.cfg.cluster.peer(node_id)
            digests = sorted({d for d, _ in wanted})
            # peer-filter trim (docs/index.md): digests the peer's
            # filter RULES OUT skip the probe payload — they fall to
            # to_push below, and the push's hash echo is the real
            # confirmation. POSITIVES are always probed: the relocation
            # pass deletes local strays on confirmations, and a bloom
            # maybe must never stand in for one. (A stale filter can
            # only cause a redundant push the receiving put dedups.)
            probe_digests = digests
            filter_known = (plane is not None
                            and plane.local_filter is not None
                            and plane.peer_filters.state(node_id)
                            is not None)
            if filter_known:
                probe_digests = [
                    d for d in digests
                    if plane.peer_filters.contains(node_id, d)
                    is not False]
                plane.probes_skipped += len(digests) \
                    - len(probe_digests)
            try:
                have: set[str] = set()
                if probe_digests:
                    # bounded, serial slices like the push below: the
                    # peer serves a probe as ONE cas.has_many job on its
                    # 2-worker latency lane, and a whole-store list is
                    # tens of thousands of stats — past the request
                    # timeout on a slow file system, so the call was
                    # RETRIED (a second such job) while live uploads'
                    # probes waited behind both until they timed out and
                    # a healthy peer was marked dead (PERF.md §6, PR 25)
                    for i in range(0, len(probe_digests),
                                   self._PROBE_SLICE_DIGESTS):
                        resp, _ = await self.client.call(
                            peer, {"op": "has_chunks",
                                   "digests": probe_digests[
                                       i:i + self._PROBE_SLICE_DIGESTS]})
                        have.update(resp.get("have", []))
                    if filter_known:
                        for d in probe_digests:
                            if d not in have:
                                # filter said maybe, the peer says no:
                                # the observed-FP stream the /metrics
                                # index.filterFp gauge reports
                                plane.peer_filters.note_fp(node_id, d)
                elif digests:
                    plane.probe_rpcs_skipped += 1
                verified |= have
                for d in have:
                    confirmed.setdefault(d, set()).add(node_id)
                to_push = sorted(set(digests) - have)
                if migrating:
                    # one designated mover per digest: a membership
                    # change must move each byte ONCE across the
                    # cluster, not once per node walking its manifests
                    # (the moved-bytes-vs-theoretical-minimum gate of
                    # REBALANCE_r14.json)
                    to_push = [d for d in to_push if designated_mover(d)]
                # local reads ride the bounded CAS pool (one job for the
                # batch, off the loop) like every other chunk-file touch
                local = dict(await self.cas.get_many(to_push))
                payload = []
                for d in to_push:
                    b = local.get(d)
                    if b is None:
                        if d in ec_digests:
                            # EC shards are stripe-placed, not on the
                            # digest ring _fetch_chunk walks — and a
                            # shard with NO surviving copy is the
                            # holder's own parity-decode job
                            # (own_missing_ec above), not a relocation
                            continue
                        try:
                            b = await self._fetch_chunk(d, chunk_len[d])
                        # not silent: the chunk stays in
                        # under_replicated (surfaced in /metrics and the
                        # doctor snapshot) and next cycle retries
                        except DownloadError:  # dfslint: ignore[DFS007]
                            continue
                    payload.append((d, b))
                if payload:
                    # Hash-echo verification, same contract as upload
                    # (StorageNode.java:248-257): only echoed digests
                    # count. Bounded slices like upload's replicate — a
                    # repair push after a big membership change can carry
                    # most of a corpus. Serial slices on purpose: repair
                    # is background work and must not compete with live
                    # ingest for per-peer bandwidth.
                    for part in self._slice_payloads(
                            payload, self._REPLICA_SLICE_BYTES):
                        if migrating:
                            # rebalance byte credits: migration pushes
                            # are rate-bounded per node so a membership
                            # change can never starve live traffic
                            # (stall time is metered — /metrics
                            # ring.rebalance.creditStallS)
                            stalled = await self.ring.credits.acquire(
                                sum(len(b) for _, b in part))
                            self.ring.note_credit_stall(stalled)
                        echoed = set(await self.client.store_chunks(
                            peer, "", part))
                        ok = {d for d, _ in part} & echoed
                        repaired += len(ok)
                        verified |= ok
                        for d in ok:
                            confirmed.setdefault(d, set()).add(node_id)
                        if migrating and ok:
                            self.ring.note_moved(
                                sum(len(b) for d, b in part if d in ok),
                                pushes=1)
            except RpcError as e:
                # journaled (DFS007): the chunks stay in
                # under_replicated and next cycle retries, but a repair
                # push that fails every hour is a durability hole with a
                # date on it
                self.obs.event("repair_push_fail", peer=peer.node_id,
                               cause=type(e).__name__)
                continue
        # only drop repair entries we actually confirmed on a peer
        self.under_replicated -= verified
        # Relocation: sloppy-quorum handoff parked copies on
        # non-canonical nodes; once every canonical holder of such a
        # digest has CONFIRMED its copy this cycle (probe answer or
        # push echo), the local stray is redundant and is deleted —
        # completing the handoff round-trip the write path promises
        # ("repair migrates them back to canonical placement") and
        # converging the census to over-replicated == 0 after a heal.
        # EC shards never relocate this way (stripe-pinned placement).
        for d in ec_digests:
            stray.pop(d, None)
        relocated: list[str] = []
        if stray:
            def _relocate() -> list[str]:
                out = []
                for d, holders in stray.items():
                    if holders <= confirmed.get(d, set()) \
                            and self.store.chunks.delete(d):
                        out.append(d)
                return out

            relocated = await asyncio.to_thread(_relocate)
            if relocated:
                self.serve.drop_cached(relocated)
                self.counters.inc("relocated_chunks", len(relocated))
        # migration completion: this walk probed EVERY current-epoch
        # owner of EVERY digest this node's manifests reference (the
        # `need` map) — if each one confirmed its copy (has_chunks
        # answer or push hash-echo) and our own copies are whole, the
        # data has fully reached its new-epoch homes and the dual-read
        # window can close. The identity checks gate racing epoch
        # bumps: a map adopted mid-walk means these confirmations
        # were computed against a stale expectation — next cycle
        # re-judges.
        if migrating and self.ring.current is cur \
                and self.ring.previous is prev:
            complete = own_restored and all(
                all(node_id in confirmed.get(d, ())
                    for d, _ in wanted)
                for node_id, wanted in need.items())
            if complete:
                self.ring.finish_migration()
        # aged orphan sweep: chunks of aborted streaming uploads (placed
        # before their manifest existed, then never committed) have no
        # other reclamation path; the 1h grace keeps in-flight uploads
        # safe (manifest-last ordering makes their chunks look orphaned)
        swept = self.store.gc(min_age_s=3600.0)
        if swept:
            self.serve.drop_cached(swept)
            self.log.info("gc: swept %d aged orphan chunks", len(swept))
        if repaired or swept or relocated:
            # repair/GC decisions are exactly the state changes a
            # post-mortem needs dated — journal them (flight recorder)
            self.obs.event("repair", repaired=repaired,
                           sweptOrphans=len(swept),
                           relocated=len(relocated),
                           underReplicated=len(self.under_replicated))
        self.counters.inc("repairs")
        return repaired

    async def scrub_once(self) -> dict:
        """Verify every local chunk against its content address; delete
        any whose bytes no longer hash to their digest (bit rot, partial
        writes the atomic-rename discipline should prevent, disk faults)
        and queue them for repair — the next repair_once re-fetches from
        a replica and re-replicates. The reference's only integrity check
        runs at read time on the whole file (StorageNode.java:453-458);
        scrubbing finds rot before a read does."""
        scanned = corrupt = delta_missing_base = 0
        ch = self.store.chunks
        digests = ch.digests()
        # read+hash happen OFF the event loop in worker-thread batches
        # (chunks are up to max_chunk bytes; hashing one inline would
        # stall live requests — upload/download already to_thread theirs),
        # batched through sha256_many_hex like range reads are
        batch_n = 64
        for i in range(0, len(digests), batch_n):
            batch = digests[i:i + batch_n]

            def read_and_hash(ds=batch) -> list[tuple[str, str]]:
                # pre-capture delta residency so an absent read can be
                # classified: a delta get() dropped as corrupt looks
                # exactly like a raw chunk deleted mid-scrub otherwise
                pre = {d: ch.delta_base(d) for d in ds} \
                    if ch.delta_count() else {}
                blobs = [(d, ch.get(d)) for d in ds]
                present = [(d, b) for d, b in blobs if b is not None]
                hexes = sha256_many_hex([b for _, b in present])
                okmap = {d: h == d for (d, _), h in zip(present, hexes)}
                out = []
                for d, b in blobs:
                    if b is not None:
                        out.append((d, "ok" if okmap[d] else "corrupt"))
                    elif pre.get(d):
                        if ch.delta_base(d):
                            # delta resident but unreadable: the base
                            # chain is broken — find the first
                            # unresolvable link and queue THAT for
                            # repair instead of declaring the delta
                            # corrupt (docs/similarity.md)
                            cur = d
                            while (nb := ch.delta_base(cur)) is not None:
                                cur = nb
                            out.append((d, f"base:{cur}"))
                        else:
                            # get() dropped it (structural damage or
                            # digest mismatch): corrupt
                            out.append((d, "corrupt"))
                return out

            for d, status in await asyncio.to_thread(read_and_hash):
                scanned += 1
                if status == "ok":
                    continue
                if status.startswith("base:"):
                    base_d = status[5:]
                    delta_missing_base += 1
                    self.under_replicated.add(base_d)
                    self.log.warning(
                        "scrub: delta %s missing base %s — queued for "
                        "repair", d[:12], base_d[:12])
                    continue
                corrupt += 1
                if not ch.delete(d) and ch.delta_pinned(d):
                    # corrupt PINNED base: its dependent deltas all
                    # reconstruct through the rotten bytes — they are
                    # lost too. Cascade deepest-first (each delete
                    # releases the next pin), queue everything for
                    # repair, then the base delete succeeds.
                    for dep in ch.delta_dependents(d):
                        if ch.delete(dep):
                            self.serve.drop_cached([dep])
                            self.under_replicated.add(dep)
                    ch.delete(d)
                self.serve.drop_cached([d])
                self.under_replicated.add(d)
                self.log.warning("scrub: corrupt chunk %s deleted",
                                 d[:12])
        self.counters.inc("scrubs")
        if corrupt:
            self.counters.inc("scrub_corrupt", corrupt)
            self.obs.event("scrub_corrupt", scanned=scanned,
                           corrupt=corrupt)
        if delta_missing_base:
            self.counters.inc("scrub_delta_missing_base",
                              delta_missing_base)
        out = {"scanned": scanned, "corrupt": corrupt,
               "deltaMissingBase": delta_missing_base}
        if self.index is not None:
            healed = await asyncio.to_thread(
                self._scrub_index_heal, digests)
            out.update(healed)
        return out

    def _scrub_index_heal(self, cas_digests: list[str]) -> dict:
        """Index-vs-walk divergence healing (r20 satellite): the scrub
        just paid for a full CAS readdir, so diff it against the digest
        index and repair both divergence directions — digests on disk
        the index never heard of (lost WAL tail, crash between link and
        note_put) become present; digests the index believes present
        but the walk cannot find (missed delete record) are expunged.
        Phantoms are the dangerous direction — a stale "present" makes
        ``has_chunks`` vouch for bytes that do not exist — which is why
        this runs every scrub, not only at the boot rebuild. Worker
        thread: the merge pass + WAL writes are blocking."""
        # re-list rather than trusting the scan-start snapshot for the
        # on-disk side of PHANTOM decisions: a chunk stored mid-scrub
        # must not be expunged as a phantom (stale-present is the
        # direction we heal, stale-absent the index design tolerates)
        on_disk = set(self.store.chunks.digests())
        on_disk.update(cas_digests)
        in_index = {d.hex() for d in self.index.lsi.present_digests()}
        missing = on_disk - in_index       # disk has it, index doesn't
        phantom = in_index - on_disk       # index has it, disk doesn't
        for d in missing:
            self.index.note_put(d)
        for d in phantom:
            self.index.note_delete(d)
        if missing or phantom:
            self.counters.inc("index_healed_missing", len(missing))
            self.counters.inc("index_healed_phantom", len(phantom))
            self.obs.event("index_healed", missing=len(missing),
                           phantom=len(phantom))
            self.log.warning(
                "scrub: index healed (%d missing, %d phantom)",
                len(missing), len(phantom))
        return {"healedMissing": len(missing),
                "healedPhantom": len(phantom)}

    # ------------------------------------------------------------------ #
    # hot/cold tiering plane (r20, dfs_tpu.tier, docs/tiering.md)
    # ------------------------------------------------------------------ #

    async def _tier_loop(self) -> None:
        """Periodic demotion scan (started by :meth:`start` when
        ``tier.scan_interval_s > 0``). Background work: no request
        deadline, and a scan already in flight sheds the next tick
        (single-slot gate) instead of stacking."""
        deadline.clear()
        from dfs_tpu.serve.admission import ShedError
        while True:
            await asyncio.sleep(self.cfg.tier.scan_interval_s)
            try:
                await self.tier_scan_once()
            # silent on purpose: a manual POST /tier holds the single
            # slot — the loop's next tick simply retries
            except ShedError:  # dfslint: ignore[DFS007]
                continue
            # not silent: counted + journaled, and the loop must outlive
            # any one bad cycle (transient peer failures mid-demotion)
            except (RpcError, OSError, DownloadError) as e:
                self.tier.errors += 1
                self.obs.event("tier_error", where="scan", error=str(e))
                self.log.warning("tier scan failed: %s", e)

    async def tier_scan_once(self) -> dict:
        """One demotion scan (POST /tier, the worker loop): classify
        every replicated file by temperature, demote the cold tail to
        EC, and finish any half-reclaimed earlier demotions. Raises
        ShedError when a scan is already running (the single-slot
        admission class — HTTP maps it to 503 Retry-After)."""
        plane = self.tier
        cfg = self.cfg.tier
        deadline.clear()          # background-class work: a manual POST
        # /tier must not ride (and die by) the request's read budget
        async with plane.gate.slot():
            out = {"scanned": 0, "cold": 0, "demoted": 0,
                   "finished": 0, "skipped": None}
            if self.ring.migrating:
                # a rebalance in flight moves ownership under the
                # dual-read window — demotion waits for stable ground
                out["skipped"] = "migrating"
                return out
            if cfg.ec_k + 2 > len(self.ring.node_ids()):
                out["skipped"] = "ring too small for ec stripes"
                return out
            now = time.time()
            manifests = await asyncio.to_thread(self.store.manifests.list)
            entries: list[dict] = []
            by_id: dict[str, Manifest] = {}
            cold_done: list[Manifest] = []
            for m in manifests:
                if m.tier == "cold":
                    cold_done.append(m)
                    continue
                if m.ec is not None:
                    continue      # user-chosen EC layout: not ours to move
                heat, last = plane.ledger.file_temperature(
                    (c.digest for c in m.chunks), now=now)
                entries.append({"fileId": m.file_id, "bytes": m.size,
                                "heat": heat, "lastAccess": last})
                by_id[m.file_id] = m
            from dfs_tpu.tier import classify
            # the budget base counts ALREADY-COLD bytes too: the hot
            # set is a fraction of the corpus, not of the not-yet-
            # demoted remainder (which shrinks every scan)
            cold = classify(entries, cfg.hot_fraction, cfg.min_idle_s,
                            now=now,
                            total_bytes=(sum(e["bytes"]
                                             for e in entries)
                                         + sum(m.size
                                               for m in cold_done)))
            out["scanned"] = len(entries)
            out["cold"] = len(cold)
            for fid in sorted(cold):
                if fid in self._tier_promoting:
                    continue      # racing promotion wins: it has reads
                if plane.in_redemote_cooldown(fid, now=now):
                    # re-demotion hysteresis: freshly-promoted files sit
                    # out the scan for redemote_cooldown_s, so a file
                    # flapping around promote_reads cannot churn the
                    # encode/decode cycle every scan (docs/tiering.md)
                    out["cooldown"] = out.get("cooldown", 0) + 1
                    continue
                try:
                    if await self._demote_file(by_id[fid]):
                        out["demoted"] += 1
                # not silent: per-file isolation — one unreachable
                # replica set must not starve the rest of the scan
                except (RpcError, OSError, DownloadError,
                        UploadError) as e:
                    plane.errors += 1
                    self.obs.event("tier_error", where="demote",
                                   fileId=fid, error=str(e))
                    self.log.warning("tier demote %s failed: %s",
                                     fid[:12], e)
            # finish pass: earlier demotions whose surplus reclaim was
            # interrupted (crash between tier flip and deletes, stale
            # peers that refused) — idempotent, skipped once confirmed
            # clean at this ring epoch
            for m in cold_done:
                if self._tier_surplus_done.get(m.file_id) \
                        == self.ring.epoch:
                    continue
                try:
                    await self._tier_delete_surplus(m)
                    out["finished"] += 1
                # not silent: same per-file isolation as the demote loop
                except (RpcError, OSError) as e:
                    plane.errors += 1
                    self.obs.event("tier_error", where="finish",
                                   fileId=m.file_id, error=str(e))
            plane.scans += 1
            plane.last_scan_at = now
            plane.note_progress()
            await asyncio.to_thread(plane.snapshot_ledger)
            self.obs.event("tier_scan", scanned=out["scanned"],
                           cold=out["cold"], demoted=out["demoted"],
                           finished=out["finished"])
            return out

    async def _demote_file(self, m: Manifest) -> bool:
        """Demote one cold replicated file to EC: gather its bytes,
        encode parity, place data+parity at the stripe-derived single
        holders, commit the cold manifest (the durable tier flip —
        fsync-barriered like every manifest save), then reclaim the
        surplus replicas. Ordered so a crash at ANY point leaves the
        file readable: parity before flip (a flip without parity would
        strip redundancy), flip before deletes (deletes only remove
        copies the cold layout no longer expects)."""
        import dataclasses

        plane = self.tier
        plane.note_credit_stall(await plane.credits.acquire(m.size))
        data = await self._gather_chunks(m)
        cold_m, parity = await asyncio.to_thread(
            self._ec_extend_from, dataclasses.replace(m, tier="cold"),
            data, self.cfg.tier.ec_k)
        seen: set[str] = set()
        batch: list[tuple[str, bytes]] = []
        for c in m.chunks:
            if c.digest not in seen:
                seen.add(c.digest)
                batch.append((c.digest, data[c.digest]))
        for d, b in parity:
            if d not in seen:     # k=1 makes Q == P (upload's rule)
                seen.add(d)
                batch.append((d, b))
        stats = self._new_upload_stats()
        placement = ec_placement_map(cold_m, self.ring.current)
        await self._place_batch(m.file_id, batch, stats, rf=1,
                                placement=placement)
        if self.chaos is not None:
            self.chaos.maybe_crash("demote.after_parity_write")
        # the COMMIT: a tombstone landing mid-demotion wins — the file
        # was deleted, so the cold layout must not resurrect it
        if not await asyncio.to_thread(self.store.manifests.save,
                                       cold_m):
            return False
        if self.index is not None:
            def flip():
                for d in sorted({c.digest for c in m.chunks}):
                    self.index.note_tier(d, True)
            await asyncio.to_thread(flip)
        if self.chaos is not None:
            self.chaos.maybe_crash("demote.after_tier_flip")
        await self._announce_all(cold_m)
        pbytes = sum(len(b) for _, b in parity)
        plane.demoted_files += 1
        plane.demoted_bytes += m.size
        plane.parity_bytes += pbytes
        plane.note_progress()
        self.counters.inc("tier_demotions")
        self.obs.event("tier_demote", fileId=m.file_id, bytes=m.size,
                       parityBytes=pbytes)
        await self._tier_delete_surplus(cold_m)
        return True

    async def _tier_delete_surplus(self, m: Manifest) -> tuple[int, int]:
        """Reclaim replica copies the cold layout no longer expects —
        locally via the same re-derivation peers use (a digest SHARED
        with a hot manifest keeps its replicas), remotely via the
        ``delete_chunks`` op, where each peer re-derives its OWN
        expected set and refuses anything it still believes it owns.
        ``refused > 0`` means some peer holds a stale (replicated) view
        of this manifest — re-announce the cold manifest so the next
        pass converges. Returns (removed, refused) across the cluster."""
        if self.chaos is not None:
            self.chaos.maybe_crash("demote.before_replica_delete")
        digests = sorted({c.digest for c in m.chunks})
        length = {c.digest: c.length for c in m.chunks}
        plane = self.tier

        def local_reclaim() -> list[str]:
            expected = self._expected_digests_here(set(digests))
            return [d for d in digests
                    if d not in expected and self.store.chunks.delete(d)]

        removed_local = await asyncio.to_thread(local_reclaim)
        self.serve.drop_cached(removed_local)
        removed = len(removed_local)
        refused = 0
        plane.reclaimed_bytes += sum(length[d] for d in removed_local)

        async def one(peer) -> tuple[list[str], int]:
            try:
                resp, _ = await self.client.call(
                    peer, {"op": "delete_chunks", "digests": digests},
                    retries=1)
                return (resp.get("removed") or [],
                        len(resp.get("refused") or []))
            # not silent: an unreachable peer counts as refused — the
            # finish pass retries next scan
            except RpcError:  # dfslint: ignore[DFS007]
                return [], len(digests)

        for got, ref in await asyncio.gather(
                *(one(p) for p in self._peers())):
            removed += len(got)
            refused += ref
            plane.reclaimed_bytes += sum(
                length.get(d, 0) for d in got)
        if refused:
            # stale peers (missed the demote announce) refuse deletes —
            # the safe direction; converge them and retry next scan
            await self._announce_all(m)
            self._tier_surplus_done.pop(m.file_id, None)
        else:
            self._tier_surplus_done[m.file_id] = self.ring.epoch
        plane.note_progress()
        return removed, refused

    def _expected_digests_here(self, candidates: set[str]) -> set[str]:
        """The subset of ``candidates`` this node is a canonical holder
        of under its OWN manifests + ring view: EC manifests pin via the
        stripe placement map, replicated manifests via the digest ring.
        Worker-thread code (manifest walk). The reclaim paths delete
        only what this never returns — first-party evidence, never the
        caller's claim."""
        out: set[str] = set()
        rf = self.cfg.cluster.replication_factor
        ring = self.ring.current
        me = self.cfg.node_id
        for m in self.store.manifests.list():
            if m.ec is not None:
                pl = ec_placement_map(m, ring)
                for d in m.all_digests():
                    if d in candidates and me in pl.get(d, ()):
                        out.add(d)
            else:
                for c in m.chunks:
                    if c.digest in candidates \
                            and me in ring.owners(c.digest, rf):
                        out.add(c.digest)
            if len(out) == len(candidates):
                break
        return out

    def _tier_maybe_promote(self, manifest: Manifest) -> None:
        """Read-path promotion check (download_stream): a cold file
        whose decayed heat crossed ``promote_reads`` re-materializes
        replicated in the background. The triggering read itself is
        served by the transparent EC decode — promotion is never on the
        read's critical path."""
        if self.tier is None or manifest.tier != "cold":
            return
        if manifest.file_id in self._tier_promoting:
            return
        heat, _ = self.tier.ledger.file_temperature(
            c.digest for c in manifest.chunks)
        if heat < self.cfg.tier.promote_reads:
            return
        self._tier_promoting.add(manifest.file_id)
        create_logged_task(self._promote_file(manifest), self.log,
                           "tier-promote")

    async def _promote_file(self, m: Manifest) -> None:
        """Re-materialize a hot-again cold file at full replication:
        gather (EC decode fills any dead holder), place at the digest
        ring's rf owners, commit the hot manifest, then reclaim the
        now-unreferenced parity through the delete_chunks discipline.
        Mirror-ordered to demotion: replicas before flip, flip before
        parity deletes."""
        import dataclasses

        plane = self.tier
        deadline.clear()          # spawned from a request's context —
        # background re-materialization must not inherit its budget
        try:
            plane.note_credit_stall(await plane.credits.acquire(m.size))
            data = await self._gather_chunks(m)
            hot_m = dataclasses.replace(m, ec=None, tier=None)
            seen: set[str] = set()
            batch: list[tuple[str, bytes]] = []
            for c in m.chunks:
                if c.digest not in seen:
                    seen.add(c.digest)
                    batch.append((c.digest, data[c.digest]))
            stats = self._new_upload_stats()
            await self._place_batch(m.file_id, batch, stats)
            # the COMMIT (tombstone race aborts, as in demotion)
            if not await asyncio.to_thread(self.store.manifests.save,
                                           hot_m):
                return
            if self.index is not None:
                def flip():
                    for d in sorted(seen):
                        self.index.note_tier(d, False)
                await asyncio.to_thread(flip)
            await self._announce_all(hot_m)
            self._tier_surplus_done.pop(m.file_id, None)
            await self._tier_reclaim_parity(m)
            plane.promoted_files += 1
            plane.promoted_bytes += m.size
            plane.note_promoted(m.file_id)   # re-demotion hysteresis
            plane.note_progress()
            self.counters.inc("tier_promotions")
            self.obs.event("tier_promote", fileId=m.file_id,
                           bytes=m.size)
        # not silent: counted + journaled; the file stays cold and a
        # later read re-triggers promotion
        except (RpcError, OSError, DownloadError,
                UploadError) as e:
            plane.errors += 1
            self.obs.event("tier_error", where="promote",
                           fileId=m.file_id, error=str(e))
            self.log.warning("tier promote %s failed: %s",
                             m.file_id[:12], e)
        finally:
            self._tier_promoting.discard(m.file_id)

    async def _tier_reclaim_parity(self, m: Manifest) -> tuple[int, int]:
        """Delete the parity chunks a promotion orphaned — same
        receiver-re-derives discipline as surplus reclaim (a peer whose
        manifests still expect the parity, e.g. one that missed the
        hot announce, refuses; the re-announce converges it)."""
        if m.ec is None:
            return 0, 0
        parity = sorted({d for st in m.ec.stripes for d in (st.p, st.q)})

        def local() -> int:
            expected = self._expected_digests_here(set(parity))
            return sum(1 for d in parity
                       if d not in expected
                       and self.store.chunks.delete(d))

        removed = await asyncio.to_thread(local)
        self.serve.drop_cached(parity)
        refused = 0

        async def one(peer) -> tuple[int, int]:
            try:
                resp, _ = await self.client.call(
                    peer, {"op": "delete_chunks", "digests": parity},
                    retries=1)
                return (len(resp.get("removed") or []),
                        len(resp.get("refused") or []))
            # not silent: unreachable = refused; aged GC is the backstop
            except RpcError:  # dfslint: ignore[DFS007]
                return 0, len(parity)

        for got, ref in await asyncio.gather(
                *(one(p) for p in self._peers())):
            removed += got
            refused += ref
        return removed, refused

    async def _announce_all(self, manifest: Manifest) -> None:
        """Best-effort manifest announce to every peer (the
        _finalize_upload fan-out WITHOUT fresh=True: a tier flip must
        bounce off tombstones, never resurrect a deleted file)."""
        mj = manifest.to_json()

        async def announce(peer) -> None:
            try:
                await self.client.announce(peer, mj)
            except RpcError as e:
                self.log.warning("announce to node %d failed: %s",
                                 peer.node_id, e)
                self.counters.inc("announce_failures")

        await asyncio.gather(*(announce(p) for p in self._peers()))

    def tier_stats(self) -> dict:
        """``/metrics`` ``tier`` section. The enabled/hotFraction/
        minIdleS/scanIntervalS/ecK/demoteCreditBytes/halfLifeS/
        promoteReads/ledgerEntries keys mirror TierConfig fields
        (dfslint DFS005 checks the config ⇄ CLI ⇄ metrics mapping);
        the rest is live plane state. ``{"enabled": False}`` is the
        whole story for the default tier-less node."""
        t = self.cfg.tier
        plane = self.tier
        out = {"enabled": t.enabled,
               "hotFraction": t.hot_fraction,
               "minIdleS": t.min_idle_s,
               "scanIntervalS": t.scan_interval_s,
               "ecK": t.ec_k,
               "demoteCreditBytes": t.demote_credit_bytes,
               "halfLifeS": t.half_life_s,
               "promoteReads": t.promote_reads,
               "redemoteCooldownS": t.redemote_cooldown_s,
               "ledgerEntries": t.ledger_entries}
        if plane is None:
            return {"enabled": False}
        out["ledgerSize"] = len(plane.ledger)
        out["scans"] = plane.scans
        out["demotedFiles"] = plane.demoted_files
        out["demotedBytes"] = plane.demoted_bytes
        out["parityBytes"] = plane.parity_bytes
        out["reclaimedBytes"] = plane.reclaimed_bytes
        out["promotedFiles"] = plane.promoted_files
        out["promotedBytes"] = plane.promoted_bytes
        out["errors"] = plane.errors
        out["creditStallS"] = round(plane.credit_stall_s, 3)
        out["sinceProgressS"] = round(
            time.monotonic() - plane.last_progress_at, 3)
        out["admission"] = plane.gate.stats()
        return out

    def sim_stats(self) -> dict:
        """``/metrics`` ``sim`` section. The enabled/sketchSize/bands/
        shingleBytes/maxCandidates/minChunkBytes/minSavingsFrac/
        maxDeltaDepth/devices/rematerializeReads keys mirror SimConfig
        fields (dfslint DFS005 checks the config ⇄ CLI ⇄ metrics
        mapping); the rest is live plane + store state.
        ``{"enabled": False}`` is the whole story for the default
        sim-less node."""
        s = self.cfg.sim
        plane = self.sim
        out = {"enabled": s.enabled,
               "sketchSize": s.sketch_size,
               "bands": s.bands,
               "shingleBytes": s.shingle_bytes,
               "maxCandidates": s.max_candidates,
               "minChunkBytes": s.min_chunk_bytes,
               "minSavingsFrac": s.min_savings_frac,
               "maxDeltaDepth": s.max_delta_depth,
               "devices": s.devices,
               "rematerializeReads": s.rematerialize_reads}
        if plane is None:
            return {"enabled": False}
        out.update(plane.stats())
        out["deltaChunks"] = self.store.chunks.delta_count()
        return out
