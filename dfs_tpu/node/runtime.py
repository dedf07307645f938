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
import dataclasses
import math
import time
from typing import Sequence

from dfs_tpu.comm.rpc import (DeadlineExpired, InternalClient, RpcError,
                              RpcRemoteError, RpcUnreachable, slice_payloads)
from dfs_tpu.comm.wire import (FrameServerProtocol, WireError, encode_frame,
                               pack_chunks, unpack_chunks)
from dfs_tpu.config import NodeConfig
from dfs_tpu.fragmenter.base import get_fragmenter
from dfs_tpu.meta.manifest import ChunkRef, Manifest, ec_stripe_groups
from dfs_tpu.node.errors import (DeadlineExceeded, DownloadError,
                                 NotFoundError, RangeNotSatisfiable,
                                 UploadError)
from dfs_tpu.node.health import HealthMonitor
from dfs_tpu.node.ingest import Ingest
from dfs_tpu.node.placement import (Placement, ec_placement_map,
                                    ec_shard_items, new_upload_stats)
from dfs_tpu.node.repair import ManifestMemo
from dfs_tpu.node.repair import walk as repair_walk
from dfs_tpu.obs import Observability, Span, parse_wire_trace
from dfs_tpu.ring.manager import RingManager
from dfs_tpu.serve import BatchPrefetcher, ServingTier
from dfs_tpu.store.aio import AsyncChunkStore
from dfs_tpu.store.cas import NodeStore
from dfs_tpu.utils import deadline
from dfs_tpu.utils.hashing import (is_hex_digest, sha256_hex,
                                   sha256_many_hex, sha256_new)
from dfs_tpu.utils.aio import create_logged_task, on_loop_seconds
from dfs_tpu.utils.logging import Counters, Stopwatches, get_logger
from dfs_tpu.utils.trace import LatencyRecorder


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


def _config_fingerprint(cfg: NodeConfig) -> str:
    """sha256 over the SHARED config surface — everything that should be
    identical across a healthy cluster. Node-local identity fields
    (node_id, data_root, sidecar_port) are excluded so the doctor's
    config_drift rule compares policy, not identity."""
    import json as _json

    d = dataclasses.asdict(cfg)
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
        # manifests the repair cycle's pass remembers between cycles
        # (node/repair.py), and the seconds the cycles held the loop
        self._repair_memo = ManifestMemo(self.store)
        self._repair_on_loop_s = 0.0
        # async CAS tier: every event-loop chunk put/get routes through a
        # bounded thread pool (store/aio.py) — the loop never blocks on
        # chunk file I/O and disk concurrency is explicit
        self.cas = AsyncChunkStore(self.store.chunks,
                                   workers=cfg.ingest.cas_io_threads,
                                   obs=self.obs)
        if cfg.sidecar_port:
            # delegate chunk+hash to a sidecar process (north-star shape:
            # device init/compiles never block the serving loop)
            from dfs_tpu.sidecar.service import SidecarFragmenter

            fragmenter = SidecarFragmenter(cfg.sidecar_port)
        else:
            fragmenter = get_fragmenter(
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
        # the write path's two layers (docs/ingest.md), each handed its
        # collaborators by name: batch placement below, the upload verb
        # above it. The read path lends each its fetch.
        self.placement = Placement(
            cfg, self.ring, self.cas, self.client, self.health,
            index=self.index, hedge=self.serve.hedge, obs=self.obs,
            counters=self.counters, stalls=self.ingest_stalls,
            chaos=self.chaos, under_replicated=self.under_replicated,
            fetch_chunk=self._fetch_chunk)
        self.ingest = Ingest(
            cfg, fragmenter, self.placement, self.cas, self.client,
            self.health, self.ring, self.store.manifests,
            index=self.index, obs=self.obs, counters=self.counters,
            stalls=self.ingest_stalls, chaos=self.chaos,
            fetch_verified=self._fetch_verified)
        self._internal_server: asyncio.AbstractServer | None = None
        self._http_server: asyncio.AbstractServer | None = None
        self._inbound: set[FrameServerProtocol] = set()  # live peer conns

    @property
    def fragmenter(self):
        """The chunk+hash engine: ingest's, shown here for the stats,
        the lifecycle and whoever swaps it on a running node."""
        return self.ingest.fragmenter

    @fragmenter.setter
    def fragmenter(self, engine) -> None:
        self.ingest.fragmenter = engine

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
        # manifest write with the same path aborted streams already use.
        # The sweep's listing also seeds the chunk store's resident set,
        # complete from here on: "absent" needs no stat (store/cas.py)
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
            # answer is a look at the disk, a stat a digest. Either way,
            # where the CALLER sets `residentOk` (placement's probes and
            # pre-ack rounds only) the store's resident set answers
            # first (store/cas.py has) — "present", and, complete since
            # the boot sweep seeded it, "absent" too: no stat and no
            # lookup for a digest nobody has. A caller that does not
            # send the key — the repair cycle, who_has, an older peer —
            # is answered from the index or the disk, and a look at the
            # disk heals the set.
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
            # off-loop: with fsync durability the save is a disk barrier
            # — and a fresh one clears the tombstone in the same pass
            if await asyncio.to_thread(self.store.manifests.save, m,
                                       fresh=bool(header.get("fresh"))):
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
        return await self.ingest.upload(data, name, ec_k)

    async def upload_stream(self, blocks, name: str) -> tuple[Manifest, dict]:
        return await self.ingest.upload_stream(blocks, name)

    async def upload_resume(self, table, name: str, file_id: str,
                            size: int, provided: dict[str, bytes]
                            ) -> tuple[Manifest, dict]:
        return await self.ingest.upload_resume(table, name, file_id, size,
                                               provided)

    async def commit_manifest(self, table, name: str, file_id: str,
                              size: int) -> tuple[Manifest, dict]:
        return await self.ingest.commit_manifest(table, name, file_id, size)

    async def missing_digests(self, digests: list[str]) -> list[str]:
        return await self.ingest.missing_digests(digests)

    def dataplane_info(self) -> dict:
        return self.ingest.dataplane_info()

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
        return await self._walk_replicas(digest, length, candidates)

    async def _walk_replicas(self, digest: str, length: int,
                             candidates: list[int]) -> bytes:
        """:meth:`_fetch_chunk`'s candidate walk: one replica after
        another, the first VERIFIED answer wins. Under a hedge policy
        ("The Tail at Scale", docs/serve.md) a replica that has not
        answered within ``HedgePolicy.delay_s`` of the best replica's
        windowed mean latency races the next one in the
        (dual-read/ring-aware) candidate order; the loser is cancelled,
        and every hedge draws from the node's token bucket so hedging
        can never double cluster fetch load. A hedge changes WHEN the
        next replica is asked, never what counts as an answer.
        Coalesced readers (serve/rpc single-flight) share the leader's
        hedge decision by construction: the hedge fires inside the one
        flight they all await."""
        hedge = self.serve.hedge
        rf = self.cfg.cluster.replication_factor

        async def attempt(nid: int) -> bytes | None:
            """One replica's verified bytes, or None — miss, corrupt,
            or dead."""
            try:
                data = await self.client.get_chunk(
                    self.cfg.cluster.peer(nid), digest)
                self.health.mark_alive(nid)
            except DeadlineExpired as e:
                # the budget died, not the replicas: stop the walk —
                # touring the remaining candidates would count each
                # refusal as a remote miss (placement-skew evidence)
                # and waste exactly the work the deadline forbids
                raise DeadlineExceeded(str(e)) from e
            except RpcUnreachable:
                self.health.mark_dead(nid)
                return None
            except RpcError:
                # live peer without the chunk — not a death signal, but
                # counted (DFS007): a ring walk that keeps missing is
                # placement skew the terminal DownloadError hides
                self.counters.inc("remote_chunk_misses")
                return None
            # Verify against the manifest digest before trusting a peer
            # (stronger than the reference, which only checks the whole file).
            if len(data) == length and sha256_hex(data) == digest:
                return data
            self.log.warning("corrupt chunk %s from node %d",
                             digest[:12], nid)
            return None

        def accept(data: bytes, src: int) -> bytes:
            self.counters.inc("chunks_fetched_remote")
            if self.ring.is_prev_only(digest, src, rf):
                # served through the dual-read window: the byte came
                # from a previous-epoch owner mid-move
                self.ring.note_dual_read_hit()
            return data

        i = 0
        while i < len(candidates):
            nid = candidates[i]
            backup_id = candidates[i + 1] \
                if hedge is not None and i + 1 < len(candidates) else None
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
                src, asked = node_id, list(batch)
                expect = sum(need[d] for d in asked)

                async def issue(nid: int):
                    # known-dead peers get one fast probe, not the full
                    # retry envelope (same rule replication uses) — a
                    # degraded EC read would otherwise pay retries per
                    # batch for holders that died
                    return await self.client.get_chunks(
                        self.cfg.cluster.peer(nid), asked,
                        retries=None if self.health.is_alive(nid) else 1,
                        expect_bytes=expect)

                try:
                    if backup_id is not None:
                        hedge = self.serve.hedge
                        got, src = await hedge.race(
                            issue, node_id, backup_id, op="get_chunks",
                            # best-replica seed, not the primary's own
                            # mean — see RpcStats.recent_best_mean for
                            # the observed failure mode
                            delay_s=hedge.delay_s(
                                self.obs.rpc_client.recent_best_mean(
                                    "get_chunks")),
                            event=self.obs.event,
                            mark_dead=self.health.mark_dead,
                            chunks=len(asked))
                    else:
                        got = await issue(node_id)
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
                    for d in await self.client.has_chunks(
                            self.cfg.cluster.peer(nid), missing,
                            retries=1):
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
        counted = self.counters.snapshot()
        return {"window": ing.window,
                "flushBytes": self.ingest.flush_bytes,
                "creditBytes": ing.credit_bytes,
                "sliceInflight": ing.slice_inflight,
                "stalls": self.ingest_stalls.snapshot(),
                # the owner seam: crossings from a fragmenter thread to
                # the loop, and the chunks they carried; where a chip
                # owner chunks, the tee's peak and its waits at the cap
                "seam": {"handoffs": counted.get("seam_handoffs", 0),
                         "chunks": counted.get("seam_chunks", 0),
                         **self.fragmenter.tee_stats()},
                # the documents the acks saved (``Ingest._finalize``)
                "commit": {"manifests": counted.get("manifests_saved", 0),
                           "manifestBytes":
                               counted.get("manifest_bytes", 0),
                           "manifestChunks":
                               counted.get("manifest_chunks", 0)},
                "cas": self.cas.stats()}

    def ec_stats(self) -> dict:
        """Erasure coding for /metrics "ec" (``Ingest.ec_extend``, at
        ingest or at a demotion): objects encoded, their stripes, the
        ``encode_pq_batch`` calls that took (one a width bucket), and
        the parity bytes made, before placement's dedup."""
        counted = self.counters.snapshot()
        return {"objects": counted.get("ec_objects", 0),
                "stripes": counted.get("ec_stripes", 0),
                "encodeCalls": counted.get("ec_encode_calls", 0),
                "parityBytes": counted.get("ec_parity_bytes", 0)}

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
        existence check — "present" or, complete, "absent" — in place of
        a ``stat`` or an index lookup; ``put`` the
        put job's phase clock (``ChunkStore.put_stats``): calls, items,
        new files, and the write workers' seconds by phase."""
        return {"mode": self.cfg.durability.mode,
                "fsyncs": self.store.chunks.fsync_count(),
                "dirBarriers": self.store.chunks.dir_barrier_count(),
                **self.store.chunks.resident_stats(),
                **self.store.chunks.look_stats(),
                "put": self.store.chunks.put_stats()}

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
            # finish_migration). One trace a cycle — `repair.cycle`, with
            # `repair.walk`, a `repair.probe` a peer and `repair.sweep`
            # under it — and the seconds it held the loop between awaits
            # (/metrics repair.onLoopS), which is what live requests wait
            # for; the rest of a cycle is spent in worker threads and
            # on peers.
            with self.obs.request_span("repair.cycle"):
                return await on_loop_seconds(self._repair_once_locked(),
                                             self._repair_held_loop)

    def _repair_held_loop(self, seconds: float) -> None:
        self._repair_on_loop_s += seconds

    def repair_stats(self) -> dict:
        """``/metrics`` ``repair`` section: cycles ended, manifests the
        cycles' passes read and parsed (ever) against those remembered
        now (node/repair.py ManifestMemo), and the seconds the cycles
        held the event loop."""
        return {"cycles": self.counters.snapshot().get("repairs", 0),
                "manifestsRead": self._repair_memo.read,
                "manifestsRemembered": self._repair_memo.remembered,
                "onLoopS": round(self._repair_on_loop_s, 6)}

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
        # the pass over the manifests — what this node lacks, what each
        # peer should hold, what is stray — in a worker thread, from
        # manifests remembered since the last cycle (node/repair.py):
        # it shares nothing mutable with the loop but the two maps
        # taken above
        with self.obs.span("repair.walk"):
            walked = await asyncio.to_thread(
                repair_walk, self.store, self._repair_memo,
                self.cfg.node_id, rf, cur, prev)
        need = walked.need
        chunk_len = walked.chunk_len
        own_missing = walked.own_missing
        own_missing_ec = walked.own_missing_ec
        ec_digests = walked.ec_digests
        # previous-epoch holders of EC shards (designated-mover order);
        # replicated digests compute theirs on demand (one ring walk)
        prev_ec_holders = walked.prev_ec_holders
        stray = walked.stray

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

        async def peer_pass(node_id: int, wanted) -> int:
            """Probe one peer for what it should hold, push what it
            lacks; returns the chunks pushed and echoed."""
            nonlocal verified
            repaired = 0
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
                        have |= await self.client.has_chunks(
                            peer, probe_digests[
                                i:i + self._PROBE_SLICE_DIGESTS])
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
                    for part in slice_payloads(
                            payload, self.placement.slice_bytes):
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
            return repaired

        for node_id, wanted in need.items():
            with self.obs.span("repair.probe", peer=node_id):
                repaired += await peer_pass(node_id, wanted)
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
        # — in a worker thread, over the pass's listing and rows; the
        # manifests saved since the pass are read before anything is
        # deleted (store/cas.py sweep_orphans)
        with self.obs.span("repair.sweep"):
            swept = await asyncio.to_thread(
                self.store.sweep_orphans, walked.local_digests,
                set(chunk_len), 3600.0,
                lambda: self._repair_memo.named_since(walked.seen))
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
            # and a look at the disk: the store's resident set stands in
            # front of the index for placement's callers, and the
            # backstop's stat drops the entry (or re-records a name
            # linked since the listing)
            self.store.chunks.has(d)
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
        plane = self.tier
        plane.note_credit_stall(await plane.credits.acquire(m.size))
        data = await self._gather_chunks(m)
        cold_m, parity = await asyncio.to_thread(
            self.ingest.ec_extend,
            dataclasses.replace(m, tier="cold"),
            data, self.cfg.tier.ec_k)
        # every shard once: a file repeats chunks, and k=1 makes Q == P
        batch = {c.digest: data[c.digest] for c in m.chunks}
        batch.update(parity)
        await self.placement.place(
            m.file_id, list(batch.items()), new_upload_stats(), rf=1,
            placement=ec_placement_map(cold_m, self.ring.current))
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
        plane = self.tier
        deadline.clear()          # spawned from a request's context —
        # background re-materialization must not inherit its budget
        try:
            plane.note_credit_stall(await plane.credits.acquire(m.size))
            data = await self._gather_chunks(m)
            hot_m = dataclasses.replace(m, ec=None, tier=None)
            batch = {c.digest: data[c.digest] for c in m.chunks}
            await self.placement.place(m.file_id, list(batch.items()),
                                       new_upload_stats())
            # the COMMIT (tombstone race aborts, as in demotion)
            if not await asyncio.to_thread(self.store.manifests.save,
                                           hot_m):
                return
            if self.index is not None:
                def flip():
                    for d in sorted(batch):
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
        upload ack's fan-out WITHOUT fresh=True: a tier flip must
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
        out["promoting"] = len(self._tier_promoting)   # in flight now
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
