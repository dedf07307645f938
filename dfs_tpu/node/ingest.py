"""The upload verb — the node's upper write layer (docs/ingest.md): how a
body becomes batches (whole, streamed, resumed, or pre-staged by a smart
client), the byte credits and the placement window that bound it, and
the ack. Who gets the bytes is :mod:`dfs_tpu.node.placement`'s; this
module calls down into it and knows neither the server nor the HTTP
edge."""

from __future__ import annotations

import asyncio
import dataclasses
import queue
import threading
import time
from collections import deque
from typing import Awaitable, Callable, Mapping

from dfs_tpu.comm.rpc import DeadlineExpired, RpcError, RpcUnreachable
from dfs_tpu.meta.manifest import (ChunkRef, EcInfo, Manifest, StripeRef,
                                   ec_stripe_groups, stripe_shard_len)
from dfs_tpu.node.errors import UploadError
from dfs_tpu.node.placement import (Placement, TrustLedger,
                                    ec_placement_map, new_upload_stats)
from dfs_tpu.utils.hashing import (is_hex_digest, sha256_hex,
                                   sha256_many_hex, sha256_new)
from dfs_tpu.utils.logging import get_logger


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


def merge_upload_stats(into: dict, part: dict) -> None:
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


class Ingest:
    """One node's upload verb, its collaborators named once. The four
    entry points differ in where the batches come from; they share the
    ack (:meth:`_ack`)."""

    # upload_resume assembles (and places) in batches of this many
    # bytes — the read path's gather bound, for the same memory reason
    _RESUME_BATCH_BYTES = 32 * 1024 * 1024

    def __init__(self, cfg, fragmenter, placement: Placement, cas, client,
                 health, ring, manifests, *, index, obs, counters, stalls,
                 chaos,
                 fetch_verified: Callable[..., Awaitable[dict]]) -> None:
        self.cfg = cfg
        self.fragmenter = fragmenter
        self.placement = placement
        self.cas = cas                    # AsyncChunkStore
        self.client = client              # InternalClient
        self.health = health              # HealthMonitor
        self.ring = ring                  # RingManager
        self.manifests = manifests        # the node's ManifestStore
        self.index = index                # IndexPlane or None
        self.obs = obs
        self.counters = counters
        self.stalls = stalls              # the node's ingest stopwatches
        self.chaos = chaos                # ChaosInjector or None
        # the read path's verified batch fetch: resume and commit read
        # back chunks the client did not send
        self.fetch_verified = fetch_verified
        # streaming-ingest flush size: config-driven, an instance
        # attribute so tests/benches can still scale it per node
        self.flush_bytes = cfg.ingest.flush_bytes
        self.log = get_logger("node", cfg.node_id)

    # ------------------------------------------------------------------ #
    # upload (L4) — reference handleUpload, StorageNode.java:115-181
    # ------------------------------------------------------------------ #

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

        stats = new_upload_stats()
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
        pinned = None
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
                # the batch IS the digest -> payload map the encode
                # reads (every chunk of the file, each once)
                manifest, parity = await asyncio.to_thread(
                    self.ec_extend, manifest, dict(batch), ec_k)
            for d, b in parity:
                # per-item seen check: P and Q can share a digest
                # (k=1 makes Q == P), and a lazy bulk-extend would
                # place it twice
                if d not in seen:
                    seen.add(d)
                    batch.append((d, b))
            stats["ecParityBytes"] = sum(len(b) for _, b in parity)
            pinned = ec_placement_map(manifest, self.ring.current)
            rf = 1   # the parity IS the redundancy (any 2 shards may die)
        ledger = self.placement.new_ledger()
        await self.placement.place(file_id, batch, stats, rf=rf,
                                   placement=pinned, ledger=ledger)
        return await self._ack(manifest, stats, ledger, rf=rf,
                               pinned=pinned)

    def ec_extend(self, manifest: Manifest,
                  chunk_bytes: Mapping[str, bytes], k: int
                  ) -> tuple[Manifest, list[tuple[str, bytes]]]:
        """Compute P+Q parity per stripe of ``k`` data chunks and return
        the EC manifest plus the parity (digest, payload) list. An
        object's stripes are packed into a few fixed widths
        (``ops.ec.batch_width``: powers of two, six from 2 KiB to the
        default ``max_chunk``) and each run of one width is ONE
        ``encode_pq_batch`` call — 6 calls a 16 MiB object where a call
        a stripe was ~600, each at a length of its own. Which twin runs
        is what ``utils.device.holds_tpu`` says of THIS process: NumPy on
        a node that only talks to a chip owner (``JAX_PLATFORMS=cpu``,
        whatever the engine is called) and on a CPU engine; the jitted
        twin, on the chip, where the node's own engine took it. The
        manifest is byte-identical either way. Payloads come from a
        digest map, never one contiguous buffer — the shape upload's
        batch and tier demotion's gathered dict both have. Runs in a
        worker thread — NumPy/encode work; the spans ``ec.pack``,
        ``ec.math`` and ``ec.hash`` nest under the caller's
        ``upload.ec_encode``."""
        import numpy as np

        from dfs_tpu.ops import ec as ec_ops
        from dfs_tpu.utils.device import holds_tpu

        device = holds_tpu()
        groups = ec_stripe_groups(manifest.chunks, k)
        pads = [stripe_shard_len(grp) for grp in groups]
        stripes: list[StripeRef] = []
        parity: list[tuple[str, bytes]] = []
        calls = 0
        lo = 0
        while lo < len(groups):
            # groups come sorted by length, so a width is one run of them
            width = ec_ops.batch_width(pads[lo])
            hi = lo + 1
            most = lo + ec_ops.batch_rows(k, width)
            while hi < min(most, len(groups)) and pads[hi] <= width:
                hi += 1
            with self.obs.span("ec.pack"):
                sh = np.zeros((hi - lo, k, width), dtype=np.uint8)
                for row, grp in zip(sh, groups[lo:hi]):
                    # a short last stripe takes the LAST slots (ops.ec)
                    for shard, c in zip(row[k - len(grp):], grp):
                        shard[:c.length] = np.frombuffer(
                            chunk_bytes[c.digest], dtype=np.uint8,
                            count=c.length)
            with self.obs.span("ec.math"):
                p, q = ec_ops.encode_pq_batch(sh, device=device)
            calls += 1
            with self.obs.span("ec.hash"):
                for prow, qrow, pad in zip(p, q, pads[lo:hi]):
                    pb, qb = prow[:pad].tobytes(), qrow[:pad].tobytes()
                    pd, qd = sha256_hex(pb), sha256_hex(qb)
                    stripes.append(StripeRef(p=pd, q=qd, shard_len=pad))
                    parity.append((pd, pb))
                    parity.append((qd, qb))
            lo = hi
        self.counters.inc("ec_objects")
        self.counters.inc("ec_stripes", len(stripes))
        self.counters.inc("ec_encode_calls", calls)
        self.counters.inc("ec_parity_bytes", 2 * sum(pads))
        ec = EcInfo(k=k, stripes=tuple(stripes))
        return dataclasses.replace(manifest, ec=ec), parity

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
        return await _StreamUpload(self, blocks, name).run()

    # ------------------------------------------------------------------ #
    # resume and single-hop commit: the client brings the chunk table
    # ------------------------------------------------------------------ #

    async def missing_digests(self, digests: list[str]) -> list[str]:
        """Which of ``digests`` the cluster holds NOwhere reachable —
        the resumable-upload probe (SURVEY §5.4: chunk-level resume falls
        out of the dedup index). Local CAS first — ONE batched
        ``has_many`` job of the CAS latency lane (this loop used to
        stat inline ON the event loop, one syscall per digest); the
        remainder is asked of each digest's replica set via batched
        has_chunks, with peer-filter-ruled-out digests never probed at
        all. Both take a resident answer (``resident_ok``): this is
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
                found.update(await self.client.has_chunks(
                    self.cfg.cluster.peer(nid), ds, resident_ok=True,
                    retries=1))
            except RpcError:
                # best-effort: an unanswered probe only makes the client
                # resend bytes the cluster already has — but count it
                # (DFS007): habitual probe failures silently erase the
                # resume/dedup win
                self.counters.inc("probe_failures")

        await asyncio.gather(*(probe(n, ds) for n, ds in by_peer.items()))
        return [d for d in missing if d not in found]

    def _table_manifest(self, table: list[tuple[int, int, str]],
                        name: str, file_id: str, size: int) -> Manifest:
        """The manifest a client's chunk table describes, after the
        table's sanity check: a contiguous tiling of [0, size)."""
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
        return Manifest(file_id=file_id,
                        name=name or f"file-{file_id[:8]}",  # reference default naming
                        size=size, fragmenter=self.fragmenter.name,
                        chunks=tuple(refs))

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
        manifest = self._table_manifest(table, name, file_id, size)
        hexes = await asyncio.to_thread(
            sha256_many_hex, list(provided.values()))
        for d, h in zip(provided, hexes):
            if d != h:
                raise UploadError(f"provided chunk {d[:12]}… hash mismatch",
                                  status=400)

        # assemble incrementally (batches) to verify the whole-stream
        # hash AND place everything; bytes come from `provided`, the
        # local CAS, or replicas
        refs = manifest.chunks
        stats = new_upload_stats()
        stats["bytes"] = sum(len(b) for b in provided.values())
        hasher = sha256_new()
        seen: set[str] = set()
        ledger = self.placement.new_ledger()
        batch: list = []
        bsize = 0
        for c in refs:
            batch.append(c)
            bsize += c.length
            if bsize >= self._RESUME_BATCH_BYTES or c is refs[-1]:
                got = dict(provided)
                need = [x for x in batch if x.digest not in got]
                if need:
                    # digest-verified like every read path: a rotten
                    # local copy of an interrupted upload's chunk heals
                    # from a replica instead of failing the resume with
                    # a client-blaming hash error forever
                    got.update(await self.fetch_verified(
                        manifest, need, strict=False))
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
                await self.placement.place(file_id, place, stats,
                                           ledger=ledger)
                batch, bsize = [], 0
        if hasher.hexdigest() != file_id:
            raise UploadError("resumed stream does not hash to fileId",
                              status=400)
        stats["uniqueChunks"] = len(seen)
        return await self._ack(manifest, stats, ledger,
                               counter="uploads_resumed")

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
        manifest = self._table_manifest(table, name, file_id, size)
        stats = new_upload_stats()
        stats["bytes"] = size
        rf = self.cfg.cluster.replication_factor
        quorum = min(self.cfg.write_quorum, rf,
                     len(self.ring.current.active_ids()))
        digests = list(dict.fromkeys(dg for _, _, dg in table))
        copies = await self._count_copies(digests, rf)
        confirmed = {d: n for d, n in copies.items() if n >= quorum}
        stats["dedupSkippedBytes"] = sum(
            ln for _, ln, dg in table if dg in confirmed)
        below = {d for d in digests if d not in confirmed}
        if below:
            # heal below-quorum chunks pre-ack: fetch the bytes (local
            # CAS, then any replica — the client may have reached SOME
            # owners) and re-place through the normal batch path, which
            # re-probes, transfers, and falls to handoff as needed.
            # Chunks absent everywhere 409 — the ack was never given.
            self.obs.event("commit_replace", chunks=len(below))
            dedup: set[str] = set()
            need = [c for c in manifest.chunks if c.digest in below
                    and not (c.digest in dedup or dedup.add(c.digest))]
            fetched = await self.fetch_verified(manifest, need,
                                                strict=False)
            absent = [c.digest for c in need if c.digest not in fetched]
            if absent:
                raise UploadError(
                    "commit missing chunks: "
                    + ",".join(d[:12] for d in absent), status=409)
            await self.placement.place(
                file_id, [(c.digest, fetched[c.digest]) for c in need],
                stats)
        stats["uniqueChunks"] = len(digests)
        batch_min = min(confirmed.values(), default=rf)
        stats["minCopies"] = batch_min if stats["minCopies"] is None \
            else min(stats["minCopies"], batch_min)
        stats["degraded"] = stats["degraded"] or batch_min < rf
        return await self._ack(manifest, stats,
                               counter="uploads_committed")

    async def _count_copies(self, digests: list[str], rf: int
                            ) -> dict[str, int]:
        """Durable copies of each digest at its ring owners, counted
        first-party: the local CAS, then one real has_chunks round per
        owner peer — the same pre-ack discipline as verify_trusted."""
        ring = self.ring.current
        cache = self.placement.echo_cache
        copies = {d: 0 for d in digests}
        # local holdings first (this node is an owner for its arc)
        mask = await self.cas.has_many(digests, resident_ok=True)
        for d, h in zip(digests, mask):
            if h:
                copies[d] += 1
        by_peer: dict[int, list[str]] = {}
        for d in digests:
            for t in ring.owners(d, rf):
                if t != self.cfg.node_id:
                    by_peer.setdefault(t, []).append(d)

        async def probe(nid: int, ds: list[str]) -> set[str]:
            try:
                have = await self.client.has_chunks(
                    self.cfg.cluster.peer(nid), ds, resident_ok=True,
                    retries=None if self.health.is_alive(nid) else 1)
                self.health.mark_alive(nid)
                return have
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
        return copies

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

    # ------------------------------------------------------------------ #
    # the ack: what every entry point ends with
    # ------------------------------------------------------------------ #

    async def _ack(self, manifest: Manifest, stats: dict,
                   ledger: TrustLedger | None = None, *,
                   rf: int | None = None,
                   pinned: Mapping[str, tuple[int, ...]] | None = None,
                   counter: str | None = None) -> tuple[Manifest, dict]:
        """Every batch is placed: confirm what was credited on trust —
        every filter-credited copy across every placed batch, in ONE
        has_chunks round per peer (docs/index.md) — THEN write the
        manifest that acks the upload, and count it."""
        if stats["minCopies"] is None:     # zero-chunk (empty) upload
            stats["minCopies"] = self.cfg.cluster.replication_factor
        if ledger:
            await self.placement.verify_trusted(
                manifest.file_id, ledger, stats, rf=rf, placement=pinned)
        with self.obs.span("upload.commit"):
            await self._finalize(manifest)
        if counter:
            self.counters.inc(counter)
        self.counters.inc("upload_bytes", manifest.size)
        return manifest, stats

    async def _finalize(self, manifest: Manifest) -> None:
        """The commit: ONE pass in a worker thread on each node, every
        node's started at once (docs/ingest.md "The commit").

        Manifest-last ordering (SURVEY.md §5.4) is the caller's: every
        batch is placed and verified before this runs. Nothing orders
        the nodes' saves among themselves, so the peers' announces
        (best-effort — reference: a failure is only logged,
        StorageNode.java:338-346) run BESIDE the local save, and the ack
        waits for the slower of the two, not for their sum. Both saves
        are fresh: a new upload clears tombstones (here and, through
        ``fresh=True``, at the peers) — re-uploading deleted content
        must resurrect the content-derived file id, not leave it
        permanently undownloadable — inside the save's own pass
        (``ManifestStore.save``). With fsync durability a save is a disk
        BARRIER (file + dir) and this is the write that acks the upload:
        no call of it runs on the loop.

        A local save that fails leaves an unacked upload whose manifest
        some peers hold — the mirror image of a crash at
        ``upload.after_manifest`` — and the manifest pull and the
        tombstones' last-writer-wins converge it as they do that one;
        its chunks are durable on ``rf`` nodes either way."""
        if self.chaos is not None:
            self.chaos.maybe_crash("upload.before_manifest")
        mj = manifest.to_json()     # once: saved here, sent to every peer

        async def local() -> bool:
            with self.obs.span("commit.save"):
                saved = await asyncio.to_thread(
                    self.manifests.save, manifest, fresh=True, text=mj)
            if saved and self.chaos is not None:
                # local manifest durable, announces possibly in flight
                self.chaos.maybe_crash("upload.after_manifest")
            return saved

        async def announce(peer) -> None:
            try:
                await self.client.announce(peer, mj, fresh=True)
            except RpcError as e:
                self.log.warning("announce to node %d failed: %s",
                                 peer.node_id, e)
                self.counters.inc("announce_failures")

        async def announce_all() -> None:
            with self.obs.span("commit.announce"):
                await asyncio.gather(*(
                    announce(p) for p in self.cfg.cluster.peers
                    if p.node_id != self.cfg.node_id))

        # return_exceptions: a failed save still waits for the announces
        # already sent — no task is left behind a failed upload
        saved, told = await asyncio.gather(local(), announce_all(),
                                           return_exceptions=True)
        if isinstance(saved, OSError):
            self.placement.raise_if_disk_full(saved)
        for failed in (saved, told):
            if isinstance(failed, BaseException):
                raise failed
        if not saved:
            raise UploadError("manifest save refused (tombstone race)")
        # the document as saved (ASCII: its characters are its bytes)
        self.counters.inc("manifests_saved")
        self.counters.inc("manifest_bytes", len(mj))
        self.counters.inc("manifest_chunks", len(manifest.chunks))
        self.counters.inc("uploads")


# The fragmenter thread crosses to the event loop once per HAND-OFF, not
# once per chunk: it holds the chunks an engine (or a sidecar reply)
# gives it until this share of ``ingest.credit_bytes`` is held — never
# more than one placement batch (``flush_bytes``), so no batch starts
# later than its last chunk's hand-off — or the budget is full, or the
# engine returns. What is held is already charged to the budget.
_HANDOFF_CREDIT_SHARE = 4


class _StreamUpload:
    """One streamed upload's pipeline and what its stages share: the
    feeder (socket → ``inq``), the fragmenter thread (``inq`` → chunks,
    gated by byte credits → hand-offs on ``outq``), and the consume loop
    (``outq`` → batches → up to ``ingest.window`` placements in
    flight)."""

    def __init__(self, ing: Ingest, blocks, name: str) -> None:
        self.ing = ing
        self.blocks = blocks
        self.name = name
        self.loop = asyncio.get_running_loop()
        self.inq: queue.Queue = queue.Queue(maxsize=4)
        self.outq: asyncio.Queue = asyncio.Queue()
        self.hasher = sha256_new()
        self.frag_dead = threading.Event()
        self.aborted = threading.Event()
        # byte credits: the fragmenter thread blocks once this many
        # produced-but-unconsumed payload BYTES are outstanding, which
        # stops it draining inq, which blocks the feeder, which stops
        # reading the socket — TCP backpressure end to end. Without it a
        # fast client outruns slow replication and the 'bounded-memory'
        # contract silently fails. (Counting chunks instead of bytes —
        # the gate until round 7 — let max-size chunks oversubscribe the
        # budget by orders of magnitude.)
        self.credits = ByteBudget(ing.cfg.ingest.credit_bytes)
        # the fragmenter thread's own: chunks charged and not yet handed
        # to the loop, and the seconds it spent on them (``seamReplyS``)
        self.held: list[tuple[str, bytes]] = []
        self.held_bytes = 0
        self.handoff_bytes = max(1, min(
            self.credits.budget // _HANDOFF_CREDIT_SHARE, ing.flush_bytes))
        # ... since the last hand-off. (Every stopwatch of a stream is
        # added to when the wait happens — a block, a hand-off — not at
        # the stream's end: a stream that lasts half a minute would land
        # whole in one reading of /metrics, or in none.)
        self.seam_s = 0.0
        self.stats = new_upload_stats()
        self.seen: set[str] = set()
        self.window = max(1, ing.cfg.ingest.window)
        # (task, per-batch stats) in submission order — awaited FIFO so
        # stats merge deterministically and the FIRST failing batch is
        # the one that aborts the stream
        self.inflight: deque[tuple[asyncio.Task, dict]] = deque()
        self.ledger = ing.placement.new_ledger()

    # ---- the fragmenter's thread -------------------------------------- #

    def _feed_iter(self):
        while True:
            try:
                b = self.inq.get(timeout=0.5)
            except queue.Empty:
                # abort must not depend on the end-of-stream sentinel
                # arriving: the feeder's cancelled finally submits it
                # through the shared to_thread pool, which can be
                # saturated — a fragmenter parked in a bare get()
                # would deadlock the abort path's gather forever
                if self.aborted.is_set():
                    return
                continue
            if b is None:
                return
            yield b

    def _on_chunk(self, digest: str, payload: bytes) -> None:
        t0 = time.perf_counter()
        n, waited = len(payload), 0.0
        if not self.credits.acquire(n, timeout=0):
            # the budget is full: what is held goes over first — the
            # consume loop releases only what it has been handed
            self._hand_off()
            t1 = time.perf_counter()
            while not self.credits.acquire(n, timeout=0.5):
                if self.aborted.is_set():
                    raise RuntimeError("upload aborted")
            waited = time.perf_counter() - t1
            if waited > 0.001:   # stall attribution: chunking blocked
                # on unconsumed output (downstream placement is the
                # bottleneck); sub-ms lock noise is not a stall
                self.ing.stalls.add("creditS", waited)
        self.held.append((digest, payload))
        self.held_bytes += n
        if self.held_bytes >= self.handoff_bytes:
            self._hand_off()
        self.seam_s += time.perf_counter() - t0 - waited

    def _hand_off(self) -> None:
        """Everything held crosses to the loop as ONE list, in stream
        order; nothing crosses once the upload is aborted."""
        if self.aborted.is_set():
            raise RuntimeError("upload aborted")
        if self.held:
            self.loop.call_soon_threadsafe(self.outq.put_nowait, self.held)
            self.ing.counters.inc("seam_handoffs")
            self.ing.counters.inc("seam_chunks", len(self.held))
            self.held, self.held_bytes = [], 0
            self.ing.stalls.add("seamReplyS", self.seam_s)
            self.seam_s = 0.0

    def _run_fragmenter(self) -> None:
        put = self.outq.put_nowait
        try:
            # to_thread copied the request's context: the span (the
            # owner seam as this node sees it, same name as the
            # whole-payload path's) parents to the request, and a
            # chip owner's spans hang under it
            with self.ing.obs.span("upload.fragment", latency=True):
                m = self.ing.fragmenter.manifest_stream(
                    self._feed_iter(), name=self.name or "stream",
                    store=self._on_chunk)
                self._hand_off()          # what the engine's end left
            self.loop.call_soon_threadsafe(put, ("done", m))
        # not silent: surfaced to the async consumer via the
        # ("error", e) queue item, which re-raises on the loop
        except BaseException as e:  # dfslint: ignore[DFS007]
            self.loop.call_soon_threadsafe(put, ("error", e))
        finally:
            # chunks an abort or an engine's failure left here were
            # charged and will never be consumed
            self.credits.release(self.held_bytes)
            self.ing.stalls.add("seamReplyS", self.seam_s)
            self.frag_dead.set()

    def _put_block(self, b) -> None:
        # bounded put that cannot deadlock: if the fragmenter thread
        # died it stopped draining inq, so give up instead of blocking
        # a worker thread (and the feeder await) forever
        while not self.frag_dead.is_set():
            try:
                self.inq.put(b, timeout=0.5)
                return
            except queue.Full:
                continue

    # ---- the event loop ------------------------------------------------ #

    async def _feeder(self) -> int:
        total = 0
        # the body's two waits, told apart: for the next block from
        # the socket (the client, or TCP backpressure) and for
        # put_block (the fragmenter side is not draining inq)
        waited = self.ing.stalls.add        # as it happens, a block
        with self.ing.obs.span("upload.body") as sp:
            try:
                t = time.perf_counter()
                async for b in self.blocks:
                    waited("bodyWaitS", time.perf_counter() - t)
                    if self.aborted.is_set():
                        break    # placement failed: stop reading, do
                        # NOT drain the rest of the body into memory
                    total += len(b)
                    self.hasher.update(b)
                    t = time.perf_counter()
                    await asyncio.to_thread(self._put_block, b)
                    now = time.perf_counter()
                    waited("feedWaitS", now - t)
                    t = now
                else:       # the wait that found the body's end
                    waited("bodyWaitS", time.perf_counter() - t)
            finally:
                await asyncio.to_thread(self._put_block, None)
                sp.bytes = total
        return total

    async def _drain_one(self) -> None:
        task, bstats = self.inflight[0]
        # removed only AFTER the await resolves: if THIS coroutine
        # is cancelled mid-await (client hung up), the still-running
        # placement must remain in `inflight` so the abort path
        # cancels and reaps it — popping first leaked it
        await task
        self.inflight.popleft()
        merge_upload_stats(self.stats, bstats)

    async def _submit(self, b: list[tuple[str, bytes]]) -> None:
        # file_id is only known at stream end; batches placed before that
        # tag transfers with a placeholder (store_chunks ignores it)
        place, inflight = self.ing.placement.place, self.inflight
        if self.window == 1:     # serial placement: the historical
            # schedule, byte-identical behavior
            await place("", b, self.stats, ledger=self.ledger)
            return
        while len(inflight) >= self.window:
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
                await self._drain_one()       # FIFO merge
            else:
                await asyncio.wait(
                    [t for t, _ in inflight if not t.done()],
                    return_when=asyncio.FIRST_COMPLETED)
            self.ing.stalls.add("placementS", time.perf_counter() - t0)
        bstats = new_upload_stats()
        task = asyncio.create_task(
            place("", b, bstats, ledger=self.ledger))
        # completion wakes the consume loop via a sentinel: a
        # FAILED placement must abort the stream even while the
        # consumer is parked on outq behind a slow client — without
        # the wakeup, abort latency was coupled to body progress
        task.add_done_callback(
            lambda t: self.outq.put_nowait(("placed", t)))
        inflight.append((task, bstats))
        self.ing.stalls.peak("placeWindow", len(inflight))

    async def _consume(self) -> Manifest:
        """Batch the fragmenter's chunks into placements until its
        manifest arrives; returns it with every batch placed."""
        inflight = self.inflight
        batch: list[tuple[str, bytes]] = []
        pending = 0
        while True:
            # merge (and surface failures of) any placements that
            # already resolved, oldest first
            while inflight and inflight[0][0].done():
                await self._drain_one()
            item = await self.outq.get()
            if type(item) is tuple:      # ("placed" | "error" | "done", _)
                tag, what = item
                if tag == "placed":
                    if not what.cancelled() \
                            and what.exception() is not None:
                        await what   # re-raise the placement failure
                        # NOW — reading the body stops immediately
                    continue         # success: head drain above merges
                if tag == "error":
                    raise UploadError(f"fragmenter failed: {what}")
                manifest = what
                break
            # a hand-off: chunks in stream order. Its credit goes back
            # once — and before a batch it fills is submitted: a batch
            # in flight counts against the window, not the budget —
            # while duplicates and the batch cut are per chunk
            freed = 0
            for digest, payload in item:
                freed += len(payload)
                if digest in self.seen:
                    continue
                self.seen.add(digest)
                batch.append((digest, payload))
                pending += len(payload)
                if pending >= self.ing.flush_bytes:
                    self.credits.release(freed)
                    freed = 0
                    await self._submit(batch)
                    batch, pending = [], 0
            self.credits.release(freed)
        if batch:
            await self._submit(batch)
        while inflight:        # tail drain: the stream is chunked,
            t0 = time.perf_counter()   # only placement remains
            await self._drain_one()
            self.ing.stalls.add("placementS", time.perf_counter() - t0)
        return manifest

    async def run(self) -> tuple[Manifest, dict]:
        frag_task = asyncio.create_task(
            asyncio.to_thread(self._run_fragmenter))
        feed_task = asyncio.create_task(self._feeder())
        try:
            chunked = await self._consume()
        except BaseException:
            self.aborted.set()             # unblock fragmenter + feeder
            # the feeder may be parked in a socket read with no timeout
            # (a stalled client mid-body) — cancel it rather than wait
            # for the next block that may never come; its finally still
            # hands the fragmenter the end-of-stream sentinel
            feed_task.cancel()
            for task, _ in self.inflight:  # first failure aborts: stop
                task.cancel()              # sibling placements too
            await asyncio.gather(feed_task, frag_task,
                                 *(t for t, _ in self.inflight),
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
        file_id = self.hasher.hexdigest()
        manifest = Manifest(file_id=file_id,
                            name=self.name or f"file-{file_id[:8]}",
                            size=total, fragmenter=chunked.fragmenter,
                            chunks=chunked.chunks)
        self.stats["bytes"] = total
        self.stats["uniqueChunks"] = len(self.seen)
        return await self.ing._ack(manifest, self.stats, self.ledger)
