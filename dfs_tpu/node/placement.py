"""Chunk placement — the node's lower layer: who gets a batch's bytes,
who is asked, who is credited, and whether quorum was met.

First, the list-of-ids shims over the membership ring (dfs_tpu.ring).
Until r14 they WERE the placement policy — content-derived cyclic
replica sets over a fixed, boot-time node list (the primary is
``int(digest[:16], 16) mod N`` and replicas follow cyclically,
preserving the reference's cyclic-×2 redundancy geometry while making
placement deterministic from content alone). That math now lives in
:mod:`dfs_tpu.ring` as the STATIC ring mode (``RingMap.static``), the
epoch-0 compilation every default-config cluster runs — byte-stable
with the pre-r14 behavior by construction. The shims remain as the
convenience surface of tests, benches and standalone tools.

Then batch placement itself (docs/ingest.md): :class:`Placement` holds a
node's collaborators, one :class:`BatchPlacement` per batch holds what
its steps share. Ingest and the tier plane call in; nothing here knows
the server, the HTTP edge or the upload verb.
"""

from __future__ import annotations

import asyncio
import dataclasses
import errno
import threading
import types
from typing import Awaitable, Callable, Collection, Mapping, Sequence

from dfs_tpu.comm.rpc import (DeadlineExpired, RpcError, RpcUnreachable,
                              slice_payloads)
from dfs_tpu.meta.manifest import Manifest, ec_stripe_groups
from dfs_tpu.node.errors import DeadlineExceeded, DownloadError, UploadError
from dfs_tpu.ring import (RingMap, static_ec_shard_node,
                          static_handoff_order, static_replica_set)
from dfs_tpu.utils.aio import gather_abort_siblings
from dfs_tpu.utils.logging import get_logger


def replica_set(digest: str, node_ids: list[int], rf: int) -> list[int]:
    """Deterministic replica node-ids for a chunk digest over a STATIC
    membership list (``node_ids`` must be the same sorted list on every
    node) — the epoch-0 ring's owner set."""
    return static_replica_set(digest, node_ids, rf)


def ec_shard_node(file_id: str, stripe: int, shard: int,
                  node_ids: list[int]) -> int:
    """Holder of shard ``shard`` (0..k-1 data, k = P, k+1 = Q) of
    erasure stripe ``stripe`` over a static membership list.
    Digest-derived placement would let two shards of a stripe collide
    on one node — then a single node loss can exceed the P+Q budget —
    so the stripe's base derives from (file_id, stripe) and shards fan
    out consecutively, all distinct whenever the cluster is big enough
    (upload enforces k+2 <= N). Computable from the manifest alone."""
    return static_ec_shard_node(file_id, stripe, shard, node_ids)


def handoff_order(pinned: Sequence[int],
                  node_ids: list[int]) -> list[int]:
    """The agreed candidate order for a PINNED (erasure-coded) shard
    over a static membership list: its pinned holders, then the
    membership ring cyclically from the first pinned holder. The write
    side's sloppy-quorum handoff and the read side's candidate walk
    must agree on this order (see RingMap.handoff_order for the
    hash-mode generalization)."""
    return static_handoff_order(pinned, node_ids)


# ---------------------------------------------------------------------- #
# erasure-coded placement: stripe-derived single holders
# ---------------------------------------------------------------------- #

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
        # the repair cycle's pass asks from a worker thread (PR 35),
        # reads from the loop: the eviction iterates the dict
        with _EC_PLACEMENT_MU:
            if len(_EC_PLACEMENT_CACHE) >= 64:
                _EC_PLACEMENT_CACHE.pop(next(iter(_EC_PLACEMENT_CACHE)))
            _EC_PLACEMENT_CACHE[key] = hit
    return hit


_EC_PLACEMENT_CACHE: dict = {}
_EC_PLACEMENT_MU = threading.Lock()


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


# ---------------------------------------------------------------------- #
# batch placement
# ---------------------------------------------------------------------- #

def new_upload_stats() -> dict:
    """What placing a file's batches reports; every caller starts one."""
    return {"bytes": 0, "uniqueChunks": 0, "transferredBytes": 0,
            "dedupSkippedBytes": 0, "minCopies": None,
            "handoffChunks": 0, "degraded": False}


class TrustLedger:
    """Filter-credited replica copies awaiting pre-ack verification.

    When placement trusts a peer-filter POSITIVE (skipping both the
    has_chunks probe and the transfer — the re-upload fast path,
    docs/index.md), the copy it credited is a bloom ``maybe``, not a
    fact. Every trusted (peer, digest, length) lands here, and
    :meth:`Placement.verify_trusted` confirms the whole ledger
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


@dataclasses.dataclass
class BatchPlan:
    """Who gets a batch's bytes first: this node's own puts and one
    leg's list per peer, with the copy count the quorum check reads."""
    local_puts: list[tuple[str, bytes]]
    per_node: dict[int, list[tuple[str, bytes]]]
    copies: dict[str, int]
    payload_of: dict[str, bytes]


def plan_batch(ring: RingMap, self_id: int,
               batch: list[tuple[str, bytes]], rf: int,
               pinned: Mapping[str, tuple[int, ...]]) -> BatchPlan:
    """A batch's primary targets: ``pinned`` holders (EC stripe
    placement) where given, else the digest's ``rf`` ring owners. A
    local target is credited at once — a failed local put fails the
    batch."""
    plan = BatchPlan([], {}, {}, {})
    for digest, payload in batch:
        plan.copies[digest] = 0
        plan.payload_of[digest] = payload
        for target in pinned.get(digest) or ring.owners(digest, rf):
            if target == self_id:
                plan.local_puts.append((digest, payload))
                plan.copies[digest] += 1
            else:
                plan.per_node.setdefault(target, []).append(
                    (digest, payload))
    return plan


def filter_credits(plan: BatchPlan, plane,
                   is_alive: Callable[[int], bool]) -> dict[int, set[str]]:
    """The filter positives each primary leg may credit unasked
    (docs/index.md §3), settled before a leg starts. A chunk this node
    does not own, which no leg would be sent or asked about, would be
    credited by every filter and stored nowhere this node can vouch
    for — and its payload leaves with the batch, so the pre-ack verify
    round could name two false positives but heal neither. The first
    leg that would credit such a chunk asks its peer instead, side by
    side with the other legs; the rest may credit it. ``plane`` is the
    index plane, or None (nothing is credited)."""
    maybe: dict[int, set[str]] = {}
    if plane is None or plane.local_filter is None:
        return maybe
    cache = plane.echo_cache
    real = {d for d, _ in plan.local_puts}
    for nid, wanted in plan.per_node.items():
        if not is_alive(nid):
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


class Placement:
    """One node's batch placement: its collaborators, named once. The
    lower layer of the write path — ingest and the tier plane call
    :meth:`place`; it calls the chunk store and the RPC client."""

    # per-RPC payload cap for replication slices (see BatchPlacement.
    # _leg); class-level so tests/benches can scale it per node
    slice_bytes = 8 * 1024 * 1024

    def __init__(self, cfg, ring, cas, client, health, *, index, hedge,
                 obs, counters, stalls, chaos, under_replicated: set[str],
                 fetch_chunk: Callable[[str, int], Awaitable[bytes]]
                 ) -> None:
        self.cfg = cfg
        self.ring = ring                  # RingManager
        self.cas = cas                    # AsyncChunkStore
        self.client = client              # InternalClient
        self.health = health              # HealthMonitor
        self.index = index                # IndexPlane or None
        self.hedge = hedge                # HedgePolicy or None
        self.obs = obs
        self.counters = counters
        self.stalls = stalls              # the node's ingest stopwatches
        self.chaos = chaos                # ChaosInjector or None
        self.under_replicated = under_replicated    # the repair queue
        # the read path's one-chunk fetch: verify_trusted's heal re-reads
        # bytes that left with their batch
        self.fetch_chunk = fetch_chunk
        self.log = get_logger("node", cfg.node_id)

    @property
    def echo_cache(self):
        return self.index.echo_cache if self.index is not None else None

    def new_ledger(self) -> TrustLedger | None:
        """A trust ledger when the filter plane is on, else None (the
        pre-index placement path, probe per batch per peer)."""
        if self.index is not None and self.index.local_filter is not None:
            return TrustLedger()
        return None

    def raise_if_disk_full(self, e: OSError) -> None:
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

    async def place(self, file_id: str, batch: list[tuple[str, bytes]],
                    stats: dict, rf: int | None = None,
                    placement: Mapping[str, tuple[int, ...]] | None = None,
                    ledger: TrustLedger | None = None) -> None:
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
        :meth:`verify_trusted` on the ledger before acking), except
        that a chunk nothing else would vouch for is put to one of its
        peers (``filter_credits``) or, with no ledger, probed as before
        minus the ruled-out payload."""
        with self.obs.span("upload.place"):
            if self.chaos is not None:
                self.chaos.maybe_crash("place.before_local_put")
            await BatchPlacement(self, file_id, batch, stats, rf,
                                 placement or {}, ledger).run()

    async def verify_trusted(self, file_id: str, ledger: TrustLedger,
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
                digests = sorted(entries)
                try:
                    have = await self.client.has_chunks(
                        self.cfg.cluster.peer(node_id), digests,
                        resident_ok=True)
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
                    b = await self.fetch_chunk(d, ln)
                except DeadlineExceeded:
                    raise          # budget died: 503-class, never a
                    # "held nowhere reachable" 500
                except DownloadError:
                    raise UploadError(
                        f"filter-credited chunk {d[:12]}… held nowhere "
                        "reachable — retry the upload (the filter "
                        "override now forces a real transfer)")
            items.append((d, b))
        await self.place(file_id, items, stats, rf=rf, placement=placement)


class BatchPlacement:
    """One batch on its way to quorum: what the steps share (the plan,
    the copy counts, the byte accounting, the ring map taken ONCE) and
    the steps, in the order :meth:`run` takes them. Event loop only."""

    def __init__(self, env: Placement, file_id: str,
                 batch: list[tuple[str, bytes]], stats: dict,
                 rf: int | None, pinned: Mapping[str, tuple[int, ...]],
                 ledger: TrustLedger | None) -> None:
        self.env = env
        self.file_id = file_id
        self.stats = stats
        self.ledger = ledger
        self.pinned = pinned
        # placement snapshot: ONE ring map for the whole batch — a
        # concurrent epoch adoption must not split a batch between two
        # maps (the rebalancer reconciles whole batches placed under
        # either epoch; a half-and-half batch would satisfy neither)
        self.ring = env.ring.current
        self.ids = self.ring.active_ids()
        if env.echo_cache is not None:
            # pin the echo cache to this batch's epoch: an adoption
            # since the last batch clears every session confirmation
            # (ownership moved — docs/client.md §filter freshness)
            env.echo_cache.note_epoch(self.ring.epoch)
        self.rf = env.cfg.cluster.replication_factor if rf is None else rf
        self.plan = plan_batch(self.ring, env.cfg.node_id, batch, self.rf,
                               pinned)
        self.copies = self.plan.copies
        # (peer, digest) pairs whose bytes are already accounted in
        # transferredBytes/dedupSkippedBytes: a chunk's bytes count at
        # most ONCE per peer across the primary and handoff passes, so
        # repeated handoff probes cannot double-count one transfer
        self.counted: set[tuple[int, str]] = set()

    def _primary_targets(self, digest: str) -> Sequence[int]:
        return self.pinned.get(digest) or self.ring.owners(digest, self.rf)

    def _handoff_ring(self, digest: str) -> list[int]:
        pinned = self.pinned.get(digest)
        if not pinned:
            return self.ring.owners(digest, len(self.ids))
        return self.ring.handoff_order(pinned)

    async def run(self) -> None:
        env, plan = self.env, self.plan
        with env.obs.span("upload.replicate", latency=True):
            credits = filter_credits(plan, env.index, env.health.is_alive) \
                if self.ledger is not None else {}
            await self._gather(
                self._put_local(plan.local_puts),
                *(self._leg(nid, w, credits.get(nid, ()))
                  for nid, w in plan.per_node.items()))
        if env.chaos is not None:
            env.chaos.maybe_crash("place.after_replicate")
        # Effective quorum: write_quorum can't exceed the copies placement
        # will ever make — rf (the policy) or the cluster size (a 1-node
        # cluster's single copy IS every copy in the world). Without the
        # clamp a legal `--nodes 1` deployment fails every upload.
        quorum = min(env.cfg.write_quorum, self.rf, len(self.ids))
        with env.obs.span("upload.handoff", latency=True):
            handoff = await self._handoff_rounds(quorum)
        self._settle(quorum, handoff)

    async def _gather(self, *jobs) -> None:
        try:
            await gather_abort_siblings(*jobs)
        except OSError as e:
            self.env.raise_if_disk_full(e)
            raise

    async def _put_local(self, items: list[tuple[str, bytes]],
                         count_dedup: bool = True) -> None:
        # local canonical copies through the async CAS tier: one
        # bounded-pool job for the whole list, OFF the event loop
        # (inline puts occupied it for the full writeback pass) and
        # overlapping peer replication instead of preceding it. A
        # failed put still fails the batch via the gather.
        counters = self.env.counters
        results = await self.env.cas.put_many(items, verify=False)
        nstored = nbytes = 0
        for (d, b), newly in zip(items, results):
            if newly:
                nstored += 1
                nbytes += len(b)
        if nstored:
            counters.inc("chunks_stored", nstored)
            counters.inc("bytes_stored", nbytes)
        if count_dedup and len(items) > nstored:
            counters.inc("dedup_hits", len(items) - nstored)

    def _skip(self, node_id: int, d: str, b: bytes) -> bool:
        """Credit a copy ``node_id`` holds without a transfer; True the
        first time its bytes are accounted for that peer."""
        self.copies[d] += 1
        if (node_id, d) in self.counted:
            return False
        self.counted.add((node_id, d))
        self.stats["dedupSkippedBytes"] += len(b)
        return True

    async def _leg(self, node_id: int, wanted: list[tuple[str, bytes]],
                   credit: Collection[str] = ()) -> None:
        """One peer's share of the batch — existence, then transfer.
        Primary and handoff legs alike; ``credit`` is the filter
        positives this leg may take on trust (a handoff leg is given
        none and asks about each)."""
        env = self.env
        cache = env.echo_cache
        # Known-dead peers get one fast probe instead of the full retry
        # envelope (health registry, SURVEY.md §5.3).
        retries = None if env.health.is_alive(node_id) else 1
        try:
            with env.obs.span("upload.probe"):
                remaining, have, staged, trusted = await self._existence(
                    node_id, wanted, credit, retries)
            for d, b in remaining:
                if d in have:
                    # durable on the peer no matter what later slices
                    # do — credit the copy immediately
                    if cache is not None:
                        cache.confirm(node_id, d)
                    if self._skip(node_id, d, b):
                        env.counters.inc("dedup_remote_hits")
            missing = [(d, b) for d, b in remaining
                       if d not in have and d not in trusted]
            if missing:
                # bounded RPCs: the receiver recomputes the hash echo
                # of everything in one call before replying, so an
                # unbounded payload turns into an unbounded server
                # pass — a ~300 MB push under 1-core contention blew
                # the request timeout and failed a whole 2 GiB-corpus
                # upload below quorum; bounded slices keep each
                # call's work (and any retry's re-send) small
                slices = staged if staged is not None and not have \
                    else slice_payloads(missing, env.slice_bytes)
                await self._transfer(node_id, missing, slices)
            env.health.mark_alive(node_id)
        except DeadlineExpired:
            # the caller's budget died, not the peer: abort the upload
            # as a 503-class refusal (through run()'s gather) —
            # swallowing it here would count every peer as a
            # replication failure and end in a quorum-fail 500 on a
            # healthy cluster
            raise
        except RpcError as e:
            env.log.warning("replication to node %d failed: %s",
                            node_id, e)
            env.counters.inc("replication_failures")
            if isinstance(e, RpcUnreachable):
                # only transport-level exhaustion is liveness evidence;
                # an application error came from a live peer
                env.health.mark_dead(node_id)
                if cache is not None:
                    # session confirmations were about THAT process;
                    # its successor re-earns them
                    cache.drop(node_id)

    async def _existence(self, node_id: int,
                         wanted: list[tuple[str, bytes]],
                         credit: Collection[str], retries: int | None
                         ) -> tuple[list, set[str], list | None, set[str]]:
        """A leg's existence check: the echo cache, then the peer's
        filter, then ONE probe for what they leave. Returns what was
        still to be weighed after the echo cache, what the peer
        answered it has, the payload slices staged meanwhile, and the
        filter positives credited on trust. A dead peer's cache and
        filter are never consulted (a stale summary crediting copies
        on a corpse is exactly the phantom the health registry exists
        to prevent); no replica of the peer's filter = the pre-index
        path."""
        env, plane = self.env, self.env.index
        remaining = wanted
        if env.echo_cache is not None and retries is None:
            remaining = self._echo_skip(node_id, wanted)
        filtered = plane is not None and plane.local_filter is not None \
            and retries is None \
            and plane.peer_filters.state(node_id) is not None
        to_probe, trusted = remaining, set()
        if filtered:
            to_probe, trusted = self._filter_verdicts(
                node_id, remaining, credit)
        digests = [d for d, _ in to_probe]
        if plane is not None:
            plane.place_considered += len(wanted)
            plane.place_skipped += len(wanted) - len(digests)
        staged = None
        have: set[str] = set()
        if not to_probe:
            return remaining, have, staged, trusted
        probe = env.client.has_chunks(
            env.cfg.cluster.peer(node_id), digests,
            resident_ok=True, retries=retries)
        if filtered:
            # with filter verdicts in hand only the doubtful part is
            # asked about: nothing to stage meanwhile, no task
            have = await probe
            for d in digests:
                if d not in have:
                    # the filter said maybe, the peer says no: an
                    # OBSERVED false positive — counted, and
                    # overridden so a retry stops re-trusting
                    plane.peer_filters.note_fp(node_id, d)
            return remaining, have, staged, trusted
        # the probe flies while the payload list is staged into bounded
        # slices — fresh data rarely dedups, so the optimistic staging
        # is usually final; a dedup hit restages only the missing
        # remainder. On a worker thread so it is GENUINELY concurrent
        # with the probe's RTT: the to_thread await yields the loop,
        # which runs the probe task's send before (and while) the
        # slicing executes
        call = asyncio.create_task(probe)
        try:
            staged = await asyncio.to_thread(
                slice_payloads, remaining, env.slice_bytes)
            have = await call
        except BaseException:
            call.cancel()    # the leg was cancelled or failed first:
            raise            # don't orphan the probe
        return remaining, have, staged, trusted

    def _echo_skip(self, node_id: int, wanted: list[tuple[str, bytes]]
                   ) -> list[tuple[str, bytes]]:
        """Echo-cache consult (ISSUE 16 satellite): a digest this peer
        hash-echo-confirmed THIS SESSION under the current epoch is
        first-party evidence, stronger than a bloom positive — credit
        the copy with NO ledger entry, skipping the probe AND the
        pre-ack verify round. Returns what is left."""
        plane, cache = self.env.index, self.env.echo_cache
        remaining = []
        for d, b in wanted:
            if cache.confirmed(node_id, d):
                self._skip(node_id, d, b)
            else:
                remaining.append((d, b))
        echoed = len(wanted) - len(remaining)
        if echoed:
            plane.echo_trusted += echoed
            plane.probes_skipped += echoed
        return remaining

    def _filter_verdicts(self, node_id: int,
                         remaining: list[tuple[str, bytes]],
                         credit: Collection[str]
                         ) -> tuple[list[tuple[str, bytes]], set[str]]:
        """Split a leg's list by the peer's filter (docs/index.md):
        trusted (a positive this leg was given to credit — probe AND
        transfer skipped, verified pre-ack), ruled out (definitely
        absent — transfer without probing), and what is left to ask."""
        plane = self.env.index
        trusted: set[str] = set()
        to_probe = []
        ruled_out = 0
        for d, b in remaining:
            if d in credit:
                trusted.add(d)
                self.ledger.credit(node_id, d, len(b))
                self._skip(node_id, d, b)
            elif plane.peer_filters.contains(node_id, d) is False:
                ruled_out += 1       # straight to transfer
            else:
                to_probe.append((d, b))
        plane.probes_skipped += ruled_out + len(trusted)
        plane.trusted += len(trusted)
        if not to_probe and remaining:
            plane.probe_rpcs_skipped += 1
        return to_probe, trusted

    def _on_slice(self, leg_id: int, nid: int):
        """The per-slice callback of leg ``leg_id``'s train to ``nid``
        (the leg's own peer, or its hedge backup)."""
        cache = self.env.echo_cache

        def on_slice(part: list[tuple[str, bytes]],
                     echoed: list[str]) -> None:
            # hash-echo verification per slice (reference contract,
            # StorageNode.java:248-257) + per-slice crediting: a
            # verified slice is durable on the peer even if a LATER
            # slice fails — end-of-call crediting forgot delivered
            # bytes on partial failure, and handoff re-transferred (and
            # re-counted) them. The echo IS the session confirmation
            # the echo cache keys on.
            sent = {d for d, _ in part}
            if sent & set(echoed) != sent:
                raise RpcError(f"hash echo mismatch from node {nid}")
            for d, b in part:
                self.copies[d] += 1
                if cache is not None:
                    cache.confirm(nid, d)
                if nid != leg_id:
                    # hedge-backup copy: durable but on a non-canonical
                    # holder — queue it for repair like a handoff copy
                    self.env.under_replicated.add(d)
                if (nid, d) not in self.counted:
                    self.counted.add((nid, d))
                    self.stats["transferredBytes"] += len(b)
        return on_slice

    async def _transfer(self, node_id: int,
                        missing: list[tuple[str, bytes]],
                        slices: list[list[tuple[str, bytes]]]) -> None:
        """Send a leg's slices — under a hedge policy (ISSUE 16
        satellite) as a race (``HedgePolicy.race``): if the primary
        stalls past the p~99 envelope, a SECOND train goes to the next
        ring holder under the shared token budget; per-slice crediting
        under ``counted`` keeps the byte accounting exact."""
        env = self.env
        backup_id = None
        if env.hedge is not None:
            # first digest in the batch with a live third holder
            # nominates the backup (the batch mixes owner sets;
            # anchoring on missing[0] alone left whole trains unhedged
            # on a coin flip)
            for dg, _ in missing:
                primaries = set(self._primary_targets(dg))
                backup_id = next(
                    (t for t in self._handoff_ring(dg)
                     if t != node_id and t != env.cfg.node_id
                     and t not in primaries
                     and env.health.is_alive(t)), None)
                if backup_id is not None:
                    break

        async def issue(nid: int) -> int:
            return await env.client.store_chunks_windowed(
                env.cfg.cluster.peer(nid), self.file_id, slices,
                window=env.cfg.ingest.slice_inflight,
                on_slice=self._on_slice(node_id, nid))

        if backup_id is None:
            peak, winner = await issue(node_id), node_id
        else:
            # success of EITHER train completes the call: whatever
            # landed was credited slice by slice
            peak, winner = await env.hedge.race(
                issue, node_id, backup_id, op="store_chunks",
                delay_s=env.hedge.delay_s(
                    env.obs.rpc_client.recent_best_mean("store_chunks")),
                event=env.obs.event, mark_dead=env.health.mark_dead,
                slices=len(slices))
        if winner == node_id:
            env.stalls.peak("sliceInflight", peak)

    async def _handoff_rounds(self, quorum: int) -> set[str]:
        """Sloppy-quorum fallback (hinted handoff): chunks still below
        quorum try the next nodes in their digest ring, so a dead
        canonical target costs availability only when fewer than
        ``write_quorum`` nodes in the WHOLE cluster are reachable. The
        reference aborts the entire upload on ANY dead peer
        (StorageNode.java:218-221); this keeps its >=2-copies durability
        without its write-all fragility. Returns the digests that took
        a handoff copy: queued for repair, which migrates them back to
        canonical placement."""
        env, copies = self.env, self.copies
        payload_of = self.plan.payload_of
        handoff: set[str] = set()
        next_try = {d: len(self._primary_targets(d))   # ring index per digest
                    for d in copies}
        while True:
            need = [d for d, n in copies.items() if n < quorum]
            if not need:
                break
            groups: dict[int, list[tuple[str, bytes]]] = {}
            local_handoff: list[tuple[str, bytes]] = []
            progress = False
            for d in need:
                order = self._handoff_ring(d)
                if next_try[d] >= len(order):
                    continue                     # cluster exhausted
                target = order[next_try[d]]
                next_try[d] += 1
                progress = True
                handoff.add(d)
                if target == env.cfg.node_id:
                    local_handoff.append((d, payload_of[d]))
                    copies[d] += 1   # local copy counts even on dedup
                else:
                    groups.setdefault(target, []).append(
                        (d, payload_of[d]))
            if not progress:
                break
            jobs = []
            if local_handoff:
                # count_dedup=False: the handoff path never counted a
                # local dedup hit (the copy was credited above)
                jobs.append(self._put_local(local_handoff,
                                            count_dedup=False))
            jobs.extend(self._leg(nid, w) for nid, w in groups.items())
            if jobs:
                await self._gather(*jobs)
        return handoff

    def _settle(self, quorum: int, handoff: set[str]) -> None:
        """Write-quorum policy (vs reference write-all abort, :218-221):
        fail the batch loudly below quorum, else queue what is short of
        ``rf`` or sits on a handoff holder for repair, and report."""
        env, copies, stats, rf = self.env, self.copies, self.stats, self.rf
        failed = [d for d, n in copies.items() if n < quorum]
        if failed:
            # journaled: a quorum failure is the write path's loudest
            # lifecycle event and the HTTP 500 it becomes carries no
            # cluster state — the flight recorder keeps the evidence
            env.obs.event("quorum_fail", chunksBelow=len(failed),
                          quorum=quorum)
            raise UploadError(
                f"Replication failed: {len(failed)} chunks below quorum "
                f"{quorum}")
        for d, n in copies.items():
            if n < rf or d in handoff:
                env.under_replicated.add(d)
        batch_min = min(copies.values(), default=rf)
        stats["minCopies"] = batch_min if stats["minCopies"] is None \
            else min(stats["minCopies"], batch_min)
        stats["handoffChunks"] += len(handoff)
        stats["degraded"] = stats["degraded"] or bool(
            handoff or any(n < rf for n in copies.values()))
