"""SmartClient: edge CDC + dedup, direct-to-owner striping, single-hop
ingest (docs/client.md).

Protocol shape (upload)::

    GET /dataplane            ring map + address book + chunking + rf
    [internal] get_filters    every peer's existence filter, one call
    chunk + sha256 locally    the cluster's exact fragmenter params
    [internal] has_chunks     probes ONLY where no filter rules
    [internal] store_chunks   striped to the rf owners, windowed,
                              hash-echo verified per slice
    [internal] has_chunks     the r16 trust-verification round: every
                              filter-credited skip re-checked first-
                              party BEFORE commit (a stale bloom can
                              cost extra RPCs, never acked bytes)
    POST /commit              ONE coordinator call; the server
                              re-counts durable copies at quorum and
                              heals below-quorum chunks before acking

Downloads run the same plane in reverse: manifest -> owner groups ->
striped ``get_chunks`` with budget-capped hedging -> per-chunk sha256
verification at the client -> whole-stream hash gate. Any gap (old
server, epoch churn, unreachable owner, missing chunk) falls back to
the legacy coordinator path — byte-identical by construction, proven
by bench_client.py gate (4).

Sync facade on purpose: the CLI and benches are synchronous; each bulk
operation runs its own event loop with a fresh
:class:`~dfs_tpu.comm.rpc.InternalClient` (pooled connections cannot
outlive a loop). Cross-operation state — ring view, filter replicas,
echo cache, hedge tokens, counters — is plain data owned by the
calling thread.
"""

from __future__ import annotations

import asyncio
import json
import time
import urllib.parse

from dfs_tpu.cli.client import NodeClient
from dfs_tpu.comm.rpc import (InternalClient, RpcError, RpcRemoteError,
                              slice_payloads)
from dfs_tpu.config import ClientConfig, PeerAddr
from dfs_tpu.fragmenter.base import fragmenter_from_description
from dfs_tpu.index import EchoCache
from dfs_tpu.index.filter import BlockedBloomFilter
from dfs_tpu.ring import RingMap
from dfs_tpu.serve.hedge import HedgePolicy
from dfs_tpu.utils.hashing import is_hex_digest, sha256_hex

# one get_chunks batch per ~8 MiB per peer: big enough to amortize the
# round-trip, small enough that a hedge re-request is cheap
_READ_BATCH_BYTES = 8 * 1024 * 1024


class SmartClientError(RuntimeError):
    """Smart path failed AND fallback was disabled (cfg.fallback=False,
    the bench/test mode that must measure the smart plane, not the
    legacy one silently standing in for it)."""


class _Fallback(Exception):
    """Internal signal: this operation cannot run on the smart plane —
    degrade to the legacy coordinator path (docs/client.md fallback
    matrix). Carries the human-readable reason for stats/debugging."""

    def __init__(self, reason: str) -> None:
        super().__init__(reason)
        self.reason = reason


class _ClientRingView:
    """The minimal ring-manager shim :class:`InternalClient` needs to
    stamp placement-bearing ops with (epoch, fingerprint) and converge
    on RingEpochMismatch — the SDK adopts the peer's newer map exactly
    like a node would, then replans. Placement computed under the OLD
    map stays safe: /commit re-counts durable copies under the
    coordinator's current map and heals, so epoch churn mid-transfer
    costs extra work, never bytes."""

    def __init__(self, ring: RingMap) -> None:
        self.current = ring
        self.mismatches = 0

    @property
    def epoch(self) -> int:
        return self.current.epoch

    def note_epoch_mismatch(self) -> None:
        self.mismatches += 1

    def adopt(self, ring_dict: dict, source: str = "client") -> bool:
        new = RingMap.from_dict(ring_dict)
        if (new.epoch, new.fingerprint) <= (self.current.epoch,
                                            self.current.fingerprint):
            return False
        self.current = new
        return True


class SmartClient:
    """Programmatic data-plane client (docs/client.md). Public surface:
    :meth:`upload`, :meth:`download`, :meth:`stats`, :meth:`close` —
    plus everything :class:`NodeClient` offers via :attr:`legacy`.

    Every :class:`~dfs_tpu.config.ClientConfig` knob surfaces in
    :meth:`stats` (the DFS005 contract) and as a CLI flag on
    ``dfs-tpu upload``/``download``."""

    def __init__(self, host: str = "127.0.0.1", port: int = 5001,
                 cfg: ClientConfig | None = None,
                 timeout_s: float = 30.0) -> None:
        self.cfg = cfg or ClientConfig()
        self.legacy = NodeClient(host, port, timeout_s=timeout_s)
        self.timeout_s = timeout_s
        # bootstrap state (None = never fetched; False = server has no
        # /dataplane — a pre-r19 build, legacy-only for this client)
        self._boot: dict | bool | None = None
        self._ringview: _ClientRingView | None = None
        self._peers: dict[int, PeerAddr] = {}
        self._rf = 1
        self._frag = None
        # filter replicas: node_id -> {"bloom", "gen", "fetchedAt",
        # "baseAgeS"} — fetched in ONE get_filters call, refreshed when
        # older than cfg.filter_max_age_s
        self._filters: dict[int, dict] = {}
        self._filters_at = 0.0
        self._echo = EchoCache(self.cfg.echo_cache_entries) \
            if self.cfg.echo_cache_entries > 0 else None
        self._hedge = HedgePolicy(
            self.cfg.hedge_floor_s, self.cfg.hedge_cap_s,
            self.cfg.hedge_budget_per_s) \
            if self.cfg.hedge_budget_per_s > 0 else None
        self.counters = {
            "smartUploads": 0, "smartDownloads": 0,
            "legacyUploads": 0, "legacyDownloads": 0,
            "fallbacks": 0, "transferredBytes": 0,
            "dedupSkippedBytes": 0, "probeRpcs": 0, "verifyRpcs": 0,
            "filterFp": 0, "chunksVerified": 0, "healedChunks": 0,
            "filterRefreshes": 0}
        self._last_fallback: str | None = None

    # ------------------------------------------------------------------ #
    # bootstrap
    # ------------------------------------------------------------------ #

    def _bootstrap(self) -> None:
        """Fetch (or refuse) the data-plane description. A 404 pins
        this client to the legacy path for its lifetime — the server
        predates the protocol; nothing will change mid-process."""
        if self._boot is not None:
            return
        try:
            boot = json.loads(self.legacy._request("GET", "/dataplane"))
        except RuntimeError as e:
            if "HTTP 404" in str(e):
                self._boot = False
                return
            raise
        self._install_boot(boot)

    def _install_boot(self, boot: dict) -> None:
        self._boot = boot
        self._ringview = _ClientRingView(RingMap.from_dict(boot["ring"]))
        self._peers = {int(p["nodeId"]): PeerAddr(
            node_id=int(p["nodeId"]), host=str(p["host"]),
            port=int(p["port"]), internal_port=int(p["internalPort"]))
            for p in boot["peers"]}
        self._rf = int(boot["replicationFactor"])
        chunking = boot.get("chunking")
        self._frag = None
        if chunking and chunking.get("describe"):
            try:
                self._frag = fragmenter_from_description(
                    chunking["describe"])
            except (ValueError, KeyError):
                self._frag = None   # unknown engine: legacy path

    def _smart_ready(self) -> bool:
        self._bootstrap()
        return bool(self._boot) and self._frag is not None \
            and self._ringview is not None

    def _note_fallback(self, reason: str) -> None:
        self.counters["fallbacks"] += 1
        self._last_fallback = reason

    def _rpc(self) -> InternalClient:
        """A fresh storage-plane client bound to the CURRENT event
        loop (one per operation — see module docstring)."""
        return InternalClient(request_timeout_s=self.timeout_s,
                              ring=self._ringview)

    # ------------------------------------------------------------------ #
    # filters
    # ------------------------------------------------------------------ #

    async def _ensure_filters(self, rpc: InternalClient) -> None:
        """One batched ``get_filters`` call to the bootstrap node,
        refreshed when the copy is older than ``filter_max_age_s``
        (0 = every upload). Missing/failed filters simply mean the
        probing path — never an error."""
        max_age = self.cfg.filter_max_age_s
        now = time.monotonic()
        if self._filters and max_age > 0 \
                and now - self._filters_at < max_age:
            return
        boot_nid = int(self._boot["nodeId"])  # type: ignore[index]
        try:
            got = await rpc.get_filters(self._peers[boot_nid], retries=1)
        except RpcError:
            # pre-r19 peer (unknown op) or sick node: no filters,
            # placement probes everything — the pre-filter wire
            self._filters = {}
            self._filters_at = now
            return
        filters: dict[int, dict] = {}
        for meta, blob in got:
            try:
                bloom = BlockedBloomFilter(
                    int(meta["capacity"]), int(meta["bitsPerKey"]),
                    buf=bytearray(blob))
                filters[int(meta["nodeId"])] = {
                    "bloom": bloom, "gen": int(meta["gen"]),
                    "fetchedAt": now,
                    "baseAgeS": float(meta.get("ageS", 0.0))}
            except (KeyError, ValueError, TypeError):
                continue   # one malformed entry never poisons the rest
        self._filters = filters
        self._filters_at = now
        self.counters["filterRefreshes"] += 1

    def _filter_verdict(self, nid: int, digest: str) -> bool | None:
        """Tri-state like PeerFilterSet.contains: True = maybe present
        (must be trust-verified pre-commit), False = definitely absent
        at the filter's generation (send), None = no usable filter
        (probe). A replica past the freshness bound is unusable — the
        filter-staleness rule of docs/client.md."""
        st = self._filters.get(nid)
        if st is None:
            return None
        max_age = self.cfg.filter_max_age_s
        if max_age > 0:
            age = st["baseAgeS"] + (time.monotonic() - st["fetchedAt"])
            if age > max_age:
                return None
        try:
            return st["bloom"].contains(digest)
        except ValueError:
            return None

    # ------------------------------------------------------------------ #
    # upload
    # ------------------------------------------------------------------ #

    def upload(self, data: bytes, name: str = "") -> dict:
        """Single-hop upload when the cluster supports it, else the
        legacy coordinator POST. Returns the server's upload reply plus
        client-side accounting: ``clientBytesSent`` (payload bytes that
        crossed the wire), ``dataPlane`` ("smart" | "legacy")."""
        self._bootstrap()
        if self._smart_ready():
            try:
                return self._upload_smart(data, name)
            except _Fallback as e:
                self._note_fallback(e.reason)
                if not self.cfg.fallback:
                    raise SmartClientError(
                        f"smart upload failed ({e.reason}) and fallback "
                        "is disabled") from e
        elif not self.cfg.fallback:
            raise SmartClientError(
                "cluster has no smart data plane and fallback is "
                "disabled")
        out = self.legacy.upload(data, name)
        out["clientBytesSent"] = len(data)
        out["dataPlane"] = "legacy"
        self.counters["legacyUploads"] += 1
        self.counters["transferredBytes"] += len(data)
        return out

    def _upload_smart(self, data: bytes, name: str) -> dict:
        refs = self._frag.chunk(data)
        table = [[c.offset, c.length, c.digest] for c in refs]
        file_id = sha256_hex(data)
        payload_of = {c.digest: data[c.offset:c.offset + c.length]
                      for c in refs}   # first occurrence wins
        if self._echo is not None:
            self._echo.note_epoch(self._ringview.epoch)
        sent_bytes = asyncio.run(self._stripe_upload(payload_of))
        # manifest commit stays ONE coordinator call with unchanged
        # ack semantics (fsync-before-ack, deadline, quorum)
        meta = json.dumps({"fileId": file_id, "size": len(data),
                           "chunks": table}).encode()
        body = len(meta).to_bytes(4, "big") + meta
        q = urllib.parse.urlencode({"name": name})
        try:
            out = json.loads(self.legacy._request(
                "POST", f"/commit?{q}", body=body))
        except RuntimeError as e:
            if "HTTP 409" in str(e) or "HTTP 404" in str(e):
                # chunks not durably present (or old coordinator):
                # the documented degrade — nothing was acked
                raise _Fallback(f"commit refused: {e}") from e
            raise
        out["clientBytesSent"] = sent_bytes + len(body)
        out["dataPlane"] = "smart"
        self.counters["smartUploads"] += 1
        return out

    async def _stripe_upload(self, payload_of: dict[str, bytes]) -> int:
        """Stripe payloads directly to the rf ring owners. Returns
        payload bytes actually sent. Raises :class:`_Fallback` when
        some digest could not be confirmed on ANY owner (the commit
        would 409; go legacy without the wasted round-trip)."""
        rpc = self._rpc()
        try:
            await self._ensure_filters(rpc)
            ring = self._ringview.current
            per_peer: dict[int, list[str]] = {}
            for d in payload_of:
                for nid in ring.owners(d, self._rf):
                    per_peer.setdefault(nid, []).append(d)
            landed: set[str] = set()   # >=1 first-party confirmation
            sent = 0

            async def one_peer(nid: int, digests: list[str]) -> None:
                nonlocal sent
                peer = self._peers.get(nid)
                if peer is None:
                    return               # address book gap: other
                                         # owners / commit heal cover it
                # split: echo-confirmed skip, filter-positive trusted
                # (verify pre-commit), filter-negative send, unknown
                # probe
                trusted: list[str] = []
                to_probe: list[str] = []
                to_send: list[str] = []
                for d in digests:
                    if self._echo is not None \
                            and self._echo.confirmed(nid, d):
                        landed.add(d)
                        self.counters["dedupSkippedBytes"] += \
                            len(payload_of[d])
                        continue
                    verdict = self._filter_verdict(nid, d)
                    if verdict is True:
                        trusted.append(d)
                    elif verdict is False:
                        to_send.append(d)
                    else:
                        to_probe.append(d)
                if to_probe:
                    self.counters["probeRpcs"] += 1
                    have = await rpc.has_chunks(peer, to_probe)
                    for d in to_probe:
                        if d in have:
                            landed.add(d)
                            if self._echo is not None:
                                self._echo.confirm(nid, d)
                            self.counters["dedupSkippedBytes"] += \
                                len(payload_of[d])
                        else:
                            to_send.append(d)
                # await FIRST, then accumulate: `sent += await ...`
                # loads `sent` before the suspension point and loses
                # concurrent peers' updates on resume
                n = await self._send_chunks(rpc, peer, nid, to_send,
                                            payload_of, landed)
                sent += n
                # r16 trust-verification round, client edition: every
                # filter-credited skip is re-checked FIRST-PARTY before
                # commit — a stale/corrupt bloom degrades to this probe
                # + a real send, never to a committed phantom
                if trusted:
                    self.counters["verifyRpcs"] += 1
                    have = await rpc.has_chunks(peer, trusted)
                    heal = [d for d in trusted if d not in have]
                    for d in trusted:
                        if d in have:
                            landed.add(d)
                            if self._echo is not None:
                                self._echo.confirm(nid, d)
                            self.counters["dedupSkippedBytes"] += \
                                len(payload_of[d])
                    if heal:
                        self.counters["filterFp"] += len(heal)
                        n = await self._send_chunks(
                            rpc, peer, nid, heal, payload_of, landed)
                        sent += n

            results = await asyncio.gather(
                *(one_peer(n, ds) for n, ds in per_peer.items()),
                return_exceptions=True)
            hard = [r for r in results
                    if isinstance(r, BaseException)
                    and not isinstance(r, RpcError)]
            if hard:
                raise hard[0]
            not_landed = [d for d in payload_of if d not in landed]
            if not_landed:
                # an owner set was entirely unreachable (every RpcError
                # above swallowed into the gather): commit would 409
                raise _Fallback(
                    f"{len(not_landed)} chunks reached no owner")
            return sent
        finally:
            rpc.close()

    async def _send_chunks(self, rpc: InternalClient, peer: PeerAddr,
                           nid: int, digests: list[str],
                           payload_of: dict[str, bytes],
                           landed: set[str]) -> int:
        """Windowed, hash-echo-verified slice train to one owner
        (the comm/rpc.py slice-pipelining discipline)."""
        if not digests:
            return 0
        items = [(d, payload_of[d]) for d in digests]
        slices = slice_payloads(items, _READ_BATCH_BYTES)
        sent = 0

        def on_slice(part: list[tuple[str, bytes]],
                     echoed: list[str]) -> None:
            nonlocal sent
            got = set(echoed)
            missing = [d for d, _ in part if d not in got]
            if missing:
                raise RpcRemoteError(
                    f"hash echo mismatch from node {nid}")
            for d, b in part:
                landed.add(d)
                sent += len(b)
                self.counters["transferredBytes"] += len(b)
                if self._echo is not None:
                    self._echo.confirm(nid, d)

        try:
            await rpc.store_chunks_windowed(
                peer, "client-upload", slices,
                window=self.cfg.window, on_slice=on_slice)
        except RpcError:
            if self._echo is not None:
                self._echo.drop(nid)
            raise
        return sent

    # ------------------------------------------------------------------ #
    # download
    # ------------------------------------------------------------------ #

    def download(self, file_id: str) -> bytes:
        """Striped direct-from-owner download with client-side digest
        verification of EVERY chunk plus the whole-stream hash gate.
        EC manifests and any unrecoverable gap fall back to the legacy
        coordinator read (byte-identical; the gap may also heal
        per-chunk via ranged coordinator reads)."""
        self._bootstrap()
        if self._smart_ready():
            try:
                return self._download_smart(file_id)
            except _Fallback as e:
                self._note_fallback(e.reason)
                if not self.cfg.fallback:
                    raise SmartClientError(
                        f"smart download failed ({e.reason}) and "
                        "fallback is disabled") from e
        elif not self.cfg.fallback:
            raise SmartClientError(
                "cluster has no smart data plane and fallback is "
                "disabled")
        data = self.legacy.download(file_id)
        self.counters["legacyDownloads"] += 1
        return data

    def _download_smart(self, file_id: str) -> bytes:
        try:
            mdoc = self.legacy.manifest(file_id)
        except RuntimeError as e:
            raise _Fallback(f"manifest fetch failed: {e}") from e
        if mdoc.get("ec"):
            raise _Fallback("ec manifest (coordinator decodes parity)")
        chunks = [(int(c["offset"]), int(c["length"]), str(c["digest"]))
                  for c in mdoc.get("chunks", [])]
        size = int(mdoc.get("size", 0))
        got = asyncio.run(self._stripe_download(file_id, chunks))
        out = bytearray(size)
        for off, ln, d in chunks:
            out[off:off + ln] = got[d]
        data = bytes(out)
        if is_hex_digest(file_id) and sha256_hex(data) != file_id:
            # end-to-end integrity gate: every chunk already verified,
            # so a whole-stream miss means a wrong/torn manifest —
            # never return corrupt bytes, re-read via the coordinator
            raise _Fallback("assembled stream hash mismatch")
        self.counters["smartDownloads"] += 1
        return data

    async def _stripe_download(self, file_id: str,
                               chunks: list[tuple[int, int, str]]
                               ) -> dict[str, bytes]:
        """digest -> verified bytes for every chunk, striped across the
        ring owners (``cfg.stripe`` peer batches in flight), hedged
        under the token budget, with per-chunk candidate walk and a
        ranged coordinator read as the last resort per chunk."""
        rpc = self._rpc()
        try:
            ring = self._ringview.current
            need: dict[str, int] = {}
            span_of: dict[str, tuple[int, int]] = {}
            for off, ln, d in chunks:
                if d not in need:
                    need[d] = ln
                    span_of[d] = (off, ln)
            # spread digests across their owner sets round-robin so rf
            # replicas share the read load (the striping win)
            groups: dict[int, list[str]] = {}
            for i, (d, ln) in enumerate(need.items()):
                owners = [n for n in ring.owners(d, self._rf)
                          if n in self._peers]
                if not owners:
                    continue
                groups.setdefault(owners[i % len(owners)], []).append(d)
            out: dict[str, bytes] = {}
            sem = asyncio.Semaphore(self.cfg.stripe)

            async def fetch_group(nid: int, digests: list[str]) -> None:
                for batch in slice_payloads(digests, _READ_BATCH_BYTES,
                                            size=need.__getitem__):
                    expect = sum(need[d] for d in batch)
                    async with sem:
                        try:
                            pairs = await self._hedged_get(
                                rpc, nid, batch, expect)
                        except RpcError:
                            continue    # mop-up walk covers the batch
                    for d, view in pairs:
                        b = bytes(view)
                        if d in need and sha256_hex(b) == d:
                            out[d] = b
                            self.counters["chunksVerified"] += 1

            await asyncio.gather(
                *(fetch_group(n, ds) for n, ds in groups.items()))
            # mop-up: candidate walk for anything missed (wrong owner
            # guess, dead peer, corrupt reply), then a ranged
            # coordinator read per chunk — correctness never depends
            # on the stripe plan being right
            for d in [d for d in need if d not in out]:
                b = await self._fetch_one(rpc, ring, d, need[d])
                if b is None:
                    off, ln = span_of[d]
                    try:
                        b = await asyncio.to_thread(
                            self.legacy.download_range, file_id, off,
                            off + ln)
                    except RuntimeError as e:
                        raise _Fallback(
                            f"chunk {d[:12]}… unrecoverable: {e}") from e
                    if sha256_hex(b) != d:
                        raise _Fallback(
                            f"chunk {d[:12]}… digest mismatch from "
                            "coordinator")
                    self.counters["chunksVerified"] += 1
                    self.counters["healedChunks"] += 1
                out[d] = b
            return out
        finally:
            rpc.close()

    async def _fetch_one(self, rpc: InternalClient, ring: RingMap,
                         digest: str, length: int) -> bytes | None:
        for nid in ring.owners(digest, len(ring.active_ids())):
            peer = self._peers.get(nid)
            if peer is None:
                continue
            try:
                pairs = await rpc.get_chunks(peer, [digest], retries=1,
                                             expect_bytes=length)
            except RpcError:
                continue
            for d, view in pairs:
                b = bytes(view)
                if d == digest and sha256_hex(b) == digest:
                    self.counters["chunksVerified"] += 1
                    self.counters["healedChunks"] += 1
                    return b
        return None

    async def _hedged_get(self, rpc: InternalClient, nid: int,
                          digests: list[str], expect: int):
        """Client-side budget-capped hedging (the serve/hedge.py
        shapes): race the batch to the next owner when the primary
        outlives the configured floor and the token bucket allows."""
        hedge = self._hedge
        backup = None
        if hedge is not None:
            ring = self._ringview.current
            backup = next(
                (n for n in ring.owners(digests[0], len(ring.active_ids()))
                 if n != nid and n in self._peers), None)

        async def issue(n: int):
            return await rpc.get_chunks(self._peers[n], digests,
                                        expect_bytes=expect)

        if backup is None:
            return await issue(nid)
        # no client-side latency history: the floor IS the delay (the
        # conservative end of the serve-side clamp)
        pairs, _ = await hedge.race(issue, nid, backup, op="get_chunks",
                                    delay_s=hedge.delay_s(None))
        return pairs

    # ------------------------------------------------------------------ #
    # introspection / lifecycle
    # ------------------------------------------------------------------ #

    def stats(self) -> dict:
        """Config echo (every ClientConfig field — the DFS005 contract)
        + live data-plane counters."""
        out = {"window": self.cfg.window,
               "stripe": self.cfg.stripe,
               "hedgeBudgetPerS": self.cfg.hedge_budget_per_s,
               "hedgeFloorS": self.cfg.hedge_floor_s,
               "hedgeCapS": self.cfg.hedge_cap_s,
               "filterMaxAgeS": self.cfg.filter_max_age_s,
               "echoCacheEntries": self.cfg.echo_cache_entries,
               "fallback": self.cfg.fallback,
               "smart": self._smart_ready(),
               "ringEpoch": self._ringview.epoch
               if self._ringview is not None else None,
               "ringMismatches": self._ringview.mismatches
               if self._ringview is not None else 0,
               "filterPeers": sorted(self._filters),
               "lastFallback": self._last_fallback,
               **self.counters}
        if self._echo is not None:
            out["echoCache"] = self._echo.stats()
        if self._hedge is not None:
            out["hedge"] = self._hedge.stats()
        return out

    def close(self) -> None:
        """Nothing pooled survives an operation (see module docstring);
        close() exists for symmetry and future connection reuse."""
