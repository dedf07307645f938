"""Smart client data plane (r19): edge CDC + dedup, direct-to-owner
striped transfers, single-hop ingest (docs/client.md).

Layers of coverage:

- UNIT: ClientConfig validation, the EchoCache (LRU bound, epoch
  invalidation, per-peer drop), and the client-side filter verdict
  (tri-state + the freshness bound that turns a stale replica into
  probes).
- IN-PROCESS CLUSTER: smart upload/download byte identity against
  real nodes, near-total dedup on re-upload, the stale/corrupt filter
  degrade (extra RPCs, never acked-byte loss or a wrong manifest),
  the legacy fallback matrix (old server / fallback=False), and the
  /commit endpoint's quorum re-count (dedup commit + 409 on absent
  chunks + heal of a below-quorum chunk).
- HEDGED WRITES (r18 leftover): a pulsing-slow replica makes the
  store-side hedge fire and win on the coordinator, with journal
  evidence — and the acked bytes read back from every node.
- BACKGROUND COMPACTION (r16 leftover): full compactions run on the
  dedicated thread, drain deterministically, and surface the stall
  attribution counters.
- The ``bench_client.py --tiny`` subprocess smoke (CLIENT_r19.json
  schema lock) rides tier-1.
"""

from __future__ import annotations

import asyncio
import json
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

from dfs_tpu.client import SmartClient, SmartClientError
from dfs_tpu.config import (CDCParams, CensusConfig, ChaosConfig,
                            ClientConfig, ClusterConfig, IndexConfig,
                            NodeConfig, PeerAddr, ServeConfig)
from dfs_tpu.index import EchoCache
from dfs_tpu.index.filter import BlockedBloomFilter
from dfs_tpu.index.lsi import DigestIndex
from dfs_tpu.node.runtime import StorageNodeServer, UploadError
from dfs_tpu.utils.hashing import sha256_hex

REPO = Path(__file__).resolve().parent.parent
CDC = CDCParams(min_size=2048, avg_size=8192, max_size=65536)
CENSUS_OFF = CensusConfig(history_interval_s=0)


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _mk_cluster(n: int, rf: int) -> ClusterConfig:
    ports = _free_ports(2 * n)
    peers = tuple(PeerAddr(node_id=i + 1, host="127.0.0.1",
                           port=ports[2 * i],
                           internal_port=ports[2 * i + 1])
                  for i in range(n))
    return ClusterConfig(peers=peers, replication_factor=rf)


async def _start_nodes(cluster: ClusterConfig, root: Path,
                       index: IndexConfig | None = None,
                       overrides: dict[int, dict] | None = None
                       ) -> dict[int, StorageNodeServer]:
    nodes = {}
    for p in cluster.peers:
        kw = dict((overrides or {}).get(p.node_id, {}))
        cfg = NodeConfig(node_id=p.node_id, cluster=cluster,
                         data_root=root, fragmenter="cdc", cdc=CDC,
                         health_probe_s=0, census=CENSUS_OFF,
                         index=index or IndexConfig(), **kw)
        n = StorageNodeServer(cfg)
        await n.start()
        nodes[p.node_id] = n
    return nodes


async def _stop_all(nodes) -> None:
    for n in nodes.values():
        await n.stop()


def _smart(cluster: ClusterConfig, nid: int = 1,
           **cfg_kw) -> SmartClient:
    cfg_kw.setdefault("fallback", False)
    return SmartClient(host="127.0.0.1", port=cluster.peer(nid).port,
                       cfg=ClientConfig(**cfg_kw))


IX = IndexConfig(enabled=True, memtable_entries=1024, filter_sync_s=0)


# ------------------------------------------------------------------ #
# unit: config validation
# ------------------------------------------------------------------ #

def test_client_config_validates():
    c = ClientConfig()
    assert c.window == 2 and c.stripe == 4 and c.fallback
    for bad in (dict(window=0), dict(stripe=0),
                dict(hedge_budget_per_s=-1.0), dict(hedge_floor_s=-0.1),
                dict(hedge_cap_s=-1.0), dict(filter_max_age_s=-1.0),
                dict(echo_cache_entries=-1)):
        with pytest.raises(ValueError):
            ClientConfig(**bad)


# ------------------------------------------------------------------ #
# unit: echo-confirmed existence cache
# ------------------------------------------------------------------ #

def test_echo_cache_lru_bound_and_recency():
    c = EchoCache(per_peer=3)
    for d in ("d1", "d2", "d3"):
        c.confirm(7, d)
    assert c.confirmed(7, "d1")          # hit refreshes recency
    c.confirm(7, "d4")                   # evicts d2 (oldest untouched)
    assert not c.confirmed(7, "d2")
    assert c.confirmed(7, "d1") and c.confirmed(7, "d4")
    st = c.stats()
    assert st["perPeerCap"] == 3 and st["entries"] == 3
    assert st["hits"] >= 3 and st["confirms"] == 4


def test_echo_cache_epoch_change_invalidates_everything():
    c = EchoCache(per_peer=8)
    c.note_epoch(0)
    c.confirm(1, "a")
    c.confirm(2, "b")
    c.note_epoch(0)                      # same epoch: no-op
    assert c.confirmed(1, "a") and c.confirmed(2, "b")
    c.note_epoch(1)                      # ownership moved: all gone
    assert not c.confirmed(1, "a") and not c.confirmed(2, "b")
    assert c.stats()["invalidations"] == 1


def test_echo_cache_drop_is_per_peer():
    c = EchoCache(per_peer=8)
    c.confirm(1, "a")
    c.confirm(2, "b")
    c.drop(1)                            # peer 1 unreachable
    assert not c.confirmed(1, "a")
    assert c.confirmed(2, "b")


# ------------------------------------------------------------------ #
# unit: client-side filter verdict (freshness bound)
# ------------------------------------------------------------------ #

def test_filter_verdict_tristate_and_staleness_bound():
    c = SmartClient(cfg=ClientConfig(filter_max_age_s=1.0))
    d_in = sha256_hex(b"present")
    d_out = sha256_hex(b"absent")
    bloom = BlockedBloomFilter(64, 10)
    bloom.add(d_in)
    now = time.monotonic()
    c._filters = {3: {"bloom": bloom, "gen": 1,
                      "fetchedAt": now, "baseAgeS": 0.0}}
    assert c._filter_verdict(3, d_in) is True      # maybe: verify
    assert c._filter_verdict(3, d_out) is False    # definitely absent
    assert c._filter_verdict(9, d_in) is None      # no filter: probe
    # past the freshness bound (server-side age counts too): unusable
    c._filters[3]["baseAgeS"] = 5.0
    assert c._filter_verdict(3, d_in) is None
    assert c._filter_verdict(3, d_out) is None


# ------------------------------------------------------------------ #
# unit: the client's hedged batch read when BOTH owners fail
# ------------------------------------------------------------------ #

@pytest.mark.parametrize("backup_ends_first", [False, True],
                         ids=["primary fails first", "backup fails first"])
def test_hedged_get_both_failed_surfaces_the_primarys_error(
        backup_ends_first):
    """The striped download's hedged batch (``HedgePolicy.race``, shared
    with the node since PR 29): both owners failing raises the error of
    the owner the stripe plan CHOSE, whichever ended first — an
    ``RpcError`` here, which ``fetch_group`` leaves to the mop-up walk.
    The client's own copy of the race raised the side that ended LAST:
    with the backup garbling its frame after the primary had refused,
    that was a ``WireError`` no caller caught."""
    from dfs_tpu.client.smart import _ClientRingView
    from dfs_tpu.comm.rpc import RpcError
    from dfs_tpu.comm.wire import WireError
    from dfs_tpu.ring import RingMap

    c = SmartClient(cfg=ClientConfig(hedge_budget_per_s=100.0,
                                     hedge_floor_s=0.0, hedge_cap_s=0.0))
    ring = RingMap.static([1, 2, 3])
    c._ringview = _ClientRingView(ring)
    c._peers = {n: PeerAddr(node_id=n, host="127.0.0.1", port=1,
                            internal_port=1) for n in (1, 2, 3)}
    d = sha256_hex(b"both-fail")
    primary, backup = ring.owners(d, 3)[:2]
    gate = {n: asyncio.Event() for n in (primary, backup)}
    ended = {n: asyncio.Event() for n in (primary, backup)}

    class Rpc:
        async def get_chunks(self, peer, digests, expect_bytes=0):
            try:
                await gate[peer.node_id].wait()
                if peer.node_id == primary:
                    raise RpcError("primary refused")
                raise WireError("backup garbled")
            finally:
                ended[peer.node_id].set()

    async def run() -> None:
        got = asyncio.ensure_future(
            c._hedged_get(Rpc(), primary, [d], 9))
        order = (backup, primary) if backup_ends_first \
            else (primary, backup)
        for n in order:
            gate[n].set()
            await ended[n].wait()
            for _ in range(3):
                await asyncio.sleep(0)
        with pytest.raises(RpcError, match="primary refused"):
            await got
        assert c._hedge.fired == 1 and c._hedge.won == 0

    asyncio.run(run())


# ------------------------------------------------------------------ #
# in-process cluster: smart path end to end
# ------------------------------------------------------------------ #

def test_smart_upload_download_byte_identity(tmp_path):
    """Fresh upload stripes rf copies directly to the owners, commits
    in one call, and the striped download re-verifies every chunk —
    byte-identical from every node, including via the legacy path."""

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        nodes = await _start_nodes(cluster, tmp_path, index=IX)
        try:
            c = _smart(cluster)
            data = os.urandom(250_000)
            info = await asyncio.to_thread(c.upload, data, "a.bin")
            assert info["dataPlane"] == "smart"
            assert info["fileId"] == sha256_hex(data)
            # rf copies crossed the wire (fresh corpus, no dedup)
            assert c.counters["transferredBytes"] == 2 * len(data)
            got = await asyncio.to_thread(c.download, info["fileId"])
            assert got == data
            assert c.counters["smartDownloads"] == 1
            assert c.counters["chunksVerified"] >= info["chunks"]
            # interop: the acked file reads back through EVERY node's
            # legacy coordinator path byte-identically
            for n in nodes.values():
                _, body = await n.download(info["fileId"])
                assert bytes(body) == data
            st = c.stats()
            assert st["smart"] and st["fallbacks"] == 0
            assert st["window"] == 2 and st["fallback"] is False
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_smart_reupload_dedups_via_filters(tmp_path):
    """Once filters have gossiped, a second client re-uploading the
    same corpus transfers ZERO payload bytes: filter credits are
    trust-verified pre-commit, never taken on faith."""

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        nodes = await _start_nodes(cluster, tmp_path, index=IX)
        try:
            data = os.urandom(250_000)
            c1 = _smart(cluster)
            info = await asyncio.to_thread(c1.upload, data, "a.bin")
            assert info["dataPlane"] == "smart"
            for n in nodes.values():
                await n._filter_sync_once()
            c2 = _smart(cluster, nid=2)
            info2 = await asyncio.to_thread(c2.upload, data, "a.bin")
            assert info2["fileId"] == info["fileId"]
            assert c2.counters["transferredBytes"] == 0
            assert c2.counters["dedupSkippedBytes"] == 2 * len(data)
            assert c2.counters["verifyRpcs"] >= 1   # the trust round
            assert c2.counters["filterFp"] == 0
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_stale_corrupt_filter_degrades_to_probes_never_loses_bytes(
        tmp_path):
    """Satellite: a deliberately corrupt filter replica (every bit
    set — it claims EVERYTHING exists) must cost extra RPCs and real
    sends, never an acked manifest naming bytes that do not exist.
    A stale replica (past the freshness bound) must degrade to plain
    probes. Both uploads ack and read back byte-identical."""

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        nodes = await _start_nodes(cluster, tmp_path, index=IX)
        try:
            c = _smart(cluster)
            # seed: a first upload fetches the filter replicas
            await asyncio.to_thread(c.upload, os.urandom(50_000), "s")
            assert c._filters is not None
            # corrupt every fetched replica: all-ones bloom = "present"
            # for every digest ever asked
            for st in c._filters.values():
                buf = st["bloom"].buf
                for i in range(len(buf)):
                    buf[i] = 0xFF
            fresh = os.urandom(200_000)
            info = await asyncio.to_thread(c.upload, fresh, "fresh.bin")
            assert info["dataPlane"] == "smart"
            # the lie was caught first-party: verification probes ran,
            # false positives were counted, and REAL bytes were sent
            assert c.counters["verifyRpcs"] >= 1
            assert c.counters["filterFp"] > 0
            assert c.counters["transferredBytes"] >= len(fresh)
            for n in nodes.values():
                _, body = await n.download(info["fileId"])
                assert bytes(body) == fresh
            got = await asyncio.to_thread(c.download, info["fileId"])
            assert got == fresh

            # stale replica: age past the bound -> verdict None ->
            # plain probe RPCs (extra round trips, correct bytes)
            for st in c._filters.values():
                st["baseAgeS"] = 10_000.0
            probes_before = c.counters["probeRpcs"]
            fresh2 = os.urandom(120_000)
            info2 = await asyncio.to_thread(c.upload, fresh2, "f2.bin")
            assert c.counters["probeRpcs"] > probes_before
            got2 = await asyncio.to_thread(c.download, info2["fileId"])
            assert got2 == fresh2
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_echo_cache_skips_verify_round_on_reupload(tmp_path):
    """Satellite: a digest whose hash-echo was confirmed THIS session
    skips even the trust-verification round on re-upload; a ring epoch
    change clears every session confirmation."""

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        nodes = await _start_nodes(cluster, tmp_path, index=IX)
        try:
            c = _smart(cluster, echo_cache_entries=4096)
            data = os.urandom(150_000)
            info = await asyncio.to_thread(c.upload, data, "a.bin")
            v_before = c.counters["verifyRpcs"]
            p_before = c.counters["probeRpcs"]
            info2 = await asyncio.to_thread(c.upload, data, "b.bin")
            assert info2["fileId"] == info["fileId"]
            # every owner copy was echo-confirmed at store time: the
            # re-upload needs NO probe and NO verify round
            assert c.counters["verifyRpcs"] == v_before
            assert c.counters["probeRpcs"] == p_before
            assert c.counters["transferredBytes"] == 2 * len(data)
            assert c.counters["dedupSkippedBytes"] >= 2 * len(data)
            # epoch change invalidates the session cache
            c._echo.note_epoch(c._ringview.epoch + 1)
            assert c._echo.stats()["entries"] == 0
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# in-process cluster: fallback matrix
# ------------------------------------------------------------------ #

def test_old_server_pins_client_to_legacy_path(tmp_path):
    """A server without /dataplane (pre-r19) 404s the bootstrap: the
    client pins itself to the legacy coordinator path for life and
    stays byte-identical."""

    async def run() -> None:
        cluster = _mk_cluster(2, rf=2)
        nodes = await _start_nodes(cluster, tmp_path)
        try:
            c = SmartClient(host="127.0.0.1", port=cluster.peer(1).port,
                            cfg=ClientConfig())
            orig = c.legacy._request

            def no_dataplane(method, path, *a, **kw):
                if path == "/dataplane":
                    raise RuntimeError("HTTP 404: Not Found")
                return orig(method, path, *a, **kw)

            c.legacy._request = no_dataplane
            data = os.urandom(100_000)
            info = await asyncio.to_thread(c.upload, data, "a.bin")
            assert info["dataPlane"] == "legacy"
            assert info["fileId"] == sha256_hex(data)
            got = await asyncio.to_thread(c.download, info["fileId"])
            assert got == data
            assert c.counters["legacyUploads"] == 1
            assert c.counters["legacyDownloads"] == 1
            assert c._boot is False      # pinned: no re-probe
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_no_fallback_raises_instead_of_degrading(tmp_path):
    async def run() -> None:
        cluster = _mk_cluster(1, rf=1)
        nodes = await _start_nodes(cluster, tmp_path)
        try:
            c = _smart(cluster)          # fallback=False
            c.legacy._request = _raise_404
            with pytest.raises(SmartClientError):
                await asyncio.to_thread(c.upload, b"x" * 10_000, "a")
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def _raise_404(method, path, *a, **kw):
    raise RuntimeError("HTTP 404: Not Found")


def test_ec_manifest_downloads_via_legacy_path(tmp_path):
    """EC stripes are a coordinator-side reconstruction concern: the
    smart client detects the manifest and hands the read to the legacy
    path (byte-identical), counting the fallback."""

    async def run() -> None:
        cluster = _mk_cluster(4, rf=2)
        nodes = await _start_nodes(cluster, tmp_path)
        try:
            data = os.urandom(120_000)
            m, _ = await nodes[1].upload(data, "e.bin", ec_k=2)
            c = SmartClient(host="127.0.0.1", port=cluster.peer(1).port,
                            cfg=ClientConfig())
            got = await asyncio.to_thread(c.download, m.file_id)
            assert got == data
            assert c.counters["legacyDownloads"] == 1
            assert c.counters["fallbacks"] == 1
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# in-process cluster: the /commit quorum re-count
# ------------------------------------------------------------------ #

def test_commit_refuses_phantom_chunks_with_409(tmp_path):
    """A manifest naming chunks held NOWHERE must never ack: the
    coordinator's own has_chunks re-count raises the 409-class error
    and no manifest is saved (a stale client filter cannot manufacture
    durability)."""

    async def run() -> None:
        cluster = _mk_cluster(2, rf=2)
        nodes = await _start_nodes(cluster, tmp_path)
        try:
            body = os.urandom(30_000)
            dg = sha256_hex(body)
            fid = sha256_hex(b"claimed-stream")
            with pytest.raises(UploadError) as ei:
                await nodes[1].commit_manifest(
                    [(0, len(body), dg)], "ghost.bin", fid, len(body))
            assert ei.value.status == 409
            with pytest.raises(KeyError):
                await nodes[1].download(fid)
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_commit_heals_below_quorum_chunk_before_ack(tmp_path):
    """A chunk present on ONE owner but below write quorum is healed
    through the normal placement path before the ack — commit needs
    real durability, not one lucky copy."""

    async def run() -> None:
        cluster = _mk_cluster(2, rf=2)
        nodes = await _start_nodes(cluster, tmp_path)
        try:
            body = os.urandom(40_000)
            dg = sha256_hex(body)
            # stage on node 1 ONLY (one copy; quorum is 2)
            assert await nodes[1].cas.put(dg, body)
            fid = sha256_hex(body)       # single-chunk stream
            manifest, stats = await nodes[1].commit_manifest(
                [(0, len(body), dg)], "heal.bin", fid, len(body))
            assert stats["minCopies"] >= 2
            # the heal landed a REAL copy on the peer
            _, got = await nodes[2].download(fid)
            assert bytes(got) == body
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_commit_of_fully_present_chunks_is_pure_dedup(tmp_path):
    async def run() -> None:
        cluster = _mk_cluster(2, rf=2)
        nodes = await _start_nodes(cluster, tmp_path)
        try:
            data = os.urandom(80_000)
            m, _ = await nodes[1].upload(data, "orig.bin")
            table = [(c.offset, c.length, c.digest) for c in m.chunks]
            m2, stats = await nodes[1].commit_manifest(
                table, "alias.bin", m.file_id, len(data))
            assert stats["transferredBytes"] == 0
            assert stats["dedupSkippedBytes"] == len(data)
            assert stats["minCopies"] >= 2
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# hedged writes (r18 leftover): pulsing-slow replica
# ------------------------------------------------------------------ #

def test_hedged_write_beats_pulsing_slow_replica(tmp_path):
    """Satellite: with a pulsing-slow replica (chaos serve delay
    toggled on/off across uploads), the coordinator hedges the
    store_chunks slice train to the next holder under the existing
    token budget — hedge_fired/hedge_won journal evidence with
    op=store_chunks — and every acked byte reads back from every
    node."""

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        hedged = ServeConfig(hedge_budget_per_s=50.0,
                             hedge_floor_s=0.05, hedge_cap_s=0.3)
        nodes = await _start_nodes(
            cluster, tmp_path,
            overrides={1: {"serve": hedged},
                       3: {"chaos": ChaosConfig(enabled=True)}})
        try:
            uploaded: list[tuple[str, bytes]] = []
            fired_total = 0
            for pulse in range(2):
                nodes[3].chaos.set(serve_delay_s=0.25)
                # ~25 chunks: ~1/3 land in a {1,3} owner set where the
                # remote train targets slow node 3 with node 2 free as
                # the hedge backup
                data = os.urandom(200_000)
                m, _ = await nodes[1].upload(data, f"p{pulse}.bin")
                uploaded.append((m.file_id, data))
                nodes[3].chaos.set(serve_delay_s=0.0)   # pulse ends
                calm = os.urandom(60_000)
                mc, _ = await nodes[1].upload(calm, f"c{pulse}.bin")
                uploaded.append((mc.file_id, calm))
            hs = nodes[1].serve.hedge.stats()
            assert hs["fired"] >= 1 and hs["won"] >= 1
            await asyncio.to_thread(nodes[1].obs.journal.flush)
            tail = await asyncio.to_thread(nodes[1].obs.journal.tail,
                                           0.0, 1024)
            store_hedges = [e for e in tail["events"]
                            if e.get("type") in ("hedge_fired",
                                                 "hedge_won")
                            and e.get("op") == "store_chunks"]
            assert store_hedges, "no store-side hedge evidence"
            # zero acked-byte loss through the pulses — from EVERY node
            for fid, want in uploaded:
                for n in nodes.values():
                    _, body = await n.download(fid)
                    assert bytes(body) == want
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# background index compaction (r16 leftover)
# ------------------------------------------------------------------ #

def test_background_compaction_off_worker_thread(tmp_path):
    """Satellite: with background_compact=True the full compaction
    runs on the dedicated thread — note() returns without folding runs
    inline, drain_compaction() reaches the folded state, and the
    stall-attribution counters surface."""
    idx = DigestIndex(tmp_path / "ix", memtable_entries=256,
                      compact_runs=2, background_compact=True)
    assert idx.open_or_rebuild(lambda: [])["rebuilt"] is False
    try:
        for batch in range(6):
            for i in range(256):
                idx.note_put(sha256_hex(f"{batch}:{i}".encode()))
        idx.drain_compaction()
        st = idx.stats()
        assert st["compactions"] >= 1
        assert st["runCount"] <= 3       # folded to (about) one base
        assert st["bgCompactS"] > 0.0    # the thread did the folding
        assert st["compactStallS"] == 0.0  # CAS workers never stalled
        # every key still resolves after the background fold
        assert idx.lookup(sha256_hex(b"0:0"))
        assert idx.lookup(sha256_hex(b"5:255"))
    finally:
        idx.close()


def test_inline_mode_unchanged_and_drain_is_noop(tmp_path):
    idx = DigestIndex(tmp_path / "ix", memtable_entries=256,
                      compact_runs=2)
    assert idx.open_or_rebuild(lambda: [])["rebuilt"] is False
    try:
        for batch in range(6):
            for i in range(256):
                idx.note_put(sha256_hex(f"{batch}:{i}".encode()))
        idx.drain_compaction()           # inline mode: returns at once
        st = idx.stats()
        assert st["compactions"] >= 1    # folded inline, as before
        assert st["bgCompactS"] == 0.0   # no thread involved
        assert idx.lookup(sha256_hex(b"3:7"))
    finally:
        idx.close()


# ------------------------------------------------------------------ #
# bench smoke (tier-1)
# ------------------------------------------------------------------ #

def test_bench_client_tiny_smoke(tmp_path):
    """bench_client.py --tiny end to end as a subprocess: every gate
    runs against a real multi-process cluster and the artifact schema
    locks (CLIENT_r19.json shape)."""
    out = tmp_path / "client.json"
    r = subprocess.run(
        [sys.executable, str(REPO / "bench_client.py"), "--tiny",
         "--out", str(out)],
        cwd=tmp_path, env={**os.environ, "JAX_PLATFORMS": "cpu",
                           "PYTHONPATH": str(REPO)},
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(out.read_text())
    assert rep["metric"] == "client_data_plane"
    assert rep["tiny"] is True and rep["ok"] is True
    for gate in ("dedup_reupload", "striped_speedup",
                 "verified_stale_and_slow", "interop"):
        assert gate in rep["gates"], rep["gates"]
        assert rep["gates"][gate]["ok"] is True, rep["gates"][gate]
