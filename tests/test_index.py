"""Dedup/index plane (dfs_tpu/index, docs/index.md).

Layers of coverage:

- UNIT: LSI round-trip through flush + compaction, torn-WAL-tail
  truncation, corrupt-run → rebuild-from-CAS, the blocked bloom's
  no-false-negative contract, and the filter delta/resync protocol
  including the corrupted-delta → full-resync path.
- DEFAULT-OFF IDENTITY: ``IndexConfig()`` builds no plane, no store
  seam, no sync loop — the zero-knob node runs the historical
  stat-per-digest paths (the chaos/serve discipline).
- CRASH SAFETY (real ``kill -9``): a child process feeds a real
  ChunkStore+DigestIndex and SIGKILLs itself mid-compaction (the
  DigestIndex hook seam — deterministic, before the CURRENT commit)
  and mid-append; the parent reopens and asserts the index's answers
  match a fresh CAS walk, with zero false positives (the one
  divergence direction the design forbids). Same discipline as the
  r11 journal torn-tail test.
- CLUSTER: filter gossip replicates, re-upload placement skips probe
  RPCs with copies verified pre-ack, a POISONED filter (forced false
  positive) is detected at verification and healed by a real transfer
  before the ack, and repair's probe trim never deletes strays on a
  bloom maybe.
- BENCH: ``bench_dedup_index.py --tiny`` subprocess smoke + schema
  lock for the committed DEDUP_INDEX_r16.json.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

from dfs_tpu.config import (CDCParams, CensusConfig, ClusterConfig,
                            IndexConfig, NodeConfig, PeerAddr)
from dfs_tpu.index import DELTA_CAP, IndexPlane
from dfs_tpu.index.filter import (BlockedBloomFilter, LocalFilter,
                                  PeerFilterSet)
from dfs_tpu.index.lsi import DigestIndex
from dfs_tpu.node.runtime import StorageNodeServer
from dfs_tpu.store.cas import ChunkStore
from dfs_tpu.utils.hashing import sha256_hex

REPO = Path(__file__).resolve().parent.parent
CDC = CDCParams(min_size=2048, avg_size=8192, max_size=65536)
CENSUS_OFF = CensusConfig(history_interval_s=0)


def _digests(n: int, tag: str = "") -> list[str]:
    return [sha256_hex(f"{tag}{i}".encode()) for i in range(n)]


# ------------------------------------------------------------------ #
# unit: log-structured index
# ------------------------------------------------------------------ #

def test_lsi_roundtrip_through_flush_and_compaction(tmp_path):
    """Puts and deletes survive memtable flushes and full compactions;
    lookups answer identically before and after reopen."""
    idx = DigestIndex(tmp_path / "ix", memtable_entries=256,
                      compact_runs=2)
    assert idx.open_or_rebuild(lambda: [])["rebuilt"] is False
    present = _digests(3000, "p")
    gone = _digests(300, "g")
    for d in present + gone:
        idx.note_put(d)
    for d in gone:
        idx.note_delete(d)
    assert idx.stats()["compactions"] > 0   # tiny memtable forced them
    assert all(idx.lookup(d) for d in present)
    assert not any(idx.lookup(d) for d in gone)
    assert not idx.lookup(sha256_hex(b"never-stored"))
    idx.close()

    idx2 = DigestIndex(tmp_path / "ix", memtable_entries=256,
                       compact_runs=2)
    info = idx2.open_or_rebuild(lambda: pytest.fail("no rebuild"))
    assert info["rebuilt"] is False
    assert all(idx2.lookup(d) for d in present)
    assert not any(idx2.lookup(d) for d in gone)
    idx2.close()


def test_lsi_torn_wal_tail_truncated_not_fatal(tmp_path):
    """A torn trailing WAL record (kill -9 mid-append) is discarded on
    replay; every record before it survives."""
    idx = DigestIndex(tmp_path / "ix", memtable_entries=4096)
    idx.open_or_rebuild(lambda: [])
    ds = _digests(10)
    for d in ds:
        idx.note_put(d)
    idx.close()
    cur = json.loads((tmp_path / "ix" / "CURRENT").read_bytes())
    with open(tmp_path / "ix" / cur["wal"], "ab") as f:
        f.write(b"\x01torn-mid-record")
    idx2 = DigestIndex(tmp_path / "ix", memtable_entries=4096)
    info = idx2.open_or_rebuild(lambda: [])
    assert info["rebuilt"] is False   # a torn tail is NOT corruption
    assert all(idx2.lookup(d) for d in ds)
    idx2.close()


def test_lsi_corrupt_run_rebuilds_from_cas_walk(tmp_path):
    """Structural damage (a flipped run byte breaks the footer crc)
    degrades to a rebuild from the CAS walk — ground truth wins."""
    idx = DigestIndex(tmp_path / "ix", memtable_entries=256)
    idx.open_or_rebuild(lambda: [])
    for d in _digests(600, "x"):
        idx.note_put(d)
    idx.close()
    run = next(p for p in (tmp_path / "ix").iterdir()
               if p.suffix == ".idx")
    data = bytearray(run.read_bytes())
    data[40] ^= 0xFF
    run.write_bytes(data)
    truth = _digests(50, "truth")
    events = []
    idx2 = DigestIndex(tmp_path / "ix", memtable_entries=256)
    idx2.on_event = lambda etype, **kw: events.append((etype, kw))
    info = idx2.open_or_rebuild(lambda: truth)
    assert info["rebuilt"] is True and info["entries"] == 50
    assert all(idx2.lookup(d) for d in truth)
    assert not idx2.lookup(_digests(1, "x")[0])
    assert [e for e, _ in events] == ["index_rebuild"]  # journaled
    idx2.close()


def test_lsi_fence_prefix_collision_across_blocks(tmp_path):
    """Fences hold 8-byte prefixes, which are ambiguous at block
    boundaries: thousands of digests sharing one prefix must all be
    found (the back-walk), and a tombstone in a newer run must never
    be missed in favor of an older run's stale 'present' (the
    resurrection the code-review fence finding described)."""
    idx = DigestIndex(tmp_path / "ix", memtable_entries=256,
                      compact_runs=2)
    idx.open_or_rebuild(lambda: [])
    prefix = "ab" * 8                       # one shared 8-byte prefix
    same = sorted(prefix + sha256_hex(str(i).encode())[16:]
                  for i in range(3000))     # ~3 fence blocks of one
    for d in same:                          # prefix after compaction
        idx.note_put(d)
    assert all(idx.lookup(d) for d in same)
    # tombstone digests across the span (first/boundary/last), then
    # force them into a NEWER run than the base holding the puts
    victims = [same[0], same[1023], same[1024], same[-1]]
    for d in victims:
        idx.note_delete(d)
    for d in _digests(600, "churn"):        # flush + fold the deletes
        idx.note_put(d)
    assert not any(idx.lookup(d) for d in victims)
    assert all(idx.lookup(d) for d in same if d not in victims)
    idx.close()


def test_lsi_wal_bounded_under_same_key_churn(tmp_path):
    """Repeated store/delete of ONE working set must not grow the WAL
    without bound: the record-count trigger flushes even though the
    memtable's distinct-key count never reaches its cap."""
    idx = DigestIndex(tmp_path / "ix", memtable_entries=256,
                      compact_runs=2)
    idx.open_or_rebuild(lambda: [])
    ds = _digests(16, "churn")
    for _ in range(400):                    # 6400 records, 16 keys
        for d in ds:
            idx.note_put(d)
    idx.flush()
    assert idx.stats()["walRecords"] <= 8 * 256
    wal = [p for p in (tmp_path / "ix").iterdir()
           if p.name.startswith("wal-")]
    assert all(p.stat().st_size <= 8 * 256 * 37 for p in wal)
    assert all(idx.lookup(d) for d in ds)
    idx.close()


def test_lsi_lookups_race_compactions_without_errors(tmp_path):
    """Unlocked run preads vs concurrent compactions (the retired-fd
    race): reader threads hammer lookups while the writer forces
    continual flush+compaction cycles — every answer must be correct
    and no reader may ever see an EBADF/garbage read."""
    import threading

    idx = DigestIndex(tmp_path / "ix", memtable_entries=256,
                      compact_runs=1)       # compact on every flush
    idx.open_or_rebuild(lambda: [])
    stable = _digests(1200, "stable")
    for d in stable:
        idx.note_put(d)
    absent = _digests(400, "absent")
    errors: list[BaseException] = []
    stop = threading.Event()

    def reader() -> None:
        try:
            while not stop.is_set():
                for d in stable[::97]:
                    assert idx.lookup(d)
                for d in absent[::37]:
                    assert not idx.lookup(d)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    for d in _digests(4000, "writer"):      # ~15 flush+compact cycles
        idx.note_put(d)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert errors == []
    assert idx.stats()["compactions"] >= 5
    assert all(idx.lookup(d) for d in stable)
    idx.close()


def test_chunkstore_feed_and_has_fast_path(tmp_path):
    """The ChunkStore seam: put/delete feed the plane, has() trusts
    index positives (no stat) and stat-backstops negatives."""
    store = ChunkStore(tmp_path / "chunks")
    plane = IndexPlane(IndexConfig(enabled=True), tmp_path)
    plane.open_or_rebuild(store.digests)
    store.index = plane
    payload = b"chunk-payload" * 100
    d = sha256_hex(payload)
    assert store.put(d, payload)
    assert plane.lookup(d)                  # fed by the put
    assert store.has(d)
    # negative backstop: a chunk written BEHIND the index (external
    # writer / pre-index store / crash-lost WAL buffer) is still found
    # by the stat — and the backstop SELF-HEALS the index, so the miss
    # is paid once, not on every future probe
    sneak = b"sneaky" * 50
    ds = sha256_hex(sneak)
    store.index = None
    assert store.put(ds, sneak)
    store.index = plane
    assert not plane.lookup(ds)
    assert store.has(ds)                    # stat backstop
    assert plane.lookup(ds)                 # ...which healed the index
    # delete is recorded: index answers absent afterwards
    assert store.delete(d)
    assert not plane.lookup(d)
    assert not store.has(d)
    plane.close()


# ------------------------------------------------------------------ #
# unit: filters + delta protocol
# ------------------------------------------------------------------ #

def test_bloom_no_false_negatives_and_bounded_fp():
    bloom = BlockedBloomFilter(4096, bits_per_key=10)
    members = _digests(4096, "m")
    for d in members:
        bloom.add(d)
    assert all(bloom.contains(d) for d in members)   # never a false no
    others = _digests(4096, "o")
    fp = sum(1 for d in others if bloom.contains(d))
    assert fp / len(others) < 0.05   # ~2% expected at this density


def test_filter_delta_then_generation_bump_forces_resync():
    f = LocalFilter(bits_per_key=10)
    first = _digests(100, "a")
    for d in first:
        f.add(d)
    meta, body = f.snapshot()
    ps = PeerFilterSet()
    ps.apply_full(7, meta, body)
    assert all(ps.contains(7, d) for d in first)
    more = _digests(40, "b")
    for d in more:
        f.add(d)
    delta = f.delta(meta["gen"], meta["version"])
    assert delta["resync"] is False and len(delta["adds"]) == 40
    assert ps.apply_delta(7, delta["gen"], delta["version"],
                          delta["adds"])
    assert all(ps.contains(7, d) for d in more)
    # rebuild (compaction) changes the generation: the old cursor must
    # be told to resync — deltas cannot unlearn deletes
    f.rebuild([bytes.fromhex(d) for d in first])
    assert f.generation != meta["gen"]
    assert f.delta(meta["gen"], meta["version"])["resync"] is True
    # generations are RANDOM per life/rebuild: a restarted node's
    # fresh filter must never collide with its crashed life's cursor
    assert LocalFilter().generation != LocalFilter().generation
    # far-behind cursor (add log exhausted) also resyncs
    for d in _digests(DELTA_CAP + 100, "flood"):
        f.add(d)
    assert f.delta(f.generation, 0)["resync"] is True


def test_corrupted_delta_rejected_then_full_resync_recovers():
    """A malformed delta must not poison the replica — apply_delta
    refuses it, and the caller's full-resync path converges (the
    at-least-once discipline the runtime sync loop implements)."""
    f = LocalFilter(bits_per_key=10)
    for d in _digests(50, "a"):
        f.add(d)
    meta, body = f.snapshot()
    ps = PeerFilterSet()
    ps.apply_full(3, meta, body)
    # corrupt shapes: non-list adds, non-hex digest, version regress
    assert not ps.apply_delta(3, meta["gen"], meta["version"] + 1,
                              "not-a-list")
    assert not ps.apply_delta(3, meta["gen"], meta["version"] + 1,
                              ["zz-not-hex"])
    assert not ps.apply_delta(3, meta["gen"], meta["version"] - 10, [])
    assert not ps.apply_delta(3, meta["gen"] + 5, meta["version"], [])
    # the replica survived untouched and a full resync still lands
    for d in _digests(20, "late"):
        f.add(d)
    meta2, body2 = f.snapshot()
    ps.apply_full(3, meta2, body2)
    st = ps.state(3)
    assert st["version"] == meta2["version"]
    assert all(ps.contains(3, d) for d in _digests(20, "late"))


def test_fp_override_breaks_retrust():
    f = LocalFilter(bits_per_key=10)
    d = _digests(1, "fp")[0]
    f.add(d)
    meta, body = f.snapshot()
    ps = PeerFilterSet()
    ps.apply_full(2, meta, body)
    assert ps.contains(2, d) is True
    ps.note_fp(2, d)
    assert ps.contains(2, d) is False      # override beats the bloom
    assert ps.fp_observed == 1
    ps.apply_full(2, meta, body)           # resync re-judges
    assert ps.contains(2, d) is True


# ------------------------------------------------------------------ #
# default-off identity
# ------------------------------------------------------------------ #

def _mk_cluster(n: int, rf: int) -> ClusterConfig:
    socks, ports = [], []
    for _ in range(2 * n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    peers = tuple(PeerAddr(node_id=i + 1, host="127.0.0.1",
                           port=ports[2 * i],
                           internal_port=ports[2 * i + 1])
                  for i in range(n))
    return ClusterConfig(peers=peers, replication_factor=rf)


async def _start_nodes(cluster, root, index=None, **kw):
    nodes = {}
    for p in cluster.peers:
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


def test_default_config_builds_no_plane(tmp_path):
    """IndexConfig() means NO plane: no store seam, no filter task, and
    /metrics reports the plane disabled — the zero-knob node runs the
    historical stat-per-digest code paths exactly."""
    assert IndexConfig() == IndexConfig(enabled=False)

    async def run() -> None:
        cluster = _mk_cluster(1, rf=1)
        nodes = await _start_nodes(cluster, tmp_path)
        node = nodes[1]
        try:
            assert node.index is None
            assert node.store.chunks.index is None
            assert node._filter_sync_task is None
            st = node.index_stats()
            assert st["enabled"] is False and "lsi" not in st
            # the data path still works (and no index dir appears)
            m, _ = await node.upload(b"identity" * 4000, "f.bin")
            _, body = await node.download(m.file_id)
            assert bytes(body) == b"identity" * 4000
            assert not (node.store.root / "index").exists()
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# crash safety: real kill -9, mid-compaction and mid-append
# ------------------------------------------------------------------ #

_CRASH_CHILD = textwrap.dedent("""
    import os, signal, sys
    sys.path.insert(0, {repo!r})
    from dfs_tpu.config import IndexConfig
    from dfs_tpu.index import IndexPlane
    from dfs_tpu.store.cas import ChunkStore
    from dfs_tpu.utils.hashing import sha256_hex

    root = {root!r}
    mode = {mode!r}
    store = ChunkStore(os.path.join(root, "chunks"))
    plane = IndexPlane(IndexConfig(enabled=True, memtable_entries=256,
                                   compact_runs=2), root)
    plane.open_or_rebuild(store.digests)
    store.index = plane
    compactions = 0
    def hook(point):
        global compactions
        compactions += 1
        if mode == "compact" and compactions >= 3:
            print("KILLING-MID-COMPACTION", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
    plane.lsi.hook = hook
    i = 0
    while True:
        payload = (b"crash-corpus-%d" % i) * 40
        d = sha256_hex(payload)
        store.put(d, payload)
        if i % 7 == 3 and i > 100:
            # interleave deletes: the written-through delete record is
            # the crash-ordering half the parent asserts on
            gone = (b"crash-corpus-%d" % (i - 100)) * 40
            store.delete(sha256_hex(gone))
        i += 1
        if i % 500 == 0:
            print("PROGRESS", i, flush=True)
""")


def _run_crash_child(tmp_path: Path, mode: str) -> None:
    child = tmp_path / "child.py"
    child.write_text(_CRASH_CHILD.format(repo=str(REPO),
                                         root=str(tmp_path / "store"),
                                         mode=mode))
    proc = subprocess.Popen(
        [sys.executable, str(child)], cwd=tmp_path,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if mode == "compact":
        # the child SIGKILLs ITSELF inside the 3rd compaction — before
        # the CURRENT commit, deterministically mid-compaction
        rc = proc.wait(timeout=120)
        assert rc == -signal.SIGKILL
        assert "KILLING-MID-COMPACTION" in (proc.stdout.read() or "")
    else:
        # mid-append: let it write for a moment, then kill -9 from
        # outside at an arbitrary instant (high probability of landing
        # inside a WAL append / flush — the journal-test discipline)
        deadline = time.time() + 60
        while time.time() < deadline:
            line = proc.stdout.readline()
            if line.startswith("PROGRESS"):
                break
        else:
            pytest.fail("crash child made no progress")
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)


@pytest.mark.parametrize("mode", ["compact", "append"])
def test_kill9_index_reopens_consistent_with_cas_walk(tmp_path, mode):
    """After a real SIGKILL mid-compaction (deterministic, via the
    DigestIndex hook seam) or mid-append, the reopened index must
    answer consistently with a fresh CAS walk: ZERO false positives
    (every index-present digest exists on disk) and has() — index fast
    path plus stat backstop — exactly equal to the walk for both
    present and absent digests."""
    _run_crash_child(tmp_path, mode)
    root = tmp_path / "store"
    store = ChunkStore(root / "chunks")
    walk = set(store.digests())
    assert walk, "child stored nothing before dying"
    plane = IndexPlane(IndexConfig(enabled=True, memtable_entries=256,
                                   compact_runs=2), root)
    info = plane.open_or_rebuild(store.digests)
    store.index = plane
    # candidate universe: everything the child could have written or
    # deleted, present or not
    universe = [sha256_hex((b"crash-corpus-%d" % i) * 40)
                for i in range(20000)]
    false_pos = [d for d in universe
                 if plane.lookup(d) and d not in walk]
    assert false_pos == [], (
        f"{len(false_pos)} stale-present digests after {mode} crash "
        f"(rebuilt={info['rebuilt']})")
    for d in universe[:4000]:
        assert store.has(d) == (d in walk)
    plane.close()


# ------------------------------------------------------------------ #
# cluster: gossip + probe skipping + FP healing
# ------------------------------------------------------------------ #

def test_cluster_filter_gossip_and_reupload_probe_skip(tmp_path):
    """Filters replicate via the sync round; a re-upload then credits
    every remote copy from the filters (zero transfer), issues only
    the pre-ack verification probes, and a fresh upload after that
    skips probe RPCs entirely (all digests ruled out)."""
    ix = IndexConfig(enabled=True, memtable_entries=1024,
                     filter_sync_s=0)   # synced explicitly below

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        nodes = await _start_nodes(cluster, tmp_path, index=ix)
        try:
            data = os.urandom(400_000)
            m, s1 = await nodes[1].upload(data, "a.bin")
            assert s1["transferredBytes"] > 0
            for n in nodes.values():
                assert await n._filter_sync_once() == 2
            probes_before = _client_probe_rpcs(nodes[1])
            m2, s2 = await nodes[1].upload(data, "again.bin")
            probes_during = _client_probe_rpcs(nodes[1]) - probes_before
            assert s2["transferredBytes"] == 0
            assert s2["dedupSkippedBytes"] == s1["transferredBytes"]
            assert s2["minCopies"] >= 2          # verified, not hoped
            st = nodes[1].index_stats()
            assert st["filterTrusted"] > 0
            assert st["probesSkipped"] >= st["filterTrusted"]
            assert st["filterFp"] == 0
            # the verification round probed, one RPC per peer — and,
            # since PR 27, one leg of the one batch asked its peer
            # about the chunks node 1 does not own, which would
            # otherwise rest on two filters' maybe alone (see the
            # two-filters-wrong test below): three, exactly
            assert probes_during == 3
            assert st["probeRpcsSkipped"] == 1     # the other leg
            # fresh data: every digest ruled out -> zero probe RPCs
            rpcs_before = _client_probe_rpcs(nodes[1])
            skipped_before = st["probeRpcsSkipped"]
            m3, s3 = await nodes[1].upload(os.urandom(200_000), "b.bin")
            assert _client_probe_rpcs(nodes[1]) == rpcs_before
            assert nodes[1].index_stats()["probeRpcsSkipped"] \
                > skipped_before
            # everything still reads back from every node
            for fid, want in ((m.file_id, data),):
                for n in nodes.values():
                    _, body = await n.download(fid)
                    assert bytes(body) == want
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def _client_probe_rpcs(node) -> int:
    return sum(row[0] for peer, op, row in node.obs.rpc_client.rows()
               if op == "has_chunks")


def test_poisoned_filter_fp_detected_and_healed_before_ack(tmp_path):
    """Force a false positive: poison node 1's replica of node 2's
    filter with the digests of an upload node 2 does NOT hold. The
    trusted credits must fail pre-ack verification, be counted as
    observed FPs, and be healed by a REAL transfer — after the ack the
    bytes exist on the peer (no phantom copies) and the file reads
    back from it."""
    ix = IndexConfig(enabled=True, filter_sync_s=0)

    async def run() -> None:
        cluster = _mk_cluster(2, rf=2)
        nodes = await _start_nodes(cluster, tmp_path, index=ix)
        try:
            seed = await nodes[1].upload(b"seed" * 3000, "seed.bin")
            for n in nodes.values():
                await n._filter_sync_once()
            data = os.urandom(120_000)
            manifest = nodes[1].fragmenter.manifest(
                data, name="x", file_id=sha256_hex(data))
            st2 = nodes[1].index.peer_filters.state(2)
            for c in manifest.chunks:
                st2["bloom"].add(c.digest)     # the lie
            m, stats = await nodes[1].upload(data, "x.bin")
            ixs = nodes[1].index_stats()
            assert ixs["filterFp"] > 0
            # healed: node 2 genuinely holds every chunk
            for c in m.chunks:
                assert nodes[2].store.chunks.has(c.digest)
            _, body = await nodes[2].download(m.file_id)
            assert bytes(body) == data
            # the heal transferred real bytes and un-counted the
            # phantom dedup credit
            assert stats["transferredBytes"] > 0
            assert seed is not None
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_two_filters_wrong_about_a_chunk_this_node_does_not_own(tmp_path):
    """Both owners of a fresh chunk are peers and BOTH their filters say
    maybe (found on the chip under a snapshot stream, PR 27: one upload
    in a hundred failed with "held nowhere reachable" — every copy a
    credit, the payload gone by the verify round). Placement settles
    before its legs start that one of them asks its peer about each
    such chunk while the bytes are in hand; the other credit is then an
    ordinary false positive the verify round heals. The upload is acked
    with every chunk on two nodes."""
    ix = IndexConfig(enabled=True, filter_sync_s=0)

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        nodes = await _start_nodes(cluster, tmp_path, index=ix)
        try:
            await nodes[1].upload(b"seed" * 3000, "seed.bin")
            for n in nodes.values():
                await n._filter_sync_once()
            data = os.urandom(200_000)
            manifest = nodes[1].fragmenter.manifest(
                data, name="x", file_id=sha256_hex(data))
            for peer in (2, 3):
                st = nodes[1].index.peer_filters.state(peer)
                for c in manifest.chunks:
                    st["bloom"].add(c.digest)     # both lie
            was = nodes[1].index_stats()
            m, stats = await nodes[1].upload(data, "x.bin")
            assert stats["minCopies"] >= 2
            assert stats["dedupSkippedBytes"] == 0
            owned_elsewhere = 0
            for c in m.chunks:
                held = [i for i in (1, 2, 3)
                        if nodes[i].store.chunks.has(c.digest)]
                assert len(held) >= 2, (c.digest, held)
                owned_elsewhere += 1 not in held
            assert owned_elsewhere > 0            # the case was met
            now = nodes[1].index_stats()
            grew = {k: now[k] - was[k] for k in (
                "filterFp", "filterTrusted", "placementSkipped",
                "placementConsidered")}
            # a leg a peer copy; one leg asked about each chunk owned
            # elsewhere and was told no, every other leg credited its
            # chunk and the verify round was told no; the heal then
            # placed the credited chunks again, every leg now ruled
            # out by the overrides and sent without asking
            legs = len(m.chunks) + owned_elsewhere
            assert grew == {"filterFp": legs,
                            "filterTrusted": len(m.chunks),
                            "placementSkipped": len(m.chunks) + legs,
                            "placementConsidered": 2 * legs}
            _, body = await nodes[3].download(m.file_id)
            assert bytes(body) == data
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_repair_probe_trim_never_trusts_positives(tmp_path):
    """Repair consults filters only for the NEGATIVE side (skip probe
    payload for ruled-out digests); confirmations that gate stray
    deletion stay real has_chunks answers. A cycle after a heal still
    converges — and a poisoned positive cannot make repair skip a
    push it owes."""
    ix = IndexConfig(enabled=True, filter_sync_s=0)

    async def run() -> None:
        cluster = _mk_cluster(2, rf=2)
        nodes = await _start_nodes(cluster, tmp_path, index=ix)
        try:
            data = os.urandom(150_000)
            m, _ = await nodes[1].upload(data, "r.bin")
            for n in nodes.values():
                await n._filter_sync_once()
            # node 2 loses a chunk; node 1's replica of node 2's
            # filter still says maybe-present (stale) — repair must
            # STILL push it (positives are probed, not trusted)
            lost = m.chunks[0].digest
            assert nodes[2].store.chunks.delete(lost)
            assert not nodes[2].store.chunks.has(lost)
            await nodes[1].repair_once()
            assert nodes[2].store.chunks.has(lost)
            assert nodes[1].index_stats()["filterFp"] >= 1
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_wal_replay_never_overwrites_pre_open_notes(tmp_path):
    """WAL records are strictly OLDER than anything noted in this
    life: a delete recorded before open() (the boot-sweep shape) must
    not be resurrected by the previous life's replayed put record."""
    idx = DigestIndex(tmp_path / "ix", memtable_entries=4096)
    idx.open_or_rebuild(lambda: [])
    d = sha256_hex(b"phantom")
    idx.note_put(d)
    idx.close()                      # the put record is in the WAL
    idx2 = DigestIndex(tmp_path / "ix", memtable_entries=4096)
    idx2.note_delete(d)              # noted BEFORE open
    idx2.open_or_rebuild(lambda: [])
    assert not idx2.lookup(d)
    idx2.close()


def test_boot_sweep_orphans_not_resurrected_by_index(tmp_path):
    """End to end: an aged orphan chunk swept at boot must be ABSENT
    from the index afterwards — the index opens before the sweep, so
    the sweep's deletes are recorded on a live index instead of being
    overwritten by the WAL replay (the phantom the code review's repro
    demonstrated: has_chunks answering 'have' for swept bytes)."""
    ix = IndexConfig(enabled=True, filter_sync_s=0)

    async def run() -> None:
        cluster = _mk_cluster(1, rf=1)
        nodes = await _start_nodes(cluster, tmp_path, index=ix)
        node = nodes[1]
        payload = b"orphan-chunk" * 800
        d = sha256_hex(payload)
        await node.cas.put(d, payload)      # no manifest: an orphan
        old = time.time() - 7200            # past the 1 h GC grace
        os.utime(node.store.chunks._path(d), (old, old))
        await _stop_all(nodes)
        nodes = await _start_nodes(cluster, tmp_path, index=ix)
        node = nodes[1]
        try:
            assert not (node.store.root / "chunks" / d[:2] / d).exists()
            assert not node.index.lookup(d)   # no phantom
            assert not node.store.chunks.has(d)
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_doctor_index_stale_rule():
    """The doctor names a node whose peer-filter replicas stopped
    refreshing (>= 10x the sync cadence, 60 s floor) — and stays quiet
    for fresh replicas, disabled planes, and exchange-off nodes."""
    from dfs_tpu.obs.doctor import diagnose

    now = time.time()

    def snap(index) -> dict:
        return {"now": now, "receivedAt": now, "index": index}

    findings = diagnose(
        {1: snap({"enabled": True, "syncS": 1.0,
                  "peerAgeS": {"2": 300.0, "3": 2.0}}),
         2: snap({"enabled": True, "syncS": 1.0,
                  "peerAgeS": {"1": 3.0}}),
         3: snap({"enabled": False})}, coordinator_now=now)
    stale = [f for f in findings if f["rule"] == "index_stale"]
    assert len(stale) == 1 and stale[0]["peers"] == [1]
    assert "'2'" in stale[0]["evidence"]
    # exchange off (syncS 0) or fresh everywhere: no finding
    findings = diagnose(
        {1: snap({"enabled": True, "syncS": 0,
                  "peerAgeS": {"2": 9999.0}}),
         2: snap({"enabled": True, "syncS": 1.0,
                  "peerAgeS": {"1": 1.0}})}, coordinator_now=now)
    assert not [f for f in findings if f["rule"] == "index_stale"]


# ------------------------------------------------------------------ #
# bench smoke + schema lock
# ------------------------------------------------------------------ #

def test_bench_dedup_index_tiny_smoke(tmp_path):
    """``bench_dedup_index.py --tiny`` end to end: all four gate
    families must hold at tiny scale, and the JSON schema matches what
    the committed DEDUP_INDEX_r16.json embeds."""
    out_path = tmp_path / "ix_tiny.json"
    res = subprocess.run(
        [sys.executable, str(REPO / "bench_dedup_index.py"), "--tiny",
         "--out", str(out_path)],
        cwd=tmp_path, capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(REPO)})
    assert res.returncode == 0, (
        f"bench_dedup_index --tiny failed:\n{res.stdout[-2000:]}"
        f"\n{res.stderr[-4000:]}")
    out = json.loads(out_path.read_text())
    assert out["metric"] == "dedup_index_plane" and out["round"] == 16
    assert out["ok"] is True
    g = out["gates"]
    assert g["memory"]["ok"] and g["memory"]["bytesPerChunk"] <= 32.0
    assert g["probe_reduction"]["ok"]
    # a batch: one leg asks, the other credits; then the verify round
    assert g["probe_reduction"]["probeRpcsOn"] \
        == g["probe_reduction"]["probeRpcsOff"] // 2 + 2
    assert g["dedup_preserved"]["ok"]
    assert g["dedup_preserved"]["storedBytesIndexOn"] \
        == g["dedup_preserved"]["storedBytesIndexOff"]
    assert g["crash_mid_compaction"]["ok"]
    assert g["crash_mid_compaction"]["ackedFilesIntact"]
    assert g["crash_mid_compaction"]["indexMatchesWalk"]


def test_lsi_open_info_runs_count_reported_under_lock(tmp_path):
    """r17 DFS008 regression: open_or_rebuild's run-list length moved
    under the store lock (nothing pins the open to run before workers
    start); the reported count must still match the persisted runs."""
    idx = DigestIndex(tmp_path / "ix", memtable_entries=256,
                      compact_runs=64)
    idx.open_or_rebuild(lambda: [])
    for d in _digests(600, "r"):
        idx.note_put(d)            # memtable flushes => persisted runs
    idx.close()
    idx2 = DigestIndex(tmp_path / "ix", memtable_entries=256,
                       compact_runs=64)
    info = idx2.open_or_rebuild(lambda: [])
    cur = json.loads((tmp_path / "ix" / "CURRENT").read_bytes())
    assert info["rebuilt"] is False
    assert info["runs"] == len(cur["runs"]) and info["runs"] > 0
    idx2.close()
