"""Pipelined write path (docs/ingest.md): the byte-budget backpressure
gate, the async CAS tier, per-peer windowed slice replication, the
once-per-peer transfer accounting, and the tier-1 smoke mode of
bench_ingest_pipeline.py (artifact schema + overlap engagement on every
run — the committed INGEST_r07.json carries the perf claim)."""

import asyncio
import json
import os
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from dfs_tpu.comm.rpc import InternalClient, RpcError, RpcUnreachable
from dfs_tpu.config import (CDCParams, ClusterConfig, IngestConfig,
                            NodeConfig, PeerAddr)
from dfs_tpu.node.ingest import ByteBudget
from dfs_tpu.node.runtime import StorageNodeServer
from dfs_tpu.store.aio import AsyncChunkStore
from dfs_tpu.store.cas import ChunkStore
from dfs_tpu.utils.hashing import sha256_hex

REPO = Path(__file__).resolve().parent.parent
CDC = CDCParams(min_size=64, avg_size=256, max_size=1024)


# ---------------------------------------------------------------------- #
# ByteBudget: byte-denominated backpressure
# ---------------------------------------------------------------------- #

def test_byte_budget_blocks_until_release():
    b = ByteBudget(100)
    assert b.acquire(60, timeout=0)
    assert b.acquire(40, timeout=0)
    assert not b.acquire(1, timeout=0.01)      # full: times out
    order = []

    def waiter():
        assert b.acquire(50, timeout=5)
        order.append("acquired")

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    assert order == []                          # still blocked
    b.release(60)
    t.join(timeout=5)
    assert order == ["acquired"]
    assert b.outstanding == 90


def test_byte_budget_admits_oversize_when_empty():
    """One chunk larger than the whole budget must not deadlock: it is
    admitted alone (budget oversubscribed until consumed)."""
    b = ByteBudget(100)
    assert b.acquire(500, timeout=0)            # empty gate: admitted
    assert not b.acquire(1, timeout=0.01)       # now genuinely full
    b.release(500)
    assert b.outstanding == 0
    assert b.acquire(1, timeout=0)


def test_byte_budget_release_clamps_at_zero():
    b = ByteBudget(10)
    b.release(99)                               # spurious release
    assert b.outstanding == 0
    assert b.acquire(10, timeout=0)


# ---------------------------------------------------------------------- #
# AsyncChunkStore: the bounded CAS thread pool
# ---------------------------------------------------------------------- #

def test_async_chunk_store_roundtrip(tmp_path, rng):
    store = ChunkStore(tmp_path / "chunks")
    aio = AsyncChunkStore(store, workers=2)
    payloads = [rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
                for n in (10, 1000, 5000)]
    items = [(sha256_hex(p), p) for p in payloads]

    async def run():
        stored = await aio.put_many(items)
        assert stored == [True, True, True]
        again = await aio.put_many(items)       # dedup: nothing new
        assert again == [False, False, False]
        got = dict(await aio.get_many(
            [d for d, _ in items] + ["0" * 64]))  # absent digest skipped
        assert got == dict(items)
        assert await aio.get("0" * 64) is None
        assert await aio.get(items[0][0]) == payloads[0]
        assert await aio.put(items[0][0], payloads[0]) is False

    asyncio.run(run())
    st = aio.stats()
    assert st["workers"] == 2 and st["ops"] >= 5
    assert st["busyS"] >= 0 and st["queueS"] >= 0
    aio.close()


def _items(n, seed=0, size=48):
    import random
    r = random.Random(seed)
    out = []
    for _ in range(n):
        b = r.randbytes(size)
        out.append((sha256_hex(b), b))
    return out


class _Jobs:
    """Which items each put_batch call (= one cas-w job) was handed."""

    def __init__(self, store, monkeypatch):
        self.calls = []
        real = store.put_batch

        def put_batch(items, verify=True):
            items = list(items)
            self.calls.append([d for d, _ in items])
            return real(items, verify=verify)

        monkeypatch.setattr(store, "put_batch", put_batch)


@pytest.mark.parametrize("workers", [4, 3])
def test_put_many_splits_a_batch_by_directory(tmp_path, monkeypatch,
                                              workers):
    """A large batch: at most ``workers`` jobs, every directory's files
    in one of them (one barrier per directory per batch, read from the
    store's counter), results in the items' order."""
    store = ChunkStore(tmp_path / "chunks", fsync=True)
    aio = AsyncChunkStore(store, workers=workers)
    old = _items(40, seed=1)
    store.put_batch(old)
    base_dirs = store.dir_barrier_count()
    jobs = _Jobs(store, monkeypatch)
    fresh = _items(600, seed=2)
    batch = fresh[:300] + old[:20] + fresh[300:] + fresh[:5]
    want_new = [True] * 300 + [False] * 20 + [True] * 300 + [False] * 5

    async def run():
        return await aio.put_many(batch)

    assert asyncio.run(run()) == want_new
    assert 2 <= len(jobs.calls) <= workers
    assert sorted(d for c in jobs.calls for d in c) \
        == sorted(d for d, _ in batch)
    where = {}
    for k, call in enumerate(jobs.calls):
        for d in call:
            assert where.setdefault(d[:2], k) == k    # a directory: one part
    sizes = [len(c) for c in jobs.calls]
    assert max(sizes) <= 2 * min(sizes)               # near-even ranges
    assert store.fsync_count() == 40 + 600
    assert store.dir_barrier_count() - base_dirs \
        == len({d[:2] for d, _ in fresh})
    st = aio.stats()
    assert st["pending"] == 0 and st["ops"] == len(jobs.calls)
    for d, b in batch:
        assert store.get(d) == b
    aio.close()


def test_put_many_small_batch_and_sim_stay_one_job(tmp_path, monkeypatch):
    store = ChunkStore(tmp_path / "chunks")
    aio = AsyncChunkStore(store, workers=4)
    jobs = _Jobs(store, monkeypatch)

    class _NoSim:                       # a plane that never deltas
        def sketch_for_batch(self, store, items):
            return {}

        def encode_for_put(self, store, digest, data, sketch=None):
            return None

    async def run():
        small = _items(40, seed=3)
        assert await aio.put_many(small) == [True] * 40
        assert len(jobs.calls) == 1
        store.sim = _NoSim()
        big = _items(400, seed=4)
        assert await aio.put_many(big) == [True] * 400
        assert len(jobs.calls) == 2     # one more job, not four
        store.sim = None
        assert await aio.put_many(big + small) == [False] * 440
        assert len(jobs.calls) > 2

    asyncio.run(run())
    assert aio.stats()["pending"] == 0
    aio.close()


def test_put_many_part_failure_fails_the_call(tmp_path, monkeypatch):
    """An exception in one part fails the call — after every part has
    ended — and leaves no temp; the backlog gauge returns to 0."""
    store = ChunkStore(tmp_path / "chunks", fsync=True)
    aio = AsyncChunkStore(store, workers=4)
    batch = _items(400, seed=5)
    victim = sorted(d for d, _ in batch)[-1]          # in the last part

    def fault(op, digest):
        if op == "put" and digest == victim:
            raise OSError(28, "No space left on device (injected)")

    store.fault = fault

    async def run():
        with pytest.raises(OSError, match="injected"):
            await aio.put_many(batch)

    asyncio.run(run())
    assert aio.stats()["pending"] == 0
    assert list(store.root.rglob(".tmp-*")) == []
    on_disk = store.digests()
    assert victim not in on_disk and 0 < len(on_disk) < 400
    assert store.fsync_count() == len(on_disk)        # the other parts ended
    store.fault = None

    async def again():
        return await aio.put_many(batch)

    got = asyncio.run(again())
    assert got.count(True) == 400 - len(on_disk)
    assert len(store.digests()) == 400
    aio.close()


# ---------------------------------------------------------------------- #
# cluster helpers (same in-process idiom as test_node_cluster)
# ---------------------------------------------------------------------- #

def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _cluster_cfg(n, rf=2):
    ports = _free_ports(2 * n)
    return ClusterConfig(peers=tuple(
        PeerAddr(node_id=i + 1, host="127.0.0.1", port=ports[2 * i],
                 internal_port=ports[2 * i + 1]) for i in range(n)),
        replication_factor=rf)


async def _start(cluster, root, **kw):
    nodes = {}
    for p in cluster.peers:
        cfg = NodeConfig(node_id=p.node_id, cluster=cluster,
                         data_root=root, fragmenter="cdc", cdc=CDC,
                         health_probe_s=0, **kw)
        n = StorageNodeServer(cfg)
        await n.start()
        nodes[p.node_id] = n
    return nodes


# ---------------------------------------------------------------------- #
# windowed slice replication (comm/rpc.py)
# ---------------------------------------------------------------------- #

def test_store_chunks_windowed_delivers_and_reports_peak(tmp_path, rng):
    async def run():
        cluster = _cluster_cfg(1, rf=1)
        nodes = await _start(cluster, tmp_path)
        try:
            peer = cluster.peer(1)
            client = InternalClient()
            payloads = [rng.integers(0, 256, size=2000,
                                     dtype=np.uint8).tobytes()
                        for _ in range(8)]
            slices = [[(sha256_hex(p), p)] for p in payloads]
            done = []
            peak = await client.store_chunks_windowed(
                peer, "", slices, window=3,
                on_slice=lambda part, echoed: done.append(
                    (part[0][0], list(echoed))))
            assert len(done) == 8
            for d, echoed in done:              # hash echo round-trips
                assert echoed == [d]
            # every slice landed on the peer
            for p in payloads:
                assert nodes[1].store.chunks.has(sha256_hex(p))
            assert peak >= 2                    # pipeline actually filled
            client.close()
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


def test_store_chunks_writes_through_the_cas_pool(tmp_path, rng):
    """A peer stores what it receives through the bounded CAS write pool
    (``ingest.cas.ops`` rises; ``cas.put_many`` is a child span of
    ``peer.store_chunks``), as a batch, and leaves out a chunk whose
    echo differs from the digest claimed for it."""
    async def run():
        cluster = _cluster_cfg(1, rf=1)
        nodes = await _start(cluster, tmp_path)
        node = nodes[1]
        try:
            peer = cluster.peer(1)
            client = InternalClient()
            good = _items(200, seed=7, size=300)
            liar = ("f" * 64, b"not what the digest says")
            ops0 = node.ingest_stats()["cas"]["ops"]
            dirs0 = node.durability_stats()["dirBarriers"]
            files0 = node.durability_stats()["fsyncs"]
            echoed = await client.store_chunks(
                peer, "", good[:100] + [liar] + good[100:])
            assert len(echoed) == 201
            assert echoed[100] == sha256_hex(liar[1]) != liar[0]
            assert [e for i, e in enumerate(echoed) if i != 100] \
                == [d for d, _ in good]
            for d, b in good:
                assert node.store.chunks.get(d) == b
            assert not node.store.chunks.has(liar[0])
            assert not node.store.chunks.has(sha256_hex(liar[1]))
            assert node.ingest_stats()["cas"]["ops"] > ops0
            assert node.ingest_stats()["cas"]["pending"] == 0
            dur = node.durability_stats()
            assert dur["fsyncs"] - files0 == 200
            assert dur["dirBarriers"] - dirs0 \
                == len({d[:2] for d, _ in good}) < 200
            spans = node.obs.spans_between(0, 2 ** 63 - 1)
            by_id = {s["s"]: s for s in spans}
            puts = [s for s in spans if s["name"] == "cas.put_many"]
            assert len(puts) == 1
            assert by_id[puts[0]["p"]]["name"] == "peer.store_chunks"
            client.close()
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


def test_store_chunks_windowed_callback_error_propagates(tmp_path, rng):
    """An on_slice exception (the caller's hash-echo verdict) must cancel
    the remaining in-flight slices and propagate — the serial path's
    failure semantics."""
    async def run():
        cluster = _cluster_cfg(1, rf=1)
        nodes = await _start(cluster, tmp_path)
        try:
            peer = cluster.peer(1)
            client = InternalClient()
            payloads = [rng.integers(0, 256, size=1000,
                                     dtype=np.uint8).tobytes()
                        for _ in range(6)]
            slices = [[(sha256_hex(p), p)] for p in payloads]

            def on_slice(part, echoed):
                raise RpcError("verification failed (injected)")

            with pytest.raises(RpcError, match="injected"):
                await client.store_chunks_windowed(
                    peer, "", slices, window=2, on_slice=on_slice)
            client.close()
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


def test_repair_probes_a_peer_in_bounded_slices(tmp_path, rng, monkeypatch):
    """The repair cycle asks a peer what it holds in bounded has_chunks
    calls, never one list of every digest (one cas.has_many job of the
    whole store on the peer's latency lane outlasts the request timeout
    on a slow file system and starves live uploads' probes), and still
    finds and repairs exactly what is missing."""
    data = rng.integers(0, 256, size=300_000, dtype=np.uint8).tobytes()
    monkeypatch.setattr(StorageNodeServer, "_PROBE_SLICE_DIGESTS", 100)

    async def run():
        cluster = _cluster_cfg(2, rf=2)
        nodes = await _start(cluster, tmp_path)
        try:
            async def blocks():
                yield data

            manifest, _ = await nodes[1].upload_stream(blocks(), "r.bin")
            digests = sorted({c.digest for c in manifest.chunks})
            assert len(digests) > 300
            victims = digests[::97]                 # lost on the peer
            for d in victims:
                assert nodes[2].store.chunks.delete(d)
            asked = []
            real = nodes[2]._dispatch

            async def dispatch(header, body):
                if header.get("op") == "has_chunks":
                    asked.append(list(header.get("digests", [])))
                return await real(header, body)

            monkeypatch.setattr(nodes[2], "_dispatch", dispatch)
            repaired = await nodes[1].repair_once()
            assert len(asked) >= 4 and max(map(len, asked)) <= 100
            assert sorted(d for part in asked for d in part) == digests
            assert repaired == len(victims)
            for d in victims:
                assert nodes[2].store.chunks.has(d)
            asked.clear()
            assert await nodes[1].repair_once() == 0     # and a clean cycle
            assert sorted(d for part in asked for d in part) == digests
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------- #
# the resident set on the wire (PR 28): placement's has_chunks carries
# `residentOk`, every other caller is answered from the disk
# ---------------------------------------------------------------------- #

def _chunk_stats(monkeypatch, nodes):
    """Record every ``stat`` of a chunk file name under a node's chunk
    store: (node id, digest), in order."""
    real = os.stat
    roots = {os.fspath(n.store.chunks.root): nid
             for nid, n in nodes.items()}
    seen = []

    def stat(path, *a, **kw):
        if isinstance(path, (str, os.PathLike)):
            head, name = os.path.split(os.fspath(path))
            nid = roots.get(os.path.dirname(head))
            if nid is not None and len(name) == 64:
                seen.append((nid, name))
        return real(path, *a, **kw)

    monkeypatch.setattr(os, "stat", stat)
    return seen


@pytest.mark.parametrize("index", [False, True],
                         ids=["index-off", "index-on"])
def test_reupload_through_another_coordinator_stats_no_known_chunk(
        tmp_path, rng, monkeypatch, index):
    """What every node holds it linked itself: a re-upload through
    another coordinator — its own pre-check, both peers' has_chunks —
    issues no ``stat`` for a chunk name on any node, nor did the first
    upload (until PR 44 one a digest a holder); /metrics says so. With the index
    plane on every node (since PR 39) the same, and no index lookup
    either: the resident set stands in front of the index."""
    from dfs_tpu.config import IndexConfig
    data = rng.integers(0, 256, size=300_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = _cluster_cfg(3, rf=2)
        nodes = await _start(
            cluster, tmp_path,
            **({"index": IndexConfig(enabled=True)} if index else {}))

        def lookups():
            return sum(n.index.lsi.stats()["lookups"]
                       for n in nodes.values()) if index else 0

        try:
            seen = _chunk_stats(monkeypatch, nodes)
            manifest, _ = await nodes[1].upload(data, "first.bin")
            digests = {c.digest for c in manifest.chunks}
            assert len(digests) > 300
            # first sight: the holders' put pre-checks and the probes —
            # answered "absent" from memory, a booted node's set being
            # complete (PR 44): no stat of a name nobody has either
            assert seen == []
            for n in nodes.values():
                dur = n.durability_stats()
                assert dur["residentHits"] == 0 and dur["residentComplete"]
                assert dur["residentAbsent"] == dur["residentMisses"] > 0
            held = {nid: set(n.store.chunks.digests())
                    for nid, n in nodes.items()}
            seen.clear()
            asked = lookups()
            again, stats = await nodes[2].upload(data, "first.bin")
            assert again.file_id == manifest.file_id
            assert seen == [] and lookups() == asked
            # (plane on, before the first filter gossip: a peer's filter
            # rules the chunks out and they are sent; the peer's put
            # pre-check is then what finds them — in the set)
            assert index or stats["transferredBytes"] == 0
            for nid, n in nodes.items():
                dur = n.durability_stats()
                assert dur["residentEntries"] == len(held[nid]) > 0
                assert dur["residentHits"] >= len(held[nid])
                assert dur["residentDrops"] == 0
                assert set(n.store.chunks.digests()) == held[nid]
            assert sum(n.durability_stats()["residentHits"]
                       for n in nodes.values()) >= 2 * len(digests)
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


@pytest.mark.parametrize("look", ["a_stat_a_name", "a_listing_a_directory",
                                  "as_shipped"])
def test_repair_restores_a_file_removed_behind_a_store_that_remembers_it(
        tmp_path, rng, monkeypatch, look):
    """The one caveat and its bound: a chunk file unlinked behind the
    peer's store, after the store memoised it, is still "present" to a
    caller that takes a resident answer — and ONE repair cycle, which
    looks at the disk, drops the entry, pushes, and the push's pre-check
    misses and writes: exactly that chunk, byte-identical. The look is
    the same bound whether the peer's store answers a directory's names
    by a ``stat`` each, from one listing of it (since PR 35), or — as
    shipped, on a store this small — some directories one way and some
    the other."""
    import dfs_tpu.store.cas as cas
    if look != "as_shipped":
        monkeypatch.setattr(cas, "_LIST_MIN_NAMES",
                            1 if look == "a_listing_a_directory"
                            else 10 ** 9)
    data = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = _cluster_cfg(2, rf=2)
        nodes = await _start(cluster, tmp_path)
        try:
            manifest, _ = await nodes[1].upload(data, "r.bin")
            digests = sorted({c.digest for c in manifest.chunks})
            victim = digests[len(digests) // 2]
            ch = nodes[2].store.chunks
            payload = ch.get(victim)
            assert ch.has(victim, resident_ok=True)
            os.unlink(ch._path_str(victim))         # behind its back
            assert ch.has(victim, resident_ok=True)  # the caveat
            asked = []
            real = nodes[2]._dispatch

            async def dispatch(header, body):
                if header.get("op") == "has_chunks":
                    asked.append(header)
                return await real(header, body)

            monkeypatch.setattr(nodes[2], "_dispatch", dispatch)
            seen = _chunk_stats(monkeypatch, nodes)
            listed = []
            real_scandir = os.scandir

            def scandir(path):
                listed.append(os.fspath(path))
                return real_scandir(path)

            monkeypatch.setattr(os, "scandir", scandir)
            before = ch.look_stats()
            assert await nodes[1].repair_once() == 1
            assert asked and not any("residentOk" in h for h in asked)
            assert sorted(d for h in asked for d in h["digests"]) \
                == digests
            # the cycle looked at every name on the peer's disk: each
            # asked name by a stat, or from a listing of its directory
            looked = {k: v - before[k] for k, v in ch.look_stats().items()}
            assert looked["lookStats"] + looked["lookListed"] \
                == len(digests)
            dirs = {os.path.dirname(ch._path_str(d)) for d in digests}
            statted = {d for nid, d in seen if nid == 2}
            if look == "a_stat_a_name":
                assert looked["lookListings"] == 0
                assert statted == set(digests)
            elif look == "a_listing_a_directory":
                assert looked == {"lookStats": 0,
                                  "lookListed": len(digests),
                                  "lookListings": len(dirs)}
                assert dirs <= set(listed)
                assert nodes[2].durability_stats()["lookListed"] \
                    == len(digests)
            else:
                assert looked["lookListings"] > 0 < looked["lookStats"]
                assert statted | {
                    d for d in digests if os.path.dirname(
                        ch._path_str(d)) in set(listed)} == set(digests)
            assert os.path.isfile(ch._path_str(victim))
            assert ch.get(victim) == payload
            assert ch.resident_stats()["residentDrops"] == 1
            assert sorted(ch.digests()) == digests
            assert await nodes[1].repair_once() == 0
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


def test_index_on_scrub_drops_what_was_removed_behind_a_store_that_remembers(
        tmp_path, rng):
    """The one caveat with the index plane on: a chunk file unlinked
    behind the store is "present" to the index AND to the resident set
    in front of it. Scrub, which expunges the index's phantom, drops the
    resident entry with it — a caller that takes a resident answer hears
    "absent" from then on — and one repair cycle puts the file back."""
    from dfs_tpu.config import IndexConfig
    data = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = _cluster_cfg(2, rf=2)
        nodes = await _start(cluster, tmp_path,
                             index=IndexConfig(enabled=True))
        try:
            manifest, _ = await nodes[1].upload(data, "s.bin")
            digests = sorted({c.digest for c in manifest.chunks})
            victim = digests[len(digests) // 3]
            ch = nodes[2].store.chunks
            payload = ch.get(victim)
            assert ch.resident_stats()["residentEntries"] == len(digests)
            os.unlink(ch._path_str(victim))             # behind its back
            assert ch.has(victim, resident_ok=True)     # the caveat,
            assert ch.has(victim)                       # the index's too
            out = await nodes[2].scrub_once()
            assert out["healedPhantom"] == 1
            assert not ch.has(victim) \
                and not ch.has(victim, resident_ok=True)
            assert ch.resident_stats()["residentDrops"] == 1
            assert ch.resident_stats()["residentEntries"] \
                == len(digests) - 1
            assert await nodes[1].repair_once() == 1
            assert ch.get(victim) == payload
            assert ch.has(victim, resident_ok=True) and ch.has(victim)
            assert (await nodes[2].scrub_once())["healedPhantom"] == 0
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


def test_has_chunks_without_the_key_is_answered_from_the_disk(
        tmp_path, rng):
    """An older caller — and the repair cycle, who_has, relocation, the
    smart client — sends no `residentOk`: its answer is a look at the
    disk, which heals the set for the callers that do."""
    async def run():
        cluster = _cluster_cfg(1, rf=1)
        nodes = await _start(cluster, tmp_path)
        node = nodes[1]
        try:
            peer = cluster.peer(1)
            client = InternalClient()
            items = _items(40, seed=3, size=200)
            await client.store_chunks(peer, "", items)
            digests = [d for d, _ in items]
            lost = digests[7]
            os.unlink(node.store.chunks._path_str(lost))

            async def have(**extra):
                resp, _ = await client.call(
                    peer, {"op": "has_chunks", "digests": digests,
                           **extra})
                return resp["have"]

            assert await have(residentOk=True) == digests   # remembered
            assert await have(residentOk=False) \
                == [d for d in digests if d != lost]        # the disk
            assert await have(residentOk=True) \
                == [d for d in digests if d != lost]        # healed
            os.unlink(node.store.chunks._path_str(digests[8]))
            assert await have() \
                == [d for d in digests if d not in (lost, digests[8])]
            assert node.durability_stats()["residentDrops"] == 2
            assert node.durability_stats()["residentEntries"] == 38
            client.close()
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


def test_a_handler_that_does_not_know_the_key_ignores_it(
        tmp_path, rng, monkeypatch):
    """A peer running the parent's handler reads `digests` alone: an
    upload placed through it succeeds, dedups, and is repaired as
    before; the key rides placement's calls only."""
    data = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = _cluster_cfg(2, rf=2)
        nodes = await _start(cluster, tmp_path)
        try:
            old = nodes[2]
            real = old._dispatch
            asked = []

            async def parents_dispatch(header, body):
                if header.get("op") != "has_chunks":
                    return await real(header, body)
                asked.append("residentOk" in header)
                digests = header.get("digests", [])
                mask = await old.cas.has_many(digests)
                return {"ok": True, "have": [
                    d for d, h in zip(digests, mask) if h]}, b""

            monkeypatch.setattr(old, "_dispatch", parents_dispatch)
            manifest, stats = await nodes[1].upload(data, "o.bin")
            assert stats["minCopies"] == 2 and asked and all(asked)
            _, stats2 = await nodes[1].upload(data, "o.bin")
            assert stats2["transferredBytes"] == 0
            assert old.durability_stats()["residentHits"] == 0
            n_placed = len(asked)
            assert await nodes[1].repair_once() == 0
            assert len(asked) > n_placed and not any(asked[n_placed:])
            _, body = await old.download(manifest.file_id)
            assert bytes(body) == data
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------- #
# transfer accounting: bytes counted at most once per peer, per-slice
# crediting across primary + handoff passes
# ---------------------------------------------------------------------- #

def test_transfer_accounting_counts_once_per_peer(tmp_path, rng):
    """Fail the SECOND slice to one peer mid-upload: the first slice's
    chunks are echo-verified on that peer and must stay credited (no
    handoff re-transfer of delivered bytes), and ``transferredBytes``
    must equal the bytes that actually crossed the wire — each chunk at
    most once per peer."""
    data = rng.integers(0, 256, size=120_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = _cluster_cfg(3, rf=2)
        # serial slices: deterministic first-slice-then-failure order
        nodes = await _start(cluster, tmp_path,
                             ingest=IngestConfig(slice_inflight=1))
        try:
            up = nodes[1]
            up.placement.slice_bytes = 16 * 1024    # several slices/peer
            orig = up.client.store_chunks
            delivered: list[tuple[int, str, int]] = []
            peer2_calls = {"n": 0}

            async def flaky(peer, file_id, chunks):
                if peer.node_id == 2:
                    peer2_calls["n"] += 1
                    if peer2_calls["n"] >= 2:
                        raise RpcUnreachable("injected slice failure")
                echoed = await orig(peer, file_id, chunks)
                delivered.extend((peer.node_id, d, len(b))
                                 for d, b in chunks)
                return echoed

            up.client.store_chunks = flaky
            manifest, stats = await up.upload(data, "acct.bin")
            # quorum held: slice-1 chunks kept their peer-2 credit,
            # slice-2 chunks found copies via handoff
            assert stats["minCopies"] >= 2
            # nothing crossed the wire twice to the same peer…
            pairs = [(nid, d) for nid, d, _ in delivered]
            assert len(pairs) == len(set(pairs))
            # …and the stat equals exactly the bytes that did cross it
            assert stats["transferredBytes"] == sum(
                ln for _, _, ln in delivered)

            # re-upload the same payload with the fault healed: skipped
            # + transferred must cover every remote copy exactly once
            up.client.store_chunks = orig
            _, stats2 = await up.upload(data, "acct.bin")
            ids = cluster.sorted_ids()
            from dfs_tpu.node.placement import replica_set
            seen = {}
            for c in manifest.chunks:
                seen.setdefault(c.digest, c.length)
            remote_total = sum(
                ln * sum(1 for t in replica_set(d, ids, 2) if t != 1)
                for d, ln in seen.items())
            assert (stats2["transferredBytes"]
                    + stats2["dedupSkippedBytes"]) == remote_total
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------- #
# windowed ingest over a real cluster: equivalence + metrics surface
# ---------------------------------------------------------------------- #

def test_windowed_cluster_ingest_and_metrics(tmp_path, rng):
    data = rng.integers(0, 256, size=400_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = _cluster_cfg(3, rf=2)
        nodes = await _start(cluster, tmp_path,
                             ingest=IngestConfig(window=3,
                                                 flush_bytes=64 * 1024))
        try:
            async def blocks():
                for off in range(0, len(data), 20_000):
                    yield data[off:off + 20_000]

            manifest, stats = await nodes[1].upload_stream(blocks(),
                                                           "w.bin")
            assert stats["minCopies"] >= 2
            # download from a DIFFERENT node: replicated bytes intact
            _, got = await nodes[3].download(manifest.file_id)
            assert got == data
            ing = nodes[1].ingest_stats()
            assert ing["window"] == 3
            assert ing["stalls"].get("placeWindowPeak", 0) >= 2
            assert ing["cas"]["ops"] > 0
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------- #
# tier-1 smoke: bench_ingest_pipeline --tiny exercises the overlap logic
# and the artifact schema on every run
# ---------------------------------------------------------------------- #

def test_bench_ingest_pipeline_tiny(tmp_path):
    out_path = tmp_path / "INGEST_tiny.json"
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    proc = subprocess.run(
        [sys.executable, str(REPO / "bench_ingest_pipeline.py"),
         "--tiny", "--out", str(out_path)],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    art = json.loads(out_path.read_text())
    # schema: the keys INGEST_r07.json (full mode) commits to
    for key in ("metric", "round", "mode", "workload", "serial",
                "windowed", "speedup", "byte_identical", "overlap", "ok"):
        assert key in art, f"artifact missing {key!r}"
    assert art["metric"] == "ingest_pipeline" and art["mode"] == "tiny"
    assert art["byte_identical"] is True
    assert art["ok"] is True
    # the pipeline actually overlapped: batch window and per-peer slice
    # window both filled beyond one
    assert art["overlap"]["place_window_peak"] >= 2
    assert art["overlap"]["slice_inflight_peak"] >= 2
    for phase in ("serial", "windowed"):
        assert art[phase]["seconds"] > 0
        assert art[phase]["ingest"]["cas"]["ops"] > 0
