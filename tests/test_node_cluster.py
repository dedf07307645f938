"""Integration tests: an N-node cluster in one process (each node a real
asyncio server pair on localhost ports), scripting the reference's manual
verification scenarios (README.md:172-179, SURVEY.md §4) plus the new
capabilities (dedup transfer skip, write-quorum, repair, delete).

No TPU involved: nodes use the CPU CDC fragmenter — the fragmenter interface
makes the distributed layer backend-agnostic.
"""

import asyncio
import socket
from pathlib import Path

import numpy as np
import pytest

from dfs_tpu.cli.client import NodeClient
from dfs_tpu.config import CDCParams, ClusterConfig, NodeConfig, PeerAddr
from dfs_tpu.node.runtime import (DownloadError, NotFoundError,
                                  StorageNodeServer, UploadError)

CDC = CDCParams(min_size=64, avg_size=256, max_size=1024)


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


def make_cluster_cfg(n: int, rf: int = 2) -> ClusterConfig:
    ports = _free_ports(2 * n)
    peers = tuple(
        PeerAddr(node_id=i + 1, host="127.0.0.1",
                 port=ports[2 * i], internal_port=ports[2 * i + 1])
        for i in range(n))
    return ClusterConfig(peers=peers, replication_factor=rf)


async def start_nodes(cluster: ClusterConfig, root: Path,
                      ids=None, **cfg_kw) -> dict[int, StorageNodeServer]:
    nodes = {}
    cfg_kw.setdefault("cdc", CDC)
    for p in cluster.peers:
        if ids is not None and p.node_id not in ids:
            continue
        cfg = NodeConfig(node_id=p.node_id, cluster=cluster, data_root=root,
                         fragmenter="cdc", **cfg_kw)
        node = StorageNodeServer(cfg)
        await node.start()
        nodes[p.node_id] = node
    return nodes


async def stop_nodes(nodes) -> None:
    for n in nodes.values():
        await n.stop()


def test_upload_download_across_nodes(tmp_path, rng):
    """Round-trip through different nodes: upload at node 1, list + download
    at node 3 (reference scenario README.md:173-176)."""
    data = rng.integers(0, 256, size=50_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(5)
        nodes = await start_nodes(cluster, tmp_path)
        try:
            manifest, stats = await nodes[1].upload(data, "blob.bin")
            assert stats["uniqueChunks"] == manifest.total_chunks
            # every node lists the file (announce-to-all, §3.4)
            for n in nodes.values():
                assert [f["fileId"] for f in n.list_files()] == [manifest.file_id]
            m2, got = await nodes[3].download(manifest.file_id)
            assert got == data and m2.name == "blob.bin"
            # downloading node must have pulled remote chunks
            assert nodes[3].counters.snapshot().get("chunks_fetched_remote", 0) > 0
            return manifest
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_download_with_one_node_offline(tmp_path, rng):
    """The reference's headline fault-tolerance claim, automated: kill one
    node, download still reconstructs (README.md:177, StorageNode.java:425-441)."""
    data = rng.integers(0, 256, size=80_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(5)
        nodes = await start_nodes(cluster, tmp_path)
        try:
            manifest, _ = await nodes[1].upload(data, "resilient.bin")
            # kill node 4 (its chunks stay on its disk, but it's unreachable)
            await nodes.pop(4).stop()
            _, got = await nodes[2].download(manifest.file_id)
            assert got == data
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_upload_with_node_down_write_quorum(tmp_path, rng):
    """Upload succeeds with a node down (write-quorum) — the reference aborts
    the whole upload in this case (StorageNode.java:218-221); SURVEY.md §5.3
    mandates quorum + repair instead. After the node returns, repair_once
    restores full replication."""
    data = rng.integers(0, 256, size=60_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(5)
        nodes = await start_nodes(cluster, tmp_path, ids={1, 2, 3, 4},
                                  retries=1, connect_timeout_s=0.3)
        try:
            manifest, _ = await nodes[1].upload(data, "quorum.bin")
            _, got = await nodes[2].download(manifest.file_id)
            assert got == data

            # node 5 comes back empty-handed; repair pushes its chunks
            nodes.update(await start_nodes(cluster, tmp_path, ids={5},
                                           retries=1, connect_timeout_s=0.3))
            repaired = await nodes[1].repair_once()
            ids = cluster.sorted_ids()
            from dfs_tpu.node.placement import replica_set
            for c in manifest.chunks:
                for target in replica_set(c.digest, ids, 2):
                    assert nodes[target].store.chunks.has(c.digest), \
                        f"chunk {c.digest[:8]} missing on node {target}"
            assert repaired > 0
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_upload_all_peers_down_fails_at_default_quorum(tmp_path, rng):
    """With every peer down, the default write_quorum=2 must refuse the
    upload — a 201 with exactly one copy in the world is weaker durability
    than the reference's write-all (VERDICT r1 weak §6)."""
    data = rng.integers(0, 256, size=20_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path, ids={1},
                                  retries=1, connect_timeout_s=0.2)
        try:
            assert nodes[1].cfg.write_quorum == 2   # the default
            with pytest.raises(UploadError):
                await nodes[1].upload(data, "doomed.bin")
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_upload_handoff_keeps_quorum_with_target_down(tmp_path, rng):
    """A dead canonical target must not fail the upload OR degrade to one
    copy: sloppy-quorum handoff places the second copy on the next ring
    node, the response reports it, and repair migrates it back."""
    data = rng.integers(0, 256, size=60_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(4)
        nodes = await start_nodes(cluster, tmp_path, ids={1, 2, 3},
                                  retries=1, connect_timeout_s=0.3)
        try:
            manifest, stats = await nodes[1].upload(data, "handoff.bin")
            assert stats["minCopies"] >= 2          # quorum held
            # every unique chunk has >= 2 live copies among nodes 1..3
            alive = [nodes[i] for i in (1, 2, 3)]
            for c in manifest.chunks:
                have = sum(n.store.chunks.has(c.digest) for n in alive)
                assert have >= 2, f"chunk {c.digest[:8]} has {have} copies"
            if stats["handoffChunks"]:
                assert stats["degraded"]
                # node 4 returns; repair restores canonical placement
                nodes.update(await start_nodes(
                    cluster, tmp_path, ids={4},
                    retries=1, connect_timeout_s=0.3))
                await nodes[1].repair_once()
                from dfs_tpu.node.placement import replica_set
                ids = cluster.sorted_ids()
                for c in manifest.chunks:
                    for t in replica_set(c.digest, ids, 2):
                        assert nodes[t].store.chunks.has(c.digest)
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_upload_fails_below_quorum(tmp_path, rng):
    """With every replica target down and quorum unreachable, upload must
    fail loudly (HTTP 500 'Replication failed' at the API layer)."""
    data = rng.integers(0, 256, size=30_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path, ids={1},
                                  retries=1, connect_timeout_s=0.2,
                                  write_quorum=2)
        try:
            with pytest.raises(UploadError):
                await nodes[1].upload(data, "doomed.bin")
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_dedup_skips_transfer(tmp_path, rng):
    """Re-uploading identical content must move (almost) no chunk bytes —
    the content-addressed dedup the reference only has at whole-file level
    (SURVEY.md §2.5(4))."""
    data = rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(4)
        nodes = await start_nodes(cluster, tmp_path)
        try:
            _, s1 = await nodes[1].upload(data, "v1.bin")
            assert s1["transferredBytes"] > 0
            _, s2 = await nodes[1].upload(data, "v1-again.bin")
            assert s2["transferredBytes"] == 0
            assert s2["dedupSkippedBytes"] > 0

            # near-duplicate: most chunks shared → transfer ≪ full size
            edited = data[:500] + b"PATCH" + data[500:]
            _, s3 = await nodes[2].upload(edited, "v2.bin")
            assert s3["transferredBytes"] < len(edited) // 2
            return None
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_rpc_connection_reuse(tmp_path, rng):
    """The storage plane must NOT reconnect per RPC: across an upload +
    cross-node download, each node dials each peer a bounded number of
    times (pool warm-up + concurrency), far fewer than the RPC count."""
    import dfs_tpu.comm.rpc as rpc_mod

    data = rng.integers(0, 256, size=120_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        dials = 0
        real_open = asyncio.open_connection

        async def counting_open(*a, **kw):
            nonlocal dials
            dials += 1
            return await real_open(*a, **kw)

        rpc_mod.asyncio.open_connection = counting_open
        try:
            m, _ = await nodes[1].upload(data, "pooled.bin")
            for _ in range(5):
                _, got = await nodes[2].download(m.file_id)
                assert got == data
            calls = sum(n.counters.snapshot().get("chunks_fetched_remote", 0)
                        for n in nodes.values())
            # 2 peers × ≤ pool size dials per node would be the cap if
            # everything were perfectly reused; allow slack for handshake
            # concurrency but reconnect-per-RPC (≥ 1 dial per call) fails
            assert dials <= 3 * rpc_mod.InternalClient._MAX_IDLE_PER_PEER * 2, \
                f"{dials} dials for {calls}+ RPCs — pool not reusing"
        finally:
            rpc_mod.asyncio.open_connection = real_open
            await stop_nodes(nodes)

    asyncio.run(run())


def test_http_api_roundtrip(tmp_path, rng):
    """Full external-surface parity pass over real HTTP: /status /files
    /upload /download /metrics /manifest + DELETE (reference routes
    StorageNode.java:71-89)."""
    data = rng.integers(0, 256, size=20_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        c1 = NodeClient(port=cluster.peer(1).port)
        c2 = NodeClient(port=cluster.peer(2).port)
        try:
            assert await asyncio.to_thread(c1.status) == "OK"
            info = await asyncio.to_thread(
                c1.upload, data, "hello file.bin")  # space → URL-encoding path
            assert info["fileId"]
            files = await asyncio.to_thread(c2.list_files)
            assert [f.name for f in files] == ["hello file.bin"]
            got = await asyncio.to_thread(c2.download, info["fileId"])
            assert got == data
            man = await asyncio.to_thread(c2.manifest, info["fileId"])
            assert man["fileId"] == info["fileId"]
            metrics = await asyncio.to_thread(c1.metrics)
            assert metrics["uploads"] == 1
            # unknown file → 404 (reference :408-411)
            try:
                await asyncio.to_thread(c1.download, "0" * 64)
                raise AssertionError("expected 404")
            except RuntimeError as e:
                assert "404" in str(e)
            assert "Deleted" == await asyncio.to_thread(c1.delete, info["fileId"])
            assert await asyncio.to_thread(c1.list_files) == []
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_cluster_with_anchored_device_pipeline(tmp_path, rng):
    """Upload through the anchored DEVICE pipeline (the flagship the
    'auto' default picks on TPU hosts; here it runs on the CPU backend
    with tiny lanes) inside a real cluster: region walk, placement,
    replication, cross-node download — byte identical, and the manifest
    matches what the CPU oracle fragmenter produces for the same bytes."""
    from dfs_tpu.fragmenter.cdc_anchored import (AnchoredCpuFragmenter,
                                                 AnchoredTpuFragmenter)
    from dfs_tpu.ops.cdc_anchored import AnchoredCdcParams
    from dfs_tpu.ops.cdc_v2 import AlignedCdcParams

    small = AnchoredCdcParams(
        chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                               strip_blocks=64),
        seg_min=2048, seg_max=4096, seg_mask=2047, strong_bits=1)
    data = rng.integers(0, 256, size=150_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        try:
            nodes[1].fragmenter = AnchoredTpuFragmenter(
                small, region_bytes=16384, lane_multiple=8)
            manifest, _ = await nodes[1].upload(data, "device.bin")
            _, got = await nodes[2].download(manifest.file_id)
            assert got == data
            cpu = AnchoredCpuFragmenter(small).chunk(data)
            assert [(c.offset, c.length, c.digest)
                    for c in manifest.chunks] == \
                [(c.offset, c.length, c.digest) for c in cpu]
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_manifest_antientropy_adopts_missed_creates(tmp_path, rng):
    """A node that slept through an upload's announce adopts the manifest
    on its next repair (the reference leaves it silently ignorant
    forever, SURVEY §3.4) AND restores its own canonical chunks."""
    data = rng.integers(0, 256, size=50_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path, ids={1, 2},
                                  retries=1, connect_timeout_s=0.3)
        try:
            manifest, _ = await nodes[1].upload(data, "missed.bin")
            nodes.update(await start_nodes(cluster, tmp_path, ids={3},
                                           retries=1, connect_timeout_s=0.3))
            assert nodes[3].store.manifests.load(manifest.file_id) is None
            await nodes[3].repair_once()
            assert nodes[3].store.manifests.load(manifest.file_id) \
                is not None
            # canonical chunks of the adopted file now live on node 3 too
            from dfs_tpu.node.placement import replica_set
            ids = cluster.sorted_ids()
            for c in manifest.chunks:
                if 3 in replica_set(c.digest, ids, 2):
                    assert nodes[3].store.chunks.has(c.digest)
            _, got = await nodes[3].download(manifest.file_id)
            assert got == data
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_delete_survives_node_downtime(tmp_path, rng):
    """Delete while one node is down; when it returns, anti-entropy (run
    before re-replication in repair_once) applies the tombstone: the file
    stays deleted cluster-wide, its chunks get GC'd everywhere, a late
    announce cannot resurrect it (VERDICT r1 weak §8)."""
    data = rng.integers(0, 256, size=60_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path,
                                  retries=1, connect_timeout_s=0.3)
        try:
            manifest, _ = await nodes[1].upload(data, "doomed.bin")
            fid = manifest.file_id
            # node 3 sleeps through the delete (its disk state persists)
            await nodes.pop(3).stop()
            assert await nodes[1].delete(fid)
            for i in (1, 2):
                assert nodes[i].store.manifests.load(fid) is None
                assert nodes[i].store.manifests.is_tombstoned(fid)

            # node 3 returns with the stale manifest + chunks on disk
            nodes.update(await start_nodes(cluster, tmp_path, ids={3},
                                           retries=1, connect_timeout_s=0.3))
            assert nodes[3].store.manifests.load(fid) is not None

            # its own repair applies the tombstone BEFORE re-replicating
            await nodes[3].repair_once()
            assert nodes[3].store.manifests.load(fid) is None
            assert nodes[3].store.manifests.is_tombstoned(fid)
            for n in nodes.values():
                for c in manifest.chunks:
                    assert not n.store.chunks.has(c.digest), \
                        f"chunk {c.digest[:8]} survived on node"

            # a late announce of the stale manifest must be refused
            await nodes[3].client.announce(cluster.peer(1),
                                           manifest.to_json())
            assert nodes[1].store.manifests.load(fid) is None
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


@pytest.mark.parametrize("retired",
                         ["cdc-tpu", "cdc-aligned", "cdc-aligned-tpu"])
def test_manifest_of_a_retired_kind_still_reads(tmp_path, rng, retired):
    """Stores in the wild hold manifests whose ``fragmenter`` field names
    a kind this tree no longer builds (retired at PR 46). The field is a
    record, never a dispatch: such a file is listed, adopted over the
    wire by a node that missed it, downloaded byte-identical, walked by
    the repair cycle (which restores a lost chunk of it) and deleted."""
    import json

    data = rng.integers(0, 256, size=60_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        manifest, _ = await nodes[1].upload(data, "old.bin")
        fid = manifest.file_id
        paths = {i: n.store.manifests._path(fid) for i, n in nodes.items()}
        await stop_nodes(nodes)
        # what such a store holds on disk: the same table under the
        # retired name — on two nodes; the third never got the announce
        for i in (1, 2):
            d = json.loads(paths[i].read_text())
            assert d["fragmenter"] == "cdc"
            d["fragmenter"] = retired
            paths[i].write_text(json.dumps(d))
        paths[3].unlink()

        nodes = await start_nodes(cluster, tmp_path)
        try:
            assert [(f["fileId"], f["name"])
                    for f in nodes[1].list_files()] == [(fid, "old.bin")]
            await nodes[3].repair_once()         # adopts it from a peer
            adopted = nodes[3].store.manifests.load(fid)
            assert adopted is not None and adopted.fragmenter == retired
            m, got = await nodes[3].download(fid)
            assert bytes(got) == data and m.fragmenter == retired
            # the repair cycle walks it: a chunk lost behind node 2's
            # back comes back
            lost = next(c.digest for c in manifest.chunks
                        if nodes[2].store.chunks.has(c.digest))
            nodes[2].store.chunks.delete(lost)
            assert await nodes[1].repair_once() \
                + await nodes[2].repair_once() >= 1
            assert nodes[2].store.chunks.has(lost)
            assert await nodes[2].delete(fid)
            for n in nodes.values():
                assert n.store.manifests.load(fid) is None
                assert n.list_files() == []
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_streaming_upload_matches_regular(tmp_path, rng):
    """Chunked-transfer upload must produce the same file id and chunk
    table as a whole-body upload of identical bytes, be visible
    cluster-wide, and round-trip byte-identical — with the body flowing
    through the bounded-memory pipeline (multiple placement flushes are
    exercised separately; here parity is the contract)."""
    data = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        c1 = NodeClient(port=cluster.peer(1).port)
        c2 = NodeClient(port=cluster.peer(2).port)
        try:
            blocks = [data[i:i + 7000] for i in range(0, len(data), 7000)]
            info = await asyncio.to_thread(
                c1.upload_stream, blocks, "streamed.bin")
            assert info["bytes"] == len(data)
            # same content uploaded whole elsewhere -> same fileId
            info2 = await asyncio.to_thread(c2.upload, data, "streamed.bin")
            assert info2["fileId"] == info["fileId"]
            assert info2["chunks"] == info["chunks"]
            got = await asyncio.to_thread(c2.download, info["fileId"])
            assert got == data
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_streaming_upload_multiflush(tmp_path, rng):
    """A stream larger than the placement flush threshold places chunks
    in multiple batches mid-stream; quorum stats aggregate across
    batches and the result round-trips."""
    data = rng.integers(0, 256, size=300_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        try:
            nodes[1].ingest.flush_bytes = 50_000   # force several flushes

            async def blocks():
                for i in range(0, len(data), 9000):
                    yield data[i:i + 9000]

            manifest, stats = await nodes[1].upload_stream(
                blocks(), "big-stream.bin")
            assert stats["bytes"] == len(data)
            assert stats["minCopies"] >= 2
            _, got = await nodes[2].download(manifest.file_id)
            assert got == data
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_range_download(tmp_path, rng):
    """HTTP Range requests: chunk-granular partial reads, byte-exact at
    arbitrary unaligned offsets; suffix and open ranges; 416 past EOF.
    The reference can only assemble whole files."""
    data = rng.integers(0, 256, size=50_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        c1 = NodeClient(port=cluster.peer(1).port)
        try:
            info = await asyncio.to_thread(
                c1.upload, data, "ranged.bin")
            fid = info["fileId"]
            for start, end in ((0, 10), (1234, 9999), (49_990, 50_000),
                               (0, 50_000)):
                got = await asyncio.to_thread(
                    c1.download_range, fid, start, end)
                assert got == data[start:end], f"range {start}:{end}"
            # suffix + open-ended via raw header forms
            got = await asyncio.to_thread(
                c1._request, "GET", f"/download?fileId={fid}", None,
                {"Range": "bytes=-100"})
            assert got == data[-100:]
            got = await asyncio.to_thread(
                c1._request, "GET", f"/download?fileId={fid}", None,
                {"Range": "bytes=45000-"})
            assert got == data[45000:]
            # past EOF -> 416
            try:
                await asyncio.to_thread(
                    c1._request, "GET", f"/download?fileId={fid}", None,
                    {"Range": "bytes=99999-100000"})
                raise AssertionError("expected 416")
            except RuntimeError as e:
                assert "416" in str(e)
            # first > last is syntactically INVALID per RFC 9110 §14.1.1:
            # the header must be ignored (full 200 body), not answered 416
            got = await asyncio.to_thread(
                c1._request, "GET", f"/download?fileId={fid}", None,
                {"Range": "bytes=5-2"})
            assert got == data
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_range_read_heals_corrupt_local_chunk(tmp_path, rng):
    """A range read hitting a rotten LOCAL chunk must evict it, re-fetch
    from a healthy replica, and serve correct bytes — not 500 until an
    operator scrubs."""
    data = rng.integers(0, 256, size=50_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        try:
            manifest, _ = await nodes[1].upload(data, "heal.bin")
            c0 = manifest.chunks[0]
            holder = next(n for n in nodes.values()
                          if n.store.chunks.has(c0.digest))
            p = holder.store.chunks._path(c0.digest)
            raw = bytearray(p.read_bytes())
            raw[0] ^= 0xFF
            p.write_bytes(bytes(raw))

            _, parts, start, end = await holder.download_range(
                manifest.file_id, c0.offset, c0.offset + c0.length - 1)
            got = b"".join(parts)   # r10: ranges come back as buffer lists
            assert got == data[c0.offset:c0.offset + c0.length]
            assert c0.digest in holder.under_replicated
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_scrub_detects_and_repair_restores(tmp_path, rng):
    """Bit rot on one replica: scrub re-hashes local chunks, evicts the
    corrupt one, repair restores it from the healthy replica, and the
    node serves correct bytes again — proactive integrity the reference
    only checks at read time."""
    data = rng.integers(0, 256, size=60_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        try:
            manifest, _ = await nodes[1].upload(data, "rotting.bin")
            victim = manifest.chunks[0].digest
            holder = next(n for n in nodes.values()
                          if n.store.chunks.has(victim))
            p = holder.store.chunks._path(victim)
            raw = bytearray(p.read_bytes())
            raw[0] ^= 0xFF
            p.write_bytes(bytes(raw))

            res = await holder.scrub_once()
            assert res["corrupt"] == 1
            assert not holder.store.chunks.has(victim)
            assert victim in holder.under_replicated

            await holder.repair_once()      # restores own canonical copy
            assert holder.store.chunks.has(victim)
            from dfs_tpu.utils.hashing import sha256_hex
            assert sha256_hex(holder.store.chunks.get(victim)) == victim
            _, got = await holder.download(manifest.file_id)
            assert got == data
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_reupload_after_delete_resurrects(tmp_path, rng):
    """file_id is content-derived, so a fresh upload of deleted content
    must clear tombstones cluster-wide and be downloadable again — not
    silently succeed while every announce bounces off the tombstone."""
    data = rng.integers(0, 256, size=40_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path,
                                  retries=1, connect_timeout_s=0.3)
        try:
            m1, _ = await nodes[1].upload(data, "phoenix.bin")
            assert await nodes[1].delete(m1.file_id)
            for n in nodes.values():
                assert n.store.manifests.is_tombstoned(m1.file_id)
            m2, _ = await nodes[2].upload(data, "phoenix.bin")
            assert m2.file_id == m1.file_id
            for n in nodes.values():
                assert not n.store.manifests.is_tombstoned(m2.file_id)
                assert n.store.manifests.load(m2.file_id) is not None
            _, got = await nodes[3].download(m2.file_id)
            assert got == data
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_stale_tombstone_does_not_destroy_reupload(tmp_path, rng):
    """LWW ordering: node 3 sleeps through delete + re-upload of the same
    content, returns holding only the (older) tombstone. Anti-entropy must
    NOT apply it over the newer live manifest anywhere — instead the stale
    peer gets the manifest re-announced (fresh) and converges to alive."""
    data = rng.integers(0, 256, size=40_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path,
                                  retries=1, connect_timeout_s=0.3)
        try:
            m1, _ = await nodes[1].upload(data, "lww.bin")
            fid = m1.file_id
            assert await nodes[1].delete(fid)       # all 3 tombstoned
            await nodes.pop(3).stop()               # sleeps through re-up
            await asyncio.sleep(0.05)               # mtime strictly newer
            m2, _ = await nodes[1].upload(data, "lww.bin")
            assert m2.file_id == fid

            nodes.update(await start_nodes(cluster, tmp_path, ids={3},
                                           retries=1, connect_timeout_s=0.3))
            assert nodes[3].store.manifests.is_tombstoned(fid)
            # any survivor's repair sees node 3's stale tombstone: must
            # keep its live manifest and resurrect node 3 instead
            await nodes[1].repair_once()
            assert nodes[1].store.manifests.load(fid) is not None
            assert not nodes[1].store.manifests.is_tombstoned(fid)
            assert nodes[3].store.manifests.load(fid) is not None
            assert not nodes[3].store.manifests.is_tombstoned(fid)
            _, got = await nodes[2].download(fid)
            assert got == data
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_tombstone_ts_none_skipped_by_antientropy(tmp_path, rng):
    """A tombs entry arriving with ts=None (the peer's .tomb vanished
    between its glob and ts read — the concurrent fresh-re-upload race)
    must be SKIPPED. Applying it would stamp a fresh local timestamp that
    postdates the re-uploaded manifest and propagate deletion of an
    acknowledged upload cluster-wide."""
    data = rng.integers(0, 256, size=30_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(2)
        nodes = await start_nodes(cluster, tmp_path,
                                  retries=1, connect_timeout_s=0.3)
        try:
            m, _ = await nodes[1].upload(data, "race.bin")
            fid = m.file_id

            real_call = nodes[1].client.call

            async def evil_call(peer, header, body=b"", retries=None):
                if header.get("op") == "tombstones":
                    return {"ok": True,
                            "tombs": [{"id": fid, "ts": None}]}, b""
                return await real_call(peer, header, body, retries)

            nodes[1].client.call = evil_call
            await nodes[1]._tombstone_antientropy()
            assert nodes[1].store.manifests.load(fid) is not None
            assert not nodes[1].store.manifests.is_tombstoned(fid)
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_tombstones_rpc_drops_vanished_entries(tmp_path, rng):
    """Server side of the same race: the tombstones op must not advertise
    an id whose tombstone_ts reads back None."""

    async def run():
        cluster = make_cluster_cfg(1, rf=1)
        nodes = await start_nodes(cluster, tmp_path)
        try:
            ms = nodes[1].store.manifests
            ms.delete("a" * 64)              # real tombstone
            assert ("a" * 64) in ms.tombstones()
            real_ts = ms.tombstone_ts
            ms.tombstone_ts = lambda fid: None   # simulate vanished .tomb
            try:
                resp, _ = await nodes[1]._dispatch({"op": "tombstones"}, b"")
            finally:
                ms.tombstone_ts = real_ts
            assert resp["tombs"] == []
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_download_tombstoned_rejected_despite_stale_peer(tmp_path, rng):
    """A node that knows the file is deleted must 404 even while a stale
    peer still has the manifest + chunks (no resurrection via the
    peer-manifest download fallback)."""
    data = rng.integers(0, 256, size=40_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path,
                                  retries=1, connect_timeout_s=0.3)
        try:
            m, _ = await nodes[1].upload(data, "ghost.bin")
            await nodes.pop(3).stop()               # sleeps through delete
            assert await nodes[1].delete(m.file_id)
            nodes.update(await start_nodes(cluster, tmp_path, ids={3},
                                           retries=1, connect_timeout_s=0.3))
            # node 3 still has manifest + chunks; node 1 must still 404
            assert nodes[3].store.manifests.load(m.file_id) is not None
            with pytest.raises(NotFoundError):
                await nodes[1].download(m.file_id)
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_streaming_download_batched_and_exact(tmp_path, rng):
    """HTTP downloads stream: with a tiny fetch-batch bound the node
    gathers many batches (never the whole file at once), the raw HTTP
    body is byte-exact with the advertised Content-Length, and cross-node
    chunks still verify. Local heal-on-read stays wired in."""
    data = rng.integers(0, 256, size=300_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        c2 = NodeClient(port=cluster.peer(2).port)
        try:
            m, _ = await nodes[1].upload(data, "streamed.bin")
            nodes[2]._FETCH_BATCH_BYTES = 32 * 1024
            gathers = 0
            orig = nodes[2]._fetch_verified

            async def spy(manifest, chunks):
                nonlocal gathers
                gathers += 1
                return await orig(manifest, chunks)

            nodes[2]._fetch_verified = spy
            got = await asyncio.to_thread(c2.download, m.file_id)
            assert got == data
            assert gathers > 3, "download did not gather in batches"
            assert nodes[2].counters.snapshot()["downloads"] == 1
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_streaming_download_truncates_on_corrupt_assembly(tmp_path, rng):
    """If the whole-file gate fails mid-stream (stale manifest pointing at
    valid-by-digest chunks of OTHER content), the body must be truncated
    before its final byte — the client can detect it; it never receives a
    complete-but-wrong file."""
    data = rng.integers(0, 256, size=60_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(2)
        nodes = await start_nodes(cluster, tmp_path)
        c1 = NodeClient(port=cluster.peer(1).port)
        try:
            m, _ = await nodes[1].upload(data, "gate.bin")
            # forge a manifest with the RIGHT chunk digests but a fileId
            # of different content: per-chunk checks pass, the whole-file
            # gate must not
            from dataclasses import replace
            forged = replace(m, file_id="f" * 64)
            nodes[1].store.manifests.save(forged)
            with pytest.raises(Exception) as ei:
                await asyncio.to_thread(c1.download, "f" * 64)
            # urllib surfaces the held-back final chunk as IncompleteRead
            assert ("IncompleteRead" in repr(ei.value)
                    or isinstance(ei.value, ConnectionError)), ei.value
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_resumable_upload_transfers_only_missing(tmp_path, rng):
    """SURVEY §5.4: an interrupted upload leaves placed-but-unreferenced
    chunks; a resume re-POST must move only the missing payloads. Flow:
    GET /chunking -> local chunk -> POST /missing -> POST /upload_resume.
    Asserts clientBytesSent << size, byte-identical download, and that a
    fresh-content resume still round-trips (degenerate case: all chunks
    missing)."""
    data = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()

    async def run():
        from dfs_tpu.config import CDCParams

        cluster = make_cluster_cfg(3)
        # realistic-ratio chunk sizes: at the suite's tiny 256 B chunks
        # the resume TABLE itself (~100 B/chunk of JSON) would dominate
        # clientBytesSent and mask what the assertion measures
        nodes = await start_nodes(cluster, tmp_path, cdc=CDCParams(
            min_size=2048, avg_size=4096, max_size=16384))
        c1 = NodeClient(port=cluster.peer(1).port)
        try:
            # simulate the interruption: ~80% of chunks were placed
            # before the client died — no manifest was committed
            refs = nodes[1].fragmenter.chunk(data)
            placed = refs[:len(refs) * 4 // 5]
            from dfs_tpu.node.placement import new_upload_stats

            await nodes[1].placement.place(
                "", [(c.digest, data[c.offset:c.offset + c.length])
                     for c in placed], new_upload_stats())
            assert nodes[1].list_files() == []   # nothing committed

            info = await asyncio.to_thread(c1.upload_resume, data, "r.bin")
            assert info["clientBytesSent"] < len(data) // 2, \
                f"resume sent {info['clientBytesSent']} of {len(data)}"
            assert info["size"] == len(data)
            _, got = await nodes[2].download(info["fileId"])
            assert got == data

            # degenerate: brand-new content — resume degrades to sending
            # everything (plus the table), still correct
            fresh = rng.integers(0, 256, size=50_000,
                                 dtype=np.uint8).tobytes()
            info2 = await asyncio.to_thread(c1.upload_resume, fresh, "f.bin")
            _, got2 = await nodes[3].download(info2["fileId"])
            assert got2 == fresh
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_corrupt_chunk_detected(tmp_path, rng):
    """Flip bytes in a stored chunk on every replica → download must fail
    with integrity error, not return corrupt data (whole-file gate is the
    reference's check at StorageNode.java:453-458; ours also catches it at
    chunk granularity on remote fetch)."""
    data = rng.integers(0, 256, size=40_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path)
        try:
            manifest, _ = await nodes[1].upload(data, "victim.bin")
            victim = manifest.chunks[0].digest
            for n in nodes.values():
                p = n.store.chunks._path(victim)
                if p.is_file():
                    raw = bytearray(p.read_bytes())
                    raw[0] ^= 0xFF
                    p.write_bytes(bytes(raw))
            with pytest.raises((DownloadError, NotFoundError)):
                await nodes[2].download(manifest.file_id)
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_membership_growth_rebalances(tmp_path, rng):
    """Grow a 4-node cluster to 5: mod-N placement remaps most chunks,
    so (a) reads must stay correct THROUGHOUT via the cluster-wide
    holder fallback (the new replica set may hold nothing yet), and
    (b) repair must converge placement — every chunk lands on its NEW
    replica set. The reference is frozen at N=5 (StorageNode.java:15);
    rebalance cost of mod-N vs a ring is documented in README."""
    data1 = rng.integers(0, 256, size=60_000, dtype=np.uint8).tobytes()
    data2 = rng.integers(0, 256, size=45_000, dtype=np.uint8).tobytes()

    async def run():
        from dfs_tpu.node.placement import replica_set

        cluster4 = make_cluster_cfg(4)
        nodes = await start_nodes(cluster4, tmp_path,
                                  retries=1, connect_timeout_s=0.3)
        m1, _ = await nodes[1].upload(data1, "a.bin")
        m2, _ = await nodes[2].upload(data2, "b.bin")
        await stop_nodes(nodes)

        # same peers 1-4 (same ports, same data roots) + a new node 5
        new_ports = _free_ports(2)
        cluster5 = ClusterConfig(
            peers=cluster4.peers + (PeerAddr(
                node_id=5, host="127.0.0.1", port=new_ports[0],
                internal_port=new_ports[1]),),
            replication_factor=cluster4.replication_factor)
        nodes = await start_nodes(cluster5, tmp_path,
                                  retries=1, connect_timeout_s=0.3)
        try:
            # reads correct IMMEDIATELY — including from the empty new
            # node, whose remapped replica sets mostly miss
            _, got = await nodes[5].download(m1.file_id)
            assert got == data1
            _, got = await nodes[3].download(m2.file_id)
            assert got == data2

            # repair converges canonical placement for the new topology
            for n in nodes.values():
                await n.repair_once()
            ids = cluster5.sorted_ids()
            rf = cluster5.replication_factor
            for m in (nodes[1].store.manifests.load(m1.file_id),
                      nodes[1].store.manifests.load(m2.file_id)):
                for c in m.chunks:
                    for t in replica_set(c.digest, ids, rf):
                        assert nodes[t].store.chunks.has(c.digest), \
                            f"{c.digest[:8]} not yet on node {t}"

            # and reads still byte-identical after the rebalance
            _, got = await nodes[5].download(m1.file_id)
            assert got == data1
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_manifest_fallback_from_peers(tmp_path, rng):
    """A node that never saw the announce can still serve the download by
    pulling the manifest from peers (fixes reference silent-loss, §5.3)."""
    data = rng.integers(0, 256, size=25_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(4)
        nodes = await start_nodes(cluster, tmp_path, ids={1, 2, 3})
        try:
            manifest, _ = await nodes[1].upload(data, "late.bin")
            # node 4 was down for the announce; bring it up now
            nodes.update(await start_nodes(cluster, tmp_path, ids={4}))
            assert nodes[4].store.manifests.load(manifest.file_id) is None
            _, got = await nodes[4].download(manifest.file_id)
            assert got == data
            # and it cached the manifest for next time
            assert nodes[4].store.manifests.load(manifest.file_id) is not None
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_plain_content_length_upload_is_bounded_memory(tmp_path, rng):
    """A large NON-chunked POST (the most common client shape) must ride
    the same bounded-memory ingest as chunked-transfer clients instead
    of materializing the body in node RAM (the reference reads the whole
    body into one array, StorageNode.java:124; this path survived here
    until round 4). Asserted two ways: the whole-body upload() entry is
    never called, and the tracked allocation peak during ingest stays
    far below the body size."""
    import tracemalloc

    from dfs_tpu.cli.client import NodeClient
    from dfs_tpu.node.runtime import StorageNodeServer

    # low-entropy but chunkable payload, built without a 2x temp
    block = rng.integers(0, 256, size=4 * 1024 * 1024,
                         dtype=np.uint8).tobytes()
    body_blocks = 48                        # 192 MiB > STREAM_BODY_BYTES
    total = body_blocks * len(block)

    async def run():
        cluster = make_cluster_cfg(1, rf=1)
        # production chunk sizing: the suite-wide tiny CDC params would
        # make ~2M chunks of ~100 B here, and the CHUNK METADATA (refs,
        # digests, manifest JSON) would dwarf any payload buffering the
        # test is trying to observe
        nodes = await start_nodes(
            cluster, tmp_path,
            cdc=CDCParams(min_size=2048, avg_size=8192, max_size=65536))
        whole_body_calls = []
        orig_upload = StorageNodeServer.upload

        async def spy_upload(self, data, name, **kw):
            whole_body_calls.append(len(data))
            return await orig_upload(self, data, name, **kw)

        StorageNodeServer.upload = spy_upload
        try:
            # raw socket client: send the SAME 4 MiB block repeatedly so
            # the client side of this single process allocates nothing
            # body-sized — every big allocation tracemalloc sees below
            # is the server's
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", cluster.peer(1).port)
            head = (f"POST /upload?name=big.bin HTTP/1.1\r\n"
                    f"Host: x\r\nContent-Length: {total}\r\n"
                    f"\r\n").encode()
            tracemalloc.start()
            tracemalloc.reset_peak()
            writer.write(head)
            for _ in range(body_blocks):
                writer.write(block)
                await writer.drain()
            status = await reader.readline()
            while (await reader.readline()).strip():
                pass                     # drain response headers
            _, peak = tracemalloc.get_traced_memory()
            tracemalloc.stop()
            writer.close()
            assert b"201" in status, status
            # server-side: bounded — peak tracked allocations must stay
            # ~one flush batch, nowhere near the 192 MiB body
            assert peak < total // 3, f"ingest peaked at {peak} bytes"
            assert not whole_body_calls, \
                "plain upload must not take the whole-body path"
            client = NodeClient(port=cluster.peer(1).port,
                                timeout_s=600.0)
            import hashlib
            h = hashlib.sha256()
            for _ in range(body_blocks):     # incremental: the test's
                h.update(block)              # own footprint stays small
            got = await asyncio.to_thread(client.download, h.hexdigest())
            assert len(got) == total
            view = memoryview(got)
            for i in range(body_blocks):
                assert view[i * len(block):(i + 1) * len(block)] == block
            del view, got
        finally:
            StorageNodeServer.upload = orig_upload
            await stop_nodes(nodes)

    asyncio.run(run())
