"""Streaming CDC: incremental chunking over block streams must produce
exactly the same manifests as one-shot chunking, with bounded state —
plus the node-level streaming-ingest contracts (windowed placement
equivalence, the abort path of a failed placement)."""

import asyncio
import time

import numpy as np
import pytest

from dfs_tpu.config import (CDCParams, ClusterConfig, IngestConfig,
                            NodeConfig)
from dfs_tpu.fragmenter.cdc_cpu import CpuCdcFragmenter
from dfs_tpu.fragmenter.fixed import FixedFragmenter
from dfs_tpu.fragmenter.stream import StreamChunker
from dfs_tpu.utils.hashing import sha256_hex

PARAMS = CDCParams(min_size=64, avg_size=256, max_size=1024)


def _blocks(data: bytes, sizes):
    out, off = [], 0
    i = 0
    while off < len(data):
        s = sizes[i % len(sizes)]
        out.append(data[off:off + s])
        off += s
        i += 1
    return out


def test_stream_chunker_matches_oneshot(rng):
    frag = CpuCdcFragmenter(PARAMS)
    data = rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes()
    for sizes in ([1000], [1], [4096, 33, 777], [100_000]):
        if sizes == [1]:  # 1-byte feeds are slow; shrink the input
            payload = data[:3000]
        else:
            payload = data
        chunker = StreamChunker(PARAMS, frag.bitmap_tile)
        spans = []
        for b in _blocks(payload, sizes):
            spans.extend(chunker.feed(b))
        spans.extend(chunker.finish())
        want = [(c.offset, payload[c.offset:c.offset + c.length])
                for c in frag.chunk(payload)]
        assert [(o, p) for o, p in spans] == want, f"sizes={sizes}"


def test_cpu_manifest_stream_matches(rng, tmp_path):
    frag = CpuCdcFragmenter(PARAMS)
    data = rng.integers(0, 256, size=80_000, dtype=np.uint8).tobytes()
    stored = {}
    m = frag.manifest_stream(_blocks(data, [7000, 123]), "s.bin",
                             store=lambda d, b: stored.__setitem__(d, b))
    assert m == frag.manifest(data, "s.bin")
    assert m.file_id == sha256_hex(data)
    rebuilt = b"".join(stored[c.digest] for c in m.chunks)
    assert rebuilt == data


def test_fixed_manifest_stream_fallback(rng):
    frag = FixedFragmenter(parts=5)
    data = rng.integers(0, 256, size=1_000, dtype=np.uint8).tobytes()
    m = frag.manifest_stream(_blocks(data, [100]), "f.bin")
    assert m == frag.manifest(data, "f.bin")


def test_bounded_state(rng):
    """Resident buffer must never exceed max_size + feed block."""
    frag = CpuCdcFragmenter(PARAMS)
    chunker = StreamChunker(PARAMS, frag.bitmap_tile)
    data = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()
    worst = 0
    for b in _blocks(data, [4096]):
        for _ in chunker.feed(b):
            pass
        worst = max(worst, len(chunker.buf))
    assert worst <= PARAMS.max_size + 4096


# ---------------------------------------------------------------------- #
# node-level streaming ingest (upload_stream): windowed placement
# equivalence and the placement-failure abort path. A 1-node cluster
# needs no listeners — upload_stream only touches the local store.
# ---------------------------------------------------------------------- #

def _stream_node(tmp_path, sub: str, window: int = 2,
                 flush: int = 64 * 1024):
    from dfs_tpu.node.runtime import StorageNodeServer

    cfg = NodeConfig(
        node_id=1, cluster=ClusterConfig.localhost(1, replication_factor=1),
        data_root=tmp_path / sub, fragmenter="cdc", cdc=PARAMS,
        health_probe_s=0, ingest=IngestConfig(window=window))
    node = StorageNodeServer(cfg)
    node.ingest.flush_bytes = flush   # several batches on small inputs
    return node


def test_upload_stream_windowed_matches_serial(tmp_path, rng):
    """window=3 must commit the same manifest, stats, and bytes as the
    strictly-serial window=1 schedule (pipelining is a schedule change,
    not a semantics change)."""
    data = rng.integers(0, 256, size=500_000, dtype=np.uint8).tobytes()

    async def upload(window: int):
        node = _stream_node(tmp_path, f"w{window}", window=window)

        async def blocks():
            for off in range(0, len(data), 10_000):
                yield data[off:off + 10_000]

        manifest, stats = await node.upload_stream(blocks(), "s.bin")
        _, gen = await node.download_stream(manifest.file_id)
        got = b"".join([p async for p in gen])
        return manifest, stats, got

    m1, s1, got1 = asyncio.run(upload(1))
    m3, s3, got3 = asyncio.run(upload(3))
    assert (m1.file_id, m1.size, m1.chunks) == (m3.file_id, m3.size,
                                                m3.chunks)
    assert got1 == got3 == data
    assert s1 == s3            # per-batch stats merged deterministically


@pytest.mark.parametrize("slow_half", [False, True],
                         ids=["store", "store-slow-in-half-its-dirs"])
def test_upload_stream_abort_stops_body_and_commits_nothing(
        tmp_path, rng, slow_half):
    """Placement failure mid-stream must abort: stop consuming the body
    (an endless client cannot be drained into memory), commit NO
    manifest, and leave the already-placed chunks as orphans that only
    the AGED GC reclaims (a young orphan may belong to an in-flight
    upload). ``slow_half``: the pool jobs of a batch end far apart, as
    on a loaded machine."""
    from dfs_tpu.node.runtime import StorageNodeServer, UploadError

    node = _stream_node(tmp_path, "abort", window=2, flush=32 * 1024)
    if slow_half:
        # a batch is cut by directory into up to four pool jobs: the
        # jobs of the upper directories link their names ~0.2 s after
        # the others have
        node.store.chunks.fault = lambda op, digest: \
            time.sleep(0.004) if digest[0] in "89abcdef" else None
    real_place = node.placement.place
    calls = {"n": 0}

    async def flaky_place(file_id, batch, stats, rf=None,
                          placement=None, ledger=None):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise UploadError("Replication failed: injected")
        await real_place(file_id, batch, stats, rf=rf,
                         placement=placement, ledger=ledger)

    node.placement.place = flaky_place
    consumed = {"blocks": 0}
    cap = 50_000                      # hard stop if the abort never fires

    async def endless_body():
        block = rng.integers(0, 256, size=16_384, dtype=np.uint8)
        for i in range(cap):
            consumed["blocks"] += 1
            # fresh content per block (vectorized xor) so CDC keeps
            # producing NEW chunks instead of deduping forever
            yield (block ^ (i & 0xFF)).tobytes()
            await asyncio.sleep(0)

    async def run():
        with pytest.raises(UploadError, match="injected"):
            await node.upload_stream(endless_body(), "doomed.bin")

    asyncio.run(run())
    assert consumed["blocks"] < cap        # reading STOPPED mid-body
    assert node.store.manifests.ids() == []   # no manifest committed
    # an aborted batch's pool jobs that a worker had begun cannot be
    # recalled mid-write — their orphan puts land moments after the
    # abort returns, a job at a time: wait until the pool says none is
    # left (names "equal twice, 50 ms apart" were seen between two jobs
    # of one batch), then snapshot
    deadline = time.monotonic() + 60.0
    while node.cas.pending and time.monotonic() < deadline:
        time.sleep(0.01)
    assert node.cas.pending == 0
    orphans = sorted(node.store.chunks.digests())
    assert orphans                         # batch 1 placed, then aborted
    # the aged sweep spares them (could be an in-flight upload's chunks)…
    assert node.store.gc(min_age_s=3600.0) == []
    assert sorted(node.store.chunks.digests()) == orphans
    # …and the explicit sweep reclaims them once aged (age 0 here)
    assert sorted(node.store.gc()) == orphans
    assert node.store.chunks.digests() == []
