"""gRPC sidecar: chunk+hash service over a real local channel, and its
results must be identical to calling the fragmenter in-process."""

import numpy as np
import pytest

grpc = pytest.importorskip("grpc")

from dfs_tpu.config import CDCParams  # noqa: E402
from dfs_tpu.fragmenter.cdc_cpu import CpuCdcFragmenter  # noqa: E402
from dfs_tpu.sidecar.service import SidecarClient, SidecarServer  # noqa: E402

CDC = CDCParams(min_size=64, avg_size=256, max_size=1024)


@pytest.fixture(scope="module")
def sidecar():
    srv = SidecarServer(port=0, fragmenter="cdc", cdc_params=CDC)
    srv.start()
    client = SidecarClient(srv.port)
    yield client
    client.close()
    srv.stop()


def test_health(sidecar):
    h = sidecar.health()
    assert h["ok"] and h["fragmenter"] == "cdc" and h["window"] == 0
    assert h["describe"]["kind"] == "cdc"
    assert h["device"] is None          # host engine: no backend to name


def test_health_names_the_device_and_counts_regions(rng):
    """The chip owner's Health says what the engine computes on, as JAX
    reports it, and how many regions went there since start — what
    chip_smoke.py cannot see from outside."""
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter

    srv = SidecarServer(port=0, fragmenter="cdc-anchored-tpu")
    srv.start()
    client = SidecarClient(srv.port)
    try:
        dev = client.health()["device"]
        assert {k: dev[k] for k in ("platform", "device_kind", "regions",
                                    "overflow_redos", "streams",
                                    "segments", "strong_cuts")} \
            == {"platform": "cpu", "device_kind": "cpu", "regions": 0,
                "overflow_redos": 0, "streams": 0, "segments": 0,
                "strong_cuts": 0}
        # more than a packed region holds: a window of its own, the
        # shape tests/test_span_totals.py compiles too
        data = rng.integers(0, 256, size=4 * 2**20 + 17,
                            dtype=np.uint8).tobytes()
        resp = client.chunk_hash_stream([data])
        assert [c["digest"] for c in resp["chunks"]] \
            == [c.digest for c in AnchoredCpuFragmenter().chunk(data)]
        dev = client.health()["device"]
        assert dev["regions"] == 1 and dev["overflow_redos"] == 0
        # how the region's ~50 segments came to end: most at a strong
        # anchor, the rest at the last anchor of the window, one with
        # the stream
        assert dev["segments"] == dev["strong_cuts"] + dev["window_cuts"] \
            + dev["forced_cuts"] + 1
        assert 0.6 * dev["segments"] <= dev["strong_cuts"] \
            <= 0.95 * dev["segments"]
    finally:
        client.close()
        srv.stop()


def test_chunk_hash_matches_inprocess(sidecar, rng):
    data = rng.integers(0, 256, size=30_000, dtype=np.uint8).tobytes()
    resp = sidecar.chunk_hash(data)
    want = CpuCdcFragmenter(CDC).chunk(data)
    assert resp["size"] == len(data)
    assert [(c["offset"], c["length"], c["digest"]) for c in resp["chunks"]] \
        == [(c.offset, c.length, c.digest) for c in want]


def test_empty_payload(sidecar):
    resp = sidecar.chunk_hash(b"")
    assert resp["chunks"] == [] and resp["size"] == 0


def test_stream_matches_unary_any_blocking(sidecar, rng):
    """Client-streaming ChunkHashStream must produce the same table as the
    unary path for every blocking — the production path for payloads past
    the 1 GiB unary message cap (scaled here)."""
    data = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()
    want = sidecar.chunk_hash(data)
    for bs in (1000, 8192, 65536):
        blocks = [data[i:i + bs] for i in range(0, len(data), bs)]
        got = sidecar.chunk_hash_stream(blocks)
        assert got["chunks"] == want["chunks"]
        assert got["size"] == len(data)


def test_stream_generator_is_consumed_lazily(sidecar, rng):
    """The server must pull blocks from the request stream incrementally
    (bounded memory — the multi-GiB shape, scaled): the generator yields
    many blocks and is fully drained exactly once."""
    data = rng.integers(0, 256, size=120_000, dtype=np.uint8).tobytes()
    pulled = []

    def gen():
        for i in range(0, len(data), 4096):
            pulled.append(i)
            yield data[i:i + 4096]

    resp = sidecar.chunk_hash_stream(gen())
    assert len(pulled) == -(-len(data) // 4096)
    assert sum(c["length"] for c in resp["chunks"]) == len(data)


def test_sidecar_fragmenter_adapter(sidecar, rng):
    """SidecarFragmenter is a drop-in Fragmenter: chunk() and manifest()
    delegate over the channel and match the in-process fragmenter."""
    from dfs_tpu.sidecar.service import SidecarFragmenter

    frag = SidecarFragmenter(_port(sidecar))
    data = rng.integers(0, 256, size=50_000, dtype=np.uint8).tobytes()
    want = CpuCdcFragmenter(CDC).chunk(data)
    got = frag.chunk(data)
    assert [(c.offset, c.length, c.digest) for c in got] \
        == [(c.offset, c.length, c.digest) for c in want]
    m = frag.manifest(data, name="f", file_id="ab" * 32)
    assert m.file_id == "ab" * 32 and m.size == len(data)
    assert frag.name == "sidecar:cdc"
    frag.close()


def _port(client: SidecarClient) -> int:
    return int(client._channel._channel.target().decode().rsplit(":", 1)[-1])


def _anchored_sidecar(region_bytes=16384):
    """Sidecar whose fragmenter streams incrementally (anchored CPU walk,
    tiny windows so a small payload spans many of them)."""
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter
    from dfs_tpu.ops.cdc_anchored import AnchoredCdcParams
    from dfs_tpu.ops.cdc_v2 import AlignedCdcParams

    small = AnchoredCdcParams(
        chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                               strip_blocks=64),
        seg_min=2048, seg_max=4096, seg_mask=2047, strong_bits=1)
    srv = SidecarServer(port=0, fragmenter="fixed")   # placeholder
    srv.fragmenter = AnchoredCpuFragmenter(small, region_bytes=region_bytes)
    srv.start()
    return srv


def test_duplex_matches_stream_unary(rng):
    """ChunkHashDuplex must emit the same chunks as the stream-unary
    table, split across MANY incremental batches (one per walk window),
    with the summary message last."""
    srv = _anchored_sidecar()
    client = SidecarClient(srv.port)
    try:
        data = rng.integers(0, 256, size=150_000, dtype=np.uint8).tobytes()
        want = client.chunk_hash_stream(
            data[i:i + 7000] for i in range(0, len(data), 7000))
        msgs = list(client.chunk_hash_duplex(
            data[i:i + 7000] for i in range(0, len(data), 7000)))
        assert msgs[-1]["done"] and msgs[-1]["size"] == len(data)
        assert msgs[-1]["fileId"] == want["fileId"]
        got = [c for m in msgs[:-1] for c in m["chunks"]]
        assert got == want["chunks"]
        assert len(msgs) > 3, "duplex replies were not incremental"
    finally:
        client.close()
        srv.stop()


def test_sidecar_fragmenter_streaming_store_bounded(rng):
    """SidecarFragmenter.manifest_stream with a store callback must NOT
    materialize the body (the round-2 advisor finding): the tee buffer's
    high-water mark stays window-sized while every chunk payload reaches
    the store intact."""
    from dfs_tpu.sidecar.service import SidecarFragmenter

    srv = _anchored_sidecar()
    try:
        frag = SidecarFragmenter(srv.port)
        data = rng.integers(0, 256, size=2_000_000,
                            dtype=np.uint8).tobytes()
        stored: dict[str, bytes] = {}
        m = frag.manifest_stream(
            (data[i:i + 50_000] for i in range(0, len(data), 50_000)),
            name="big", store=stored.__setitem__)
        assert m.size == len(data)
        assert b"".join(stored[c.digest] for c in m.chunks) == data
        want = srv.fragmenter.chunk(data)
        assert [(c.offset, c.length, c.digest) for c in m.chunks] == \
            [(c.offset, c.length, c.digest) for c in want]
        # bound: windows are 16 KiB; allow generous transport slack but
        # nothing near the 2 MB body
        assert frag.last_peak_buffer < len(data) // 2, \
            f"teed buffer peaked at {frag.last_peak_buffer}"
        frag.close()
    finally:
        srv.stop()


def test_node_streaming_upload_through_sidecar_bounded(tmp_path, rng):
    """Chunked-transfer upload on a sidecar-delegating node: byte-exact
    round-trip AND bounded node-side buffering (upload_stream always
    passes store=on_chunk — the path that silently materialized before)."""
    import asyncio

    from dfs_tpu.config import ClusterConfig, NodeConfig, PeerAddr
    from dfs_tpu.node.runtime import StorageNodeServer

    srv = _anchored_sidecar()
    try:
        import socket

        def free_port():
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        cluster = ClusterConfig(
            peers=(PeerAddr(node_id=1, host="127.0.0.1", port=free_port(),
                            internal_port=free_port()),),
            replication_factor=1)
        cfg = NodeConfig(node_id=1, cluster=cluster, data_root=tmp_path,
                         sidecar_port=srv.port)
        data = rng.integers(0, 256, size=1_500_000,
                            dtype=np.uint8).tobytes()

        async def blocks():
            for i in range(0, len(data), 40_000):
                yield data[i:i + 40_000]

        async def run():
            node = StorageNodeServer(cfg)
            node.ingest.flush_bytes = 128 * 1024   # scale the flush down
            await node.start()
            try:
                manifest, stats = await node.upload_stream(blocks(), "s.bin")
                assert stats["bytes"] == len(data)
                _, got = await node.download(manifest.file_id)
                assert got == data
                assert node.fragmenter.last_peak_buffer < len(data) // 2, \
                    f"node tee peaked at {node.fragmenter.last_peak_buffer}"
            finally:
                await node.stop()

        asyncio.run(run())
    finally:
        srv.stop()


def test_node_delegates_to_sidecar(tmp_path, rng):
    """NodeConfig.sidecar_port routes the node's fragmentation through the
    sidecar process; upload/download round-trips byte-identical."""
    import asyncio

    from dfs_tpu.config import ClusterConfig, NodeConfig
    from dfs_tpu.node.runtime import StorageNodeServer

    srv = SidecarServer(port=0, fragmenter="cdc", cdc_params=CDC)
    srv.start()
    try:
        import socket

        def free_port():
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            p = s.getsockname()[1]
            s.close()
            return p

        from dfs_tpu.config import PeerAddr
        cluster = ClusterConfig(
            peers=(PeerAddr(node_id=1, host="127.0.0.1", port=free_port(),
                            internal_port=free_port()),),
            replication_factor=1)
        cfg = NodeConfig(node_id=1, cluster=cluster, data_root=tmp_path,
                         sidecar_port=srv.port)
        data = rng.integers(0, 256, size=40_000, dtype=np.uint8).tobytes()

        async def run():
            node = StorageNodeServer(cfg)
            assert node.fragmenter.name == "sidecar:cdc"
            await node.start()
            try:
                manifest, _ = await node.upload(data, "s.bin")
                _, got = await node.download(manifest.file_id)
                assert got == data
            finally:
                await node.stop()

        asyncio.run(run())
    finally:
        srv.stop()
