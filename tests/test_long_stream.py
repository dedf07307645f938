"""PR 43: one LONG stream — an upload of many device windows — on the
normal path: ``upload_stream`` → ``_StreamUpload`` →
``SidecarFragmenter.chunks_stream`` → the owner's ``chunk_hash_duplex``
→ ``AnchoredTpuFragmenter.chunks_stream`` → replies → placement →
``_ack`` (docs/ingest.md "Long streams").

The deployment at 1/64: an in-process owner whose device engine is built
with a 1 MiB window (the constructor's ``region_bytes``; the deployed
chunk parameters), and three nodes whose ``credit_bytes`` is one
window's payload and ``flush_bytes`` half of it, as deployed (64 and
32 MiB) — so an 8 MiB stream is nine full windows and a tail, and the
four bounds (owner windows in flight, tee cap, credit, placement
window) meet as they do at 1 GiB.
"""

from __future__ import annotations

import asyncio
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from dfs_tpu.config import IngestConfig, NodeConfig
from dfs_tpu.fragmenter.cdc_anchored import (AnchoredCpuFragmenter,
                                             AnchoredTpuFragmenter)
from dfs_tpu.node.runtime import StorageNodeServer
from dfs_tpu.ops.cdc_anchored import (AnchoredCdcParams,
                                      chunk_file_anchored_np)
from dfs_tpu.sidecar.service import SidecarServer
from test_node_cluster import make_cluster_cfg, stop_nodes

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmarks"

REGION = 1 << 20
PARAMS = AnchoredCdcParams()
STRIDE = REGION - PARAMS.seg_max
FULL, TAIL = 9, 300 * 1024
TOTAL = FULL * STRIDE + TAIL        # nine full windows and a tail
BLOCK = 64 * 1024                   # the cell's 4 MiB block at 1/64
SPAN = 3 * REGION                   # the owner's stream_span
TEE_CAP = 2 * SPAN + 2 * BLOCK      # SidecarFragmenter's cap + its slack


@pytest.fixture(scope="module")
def owner():
    """The chip owner in this process, its engine the device chain with
    a 1 MiB window: the two shapes a long stream dispatches (the full
    window, the tail's bucket) compile once for the module."""
    srv = SidecarServer(port=0, fragmenter="fixed")     # placeholder
    srv.fragmenter = AnchoredTpuFragmenter(PARAMS, region_bytes=REGION)
    srv.fragmenter.obs = srv.obs
    srv.start()
    yield srv
    srv.stop()


def body_of(seed: int, size: int = TOTAL) -> bytes:
    return np.random.default_rng([43, seed]).integers(
        0, 256, size=size, dtype=np.uint8).tobytes()


async def blocks_of(data: bytes):
    for i in range(0, len(data), BLOCK):
        yield data[i:i + BLOCK]


def rows(chunks) -> list[tuple[int, int, str]]:
    return [(c.offset, c.length, c.digest) for c in chunks]


async def start_cluster(owner, root: Path) -> dict[int, StorageNodeServer]:
    cluster = make_cluster_cfg(3)
    nodes = {}
    for p in cluster.peers:
        node = StorageNodeServer(NodeConfig(
            node_id=p.node_id, cluster=cluster, data_root=root,
            sidecar_port=owner.port, health_probe_s=0,
            ingest=IngestConfig(credit_bytes=REGION,
                                flush_bytes=REGION // 2)))
        await node.start()
        nodes[p.node_id] = node
    return nodes


def on_cluster(owner, root: Path, scenario):
    async def run():
        nodes = await start_cluster(owner, root)
        try:
            return await scenario(nodes)
        finally:
            await stop_nodes(nodes)

    return asyncio.run(run())


def windows_of(total: int) -> tuple[int, int]:
    """(windows, their bytes) of a stream of ``total`` bytes, as the
    stride says: a window starts every ``STRIDE``, is ``REGION`` long,
    and the last is what is left."""
    base = windows = nbytes = 0
    while base + REGION < total:
        windows, nbytes, base = windows + 1, nbytes + REGION, base + STRIDE
    return windows + 1, nbytes + total - base


# ------------------------------------------------------------------ (a) --

def test_a_long_streams_manifest_is_the_oracles_and_reads_back(
        owner, tmp_path):
    data = body_of(1)
    before = owner.fragmenter.device_stats()

    async def scenario(nodes):
        manifest, stats = await nodes[1].upload_stream(
            blocks_of(data), "night.img")
        _, got = await nodes[2].download(manifest.file_id)
        return manifest, stats, bytes(got), nodes[1].ingest_stats()

    manifest, stats, got, ingest = on_cluster(owner, tmp_path, scenario)
    want = chunk_file_anchored_np(np.frombuffer(data, np.uint8), PARAMS)
    assert rows(manifest.chunks) == want            # row for row
    assert rows(AnchoredCpuFragmenter().chunk(data)) == want
    assert got == data and stats["bytes"] == TOTAL
    assert stats["minCopies"] == 2

    # (d) the counters, each what the stride says for this length
    after = owner.fragmenter.device_stats()
    grew = {k: after[k] - before[k] for k in (
        "windows", "windowBytes", "tailWindows", "pendingAtDispatch",
        "regions", "bytes", "streams", "stagedTimed", "stagedTimedBytes")}
    assert windows_of(TOTAL) == (FULL + 1, FULL * REGION + TAIL)
    assert grew["windows"] == grew["regions"] == FULL + 1
    assert grew["windowBytes"] == FULL * REGION + TAIL
    assert grew["tailWindows"] == 1
    assert grew["bytes"] == TOTAL and grew["streams"] == 1
    engine = owner.fragmenter
    assert 0 < grew["pendingAtDispatch"] <= engine.max_inflight * (FULL + 1)
    # the first window of a stream is always timed; a window's staging
    # buffer is its bucket, never less than its bytes
    assert 1 <= grew["stagedTimed"] <= FULL + 1
    assert grew["stagedTimedBytes"] >= REGION
    assert after["stagedTimedS"] > before["stagedTimedS"]
    assert REGION < after["bufferPeakBytes"] <= SPAN + REGION
    assert ingest["commit"] == {
        "manifests": 1, "manifestChunks": len(want),
        "manifestBytes": len(manifest.to_json())}
    assert 0 < ingest["seam"]["teePeakBytes"] <= TEE_CAP
    assert ingest["seam"]["teeWaitS"] >= 0.0


# ------------------------------------------------------------------ (b) --

def test_the_four_bounds_meet_and_the_upload_still_acks(owner, tmp_path):
    """One peer's ``store_chunks`` slowed: placement backs up, the
    window fills, the credit gate waits, the tee stays under its cap —
    and no pair of the four bounds deadlocks. A chunk is 64 KiB at most
    and the budget 1 MiB: ``ByteBudget``'s "a single chunk larger than
    the budget" clause is not what lets it through."""
    data = body_of(2)
    adds: list[tuple[str, float]] = []

    async def scenario(nodes):
        put_many = nodes[3].cas.put_many

        async def slow(items, **kw):
            await asyncio.sleep(0.15)
            return await put_many(items, **kw)

        nodes[3].cas.put_many = slow
        stalls = nodes[1].ingest_stalls
        add = stalls.add
        stalls.add = lambda name, s: (adds.append((name, s)), add(name, s))
        with nodes[1].obs.request_span("http./upload"):     # traced, so
            manifest, _ = await asyncio.wait_for(           # totalled
                nodes[1].upload_stream(blocks_of(data), "slow.img"), 120)
        _, got = await nodes[2].download(manifest.file_id)
        return manifest, bytes(got), nodes[1].ingest_stats(), \
            nodes[1].obs.span_totals()

    manifest, got, ingest, spans = on_cluster(owner, tmp_path, scenario)
    assert got == data
    assert rows(manifest.chunks) == rows(AnchoredCpuFragmenter().chunk(data))
    assert PARAMS.chunk.max_blocks * 64 < REGION    # no chunk near the budget
    stalls = ingest["stalls"]
    assert stalls["creditS"] > 0 and stalls["placementS"] > 0
    assert stalls["placeWindowPeak"] == ingest["window"] == 2
    assert ingest["creditBytes"] == REGION
    assert ingest["flushBytes"] == REGION // 2
    assert 0 < ingest["seam"]["teePeakBytes"] <= TEE_CAP

    # the stopwatches accrue as they happen — a block, a hand-off — and
    # add up to what they added up to when a stream's end booked them:
    # the body's two waits fill the body span but for the hashing
    by_name: dict[str, list[float]] = {}
    for name, s in adds:
        by_name.setdefault(name, []).append(s)
    n_blocks = -(-TOTAL // BLOCK)
    assert len(by_name["bodyWaitS"]) == n_blocks + 1
    assert len(by_name["feedWaitS"]) == n_blocks
    assert len(by_name["seamReplyS"]) == ingest["seam"]["handoffs"] + 1
    for name, parts in by_name.items():
        assert sum(parts) == pytest.approx(stalls[name], abs=1e-4), name
    body_s = spans["upload.body"]["seconds"]
    waits = stalls["bodyWaitS"] + stalls["feedWaitS"]
    assert 0.5 * body_s < waits <= body_s
    assert 0 < stalls["seamReplyS"] < spans["upload.fragment"]["seconds"] \
        - stalls["creditS"]


# ------------------------------------------------------------------ (c) --

def test_three_long_streams_at_once_each_get_their_own_table(
        owner, tmp_path):
    bodies = {i: body_of(10 + i, TOTAL - i * 70_001) for i in (1, 2, 3)}
    before = owner.fragmenter.device_stats()

    async def scenario(nodes):
        done = await asyncio.gather(*(
            nodes[i].upload_stream(blocks_of(bodies[i]), f"img{i}")
            for i in bodies))
        reads = [bytes((await nodes[i % 3 + 1].download(m.file_id))[1])
                 for i, (m, _) in zip(bodies, done)]
        return [m for m, _ in done], reads

    manifests, reads = on_cluster(owner, tmp_path, scenario)
    cpu = AnchoredCpuFragmenter()
    for i, m, got in zip(bodies, manifests, reads):
        assert got == bodies[i]
        assert rows(m.chunks) == rows(cpu.chunk(bodies[i])), i
    after = owner.fragmenter.device_stats()
    want = [windows_of(len(b)) for b in bodies.values()]
    assert after["windows"] - before["windows"] == sum(w for w, _ in want)
    assert after["windowBytes"] - before["windowBytes"] \
        == sum(b for _, b in want)
    assert after["tailWindows"] - before["tailWindows"] == 3
    assert after["bytes"] - before["bytes"] == sum(map(len, bodies.values()))


def test_phase_bytes_accrue_a_window_not_at_the_close(owner):
    """``Health.device`` ``bytes`` grows while a stream is open, by what
    each collected window consumed, and ends at the stream's length."""
    data = body_of(4, 4 * STRIDE + TAIL)
    engine = owner.fragmenter
    seen = []
    base = engine.device_stats()["bytes"]
    for _ in engine.chunks_stream(
            data[i:i + BLOCK] for i in range(0, len(data), BLOCK)):
        seen.append(engine.device_stats()["bytes"] - base)
    assert len(seen) == 5 and seen == sorted(seen)
    assert 0 < seen[0] <= REGION and seen[-1] == len(data)
    assert len(set(seen)) == 5


# ------------------------------------------------------------------ (e) --

sys.path.insert(0, str(BENCH))
import reference_images  # noqa: E402
from window import load_by_name  # noqa: E402

TRAFFIC = json.loads((BENCH / "traffic" / "ingest-nightly.json").read_text())
CONFIG = json.loads((BENCH / "configs" / "images-3n-rf2.json").read_text())
SMALL = {**TRAFFIC, **TRAFFIC["rehearsal"]}


def generator(seed: int, traffic: dict = SMALL):
    return load_by_name("generators", "images").Generator(
        traffic, CONFIG, seed)


def test_the_generators_objects_are_the_references_byte_for_byte():
    gen, ref = generator(7), reference_images.Reference(SMALL)
    for k in range(0, 10):
        assert ref.object(k) == bytes(gen.make(("img", k))), k
    # a client's image, night after night in place, is make()'s
    arr = gen.base().copy()
    for n in (1, 2, 3):
        gen.night(arr, 2, n)
        assert bytes(arr) == ref.object(gen.key_of(2, n)[1])
    source = (BENCH / "reference_images.py").read_text().split('"""', 2)[2]
    assert "import data" not in source and "generators" not in source


def test_seed_only_orders_the_images_and_the_coordinators():
    a, b = generator(2147483659), generator(5)
    assert sorted(a.image_of) == sorted(b.image_of) == [0, 1, 2]
    assert (a.image_of, a.rotation) != (b.image_of, b.rotation)
    c = generator(2147483659)
    assert (a.image_of, a.rotation) == (c.image_of, c.rotation)
    for k in (0, 1, 3, 4, 9):
        assert bytes(a.make(("img", k))) == bytes(b.make(("img", k)))
    # night n of an image never meets night n-1 on its coordinator
    for gen in (a, b):
        for image in range(3):
            nodes = [gen.node_of(image, n) for n in range(1, 7)]
            assert all(x != y for x, y in zip(nodes, nodes[1:]))
    # the slice is night 1 of the three images: keys 1..3
    assert sorted(a.key_of(i, 1)[1] for i in range(3)) == [1, 2, 3]


def test_the_traffic_file_is_the_issues_under_its_one_fallback():
    """ISSUE 43's parameters, with its one fallback taken: a run at the
    source's 1 GiB took over 8 minutes (``reduced_why``), so an upload
    is 512 MiB — 9 device windows, 8 full ones and a 1 MiB tail."""
    assert TRAFFIC["object_bytes"] == CONFIG["object_bytes"] == 1 << 29
    assert CONFIG["source_object_bytes"] == 1 << 30
    assert CONFIG["reduced"] == ["nodes", "object_bytes", "corpus_bytes"]
    assert "8.05 minutes" in CONFIG["reduced_why"]["object_bytes"]
    assert set(CONFIG["reduced"]) == set(CONFIG["reduced_why"])
    assert (TRAFFIC["clients"], TRAFFIC["block_bytes"]) == (3, 4 << 20)
    assert (TRAFFIC["extents_per_night"], TRAFFIC["extent_min_bytes"],
            TRAFFIC["extent_max_bytes"]) == (32, 65536, 2 << 20)
    assert (TRAFFIC["lead_objects"], TRAFFIC["ratio_objects"]) == (1, 3)
    assert (TRAFFIC["check_sample"], TRAFFIC["check_deletes"]) == (2, 0)
    # the warm-up compiles both shapes a stream dispatches: one full
    # 64 MiB window and the tail's bucket
    from dfs_tpu.fragmenter import cdc_anchored as F
    stride = F._REGION_BYTES - PARAMS.seg_max
    tail = TRAFFIC["object_bytes"] - 8 * stride
    assert tail == 1 << 20 and TRAFFIC["warm_sizes"] == [stride + tail]
    assert (1 << 30) == 16 * stride + (2 << 20)     # the source's: 17
    old = json.loads((BENCH / "configs" / "tarball-3n-rf2.json").read_text())
    for key in ("guarantees", "controls", "departures"):
        assert CONFIG[key] == old[key]
    assert {k: v for k, v in CONFIG["deployment"].items()
            if k != "redundancy"} == old["deployment"]
    assert CONFIG["deployment"]["redundancy"] == {"scheme": "copies",
                                                  "copies": 2}
    assert CONFIG["reduced_why"]["nodes"] == old["reduced_why"]["nodes"]
    assert len(CONFIG["source"]) <= 200


def test_the_references_count_is_what_the_long_stream_stores(
        owner, tmp_path):
    """``stored_ratio_of`` — every object chunked whole and alone by the
    CPU engine — against the chunk files the three nodes hold once the
    base and night 1 of the three images went in through the owner's
    windows (4 MiB objects: five windows each)."""
    gen = generator(11)
    count = reference_images.stored_ratio_of(SMALL, 2)
    assert count == reference_images.stored_ratio_of(
        SMALL, 2, make=lambda k: gen.make(("img", k)))
    assert 0.03 < count < 0.3

    async def scenario(nodes):
        out = []
        for k in range(4):
            body = bytes(gen.make(("img", k)))
            m, _ = await nodes[k % 3 + 1].upload_stream(
                blocks_of(body), f"img-{k}")
            out.append(m)
        return out

    manifests = on_cluster(owner, tmp_path, scenario)
    base = {c.digest for c in manifests[0].chunks}
    new = {c.digest for m in manifests[1:] for c in m.chunks} - base
    on_disk = sum(p.stat().st_size
                  for d in new
                  for p in tmp_path.glob(f"node-*/chunks/{d[:2]}/{d}"))
    assert on_disk / (3 * SMALL["object_bytes"]) == count
