"""The owner seam at the node (docs/ingest.md, "Hand-offs"): a
fragmenter thread holds the chunks an engine — an in-process one, or a
chip owner's reply through ``SidecarFragmenter`` — gives it and crosses
to the event loop once per hand-off. What reaches placement, in which
order and cut into which batches is the per-chunk schedule's, pinned
against a table recorded from it; the byte budget binds as it did; and
the paths a held list could break (abort, hang-up, a failing engine) end
as they did, within the same polls."""

import asyncio
import hashlib
import json
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

from dfs_tpu.config import (CDCParams, ClusterConfig, IngestConfig,
                            NodeConfig)
from dfs_tpu.fragmenter.base import Fragmenter
from dfs_tpu.meta.manifest import ChunkRef, Manifest
from dfs_tpu.node import ingest as ingest_mod
from dfs_tpu.node.errors import UploadError
from dfs_tpu.node.runtime import StorageNodeServer
from dfs_tpu.sidecar.service import SidecarServer
from dfs_tpu.utils.hashing import sha256_hex
from test_sidecar import _anchored_sidecar

CDC = CDCParams(min_size=64, avg_size=256, max_size=1024)
ENGINES = ["in-process", "sidecar"]


@pytest.fixture
def make_node(tmp_path):
    """``make_node(engine, **ingest)`` → a 1-node rf=1 server (no
    listeners: ``upload_stream`` touches the local store only) on the
    rolling ``cdc`` engine in-process, or delegating to an anchored
    sidecar with 16 KiB regions: many replies to a small stream."""
    made = []

    def make(engine: str, flush: int = 64 * 1024, big: bool = False,
             **ingest):
        # ``big``: the deployed chunk sizes (8 KiB on average), for a
        # stream of tens of MiB — the anchored walk on both sides
        kw = {"fragmenter": "cdc-anchored"} if big \
            else {"fragmenter": "cdc", "cdc": CDC}
        if engine == "sidecar":
            srv = SidecarServer(port=0, fragmenter="cdc-anchored") \
                if big else _anchored_sidecar()
            if big:
                srv.start()
            made.append(srv)
            kw = {"sidecar_port": srv.port}
        cfg = NodeConfig(
            node_id=1, data_root=tmp_path / f"n{len(made)}-{engine}",
            cluster=ClusterConfig.localhost(1, replication_factor=1),
            health_probe_s=0, ingest=IngestConfig(**ingest), **kw)
        node = StorageNodeServer(cfg)
        node.ingest.flush_bytes = flush
        return node

    yield make
    for srv in made:
        srv.stop()


def _body(nbytes: int = 700_000) -> bytes:
    """Seeded bytes with a block that comes three times: content the
    stream has already placed must be skipped chunk by chunk."""
    r = np.random.default_rng(30)
    fresh = r.integers(0, 256, size=nbytes, dtype=np.uint8).tobytes()
    again = fresh[100_000:160_000]
    return fresh[:400_000] + again + fresh[400_000:] + again


def _blocks(data: bytes, step: int):
    async def gen():
        for off in range(0, len(data), step):
            yield data[off:off + step]
    return gen()


def _record_batches(node) -> list[list[str]]:
    """The digests of every batch ``Placement.place`` is given, in the
    order the batches are submitted."""
    batches: list[list[str]] = []
    real = node.placement.place

    async def place(file_id, batch, stats, **kw):
        batches.append([d for d, _ in batch])
        await real(file_id, batch, stats, **kw)

    node.placement.place = place
    return batches


def _per_chunk_schedule(manifest, data: bytes, flush: int):
    """The plain reference: one chunk at a time, a digest placed once, a
    batch cut when the chunks since the last cut reach ``flush``."""
    seen, batches, batch, pending = set(), [], [], 0
    for c in manifest.chunks:
        if c.digest in seen:
            continue
        seen.add(c.digest)
        batch.append(c.digest)
        pending += c.length
        if pending >= flush:
            batches.append(batch)
            batch, pending = [], 0
    if batch:
        batches.append(batch)
    return batches


# what the parent of PR 30 — a crossing a chunk — placed for ``_body()``
# at flush_bytes 64 KiB, whatever the blocking: chunks a batch, and
# sha256 over the placed digests ("," within a batch, "\n" between).
# The sidecar's row is the anchored engine's and was regenerated at PR 37
# with its segment rule (the schedule test beside it held before and
# after); the in-process row is the Gear engine's, untouched.
RECORDED = {
    "in-process": (
        [220, 232, 225, 215, 186, 214, 210, 206, 229, 188, 136],
        "6c49e7ef9088741b520a13b75cdb32e51bcbd99c998cd220b1d490ef481def35"),
    "sidecar": (
        [218, 211, 201, 215, 194, 215, 207, 206, 217, 232, 159],
        "f667fd67a4a852cd731c8ed64fee9e99ce111bf9978e549305b8d45482619a5b"),
}


def _table(batches):
    text = "\n".join(",".join(b) for b in batches)
    return [len(b) for b in batches], hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("step", [10_000, 262_144])
@pytest.mark.parametrize("engine", ENGINES)
def test_manifest_order_and_batch_cuts_are_the_per_chunk_schedules(
        make_node, engine, step):
    data = _body()
    node = make_node(engine, window=2)
    batches = _record_batches(node)

    manifest, stats = asyncio.run(
        node.upload_stream(_blocks(data, step), "s.bin"))

    assert manifest.file_id == sha256_hex(data)
    assert manifest.size == len(data)
    want = node.fragmenter.chunk(data)
    assert [(c.index, c.offset, c.length, c.digest)
            for c in manifest.chunks] \
        == [(c.index, c.offset, c.length, c.digest) for c in want]
    assert batches == _per_chunk_schedule(manifest, data, 64 * 1024)
    assert _table(batches) == RECORDED[engine]
    assert stats["uniqueChunks"] == sum(len(b) for b in batches) \
        < len(manifest.chunks)
    seam = node.ingest_stats()["seam"]
    assert seam["chunks"] == len(manifest.chunks)
    assert 0 < seam["handoffs"] * 20 < seam["chunks"]
    assert node.ingest_stats()["stalls"]["seamReplyS"] > 0


class _Watched(ingest_mod.ByteBudget):
    """A byte budget that remembers its deepest ``outstanding``."""

    made: list = []

    def __init__(self, budget):
        super().__init__(budget)
        self.deepest = 0
        _Watched.made.append(self)

    def acquire(self, n, timeout=None):
        ok = super().acquire(n, timeout)
        if ok:
            self.deepest = max(self.deepest, self.outstanding)
        return ok


@pytest.fixture
def budgets(monkeypatch):
    _Watched.made = []
    monkeypatch.setattr(ingest_mod, "ByteBudget", _Watched)
    return _Watched.made


def _slow_placement(node, delay_s: float):
    async def place(file_id, batch, stats, **kw):
        await asyncio.sleep(delay_s)
        stats["minCopies"] = 1

    node.placement.place = place


@pytest.mark.parametrize("engine", ENGINES)
def test_outstanding_never_exceeds_credit_bytes_with_a_slow_consumer(
        make_node, budgets, engine):
    """64 MiB through a 1 MiB budget whose consumer sleeps on every
    batch: the budget, not the engine, paces the stream — it fills, the
    fragmenter thread hands over what it holds BEFORE it blocks (a held
    chunk is charged, and only the loop gives credit back), and the
    stream still ends."""
    credit, flush = 1 << 20, 256 * 1024
    node = make_node(engine, flush=flush, big=True, window=1,
                     credit_bytes=credit)
    _slow_placement(node, 0.004)
    block = np.random.default_rng(31).integers(
        0, 256, size=1 << 20, dtype=np.uint8)

    async def body():
        for i in range(64):
            yield (block ^ np.uint8(i)).tobytes()

    manifest, _ = asyncio.run(node.upload_stream(body(), "big.bin"))

    assert manifest.size == 64 << 20
    (budget,) = budgets
    assert credit // 2 < budget.deepest <= credit
    assert budget.outstanding == 0
    stats = node.ingest_stats()
    assert stats["stalls"]["creditS"] > 0
    # a hand-off is a quarter of the budget at most, and never more
    # than a placement batch
    assert stats["seam"]["chunks"] == len(manifest.chunks)
    assert stats["seam"]["handoffs"] >= 200


@pytest.mark.parametrize("engine", ENGINES)
def test_a_budget_smaller_than_a_chunk_admits_them_one_at_a_time(
        make_node, budgets, engine):
    """``ByteBudget``'s one-oversized-item rule needs an EMPTY budget:
    with a chunk held and charged it would never come true."""
    node = make_node(engine, flush=4096, window=2, credit_bytes=48)
    data = _body(120_000)

    manifest, _ = asyncio.run(
        node.upload_stream(_blocks(data, 7_000), "tiny-budget.bin"))

    assert manifest.file_id == sha256_hex(data)
    seam = node.ingest_stats()["seam"]
    assert seam["handoffs"] == seam["chunks"] == len(manifest.chunks)
    assert budgets[0].outstanding == 0


# ---------------------------------------------------------------------- #
# the paths a held list could break
# ---------------------------------------------------------------------- #

class _Uploads:
    """Every ``_StreamUpload`` the module starts, for a look inside."""

    def __init__(self, monkeypatch):
        self.made = made = []

        class Kept(ingest_mod._StreamUpload):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                made.append(self)

        monkeypatch.setattr(ingest_mod, "_StreamUpload", Kept)

    def only(self):
        (su,) = self.made
        return su


def _unconsumed(su) -> int:
    """Payload bytes of hand-offs the loop was sent and never took."""
    left = 0
    while not su.outq.empty():
        item = su.outq.get_nowait()
        if isinstance(item, list):
            left += sum(len(p) for _, p in item)
    return left


class _Gated(Fragmenter):
    """An engine under the test's thumb: ``first`` chunks of 1 KiB, then
    nothing until ``go()`` says so, then ``rest`` more (or an error)."""

    name = "gated"

    def __init__(self, first: int, rest: int, go, fail: bool = False):
        self.first, self.rest, self.go, self.fail = first, rest, go, fail
        self.ended_with: BaseException | None = None
        self.stored = 0

    def chunk(self, data):
        raise NotImplementedError

    def manifest_stream(self, blocks, name, store=None):
        refs = []
        try:
            for i in range(self.first + self.rest):
                if i == self.first:
                    deadline = time.monotonic() + 10
                    while not self.go() and time.monotonic() < deadline:
                        time.sleep(0.005)
                    if self.fail:
                        raise ValueError("engine broke")
                payload = i.to_bytes(4, "big") * 256
                refs.append(ChunkRef(index=i, offset=1024 * i, length=1024,
                                     digest=sha256_hex(payload)))
                store(refs[-1].digest, payload)
                self.stored += 1
        except BaseException as e:
            self.ended_with = e
            raise
        for _ in blocks:
            pass
        return Manifest(file_id="0" * 64, name=name, size=1024 * len(refs),
                        fragmenter=self.name, chunks=tuple(refs))


async def _stalled_body():
    yield b"x" * 1000
    await asyncio.sleep(3600)


def test_a_placement_failure_hands_nothing_over_after_the_abort(
        make_node, budgets, monkeypatch):
    """Batch 1 fails while 8 chunks sit held in the fragmenter thread;
    the engine then gives 200 more — three hand-offs' worth. None
    crosses: the hand-off that comes due raises, the engine ends, and
    what was held goes back to the budget."""
    uploads = _Uploads(monkeypatch)
    node = make_node("in-process", flush=64 * 1024, window=2)
    engine = _Gated(first=64 + 8, rest=200,
                    go=lambda: uploads.only().aborted.is_set())
    node.ingest.fragmenter = engine

    async def place(file_id, batch, stats, **kw):
        assert len(batch) == 64
        raise UploadError("Replication failed: injected")

    node.placement.place = place

    async def run():
        t0 = time.monotonic()
        with pytest.raises(UploadError, match="injected"):
            await node.upload_stream(_stalled_body(), "doomed.bin")
        return time.monotonic() - t0

    assert asyncio.run(run()) < 2.0          # the 0.5 s polls, not 10 s
    su = uploads.only()
    assert isinstance(engine.ended_with, RuntimeError)
    assert "aborted" in str(engine.ended_with)
    assert 64 + 8 < engine.stored <= 64 + 8 + 64
    assert su.frag_dead.is_set()
    assert _unconsumed(su) == 0 and budgets[0].outstanding == 0
    assert node.store.manifests.ids() == []
    assert node.ingest_stats()["seam"] == {"handoffs": 1, "chunks": 64}


@pytest.mark.parametrize("engine", ENGINES)
def test_a_placement_failure_ends_the_stream_within_the_polls(
        make_node, budgets, monkeypatch, engine):
    uploads = _Uploads(monkeypatch)
    node = make_node(engine, flush=32 * 1024, window=2)

    async def place(file_id, batch, stats, **kw):
        raise UploadError("Replication failed: injected")

    node.placement.place = place
    data = _body(300_000)

    async def body():
        for off in range(0, len(data), 20_000):
            yield data[off:off + 20_000]
        await asyncio.sleep(3600)            # the client never ends it

    async def run():
        t0 = time.monotonic()
        with pytest.raises(UploadError, match="injected"):
            await node.upload_stream(body(), "doomed.bin")
        return time.monotonic() - t0

    assert asyncio.run(run()) < 3.0
    su = uploads.only()
    assert su.frag_dead.is_set()
    assert budgets[0].outstanding == _unconsumed(su)
    assert node.store.manifests.ids() == []


@pytest.mark.parametrize("engine", ENGINES)
def test_a_client_that_hangs_up_mid_body_ends_within_the_polls(
        make_node, budgets, monkeypatch, engine):
    uploads = _Uploads(monkeypatch)
    node = make_node(engine, flush=32 * 1024, window=2)
    data = _body(300_000)
    sent = asyncio.Event()

    async def body():
        for off in range(0, len(data), 20_000):
            yield data[off:off + 20_000]
        sent.set()
        await asyncio.sleep(3600)

    async def run():
        task = asyncio.create_task(node.upload_stream(body(), "gone.bin"))
        await sent.wait()
        await asyncio.sleep(0.2)             # chunks held, batches placed
        t0 = time.monotonic()
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        return time.monotonic() - t0

    assert asyncio.run(run()) < 3.0
    su = uploads.only()
    assert su.aborted.is_set() and su.frag_dead.is_set()
    assert all(t.done() for t, _ in su.inflight)
    assert budgets[0].outstanding == _unconsumed(su)
    assert node.store.manifests.ids() == []


def test_an_engine_that_raises_after_some_chunks_fails_the_upload(
        make_node, budgets, monkeypatch):
    """100 chunks reach the loop in one hand-off, 8 more are held when
    the engine breaks: the upload fails naming the engine's error, the
    held chunks' credit goes back, nothing is committed."""
    uploads = _Uploads(monkeypatch)
    node = make_node("in-process", flush=1 << 20, window=2,
                     credit_bytes=400 * 1024)
    node.ingest.fragmenter = _Gated(first=100 + 8, rest=1,
                                    go=lambda: True, fail=True)

    async def body():
        yield b"x" * 1000

    async def run():
        with pytest.raises(UploadError,
                           match="fragmenter failed: engine broke"):
            await node.upload_stream(body(), "broken.bin")

    asyncio.run(run())
    su = uploads.only()
    assert node.ingest_stats()["seam"] == {"handoffs": 1, "chunks": 100}
    assert su.frag_dead.is_set()
    assert budgets[0].outstanding == _unconsumed(su)
    assert node.store.manifests.ids() == []
    assert threading.active_count() < 50


# ---------------------------------------------------------------------- #
# the two per-layer readers (benchmarks/layer_metrics/), on the program's
# own /metrics shape: nothing to read on the parent, numbers here
# ---------------------------------------------------------------------- #

BENCH = Path(__file__).resolve().parent.parent / "benchmarks"
SEAM_METRICS = {"seam.reply_s_per_gib": ("s/GiB", "lower"),
                "seam.chunks_per_handoff": ("chunks/handoff", "higher")}


def _bench_window(nodes_before, nodes_after):
    sys.path.insert(0, str(BENCH))
    import window

    put = SimpleNamespace(kind="put", acked=True, nbytes=window.GIB // 2)
    return window, window.Window(
        seconds=50.0, t_open=0.0, t_close=50.0, setup_s=1.0, ops=[put],
        session_ops=[put], stores=None, manifests={},
        nodes_before=nodes_before, nodes_after=nodes_after,
        prom_before=[], prom_after=[], owner_before={}, owner_after={},
        config={}, traffic={}, device_kind="x")


def _served(handoffs, chunks, reply_s):
    return {"ingest": {"stalls": {"creditS": 1.0, "seamReplyS": reply_s},
                       "seam": {"handoffs": handoffs, "chunks": chunks}}}


@pytest.mark.parametrize("name", sorted(SEAM_METRICS))
def test_the_seam_readers_find_nothing_on_the_parent_and_read_here(
        make_node, name):
    parent = [{"ingest": {"stalls": {"creditS": 1.0, "feedWaitS": 2.0},
                          "cas": {"ops": 7}}}] * 3
    window, w = _bench_window(parent, parent)
    read = window.load_by_name("layer_metrics", name).read
    assert read(w) is None
    assert read(_bench_window([{}] * 3, [{}] * 3)[1]) is None

    # node 3 had served nothing when the window opened
    before = [_served(10, 20_000, 0.5), _served(4, 8_000, 0.2), {}]
    after = [_served(30, 60_000, 1.5), _served(4, 8_000, 0.2),
             _served(10, 21_440, 0.25)]
    got = read(_bench_window(before, after)[1])
    assert got == {"seam.reply_s_per_gib": (1.0 + 0.25) / 0.5,
                   "seam.chunks_per_handoff": (40_000 + 21_440) / 30}[name]
    # no upload ended in the window: no crossing to divide by
    idle = read(_bench_window(after, after)[1])
    assert idle == {"seam.reply_s_per_gib": 0.0,
                    "seam.chunks_per_handoff": None}[name]

    # the shape is the program's own
    node = make_node("in-process")
    asyncio.run(node.upload_stream(_blocks(_body(100_000), 9_000), "m.bin"))
    live = {"ingest": node.ingest_stats()}
    assert read(_bench_window([{}], [live])[1]) > 0

    unit, better = SEAM_METRICS[name]
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {
        "name": name, "unit": unit, "better": better,
        "source": "program_counter", "layer": "owner seam at the node",
        "moves": "ingest_mibps",
        # every cell that STREAMS its uploads (chunked transfer, block by
        # block): a whole-body upload crosses by no hand-off (PR 36)
        "workloads": [c["name"] for c in bench["workloads"] if json.loads(
            (BENCH / "traffic" / f"{c['traffic']}.json").read_text())[
                "block_bytes"]]}
