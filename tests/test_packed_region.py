"""PR 41: packed regions — several independent streams in one device
dispatch (ops/cdc_anchored.py "packed regions"), the owner's packer in
front of them (fragmenter/cdc_anchored.py ``_Packer``) and the chip
owner's handlers waiting on it (sidecar/service.py).

The law: each stream's chunk table is bit for bit
``chunk_file_anchored_np`` of that stream alone, whatever lies beside
it, in whatever order, at whatever offset. Small sizes, CPU, ONE
compiled packed shape for the ops and the engine tests (the tight one
of ``SHAPE``; (c) adds an 8-lane one to overflow).
"""

from __future__ import annotations

import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from dfs_tpu.fragmenter.cdc_anchored import AnchoredTpuFragmenter
from dfs_tpu.ops import cdc_anchored as A
from dfs_tpu.ops.cdc_anchored import (TILE_BYTES, AnchoredCdcParams,
                                      chunk_file_anchored_np, cut_counts,
                                      anchors_np, packed_chunks,
                                      packed_lanes, packed_layout,
                                      select_segments_kinds)
from dfs_tpu.ops.cdc_v2 import AlignedCdcParams

REPO = Path(__file__).resolve().parent.parent

# tests/test_cdc_anchored.py's geometry: 4 KiB lanes, strong_min 1 KiB
SMALL = AnchoredCdcParams(
    chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                           strip_blocks=64),
    seg_min=2048, seg_max=4096, seg_mask=2047, strong_bits=1)
PACK_BYTES = 1 << 20
TOP = 300 * 1024
EDGES = (0, 1, TILE_BYTES, SMALL.strong_min, SMALL.seg_max - 1,
         SMALL.seg_max, SMALL.seg_max + 1)


def engine(**kw) -> AnchoredTpuFragmenter:
    # a region of 16 MiB: the packed shape comes down to a sixteenth
    # of it, 1 MiB
    return AnchoredTpuFragmenter(SMALL, region_bytes=16 * PACK_BYTES,
                                 lane_multiple=8, **kw)


# the engine's own shape, so every test here shares one executable
SHAPE = engine()._packer.shape              # (region words, lanes)


def oracle(stream: np.ndarray) -> list:
    return chunk_file_anchored_np(stream, SMALL)


def streams_of(seed: int) -> list[np.ndarray]:
    """1-48 streams of 1 B-300 KiB, log-uniform (a region is bounded by
    its lanes as by its bytes: mostly small, a few large), an empty one
    and the rule's edge lengths among them."""
    rng = np.random.default_rng([41, seed])
    sizes = [int(np.exp(rng.uniform(0, np.log(TOP))))
             for _ in range(int(rng.integers(1, 49)))]
    for at, size in zip(rng.permutation(len(sizes)), EDGES[seed % 3::3]):
        sizes[int(at)] = size
    return [rng.integers(0, 256, size=n, dtype=np.uint8) for n in sizes]


def regions_of(streams: list) -> list[list[int]]:
    """Indices of ``streams`` cut greedily into regions of ``SHAPE``
    (bytes by the layout's own rule, lanes by ``packed_lanes``: the
    packer's rules)."""
    out, cur, lanes = [], [], 0
    for i, s in enumerate(streams):
        need = packed_lanes(s.shape[0], SMALL)
        sizes = [streams[j].shape[0] for j in cur] + [s.shape[0]]
        if cur and (packed_layout(sizes)[1] > SHAPE[0] * 4
                    or lanes + need > SHAPE[1]):
            out.append(cur)
            cur, lanes = [], 0
        cur.append(i)
        lanes += need
    return out + [cur]


def packed_tables(streams: list) -> list:
    tables: list = [None] * len(streams)
    for region in regions_of(streams):
        got, _ = packed_chunks([streams[i] for i in region], SMALL,
                               *SHAPE, lane_multiple=8)
        for i, table in zip(region, got):
            tables[i] = table
    return tables


# ------------------------------------------------------------ (a), (b) --

@pytest.mark.parametrize("seed", range(64))
def test_every_stream_of_a_packed_region_is_chunked_as_if_alone(seed):
    streams = streams_of(seed)
    assert packed_tables(streams) == [oracle(s) for s in streams]


@pytest.mark.parametrize("seed", range(0, 64, 4))
def test_order_and_offsets_do_not_change_a_streams_table(seed):
    """The same streams in another order, and behind a spacer of a
    seeded length that shifts every offset, give the same tables."""
    streams = streams_of(seed)
    want = [oracle(s) for s in streams]
    rng = np.random.default_rng([42, seed])
    order = [int(i) for i in rng.permutation(len(streams))]
    spacer = rng.integers(0, 256, size=int(rng.integers(1, 9000)),
                          dtype=np.uint8)
    got = packed_tables([spacer] + [streams[i] for i in order])
    assert got[0] == oracle(spacer)
    assert [got[1 + order.index(i)] for i in range(len(streams))] == want


def test_layout_is_tile_aligned_with_eight_zero_bytes_between():
    sizes = [1, 504, 505, 512, 0, 513, 4096]
    offs, used = packed_layout(sizes)
    assert offs[0] == 0 and used == offs[-1] + sizes[-1]
    for a, n, b in zip(offs, sizes, offs[1:]):
        assert b % TILE_BYTES == 0 and b >= a + n + 8
        assert b - (a + n + 8) < TILE_BYTES        # and no tile wasted
    # 504 B end 8 short of a tile: the next stream starts on it; 505 B
    # would leave 7, so a whole tile lies between
    assert offs[2] - offs[1] == 512 and offs[3] - offs[2] == 1024


def test_region_cut_counts_are_every_streams_own():
    streams = streams_of(7)[:12]
    _, cuts = packed_chunks(streams, SMALL, *SHAPE, lane_multiple=8)
    want = np.zeros(4, dtype=np.int64)
    for s in streams:
        want += cut_counts(select_segments_kinds(
            *anchors_np(s, SMALL), s.shape[0], SMALL)[1])
    assert cuts == tuple(int(c) for c in want)


# ------------------------------------------------------------------ (c) --

def test_overflowing_stream_is_redone_and_its_neighbours_stand(monkeypatch):
    """Eight lanes, three one-lane streams, then a stream of some ten
    segments: it overflows the lanes, and with it the stream behind it
    has none. Both are redone at the worst-case bound — those two alone,
    the three tables in front stand as the tight dispatch gave them."""
    rng = np.random.default_rng(43)
    streams = [rng.integers(0, 256, size=n, dtype=np.uint8)
               for n in (700, 3000, 4096, 30000, 900)]
    calls: list[tuple[str, list[int]]] = []
    orig = A.packed_dispatch

    def spy(words, offs, lengths, params, lanes, lane_multiple=128,
            cap_mode="tight"):
        calls.append((cap_mode, list(lengths)))
        return orig(words, offs, lengths, params, lanes, lane_multiple,
                    cap_mode)

    monkeypatch.setattr(A, "packed_dispatch", spy)
    tables, cuts = packed_chunks(streams, SMALL, 16384, 8, lane_multiple=8)
    assert calls == [("tight", [700, 3000, 4096, 30000, 900]),
                     ("full", [30000, 900])]
    assert tables == [oracle(s) for s in streams]
    # the walk ran at the full bound the first time: every stream counted
    assert cuts[0] == sum(cut_counts(select_segments_kinds(
        *anchors_np(s, SMALL), s.shape[0], SMALL)[1])[0] for s in streams)


def test_engine_redoes_what_overflowed_and_counts_it():
    """Through the engine: content that cuts at min_blocks everywhere
    (four times the cuts the tight capacity expects) beside ordinary
    neighbours."""
    from dfs_tpu.ops.cdc_v2 import candidates_np

    dense = next(v for v in range(256) if candidates_np(
        np.full(64, v, np.uint8), SMALL.chunk).any())
    frag = engine()
    rng = np.random.default_rng(44)
    datas = [rng.integers(0, 256, size=5000, dtype=np.uint8).tobytes(),
             bytes([dense]) * 900_000,
             rng.integers(0, 256, size=70000, dtype=np.uint8).tobytes()]
    for data in datas:
        got = frag.chunk(data)
        assert [(c.offset, c.length, c.digest) for c in got] \
            == oracle(np.frombuffer(data, np.uint8))
    st = frag.device_stats()
    assert st["overflow_redos"] == 1 and st["packedRegions"] == 3
    assert st["regions"] == 3          # a redo is no region of its own


# ------------------------------------------------------------ the engine --

def test_a_stream_of_any_size_reaches_the_chain():
    """1 B to the packed limit through ``chunk`` and through the block
    stream; one byte more walks windows of its own. No size is chunked
    by the host oracle any more."""
    frag = engine()
    limit = frag._packer.limit
    assert limit == PACK_BYTES
    rng = np.random.default_rng(45)
    regions = 0
    for n in (1, 63, 5000, limit):
        data = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        want = oracle(np.frombuffer(data, np.uint8))
        stored: dict = {}
        got = frag.manifest_stream(
            [data[i:i + 4000] for i in range(0, n, 4000)], name="f",
            store=lambda dg, b: stored.setdefault(dg, b))
        assert [(c.offset, c.length, c.digest) for c in got.chunks] == want
        assert b"".join(stored[c.digest] for c in got.chunks) == data
        assert [(c.offset, c.length, c.digest)
                for c in frag.chunk(data)] == want
        regions += 2
        st = frag.device_stats()
        assert st["regions"] == st["packedRegions"] == regions
    assert frag.chunk(b"") == [] and frag.device_stats()["regions"] == regions
    st = frag.device_stats()
    assert st["packedStreams"] == regions      # one at a time: each alone
    assert st["packedBytes"] == 2 * (1 + 63 + 5000 + limit)
    assert st["packedCapacityBytes"] == regions * PACK_BYTES
    assert 0 <= st["packWaitS"] < st["packRoundS"]
    # the streams' clock still adds up, the wait for a region in it
    assert sum(st[p] for p in ("inputWaitS", "dispatchS", "collectS",
                               "replyS")) == pytest.approx(st["streamS"],
                                                          abs=1e-4)
    assert 0 < st["deviceWaitS"] <= st["collectS"]
    data = rng.integers(0, 256, size=limit + 1, dtype=np.uint8).tobytes()
    assert [(c.offset, c.length, c.digest) for c in frag.chunk(data)] \
        == oracle(np.frombuffer(data, np.uint8))
    st = frag.device_stats()
    assert st["packedRegions"] == regions and st["regions"] == regions + 1


def test_the_default_shape_is_one_and_a_region_bounds_it():
    """2 MiB of payload by 128 lanes — where the host oracle's cutoff
    stood, so a stream of more than 2 MiB walks windows as it always
    did."""
    from dfs_tpu.fragmenter import cdc_anchored as F

    assert F._PACK_BYTES == 2 << 20
    frag = AnchoredTpuFragmenter.__new__(AnchoredTpuFragmenter)
    frag.params, frag.lane_multiple = AnchoredCdcParams(), 128
    packer = F._Packer(frag, F._PACK_BYTES)
    assert packer.shape == (1 << 19, 128) and packer.limit == 2 << 20
    # a region of 16 KiB (the tests of the walk): a shape of 1 KiB
    assert AnchoredTpuFragmenter(
        SMALL, region_bytes=16384, lane_multiple=8)._packer.shape \
        == (256, 8)
    assert not hasattr(F, "_CPU_CUTOFF")


def test_a_region_that_fails_fails_its_streams_and_the_next_one_runs(
        monkeypatch):
    """The streams a failed region took get the error, each its own
    caller's to raise; nothing of it stays at the head of the queue,
    and the staging buffer is back in the pool."""
    from dfs_tpu.fragmenter import cdc_anchored as F

    frag = engine()
    data = np.random.default_rng(48).integers(0, 256, size=7000,
                                              dtype=np.uint8)
    real, calls = F.packed_dispatch, []

    def once(*a, **kw):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("the device said no")
        return real(*a, **kw)

    monkeypatch.setattr(F, "packed_dispatch", once)
    with pytest.raises(RuntimeError, match="said no"):
        frag.chunk(data)
    assert not frag._packer._queue and not frag._packer._driving
    assert [len(v) for v in frag._buf_pool.values()] == [1]
    assert [(c.offset, c.length, c.digest) for c in frag.chunk(data)] \
        == oracle(data)
    assert [len(v) for v in frag._buf_pool.values()] == [1]
    st = frag.device_stats()
    assert st["packedRegions"] == st["regions"] == 1


def test_a_waiting_stream_books_its_regions_device_time_not_its_sleep(
        monkeypatch):
    """``deviceWaitS`` is the ``block_until_ready`` of the region that
    carries a stream, once a stream of it — not the time a stream slept
    while other regions were staged, run and collected."""
    import jax

    frag = engine()
    data = np.random.default_rng(49).integers(
        0, 256, size=3000, dtype=np.uint8).tobytes()
    frag.chunk(data)                        # the compile, out of the way
    real, slow = jax.block_until_ready, 0.3
    first_in = threading.Event()

    def block(x):
        if isinstance(x, tuple):            # a region's outputs
            first_in.set()
            time.sleep(slow)
        return real(x)

    monkeypatch.setattr(jax, "block_until_ready", block)
    before = frag.device_stats()

    def stream() -> None:
        frag.manifest_stream([data], name="f")

    threads = [threading.Thread(target=stream) for _ in range(3)]
    threads[0].start()
    assert first_in.wait(60)                # A's region is "on the device"
    for t in threads[1:]:                   # B and C gather behind it
        t.start()
    for t in threads:
        t.join(120)
    after = frag.device_stats()
    regions = after["packedRegions"] - before["packedRegions"]
    assert regions == 2 and after["packedStreams"] \
        - before["packedStreams"] == 3
    waited = after["deviceWaitS"] - before["deviceWaitS"]
    # A: 0.3; B and C: 0.3 each of their one region; their sleep behind
    # A's region (~0.3 each more) is ``packWaitS``, not the device's
    assert 3 * slow <= waited < 3 * slow + 0.25
    assert after["packWaitS"] - before["packWaitS"] > slow
    assert waited <= after["collectS"] - before["collectS"]


# ------------------------------------------------------------------ (e) --

def test_a_16_mib_stream_dispatches_as_before(monkeypatch):
    """One window of the 16 MiB bucket, start 0, final, the tight chain
    at 128 lanes — the executable key the four accepted cells warm —
    and nothing packed."""
    from dfs_tpu.fragmenter import cdc_anchored as F

    class Seen(Exception):
        pass

    seen = []

    def spy(words, n, start0, final, params, lane_multiple=128,
            cap_mode="tight"):
        seen.append((int(words.shape[0]), n, start0, final, lane_multiple,
                     cap_mode))
        raise Seen

    monkeypatch.setattr(F, "region_dispatch", spy)
    frag = AnchoredTpuFragmenter()
    n = 16 * 2**20
    with pytest.raises(Seen):
        frag.chunk(np.zeros(n, np.uint8))
    params = AnchoredCdcParams()
    assert seen == [(A.region_buffer_size(n, params) // 4, n, 0, True, 128,
                     "tight")]
    assert A.region_buffer_size(n, params) == 8 + n + params.seg_max + 4 \
        + 4084                          # the 16 MiB bucket, DMA-rounded
    st = frag.device_stats()
    assert st["packedRegions"] == st["regions"] == 0


# ------------------------------------------------------------------ (d) --

@pytest.fixture
def owner():
    """A chip owner whose engine is the device engine at test geometry
    (built by hand: the factory only makes it at the production one)."""
    from dfs_tpu.sidecar.service import SidecarClient, SidecarServer

    srv = SidecarServer(port=0, fragmenter="cdc-anchored")
    srv.fragmenter = engine()
    srv.fragmenter.obs = srv.obs
    srv.start()
    client = SidecarClient(srv.port)
    yield srv, client
    client.close()
    srv.stop()


def test_32_calls_at_once_share_regions_and_each_gets_its_own_table(owner):
    srv, client = owner
    rng = np.random.default_rng(46)
    datas = [rng.integers(0, 256, size=int(n), dtype=np.uint8).tobytes()
             for n in rng.integers(1, 20000, size=32)]
    client.chunk_hash(datas[0])                # the compile, out of the way
    before = client.health()
    got: list = [None] * 32
    gate = threading.Barrier(32)

    def call(i: int) -> None:
        gate.wait()
        resp = client.chunk_hash(datas[i]) if i % 2 \
            else client.chunk_hash_stream([datas[i][:777], datas[i][777:]])
        got[i] = [(c["offset"], c["length"], c["digest"])
                  for c in resp["chunks"]]

    threads = [threading.Thread(target=call, args=(i,)) for i in range(32)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert got == [oracle(np.frombuffer(d, np.uint8)) for d in datas]
    after = client.health()
    dev = {k: after["device"][k] - before["device"][k]
           for k in ("packedStreams", "packedRegions", "regions",
                     "packedBytes", "streams")}
    assert dev["packedStreams"] == 32 and dev["streams"] == 16
    assert dev["packedRegions"] == dev["regions"] < 32
    assert dev["packedBytes"] == sum(map(len, datas))
    spans = {k: after["spans"][k]["count"] - before["spans"][k]["count"]
             for k in ("owner.pack", "owner.dispatch", "owner.collect",
                       "owner.stream")}
    assert spans == {"owner.pack": dev["regions"],
                     "owner.dispatch": dev["regions"],
                     "owner.collect": dev["regions"], "owner.stream": 32}


def test_owner_pack_is_a_root_over_dispatch_and_collect(owner):
    """``owner.pack`` hangs under nothing — a region serves several
    callers' streams — and holds the region's dispatch and collect; the
    caller's ``owner.stream`` stays in the caller's own trace."""
    from dfs_tpu.obs import Observability, current
    from dfs_tpu.config import ObsConfig
    from dfs_tpu.sidecar.service import SidecarFragmenter

    srv, client = owner
    frag = SidecarFragmenter(srv.port)
    node_obs = Observability(ObsConfig(), node_id=1)
    data = np.random.default_rng(47).integers(
        0, 256, size=9000, dtype=np.uint8).tobytes()
    try:
        with node_obs.request_span("upload.fragment"):
            tid, parent = current()
            frag.chunk(data)
        mine = client.trace(traceId=tid)
        assert [s["name"] for s in mine] == ["owner.stream"]
        assert mine[0]["p"] == parent and mine[0]["bytes"] == len(data)
        lo = mine[0]["m0"]
        around = client.trace(sinceMonoNs=lo,
                              untilMonoNs=lo + int(mine[0]["d"] * 1e9))
        pack = [s for s in around if s["name"] == "owner.pack"]
        assert len(pack) == 1 and pack[0]["p"] is None \
            and pack[0]["t"] != tid
        kids = {s["name"] for s in around if s["p"] == pack[0]["s"]}
        assert kids == {"owner.dispatch", "owner.collect"}
    finally:
        frag.close()


# --------------------------------------- the benchmark's by-hand tests --

def test_the_files_cell_tests_of_the_benchmark_still_pass():
    """``benchmarks/tests/test_files_cell.py`` is run by hand; its quick
    half (the generator's sizes, the slice under ``--seed``, the plain
    reference's bytes) runs here, so tier-1 says when it breaks."""
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "benchmarks/tests/test_files_cell.py", "-k", "not rehearsal"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-3000:] + done.stderr[-2000:]
    assert " passed" in done.stdout and "skipped" not in done.stdout
