"""Anchored two-level CDC (v3): oracle properties, device parity, and the
shift-resilience the aligned v2 grid lacks."""

import hashlib

import numpy as np
import pytest

from dfs_tpu.ops.cdc_anchored import (TILE_BYTES, AnchoredCdcParams,
                                      anchor_hash_np, batch_chunks_anchored,
                                      chunk_file_anchored_np,
                                      chunk_spans_anchored_np,
                                      kept_anchors_np, select_segments)
from dfs_tpu.ops.cdc_v2 import AlignedCdcParams

SMALL = AnchoredCdcParams(
    chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                           strip_blocks=64),           # 4 KiB lanes
    seg_min=2048, seg_max=4096, seg_mask=2047)


def corpus(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n,
                                                dtype=np.uint8)


# ---------------------------------------------------------------- oracle --

def test_anchor_hash_window_is_8_bytes():
    # changing byte p-8 must not affect h_p; changing p-7..p must
    data = corpus(64, seed=1)
    h = anchor_hash_np(data, SMALL)
    p = 40
    d2 = data.copy()
    d2[p - 8] ^= 0xFF
    assert anchor_hash_np(d2, SMALL)[p] == h[p]
    d3 = data.copy()
    d3[p - 7] ^= 0xFF
    assert anchor_hash_np(d3, SMALL)[p] != h[p]


def test_kept_anchors_two_per_tile():
    data = corpus(200000, seed=2)
    kept = kept_anchors_np(data, SMALL)
    tiles = kept // TILE_BYTES
    counts = np.bincount(tiles)
    assert counts.max() <= 2
    assert np.all(np.diff(kept) > 0)
    # the rule keeps the FIRST two of each tile: every kept pair must be
    # the two smallest qualifying positions of its tile
    from dfs_tpu.ops.cdc_anchored import anchor_hash_np
    hit = (anchor_hash_np(data, SMALL) & np.uint32(SMALL.seg_mask)) == 0
    pos = np.flatnonzero(hit)
    for t in np.unique(tiles):
        in_tile = pos[pos // TILE_BYTES == t]
        expect = in_tile[:2]
        got = kept[tiles == t]
        assert np.array_equal(got, expect)


def test_segments_respect_bounds():
    data = corpus(300000, seed=3)
    bounds = select_segments(kept_anchors_np(data, SMALL),
                             data.shape[0], SMALL)
    assert bounds[-1] == data.shape[0]
    prev = 0
    for b in bounds[:-1].tolist():
        assert SMALL.seg_min <= b - prev <= SMALL.seg_max
        prev = b
    assert bounds[-1] - prev <= SMALL.seg_max


def test_spans_tile_stream_and_match_hashlib():
    for n in (1, 63, 65, 5000, 100001):
        data = corpus(n, seed=n)
        spans = chunk_spans_anchored_np(data, SMALL)
        assert spans[0][0] == 0
        assert sum(ln for _, ln in spans) == n
        for (o1, l1), (o2, _) in zip(spans, spans[1:]):
            assert o1 + l1 == o2
    chunks = chunk_file_anchored_np(corpus(50000, seed=9), SMALL)
    data = corpus(50000, seed=9)
    for o, ln, dg in chunks:
        assert dg == hashlib.sha256(data[o:o + ln].tobytes()).hexdigest()


def test_shift_resilience_vs_aligned():
    """The defining property: after an unaligned insertion, most chunks
    must still dedup (the v2 aligned grid loses everything downstream)."""
    base = corpus(300000, seed=4)
    edited = np.concatenate(
        [base[:50001], corpus(77, seed=5), base[50001:]])
    a = {dg for _, _, dg in chunk_file_anchored_np(base, SMALL)}
    b = [(o, ln, dg) for o, ln, dg in chunk_file_anchored_np(edited, SMALL)]
    shared = sum(ln for _, ln, dg in b if dg in a)
    assert shared / edited.shape[0] > 0.85, \
        f"only {shared / edited.shape[0]:.0%} of bytes deduped after insert"


# ---------------------------------------------------------- device parity --

@pytest.mark.parametrize("n", [1, 63, 4096, 5000, 100001, 300000])
def test_device_matches_oracle(n):
    data = corpus(n, seed=n + 100)
    got = batch_chunks_anchored(data, SMALL, lane_multiple=8)
    want = chunk_file_anchored_np(data, SMALL)
    assert got == want


def test_device_low_entropy():
    # all-zeros: anchor hash is constant; whatever it decides, device and
    # oracle must agree, max-size forcing must bound segments
    data = np.zeros((100000,), dtype=np.uint8)
    got = batch_chunks_anchored(data, SMALL, lane_multiple=8)
    want = chunk_file_anchored_np(data, SMALL)
    assert got == want
    # repeating pattern (anchor-dense)
    data = np.tile(corpus(256, seed=6), 400)
    assert batch_chunks_anchored(data, SMALL, lane_multiple=8) == \
        chunk_file_anchored_np(data, SMALL)


def test_device_tail_digests():
    # segment tails end in partial blocks — the device finalize path must
    # agree with hashlib for every chunk, including tails >= 56 bytes mod 64
    for seed in range(3):
        data = corpus(37777 + seed * 1111, seed=seed + 20)
        for o, ln, dg in batch_chunks_anchored(data, SMALL, lane_multiple=8):
            assert dg == hashlib.sha256(
                data[o:o + ln].tobytes()).hexdigest()


def _dense_byte() -> int:
    """A uniform byte value whose 64-byte block is a Gear candidate under
    SMALL.chunk — filling a stream with it forces a cut every min_blocks,
    ~avg/min times the provisioned expectation."""
    from dfs_tpu.ops.cdc_v2 import candidates_np

    return next(v for v in range(256)
                if candidates_np(np.full(64, v, np.uint8),
                                 SMALL.chunk).any())


def test_tight_capacity_overflow_redispatches(monkeypatch):
    """Cut capacity is provisioned for ~1.25x the EXPECTED count
    (cap_mode='tight'); content cutting at min_blocks everywhere must be
    detected (the device count is exact) and redone at the worst-case
    bound — byte-identical to the oracle, never silently truncated."""
    import dfs_tpu.ops.cdc_anchored as A

    data = np.full(100000, _dense_byte(), dtype=np.uint8)
    calls: list[str] = []
    orig = A.region_dispatch

    def spy(*a, **kw):
        calls.append(kw.get("cap_mode", "tight"))
        return orig(*a, **kw)

    monkeypatch.setattr(A, "region_dispatch", spy)
    got = batch_chunks_anchored(data, SMALL, lane_multiple=8)
    assert "full" in calls, "dense content never hit the retry path"
    assert got == chunk_file_anchored_np(data, SMALL)


def test_tight_capacity_overflow_in_region_walk(monkeypatch):
    """Same retry through the pipelined multi-window walk (the fragmenter
    collect path), where the device carry chained past the overflowing
    window must stay valid."""
    import dfs_tpu.fragmenter.cdc_anchored as F

    data = np.full(200000, _dense_byte(), dtype=np.uint8).tobytes()
    calls: list[str] = []
    orig = F.region_chunks

    def spy(*a, **kw):
        calls.append(kw.get("cap_mode", "tight"))
        return orig(*a, **kw)

    monkeypatch.setattr(F, "region_chunks", spy)
    # 64 KiB windows: at SMALL's geometry the dense cut count per window
    # (stride/min_bytes) clears the tight bound; 16 KiB windows would not
    got = anchored_frag(region_bytes=65536).chunk(data)
    assert "full" in calls, "walk never hit the collect-retry path"
    arr = np.frombuffer(data, np.uint8)
    assert [(c.offset, c.length, c.digest) for c in got] == \
        chunk_file_anchored_np(arr, SMALL)


# ----------------------------------------------------------- fragmenters --

def anchored_frag(**kw):
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredTpuFragmenter

    kw.setdefault("region_bytes", 16384)
    return AnchoredTpuFragmenter(SMALL, cpu_cutoff=0, lane_multiple=8, **kw)


def test_fragmenter_matches_oracle_and_cpu():
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter

    data = corpus(100000, seed=40).tobytes()
    tpu = anchored_frag().chunk(data)
    cpu = AnchoredCpuFragmenter(SMALL).chunk(data)
    assert tpu == cpu
    assert sum(c.length for c in tpu) == len(data)


def test_region_walk_transparent():
    # region_bytes small forces many carries; result must equal one-shot
    data = corpus(120000, seed=41).tobytes()
    big = anchored_frag(region_bytes=1 << 30)
    small = anchored_frag()
    assert big.chunk(data) == small.chunk(data)


def test_three_way_region_streaming_equality():
    """Large-region one-shot == tiny-region walk == streaming, and all
    equal the NumPy whole-stream oracle — the transparency property the
    region/carry design exists to guarantee."""
    arr = corpus(200000, seed=43)
    data = arr.tobytes()
    want = [(o, ln, dg) for o, ln, dg in chunk_file_anchored_np(arr, SMALL)]

    one_shot = anchored_frag(region_bytes=1 << 30).chunk(data)
    tiny_frag = anchored_frag()            # 16 KiB regions: many carries
    tiny = tiny_frag.chunk(data)
    blocks = [data[i:i + 7333] for i in range(0, len(data), 7333)]
    streamed = tiny_frag.manifest_stream(blocks, name="f").chunks

    for got in (one_shot, tiny, list(streamed)):
        assert [(c.offset, c.length, c.digest) for c in got] == want


def test_streaming_matches_chunk_any_blocking():
    data = corpus(90000, seed=42).tobytes()
    frag = anchored_frag()
    want = frag.manifest(data, name="f")
    for bs in (1000, 8192, 30000):
        stored = {}
        blocks = [data[i:i + bs] for i in range(0, len(data), bs)]
        got = frag.manifest_stream(
            blocks, name="f", store=lambda dg, b: stored.setdefault(dg, b))
        assert got.chunks == want.chunks
        assert got.file_id == want.file_id
        assert b"".join(stored[c.digest] for c in got.chunks) == data


def test_streaming_block_lands_exactly_on_window_end():
    """A block boundary that lands exactly on a window end mid-stream must
    NOT finalize the walk early (the tail segment carries on): regression
    for inferring `final` from end == bytes-received-so-far."""
    frag = anchored_frag()             # region_bytes=16384
    data = corpus(50000, seed=44).tobytes()
    # first block = exactly one region; the dispatcher sees n_known ==
    # base + region_bytes with more data still to come
    blocks = [data[:16384], data[16384:]]
    got = frag.manifest_stream(blocks, name="f").chunks
    want = anchored_frag().chunk(data)
    assert list(got) == want


def test_factory_anchored_kinds():
    from dfs_tpu.fragmenter.base import get_fragmenter

    assert get_fragmenter("cdc-anchored").name == "cdc-anchored"
    assert get_fragmenter("cdc-anchored-tpu").name == "cdc-anchored-tpu"


def test_factory_auto_resolves_by_device(monkeypatch):
    """'auto' (the serve default) resolves ONCE, at construction, to the
    engine itself: the anchored TPU pipeline iff the machine has a TPU
    platform, the anchored CPU engine elsewhere — no wrapper left to
    flip engines under a running node."""
    import dfs_tpu.fragmenter.base as base
    import dfs_tpu.utils.device as device
    from dfs_tpu.fragmenter.cdc_anchored import (AnchoredCpuFragmenter,
                                                 AnchoredTpuFragmenter)

    monkeypatch.setattr(device, "wants_tpu", lambda: True)
    assert type(base.get_fragmenter("auto")) is AnchoredTpuFragmenter
    monkeypatch.setattr(device, "wants_tpu", lambda: False)
    assert type(base.get_fragmenter("auto")) is AnchoredCpuFragmenter


def test_factory_auto_honors_chunk_params(monkeypatch):
    """Operator chunk sizing flows through auto into the nested grid
    (ADVICE round 1: the anchored branch silently dropped CDCParams)."""
    import dfs_tpu.fragmenter.base as base
    import dfs_tpu.utils.device as device
    from dfs_tpu.config import CDCParams

    monkeypatch.setattr(device, "wants_tpu", lambda: False)
    f = base.get_fragmenter(
        "auto", cdc_params=CDCParams(min_size=1024, avg_size=4096,
                                     max_size=32768))
    assert f.params.chunk.min_blocks == 16
    assert f.params.chunk.avg_blocks == 64
    assert f.params.chunk.max_blocks == 512
    assert f.params.seg_max == f.params.chunk.strip_blocks * 64


def test_cdc_tpu_v1_deprecation_warning():
    import warnings

    from dfs_tpu.fragmenter.base import get_fragmenter

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        get_fragmenter("cdc-tpu")
    assert any(issubclass(x.category, DeprecationWarning) for x in w)


# ---------------------------------------------------------------------------
# Pallas repack kernel (ops.repack) vs the XLA fallback
# ---------------------------------------------------------------------------

def test_repack_pallas_matches_xla_fallback():
    """The DMA-gather + in-register-rotate kernel must agree with
    vmap(dynamic_slice)+funnel bit-for-bit, including the clamp branch
    (a segment start within one DMA window of the buffer end) and every
    byte phase. Runs through the Pallas interpreter on CPU; on real TPU
    the same kernel is exercised end-to-end by bench.py's hashlib
    asserts."""
    import jax
    import numpy as np

    from dfs_tpu.ops.repack import (_window_rows, repack_lanes,
                                    repack_lanes_xla)

    lane_words = 1024                      # 8 rows per lane
    m_total = 8 * 1024                     # multiple of the 1024-word tiling
    assert m_total // 128 >= _window_rows(lane_words)
    rng = np.random.default_rng(7)
    words = jax.device_put(
        rng.integers(0, 2**32, size=m_total, dtype=np.uint32))

    hi = m_total - lane_words - 1          # caller invariant bound
    offs = [0, 1, 5, 1023, 1024, 1025, hi, hi - 1, hi - 1023]
    offs += [int(x) for x in rng.integers(0, hi + 1, size=7)]
    w_off = np.asarray(offs, dtype=np.int32)
    sh8 = np.asarray([(i % 4) * 8 for i in range(len(offs))], np.uint32)

    want = np.asarray(repack_lanes_xla(words, jax.device_put(w_off),
                                       jax.device_put(sh8), lane_words))
    got = np.asarray(repack_lanes(words, jax.device_put(w_off),
                                  jax.device_put(sh8), lane_words,
                                  interpret=True))
    assert np.array_equal(got, want)


def test_region_buffer_size_is_dma_tiled():
    """The staging buffer must land on the repack kernel's 4096-byte DMA
    tiling, and region_dispatch's floored m_words recovery must keep the
    chunk output identical to the oracle (covered by the oracle-parity
    tests above running through region_chunks)."""
    from dfs_tpu.ops.cdc_anchored import (AnchoredCdcParams,
                                          region_buffer_size)

    p = AnchoredCdcParams()
    for n in (1, 4096, 64 * 2**20, 64 * 2**20 - 5):
        assert region_buffer_size(n, p) % 4096 == 0


def test_tight_segment_lane_overflow_redispatches(monkeypatch):
    """Segment LANES are provisioned at ~1.1x the expected count
    (cap_mode='tight', _tight_segment_lanes); a region with more
    segments than that must trip the exact on-device bound count and
    redo at the worst-case bound — byte-identical to the oracle, never
    a silently truncated chunk table."""
    import dfs_tpu.ops.cdc_anchored as A

    # force the tight provisioning far below the real segment count so
    # ORDINARY content overflows the lanes (the select scan fills every
    # slot); the full-bound redispatch must recover exactly
    monkeypatch.setattr(A, "_tight_segment_lanes",
                        lambda params, m_words, lane_multiple: 8)
    A.make_chain_fn.cache_clear()
    try:
        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, size=100000, dtype=np.uint8)
        calls: list[str] = []
        orig = A.region_dispatch

        def spy(*a, **kw):
            calls.append(kw.get("cap_mode", "tight"))
            return orig(*a, **kw)

        monkeypatch.setattr(A, "region_dispatch", spy)
        got = batch_chunks_anchored(data, SMALL, lane_multiple=8)
        assert "full" in calls, "lane overflow never hit the retry path"
        assert got == chunk_file_anchored_np(data, SMALL)
    finally:
        A.make_chain_fn.cache_clear()


def test_tight_segment_lane_overflow_in_pipelined_walk(monkeypatch):
    """Lane overflow through the MULTI-WINDOW pipelined walk: window k's
    lane tables truncate, but its device carry (from the full-bound
    select scan) stays exact, so the windows already dispatched on that
    carry remain valid and only window k redoes at 'full'. The walk must
    produce the oracle chunk table with no discontinuity."""
    import dfs_tpu.fragmenter.cdc_anchored as F
    import dfs_tpu.ops.cdc_anchored as A

    monkeypatch.setattr(A, "_tight_segment_lanes",
                        lambda params, m_words, lane_multiple: 8)
    A.make_chain_fn.cache_clear()
    try:
        rng = np.random.default_rng(5)
        data = rng.integers(0, 256, size=200000, dtype=np.uint8).tobytes()
        calls: list[str] = []
        orig = F.region_chunks

        def spy(*a, **kw):
            calls.append(kw.get("cap_mode", "tight"))
            return orig(*a, **kw)

        monkeypatch.setattr(F, "region_chunks", spy)
        got = anchored_frag(region_bytes=65536, max_inflight=3).chunk(data)
        assert "full" in calls, "walk never hit the lane-overflow retry"
        arr = np.frombuffer(data, np.uint8)
        assert [(c.offset, c.length, c.digest) for c in got] == \
            chunk_file_anchored_np(arr, SMALL)
    finally:
        A.make_chain_fn.cache_clear()


def _random_two_plane_tiles(rng, m_tiles, density=2):
    """Random pass-A-shaped [2, m_tiles] tile planes: ~1/density tiles
    hold a first anchor, about half of those also a second (strictly
    larger, same tile) — mirrors make_anchor_fn's output invariants."""
    tiles = np.full((2, m_tiles), 2**30, np.int32)
    k = max(1, m_tiles // density)
    idx = rng.choice(m_tiles, size=k, replace=False)
    off1 = rng.integers(0, TILE_BYTES - 1, size=k)   # <= TILE_BYTES - 2
    tiles[0, idx] = (idx * TILE_BYTES + off1).astype(np.int32)
    has2 = rng.random(k) < 0.5
    off2 = off1 + 1 + rng.integers(0, TILE_BYTES - 1 - off1)
    tiles[1, idx[has2]] = (idx[has2] * TILE_BYTES
                           + off2[has2]).astype(np.int32)
    return tiles


def test_pallas_select_matches_xla_scan():
    """The on-core Pallas selection walk (ops.select_pallas) must agree
    with the XLA scan bit-for-bit: random anchor-tile patterns, final
    and non-final regions, zero and carried start0. Interpret mode on
    CPU; on real TPU the same kernel is exercised end-to-end by
    bench.py's hashlib gates (make_chain_fn picks it there)."""
    import jax.numpy as jnp

    from dfs_tpu.ops.select_pallas import make_select_fn_pallas

    rng = np.random.default_rng(11)
    params = SMALL
    for trial in range(2):
        n = int(rng.integers(20000, 120000))
        m_tiles = 1 << (-(-n // TILE_BYTES) - 1).bit_length()
        cap = m_tiles * TILE_BYTES // params.seg_min + 1
        tiles = _random_two_plane_tiles(rng, m_tiles)
        import dfs_tpu.ops.cdc_anchored as A
        for final in (True, False):
            for start0 in (0, 1234):
                ref = A.make_select_fn(params, m_tiles, cap)(
                    jnp.asarray(tiles), jnp.int32(start0), jnp.int32(n),
                    jnp.bool_(final))
                got = make_select_fn_pallas(
                    params, m_tiles, cap, interpret=True)(
                    jnp.asarray(tiles), jnp.int32(start0), jnp.int32(n),
                    jnp.bool_(final))
                np.testing.assert_array_equal(
                    np.asarray(ref), np.asarray(got))


def test_pallas_select_large_region_block_addressing():
    """Production-shaped geometry (96K/128K segments, 4 MiB region):
    t0 crosses the 1024-entry block boundary many times, so the kernel's
    8-row-aligned dynamic block read and (row + r0)*128 + col global
    index arithmetic are actually exercised (the small-n test's windows
    all start in block zero)."""
    import jax.numpy as jnp

    import dfs_tpu.ops.cdc_anchored as A
    from dfs_tpu.ops.select_pallas import make_select_fn_pallas

    params = AnchoredCdcParams()        # production segment geometry
    n = 4 * 2**20
    m_tiles = n // TILE_BYTES           # 8192 tiles -> t0 up to ~8192
    cap = n // params.seg_min + 1
    rng = np.random.default_rng(12)
    tiles = _random_two_plane_tiles(rng, m_tiles, density=16)
    for final in (True, False):
        ref = A.make_select_fn(params, m_tiles, cap)(
            jnp.asarray(tiles), jnp.int32(0), jnp.int32(n),
            jnp.bool_(final))
        got = make_select_fn_pallas(params, m_tiles, cap,
                                    interpret=True)(
            jnp.asarray(tiles), jnp.int32(0), jnp.int32(n),
            jnp.bool_(final))
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
