"""Anchored two-level CDC (v3) — shift-resilient dedup at TPU speed.

The block-grid chunk cuts of ops.cdc_v2 are quantized to 64 bytes; on a
grid anchored at absolute stream offset 0 (the aligned v2 chunker,
retired at PR 46) an insertion whose length is not a multiple of 64
shifts all downstream content off the grid and kills dedup (it measured
1.16x against 3.91x for byte-granular rolling CDC on a versioned
corpus). v3 re-anchors the grid with a classic two-level scheme:

1. **Byte-granular anchors.** A cheap 8-byte windowed hash is evaluated at
   EVERY byte position (elementwise over the four byte phases of the LE
   word array — no rolling state, ~1 ms per 64 MiB on v5e):

       b_p = LE32(bytes[p-3 .. p])     a_p = LE32(bytes[p-7 .. p-4])
       h_p = fmix32(fmix32(b_p) + a_p)         (bytes before 0 read as 0)
       anchor(p)  iff  h_p & seg_mask == 0

   Anchors are quantized: only the first TWO anchors inside each
   absolute ``TILE_BYTES`` tile survive (bounds the device tile table to
   two i32 per tile; the drop is deterministic given content +
   alignment). Two beats one measurably: a tile holding >1 true anchor
   flips its kept set less often under content shift when the second
   survives too — probed at 95.6% of byte-granular dedup vs 92.4% for
   first-only on the same corpus (TILE_PROBE_r04.json), where halving
   the tile to 256 B bought 96.8% but cost ~48% of chain throughput.

2. **Segment selection** (metadata-sized; the NumPy oracle, the XLA
   scan, the Pallas walk and the C++ engine are held to each other bit
   for bit): classical min / average / max cutting at the segment level.
   An anchor is *strong* when its hash clears ``strong_bits`` more bits
   (2^-16 a byte, mean gap 64 KiB); pass A keeps the FIRST strong
   position of each tile as a third plane, tested on the hash alone. A
   segment ends at the FIRST strong anchor p with
   ``start + strong_min <= p + 1 <= start + seg_max``; if the window
   holds none, at the LAST kept anchor within
   ``[start + seg_min, start + seg_max]``; else forced at
   ``start + seg_max``. Two streams whose cuts differ agree again at the
   first strong anchor ``strong_min`` or more after both their cuts —
   within one or two strong gaps — and stay agreed, since from a common
   cut the walk is a function of content. Until PR 37 the rule was its
   fallback alone ("the last kept anchor in the window: maximizing
   segment length keeps device-lane utilization high", 96 % of a lane):
   every cut then depended on the one before it with no pull towards
   agreement, a shifted stream took ~1.2 MiB to re-synchronise, and a
   snapshot in which 2 % of the files changed stored 40 % of its bytes
   again. The lanes are now ~62 % full (mean segment ~80 KiB of 128);
   that is the price, paid in device time nobody waits for.

3. **Within a segment, the block-grid math of ops.cdc_v2 runs with its
   64-byte grid anchored at the segment start**: the device repacks each
   segment into its own lane (vmap'd dynamic_slice + per-lane byte funnel
   shift, measured ~0.5 ms per 64 MiB), then candidates -> selection ->
   strip-scan SHA-256. A segment's chunking depends only on
   the segment's bytes, and segment starts move WITH content — so an
   insertion re-syncs at the next anchor and dedup survives.

Segment tails are rarely 64-byte multiples, so each lane's final chunk
ends in a partial block; its digest is finalized on device from the chain
state before the tail block plus one or two patched FIPS blocks (the
strip scan saw the tail zero-padded). Everything returning to the host is
metadata-sized.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import math

import numpy as np

from dfs_tpu.ops.cdc_v2 import (BLOCK, AlignedCdcParams, candidates_np,
                                cut_capacity, digests_to_hex,
                                select_cuts_blocks)
from dfs_tpu.utils.hashing import next_pow2

_PRIME = np.uint32(0x9E3779B1)
_M1 = np.uint32(0x7FEB352D)
_M2 = np.uint32(0x846CA68B)

TILE_BYTES = 512           # anchor quantization tile (absolute offsets).
# Small on purpose: the kept anchor of a tile flips when a tile holds >1
# true anchor and content shifts, so P(flip) ~ tile/mean_anchor_gap must
# stay small or quantization itself destroys shift resilience (measured
# 55% dedup-after-insert at tile=2048 with dense anchors vs >90% here).
_NO_ANCHOR = np.int64(2**62)


@dataclasses.dataclass(frozen=True)
class AnchoredCdcParams:
    """Two-level parameters: byte-granular segment anchoring over the
    aligned chunk grid.

    ``seg_mask`` fires with probability 2^-13 per byte (mean anchor gap
    8 KiB); a *strong* anchor clears ``strong_bits`` more bits of the
    same hash (2^-16, mean gap 64 KiB). A segment ends at the first
    strong anchor ``strong_min`` or more after its start (None = a
    quarter of ``seg_max``, tile-aligned: 32 KiB), else at the last kept
    anchor in ``[seg_min, seg_max]``, else at ``seg_max`` — see the
    module docstring. The pair 2^-16 / 32 KiB is where the chunk count
    stays within +3 % of the last-anchor rule's while a shifted snapshot
    is re-found (ISSUE 37's table). ``seg_max`` must equal
    ``chunk.strip_blocks * 64`` — a segment is one device lane.
    """
    chunk: AlignedCdcParams = dataclasses.field(
        default_factory=AlignedCdcParams)
    seg_min: int = 96 * 1024
    seg_max: int = 128 * 1024
    seg_mask: int = 8191
    seed: int = 0x51ED270B
    strong_min: int | None = None
    strong_bits: int = 3

    def __post_init__(self):
        if self.seg_max != self.chunk.strip_blocks * BLOCK:
            raise ValueError("seg_max must equal one lane "
                             f"({self.chunk.strip_blocks * BLOCK} B)")
        if not 0 < self.seg_min <= self.seg_max:
            raise ValueError("need 0 < seg_min <= seg_max")
        if self.seg_mask & (self.seg_mask + 1):
            raise ValueError("seg_mask must be 2^k - 1")
        if TILE_BYTES > self.seg_min:
            raise ValueError("anchor tile must not exceed seg_min")
        if self.seg_min % TILE_BYTES or self.seg_max % TILE_BYTES:
            raise ValueError("seg_min/seg_max must be multiples of "
                             f"{TILE_BYTES} (device selection window)")
        if self.strong_min is None:
            object.__setattr__(self, "strong_min", max(
                TILE_BYTES, self.seg_max // 4 // TILE_BYTES * TILE_BYTES))
        if not TILE_BYTES <= self.strong_min <= self.seg_min \
                or self.strong_min % TILE_BYTES:
            raise ValueError(f"strong_min must be a multiple of {TILE_BYTES}"
                             " within [tile, seg_min]")
        if not 1 <= self.strong_bits <= 8 \
                or (self.seg_mask + 1) << self.strong_bits > 2**32:
            raise ValueError("need 1 <= strong_bits <= 8 within 32 bits")

    @property
    def strong_mask(self) -> int:
        """Hash mask of a strong anchor: ``seg_mask`` plus
        ``strong_bits`` more low bits."""
        return ((self.seg_mask + 1) << self.strong_bits) - 1


# ---------------------------------------------------------------------------
# anchor hash — NumPy oracle (vectorized; bit-identical to the device pass)
# ---------------------------------------------------------------------------

def _fmix32_np(x: np.ndarray) -> np.ndarray:
    x = np.asarray(x, dtype=np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = (x * _M1).astype(np.uint32)
    x = x ^ (x >> np.uint32(15))
    x = (x * _M2).astype(np.uint32)
    return x ^ (x >> np.uint32(16))


def anchor_hash_np(data: np.ndarray, params: AnchoredCdcParams) -> np.ndarray:
    """h_p for every byte position p of ``data`` [n] u8 (bytes before the
    stream read as zero)."""
    n = data.shape[0]
    padded = np.zeros((n + 8,), dtype=np.uint8)
    padded[8:] = data
    le = padded.astype(np.uint32)
    # b_p = LE32(bytes[p-3..p]) built at padded index p+8
    b = (le[5:n + 5] | (le[6:n + 6] << np.uint32(8))
         | (le[7:n + 7] << np.uint32(16)) | (le[8:n + 8] << np.uint32(24)))
    a = (le[1:n + 1] | (le[2:n + 2] << np.uint32(8))
         | (le[3:n + 3] << np.uint32(16)) | (le[4:n + 4] << np.uint32(24)))
    return _fmix32_np(_fmix32_np(b) + np.uint32(params.seed) + a)


def _first_per_tile(pos: np.ndarray, keep: int) -> np.ndarray:
    """Keep the first ``keep`` entries of each TILE_BYTES tile from
    sorted byte positions — the single definition of the quantization
    rule (two for the kept anchors, one for the strong plane)."""
    if pos.size == 0:
        return pos.astype(np.int64)
    tile = pos // TILE_BYTES
    idx = np.arange(pos.size)
    opens = np.ones(pos.shape, dtype=bool)       # first entry of its tile
    opens[1:] = tile[1:] != tile[:-1]
    rank = idx - np.maximum.accumulate(np.where(opens, idx, 0))
    return pos[rank < keep].astype(np.int64)


def anchor_positions_np(h: np.ndarray, params: AnchoredCdcParams
                        ) -> tuple[np.ndarray, np.ndarray]:
    """(kept, strong) sorted positions from the per-byte anchor hashes
    ``h``: the first TWO anchors of each tile, and the first STRONG
    position of each tile — the latter tested on the hash alone, not on
    whether that position survived the two-a-tile quantisation, so a
    strong anchor is a function of content and tile alignment only (the
    oracle of the device pass-A output's three planes)."""
    kept = _first_per_tile(np.flatnonzero(
        (h & np.uint32(params.seg_mask)) == 0), 2)
    strong = _first_per_tile(np.flatnonzero(
        (h & np.uint32(params.strong_mask)) == 0), 1)
    return kept, strong


def anchors_np(data: np.ndarray, params: AnchoredCdcParams
               ) -> tuple[np.ndarray, np.ndarray]:
    """(kept, strong) anchor positions of a whole stream
    (:func:`anchor_positions_np` over :func:`anchor_hash_np`)."""
    if data.shape[0] == 0:
        z = np.zeros((0,), dtype=np.int64)
        return z, z
    return anchor_positions_np(anchor_hash_np(data, params), params)


def anchor_planes_np(kept: np.ndarray, strong: np.ndarray,
                     m_tiles: int) -> np.ndarray:
    """The [3, m_tiles] i32 table device pass A returns for these
    positions (2^30 = none): what the parity checks hold it to."""
    planes = np.full((3, m_tiles), 2**30, np.int32)
    if kept.size:
        tile = kept // TILE_BYTES
        second = np.zeros(kept.shape, dtype=bool)
        second[1:] = tile[1:] == tile[:-1]
        planes[0, tile[~second]] = kept[~second]
        planes[1, tile[second]] = kept[second]
    planes[2, strong // TILE_BYTES] = strong
    return planes


def planes_positions(tiles: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Invert :func:`anchor_planes_np`: a pass-A [3, m_tiles] table to
    the sorted (kept, strong) positions :func:`select_segments` takes."""
    kept = tiles[:2][tiles[:2] < 2**30].astype(np.int64)
    kept.sort()
    strong = tiles[2][tiles[2] < 2**30].astype(np.int64)
    return kept, strong


# ---------------------------------------------------------------------------
# segment selection — ONE implementation, used by oracle and production
# ---------------------------------------------------------------------------

# how a segment came to end: at the first strong anchor of its window,
# at the last kept anchor of [seg_min, seg_max], forced at seg_max, or
# with the stream
CUT_STRONG, CUT_WINDOW, CUT_FORCED, CUT_END = 0, 1, 2, 3


def select_segments_kinds(anchors: np.ndarray, strong: np.ndarray, n: int,
                          params: AnchoredCdcParams, start0: int = 0,
                          final: bool = True
                          ) -> tuple[np.ndarray, np.ndarray]:
    """Exclusive segment boundaries over a stream of ``n`` bytes, and how
    each came about (``CUT_*``); when ``final``, the last element == n.
    Boundary after byte p means segment ends at p (boundary value p+1).
    Rule: the FIRST strong anchor with start+strong_min <= p+1 <=
    start+seg_max; none -> the LAST kept anchor with start+seg_min <=
    p+1 <= start+seg_max; none -> forced at start+seg_max.
    ``start0``/``final=False`` give the region-walk semantics (start at
    a carry position; withhold the unfinished tail segment so it carries
    into the next region)."""
    bounds: list[int] = []
    kinds: list[int] = []
    start = int(start0)
    ap = np.asarray(anchors, dtype=np.int64)
    sp = np.asarray(strong, dtype=np.int64)
    while n - start > params.seg_max:
        lo = start + params.seg_min            # min admissible boundary
        hi = start + params.seg_max            # forced boundary
        # strong p with start+strong_min <= p+1 <= hi
        j = int(np.searchsorted(sp, start + params.strong_min - 1,
                                side="left"))
        if j < sp.shape[0] and sp[j] <= hi - 1:
            b, kind = int(sp[j]) + 1, CUT_STRONG
        else:
            # anchors p with lo <= p+1 <= hi  <=>  lo-1 <= p <= hi-1
            k = int(np.searchsorted(ap, hi - 1, side="right")) - 1
            if k >= 0 and ap[k] >= lo - 1:
                b, kind = int(ap[k]) + 1, CUT_WINDOW
            else:
                b, kind = hi, CUT_FORCED
        bounds.append(b)
        kinds.append(kind)
        start = b
    if final:
        bounds.append(n)
        kinds.append(CUT_END)
    return (np.asarray(bounds, dtype=np.int64),
            np.asarray(kinds, dtype=np.int64))


def select_segments(anchors: np.ndarray, strong: np.ndarray, n: int,
                    params: AnchoredCdcParams, start0: int = 0,
                    final: bool = True) -> np.ndarray:
    """The boundaries of :func:`select_segments_kinds` alone."""
    return select_segments_kinds(anchors, strong, n, params, start0,
                                 final)[0]


def cut_counts(kinds: np.ndarray) -> tuple[int, int, int, int]:
    """(segments, strong_cuts, window_cuts, forced_cuts) of a walk's
    ``kinds`` — what the device chain and the C++ engine count."""
    by = np.bincount(np.asarray(kinds, dtype=np.int64), minlength=4)
    return (int(by.sum()), int(by[CUT_STRONG]), int(by[CUT_WINDOW]),
            int(by[CUT_FORCED]))


# ---------------------------------------------------------------------------
# full oracle: anchors -> segments -> aligned chunking per segment
# ---------------------------------------------------------------------------

def _segment_spans_np(data: np.ndarray, start: int, b: int,
                      cp: AlignedCdcParams) -> list[tuple[int, int]]:
    """Aligned chunking of segment [start, b), grid re-anchored at start."""
    seg = data[start:b]
    ln = seg.shape[0]
    nb = -(-ln // BLOCK)
    pos = np.flatnonzero(candidates_np(seg, cp))
    cuts = select_cuts_blocks(pos, nb, cp)
    spans: list[tuple[int, int]] = []
    prev = 0
    for c in cuts.tolist():
        end = min(c * BLOCK, ln)
        spans.append((start + prev * BLOCK, end - prev * BLOCK))
        prev = c
    return spans


def chunk_spans_anchored_np(data: np.ndarray, params: AnchoredCdcParams
                            ) -> list[tuple[int, int]]:
    """[(offset, length)] chunks; segment grid re-anchored per segment."""
    n = data.shape[0]
    if n == 0:
        return []
    bounds = select_segments(*anchors_np(data, params), n, params)
    spans: list[tuple[int, int]] = []
    start = 0
    for b in bounds.tolist():
        spans.extend(_segment_spans_np(data, start, b, params.chunk))
        start = b
    return spans


def region_spans_np(data: np.ndarray, lookback: np.ndarray, start0: int,
                    final: bool, params: AnchoredCdcParams
                    ) -> tuple[list[tuple[int, int]], int]:
    """Host oracle of :func:`region_chunks`'s span semantics (no digests):
    region-local (offset, length) spans + consumed bound. Same contract:
    ``lookback`` = 8 stream bytes before the region (zeros at stream
    start), the region base must be TILE_BYTES-aligned in the stream,
    and when ``final`` is False the unfinished tail segment is withheld.
    Used as the streaming-walk fallback when the native library is
    unavailable (dfs_tpu/native/cdc_core.cpp:dfs_anchored_spans_region is
    the fast path)."""
    n = int(data.shape[0])
    if n == 0:
        return [], int(start0)
    ext = np.concatenate([np.asarray(lookback, np.uint8).reshape(8),
                          np.asarray(data)])
    anchors, strong = anchor_positions_np(      # region-local
        anchor_hash_np(ext, params)[8:], params)
    bounds = select_segments(anchors, strong, n, params,
                             start0=int(start0), final=bool(final))
    spans: list[tuple[int, int]] = []
    start = int(start0)
    for b in bounds.tolist():
        spans.extend(_segment_spans_np(data, start, b, params.chunk))
        start = b
    return spans, start


def chunk_file_anchored_np(data: np.ndarray, params: AnchoredCdcParams
                           ) -> list[tuple[int, int, str]]:
    mv = memoryview(np.ascontiguousarray(data))
    return [(o, ln, hashlib.sha256(mv[o:o + ln]).hexdigest())
            for o, ln in chunk_spans_anchored_np(data, params)]


# ---------------------------------------------------------------------------
# device pass A: anchor tile array
# ---------------------------------------------------------------------------

@functools.cache
def make_anchor_fn(params: AnchoredCdcParams, m_words: int):
    """Compiled: words_le [>= 2 + m_words] u32 (extra trailing words —
    the region buffer's lane slack — are ignored) -> per TILE_BYTES tile
    the first two anchor byte positions and the first STRONG position
    ([3, m_words*4/TILE_BYTES] i32; row 0 < row 1 where present, row 2
    tested on the hash alone, 2^30 = none). The leading 2 words
    are the 8 stream bytes BEFORE the region (zeros at true stream
    start), so anchor hashes near the region start see real history and
    batching is transparent; positions are region-local."""
    import jax
    import jax.numpy as jnp

    tile_w = TILE_BYTES // 4
    seed = jnp.uint32(params.seed)
    mask = jnp.uint32(params.seg_mask)
    strong_mask = jnp.uint32(params.strong_mask)

    def fmix(x):
        x = x ^ (x >> jnp.uint32(16))
        x = x * jnp.uint32(_M1)
        x = x ^ (x >> jnp.uint32(15))
        x = x * jnp.uint32(_M2)
        return x ^ (x >> jnp.uint32(16))

    @jax.jit
    def run(words_full):
        # accept the whole region buffer and slice inside the jit: a
        # host-side words[:2+m] slice is a separate dispatch that
        # materializes a full device copy (~1 ms per 64 MiB); in here XLA
        # fuses the slice into the elementwise reads
        words = jax.lax.slice_in_dim(words_full, 0, 2 + m_words)
        # b over region words -1..m-1 (one extra so a = b shifted one word)
        v, vp = words[1:], words[:-1]
        # running two smallest hit positions per word (b1 < b2): the
        # online two-min update — positions across phases are distinct,
        # so the sentinel is the only shared value and it is absorbing
        b1 = jnp.full((m_words,), jnp.int32(2**30))
        b2 = jnp.full((m_words,), jnp.int32(2**30))
        s1 = jnp.full((m_words,), jnp.int32(2**30))     # first strong
        for r in range(4):
            if r == 3:
                b_all = v
            else:
                b_all = ((vp >> jnp.uint32(8 * (r + 1)))
                         | (v << jnp.uint32(8 * (3 - r))))
            b = b_all[1:]
            a = b_all[:-1]
            h = fmix(fmix(b) + seed + a)
            hit = (h & mask) == 0
            pos = jnp.arange(m_words, dtype=jnp.int32) * 4 + r
            x = jnp.where(hit, pos, 2**30)
            b2 = jnp.minimum(b2, jnp.maximum(b1, x))
            b1 = jnp.minimum(b1, x)
            s1 = jnp.minimum(
                s1, jnp.where((h & strong_mask) == 0, pos, 2**30))
        # per-tile two smallest of the union of (b1, b2) pairs: the tile
        # min comes from b1; the runner-up is the min after the argmin
        # word's entry is replaced by its own second (any other word's b2
        # is dominated by that word's b1, which stays in the pool)
        w1 = b1.reshape(-1, tile_w)
        w2 = b2.reshape(-1, tile_w)
        m1 = jnp.min(w1, axis=1)
        m2 = jnp.min(jnp.where(w1 == m1[:, None], w2, w1), axis=1)
        return jnp.stack([m1, m2, jnp.min(s1.reshape(-1, tile_w), axis=1)])

    return run


# ---------------------------------------------------------------------------
# device segment selection (mirrors select_segments bit-for-bit)
# ---------------------------------------------------------------------------

def _select_step(tiles_p, start, n, win: int, params: AnchoredCdcParams):
    """One step of the segment walk, traced: the segment that starts at
    ``start`` in a stream that ends at ``n`` -> (its exclusive boundary,
    its CUT_* kind, whether it is the stream's last). ``tiles_p``: pass
    A's three rows, padded by ``win`` tiles of 2**30."""
    import jax
    import jax.numpy as jnp

    slo = start + jnp.int32(params.strong_min)
    lo = start + jnp.int32(params.seg_min)
    hi = start + jnp.int32(params.seg_max)
    # one window from the strong rule's low end: it holds the
    # kept-anchor window too (strong_min <= seg_min)
    t0 = (slo - 1) // jnp.int32(TILE_BYTES)
    w = jax.lax.dynamic_slice(tiles_p, (0, t0), (3, win))
    sw = w[2]
    first = jnp.min(jnp.where((sw >= slo - 1) & (sw <= hi - 1), sw, 2**30))
    kw = w[:2]
    last = jnp.max(jnp.where((kw >= lo - 1) & (kw <= hi - 1), kw, -1))
    kind = jnp.where(first < 2**30, CUT_STRONG,
                     jnp.where(last >= 0, CUT_WINDOW, CUT_FORCED))
    b = jnp.where(first < 2**30, first + 1,
                  jnp.where(last >= 0, last + 1, hi))
    fin = n - start <= jnp.int32(params.seg_max)
    return jnp.where(fin, n, b), jnp.where(fin, CUT_END, kind), fin


@functools.cache
def make_select_fn(params: AnchoredCdcParams, m_tiles: int, cap: int):
    """Compiled: (tiles [3, m_tiles] i32 — pass-A output, start0 i32,
    n i32, final bool) -> (bounds [cap] i32: exclusive segment
    boundaries in stream order, -1 padding after the last;
    cuts [3] i32: how many of them are strong / window / forced cuts).
    A sequential scan with a fixed-width three-row window gather per
    step — the walk is tiny (cap ~ hundreds) so only the boundary list
    ever reaches the host."""
    import jax
    import jax.numpy as jnp

    from dfs_tpu.ops.select_pallas import select_window_tiles

    win = select_window_tiles(params)

    @jax.jit
    def run(tiles, start0, n, final):
        """start0: region-local carry start; final: stream ends at n. For
        a non-final region the tail segment is NOT emitted (its bytes
        carry into the next region)."""
        tiles_p = jnp.concatenate(
            [tiles, jnp.full((3, win), 2**30, jnp.int32)], axis=1)

        def body(carry, _):
            start, done = carry
            b, kind, fin = _select_step(tiles_p, start, n, win, params)
            # non-final regions keep the tail segment as carry: emit
            # nothing once the remaining bytes fit in one segment
            skip = done | (fin & ~final)
            out = jnp.where(skip, -1, b)
            return ((jnp.where(skip, start, b), done | fin),
                    (out, jnp.where(skip, -1, kind)))

        # unroll amortizes the per-step scan overhead (the body itself is
        # ~100 ns of VPU work); 8 measured 1.80 -> 0.97-1.34 ms on v5e,
        # the best of {1, 2, 4, 8, 16}
        _, (bounds, kinds) = jax.lax.scan(
            body, (start0.astype(jnp.int32), jnp.bool_(False)), None,
            length=cap, unroll=8)
        cuts = jnp.sum(kinds[:, None] == jnp.arange(3, dtype=jnp.int32),
                       axis=0, dtype=jnp.int32)
        return bounds, cuts

    return run


def make_select(params: AnchoredCdcParams, m_tiles: int, cap: int):
    """The production select: the Pallas on-core walk when the backend
    and window geometry support it (measured 0.17 ms vs 1.4 ms for the
    unrolled XLA scan per 64 MiB region on v5e — the walk is the
    chain's only sequential stage), else the XLA scan. Both are pinned
    bit-identical by tests (interpret mode + the on-chip equality the
    chain's hashlib gates imply)."""
    from dfs_tpu.ops.select_pallas import (make_select_fn_pallas,
                                           select_pallas_supported)

    if select_pallas_supported(params):
        return make_select_fn_pallas(params, m_tiles, cap)
    return make_select_fn(params, m_tiles, cap)


# ---------------------------------------------------------------------------
# device segment descriptors: bounds -> lane tables (keeps the chain fused)
# ---------------------------------------------------------------------------

def _lane_tables(starts, seg_lens, s_pad: int):
    """Pass B's lane tables, traced, from every segment's start and
    length ([cap] i32, 0 where there is none): cut or padded to
    ``s_pad`` lanes -> (starts, seg_lens, w_off, sh8 u32, real_blocks,
    tail_len), each [s_pad]. The encoding :func:`lane_tables_np`
    mirrors."""
    import jax.numpy as jnp

    cap = starts.shape[0]

    def fit(x):
        return jnp.pad(x, (0, s_pad - cap)) if s_pad >= cap else x[:s_pad]

    starts, seg_lens = fit(starts), fit(seg_lens)
    w_off = starts // jnp.int32(4) + jnp.int32(2)     # +2: the lookback
    sh8 = ((starts % jnp.int32(4)) * jnp.int32(8)).astype(jnp.uint32)
    real_blocks = (seg_lens + jnp.int32(BLOCK - 1)) // jnp.int32(BLOCK)
    tail_len = seg_lens % jnp.int32(BLOCK)
    return starts, seg_lens, w_off, sh8, real_blocks, tail_len


@functools.cache
def make_descriptor_fn(params: AnchoredCdcParams, cap: int, s_pad: int):
    """Compiled: (bounds [cap] i32 — select output, start0 i32) ->
    (starts [s_pad], seg_lens [s_pad], w_off [s_pad], sh8 [s_pad] u32,
     real_blocks [s_pad], tail_len [s_pad], consumed i32, nseg i32).
    ``consumed``/``nseg`` cover the FULL boundary list; the [s_pad]
    lane tables may truncate it under tight provisioning (s_pad < cap).

    Everything pass B needs, derived on device — pulling ``bounds`` to
    the host to build these arrays would put a device->host sync in the
    middle of every region; fused, the
    anchor->select->descriptor->chunk/hash chain dispatches
    asynchronously end to end."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(bounds, start0):
        valid = bounds >= 0
        starts = jnp.concatenate(
            [start0[None].astype(jnp.int32), bounds[:-1]])
        starts = jnp.where(valid, starts, 0)
        seg_lens = jnp.where(valid, bounds - starts, 0)
        # consumed and nseg come from the FULL bounds list BEFORE the
        # lane tables truncate to s_pad: the walk chains its device
        # carry on consumed, so it must stay capacity-independent even
        # when tight lane provisioning (s_pad < cap) drops the table's
        # tail — the overflow redo then only ever repairs ONE window
        consumed = jnp.max(jnp.where(valid, bounds,
                                     start0.astype(jnp.int32)))
        nseg = jnp.sum(valid.astype(jnp.int32))
        return (*_lane_tables(starts, seg_lens, s_pad), consumed, nseg)

    return run


def lane_tables_np(bounds, start0: int, s_pad: int):
    """Host-side pass-B lane tables for ONE region from its segment
    bounds — the NumPy mirror of :func:`make_descriptor_fn`'s encoding
    (word floor + 2 lookback words, ``8*(start%4)`` funnel shift,
    ceil-div block counts, tail lengths), padded to ``s_pad`` lanes.
    The single implementation for every host caller (the sharded ingest
    walk per window, ``parallel/sharded_cdc.host_lane_descriptors`` for
    whole-stream oracles) so the layout cannot drift from the device
    side. Returns ``(starts, seg_lens, w_off, sh8, real_blocks,
    tail_len)``, each ``[s_pad]`` (``sh8`` u32, the rest i32)."""
    bounds = np.asarray(bounds, dtype=np.int64)
    nseg = int(bounds.shape[0])
    if nseg > s_pad:
        raise ValueError(f"{nseg} segments > lane table {s_pad}")
    starts = np.zeros((s_pad,), np.int32)
    seg_lens = np.zeros((s_pad,), np.int32)
    w_off = np.zeros((s_pad,), np.int32)
    sh8 = np.zeros((s_pad,), np.uint32)
    real_blocks = np.zeros((s_pad,), np.int32)
    tail_len = np.zeros((s_pad,), np.int32)
    if nseg:
        st = np.concatenate([[int(start0)], bounds[:-1]])
        lens = bounds - st
        starts[:nseg] = st
        seg_lens[:nseg] = lens
        w_off[:nseg] = st // 4 + 2       # +2: the 8 lookback bytes
        sh8[:nseg] = (st % 4) * 8
        real_blocks[:nseg] = -(-lens // BLOCK)
        tail_len[:nseg] = lens % BLOCK
    return starts, seg_lens, w_off, sh8, real_blocks, tail_len


# ---------------------------------------------------------------------------
# device pass B: repack segments into lanes + aligned chunk/hash
# ---------------------------------------------------------------------------

class CutCapacityOverflow(RuntimeError):
    """More cuts (or segments) than the tight provisioning — the caller
    retries the window at the full worst-case bound."""


def segment_cap(params: AnchoredCdcParams, m_words: int) -> int:
    """Most segments a region of ``m_words`` can hold: every cut a
    strong one at ``strong_min`` (<= seg_min). The select walk's length
    and the full-mode lane bound."""
    return m_words * 4 // params.strong_min + 1


def expected_segment_bytes(params: AnchoredCdcParams) -> float:
    """Mean segment length the selection rule gives on random content.
    With s = the strong anchors' rate a byte and W = seg_max -
    strong_min, a window holds a strong anchor with probability
    p = 1 - e^(-sW) and the first one lies an Exp(s) truncated to W
    past strong_min; the other windows end at the last kept anchor
    below seg_max, one mean anchor gap short of it. Default params:
    0.78 x 68.4 KiB + 0.22 x 120 KiB = 79.9 KiB (a lane is 128 KiB)."""
    s = 1.0 / (params.strong_mask + 1)
    w = params.seg_max - params.strong_min
    miss = math.exp(-s * w)
    strong_len = params.strong_min + 1.0 / s - w * miss / (1.0 - miss)
    window_len = max(params.seg_min, params.seg_max - (params.seg_mask + 1))
    return (1.0 - miss) * strong_len + miss * window_len


def _tight_segment_lanes(params: AnchoredCdcParams, m_words: int,
                         lane_multiple: int) -> int:
    """Lane count for cap_mode='tight': ~1.1x the EXPECTED segment
    count (:func:`expected_segment_bytes` — from the rule, not from a
    constant), rounded up to the compaction tiling. The worst case
    (every boundary a strong one at strong_min) provisions ~2.5x the
    lanes real content ever uses, and padding lanes are not free —
    repack writes them, the transpose moves them, and the strip-scan
    SHA kernel computes over them masked (measured ~17% of the scan
    half when the worst case was 1.25x). Content denser in segments
    than the margin trips the exact on-device segment count (nseg >
    lanes, counted by the full-bound select scan) and redispatches at
    'full' — same contract as the cut capacity, and the carry stays
    exact throughout (make_chain_fn)."""
    full = segment_cap(params, m_words)
    expected = max(1, int(m_words * 4 / expected_segment_bytes(params)))
    tight = -(-(expected * 11 // 10) // lane_multiple) * lane_multiple
    return min(tight, -(-full // lane_multiple) * lane_multiple)


@functools.cache
def make_anchored_segment_fn(params: AnchoredCdcParams, m_words: int,
                             s_pad: int, cap_mode: str = "tight"):
    """Compiled: (words_le [m_words] u32 — the resident batch,
    w_off [s_pad] i32 (word floor of each segment start),
    sh8 [s_pad] u32 (8 * (start % 4)),
    real_blocks [s_pad] i32 (ceil(seg_len/64); 0 = padding lane),
    tail_len [s_pad] i32 (seg_len % 64; 0 = whole-block tail),
    starts [s_pad] i32, seg_lens [s_pad] i32 (region-local byte table))
    -> (count i32, q [c_max] i32 (lane*bps + t, -1 pad),
        offs [c_max] i32 (region-local chunk byte offsets),
        lens [c_max] i32 (chunk BYTE length), digests [c_max, 8] u32)."""
    import jax
    import jax.numpy as jnp

    from dfs_tpu.ops.cdc_v2 import (gear_candidates_device,
                                    select_cuts_device)
    from dfs_tpu.ops.layout import bswap32, bswap_transpose
    from dfs_tpu.ops.sha256_jax import _H0
    from dfs_tpu.ops.sha256_strip import (_compress_dispatch,
                                          cut_state_rows,
                                          pad_finalize_device,
                                          strip_chunk_states,
                                          strip_states_xla)

    cp = params.chunk
    bps = cp.strip_blocks
    lane_words = bps * 16
    # capacity: per-lane bound AND the global bound — segments tile the
    # region disjointly, so total content blocks <= region blocks + one
    # rounded-up tail per lane, and cuts <= blocks/min + one forced
    # lane-final cut per lane (1.5x tighter than the per-lane bound alone
    # at default params; the finalize + gathers scale with c_max)
    c_full = min(cut_capacity(s_pad, cp),
                 (m_words // 16 + s_pad) // cp.min_blocks + s_pad)
    if cap_mode == "tight":
        # provision for 1.25x the EXPECTED cut count (blocks/avg + one
        # forced cut per lane), not the worst case: capacity-scaled work
        # (scatter, state/len gathers, finalize) measured 3.1 ms of a
        # 13.4 ms region at the full bound. Content dense enough to
        # overflow raises CutCapacityOverflow at collect (the count is
        # exact) and the caller redispatches this window at "full".
        c_max = min(c_full,
                    (m_words // 16 // cp.avg_blocks + s_pad) * 5 // 4)
    else:
        c_max = c_full
    use_pallas = s_pad % 128 == 0 and any(
        d.platform == "tpu" for d in jax.devices())
    # cut-position compaction tiling: tiles never span a lane (t_tile |
    # bps), so in-lane cuts are >= min_blocks apart and a tile holds at
    # most t_tile//min_blocks + 2 cuts (+1 partial leading gap, +1 forced
    # lane-final)
    t_tile = 128 if bps % 128 == 0 else bps
    k_max = t_tile // cp.min_blocks + 2

    from dfs_tpu.ops.repack import repack_lanes

    @jax.jit
    def scan_half(words, w_off, sh8, real_blocks):
        # repack: one lane per segment — Pallas DMA gather + in-register
        # rotate on TPU (0.44 ms/region incl. the transpose below, vs
        # 2.3 ms for the vmap(dynamic_slice)+funnel pair it replaces)
        packed = repack_lanes(words, w_off, sh8, lane_words)

        words_t = bswap_transpose(packed)              # [bps*16, s_pad] BE
        if use_pallas:
            # fused candidates+selection+SHA: one pass over the resident
            # words instead of three (ops.sha256_strip.strip_chunk_states)
            cf32, since, states = strip_chunk_states(
                words_t, real_blocks, cp.seed, cp.mask, cp.min_blocks,
                cp.max_blocks)
        else:
            cand = gear_candidates_device(words_t, cp)
            cutflag, since = select_cuts_device(cand, real_blocks, cp)
            cf32 = cutflag.astype(jnp.int32)
            states = strip_states_xla(words_t, cf32)
        # states relayout here (not in compact) so the 50 MB transpose
        # stays in the module XLA already fuses the scan into
        return cf32, since, cut_state_rows(states, s_pad)

    @jax.jit
    def compact_half(cf32, since, state_rows, words, w_off, sh8,
                     real_blocks, tail_len, starts, seg_lens):
        count = jnp.sum(cf32)

        # stream-order cut positions q = lane*bps + t, compacted
        # tile-wise: per tile, peel off the k-th lowest set bit (k <
        # k_max) with masked min-reductions — all vector ops, no scatter
        # over the full block space (jnp.nonzero measured 9 ms per
        # 64 MiB; this path ~1 ms)
        flat = cf32.T.reshape(-1, t_tile) != 0
        nt = flat.shape[0]
        iota = jnp.arange(t_tile, dtype=jnp.int32)[None, :]
        cnt = jnp.sum(flat, axis=1).astype(jnp.int32)
        base = jnp.cumsum(cnt) - cnt
        poss = []
        cur = flat
        for _ in range(k_max):
            pos = jnp.min(jnp.where(cur, iota, t_tile), axis=1)
            poss.append(pos)
            cur = cur & (iota != pos[:, None])
        pos_mat = jnp.stack(poss, axis=1)
        valid = pos_mat < t_tile
        gidx = jnp.where(
            valid,
            base[:, None] + jnp.arange(k_max, dtype=jnp.int32)[None, :],
            c_max)
        vals = jnp.arange(nt, dtype=jnp.int32)[:, None] * t_tile + pos_mat
        q = jnp.full((c_max,), -1, jnp.int32).at[gidx.reshape(-1)].set(
            vals.reshape(-1).astype(jnp.int32), mode="drop")

        t = jnp.maximum(q, 0) % bps
        s = jnp.maximum(q, 0) // bps

        # chunk lengths come from the selection's own block counter (lanes
        # are independent segments, so cross-lane position differences
        # say nothing); the lane-tail chunk subtracts its pad
        blocks = jnp.take(since.reshape(-1),
                          t * jnp.int32(s_pad) + s)    # since is [bps, S]
        is_tail = (t == jnp.take(real_blocks, s) - 1) \
            & (jnp.take(tail_len, s) > 0)
        lens = blocks * jnp.int32(BLOCK) \
            - jnp.where(is_tail, jnp.int32(BLOCK) - jnp.take(tail_len, s), 0)

        cut_states = jnp.take(state_rows, t * jnp.int32(s_pad) + s, axis=0)
        digests = pad_finalize_device(cut_states, lens)

        # ---- lane-tail digests: the strip scan compressed a zero-padded
        # partial block; redo the final block(s) with real FIPS padding ----
        tl = tail_len                                   # [s_pad]
        last_t = jnp.maximum(real_blocks - 1, 0)
        # chain state BEFORE the tail block (H0 when the tail chunk is a
        # single partial block)
        lane_i = jnp.arange(s_pad, dtype=jnp.int32)
        tail_since = jnp.take(since.reshape(-1),
                              last_t * jnp.int32(s_pad) + lane_i)
        prev_states = jnp.take(
            state_rows,
            jnp.maximum((last_t - 1) * jnp.int32(s_pad) + lane_i, 0), axis=0)
        single = (tail_since <= 1)[:, None]
        h0 = jnp.broadcast_to(jnp.asarray(_H0)[None, :], prev_states.shape)
        state0 = jnp.where(single, h0, prev_states)    # [s_pad, 8]

        # tail block content (LE) regathered from the region buffer (the
        # repacked lanes are not kept — dropping the 96 MiB intermediate
        # output pays for this 17-word-per-lane gather many times over),
        # masked beyond tail_len, 0x80 appended. Row-contiguous
        # vmap(dynamic_slice), NOT an element-index jnp.take: the [s, 17]
        # index-matrix gather measured ~0.6 ms slower per region on v5e.
        x = jax.vmap(lambda o: jax.lax.dynamic_slice(
            words, (o,), (17,)))(w_off + last_t * 16)   # [s_pad, 17]
        sh = sh8[:, None]
        tw = jnp.where(sh == 0, x[:, :-1],
                       (x[:, :-1] >> sh)
                       | (x[:, 1:] << (jnp.uint32(32) - sh)))
        byte0 = jnp.arange(16, dtype=jnp.int32)[None, :] * 4  # word's byte
        keep = jnp.clip(tl[:, None] - byte0, 0, 4)
        mask = jnp.where(keep >= 4, jnp.uint32(0xFFFFFFFF),
                         (jnp.uint32(1) << (jnp.uint32(8) *
                                            keep.astype(jnp.uint32)))
                         - jnp.uint32(1))
        tw = tw & mask
        in_word = (tl[:, None] // 4) == jnp.arange(16, dtype=jnp.int32)[None, :]
        tw = tw | jnp.where(
            in_word,
            jnp.uint32(0x80) << (jnp.uint32(8) *
                                 (tl % 4).astype(jnp.uint32))[:, None],
            jnp.uint32(0))
        twb = [bswap32(tw[:, i]) for i in range(16)]    # BE words

        tail_bytes = (tail_since - 1) * jnp.int32(BLOCK) + tl
        bits_lo = tail_bytes.astype(jnp.uint32) * jnp.uint32(8)
        bits_hi = tail_bytes.astype(jnp.uint32) >> jnp.uint32(29)

        # fits: tail_len <= 55 -> length goes in the same block
        fits = tl <= 55
        w_fit = list(twb)
        w_fit[14] = jnp.where(fits, bits_hi, twb[14])
        w_fit[15] = jnp.where(fits, bits_lo, twb[15])
        d_fit = jnp.stack(
            _compress_dispatch([state0[:, i] for i in range(8)], w_fit),
            axis=1)
        # overflow: content block, then a pure length block
        st2 = jnp.stack(
            _compress_dispatch([state0[:, i] for i in range(8)], list(twb)),
            axis=1)
        zero = jnp.zeros_like(bits_lo)
        w_len = [zero] * 14 + [bits_hi, bits_lo]
        d_ovf = jnp.stack(
            _compress_dispatch([st2[:, i] for i in range(8)], w_len),
            axis=1)
        tail_digest = jnp.where(fits[:, None], d_fit, d_ovf)  # [s_pad, 8]

        digests = jnp.where(is_tail[:, None],
                            jnp.take(tail_digest, jnp.maximum(s, 0), axis=0),
                            digests)

        # region-local byte spans, on device (rows past count are garbage)
        ends = jnp.take(starts, s) + jnp.minimum(
            (t + 1) * jnp.int32(BLOCK), jnp.take(seg_lens, s))
        offs = ends - lens
        return count, q, offs, lens, digests

    def run(words, w_off, sh8, real_blocks, tail_len, starts, seg_lens):
        cf32, since, state_rows = scan_half(words, w_off, sh8, real_blocks)
        return compact_half(cf32, since, state_rows, words, w_off, sh8,
                            real_blocks, tail_len, starts, seg_lens)

    run.halves = (scan_half, compact_half)   # stage profiling hook
    return run


# ---------------------------------------------------------------------------
# whole-chain jit: anchor -> select/desc -> repack/scan -> compact, fused
# ---------------------------------------------------------------------------

@functools.cache
def make_chain_fn(params: AnchoredCdcParams, total_words: int,
                  lane_multiple: int, cap_mode: str):
    """One compiled executable for the whole region chain. The nested
    stage jits inline into this trace, so a region costs ONE dispatch
    instead of five (anchor / select / descriptors / scan / compact) and
    XLA fuses across the former stage boundaries. The staged builders
    stay as profiling hooks (bench_profile.py).

    cap_mode='tight' provisions the segment LANES (the repacked batch,
    the SHA strip grid, and the compaction capacity) at ~1.1x the
    expected segment count instead of the all-boundaries-at-strong_min
    worst case (_tight_segment_lanes). The select SCAN always runs at
    the full bound — it is lane-count-independent and computing the
    complete boundary list keeps the returned ``consumed`` carry exact
    even when the lane tables truncate, so the pipelined walk's
    downstream windows (which chain on the device carry at dispatch
    time) never need repair. ``seg_overflow`` is nonzero iff the region
    really has more segments than the lanes hold (strict: an exact fit
    is not an overflow) — region_collect raises CutCapacityOverflow and
    the caller redispatches THIS window at 'full', exactly like the cut
    capacity. ``nseg`` and ``cuts`` (strong / window / forced, [3]) say
    how the region's segments came to end — from the full boundary list
    too, so a redo counts nothing twice."""
    import jax
    import jax.numpy as jnp

    m_words = recover_m_words(total_words, params)
    m_tiles = m_words * 4 // TILE_BYTES
    cap = segment_cap(params, m_words)
    if cap_mode == "tight":
        s_pad = _tight_segment_lanes(params, m_words, lane_multiple)
    else:
        s_pad = -(-cap // lane_multiple) * lane_multiple
    tight = cap_mode == "tight"
    anchor = make_anchor_fn(params, m_words)
    select = make_select(params, m_tiles, cap)
    desc = make_descriptor_fn(params, cap, s_pad)
    segfn = make_anchored_segment_fn(params, total_words, s_pad, cap_mode)

    @jax.jit
    def run(words, start0, n, final):
        tiles = anchor(words)
        bounds, cuts = select(tiles, start0, n, final)
        (starts, seg_lens, w_off, sh8, real_blocks, tail_len,
         consumed, nseg) = desc(bounds, start0)
        seg_overflow = (nseg > jnp.int32(s_pad)) if tight \
            else jnp.int32(0)
        count, q, offs, lens, dig = segfn(words, w_off, sh8, real_blocks,
                                          tail_len, starts, seg_lens)
        return (consumed, seg_overflow, count, q, offs, lens, dig,
                nseg, cuts)

    return run


# ---------------------------------------------------------------------------
# host driver: one resident batch -> chunk table
# ---------------------------------------------------------------------------

def region_buffer_size(n: int, params: AnchoredCdcParams,
                       m_words: int | None = None) -> int:
    """Byte size of the staging buffer :func:`region_buffer` builds for an
    ``n``-byte region — the single place the layout math lives (callers
    pooling buffers must agree with it exactly). Rounded up to the Pallas
    DMA tiling (4096 B = 1024 words) so the repack kernel can view the
    buffer 2D without re-materializing it (ops.repack);
    :func:`region_dispatch` recovers ``m_words`` by flooring the slack
    back off, which may grow the zero-padded tile area by up to 7 tiles —
    zero tiles past ``n`` never change selection (anchors there are
    beyond every admissible window), so the chunk output is unaffected."""
    if m_words is None:
        m_words = next_pow2(-(-n // TILE_BYTES)) * (TILE_BYTES // 4)
    raw = 8 + m_words * 4 + params.seg_max + 4
    return -(-raw // 4096) * 4096


def recover_m_words(total_words: int, params: AnchoredCdcParams) -> int:
    """Invert :func:`region_buffer_size`: region words from the buffer's
    word length (floored to whole tiles — the DMA rounding may grow the
    zero-pad tile area, which never changes selection)."""
    tile_w = TILE_BYTES // 4
    return (total_words - 2
            - (params.seg_max + 4) // 4) // tile_w * tile_w


def region_buffer(data: np.ndarray, lookback: np.ndarray,
                  params: AnchoredCdcParams,
                  m_words: int | None = None,
                  out: np.ndarray | None = None) -> np.ndarray:
    """Host-side staging buffer for one region:
    [8 lookback bytes][region padded to whole tiles] plus one full lane +
    funnel word of slack so every lane's dynamic_slice stays in bounds
    (jax clamps out-of-range slice starts, which would silently shift a
    tail segment's content). Returned as the LE u32 view device_put wants.
    Pass ``m_words`` to pin the shape (one compile across a region walk);
    pass ``out`` (a u8 buffer of exactly the right size, e.g. from a
    previous call) to fill in place — fresh 64 MiB allocations pay a
    large one-time host->device transfer setup on some links, so the
    pipelined walk recycles buffers once their transfer completed."""
    n = int(data.shape[0])
    total = region_buffer_size(n, params, m_words=m_words)
    if out is None:
        buf = np.zeros((total,), dtype=np.uint8)
    else:
        if out.shape[0] != total or out.dtype != np.uint8:
            raise ValueError("recycled buffer has the wrong shape")
        buf = out
        buf[8 + n:] = 0
    buf[:8] = lookback
    buf[8:8 + n] = data
    return buf.view("<u4")


@functools.lru_cache(maxsize=256)
def _dev_i32(v: int):
    import jax.numpy as jnp

    return jnp.int32(v)


@functools.lru_cache(maxsize=2)
def _dev_bool(v: bool):
    import jax.numpy as jnp

    return jnp.bool_(v)


def region_dispatch(words, n: int, start0, final: bool,
                    params: AnchoredCdcParams, lane_multiple: int = 128,
                    cap_mode: str = "tight"):
    """Dispatch the fused anchor->select->descriptor->chunk/hash chain on a
    device-resident region buffer (``words`` from :func:`region_buffer`,
    already device_put). ``start0`` may be a host int or a device scalar —
    a device scalar keeps a multi-region walk entirely free of host syncs
    (the carry chains on device). Returns device arrays
    (consumed i32, seg_overflow i32, count i32, q, offs, lens, digests,
    nseg i32, cuts [3] i32); nothing blocks.

    The n/start0/final scalars are cached device constants — re-putting
    them per region is a host->device transfer each (dispatch is
    otherwise fully async)."""
    import jax

    if not isinstance(start0, jax.Array):
        start0 = _dev_i32(int(start0))
    chain = make_chain_fn(params, int(words.shape[0]), lane_multiple,
                          cap_mode)
    return chain(words, start0, _dev_i32(int(n)), _dev_bool(bool(final)))


def region_collect(out) -> tuple[list[tuple[int, int, str]], int,
                                 tuple[int, int, int, int]]:
    """Pull a :func:`region_dispatch` result to the host and format it:
    ([(region_offset, length, sha256hex)], consumed, (segments,
    strong_cuts, window_cuts, forced_cuts)). The only sync point of the
    chain."""
    import jax

    (consumed, seg_of, count, q, offs, lens, dig, nseg,
     cuts) = jax.device_get(out)
    if int(seg_of):
        # more segments than the tight lane provisioning — the lane
        # tables dropped the tail segments (consumed is still exact:
        # the select scan ran at the full bound); redispatch at "full"
        raise CutCapacityOverflow("segment lanes overflowed tight "
                                  "provisioning")
    count = int(count)
    if count > q.shape[0]:
        # content denser than the tight provisioning (cap_mode="tight" in
        # region_dispatch) — the first q.shape[0] cuts are valid but the
        # rest were dropped; the caller must redispatch at "full"
        raise CutCapacityOverflow(
            f"{count} cuts > capacity {q.shape[0]}")
    if count and (q[:count] < 0).any():
        raise AssertionError("anchored cut compaction overflowed a tile")
    hexes = digests_to_hex(dig[:count])
    return ([(int(o), int(ln), h)
             for o, ln, h in zip(offs[:count], lens[:count], hexes)],
            int(consumed), (int(nseg), *(int(c) for c in cuts)))


def region_chunks(data: np.ndarray, lookback: np.ndarray, start0: int,
                  final: bool, params: AnchoredCdcParams,
                  lane_multiple: int = 128, cap_mode: str = "tight"
                  ) -> tuple[list[tuple[int, int, str]], int,
                             tuple[int, int, int, int]]:
    """Chunk one stream region on device.

    data: [n] u8 region bytes (byte 0 = stream offset ``base``, any base);
    lookback: [8] u8 — the 8 stream bytes before the region (zeros at true
    stream start); start0: carry position inside the region (bytes before
    it belong to already-emitted segments of a previous region); final:
    True iff the stream ends at data[-1] — otherwise the tail segment is
    withheld so its bytes can carry into the next region.

    Returns ([(region_offset, length, sha256hex)], consumed, cut counts
    as :func:`region_collect`): chunks of every emitted segment, and the
    region offset up to which segments were emitted (== n when final).
    Batching is transparent: for any region
    split the concatenated output equals the whole-stream oracle
    (chunk_file_anchored_np), which tests enforce.
    """
    import jax

    n = int(data.shape[0])
    if n == 0:
        return [], 0, (0, 0, 0, 0)
    words = jax.device_put(region_buffer(data, lookback, params))
    out = region_dispatch(words, n, start0, final, params,
                          lane_multiple=lane_multiple, cap_mode=cap_mode)
    try:
        return region_collect(out)
    except CutCapacityOverflow:
        # denser than the tight provisioning: one synchronous redo at the
        # worst-case bound (rare by construction; see cap_mode)
        out = region_dispatch(words, n, start0, final, params,
                              lane_multiple=lane_multiple, cap_mode="full")
        return region_collect(out)


def batch_chunks_anchored(data: np.ndarray, params: AnchoredCdcParams,
                          lane_multiple: int = 128
                          ) -> list[tuple[int, int, str]]:
    """Whole-stream convenience wrapper over :func:`region_chunks`."""
    return region_chunks(
        np.asarray(data), np.zeros((8,), np.uint8), 0, True, params,
        lane_multiple=lane_multiple)[0]


# ---------------------------------------------------------------------------
# packed regions: several independent streams, one dispatch
# ---------------------------------------------------------------------------
#
# A small stream alone would leave a region nearly empty and still cost a
# dispatch, so the owner lays the streams that wait side by side in ONE
# staging buffer (docs/ingest.md "Packed regions"):
#
#   [8 zero bytes][stream 0 ....][zeros][stream 1 ..][zeros][stream 2 ...
#                 ^ offs[0] = 0         ^ offs[1]           ^ offs[2]
#
# every offset a multiple of TILE_BYTES and at least 8 zero bytes after
# the stream before it. The anchor hash reads bytes before a stream's
# offset 0 as 0 and anchors are kept by ABSOLUTE tile, so a stream's
# anchor planes are the ones it has alone; the walk below starts anew at
# every stream's first byte and ends a stream's last segment at its last
# byte, so no segment, chunk or SHA state crosses from one stream to the
# next. The law (tests/test_packed_region.py): each stream's chunk table
# is bit for bit chunk_file_anchored_np of that stream alone. Pass A
# (make_anchor_fn) and pass B (make_anchored_segment_fn: one lane a
# segment, wherever it starts) are the single-stream chain's own.

def packed_next(off: int, length: int) -> int:
    """Where the stream after one of ``length`` bytes at ``off`` starts:
    the first tile boundary that leaves 8 zero bytes between them. The
    single definition of the rule above."""
    return -(-(off + int(length) + 8) // TILE_BYTES) * TILE_BYTES


def packed_layout(lengths) -> tuple[list[int], int]:
    """Region-local offsets of streams of these byte lengths laid one
    after the other, and the bytes the layout takes (the last stream's
    end)."""
    offs, at = [], 0
    for ln in lengths:
        offs.append(at)
        at = packed_next(at, ln)
    return offs, (offs[-1] + int(lengths[-1]) if offs else 0)


def packed_lanes(length: int, params: AnchoredCdcParams) -> int:
    """Lanes a stream of ``length`` bytes is provisioned in a packed
    region: one, and one more for every half lane of its bytes (a
    segment is 62 % of a lane on average; a stream of a lane or less is
    exactly one). What closes a region on its lanes, and what a shape's
    tight lanes are sized from."""
    return int(length) // (params.seg_max // 2) + 1


def packed_segment_cap(params: AnchoredCdcParams, m_words: int,
                       k_max: int) -> int:
    """Most segments a packed region of ``m_words`` holding up to
    ``k_max`` streams can have: :func:`segment_cap`'s bound, and one
    stream-final segment a stream."""
    return m_words * 4 // params.strong_min + k_max


@functools.cache
def make_packed_select_fn(params: AnchoredCdcParams, m_tiles: int,
                          cap: int, k_max: int):
    """Compiled: (tiles [3, m_tiles] i32 — pass-A output, offs [k_max]
    i32, ends [k_max] i32 — each stream's first byte and the byte after
    its last, region-local, k i32 — how many of them are streams) ->
    (starts [cap] i32, bounds [cap] i32: every segment of every stream in
    region order, -1 padding after the last; cuts [3] i32).
    :func:`make_select_fn`'s step with the stream's own end for ``n``;
    a stream's last segment ends at its last byte (``final``) and the
    walk starts anew at the next stream's offset. An XLA loop on every
    backend, one turn a segment and no more: ``cap`` is the bound a
    region of strong cuts alone would reach, a region of small files
    holds one segment a stream — a scan of ``cap`` steps spent 0.8 ms
    of the chip and 1 300 trace events a region on the padding (my chip
    run, PR 41)."""
    import jax
    import jax.numpy as jnp

    from dfs_tpu.ops.select_pallas import select_window_tiles

    win = select_window_tiles(params)

    @jax.jit
    def run(tiles, offs, ends, k):
        tiles_p = jnp.concatenate(
            [tiles, jnp.full((3, win), 2**30, jnp.int32)], axis=1)
        offs_p = jnp.concatenate([offs, jnp.zeros((1,), jnp.int32)])
        ends_p = jnp.concatenate([ends, jnp.zeros((1,), jnp.int32)])

        def body(state):
            i, start, idx, starts, bounds, kinds = state
            b, kind, fin = _select_step(tiles_p, start, ends_p[idx], win,
                                        params)
            nxt = jnp.where(fin, idx + 1, idx)
            return (i + 1, jnp.where(fin, offs_p[nxt], b), nxt,
                    starts.at[i].set(start), bounds.at[i].set(b),
                    kinds.at[i].set(kind))

        pad = jnp.full((cap,), -1, jnp.int32)
        *_, starts, bounds, kinds = jax.lax.while_loop(
            lambda state: (state[0] < cap) & (state[2] < k), body,
            (jnp.int32(0), offs_p[0], jnp.int32(0),
             jnp.zeros((cap,), jnp.int32), pad, pad))
        cuts = jnp.sum(kinds[:, None] == jnp.arange(3, dtype=jnp.int32),
                       axis=0, dtype=jnp.int32)
        return starts, bounds, cuts

    return run


@functools.cache
def make_packed_chain_fn(params: AnchoredCdcParams, total_words: int,
                         lanes: int, lane_multiple: int, cap_mode: str):
    """One compiled executable for a packed region: (words — the staging
    buffer, offs [lanes] i32, ends [lanes] i32, k i32) -> (seg_overflow,
    count, q, offs, lens, digests, nseg, cuts) as :func:`make_chain_fn`
    less the carry. ``lanes`` is the shape's tight lane provisioning and
    the length of the table of streams (a stream is at least one lane);
    cap_mode='full' provisions every lane the walk can fill, for the
    redo of what overflowed. The walk always runs at the full bound, so
    ``nseg`` and ``cuts`` cover every stream whatever the lanes hold."""
    import jax
    import jax.numpy as jnp

    m_words = recover_m_words(total_words, params)
    cap = packed_segment_cap(params, m_words, lanes)
    tight = cap_mode == "tight"
    s_pad = lanes if tight else -(-cap // lane_multiple) * lane_multiple
    anchor = make_anchor_fn(params, m_words)
    select = make_packed_select_fn(params, m_words * 4 // TILE_BYTES, cap,
                                   lanes)
    segfn = make_anchored_segment_fn(params, total_words, s_pad, cap_mode)

    @jax.jit
    def run(words, offs, ends, k):
        starts, bounds, cuts = select(anchor(words), offs, ends, k)
        valid = bounds >= 0
        nseg = jnp.sum(valid.astype(jnp.int32))
        starts, seg_lens, w_off, sh8, real_blocks, tail_len = _lane_tables(
            starts, jnp.where(valid, bounds - starts, 0), s_pad)
        seg_overflow = (nseg > jnp.int32(s_pad)) if tight else jnp.int32(0)
        count, q, c_offs, lens, dig = segfn(
            words, w_off, sh8, real_blocks, tail_len, starts, seg_lens)
        return seg_overflow, count, q, c_offs, lens, dig, nseg, cuts

    return run


def packed_buffer(streams, offs, params: AnchoredCdcParams, m_words: int,
                  out: np.ndarray | None = None) -> np.ndarray:
    """Host staging buffer of a packed region of ``m_words``: the
    streams (u8 arrays) at their :func:`packed_layout` offsets, zeros
    everywhere else, :func:`region_buffer`'s lookback and slack around
    them. ``out`` recycles a buffer of that exact size."""
    empty = np.zeros((8,), np.uint8)
    buf = region_buffer(empty[:0], empty, params, m_words, out).view(np.uint8)
    for arr, off in zip(streams, offs):
        buf[8 + off:8 + off + arr.shape[0]] = arr
    return buf.view("<u4")


def packed_dispatch(words, offs, lengths, params: AnchoredCdcParams,
                    lanes: int, lane_multiple: int = 128,
                    cap_mode: str = "tight"):
    """Dispatch the packed chain on a device-resident staging buffer;
    the table of streams (metadata-sized) goes with the call. Returns
    device arrays, nothing blocks."""
    k = len(offs)
    if not 0 < k <= lanes:
        raise ValueError(f"{k} streams in a table of {lanes}")
    table = np.zeros((2, lanes), np.int32)
    table[0, :k] = offs
    table[1, :k] = np.asarray(offs) + np.asarray(lengths)
    chain = make_packed_chain_fn(params, int(words.shape[0]), lanes,
                                 lane_multiple, cap_mode)
    return chain(words, table[0], table[1], _dev_i32(k))


def packed_collect(out, offs, lengths
                   ) -> tuple[list[list[tuple[int, int, str]] | None],
                              tuple[int, int, int, int]]:
    """Pull a :func:`packed_dispatch` result to the host: one chunk
    table a stream, offsets stream-local — or None for a stream the
    tight provisioning did not hold whole (lanes or cuts ran out before
    its last chunk; its neighbours' tables stand) — and the region's cut
    counts as :func:`region_collect` gives them, every stream's in
    them."""
    import jax

    seg_of, count, q, c_offs, lens, dig, nseg, cuts = jax.device_get(out)
    rows = min(int(count), q.shape[0])
    if rows and (q[:rows] < 0).any():
        raise AssertionError("anchored cut compaction overflowed a tile")
    overflowed = bool(seg_of) or int(count) > rows
    at = c_offs[:rows].astype(np.int64)
    ln = lens[:rows].astype(np.int64)
    hexes = digests_to_hex(dig[:rows])
    # rows come lane by lane, lanes in region order: sorted by offset
    los = np.searchsorted(at, np.asarray(offs, np.int64), "left")
    tables: list[list[tuple[int, int, str]] | None] = []
    for off, size, lo in zip(offs, lengths, los.tolist()):
        table, expect = [], off
        while expect < off + size and lo < rows and at[lo] == expect:
            table.append((expect - off, int(ln[lo]), hexes[lo]))
            expect += int(ln[lo])
            lo += 1
        if expect == off + size:
            tables.append(table)
        elif overflowed:
            tables.append(None)
        else:
            raise AssertionError(
                f"packed stream at {off} broke off at {expect - off} "
                f"of {size}")
    return tables, (int(nseg), *(int(c) for c in cuts))


def packed_chunks(streams, params: AnchoredCdcParams, m_words: int,
                  lanes: int, lane_multiple: int = 128,
                  cap_mode: str = "tight"
                  ) -> tuple[list[list[tuple[int, int, str]]],
                             tuple[int, int, int, int]]:
    """Chunk several streams in one packed region of ``m_words`` and
    ``lanes`` (they have to fit: :func:`packed_layout`), synchronously;
    streams the tight provisioning dropped are redone together at the
    worst-case bound. One table a stream, and the cut counts."""
    import jax

    lengths = [int(s.shape[0]) for s in streams]
    offs, used = packed_layout(lengths)
    if used > m_words * 4:
        raise ValueError(f"{used} B of streams in a region of "
                         f"{m_words * 4}")
    words = jax.device_put(packed_buffer(streams, offs, params, m_words))
    tables, cuts = packed_collect(
        packed_dispatch(words, offs, lengths, params, lanes,
                        lane_multiple, cap_mode), offs, lengths)
    packed_redo(streams, tables, params, m_words, lanes, lane_multiple)
    return tables, cuts


def packed_redo(streams, tables: list, params: AnchoredCdcParams,
                m_words: int, lanes: int, lane_multiple: int = 128) -> bool:
    """Fill in the tables :func:`packed_collect` left None: those
    streams alone, in one region at the worst-case bound; the others'
    tables stand (and the first walk, which ran at the full bound, has
    counted every stream's cuts already). True if there were any."""
    again = [i for i, t in enumerate(tables) if t is None]
    if again:
        redone, _ = packed_chunks([streams[i] for i in again], params,
                                  m_words, lanes, lane_multiple, "full")
        for i, t in zip(again, redone):
            tables[i] = t
    return bool(again)
