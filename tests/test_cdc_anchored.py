"""Anchored two-level CDC (v3): oracle properties, device parity, and the
shift-resilience a grid anchored at stream offset 0 lacks."""

import hashlib
import io
import tarfile

import numpy as np
import pytest

from dfs_tpu.ops.cdc_anchored import (CUT_END, CUT_FORCED, CUT_STRONG,
                                      CUT_WINDOW, TILE_BYTES,
                                      AnchoredCdcParams, anchor_hash_np,
                                      anchor_planes_np, anchors_np,
                                      batch_chunks_anchored,
                                      chunk_file_anchored_np,
                                      chunk_spans_anchored_np, cut_counts,
                                      select_segments,
                                      select_segments_kinds)
from dfs_tpu.ops.cdc_v2 import AlignedCdcParams

# 4 KiB lanes; strong anchors every 4 KiB and admissible from 1 KiB
# (strong_min = seg_max / 4, as in production), so about half the cuts
# are strong ones and the other half take the fallback
SMALL = AnchoredCdcParams(
    chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                           strip_blocks=64),
    seg_min=2048, seg_max=4096, seg_mask=2047, strong_bits=1)


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
    kept = anchors_np(data, SMALL)[0]
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


def test_kept_strong_plane_is_first_strong_hash_per_tile():
    """The strong plane is tested on the hash alone: a strong position
    stands whether or not it is one of its tile's two kept anchors."""
    data = corpus(400000, seed=2)
    kept, strong = anchors_np(data, SMALL)
    h = anchor_hash_np(data, SMALL)
    pos = np.flatnonzero((h & np.uint32(SMALL.strong_mask)) == 0)
    assert SMALL.strong_mask == 4095 and pos.size > 50
    _, first = np.unique(pos // TILE_BYTES, return_index=True)
    assert np.array_equal(strong, pos[first])
    # every strong hash is an anchor hash; not every one was kept
    assert np.all((h[strong] & np.uint32(SMALL.seg_mask)) == 0)
    planes = anchor_planes_np(kept, strong, -(-data.shape[0] // TILE_BYTES))
    assert planes.shape[0] == 3 and (planes[2] < 2**30).sum() == strong.size


def test_segments_respect_bounds():
    data = corpus(300000, seed=3)
    bounds, kinds = select_segments_kinds(*anchors_np(data, SMALL),
                                          data.shape[0], SMALL)
    assert bounds[-1] == data.shape[0] and kinds[-1] == CUT_END
    low = {CUT_STRONG: SMALL.strong_min, CUT_WINDOW: SMALL.seg_min,
           CUT_FORCED: SMALL.seg_max}
    prev = 0
    for b, k in zip(bounds[:-1].tolist(), kinds[:-1].tolist()):
        assert low[k] <= b - prev <= SMALL.seg_max
        prev = b
    assert bounds[-1] - prev <= SMALL.seg_max
    segments, strong, window, forced = cut_counts(kinds)
    assert segments == len(bounds) == strong + window + forced + 1
    assert strong > 20 and window > 20      # both arms of the rule run


def test_default_params_state_the_rule():
    p = AnchoredCdcParams()
    assert (p.strong_min, p.strong_bits, p.strong_mask) == \
        (32 * 1024, 3, 65535)
    with pytest.raises(ValueError):
        AnchoredCdcParams(strong_min=p.seg_min + TILE_BYTES)
    with pytest.raises(ValueError):
        AnchoredCdcParams(strong_min=1000)


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
    must still dedup (a 64-byte grid anchored at stream offset 0 loses
    everything downstream)."""
    base = corpus(300000, seed=4)
    edited = np.concatenate(
        [base[:50001], corpus(77, seed=5), base[50001:]])
    a = {dg for _, _, dg in chunk_file_anchored_np(base, SMALL)}
    b = [(o, ln, dg) for o, ln, dg in chunk_file_anchored_np(edited, SMALL)]
    shared = sum(ln for _, ln, dg in b if dg in a)
    assert shared / edited.shape[0] > 0.85, \
        f"only {shared / edited.shape[0]:.0%} of bytes deduped after insert"


# ------------------------------------- the inputs every engine is held to --

def _pattern(params, strong: bool) -> np.ndarray:
    """Eight bytes whose anchor hash is a strong anchor (or an anchor that
    is NOT strong) when they end at any position, and which set off no
    other anchor where they overlap a run of zeros."""
    data = corpus(1 << 20, seed=77)
    h = anchor_hash_np(data, params)
    hit = (h & np.uint32(params.seg_mask)) == 0
    is_strong = (h & np.uint32(params.strong_mask)) == 0
    for p in np.flatnonzero(hit & (is_strong == strong)):
        if p < 7:
            continue
        pat = data[p - 7:p + 1]
        probe = np.zeros(64, np.uint8)
        probe[20:28] = pat
        hp = anchor_hash_np(probe, params)
        if np.flatnonzero((hp & np.uint32(params.seg_mask)) == 0
                          ).tolist() == [27]:
            return pat
    raise AssertionError("no clean pattern in the probe corpus")


def edge_stream(params) -> tuple[np.ndarray, list[tuple[int, int]]]:
    """Zeros (no anchor) with anchors planted at the windows' edges, and
    the (boundary, kind) list the rule must give for them: a strong
    anchor one byte below the strong window and one at its top edge; one
    at its bottom edge; one a byte above the top edge (a forced cut); a
    plain anchor a byte below the kept window's bottom edge (forced
    again); one at that edge."""
    sp, ap = _pattern(params, True), _pattern(params, False)
    smin, lo, hi = params.strong_min, params.seg_min, params.seg_max
    plan = []                  # (boundary this anchor would give, pattern)
    want = []
    s = 0
    plan += [(s + smin - 1, sp), (s + hi, sp)]
    want.append((s + hi, CUT_STRONG))
    s += hi
    plan.append((s + smin, sp))
    want.append((s + smin, CUT_STRONG))
    s += smin
    plan.append((s + hi + 1, sp))
    want.append((s + hi, CUT_FORCED))
    s += hi
    plan.append((s + lo - 1, ap))
    want.append((s + hi, CUT_FORCED))
    s += hi
    plan.append((s + lo, ap))
    want.append((s + lo, CUT_WINDOW))
    s += lo
    n = s + hi - 100
    want.append((n, CUT_END))
    data = np.zeros(n, np.uint8)
    for b, pat in plan:
        data[b - 8:b] = pat
    return data, want


def dense_stream(params, strong: bool, n: int = 60000) -> np.ndarray:
    """An anchor (strong or plain) every eight bytes: every tile's planes
    are full, and with strong ones every cut lands on strong_min — the
    most segments a region can hold."""
    return np.tile(_pattern(params, strong), n // 8)


CASES = {
    "random": lambda: corpus(150001, seed=71),
    "zeros": lambda: np.zeros(100000, np.uint8),
    "dense": lambda: dense_stream(SMALL, strong=False),
    "dense-strong": lambda: dense_stream(SMALL, strong=True),
    "edges": lambda: edge_stream(SMALL)[0],
}


def test_edge_stream_cuts_where_the_rule_says():
    data, want = edge_stream(SMALL)
    bounds, kinds = select_segments_kinds(*anchors_np(data, SMALL),
                                          data.shape[0], SMALL)
    assert list(zip(bounds.tolist(), kinds.tolist())) == want


def test_extreme_streams_take_the_arm_they_were_built_for():
    def kinds_of(data):
        return cut_counts(select_segments_kinds(
            *anchors_np(data, SMALL), data.shape[0], SMALL)[1])

    seg, strong, window, forced = kinds_of(CASES["zeros"]())
    assert (strong, window) == (0, 0) and forced == seg - 1 > 10
    seg, strong, window, forced = kinds_of(CASES["dense"]())
    assert (strong, forced) == (0, 0) and window == seg - 1 > 10
    seg, strong, window, forced = kinds_of(CASES["dense-strong"]())
    assert (window, forced) == (0, 0) and strong == seg - 1
    # every strong cut within a tile of strong_min (a tile keeps its
    # first strong position only)
    assert seg >= 60000 // (SMALL.strong_min + TILE_BYTES)


@pytest.mark.parametrize("case", sorted(CASES))
def test_four_implementations_cut_alike(case):
    """NumPy oracle, C++ walk, XLA scan and the Pallas walk (interpret
    mode) give the same anchor planes, boundaries, cut kinds and chunks —
    as a whole stream and as a region with a carried start."""
    import jax
    import jax.numpy as jnp

    import dfs_tpu.ops.cdc_anchored as A
    from dfs_tpu.native import native_anchored_spans_region
    from dfs_tpu.ops.select_pallas import make_select_fn_pallas

    data = CASES[case]()
    n = int(data.shape[0])
    zeros8 = np.zeros(8, np.uint8)
    kept, strong = anchors_np(data, SMALL)
    words = A.region_buffer(data, zeros8, SMALL)
    m_words = A.recover_m_words(words.shape[0], SMALL)
    m_tiles = m_words * 4 // TILE_BYTES
    tiles = A.make_anchor_fn(SMALL, m_words)(jnp.asarray(words))
    np.testing.assert_array_equal(
        np.asarray(tiles), anchor_planes_np(kept, strong, m_tiles))

    cap = A.segment_cap(SMALL, m_words)
    for start0, final in ((0, True), (0, False), (777, True), (777, False)):
        bounds, kinds = select_segments_kinds(kept, strong, n, SMALL,
                                              start0=start0, final=final)
        counts = cut_counts(kinds)
        args = (tiles, jnp.int32(start0), jnp.int32(n), jnp.bool_(final))
        for name, fn in (
                ("xla", A.make_select_fn(SMALL, m_tiles, cap)),
                ("pallas", make_select_fn_pallas(SMALL, m_tiles, cap,
                                                 interpret=True))):
            got, cuts = jax.device_get(fn(*args))
            assert got[got >= 0].tolist() == bounds.tolist(), name
            assert (got[len(bounds):] == -1).all(), name
            assert tuple(cuts.tolist()) == counts[1:], name

        want_spans, want_end = A.region_spans_np(data, zeros8, start0,
                                                 final, SMALL)
        ck = np.zeros(4, np.uint64)
        native = native_anchored_spans_region(data, zeros8, start0, final,
                                              SMALL, cut_kinds=ck)
        if native is not None:
            assert [tuple(r) for r in native[0].tolist()] == want_spans
            assert native[1] == want_end
            assert ck.tolist() == np.bincount(kinds, minlength=4).tolist()

        chunks, consumed, got_counts = A.region_chunks(
            data, zeros8, start0, final, SMALL, lane_multiple=8)
        assert [(o, ln) for o, ln, _ in chunks] == want_spans
        assert consumed == want_end == (n if final else bounds[-1]
                                        if len(bounds) else start0)
        assert got_counts == counts


# ------------------------------------------------------- re-synchronising --
# The properties the last-anchor rule failed while every parity test
# passed: lane utilisation was bought with dedup, unseen (PERF.md §6,
# PR 37). Production parameters, through the CPU engine.

def _cut_ends(data: np.ndarray, params) -> np.ndarray:
    from dfs_tpu.native import native_anchored_spans

    spans = native_anchored_spans(data, params)
    if spans is None:
        spans = np.asarray(chunk_spans_anchored_np(data, params))
    return spans[:, 0] + spans[:, 1]


def test_cuts_resynchronise_after_an_insert():
    """Two versions of a stream differing by one insert of 512*k bytes
    (0.5-16 KiB, a tar's shift): in content coordinates their cuts
    coincide again within 4 strong gaps (256 KiB) of the insert in >= 95 %
    of 64 seeds. (Read at PR 37: 62 of 64, the worst 598 KiB; the
    last-anchor rule alone: 32 of 64, median 258 KiB, 1.4 MiB — the
    stream's end — in the worst.)"""
    params = AnchoredCdcParams()
    within = 4 * (params.strong_mask + 1)
    ok = 0
    for seed in range(64):
        rng = np.random.default_rng([37, seed])
        base = rng.integers(0, 256, size=3 * 2**19, dtype=np.uint8)
        k = int(rng.integers(1, 33))
        at = int(rng.integers(128 * 1024, 256 * 1024))
        ins = rng.integers(0, 256, size=512 * k, dtype=np.uint8)
        edited = np.concatenate([base[:at], ins, base[at:]])
        a = _cut_ends(base, params)
        b = _cut_ends(edited, params) - 512 * k
        differ = set(a[a > at].tolist()) ^ set(b[b > at].tolist())
        ok += (max(differ) if differ else at) - at <= within
    assert ok >= 61, f"cuts re-synchronised in only {ok} of 64 seeds"


# A source tree as a tar and its next release — BASELINE.json configs[3]'s
# workload shape: thousands of small files, edits that INSERT and DELETE
# lines (every edited file shifts all later tar content by an unaligned
# delta), whole-file adds and removes, renames (whole 512-byte record runs
# shift). The generator PR 37 pinned its thresholds on (it lived in
# bench_dedup_tree.py until PR 46).

_WORDS = None


def _tree_line(rng, width: int = 60) -> bytes:
    """Source-ish text line: identifier-shaped tokens, stable dictionary
    so repeated lines across files/versions dedup like real code."""
    global _WORDS
    if _WORDS is None:
        wrng = np.random.default_rng(99)
        _WORDS = [bytes(wrng.integers(97, 123, size=int(n)).tolist())
                  for n in wrng.integers(3, 12, size=4096)]
    k = rng.integers(2, 9)
    toks = [
        _WORDS[int(i)] for i in rng.integers(0, len(_WORDS), size=int(k))]
    return b" ".join(toks)[:width] + b"\n"


def make_tree(rng, n_files: int, mean_file_bytes: int):
    """{path: list-of-lines} — a synthetic source tree."""
    tree = {}
    for i in range(n_files):
        nbytes = max(256, int(rng.exponential(mean_file_bytes)))
        lines = []
        sz = 0
        while sz < nbytes:
            ln = _tree_line(rng)
            lines.append(ln)
            sz += len(ln)
        d1, d2 = int(rng.integers(0, 12)), int(rng.integers(0, 8))
        tree[f"src/d{d1:02d}/m{d2}/f{i:05d}.c"] = lines
    return tree


def evolve(rng, tree: dict, churn: float = 0.04) -> dict:
    """One 'release': edit ~churn of files (insert AND delete lines),
    add/remove a few files, rename a few (content unchanged)."""
    out = dict(tree)
    paths = list(out.keys())
    n_edit = max(1, int(len(paths) * churn))
    for p in rng.choice(paths, size=n_edit, replace=False):
        lines = list(out[p])
        for _ in range(int(rng.integers(1, 6))):
            at = int(rng.integers(0, max(1, len(lines))))
            op = int(rng.integers(0, 3))
            if op == 0:                          # insert a few lines
                for j in range(int(rng.integers(1, 4))):
                    lines.insert(at + j, _tree_line(rng))
            elif op == 1 and len(lines) > 3:     # delete a few lines
                del lines[at:at + int(rng.integers(1, 4))]
            else:                                # modify one line
                if lines:
                    lines[at % len(lines)] = _tree_line(rng)
        out[p] = lines
    # whole-file adds and removes (~churn/4 each)
    for p in rng.choice(paths, size=max(1, n_edit // 4), replace=False):
        out.pop(p, None)
    base = max(int(p.split("f")[-1].split(".")[0])
               for p in out if "f" in p) + 1
    for j in range(max(1, n_edit // 4)):
        d1, d2 = int(rng.integers(0, 12)), int(rng.integers(0, 8))
        nf = make_tree(rng, 1, 4096)
        out[f"src/d{d1:02d}/m{d2}/f{base + j:05d}.c"] = \
            next(iter(nf.values()))
    # renames (content identical — pure path shift in the tar)
    paths = list(out.keys())
    for p in rng.choice(paths, size=max(1, n_edit // 6), replace=False):
        if p in out:
            out[p.replace("/m", "/r")] = out.pop(p)
    return out


def tar_bytes(tree: dict) -> bytes:
    """Deterministic uncompressed tar (sorted paths, zeroed metadata) —
    the 'snapshot' artifact each version uploads."""
    buf = io.BytesIO()
    with tarfile.open(fileobj=buf, mode="w", format=tarfile.GNU_FORMAT) \
            as tf:
        for p in sorted(tree):
            body = b"".join(tree[p])
            info = tarfile.TarInfo(name=p)
            info.size = len(body)
            info.mtime = 0
            tf.addfile(info, io.BytesIO(body))
    return buf.getvalue()


def test_tree_snapshot_with_2pct_of_files_edited_is_refound():
    """A source tree of 300 files as a tar, and its next version (2 % of
    the files edited, some added, removed and renamed): at most a quarter
    of the version is stored anew at 12 KiB a file, and at most 0.12 at
    32 KiB a file, where the edits lie further apart than the old rule's
    walk took to agree again (read at PR 37: 0.153 and 0.074; the
    last-anchor rule alone: 0.178 and 0.213)."""
    params = AnchoredCdcParams()
    for mean_file_bytes, at_most in ((12 * 1024, 0.25), (32 * 1024, 0.12)):
        rng = np.random.default_rng(17)
        tree = make_tree(rng, 300, mean_file_bytes)
        versions = [np.frombuffer(tar_bytes(t), np.uint8)
                    for t in (tree, evolve(rng, tree, churn=0.02))]
        tables = []
        for v in versions:
            ends = _cut_ends(v, params)
            starts = np.concatenate([[0], ends[:-1]])
            tables.append({hashlib.sha256(v[o:e]).digest(): int(e - o)
                           for o, e in zip(starts.tolist(), ends.tolist())})
        anew = sum(ln for dg, ln in tables[1].items()
                   if dg not in tables[0])
        assert anew / versions[1].shape[0] <= at_most, mean_file_bytes


# ---------------------------------------------------------- device parity --

def _sparse(n: int = 100000) -> np.ndarray:
    """Low entropy: zeros with one random byte every 997 — few anchors
    and few chunk candidates, so most cuts are forced ones."""
    data = np.zeros((n,), dtype=np.uint8)
    data[::997] = corpus(len(data[::997]), seed=8)
    return data


# every size class of the block grid and of the segment rule: empty, under
# / at / over one block, one segment, ragged tails, whole multiples of a
# lane (4096) — and the inputs where content decides nothing
PARITY_INPUTS = {
    **{str(n): (lambda n=n: corpus(n, seed=n + 100))
       for n in (0, 1, 63, 64, 65, 4096, 4097, 5000, 3 * 4096, 40000,
                 100001, 64 * 4096, 300000, 300001)},
    "zeros": lambda: np.zeros((100000,), dtype=np.uint8),
    "repeat": lambda: np.tile(corpus(256, seed=6), 400),
    "sparse": _sparse,
}


@pytest.mark.parametrize("name", list(PARITY_INPUTS))
def test_device_matches_oracle(name):
    """The device chain, the CPU engine and the NumPy oracle give one
    chunk table — spans and digests — and it tiles the input in chunks of
    at most ``max_blocks``, each named by its sha256."""
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter

    data = PARITY_INPUTS[name]()
    got = batch_chunks_anchored(data, SMALL, lane_multiple=8)
    assert got == chunk_file_anchored_np(data, SMALL)
    cpu = AnchoredCpuFragmenter(SMALL).chunk(data.tobytes())
    assert [(c.offset, c.length, c.digest) for c in cpu] == got
    end = 0
    for o, ln, dg in got:
        assert o == end and 0 < ln <= SMALL.chunk.max_blocks * 64
        assert dg == hashlib.sha256(data[o:o + ln].tobytes()).hexdigest()
        end = o + ln
    assert end == data.shape[0]


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
    return AnchoredTpuFragmenter(SMALL, lane_multiple=8, **kw)


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


@pytest.mark.parametrize("case", sorted(CASES))
def test_three_way_region_streaming_equality(case):
    """Large-region one-shot == tiny-region walk == streaming, on the
    device engine and on the CPU engine, and all equal the NumPy
    whole-stream oracle — the transparency property the region/carry
    design exists to guarantee — and the owner's cut counters add up."""
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter

    arr = CASES[case]()
    data = arr.tobytes()
    want = [(o, ln, dg) for o, ln, dg in chunk_file_anchored_np(arr, SMALL)]

    one_shot_frag = anchored_frag(region_bytes=1 << 30)
    one_shot = one_shot_frag.chunk(data)
    tiny_frag = anchored_frag()            # 16 KiB regions: many carries
    tiny = tiny_frag.chunk(data)
    blocks = [data[i:i + 7333] for i in range(0, len(data), 7333)]
    streamed = tiny_frag.manifest_stream(blocks, name="f").chunks
    cpu = AnchoredCpuFragmenter(SMALL, region_bytes=16384)
    cpu_streamed = cpu.manifest_stream(blocks, name="f").chunks

    for got in (one_shot, tiny, list(streamed), cpu.chunk(data),
                list(cpu_streamed)):
        assert [(c.offset, c.length, c.digest) for c in got] == want

    counts = cut_counts(select_segments_kinds(
        *anchors_np(arr, SMALL), arr.shape[0], SMALL)[1])
    keys = ("segments", "strong_cuts", "window_cuts", "forced_cuts")
    st = one_shot_frag.device_stats()
    assert tuple(st[k] for k in keys) == counts
    st = tiny_frag.device_stats()           # chunk() and the stream: twice
    assert tuple(st[k] for k in keys) == tuple(2 * c for c in counts)


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


def test_describe_states_the_rule_and_round_trips():
    """``describe()`` carries the rule's two constants, a fragmenter
    rebuilt from it cuts alike, and a description from before the rule
    (no ``strong_min``) is refused, not guessed at."""
    from dfs_tpu.fragmenter.base import fragmenter_from_description
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter

    desc = AnchoredCpuFragmenter(SMALL).describe()
    assert (desc["strong_min"], desc["strong_bits"]) == (1024, 1)
    rebuilt = fragmenter_from_description(desc)
    assert rebuilt.params == SMALL
    data = corpus(60000, seed=45).tobytes()
    assert rebuilt.chunk(data) == AnchoredCpuFragmenter(SMALL).chunk(data)
    old = {k: v for k, v in desc.items()
           if k not in ("strong_min", "strong_bits")}
    with pytest.raises(ValueError, match="strong_min"):
        fragmenter_from_description(old)


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


def test_factory_grows_the_lane_for_a_large_max_chunk():
    """CLI values legal for ``cdc`` must not crash node start-up on an
    anchored kind: a ``--max-chunk`` beyond the default lane grows the
    lane (and the segment window pinned to it) instead of failing
    AlignedCdcParams' ``max <= strip`` check."""
    from dfs_tpu.config import CDCParams
    from dfs_tpu.fragmenter.base import get_fragmenter

    big = get_fragmenter("cdc-anchored", cdc_params=CDCParams(
        min_size=2048, avg_size=8192, max_size=256 * 1024)).params
    assert big.chunk.max_blocks == 4096
    assert big.chunk.strip_blocks >= big.chunk.max_blocks
    assert big.seg_max == big.chunk.strip_blocks * 64
    assert TILE_BYTES <= big.seg_min < big.seg_max


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


def _random_planes(rng, m_tiles, density=2, strong_density=8):
    """Random pass-A-shaped [3, m_tiles] tile planes: ~1/density tiles
    hold a first anchor, about half of those also a second (strictly
    larger, same tile), ~1/strong_density a strong position anywhere in
    the tile — mirrors make_anchor_fn's output invariants."""
    tiles = np.full((3, m_tiles), 2**30, np.int32)
    k = max(1, m_tiles // density)
    idx = rng.choice(m_tiles, size=k, replace=False)
    off1 = rng.integers(0, TILE_BYTES - 1, size=k)   # <= TILE_BYTES - 2
    tiles[0, idx] = (idx * TILE_BYTES + off1).astype(np.int32)
    has2 = rng.random(k) < 0.5
    off2 = off1 + 1 + rng.integers(0, TILE_BYTES - 1 - off1)
    tiles[1, idx[has2]] = (idx[has2] * TILE_BYTES
                           + off2[has2]).astype(np.int32)
    ks = max(1, m_tiles // strong_density)
    sidx = rng.choice(m_tiles, size=ks, replace=False)
    tiles[2, sidx] = (sidx * TILE_BYTES
                      + rng.integers(0, TILE_BYTES, size=ks)).astype(np.int32)
    return tiles


def _assert_selects_agree(params, tiles, m_tiles, cap, start0, n, final):
    """XLA scan == Pallas walk (interpret) == the NumPy rule, boundaries
    and cut counts."""
    import jax
    import jax.numpy as jnp

    import dfs_tpu.ops.cdc_anchored as A
    from dfs_tpu.ops.select_pallas import make_select_fn_pallas

    args = (jnp.asarray(tiles), jnp.int32(start0), jnp.int32(n),
            jnp.bool_(final))
    ref, ref_cuts = jax.device_get(
        A.make_select_fn(params, m_tiles, cap)(*args))
    got, got_cuts = jax.device_get(
        make_select_fn_pallas(params, m_tiles, cap, interpret=True)(*args))
    np.testing.assert_array_equal(ref, got)
    np.testing.assert_array_equal(ref_cuts, got_cuts)
    bounds, kinds = select_segments_kinds(
        *A.planes_positions(tiles), n, params, start0=start0, final=final)
    assert ref[ref >= 0].tolist() == bounds.tolist()
    assert tuple(ref_cuts.tolist()) == cut_counts(kinds)[1:]


def test_pallas_select_matches_xla_scan():
    """The on-core Pallas selection walk (ops.select_pallas) must agree
    with the XLA scan bit-for-bit: random anchor-tile patterns, final
    and non-final regions, zero and carried start0. Interpret mode on
    CPU; on real TPU the same kernel is exercised end-to-end by
    bench.py's hashlib gates (make_chain_fn picks it there)."""
    import dfs_tpu.ops.cdc_anchored as A

    rng = np.random.default_rng(11)
    params = SMALL
    for trial in range(2):
        n = int(rng.integers(20000, 120000))
        m_tiles = 1 << (-(-n // TILE_BYTES) - 1).bit_length()
        cap = A.segment_cap(params, m_tiles * TILE_BYTES // 4)
        tiles = _random_planes(rng, m_tiles)
        for final in (True, False):
            for start0 in (0, 1234):
                _assert_selects_agree(params, tiles, m_tiles, cap, start0,
                                      n, final)


@pytest.mark.parametrize("strong_density", [8, 128, 10**9])
def test_pallas_select_large_region_block_addressing(strong_density):
    """Production-shaped geometry (32K/96K/128K segments, 4 MiB region):
    t0 crosses the 1024-entry block boundary many times, so the kernel's
    8-row-aligned dynamic block read and (row + r0)*128 + col global
    index arithmetic are actually exercised (the small-n test's windows
    all start in block zero) — with the 193-tile strong window holding a
    strong anchor nearly always, at production's rate, and never."""
    import dfs_tpu.ops.cdc_anchored as A
    from dfs_tpu.ops.select_pallas import select_window_tiles

    params = AnchoredCdcParams()        # production segment geometry
    assert select_window_tiles(params) == 193
    n = 4 * 2**20
    m_tiles = n // TILE_BYTES           # 8192 tiles -> t0 up to ~8192
    cap = A.segment_cap(params, n // 4)
    rng = np.random.default_rng(12)
    tiles = _random_planes(rng, m_tiles, density=16,
                           strong_density=strong_density)
    for final in (True, False):
        _assert_selects_agree(params, tiles, m_tiles, cap, 0, n, final)
