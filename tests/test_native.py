"""Native C++ core vs Python oracles (skipped cleanly if g++ unavailable)."""

import hashlib

import numpy as np
import pytest

from dfs_tpu.config import CDCParams
from dfs_tpu.fragmenter.cdc_cpu import CpuCdcFragmenter, cdc_cuts_ref
from dfs_tpu.native import get_lib, native_gear_cuts, native_sha256_many
from dfs_tpu.utils.hashing import gear_table
from tests.test_cdc_anchored import CASES, SMALL

pytestmark = pytest.mark.skipif(get_lib() is None,
                                reason="native toolchain unavailable")

PARAMS = CDCParams(min_size=64, avg_size=256, max_size=1024)


def test_native_sha256_batch(rng):
    msgs = [rng.integers(0, 256, size=int(n), dtype=np.uint8).tobytes()
            for n in [0, 1, 55, 56, 64, 65, 1000, 5000]]
    assert native_sha256_many(msgs) == [
        hashlib.sha256(m).hexdigest() for m in msgs]


def test_native_gear_cuts_match_spec(rng):
    table = gear_table()
    for n in [0, 10, 1000, 50_000]:
        data = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        got = native_gear_cuts(data, table, PARAMS.mask,
                               PARAMS.min_size, PARAMS.max_size)
        assert got.tolist() == cdc_cuts_ref(data, PARAMS)


def test_native_matches_numpy_fragmenter(rng):
    # compare against the NumPy bitmap+select pair DIRECTLY: frag.cuts()
    # itself routes through the native engine when available, which
    # would make this a tautology and leave the fallback untested
    from dfs_tpu.fragmenter.cdc_cpu import gear_bitmap_numpy
    from dfs_tpu.ops.boundary import select_cuts

    data = rng.integers(0, 256, size=80_000, dtype=np.uint8).tobytes()
    frag = CpuCdcFragmenter(PARAMS)
    got = native_gear_cuts(data, frag.table, PARAMS.mask,
                           PARAMS.min_size, PARAMS.max_size)
    arr = np.frombuffer(data, dtype=np.uint8)
    bitmap = gear_bitmap_numpy(arr, frag.table, PARAMS.mask)
    want = select_cuts(bitmap, arr.shape[0],
                       PARAMS.min_size, PARAMS.max_size)
    assert got.tolist() == want.tolist()


def test_native_anchored_spans_matches_oracle(rng):
    """dfs_anchored_spans must be bit-identical to the NumPy oracle on
    random, low-entropy, tiny, and partial-block streams (the anchored
    CPU fragmenter routes through it in production)."""
    from dfs_tpu.native import native_anchored_spans
    from dfs_tpu.ops.cdc_anchored import (AnchoredCdcParams,
                                          chunk_spans_anchored_np)
    from dfs_tpu.ops.cdc_v2 import AlignedCdcParams

    params = AnchoredCdcParams(
        chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                               strip_blocks=64),
        seg_min=2048, seg_max=4096, seg_mask=2047, strong_bits=1)
    cases = [
        rng.integers(0, 256, size=300_000, dtype=np.uint8),
        rng.integers(0, 256, size=1, dtype=np.uint8),
        rng.integers(0, 256, size=4097, dtype=np.uint8),   # partial block
        np.zeros(100_000, dtype=np.uint8),                  # anchor-free
        np.tile(rng.integers(0, 256, size=256, dtype=np.uint8), 400),
    ]
    for data in cases:
        got = native_anchored_spans(data, params)
        want = chunk_spans_anchored_np(data, params)
        assert [(int(o), int(ln)) for o, ln in got] == want


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("region_bytes", [8192, 16384, 1 << 20])
def test_native_region_walk_cuts_alike_whole_and_windowed(case,
                                                          region_bytes):
    """The C++ walk, whole and as a fixed-stride window walk with the
    carried tail (the CPU engine's streamed path), gives the NumPy
    oracle's spans and counts the oracle's cut kinds — random, anchor-
    free, anchor-dense and window-edge streams."""
    from dfs_tpu.native import (native_anchored_spans,
                                native_anchored_spans_region)
    from dfs_tpu.ops.cdc_anchored import (anchors_np,
                                          chunk_spans_anchored_np,
                                          select_segments_kinds)
    data = CASES[case]()
    n = int(data.shape[0])
    want = chunk_spans_anchored_np(data, SMALL)
    assert [tuple(r) for r in native_anchored_spans(data, SMALL).tolist()] \
        == want
    kinds = select_segments_kinds(*anchors_np(data, SMALL), n, SMALL)[1]

    stride = region_bytes - SMALL.seg_max
    spans, total = [], np.zeros(4, np.uint64)
    base = start0 = 0
    while True:
        final = base + region_bytes >= n
        lookback = np.zeros(8, np.uint8)
        take = min(8, base)
        lookback[8 - take:] = data[base - take:base]
        ck = np.zeros(4, np.uint64)
        got, consumed = native_anchored_spans_region(
            data[base:base + region_bytes], lookback, start0, final,
            SMALL, cut_kinds=ck)
        spans += [(base + int(o), int(ln)) for o, ln in got]
        total += ck
        if final:
            break
        start0 = consumed - stride
        base += stride
    assert spans == want
    assert total.tolist() == np.bincount(kinds, minlength=4).tolist()


def test_native_anchored_empty():
    from dfs_tpu.native import native_anchored_spans
    from dfs_tpu.ops.cdc_anchored import AnchoredCdcParams

    assert native_anchored_spans(b"", AnchoredCdcParams()).shape == (0, 2)
