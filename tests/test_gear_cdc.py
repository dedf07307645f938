"""Gear CDC correctness (the ``cdc`` kind): the windowed NumPy bitmap must
match the sequential rolling-hash specification bit-for-bit, and chunking must
reconstruct byte-identically (north star: BASELINE.json)."""

import numpy as np

from dfs_tpu.config import CDCParams
from dfs_tpu.fragmenter.cdc_cpu import (
    CpuCdcFragmenter,
    cdc_cuts_ref,
    gear_bitmap_numpy,
    gear_hashes_seq,
)
from dfs_tpu.utils.hashing import gear_table

PARAMS = CDCParams(min_size=64, avg_size=256, max_size=1024)
SMALL = CDCParams(min_size=32, avg_size=64, max_size=256)


def _corpora(rng):
    return {
        "random": rng.integers(0, 256, size=20_000, dtype=np.uint8).tobytes(),
        "zeros": bytes(5_000),
        "repeat": b"abcdefgh" * 2_000,
        "short": b"xyz",
        "empty": b"",
        "window": bytes(rng.integers(0, 256, size=31, dtype=np.uint8)),
    }


def test_numpy_bitmap_matches_rolling(rng):
    table = gear_table()
    data = rng.integers(0, 256, size=8_192, dtype=np.uint8)
    seq = gear_hashes_seq(data.tobytes(), table)
    mask = PARAMS.mask
    np.testing.assert_array_equal(
        (seq & mask) == 0, gear_bitmap_numpy(data, table, mask))


def test_cpu_cuts_match_reference_spec(rng):
    frag = CpuCdcFragmenter(PARAMS)
    for name, data in _corpora(rng).items():
        got = frag.cuts(data).tolist()
        want = cdc_cuts_ref(data, PARAMS)
        assert got == want, f"corpus {name}: {got[:5]} != {want[:5]}"


def test_chunk_size_bounds(rng):
    data = rng.integers(0, 256, size=100_000, dtype=np.uint8).tobytes()
    chunks = CpuCdcFragmenter(PARAMS).chunk(data)
    assert sum(c.length for c in chunks) == len(data)
    for c in chunks[:-1]:
        assert PARAMS.min_size <= c.length <= PARAMS.max_size
    assert chunks[-1].length <= PARAMS.max_size


def test_reconstruction_byte_identical(rng):
    data = rng.integers(0, 256, size=30_000, dtype=np.uint8).tobytes()
    chunks = CpuCdcFragmenter(SMALL).chunk(data)
    rebuilt = b"".join(data[c.offset:c.offset + c.length] for c in chunks)
    assert rebuilt == data


def test_dedup_shift_resilience(rng):
    """Content-defined chunking's raison d'être: inserting bytes near the
    front must leave most downstream chunk digests unchanged — the fixed-N
    reference splitter (StorageNode.java:138-155) shares ~0% instead."""
    base = rng.integers(0, 256, size=200_000, dtype=np.uint8).tobytes()
    edited = base[:100] + b"INSERTED!" + base[100:]
    frag = CpuCdcFragmenter(PARAMS)
    d1 = {c.digest for c in frag.chunk(base)}
    d2 = [c.digest for c in frag.chunk(edited)]
    shared = sum(1 for d in d2 if d in d1)
    assert shared / len(d2) > 0.9


def test_forced_cuts_on_zeros():
    """All-zero input has no candidates past the first bytes → every chunk is
    forced at max_size (pathological case from SURVEY.md §7.4)."""
    data = bytes(PARAMS.max_size * 3 + 10)
    cuts = CpuCdcFragmenter(PARAMS).cuts(data).tolist()
    assert cuts == cdc_cuts_ref(data, PARAMS)
    assert all(b - a <= PARAMS.max_size for a, b in zip([0] + cuts, cuts))
