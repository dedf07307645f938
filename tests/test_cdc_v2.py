"""The block-grid Gear candidate math of the anchored chunker
(ops/cdc_v2.py): the NumPy oracle's semantics, and the plain-XLA device
functions bit-for-bit against it, strip by strip (CPU backend). What a
whole stream's chunks owe their caller is held in test_cdc_anchored.py
and test_fragmenter_contract.py.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from dfs_tpu.ops.cdc_v2 import (BLOCK, AlignedCdcParams, candidates_np,
                                cut_capacity, g_table,
                                gear_candidates_device, select_cuts_blocks,
                                select_cuts_device)

SMALL = AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                         strip_blocks=64)  # 4 KiB strips for fast tests


def corpus(n, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=n, dtype=np.uint8)


def strips(data, params, lane_multiple=8):
    """[N] uint8 -> (words_t [strip_blocks*16, S] uint32, S): the resident
    layout of ops/cdc_v2.py, a strip every ``strip_len`` bytes, zero-padded
    to whole strips and S to ``lane_multiple``."""
    sl = params.strip_len
    s = -(-max(1, -(-data.shape[0] // sl)) // lane_multiple) * lane_multiple
    buf = np.zeros((s * sl,), dtype=np.uint8)
    buf[:data.shape[0]] = data
    words = buf.view(">u4").astype(np.uint32)
    return np.ascontiguousarray(
        words.reshape(s, params.strip_blocks * 16).T), s


def strip_spans(data, params):
    """The oracle, strip by strip: [(offset, length)] of the chunks when
    chunking restarts every ``strip_len`` bytes."""
    n, sl = data.shape[0], params.strip_len
    spans = []
    for s0 in range(0, n, sl):
        seg = data[s0:s0 + sl]
        cuts = select_cuts_blocks(
            np.flatnonzero(candidates_np(seg, params)),
            -(-seg.shape[0] // BLOCK), params)
        prev = 0
        for c in cuts.tolist():
            end = min(c * BLOCK, seg.shape[0])
            spans.append((s0 + prev * BLOCK, end - prev * BLOCK))
            prev = c
    return spans


# ---------------------------------------------------------------- oracle --

def test_select_cuts_blocks_forced_max():
    # no candidates at all -> cuts every max_blocks, tail remainder
    cuts = select_cuts_blocks(np.array([], dtype=np.int64), 40, SMALL)
    assert cuts.tolist() == [16, 32, 40]


def test_cut_capacity_bounds_real_cut_count():
    """The chain provisions its cut table from this bound: random bytes
    stay under it, and bytes that cut at every ``min_blocks`` (the worst
    case it is written for) do too."""
    dense = next(v for v in range(256) if candidates_np(
        np.full(BLOCK, v, np.uint8), SMALL).any())
    for data in (corpus(300000, seed=5),
                 np.full(300000, dense, np.uint8)):
        s = -(-data.shape[0] // SMALL.strip_len)
        assert len(strip_spans(data, SMALL)) <= cut_capacity(s, SMALL)


def test_g_table_matches_arithmetic():
    t = g_table(SMALL.seed)
    assert t.dtype == np.uint32
    assert len(set(t.tolist())) > 250  # essentially all distinct


# ---------------------------------------------------------------- device --

@pytest.mark.parametrize("n", [4096 * 3, 300000, 64 * 4096])
def test_device_candidates_match_oracle(n):
    data = corpus(n, seed=11)
    words_t, s = strips(data, SMALL)
    cand_dev = np.asarray(gear_candidates_device(jnp.asarray(words_t), SMALL))
    want = candidates_np(data, SMALL)
    nb_total = n // BLOCK
    # device layout: [bps, S]; strip s block t <-> global block s*bps + t
    got = cand_dev.T.reshape(-1)[:nb_total]
    # blocks whose window crosses the padded tail are only meaningful if real
    assert np.array_equal(got, want)


def test_device_selection_matches_oracle():
    n = 300000
    data = corpus(n, seed=12)
    p = SMALL
    words_t, s = strips(data, p)
    cand = gear_candidates_device(jnp.asarray(words_t), p)
    nb_real = -(-n // BLOCK)
    real = np.clip(nb_real - np.arange(s) * p.strip_blocks, 0, p.strip_blocks)
    cut = np.asarray(select_cuts_device(cand, jnp.asarray(real, jnp.int32), p)[0])
    # rebuild spans from cutflag and compare with oracle spans
    spans = []
    for lane in range(s):
        ts = np.flatnonzero(cut[:, lane])
        prev = 0
        for t in ts.tolist():
            off = lane * p.strip_len + prev * BLOCK
            end = min(lane * p.strip_len + (t + 1) * BLOCK, n)
            spans.append((off, end - off))
            prev = t + 1
    spans.sort()
    assert spans == strip_spans(data, p)
