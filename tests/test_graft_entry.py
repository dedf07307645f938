"""Driver contract: entry() compiles single-device; dryrun_multichip executes
the sharded step on the virtual 8-device mesh (it self-checks vs oracles)."""

import sys
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import __graft_entry__  # noqa: E402


def test_entry_compiles_and_runs():
    """entry() is the flagship anchored chain: jit-compile it whole and
    check the produced chunk table against the whole-stream oracle."""
    import hashlib

    from dfs_tpu.ops.cdc_anchored import AnchoredCdcParams
    from dfs_tpu.ops.cdc_v2 import AlignedCdcParams, digests_to_hex
    from dfs_tpu.ops.cdc_anchored import chunk_file_anchored_np

    fn, args = __graft_entry__.entry()
    jitted = jax.jit(fn)
    consumed, seg_of, count, q, offs, lens, dig, nseg, cuts = jitted(*args)
    assert int(seg_of) == 0
    cuts = np.asarray(cuts).tolist()
    assert int(nseg) == sum(cuts) + 1 and min(cuts[:2]) > 0
    count = int(np.asarray(count))
    assert count > 0
    assert int(np.asarray(consumed)) == 128 * 1024   # final region
    offs = np.asarray(offs)[:count]
    lens = np.asarray(lens)[:count]
    hexes = digests_to_hex(np.asarray(dig)[:count])

    params = AnchoredCdcParams(
        chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                               strip_blocks=64),
        seg_min=2048, seg_max=4096, seg_mask=2047,
        strong_bits=1)                               # mirrors entry()
    words, _start0 = args
    n = 128 * 1024
    data = np.ascontiguousarray(words).view(np.uint8)[8:8 + n]
    want = chunk_file_anchored_np(data, params)
    got = sorted(zip(offs.tolist(), lens.tolist(), hexes))
    assert got == sorted(want)
    o, ln, dg = got[0]
    assert dg == hashlib.sha256(data[o:o + ln].tobytes()).hexdigest()


def test_dryrun_multichip_8():
    __graft_entry__.dryrun_multichip(8)


def test_dryrun_multichip_4():
    __graft_entry__.dryrun_multichip(4)
