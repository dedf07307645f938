"""Stage-level profile of the anchored device chain (diagnostic, not a
driver benchmark). Times each dispatch of region_dispatch — anchor ->
select -> descriptors -> scan_half (Pallas repack + fused
candidates/selection/SHA) -> compact_half — plus the fused kernel and
repack in isolation.

Estimator: difference-of-mins (bench.py's discipline — round 3 found
min-of-per-rep-slopes biased LOW under the shared chip's bursty
contention), with all stages sampled INTERLEAVED per round so a burst
inflates every stage equally rather than whichever ran during it.
Sub-stage numbers still jitter with chip load; the "full chain" row is
the trustworthy one and stages are indicative.

Usage: python bench_profile.py [region_mib] [reps]
"""

from __future__ import annotations

import sys
import time

import numpy as np


def main() -> int:
    region = (int(sys.argv[1]) if len(sys.argv) > 1 else 64) * 2**20
    reps = int(sys.argv[2]) if len(sys.argv) > 2 else 10

    import jax
    import jax.numpy as jnp

    from dfs_tpu.ops import cdc_anchored as A
    from dfs_tpu.utils.device import bench_device

    print(bench_device("bench_profile.py"), file=sys.stderr)

    from dfs_tpu.ops.cdc_anchored import (AnchoredCdcParams, region_buffer,
                                          region_dispatch)
    from dfs_tpu.ops.layout import bswap_transpose
    from dfs_tpu.ops.repack import repack_lanes
    from dfs_tpu.ops.sha256_strip import strip_chunk_states

    params = AnchoredCdcParams()
    cp = params.chunk
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=region, dtype=np.uint8)
    words = jax.device_put(region_buffer(data, np.zeros((8,), np.uint8),
                                         params))

    m_words = A.recover_m_words(int(words.shape[0]), params)
    m_tiles = m_words * 4 // A.TILE_BYTES
    cap = A.segment_cap(params, m_words)
    # stage sizing must match the PRODUCTION chain (cap_mode='tight'):
    # the lane tables are tight-provisioned while the select scan runs
    # at the full bound — otherwise the stage rows would overshoot the
    # 'full chain' row by exactly the padding-lane cost
    s_pad = A._tight_segment_lanes(params, m_words, 128)
    print(f"region={region / 2**20:.0f} MiB m_words={m_words} cap={cap} "
          f"s_pad={s_pad} (tight lanes)", file=sys.stderr)

    anchor = A.make_anchor_fn(params, m_words)
    select = A.make_select(params, m_tiles, cap)   # Pallas walk on TPU
    desc = A.make_descriptor_fn(params, cap, s_pad)
    seg = A.make_anchored_segment_fn(params, int(words.shape[0]), s_pad)

    n = A._dev_i32(region)
    z = A._dev_i32(0)
    fin = A._dev_bool(True)

    tiles = anchor(words)
    bounds, _cuts = select(tiles, z, n, fin)
    d = desc(bounds, z)
    (starts, seg_lens, w_off, sh8, real_blocks, tail_len, consumed,
     nseg) = d
    jax.block_until_ready(d)
    scan_half, compact_half = seg.halves
    sh_out = jax.block_until_ready(
        scan_half(words, w_off, sh8, real_blocks))

    lane_words = cp.strip_blocks * 16

    @jax.jit
    def repack_t(words, w_off, sh8):
        return bswap_transpose(repack_lanes(words, w_off, sh8, lane_words))

    words_t = jax.block_until_ready(repack_t(words, w_off, sh8))

    @jax.jit
    def fused_only(words_t, real_blocks):
        return strip_chunk_states(words_t, real_blocks, cp.seed, cp.mask,
                                  cp.min_blocks, cp.max_blocks)

    jax.block_until_ready(fused_only(words_t, real_blocks))

    stages = [
        ("anchor", lambda: anchor(words)),
        ("select", lambda: select(tiles, z, n, fin)),
        ("descriptors", lambda: desc(bounds, z)),
        ("scan_half", lambda: scan_half(words, w_off, sh8, real_blocks)),
        ("compact_half", lambda: compact_half(
            *sh_out, words, w_off, sh8, real_blocks, tail_len, starts,
            seg_lens)),
        ("  repack+bswapT", lambda: repack_t(words, w_off, sh8)),
        ("  fused cand+sel+SHA", lambda: fused_only(words_t, real_blocks)),
        ("full chain", lambda: region_dispatch(words, region, 0, True,
                                               params)),
    ]
    for _, fn in stages:
        jax.block_until_ready(fn())          # compile everything first

    acc = {name: ([], []) for name, _ in stages}
    for rep in range(reps):
        if rep:
            time.sleep(0.3)
        for name, fn in stages:
            for k, a in ((3, acc[name][0]), (12, acc[name][1])):
                jax.block_until_ready(fn())
                t0 = time.perf_counter()
                out = None
                for _ in range(k):
                    out = fn()
                jax.block_until_ready(out)
                a.append(time.perf_counter() - t0)

    total_ms = None
    for name, _ in stages:
        lo, hi = acc[name]
        dt = (min(hi) - min(lo)) / 9
        if dt <= 0:
            # sub-jitter stage: the 9-dispatch delta drowned in sync
            # noise — report as below measurement floor, not a negative
            print(f"{name:>22}:  <0.05 ms  (below noise floor)",
                  file=sys.stderr)
            continue
        print(f"{name:>22}: {dt * 1e3:7.2f} ms  "
              f"({region / dt / 2**30:6.2f} GiB/s)", file=sys.stderr)
        if name == "full chain":
            total_ms = dt * 1e3
    if total_ms:
        print(f"TOTAL {total_ms:.2f} ms -> "
              f"{region / (total_ms / 1e3) / 2**30:.2f} GiB/s",
              file=sys.stderr)
    else:
        print("TOTAL below noise floor — rerun", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
