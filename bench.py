"""Headline benchmark: anchored CDC chunk+hash throughput (GiB/s per chip).

The reference publishes no numbers (BASELINE.md) — the metric and the
north-star target come from BASELINE.json: >5 GiB/s sustained content-defined
chunking + per-chunk SHA-256 on one TPU v5e chip, with byte-identical
reconstruction. ``vs_baseline`` is therefore reported against the 5 GiB/s
north-star target (reference itself: single-threaded Java MessageDigest,
well under 1 GiB/s, but unmeasurable here — no JDK, SURVEY.md preamble).

Measures the **anchored two-level CDC pipeline** (dfs_tpu.ops.cdc_anchored)
— the production flagship: byte-granular content anchors re-sync the chunk
grid after unaligned edits (what that stores: the benchmark's
``stored_ratio``, PERF.md) while chunk+hash runs as the fused device chain
anchor-hash -> segment-select -> lane repack -> windowed-Gear candidates ->
lane-parallel selection -> strip-scan SHA-256 (Pallas, 8 blocks per grid
step) -> on-device compaction with device-side offsets. The chain
dispatches asynchronously end to end (the carry is a device scalar), so a
multi-region stream has no host sync until results are pulled.

Two numbers are reported (the round-1 conflation of compile+staging+compute
is gone):
- stdout JSON (the driver's record): **resident sustained** GiB/s — region
  buffer in HBM, min(difference-of-mins, paired-slope-median) over
  adjacent k=10/k=40 chain-timing pairs spread across ~2.5 minutes (raw
  samples embedded in the JSON). Scope: this is the KERNEL capability.
  The overlapped ingest path (double-buffered device_put,
  fragmenter/cdc_anchored.py) can in principle converge to it when
  staging outruns the chain (>= ~8 GB/s for a 64 MiB/8 ms region).
- stderr: warm end-to-end (staging + compute, compile excluded);
  bench_e2e_stream.py measures the end-to-end shape properly, against
  the CPU engine.

Refuses to run on anything but a TPU unless ``JAX_PLATFORMS=cpu`` asks
for the CPU by name (then the numbers are a rehearsal, not a speed).

Prints exactly ONE JSON line on stdout:
    {"metric": ..., "value": N, "unit": "GiB/s", "vs_baseline": N}
Diagnostics go to stderr.
"""

from __future__ import annotations

import hashlib
import json
import statistics
import sys
import time

import numpy as np

NORTH_STAR_GIBPS = 5.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_corpus(size: int, seed: int = 0) -> np.ndarray:
    """Synthetic corpus ~ '1 GiB synthetic tarball' config (BASELINE.json
    configs[2]), scaled: random base blocks with repeated sections so dedup
    has something to find."""
    rng = np.random.default_rng(seed)
    block = rng.integers(0, 256, size=4 * 1024 * 1024, dtype=np.uint8)
    reps = int(np.ceil(size / block.size))
    arr = np.tile(block, reps)[:size].copy()
    # splice fresh randomness into half the blocks so it's not pure repeats
    for off in range(0, size, 8 * 1024 * 1024):
        end = min(off + 4 * 1024 * 1024, size)
        arr[off:end] = rng.integers(0, 256, size=end - off, dtype=np.uint8)
    return arr


def main() -> int:
    size = int(sys.argv[1]) if len(sys.argv) > 1 else 256 * 1024 * 1024
    passes = max(2, int(sys.argv[2])) if len(sys.argv) > 2 else 12

    import jax

    from dfs_tpu.fragmenter.cdc_anchored import AnchoredTpuFragmenter
    from dfs_tpu.ops.cdc_anchored import (AnchoredCdcParams, region_buffer,
                                          region_collect, region_dispatch)
    from dfs_tpu.utils.device import bench_device

    log(bench_device("bench.py"))

    params = AnchoredCdcParams()         # 96..128 KiB segments, 2K/8K/64K
    region = 64 * 1024 * 1024
    size = max(size, region)
    frag = AnchoredTpuFragmenter(params, region_bytes=region)
    data = make_corpus(size)
    log(f"corpus: {size / 2**20:.0f} MiB, regions of {region / 2**20:.0f} MiB"
        f" (stride {frag.stride / 2**20:.2f} MiB, pipelined walk)")

    # ---- correctness gate + warm end-to-end (compile excluded) ----------
    chunks = frag.chunk(data.tobytes())           # compiles everything
    t0 = time.perf_counter()
    chunks = frag.chunk(data.tobytes())
    e2e = time.perf_counter() - t0
    assert sum(c.length for c in chunks) == size, "chunks must tile corpus"
    for c in (chunks[0], chunks[len(chunks) // 2], chunks[-1]):
        # raw hashlib ON PURPOSE: this gate is the independent oracle the
        # production digest path is checked AGAINST — routing it through
        # dfs_tpu.utils.hashing would make the check circular
        # dfslint: ignore[DFS004]
        want = hashlib.sha256(
            data[c.offset:c.offset + c.length].tobytes()).hexdigest()
        assert c.digest == want, "digest mismatch vs hashlib"
    log(f"warm end-to-end chunk() incl. host->device staging: {e2e:.2f}s "
        f"({size / e2e / 2**30:.3f} GiB/s), {len(chunks)} chunks, "
        f"mean {size / len(chunks):.0f} B")

    # ---- sustained resident throughput: multi-pass slope ----------------
    reg = data[:region]
    words = jax.device_put(region_buffer(reg, np.zeros((8,), np.uint8),
                                         params))
    out = region_dispatch(words, region, 0, True, params)
    spans, consumed, _cuts = region_collect(out)  # warm + sanity
    assert consumed == region and sum(ln for _, ln, _ in spans) == region
    # independent oracle, like the warm-path gate above
    # dfslint: ignore[DFS004]
    want = hashlib.sha256(reg[spans[1][0]:spans[1][0] + spans[1][1]]
                          .tobytes()).hexdigest()
    assert spans[1][2] == want, "resident-path digest mismatch vs hashlib"
    log(f"resident warm: {len(spans)} chunks in one region")

    # Estimator (round-4 revision; raw samples ship in the JSON so the
    # record is auditable). Two amortized chain lengths k_lo < k_hi are
    # timed as ADJACENT PAIRS (order alternating per rep, so neither side
    # systematically samples earlier in a contention plateau), with reps
    # spread over ~2.5 minutes — longer than a shared host's contention
    # plateaus, which a ~30 s spread fit inside (round-3 record: one calm
    # k_lo catch, zero calm k_hi catches -> difference-of-mins overshot
    # 12.9 ms in a round whose calm regions measured 7-8 ms). Two
    # estimates, each safe against a different failure mode:
    #   * dmin = (min t_hi - min t_lo)/(k_hi - k_lo): exact when both
    #     sides catch a calm window; overshoots when only k_lo does.
    #   * pairmed = median over reps of (t_hi - t_lo)/(k_hi - k_lo):
    #     per-pair slopes share one regime (adjacent in time), so the
    #     median tracks the TYPICAL regime's real cost; single lucky
    #     (biased-low, the round-2 trap) or unlucky pairs cannot move it.
    # Recorded: min(dmin, pairmed) — the calm-window capability when the
    # spread catches it on both sides, else the typical-regime cost;
    # neither component can sit below the pipeline cost of its regime.
    #
    # k choice bounds the third failure mode: the sync round-trip itself
    # jitters (±40 ms observed), so with k_hi - k_lo = 9 a single
    # low-sync catch on one side moves the estimate by up to ~4 ms/region
    # (observed: one t12=161 ms against a 197-210 cluster -> a bogus
    # 4.1 ms "calm" read). With k_hi - k_lo = 30 the same outlier moves
    # it by at most ~1.3 ms, below the quantity being measured.
    k_lo, k_hi = 10, max(passes, 40)
    reps = 28      # ~3 min spread: a worst-hour driver run still gets
    #                several chances at calm plateaus on BOTH chain sizes
    t_lo, t_hi = [], []
    t_start = time.perf_counter()
    for rep in range(reps):
        if rep:
            time.sleep(5.5)
        order = ((k_lo, t_lo), (k_hi, t_hi))
        if rep % 2:
            order = order[::-1]
        for k, acc in order:
            jax.block_until_ready(
                region_dispatch(words, region, 0, True, params))
            t0 = time.perf_counter()
            for _ in range(k):
                out = region_dispatch(words, region, 0, True, params)
            jax.block_until_ready(out)
            acc.append(time.perf_counter() - t0)
    span = time.perf_counter() - t_start
    dmin = (min(t_hi) - min(t_lo)) / (k_hi - k_lo)
    pairmed = statistics.median(
        (h - l) / (k_hi - k_lo) for l, h in zip(t_lo, t_hi))
    dt = min(dmin, pairmed)
    gibps = region / dt / 2**30
    log(f"sustained resident: {dt * 1e3:.2f} ms/region over a "
        f"{span:.0f} s spread (dmin {dmin * 1e3:.2f} ms from "
        f"min t{k_lo}={min(t_lo) * 1e3:.0f} / "
        f"min t{k_hi}={min(t_hi) * 1e3:.0f} ms; "
        f"paired-slope median {pairmed * 1e3:.2f} ms)")
    log(f"  t{k_lo} ms: {[f'{t * 1e3:.0f}' for t in t_lo]}")
    log(f"  t{k_hi} ms: {[f'{t * 1e3:.0f}' for t in t_hi]}")

    print(json.dumps({
        "metric": "anchored_cdc_chunk_hash_throughput_resident",
        "value": round(gibps, 3),
        "unit": "GiB/s",
        "vs_baseline": round(gibps / NORTH_STAR_GIBPS, 3),
        "samples": {
            "k_lo": k_lo, "k_hi": k_hi, "span_s": round(span, 1),
            "order": "adjacent pairs, alternating per rep",
            "t_lo_s": [round(t, 4) for t in t_lo],
            "t_hi_s": [round(t, 4) for t in t_hi],
            "dmin_ms": round(dmin * 1e3, 3),
            "pair_median_ms": round(pairmed * 1e3, 3),
            "dt_ms": round(dt * 1e3, 3),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
