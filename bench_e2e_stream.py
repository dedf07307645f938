"""BASELINE.json configs[2]: a GiB-class synthetic stream through the
flagship fragmenter END TO END (staging + device chain + collection, via
the bounded-memory streaming walk — not the resident-kernel metric
bench.py records), with the staging bandwidth measured alongside; the
CPU engine's number is printed for comparison. Refuses to run on
anything but a TPU unless ``JAX_PLATFORMS=cpu`` asks for the CPU by name.

Prints ONE JSON line:
    {"metric": "e2e_stream_chunk_hash_1GiB", "value": N, "unit": "GiB/s",
     "vs_baseline": N}
vs_baseline: against the native CPU engine on the same stream (>1 means
the device path beats CPU end to end on this link).

Usage: python bench_e2e_stream.py [total_bytes] [backend: tpu|cpu|both]
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_blocks(total: int, block: int = 8 * 1024 * 1024,
                seed: int = 5) -> list[bytes]:
    """Pre-generated blocks (random with repeated sections, tarball-ish):
    corpus synthesis must not land inside the timed stream."""
    rng = np.random.default_rng(seed)
    rep = rng.integers(0, 256, size=block, dtype=np.uint8).tobytes()
    out = []
    done = 0
    i = 0
    while done < total:
        n = min(block, total - done)
        out.append(rep[:n] if i % 3 == 2
                   else rng.integers(0, 256, size=n,
                                     dtype=np.uint8).tobytes())
        done += n
        i += 1
    return out


def run(frag, blocks: list[bytes]) -> tuple[float, int]:
    total = sum(len(b) for b in blocks)
    t0 = time.perf_counter()
    m = frag.manifest_stream(iter(blocks), name="e2e")
    dt = time.perf_counter() - t0
    assert m.size == total
    return dt, m.total_chunks


def probe_link(reps: int = 3) -> float:
    """Staging bandwidth at the WALK's transfer size (one region
    buffer), fresh arrays, best of ``reps`` — the link number the
    device path is honestly comparable against."""
    import jax

    from dfs_tpu.ops.cdc_anchored import (AnchoredCdcParams,
                                          region_buffer_size)

    rb = region_buffer_size(64 * 1024 * 1024, AnchoredCdcParams())
    buf = np.zeros(rb, dtype=np.uint8)
    jax.block_until_ready(jax.device_put(buf))      # warm the path
    best = float("inf")
    for _ in range(reps):
        fresh = buf.copy()
        t0 = time.perf_counter()
        jax.block_until_ready(jax.device_put(fresh))
        best = min(best, time.perf_counter() - t0)
    return rb / best


def main() -> int:
    total = int(sys.argv[1]) if len(sys.argv) > 1 else 1024 * 1024 * 1024
    backend = sys.argv[2] if len(sys.argv) > 2 else "both"

    from dfs_tpu.fragmenter.cdc_anchored import (AnchoredCpuFragmenter,
                                                 AnchoredTpuFragmenter)
    from dfs_tpu.utils.device import bench_device

    log(bench_device("bench_e2e_stream.py"))

    blocks = make_blocks(total)
    warm = make_blocks(128 * 1024 * 1024, seed=9)

    cpu_dt = None
    if backend in ("cpu", "both"):
        cpu = AnchoredCpuFragmenter()
        run(cpu, warm)                           # warm the native lib
        cpu_dt, n = run(cpu, blocks)
        log(f"cpu anchored: {total / cpu_dt / 2**30:.3f} GiB/s "
            f"({cpu_dt:.1f}s, {n} chunks)")

    if backend == "cpu":
        gibps = total / cpu_dt / 2**30
        print(json.dumps({"metric": "e2e_stream_chunk_hash_cpu",
                          "value": round(gibps, 3), "unit": "GiB/s",
                          "vs_baseline": 1.0}))
        return 0

    tpu = AnchoredTpuFragmenter()
    run(tpu, warm)                               # compile + warm transfers
    link_before = probe_link()
    tpu.reset_staging_samples()                  # scope to the timed run
    tpu_dt, n = run(tpu, blocks)
    observed = tpu.staging_observed_bw() or 0.0  # the link the walk HAD:
    # its own timed window transfers, concurrent with the run (bracket
    # probes taken seconds away can disagree with it)
    link_after = probe_link()
    tpu_gibps = total / tpu_dt / 2**30
    timed_windows = tpu.staging_timed_windows()
    log(f"tpu anchored (streamed): {tpu_gibps:.3f} GiB/s "
        f"({tpu_dt:.1f}s, {n} chunks); staging link: in-walk observed "
        f"{observed / 2**30:.3f} GiB/s over "
        f"{timed_windows} timed windows (bracket probes "
        f"{link_before / 2**30:.3f} / {link_after / 2**30:.3f}) -> "
        f"device path at {tpu_gibps / max(observed / 2**30, 1e-9):.2f}x "
        f"its observed link")

    # the recorded metric is the PRODUCTION path: what a node started
    # with the default fragmenter ingests at (`auto`: the device engine
    # iff the machine has a TPU platform, fragmenter/base.py) — the
    # explicit device and CPU numbers above are the diagnostic split
    from dfs_tpu.fragmenter.base import get_fragmenter
    auto = get_fragmenter("auto")
    log(f"auto picked: {auto.name}")
    run(auto, warm)
    auto_dt, n = run(auto, blocks)
    gibps = total / auto_dt / 2**30
    log(f"auto (streamed): {gibps:.3f} GiB/s ({auto_dt:.1f}s, {n} chunks)")
    vs = (cpu_dt / auto_dt) if cpu_dt else 1.0
    print(json.dumps({
        "metric": "e2e_stream_chunk_hash_1GiB_auto",
        "value": round(gibps, 3), "unit": "GiB/s",
        "vs_baseline": round(vs, 3),
        "engines": {
            "device_gibps": round(tpu_gibps, 4),
            "cpu_gibps": round(total / cpu_dt / 2**30, 4) if cpu_dt
            else None,
            "auto_picked": auto.name,
        },
        "staging_link": {
            "in_walk_observed_gibps": round(observed / 2**30, 4),
            "in_walk_timed_windows": timed_windows,
            "probe_before_gibps": round(link_before / 2**30, 4),
            "probe_after_gibps": round(link_after / 2**30, 4),
            "probe": "region-buffer-sized fresh device_put, best of 3; "
                     "in-walk = the walk's own timed window transfers "
                     "(concurrent with the run)",
            "device_vs_link": round(
                tpu_gibps / max(observed / 2**30, 1e-9), 3),
        }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
