"""Erasure-parity encode throughput (ops.ec P+Q over GF(256)).

The encode is table-free bitwise work (xor + the xtime funnel), so on
TPU it runs at HBM speed on the VPU — this bench records the device
encode rate for a realistic stripe shape and the NumPy engine for
comparison (what a CPU-only node pays at upload).

Prints ONE JSON line:
    {"metric": "ec_encode_pq_throughput", "value": N, "unit": "GiB/s",
     "vs_baseline": N}
vs_baseline: against the NumPy encode on the same stripes (>1 = the
device path is the right default on TPU nodes). Diagnostics on stderr.

Usage: python bench_ec.py [k] [shard_mib] [reps]
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> int:
    k = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    # 32 MiB shards: a 256 MiB stripe set makes the k-chain window large
    # vs the host's sync jitter — at 8 MiB the sub-ms encode drowns in
    # it
    shard = (int(sys.argv[2]) if len(sys.argv) > 2 else 32) * 2**20
    reps = int(sys.argv[3]) if len(sys.argv) > 3 else 12

    from dfs_tpu.ops.ec import _make_encode_fn, encode_pq_np
    from dfs_tpu.utils.device import bench_device

    log(bench_device("bench_ec.py"))

    rng = np.random.default_rng(0)
    shards = rng.integers(0, 256, size=(k, shard), dtype=np.uint8)
    total = k * shard

    t0 = time.perf_counter()
    p0, q0 = encode_pq_np(shards)
    np_dt = time.perf_counter() - t0
    log(f"numpy encode: {total / np_dt / 2**30:.3f} GiB/s ({np_dt:.3f}s)")

    import jax

    words = jax.device_put(shards.view(np.uint32))
    fn = _make_encode_fn(k)
    p1, q1 = jax.block_until_ready(fn(words))      # compile + warm
    assert np.array_equal(np.asarray(p1).view(np.uint8), p0)
    assert np.array_equal(np.asarray(q1).view(np.uint8), q0)
    log(f"device digests verified vs numpy oracle "
        f"(backend={jax.default_backend()})")

    # difference-of-mins slope, same discipline as bench.py
    t_lo, t_hi = [], []
    k_lo, k_hi = 3, 18
    for rep in range(reps):
        if rep:
            time.sleep(0.4)
        for kk, acc in ((k_lo, t_lo), (k_hi, t_hi)):
            jax.block_until_ready(fn(words))
            t0 = time.perf_counter()
            out = None
            for _ in range(kk):
                out = fn(words)
            jax.block_until_ready(out)
            acc.append(time.perf_counter() - t0)
    dt = (min(t_hi) - min(t_lo)) / (k_hi - k_lo)
    gibps = total / dt / 2**30
    log(f"device encode: {dt * 1e3:.2f} ms per {total / 2**20:.0f} MiB "
        f"stripe set ({gibps:.2f} GiB/s)")

    print(json.dumps({
        "metric": "ec_encode_pq_throughput",
        "value": round(gibps, 3),
        "unit": "GiB/s",
        "vs_baseline": round(gibps / (total / np_dt / 2**30), 3),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
