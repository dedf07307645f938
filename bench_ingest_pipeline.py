"""Write-path pipeline benchmark -> INGEST_r07.json: windowed streaming
ingest (docs/ingest.md) vs the historical serial schedule, under
injected peer latency.

The serial write path awaited every ~flush_bytes placement batch inline,
so while a batch replicated over the network the fragmenter exhausted
its credits and the socket read stalled — replication latency was paid
in full, once per batch. The pipelined path keeps ``ingest.window``
batches in flight and ``ingest.slice_inflight`` replication slices in
flight per peer, so chunking batch N+1, local CAS writes, and peer
replication of batch N all overlap.

Method: a 3-node in-process cluster (CPU CDC engine — no device in the
loop); the two replica peers get latency injected into their
storage-plane handlers (``store_chunks`` / ``has_chunks`` sleep before
dispatch — per-request, concurrent requests overlap, exactly like real
network/disk latency). Each phase uploads fresh random data through
``upload_stream`` on a fresh cluster:

1. serial   — IngestConfig(window=1, slice_inflight=1)
2. windowed — IngestConfig(window=3, slice_inflight=2)
3. byte-identity — the windowed upload streams back down byte-identical
4. overlap evidence — /metrics ingest peaks show the window and the
   per-peer slice pipeline actually filled (>= 2 in flight)

Acceptance (full mode): windowed >= 1.5x serial throughput, byte
identity, overlap peaks > 1. ``--tiny`` is the tier-1 smoke mode
(seconds, not minutes): same phases and artifact schema, overlap +
identity gated, the speedup reported but not gated (CI hosts stall
unpredictably; the committed INGEST_r07.json carries the perf claim).

Usage: python bench_ingest_pipeline.py [--tiny] [--out PATH]
Full mode writes INGEST_r07.json (and prints it); --out overrides the
artifact path (tiny mode only writes when --out is given).
"""

from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")  # before any dfs_tpu import

import argparse          # noqa: E402
import asyncio           # noqa: E402
import json              # noqa: E402
import socket            # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np       # noqa: E402

from dfs_tpu.config import (CDCParams, ClusterConfig, IngestConfig,  # noqa: E402
                            NodeConfig, PeerAddr)
from dfs_tpu.node.runtime import StorageNodeServer  # noqa: E402

ART = "INGEST_r07.json"

# latency sized so the injected replication RTTs dominate the (GIL-
# shared, in-process) CPU work — the regime the pipeline exists for:
# the paper's north-star ingest is network/peer-bound, not chunk-bound
FULL = dict(total=48 * 2**20, block=1 << 20, flush=8 * 2**20,
            slice_bytes=4 * 2**20, store_lat=0.8, probe_lat=0.15,
            cdc=CDCParams(min_size=4096, avg_size=16384, max_size=131072))
TINY = dict(total=2 * 2**20, block=128 * 1024, flush=256 * 1024,
            slice_bytes=64 * 1024, store_lat=0.1, probe_lat=0.02,
            cdc=CDCParams(min_size=1024, avg_size=4096, max_size=16384))

SERIAL = IngestConfig(window=1, slice_inflight=1)
WINDOWED = IngestConfig(window=3, slice_inflight=2)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _free_ports(n: int) -> list[int]:
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _inject_latency(node: StorageNodeServer, store_s: float,
                    probe_s: float) -> None:
    """Delay a peer's storage-plane ops BEFORE dispatch — per request,
    so concurrent requests overlap their delays exactly like wire/disk
    latency would."""
    orig = node._dispatch

    async def delayed(header: dict, body: bytes):
        op = header.get("op")
        if op == "store_chunks":
            await asyncio.sleep(store_s)
        elif op == "has_chunks":
            await asyncio.sleep(probe_s)
        return await orig(header, body)

    node._dispatch = delayed


async def _start_cluster(root: Path, p: dict, ingest: IngestConfig
                         ) -> dict[int, StorageNodeServer]:
    ports = _free_ports(6)
    cluster = ClusterConfig(
        peers=tuple(PeerAddr(node_id=i + 1, host="127.0.0.1",
                             port=ports[2 * i],
                             internal_port=ports[2 * i + 1])
                    for i in range(3)),
        replication_factor=2)
    nodes: dict[int, StorageNodeServer] = {}
    for i in (1, 2, 3):
        cfg = NodeConfig(node_id=i, cluster=cluster, data_root=root,
                         fragmenter="cdc", cdc=p["cdc"],
                         health_probe_s=0, ingest=ingest)
        node = StorageNodeServer(cfg)
        node.placement.slice_bytes = p["slice_bytes"]
        await node.start()
        nodes[i] = node
    for i in (2, 3):   # the uploader's replica peers are the slow ones
        _inject_latency(nodes[i], p["store_lat"], p["probe_lat"])
    return nodes


async def _upload_phase(root: Path, p: dict, ingest: IngestConfig,
                        data: bytes, label: str) -> dict:
    nodes = await _start_cluster(root, p, ingest)
    try:
        async def blocks():
            for off in range(0, len(data), p["block"]):
                yield data[off:off + p["block"]]

        t0 = time.perf_counter()
        manifest, stats = await nodes[1].upload_stream(blocks(), label)
        dt = time.perf_counter() - t0
        ing = nodes[1].ingest_stats()
        out = {"seconds": round(dt, 4),
               "mibps": round(len(data) / dt / 2**20, 3),
               "chunks": manifest.total_chunks,
               "transferredBytes": stats["transferredBytes"],
               "minCopies": stats["minCopies"],
               "ingest": ing}
        # byte-identity: stream the file back down from the uploader
        _, gen = await nodes[1].download_stream(manifest.file_id)
        got = b"".join([part async for part in gen])
        out["byte_identical"] = got == data
        return out
    finally:
        for n in nodes.values():
            await n.stop()


async def run_phases(p: dict, tmp: Path, tiny: bool) -> dict:
    rng = np.random.default_rng(7)
    total = p["total"]
    out: dict = {
        "metric": "ingest_pipeline", "round": 7,
        "mode": "tiny" if tiny else "full",
        "workload": {
            "total_bytes": total, "block_bytes": p["block"],
            "flush_bytes": p["flush"], "slice_bytes": p["slice_bytes"],
            "nodes": 3, "rf": 2,
            "cdc": {"min": p["cdc"].min_size, "avg": p["cdc"].avg_size,
                    "max": p["cdc"].max_size},
            "injected": {"store_chunks_s": p["store_lat"],
                         "has_chunks_s": p["probe_lat"]}},
        "serial_config": {"window": 1, "slice_inflight": 1},
        "windowed_config": {"window": WINDOWED.window,
                            "slice_inflight": WINDOWED.slice_inflight}}

    def fresh_ingest(base: IngestConfig) -> IngestConfig:
        import dataclasses
        return dataclasses.replace(base, flush_bytes=p["flush"])

    # fresh random payload per phase: cross-phase dedup would let the
    # second upload skip every transfer and void the comparison
    data_a = rng.integers(0, 256, size=total, dtype=np.uint8).tobytes()
    data_b = rng.integers(0, 256, size=total, dtype=np.uint8).tobytes()

    log("phase 1: serial ingest (window=1, slice_inflight=1)…")
    out["serial"] = await _upload_phase(
        tmp / "serial", p, fresh_ingest(SERIAL), data_a, "serial.bin")
    log(f"phase 1: {out['serial']['seconds']} s "
        f"({out['serial']['mibps']} MiB/s)")

    log(f"phase 2: windowed ingest (window={WINDOWED.window}, "
        f"slice_inflight={WINDOWED.slice_inflight})…")
    out["windowed"] = await _upload_phase(
        tmp / "windowed", p, fresh_ingest(WINDOWED), data_b,
        "windowed.bin")
    log(f"phase 2: {out['windowed']['seconds']} s "
        f"({out['windowed']['mibps']} MiB/s)")

    out["speedup"] = round(out["serial"]["seconds"]
                           / out["windowed"]["seconds"], 3)
    out["byte_identical"] = (out["serial"].pop("byte_identical")
                             and out["windowed"].pop("byte_identical"))
    stalls = out["windowed"]["ingest"]["stalls"]
    out["overlap"] = {
        "place_window_peak": stalls.get("placeWindowPeak", 0),
        "slice_inflight_peak": stalls.get("sliceInflightPeak", 0)}
    log(f"speedup {out['speedup']}x, byte_identical="
        f"{out['byte_identical']}, overlap={out['overlap']}")

    overlapped = (out["overlap"]["place_window_peak"] >= 2
                  and out["overlap"]["slice_inflight_peak"] >= 2)
    if tiny:
        # perf is NOT gated in the smoke mode — CI hosts stall
        # unpredictably; the committed full-mode artifact carries the
        # >= 1.5x claim. The smoke gates prove the overlap machinery
        # engaged and the bytes survived it.
        out["ok"] = bool(out["byte_identical"] and overlapped)
    else:
        out["ok"] = bool(out["byte_identical"] and overlapped
                         and out["speedup"] >= 1.5)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tier-1 smoke mode: seconds, overlap+identity "
                         "gated, perf reported but not gated")
    ap.add_argument("--out", default=None,
                    help="artifact path (default: INGEST_r07.json in "
                         "full mode; tiny mode writes only when given)")
    args = ap.parse_args(argv)
    p = TINY if args.tiny else FULL

    import tempfile

    # node data roots on tmpfs when available: the benchmark isolates
    # the pipeline's replication-latency hiding, and a slow container
    # filesystem (9p/overlay metadata costs ~ms per chunk file) would
    # otherwise swamp the injected peer latency with unrelated disk cost
    base = "/dev/shm" if os.path.isdir("/dev/shm") \
        and os.access("/dev/shm", os.W_OK) else None
    with tempfile.TemporaryDirectory(prefix="bench_ingest_",
                                     dir=base) as tmp:
        out = asyncio.run(run_phases(p, Path(tmp), args.tiny))
    path = args.out or (None if args.tiny
                        else Path(__file__).parent / ART)
    if path:
        Path(path).write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
