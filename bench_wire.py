"""Zero-copy data plane benchmark -> WIRE_r10.json (docs/wire.md).

Two claims on one chart-ready schema, plus a correctness gate:

1. **wire** — peer-path GiB/s, r09 joined-body data plane vs the r10
   scatter-gather one, at 64 KiB .. 4 MiB chunk sizes on a 3-node
   topology (1 sender process + 2 receiver processes — real sockets,
   real frames). The two arms differ EXACTLY by the copy discipline the
   r10 work removed:

   - *joined*: pre-r10 path — the sender ``b"".join``s each ~8 MiB
     slice body and writes it as one buffer; the receiver is the
     StreamReader loop (``read_msg``: transport chunks -> reader buffer
     -> body bytes, ~3 passes over every payload) and unpacks the chunk
     table with bytes slices (one more pass).
   - *sg*: the shipped r10 path — ``InternalClient.store_chunks_windowed``
     sends the caller's chunk buffers as a scatter-gather frame (no
     join), and the receiver is the BufferedProtocol server
     (``recv_into`` one per-frame buffer) unpacking read-only
     memoryviews (no per-chunk copies).

   Both receivers run the same LIGHTWEIGHT dispatch (validate + echo the
   claimed digests — no hashing, no disk): the bench isolates the wire
   path; the full store path's hash/disk cost is identical in both arms
   and only dilutes the ratio (phase 2 gates correctness through the
   real path).

2. **identity** — a real 3-node in-process cluster ingests a stream
   through the r10 wire (hash echo, CAS, replication all live) and a
   DIFFERENT node serves it back: sha256(download) == sha256(upload).

Acceptance (full mode): sg >= 1.3x joined at 64 KiB chunks, byte identity
everywhere. ``--tiny`` is the tier-1 smoke (seconds): same schema,
machinery + identity gated, perf reported but not gated (CI hosts stall
unpredictably; the committed artifact carries the perf claim).

(Until PR 46 a middle phase timed the Gear bitmap step sharded over a
virtual CPU mesh; it went with that engine, and its block with it from
the committed artifact.)

Usage: python bench_wire.py [--tiny] [--out PATH]
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse          # noqa: E402
import asyncio           # noqa: E402
import json              # noqa: E402
import signal            # noqa: E402
import socket            # noqa: E402
import struct            # noqa: E402
import time              # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np       # noqa: E402

ART = "WIRE_r10.json"
SLICE = 8 * 2**20
WINDOW = 2

FULL = dict(chunk_sizes=(64 * 1024, 256 * 1024, 1024 * 1024,
                         4 * 1024 * 1024),
            wire_total=768 * 2**20, ident_total=24 * 2**20)
TINY = dict(chunk_sizes=(64 * 1024, 1024 * 1024),
            wire_total=48 * 2**20, ident_total=2 * 2**20)


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


# ------------------------------------------------------------------ #
# phase 1 — wire: joined vs scatter-gather, receiver processes
# ------------------------------------------------------------------ #

def _receiver_main(port_w: int, mode: str) -> None:
    """Child process: one peer running the arm's receive discipline
    behind a lightweight echo dispatch."""
    from dfs_tpu.comm.wire import (FrameServerProtocol, WireError,
                                   read_msg, send_msg, unpack_chunks)

    async def main() -> None:
        if mode == "sg":
            async def handler(conn, header, body, nbytes):
                pairs = unpack_chunks(header.get("chunks", []), body)
                conn.send_frame({"ok": True,
                                 "digests": [d for d, _ in pairs]})
                await conn.drain()

            loop = asyncio.get_running_loop()
            srv = await loop.create_server(
                lambda: FrameServerProtocol(handler), "127.0.0.1", 0)
        else:
            async def handle(reader, writer):
                try:
                    while True:
                        header, body = await read_msg(reader)
                        out, off = [], 0
                        for e in header.get("chunks", []):
                            ln = int(e["length"])
                            # r09 unpack: a bytes slice per chunk
                            out.append((e["digest"], body[off:off + ln]))
                            off += ln
                        await send_msg(writer, {
                            "ok": True, "digests": [d for d, _ in out]})
                except (WireError, ConnectionError, OSError):
                    pass
                finally:
                    writer.close()

            srv = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        os.write(port_w, struct.pack(">I", port))
        os.close(port_w)
        async with srv:
            await srv.serve_forever()

    asyncio.run(main())


def _spawn_receivers(mode: str, n: int = 2) -> tuple[list[int], list[int]]:
    pids, ports = [], []
    for _ in range(n):
        r, w = os.pipe()
        pid = os.fork()
        if pid == 0:
            os.close(r)
            try:
                _receiver_main(w, mode)
            finally:
                os._exit(0)
        os.close(w)
        ports.append(struct.unpack(">I", os.read(r, 4))[0])
        os.close(r)
        pids.append(pid)
    return pids, ports


def _kill(pids: list[int]) -> None:
    for pid in pids:
        try:
            os.kill(pid, signal.SIGKILL)
            os.waitpid(pid, 0)
        except OSError:
            pass


def _make_slices(blob: bytes, chunk: int) -> list[list[tuple[str, memoryview]]]:
    """(digest, payload-view) slices of ~SLICE bytes each, chunk-sized
    payloads — the exact shape replicate() hands the wire. Digest VALUES
    don't matter to the lightweight receivers; realistic 64-hex strings
    keep header sizes honest."""
    mv = memoryview(blob)
    n_chunks = len(blob) // chunk
    per_slice = max(1, SLICE // chunk)
    slices: list[list[tuple[str, memoryview]]] = []
    for base in range(0, n_chunks, per_slice):
        part = [(f"{i:064x}", mv[i * chunk:(i + 1) * chunk])
                for i in range(base, min(base + per_slice, n_chunks))]
        slices.append(part)
    return slices


async def _run_sg(ports: list[int], slices, repeat: int) -> None:
    from dfs_tpu.comm.rpc import InternalClient
    from dfs_tpu.config import PeerAddr

    client = InternalClient(request_timeout_s=60.0)
    peers = [PeerAddr(node_id=i + 1, host="127.0.0.1", port=0,
                      internal_port=p) for i, p in enumerate(ports)]
    try:
        for _ in range(repeat):
            await asyncio.gather(*(
                client.store_chunks_windowed(peer, "bench", slices,
                                             window=WINDOW)
                for peer in peers))
    finally:
        client.close()


async def _run_joined(ports: list[int], slices, repeat: int) -> None:
    """The r09 sender: joined slice bodies over stream connections,
    same per-peer windowing as store_chunks_windowed."""
    from dfs_tpu.comm.wire import read_msg, send_msg

    async def one_peer(port: int) -> None:
        conns = [await asyncio.open_connection("127.0.0.1", port)
                 for _ in range(WINDOW)]
        free: asyncio.Queue = asyncio.Queue()
        for c in conns:
            free.put_nowait(c)

        async def send_slice(part) -> None:
            reader, writer = await free.get()
            try:
                table = [{"digest": d, "length": len(b)} for d, b in part]
                body = b"".join(b for _, b in part)   # THE copy under test
                await send_msg(writer, {"op": "store_chunks",
                                        "fileId": "bench",
                                        "chunks": table}, body)
                await read_msg(reader)
            finally:
                free.put_nowait((reader, writer))

        try:
            for _ in range(repeat):
                sem = asyncio.Semaphore(WINDOW)

                async def gated(part):
                    async with sem:
                        await send_slice(part)

                await asyncio.gather(*(gated(p) for p in slices))
        finally:
            for _, w in conns:
                w.close()

    await asyncio.gather(*(one_peer(p) for p in ports))


def wire_phase(p: dict) -> dict:
    rng = np.random.default_rng(5)
    blob = rng.integers(0, 256, size=SLICE * 4, dtype=np.uint8).tobytes()
    out: dict = {"slice_bytes": SLICE, "window": WINDOW, "peers": 2,
                 "chunk_sizes": list(p["chunk_sizes"]),
                 "joined_gibps": [], "sg_gibps": [], "speedup": []}
    for chunk in p["chunk_sizes"]:
        slices = _make_slices(blob, chunk)
        nbytes = sum(len(b) for part in slices for _, b in part)
        repeat = max(1, p["wire_total"] // (2 * nbytes))
        total = 2 * nbytes * repeat   # 2 peers
        rates = {}
        for mode in ("joined", "sg"):
            pids, ports = _spawn_receivers(mode)
            try:
                t0 = time.perf_counter()
                asyncio.run(_run_sg(ports, slices, repeat) if mode == "sg"
                            else _run_joined(ports, slices, repeat))
                dt = time.perf_counter() - t0
            finally:
                _kill(pids)
            rates[mode] = total / dt / 2**30
            log(f"  wire chunk={chunk // 1024}KiB {mode}: "
                f"{rates[mode]:.3f} GiB/s ({total / 2**20:.0f} MiB "
                f"in {dt:.2f}s)")
        out["joined_gibps"].append(round(rates["joined"], 3))
        out["sg_gibps"].append(round(rates["sg"], 3))
        out["speedup"].append(round(rates["sg"] / rates["joined"], 3))
    out["speedup_64k"] = out["speedup"][0]
    return out


# ------------------------------------------------------------------ #
# phase 2 — byte identity through the real storage path
# ------------------------------------------------------------------ #

async def _identity(root: Path, total: int) -> bool:
    from dfs_tpu.config import (CDCParams, ClusterConfig, NodeConfig,
                                PeerAddr)
    from dfs_tpu.node.runtime import StorageNodeServer
    from dfs_tpu.utils.hashing import sha256_hex

    ports = _free_ports(6)
    cluster = ClusterConfig(
        peers=tuple(PeerAddr(node_id=i + 1, host="127.0.0.1",
                             port=ports[2 * i],
                             internal_port=ports[2 * i + 1])
                    for i in range(3)),
        replication_factor=2)
    nodes = {}
    for i in (1, 2, 3):
        cfg = NodeConfig(node_id=i, cluster=cluster, data_root=root,
                         fragmenter="cdc",
                         cdc=CDCParams(min_size=4096, avg_size=16384,
                                       max_size=131072),
                         health_probe_s=0)
        nodes[i] = StorageNodeServer(cfg)
        await nodes[i].start()
    try:
        rng = np.random.default_rng(11)
        data = rng.integers(0, 256, size=total, dtype=np.uint8).tobytes()

        async def blocks():
            for off in range(0, len(data), 1 << 20):
                yield data[off:off + (1 << 20)]

        manifest, _ = await nodes[1].upload_stream(blocks(), "id.bin")
        _, got = await nodes[2].download(manifest.file_id)
        return sha256_hex(got) == sha256_hex(data) \
            and sha256_hex(got) == manifest.file_id
    finally:
        for n in nodes.values():
            await n.stop()


# ------------------------------------------------------------------ #

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tier-1 smoke: machinery+identity gated, perf "
                         "reported but not gated")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    p = TINY if args.tiny else FULL

    import tempfile

    out: dict = {"metric": "zero_copy_data_plane", "round": 10,
                 "mode": "tiny" if args.tiny else "full"}
    log("phase 1: wire — joined vs scatter-gather…")
    out["wire"] = wire_phase(p)
    log("phase 2: byte identity through the real path…")
    base = "/dev/shm" if os.path.isdir("/dev/shm") \
        and os.access("/dev/shm", os.W_OK) else None
    with tempfile.TemporaryDirectory(prefix="bench_wire_",
                                     dir=base) as tmp:
        out["byte_identical"] = asyncio.run(
            _identity(Path(tmp), p["ident_total"]))

    if args.tiny:
        out["ok"] = bool(out["byte_identical"])
    else:
        out["ok"] = bool(out["byte_identical"]
                         and out["wire"]["speedup_64k"] >= 1.3)
    log(f"ok={out['ok']} wire_speedup={out['wire']['speedup']}")

    path = args.out or (None if args.tiny
                        else Path(__file__).parent / ART)
    if path:
        Path(path).write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
