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
   and only dilutes the ratio (phase 3 gates correctness through the
   real path).

2. **cdc** — resident multi-device CDC+hash GiB/s vs device count on a
   virtual CPU mesh (one fresh subprocess per count, the
   MULTICHIP_SCALE_r05.json methodology): a 64 MiB region through
   ``make_sharded_step`` (windowed Gear bitmap + SHA-256 states, halo
   over the sp ring), intra-op threading pinned to ONE thread per
   device so the scaling claim is the DEVICE axis, not a hidden
   thread pool. Wall-clock on a shared-host mesh — honest per the
   committed MULTICHIP_SCALE scope note. The largest count also runs
   the full reconstruction gate: bitmap == the single-device NumPy
   oracle, device digests == hashlib, and greedy cuts reassembled ==
   the original bytes.

3. **identity** — a real 3-node in-process cluster ingests a stream
   through the r10 wire (hash echo, CAS, replication all live) and a
   DIFFERENT node serves it back: sha256(download) == sha256(upload).

Acceptance (full mode): sg >= 1.3x joined at 64 KiB chunks, 4-device
CDC >= 1.8x single-device, byte identity everywhere. ``--tiny`` is the
tier-1 smoke (seconds): same schema, machinery + identity gated, perf
reported but not gated (CI hosts stall unpredictably; the committed
artifact carries the perf claim) and the CDC phase drops to 2 devices
on a small region.

Usage: python bench_wire.py [--tiny] [--out PATH]
(internal: --cdc-worker N runs one mesh size in a fresh process)
"""

from __future__ import annotations

import os
import sys

# --cdc-worker must configure XLA BEFORE any jax import (fresh process)
if "--cdc-worker" in sys.argv:
    _n = int(sys.argv[sys.argv.index("--cdc-worker") + 1])
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        "--xla_cpu_multi_thread_eigen=false "
        "intra_op_parallelism_threads=1 "
        + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from dfs_tpu.utils.device import enable_compile_cache  # noqa: E402

enable_compile_cache()      # workers re-run this file: they share it

import argparse          # noqa: E402
import asyncio           # noqa: E402
import json              # noqa: E402
import signal            # noqa: E402
import socket            # noqa: E402
import struct            # noqa: E402
import subprocess        # noqa: E402
import time              # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np       # noqa: E402

ART = "WIRE_r10.json"
SLICE = 8 * 2**20
WINDOW = 2

FULL = dict(chunk_sizes=(64 * 1024, 256 * 1024, 1024 * 1024,
                         4 * 1024 * 1024),
            wire_total=768 * 2**20, cdc_devices=(1, 2, 4),
            cdc_region=64 * 2**20, ident_total=24 * 2**20)
TINY = dict(chunk_sizes=(64 * 1024, 1024 * 1024),
            wire_total=48 * 2**20, cdc_devices=(),
            cdc_region=0, ident_total=2 * 2**20)


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
# phase 2 — sharded CDC resident throughput (fresh process per count)
# ------------------------------------------------------------------ #

def cdc_worker(n_dev: int, region: int, check: bool) -> int:
    import jax

    from dfs_tpu.config import CDCParams
    from dfs_tpu.ops.sha256_jax import pad_messages, state_to_hex
    from dfs_tpu.parallel.mesh import make_mesh
    from dfs_tpu.parallel.sharded_cdc import make_sharded_step, shard_inputs
    from dfs_tpu.utils.hashing import gear_table, sha256_many_hex

    params = CDCParams()
    table = gear_table(params.seed)
    mesh = make_mesh(n_dev, dp=1)
    msg = 8192                       # one hashed message per avg chunk
    n_msgs = region // msg
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, size=(1, region), dtype=np.uint8)
    flat = data.reshape(-1)
    msgs = [flat[i * msg:(i + 1) * msg].tobytes() for i in range(n_msgs)]
    words, nblocks = pad_messages(msgs, n_blocks=msg // 64 + 1,
                                  batch=n_msgs)
    step = make_sharded_step(mesh, table, params.mask)
    inp = shard_inputs(mesh, data, words, nblocks)
    out = jax.block_until_ready(step(*inp))     # compile + warm
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        out = jax.block_until_ready(step(*inp))
        best = min(best, time.perf_counter() - t0)
    rec = {"devices": n_dev, "region_bytes": region,
           "seconds": round(best, 4),
           "gibps": round(region / best / 2**30, 4)}
    if check:
        bitmap, state, n_cand = out
        bitmap = np.asarray(bitmap)[0]
        from dfs_tpu.fragmenter.cdc_cpu import gear_bitmap_numpy
        from dfs_tpu.ops.boundary import cuts_to_spans, select_cuts
        if not np.array_equal(bitmap,
                              gear_bitmap_numpy(flat, table, params.mask)):
            raise AssertionError("sharded bitmap != single-device oracle")
        if state_to_hex(np.asarray(state)) != sha256_many_hex(msgs):
            raise AssertionError("device digests != hashlib")
        if int(n_cand) != int(bitmap.sum()):
            raise AssertionError("candidate psum mismatch")
        # greedy cuts -> spans tile the stream -> reassembly is
        # byte-identical (the bench's download==upload analogue for the
        # resident pipeline; phase 3 gates the full storage path)
        spans = cuts_to_spans(select_cuts(bitmap, region, params.min_size,
                                          params.max_size))
        assert spans[-1][0] + spans[-1][1] == region
        joined = b"".join(flat[o:o + ln].tobytes() for o, ln in spans)
        if sha256_many_hex([joined]) != sha256_many_hex([flat.tobytes()]):
            raise AssertionError("reconstructed spans != original bytes")
        rec["chunks"] = len(spans)
        rec["reconstruction_ok"] = True
    print(json.dumps(rec))
    return 0


def cdc_phase(p: dict) -> dict:
    out: dict = {"region_bytes": p["cdc_region"],
                 "methodology": ("virtual CPU mesh, one intra-op thread "
                                 "per device (MULTICHIP_SCALE_r05.json "
                                 "scope: wall-clock, host-bound)"),
                 "devices": [], "gibps": []}
    if not p["cdc_devices"]:
        out["skipped"] = "tiny mode"
        return out
    for n in p["cdc_devices"]:
        check = n == max(p["cdc_devices"])
        cmd = [sys.executable, __file__, "--cdc-worker", str(n),
               "--cdc-region", str(p["cdc_region"])]
        if check:
            cmd.append("--cdc-check")
        log(f"  cdc devices={n} (fresh process)…")
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=1800)
        if res.returncode != 0:
            raise RuntimeError(f"cdc worker failed:\n{res.stderr[-2000:]}")
        rec = json.loads(res.stdout.strip().splitlines()[-1])
        log(f"  cdc devices={n}: {rec['gibps']} GiB/s")
        out["devices"].append(n)
        out["gibps"].append(rec["gibps"])
        if check:
            out["reconstruction_ok"] = rec.get("reconstruction_ok", False)
            out["chunks"] = rec.get("chunks")
    out["scale_max_devices"] = round(out["gibps"][-1] / out["gibps"][0], 3)
    return out


# ------------------------------------------------------------------ #
# phase 3 — byte identity through the real storage path
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
    ap.add_argument("--cdc-worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--cdc-region", type=int, default=64 * 2**20,
                    help=argparse.SUPPRESS)
    ap.add_argument("--cdc-check", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.cdc_worker is not None:
        return cdc_worker(args.cdc_worker, args.cdc_region,
                          args.cdc_check)
    p = TINY if args.tiny else FULL

    import tempfile

    out: dict = {"metric": "zero_copy_data_plane", "round": 10,
                 "mode": "tiny" if args.tiny else "full"}
    log("phase 1: wire — joined vs scatter-gather…")
    out["wire"] = wire_phase(p)
    log("phase 2: sharded CDC resident throughput…")
    out["cdc"] = cdc_phase(p)
    log("phase 3: byte identity through the real path…")
    base = "/dev/shm" if os.path.isdir("/dev/shm") \
        and os.access("/dev/shm", os.W_OK) else None
    with tempfile.TemporaryDirectory(prefix="bench_wire_",
                                     dir=base) as tmp:
        out["byte_identical"] = asyncio.run(
            _identity(Path(tmp), p["ident_total"]))

    if args.tiny:
        out["ok"] = bool(out["byte_identical"])
    else:
        out["ok"] = bool(
            out["byte_identical"]
            and out["cdc"].get("reconstruction_ok", False)
            and out["wire"]["speedup_64k"] >= 1.3
            and out["cdc"]["scale_max_devices"] >= 1.8)
    log(f"ok={out['ok']} wire_speedup={out['wire']['speedup']} "
        f"cdc={out['cdc'].get('gibps')}")

    path = args.out or (None if args.tiny
                        else Path(__file__).parent / ART)
    if path:
        Path(path).write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
