"""Real-process cluster smoke test: 5 separate `dfs-tpu serve` OS
processes — the scripted edition of the reference's operating mode and
manual verification recipe (/root/reference/run.txt:2-7,
README.md:129-135,172-179: compile, start 5 nodes, upload the four
example fixtures, list from another node, kill one node, download
byte-identical). In-process asyncio tests cover the protocols; only this
test executes ``cmd_serve`` itself — cluster-config wiring, the
fragmenter probe, and the periodic repair loop — end to end.
"""

import os
import subprocess
import sys
import time
import urllib.request
from pathlib import Path

import numpy as np

from dfs_tpu.cli.client import NodeClient

N = 5
REPO = Path(__file__).resolve().parent.parent


def _two_port_runs(n: int) -> tuple[int, int]:
    """One free run of 2n ports split into (http_base, internal_base) —
    probing the runs separately could hand back overlapping ranges,
    since nothing holds the first range while the second is probed."""
    from scripts.chaos_harness import contiguous_free_ports

    base = contiguous_free_ports(2 * n)
    return base, base + n


def _png(width: int = 64, height: int = 64) -> bytes:
    """A REAL (decodable) PNG: 8-bit grayscale gradient, zlib-compressed
    scanlines, correct chunk CRCs — same content class as the
    reference's pl.png, built here instead of copied."""
    import struct
    import zlib

    def chunk(tag: bytes, body: bytes) -> bytes:
        return (struct.pack(">I", len(body)) + tag + body
                + struct.pack(">I", zlib.crc32(tag + body)))

    ihdr = struct.pack(">IIBBBBB", width, height, 8, 0, 0, 0, 0)
    raw = b"".join(
        b"\x00" + bytes((x * 7 + y * 13) & 0xFF for x in range(width))
        for y in range(height))
    return (b"\x89PNG\r\n\x1a\n" + chunk(b"IHDR", ihdr)
            + chunk(b"IDAT", zlib.compress(raw, 6)) + chunk(b"IEND", b""))


def _jpeg(rng, entropy_bytes: int = 9000) -> bytes:
    """A JPEG-marker-FRAMED payload (SOI / APP0-JFIF / 0xFF-stuffed
    entropy bytes / EOI) — the id.jpg analogue. NOT a decodable image
    (no DQT/DHT/SOF/SOS segments): the storage path never decodes, it
    round-trips high-entropy image-format-shaped bytes."""
    body = rng.integers(0, 256, size=entropy_bytes,
                        dtype=np.uint8).tobytes()
    stuffed = body.replace(b"\xff", b"\xff\x00")
    app0 = b"\xff\xe0\x00\x10JFIF\x00\x01\x02\x00\x00\x01\x00\x01\x00\x00"
    return b"\xff\xd8" + app0 + stuffed + b"\xff\xd9"


def _fixtures(rng) -> dict[str, bytes]:
    """Analogues of the reference's examples/ corpus (teste.txt,
    pag1.html, id.jpg, pl.png — the de-facto test set of
    /root/reference/README.md:172-179): small text, HTML, a real PNG,
    and a marker-correct JPEG payload."""
    return {
        "teste.txt": b"esta e uma mensagem de teste\n",
        "pag1.html": (b"<html><head><title>pagina 1</title></head>"
                      b"<body><h1>pagina 1</h1><p>conteudo de teste"
                      b"</p></body></html>\n"),
        "id.jpg": _jpeg(rng),
        "pl.png": _png(),
    }


def test_five_process_cluster_lifecycle(tmp_path, rng):
    base_http, base_internal = _two_port_runs(N)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    procs: dict[int, subprocess.Popen] = {}
    try:
        for i in range(1, N + 1):
            procs[i] = subprocess.Popen(
                [sys.executable, "-m", "dfs_tpu.cli.main", "serve",
                 "--node-id", str(i), "--nodes", str(N),
                 "--base-port", str(base_http),
                 "--base-internal-port", str(base_internal),
                 "--fragmenter", "cdc-anchored",
                 "--data-root", str(tmp_path / "data"),
                 "--repair-interval", "2"],
                cwd=tmp_path, env=env,
                stdout=(tmp_path / f"node{i}.log").open("wb"),
                stderr=subprocess.STDOUT)

        # wait for every /status (reference client option 1)
        deadline = time.time() + 30
        for i in range(1, N + 1):
            port = base_http + i - 1
            while True:
                if procs[i].poll() is not None:
                    raise AssertionError(
                        f"node {i} died: "
                        + (tmp_path / f"node{i}.log").read_text()[-2000:])
                try:
                    with urllib.request.urlopen(
                            f"http://127.0.0.1:{port}/status",
                            timeout=1) as r:
                        assert r.read() == b"OK"
                    break
                except OSError:
                    if time.time() > deadline:
                        raise AssertionError(f"node {i} never came up")
                    time.sleep(0.2)

        clients = {i: NodeClient(port=base_http + i - 1)
                   for i in range(1, N + 1)}
        fixtures = _fixtures(rng)

        # upload each fixture at a different node (reference README:173)
        ids = {}
        for i, (name, data) in enumerate(fixtures.items(), start=1):
            info = clients[i].upload(data, name)
            ids[name] = info["fileId"]

        # every file visible from a node that uploaded none of it
        listed = {f.name for f in clients[5].list_files()}
        assert listed == set(fixtures)

        # kill one node hard; downloads still byte-identical from
        # another (reference README:177 'download with one node offline')
        procs[2].kill()
        procs[2].wait(timeout=10)
        for name, data in fixtures.items():
            got = clients[4].download(ids[name])
            assert got == data, f"{name} mismatch after node kill"

        # the periodic repair loop is alive: metrics show repair ticks
        # on a surviving node within ~2 intervals
        deadline = time.time() + 10
        while True:
            if clients[1].metrics().get("repairs", 0) >= 1:
                break
            if time.time() > deadline:
                raise AssertionError("repair loop never ticked")
            time.sleep(0.5)
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
