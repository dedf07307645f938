#!/usr/bin/env python3
"""chip_smoke.py — the served path, end to end, with the device in it.

One deployment on one host, through the entry points a user would call
(``python -m dfs_tpu.cli.main ...`` and HTTP), at the size of
BASELINE.json configs[2] (1 GiB synthetic tarball, default CDC
parameters, 8 KiB average chunk):

- one CHIP OWNER: ``sidecar --fragmenter cdc-anchored-tpu`` started with
  ``JAX_PLATFORMS=tpu`` — JAX itself refuses to fall back;
- three NODES: ``serve --nodes 3 --replication-factor 2 --sidecar-port P``
  (fsync durability, the default), each with ``JAX_PLATFORMS=cpu``
  because it does not own the chip;
- v1 (seeded corpus) streamed to node 1, v2 (v1 with ~1 % of its bytes
  changed by 64 unaligned inserts/overwrites) streamed to node 2; v1
  downloaded whole from node 3, v2 from node 1, one Range read across a
  device-window boundary.

What must hold: fileId == sha256(body) for both; downloads and the range
byte-identical; every manifest and every node's /metrics frag.engine say
``sidecar:cdc-anchored-tpu``; both chunk tables equal the CPU engine's
(C++ walk + hashlib — code the device path shares nothing with); v2's
upload, through another coordinator, skips exactly the share of its
bytes that the CPU engine's tables say v1 already holds (device digests
dedup across coordinators); the owner's Health says platform ``tpu`` and
>= 17 regions dispatched per upload; ``census`` exits 0.

THIS process never initialises a JAX backend: it makes data with NumPy,
starts children, talks HTTP/gRPC. A chip belongs to one process, and that
process is the owner.

Prints, only when every phase passed on a TPU, two JSON lines on stdout:
the summary (``ok``, ``device``, versions, bytes, chunks, regions, set-up
and run seconds, compile-cache state, oracle engine, one ``ok`` per
phase, ``claim: null``) and then, as the LAST line, the verdict with
exactly these keys: ``{"ok": true, "device": {"platform": "tpu",
"kind": "...", "count": 1}}`` — the device as the owner's JAX reports it.
Anything else — no accelerator, a dead child, a failed assertion — exits
1 with the tail of the failing process's log on stderr and NO result
line. The summary (pass or fail) and every child's log are also written
under ``chiprun_out/``.

``--rehearse-cpu``: the same command at 8 MiB with the owner on
``JAX_PLATFORMS=cpu`` (the XLA twin of the chain; one small bucket),
skipping only the platform assertion. Prints ``REHEARSAL`` last and exits
10 — neither pass nor fail — so it can never be read as a chip run. It
exists so the command is debugged where there is no chip.
"""

from __future__ import annotations

import argparse
import hashlib
import importlib.metadata
import json
import os
import platform
import shutil
import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

EXIT_FAIL = 1
EXIT_REHEARSAL = 10

REPO = Path(__file__).resolve().parent
OUT = REPO / "chiprun_out"
ENGINE = "sidecar:cdc-anchored-tpu"
MIB = 1024 * 1024


class SmokeFailure(Exception):
    pass


# ---------------------------------------------------------------------------
# data: the seeded corpus and its edited second version (NumPy only)
# ---------------------------------------------------------------------------

def make_corpus(size: int, seed: int):
    """BASELINE.json configs[2], as bench.py builds it: one random 4 MiB
    block tiled, with fresh randomness spliced over every other 4 MiB —
    so half the stream repeats and dedup has something to find."""
    import numpy as np

    rng = np.random.default_rng(seed)
    block = rng.integers(0, 256, size=4 * MIB, dtype=np.uint8)
    arr = np.tile(block, -(-size // block.size))[:size].copy()
    for off in range(0, size, 8 * MIB):
        end = min(off + 4 * MIB, size)
        arr[off:end] = rng.integers(0, 256, size=end - off, dtype=np.uint8)
    return arr


def make_v2(v1, seed: int, n_edits: int, lo: int, hi: int):
    """v1 with ``n_edits`` edits spread evenly: alternately an overwrite
    or an insert of lo..hi fresh bytes at an ODD offset (so nothing
    downstream of an insert stays on any power-of-two grid)."""
    import numpy as np

    rng = np.random.default_rng(seed + 1)
    size = int(v1.shape[0])
    pieces = []
    cur = 0
    changed = 0
    for i in range(n_edits):
        off = ((2 * i + 1) * size // (2 * n_edits)) | 1
        ln = int(rng.integers(lo, hi + 1))
        fresh = rng.integers(0, 256, size=ln, dtype=np.uint8)
        pieces += [v1[cur:off], fresh]
        cur = off + ln if i % 2 == 0 else off      # overwrite / insert
        changed += ln
    pieces.append(v1[cur:])
    return np.concatenate(pieces), changed


def blocks_of(arr, n: int = 4 * MIB):
    mv = memoryview(arr)
    for i in range(0, len(mv), n):
        yield mv[i:i + n]


def sha256_hex(arr) -> str:
    # the oracle for fileId: plain hashlib over the bytes this process
    # made, independent of every digest path under test
    return hashlib.sha256(memoryview(arr)).hexdigest()


# ---------------------------------------------------------------------------
# children: started by Popen, tracked by object, killed in finally
# ---------------------------------------------------------------------------

class Child:
    def __init__(self, name: str, argv: list[str], env: dict) -> None:
        self.name = name
        self.log_path = OUT / f"chip_smoke.{name}.log"
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "dfs_tpu.cli.main", *argv],
            cwd=REPO, env=env, stdout=self._log, stderr=subprocess.STDOUT)

    def log_text(self) -> str:
        return self.log_path.read_text(errors="replace")

    def tail(self, lines: int = 40) -> str:
        return "\n".join(self.log_text().splitlines()[-lines:])

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(f"{self.name} exited with code {rc}")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(10)
        self._log.close()


def child_env(jax_platforms: str, cache_dir: Path) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = jax_platforms
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def wait_for(what: str, probe, children: list[Child], limit_s: float):
    """Poll ``probe()`` (returns a value or None) until it answers, a
    child dies, or ``limit_s`` passes."""
    deadline = time.monotonic() + limit_s
    while time.monotonic() < deadline:
        for c in children:
            c.check_alive()
        got = probe()
        if got is not None:
            return got
        time.sleep(0.2)
    raise SmokeFailure(f"timed out after {limit_s:.0f}s waiting for {what}")


def free_port_base(n: int) -> int:
    """A base port with ``n`` consecutive free ports (serve derives its
    HTTP and internal ports from a base + node id)."""
    for base in range(17100, 60000, 97):
        socks = []
        try:
            for p in range(base, base + n):
                s = socket.socket()
                socks.append(s)
                s.bind(("127.0.0.1", p))
            return base
        except OSError:
            continue
        finally:
            for s in socks:
                s.close()
    raise SmokeFailure("no free port range")


def version_of(dist: str) -> str | None:
    try:
        return importlib.metadata.version(dist)
    except importlib.metadata.PackageNotFoundError:
        return None


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def run(args, summary: dict, children: list[Child], data_root: Path) -> None:
    import numpy as np

    from dfs_tpu import native
    from dfs_tpu.cli.client import NodeClient
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter
    from dfs_tpu.sidecar.service import SidecarClient
    from dfs_tpu.utils.device import compile_cache_dir

    rehearsal = args.rehearse_cpu
    size = 8 * MIB if rehearsal else 1024 * MIB
    n_edits, edit_lo, edit_hi = (1, 4096, 16384) if rehearsal \
        else (64, 64 * 1024, 256 * 1024)
    phases: dict[str, bool] = summary["phases"]
    setup_s: dict[str, float] = summary["setup_s"]
    run_s: dict[str, float] = summary["run_s"]

    def phase(name: str):
        return _Phase(name, phases)

    cache_dir = compile_cache_dir()

    def cache_entries() -> int:
        return len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0

    summary["compile_cache"] = {"dir": str(cache_dir),
                                "cold": cache_entries() == 0,
                                "entries_before": cache_entries()}
    # native objects are rebuilt from source for this run (a copied tree
    # may carry objects built for another CPU)
    shutil.rmtree(REPO / "dfs_tpu" / "native" / "_build", ignore_errors=True)

    # -- the chip owner (started first: its backend init overlaps the
    # corpus synthesis below) ------------------------------------------
    t_owner = time.monotonic()
    owner = Child("owner", ["sidecar", "--fragmenter", "cdc-anchored-tpu",
                            "--sidecar-port", "0"],
                  child_env("cpu" if rehearsal else "tpu", cache_dir))
    children.append(owner)

    # an owner that cannot take the chip dies within seconds: give it
    # those seconds before a GiB is synthesised (an owner that can needs
    # 12-15 s to come up, so most of the wait hides behind its start)
    try:
        owner.proc.wait(4)
    except subprocess.TimeoutExpired:
        pass
    owner.check_alive()
    t0 = time.monotonic()
    v1 = make_corpus(size, args.seed)
    v2, changed = make_v2(v1, args.seed, n_edits, edit_lo, edit_hi)
    sha = {"v1": sha256_hex(v1), "v2": sha256_hex(v2)}
    setup_s["corpus"] = time.monotonic() - t0
    summary["bytes"] = {"v1": int(v1.shape[0]), "v2": int(v2.shape[0]),
                        "v2_fresh": changed}

    with phase("owner_start"):
        def listening():
            for line in owner.log_text().splitlines():
                if line.startswith("sidecar listening on "):
                    return int(line.split()[3].rsplit(":", 1)[1])
            return None

        sidecar_port = wait_for("the owner's `sidecar listening` line",
                                listening, [owner], 300)
        setup_s["owner_start"] = time.monotonic() - t_owner
        sc = SidecarClient(sidecar_port)
        health = sc.health()
        dev = health.get("device") or {}
        summary["device"] = {"platform": dev.get("platform"),
                             "kind": dev.get("device_kind"),
                             "count": dev.get("count")}
        if health["fragmenter"] != "cdc-anchored-tpu":
            raise SmokeFailure(f"owner runs {health['fragmenter']!r}")
        if not rehearsal and dev.get("platform") != "tpu":
            raise SmokeFailure(
                f"owner's platform is {dev.get('platform')!r}, not 'tpu'")

    def regions() -> int:
        return int(sc.health()["device"]["regions"])

    oracle = AnchoredCpuFragmenter()
    region, stride = oracle.region_bytes, oracle.stride
    if health["window"] % region:
        raise SmokeFailure("owner's window is not a multiple of the "
                           "oracle's region: parameters diverged")

    def windows(n: int) -> int:
        return 1 if n <= region else 2 + (n - region - 1) // stride

    def tail_len(n: int) -> int:
        return n - (windows(n) - 1) * stride

    with phase("warm"):
        # one stream per distinct tail bucket: a full window (when the
        # corpus has one) + a final window of the same power-of-two
        # bucket as the real tail, so no node upload meets a compile
        t0 = time.monotonic()
        rng = np.random.default_rng(args.seed + 2)
        done_buckets = set()
        for n in (int(v1.shape[0]), int(v2.shape[0])):
            t = tail_len(n)
            bucket = 1 << (t - 1).bit_length()
            if bucket in done_buckets:
                continue
            done_buckets.add(bucket)
            warm_n = t if windows(n) == 1 else stride + t
            warm = rng.integers(0, 256, size=warm_n, dtype=np.uint8)
            resp = sc.chunk_hash_stream(bytes(b) for b in blocks_of(warm))
            if resp["size"] != warm_n:
                raise SmokeFailure("warm stream came back short")
        summary["regions"] = {"warm": regions()}
        setup_s["warm"] = time.monotonic() - t0

    with phase("nodes_start"):
        t0 = time.monotonic()
        base = free_port_base(6)
        nodes = []
        for i in (1, 2, 3):
            nodes.append(Child(
                f"node{i}",
                ["serve", "--node-id", str(i), "--nodes", "3",
                 "--replication-factor", "2",
                 "--sidecar-port", str(sidecar_port),
                 "--base-port", str(base),
                 "--base-internal-port", str(base + 3),
                 "--data-root", str(data_root)],
                child_env("cpu", cache_dir)))
            children.append(nodes[-1])
        clients = [NodeClient(port=base + i, timeout_s=1000)
                   for i in range(3)]

        def all_up():
            try:
                return all(c.status() for c in clients) or None
            except OSError:
                return None

        wait_for("three nodes to answer /status", all_up, children, 120)
        setup_s["nodes_start"] = time.monotonic() - t0
    n1, n2, n3 = clients

    def upload(tag: str, client, arr) -> dict:
        r0 = regions()
        t0 = time.monotonic()
        info = client.upload_stream(blocks_of(arr), name=f"{tag}.tar")
        run_s[f"upload_{tag}"] = time.monotonic() - t0
        summary["regions"][tag] = regions() - r0
        summary["chunks"][tag] = info["chunks"]
        if info["fileId"] != sha[tag]:
            raise SmokeFailure(f"{tag}: fileId != sha256(body)")
        want = windows(int(arr.shape[0]))
        if summary["regions"][tag] < want:
            raise SmokeFailure(
                f"{tag}: owner dispatched {summary['regions'][tag]} "
                f"regions to the device, expected >= {want}")
        return info

    def manifest_of(tag: str, client) -> dict:
        m = client.manifest(sha[tag])
        if m["fragmenter"] != ENGINE:
            raise SmokeFailure(
                f"{tag}: manifest says fragmenter {m['fragmenter']!r}")
        return m

    with phase("upload_v1"):
        upload("v1", n1, v1)
        m1 = manifest_of("v1", n1)

    setup_s["oracle"] = 0.0

    def oracle_table(tag: str, arr, manifest: dict) -> list:
        """The CPU engine's chunk table; the served manifest must equal
        it, chunk for chunk."""
        t0 = time.monotonic()
        want = [(c.offset, c.length, c.digest) for c in oracle.chunk(arr)]
        setup_s["oracle"] += time.monotonic() - t0
        summary["oracle_engine"] = native.engine()
        got = [(c["offset"], c["length"], c["digest"])
               for c in manifest["chunks"]]
        if got != want:
            bad = next((i for i, (g, w) in enumerate(zip(got, want))
                        if g != w), min(len(got), len(want)))
            raise SmokeFailure(
                f"{tag} chunk table differs from the CPU oracle at chunk "
                f"{bad} ({len(got)} vs {len(want)} chunks)")
        return want

    with phase("oracle_v1"):
        table1 = oracle_table("v1", v1, m1)

    with phase("upload_v2"):
        info = upload("v2", n2, v2)
        m2 = manifest_of("v2", n2)

    with phase("oracle_v2"):
        table2 = oracle_table("v2", v2, m2)

    with phase("dedup_v2"):
        # what the reference says v1 already holds of v2's UNIQUE chunks,
        # by bytes — the same population the upload's per-peer counters
        # are drawn from (each unique chunk counts once per remote owner)
        held = {d for _, _, d in table1}
        uniq = {d: ln for _, ln, d in table2}
        expected = sum(ln for d, ln in uniq.items() if d in held) \
            / max(1, sum(uniq.values()))
        skipped = info["dedupSkippedBytes"]
        moved = info["transferredBytes"]
        ratio = skipped / max(1, skipped + moved)
        summary["dedup_v2"] = {"skippedBytes": skipped,
                               "transferredBytes": moved,
                               "ratio": round(ratio, 4),
                               "oracle_ratio": round(expected, 4)}
        if abs(ratio - expected) > 0.02:
            raise SmokeFailure(
                f"v2's upload skipped {ratio:.1%} of its placed bytes; "
                f"the CPU oracle's tables say {expected:.1%}")

    def download(tag: str, client, arr) -> None:
        t0 = time.monotonic()
        body = client.download(sha[tag])
        run_s[f"download_{tag}"] = time.monotonic() - t0
        if len(body) != arr.shape[0] or memoryview(arr) != body:
            raise SmokeFailure(f"{tag}: download is not byte-identical")

    with phase("download_v1"):
        download("v1", n3, v1)
    with phase("download_v2"):
        download("v2", n1, v2)

    with phase("range_read"):
        # across the first device-window boundary (mid-file when the
        # rehearsal corpus fits one window)
        mid = stride if windows(int(v1.shape[0])) > 1 else size // 2
        lo, hi = mid - 65536 - 1, mid + 65536 + 1
        t0 = time.monotonic()
        part = n2.download_range(sha["v1"], lo, hi)
        run_s["range"] = time.monotonic() - t0
        if memoryview(v1[lo:hi]) != part:
            raise SmokeFailure("range read is not byte-identical")

    with phase("engine_metrics"):
        for i, c in enumerate(clients, 1):
            engine = c.metrics()["frag"]["engine"]
            if engine != ENGINE:
                raise SmokeFailure(f"node {i} frag.engine is {engine!r}")

    with phase("census"):
        t0 = time.monotonic()
        cn = subprocess.run(
            [sys.executable, "-m", "dfs_tpu.cli.main", "--port", str(base),
             "census"], cwd=REPO, env=child_env("cpu", cache_dir),
            capture_output=True, text=True, timeout=900)
        run_s["census"] = time.monotonic() - t0
        (OUT / "chip_smoke.census.log").write_text(cn.stdout + cn.stderr)
        if cn.returncode != 0:
            raise SmokeFailure(f"census exited {cn.returncode}:\n"
                               + (cn.stdout + cn.stderr)[-2000:])

    with phase("children_alive"):
        for c in children:
            c.check_alive()
        summary["overflow_redos"] = int(
            sc.health()["device"]["overflow_redos"])
    sc.close()
    summary["compile_cache"]["entries_after"] = cache_entries()


class _Phase:
    """Records one ``ok`` per phase; the first failure ends the run."""

    def __init__(self, name: str, phases: dict) -> None:
        self.name, self.phases = name, phases

    def __enter__(self):
        self.phases[self.name] = False
        print(f"[chip_smoke] {self.name} ...", file=sys.stderr, flush=True)
        return self

    def __exit__(self, et, ev, tb):
        if et is None:
            self.phases[self.name] = True
        elif not issubclass(et, SmokeFailure) and issubclass(et, Exception):
            raise SmokeFailure(
                f"{self.name}: {et.__name__}: {ev}") from ev
        return False


def verdict_line(summary: dict) -> str:
    """The last stdout line of a passing chip run: ``ok`` and the device
    as the owner's JAX reported it, exactly these keys and nothing else
    (the full summary is the line before it)."""
    dev = summary["device"]
    if not (isinstance(dev["platform"], str) and isinstance(dev["kind"], str)
            and type(dev["count"]) is int):
        raise SystemExit(f"[chip_smoke] owner reported no device: {dev!r}")
    return json.dumps({"ok": True,
                       "device": {"platform": dev["platform"],
                                  "kind": dev["kind"],
                                  "count": dev["count"]}})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="8 MiB, owner on JAX_PLATFORMS=cpu, exits "
                         f"{EXIT_REHEARSAL}: debugs the command, proves "
                         "nothing about the chip")
    args = ap.parse_args()

    OUT.mkdir(exist_ok=True)
    summary: dict = {
        "ok": False,
        "device": {"platform": None, "kind": None, "count": None},
        "rehearsal": args.rehearse_cpu,
        "seed": args.seed,
        "versions": {"python": platform.python_version(),
                     "jax": version_of("jax"),
                     "jaxlib": version_of("jaxlib"),
                     "libtpu": version_of("libtpu"),
                     "numpy": version_of("numpy")},
        "bytes": {}, "chunks": {}, "regions": {}, "overflow_redos": None,
        "dedup_v2": {}, "setup_s": {}, "run_s": {}, "compile_cache": {},
        "oracle_engine": None, "phases": {}, "error": None,
    }
    children: list[Child] = []
    data_root = Path(tempfile.mkdtemp(prefix="dfs_chip_smoke_"))
    t_start = time.monotonic()
    try:
        run(args, summary, children, data_root)
        summary["ok"] = all(summary["phases"].values()) \
            and not args.rehearse_cpu
    except SmokeFailure as e:
        summary["error"] = str(e)
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        dead = [c for c in children if c.proc.poll() is not None]
        for c in dead or children:
            print(f"--- {c.name} (exit {c.proc.poll()}) log tail ---\n"
                  f"{c.tail(40 if dead else 15)}", file=sys.stderr)
    finally:
        for c in reversed(children):
            c.stop()
        shutil.rmtree(data_root, ignore_errors=True)
    summary["total_s"] = round(time.monotonic() - t_start, 1)
    for k in ("setup_s", "run_s"):
        summary[k] = {n: round(v, 2) for n, v in summary[k].items()}
    summary["claim"] = None
    line = json.dumps(summary)
    (OUT / "chip_smoke.json").write_text(line + "\n")
    if summary["error"] is not None:
        print(line, file=sys.stderr)
        return EXIT_FAIL
    if args.rehearse_cpu:
        print(line, flush=True)
        print("REHEARSAL", flush=True)
        return EXIT_REHEARSAL
    verdict = verdict_line(summary)
    print(line, flush=True)
    print(verdict, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
