"""Similarity compression plane benchmark -> SIM_r21.json.

Dedup only removes IDENTICAL chunks; the sim plane (dfs_tpu/sim,
docs/similarity.md) turns near-duplicates — edited documents, mutated
checkpoints — into ``base-digest + patch`` delta files behind the CAS.
Four phases on one chart-ready schema:

1. **corpus** — K mutated generations of a chunk corpus, stored twice:
   into a plain store (dedup-only baseline: every generation's chunks
   are distinct, so every byte lands raw) and into a sim-enabled store
   (min-hash bands nominate bases, similar chunks store as DSD1
   patches). Gates stored bytes WELL BELOW the baseline and re-reads
   every digest byte-identical through the transparent reconstruct.

2. **sketch** — batched min-hash sketch throughput at 1/2/4 virtual
   devices (one fresh subprocess per count, ONE intra-op thread per
   device, the CDC_SHARD_r15.json methodology). All mbps arms run the
   same mesh kernel via ``force_sharded`` (their ratio, ``mesh_scale``,
   isolates the device axis — on a single-core host it reflects
   dispatch amortization only, and the artifact records ``host_cores``
   so nobody reads it as parallel compute). The GATED ratio,
   ``scale_max_devices``, is user-visible: the sharded pipeline at the
   max device count vs the path ``SimConfig(devices=1)`` actually
   executes (the host oracle). The largest count also gates lane-exact
   identity against the NumPy oracle.

3. **crash** — real ``kill -9`` at each registered ``sim.*`` crash
   point (delta write, base GC, re-materialize): a fresh process arms
   the point through the chaos injector, performs the triggering store
   op, and dies mid-protocol; the parent then re-opens the store and
   gates every previously-acked chunk byte-identical (the delta-file
   header log must rebuild the pin maps on its own).

4. **default_off** — ``SimConfig()`` builds no plane: a sim-less store
   writes the exact pre-r21 tree (no deltas/ directory, raw files
   only) and serves byte-identical.

Acceptance (full mode): corpus savings >= 30% vs dedup-only, sketch
scaling at 4 devices >= 1.7x the 1-device mesh rate, every crash point
verified, default-off identical. ``--tiny`` is the tier-1 smoke
(seconds): same schema and machinery at toy scale — identity, crash
and stored-bytes-below-baseline still gated; perf reported but not
gated (CI hosts stall unpredictably; the committed artifact carries
the perf claim).

Usage: python bench_sim.py [--tiny] [--out PATH]
(internal: --sketch-worker N / --crash-worker POINT run one arm in a
fresh process)
"""

from __future__ import annotations

import os
import sys

# sketch workers must configure XLA BEFORE any jax import (fresh
# process, one thread per device — the r15 methodology)
if "--sketch-worker" in sys.argv:
    _n = int(sys.argv[sys.argv.index("--sketch-worker") + 1])
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_n} "
        "--xla_cpu_multi_thread_eigen=false "
        "intra_op_parallelism_threads=1 "
        + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from dfs_tpu.utils.device import enable_compile_cache  # noqa: E402

enable_compile_cache()      # workers re-run this file: they share it

import argparse          # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import time              # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np       # noqa: E402

ART = "SIM_r21.json"
SIM_POINTS = ("sim.after_delta_write", "sim.before_base_gc",
              "sim.after_rematerialize")

FULL = dict(devices=(1, 2, 4), window=64 * 1024, batch=192, repeats=3,
            chunks=24, chunk_bytes=64 * 1024, generations=8,
            edits=4, geometry="full")
TINY = dict(devices=(1, 2), window=4096, batch=24, repeats=2,
            chunks=6, chunk_bytes=8192, generations=3,
            edits=2, geometry="tiny")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _mutated_corpus(p: dict) -> list[list[bytes]]:
    """``generations`` lists of ``chunks`` chunk payloads; generation
    g is generation g-1 with a few small edits per chunk — every
    digest distinct (defeats dedup), every neighbor similar."""
    rng = np.random.default_rng(2101)
    gens = [[rng.integers(0, 256, size=p["chunk_bytes"],
                          dtype=np.uint8).tobytes()
             for _ in range(p["chunks"])]]
    for g in range(1, p["generations"]):
        prev = gens[-1]
        cur = []
        for c in prev:
            b = bytearray(c)
            for _ in range(p["edits"]):
                at = int(rng.integers(0, len(b)))
                b[at] = (b[at] + 1 + g) & 0xFF
            cur.append(bytes(b))
        gens.append(cur)
    return gens


def _sim_cfg(p: dict, **kw):
    from dfs_tpu.config import SimConfig

    return SimConfig(enabled=True, min_chunk_bytes=1024, devices=0,
                     **kw)


# ------------------------------------------------------------------ #
# phase 1 — K-generation mutated corpus: stored bytes vs dedup-only
# ------------------------------------------------------------------ #

def corpus_phase(root: Path, p: dict) -> dict:
    from dfs_tpu.sim import SimPlane
    from dfs_tpu.store.cas import ChunkStore
    from dfs_tpu.utils.hashing import sha256_hex

    gens = _mutated_corpus(p)
    items = [(sha256_hex(b), b) for gen in gens for b in gen]
    assert len({d for d, _ in items}) == len(items), \
        "every mutated generation must defeat exact dedup"

    dedup = ChunkStore(root / "dedup" / "chunks")
    for d, b in items:
        dedup.put(d, b)
    dedup_bytes = dedup.total_bytes()

    sim = ChunkStore(root / "sim" / "chunks")
    sim.sim = SimPlane(_sim_cfg(p), root / "sim" / "sim")
    t0 = time.perf_counter()
    for gen in gens:                     # generation = one put batch
        sim.put_batch([(sha256_hex(b), b) for b in gen])
    ingest_s = time.perf_counter() - t0
    sim_bytes = sim.total_bytes()
    identical = all(sim.get(d) == b for d, b in items)
    stats = sim.sim.stats()
    sim.sim.close()
    return {"generations": p["generations"], "chunks": len(items),
            "chunk_bytes": p["chunk_bytes"],
            "dedup_bytes": dedup_bytes, "sim_bytes": sim_bytes,
            "savings_frac": round(1.0 - sim_bytes / dedup_bytes, 4),
            "deltas_written": stats["deltasWritten"],
            "delta_chunks": sim.delta_count(),
            "ingest_seconds": round(ingest_s, 4),
            "byte_identical": bool(identical)}


# ------------------------------------------------------------------ #
# phase 2 — sketch throughput scaling (fresh process per device count)
# ------------------------------------------------------------------ #

def sketch_worker(n_dev: int, window: int, batch: int, repeats: int,
                  check: bool) -> int:
    from dfs_tpu.config import SimConfig
    from dfs_tpu.sim.sketch import SimSketcher, sketch_np

    # rows=1: the r15 one-chunk-per-device shape on every mesh arm (a
    # wider mesh moves more chunks per dispatch cycle; per-device work
    # is identical across arms)
    skt = SimSketcher(SimConfig(enabled=True, devices=n_dev),
                      window_bytes=window, force_sharded=True, rows=1)
    rng = np.random.default_rng(2102)
    datas = [rng.integers(0, 256, size=window, dtype=np.uint8).tobytes()
             for _ in range(batch)]
    out = skt.sketch_many(datas)             # compile + warm
    if skt._unavailable:
        raise RuntimeError(f"sharded sketch degraded at {n_dev} devices")
    total = window * batch
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = skt.sketch_many(datas)
        best = min(best, time.perf_counter() - t0)
    rec = {"devices": n_dev, "window_bytes": window, "batch": batch,
           "seconds": round(best, 4),
           "mbps": round(total / best / 2**20, 2)}
    if n_dev == 1:
        # the production devices=1 path (host oracle) — the baseline of
        # the gated user-visible ratio: what SimConfig(devices=1)
        # actually executes
        one = SimSketcher(SimConfig(enabled=True, devices=1),
                          window_bytes=window)
        one.sketch_many(datas[:2])           # warm
        b1 = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            o1 = one.sketch_many(datas)
            b1 = min(b1, time.perf_counter() - t0)
        if not np.array_equal(o1, out):
            raise AssertionError("oracle path != mesh kernel output")
        rec["oracle_mbps"] = round(total / b1 / 2**20, 2)
    if check:
        same = all(
            np.array_equal(out[i],
                           sketch_np(d, skt.cfg.sketch_size,
                                     skt.cfg.shingle_bytes,
                                     skt.lanes_a, skt.lanes_b))
            for i, d in enumerate(datas))
        rec["oracle_identical"] = bool(same)
        if not same:
            raise AssertionError("sharded sketch != NumPy oracle")
    print(json.dumps(rec))
    return 0


def sketch_phase(p: dict) -> dict:
    import os as _os
    cores = len(_os.sched_getaffinity(0)) if hasattr(_os,
                                                     "sched_getaffinity") \
        else (_os.cpu_count() or 1)
    out: dict = {"window_bytes": p["window"], "batch": p["batch"],
                 "host_cores": cores,
                 "methodology": (
                     "virtual CPU mesh, one intra-op thread per device, "
                     "fresh process per count (CDC_SHARD_r15.json "
                     "scope). mbps arms all run the mesh kernel, one "
                     "chunk per device per dispatch; mesh_scale is "
                     "mesh-4 / mesh-1 (on a host where virtual devices "
                     "timeshare host_cores physical cores it reflects "
                     "dispatch amortization, not parallel compute). "
                     "scale_max_devices — the gated, user-visible "
                     "ratio — is the sharded pipeline at the max "
                     "device count vs what SimConfig(devices=1) "
                     "actually executes (the host-oracle path), i.e. "
                     "the throughput multiplier of turning the device "
                     "axis on; oracle_identical pins the two paths "
                     "byte-identical"),
                 "devices": [], "mbps": []}
    for n in p["devices"]:
        check = n == max(p["devices"])
        cmd = [sys.executable, __file__, "--sketch-worker", str(n),
               "--window", str(p["window"]), "--batch", str(p["batch"]),
               "--repeats", str(p["repeats"])]
        if check:
            cmd.append("--check")
        log(f"  sketch devices={n} (fresh process)…")
        res = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=1800)
        if res.returncode != 0:
            raise RuntimeError(f"sketch worker failed:\n"
                               f"{res.stderr[-2000:]}")
        rec = json.loads(res.stdout.strip().splitlines()[-1])
        log(f"  sketch devices={n}: {rec['mbps']} MiB/s")
        out["devices"].append(n)
        out["mbps"].append(rec["mbps"])
        if "oracle_mbps" in rec:
            out["oracle_mbps_1dev"] = rec["oracle_mbps"]
        if check:
            out["oracle_identical"] = rec.get("oracle_identical", False)
    out["mesh_scale"] = round(out["mbps"][-1] / out["mbps"][0], 3)
    out["scale_max_devices"] = round(
        out["mbps"][-1] / out["oracle_mbps_1dev"], 3)
    return out


# ------------------------------------------------------------------ #
# phase 3 — kill -9 at every sim.* crash point
# ------------------------------------------------------------------ #

def _crash_store(root: Path, p: dict):
    from dfs_tpu.sim import SimPlane
    from dfs_tpu.store.cas import NodeStore

    ns = NodeStore(root, 1)
    ns.chunks.sim = SimPlane(_sim_cfg(p, rematerialize_reads=1),
                             ns.root / "sim")
    return ns


def crash_worker(point: str, root: Path, step: str, p: dict) -> int:
    from dfs_tpu.chaos import ChaosInjector
    from dfs_tpu.config import ChaosConfig
    from dfs_tpu.utils.hashing import sha256_hex

    rng = np.random.default_rng(2103)
    base = rng.integers(0, 256, size=p["chunk_bytes"],
                        dtype=np.uint8).tobytes()
    near = bytearray(base)
    near[len(near) // 2] ^= 0x5A
    near = bytes(near)
    d0, d1 = sha256_hex(base), sha256_hex(near)
    ns = _crash_store(root, p)
    if step == "prep":
        acked = {}
        ns.chunks.put(d0, base)
        acked[d0] = base.hex()
        if point != "sim.after_delta_write":
            # the delta is part of the acked state for the GC and
            # re-materialize scenarios; for after_delta_write the
            # TRIGGER is the delta put itself
            ns.chunks.put(d1, near)
            assert ns.chunks.delta_base(d1) == d0, \
                "crash scenario needs a real delta"
            acked[d1] = near.hex()
        (root / "acked.json").write_text(json.dumps(acked))
        ns.chunks.sim.close()
        return 0
    # trigger: arm the point through the real chaos injector and run
    # the op that crosses it — the process dies by SIGKILL inside
    inj = ChaosInjector(ChaosConfig(enabled=True, crash_point=point), 1)
    ns.chunks.sim.crash = inj.maybe_crash
    if point == "sim.after_delta_write":
        ns.chunks.put(d1, near)              # dies after the delta link
    elif point == "sim.before_base_gc":
        # no manifests reference anything: the whole chain is dead and
        # GC dies with live+pinned computed, nothing deleted yet
        ns.gc(min_age_s=0.0)
    else:                                    # sim.after_rematerialize
        ns.chunks.get(d1)                    # dies raw-durable,
        #                                      delta not yet unlinked
    raise RuntimeError(f"{point} never fired")


def crash_phase(root: Path, p: dict) -> dict:
    import signal

    from dfs_tpu.utils.hashing import sha256_hex

    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(Path(__file__).parent)}
    points: dict[str, dict] = {}
    for point in SIM_POINTS:
        pdir = root / point.replace(".", "_")
        pdir.mkdir(parents=True)
        base_cmd = [sys.executable, __file__, "--crash-worker", point,
                    "--dir", str(pdir), "--geometry", p["geometry"]]
        res = subprocess.run(base_cmd + ["--step", "prep"],
                             capture_output=True, text=True,
                             timeout=300, env=env)
        if res.returncode != 0:
            raise RuntimeError(f"{point} prep failed:\n"
                               f"{res.stderr[-2000:]}")
        res = subprocess.run(base_cmd + ["--step", "trigger"],
                             capture_output=True, text=True,
                             timeout=300, env=env)
        killed = res.returncode == -signal.SIGKILL
        # restart: the store must rebuild delta state from the on-disk
        # headers alone and serve every acked chunk byte-identical
        ns = _crash_store(pdir, p)
        acked = json.loads((pdir / "acked.json").read_text())
        verified = all(
            (got := ns.chunks.get(d)) is not None
            and got == bytes.fromhex(hx) and sha256_hex(got) == d
            for d, hx in acked.items())
        converged = True
        if point == "sim.before_base_gc":
            # the interrupted GC must still fully reclaim on retry
            # (fixpoint over the pin order), deltas before bases
            ns.gc(min_age_s=0.0)
            converged = ns.chunks.count() == 0 \
                and ns.chunks.delta_count() == 0
        ns.chunks.sim.close()
        rec = {"killed": bool(killed), "verified": bool(verified),
               "converged": bool(converged),
               "acked": len(acked),
               "ok": bool(killed and verified and converged)}
        log(f"  crash {point}: {rec}")
        points[point] = rec
    return {"points": points,
            "ok": all(v["ok"] for v in points.values())}


# ------------------------------------------------------------------ #
# phase 4 — default-off identity
# ------------------------------------------------------------------ #

def default_off_phase(root: Path, p: dict) -> dict:
    from dfs_tpu.config import SimConfig
    from dfs_tpu.store.cas import ChunkStore
    from dfs_tpu.utils.hashing import sha256_hex

    ok = SimConfig() == SimConfig(enabled=False)
    cs = ChunkStore(root / "chunks")
    rng = np.random.default_rng(2104)
    items = [(lambda b: (sha256_hex(b), b))(
        rng.integers(0, 256, size=p["chunk_bytes"],
                     dtype=np.uint8).tobytes()) for _ in range(4)]
    cs.put_batch(items)
    ok = ok and all(cs.get(d) == b for d, b in items)
    ok = ok and not (root / "chunks" / "deltas").exists()
    ok = ok and cs.delta_count() == 0
    # the tree is raw chunk files under 2-hex prefixes, nothing else
    subs = {q.name for q in (root / "chunks").iterdir()}
    ok = ok and subs == {d[:2] for d, _ in items}
    return {"ok": bool(ok)}


# ------------------------------------------------------------------ #

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tier-1 smoke: identity/crash/savings gated, "
                         "perf reported but not gated")
    ap.add_argument("--out", default=None)
    ap.add_argument("--sketch-worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--window", type=int, default=64 * 1024,
                    help=argparse.SUPPRESS)
    ap.add_argument("--batch", type=int, default=192,
                    help=argparse.SUPPRESS)
    ap.add_argument("--repeats", type=int, default=3,
                    help=argparse.SUPPRESS)
    ap.add_argument("--check", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--crash-worker", default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--dir", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--step", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--geometry", default="full",
                    choices=["full", "tiny"], help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.sketch_worker is not None:
        return sketch_worker(args.sketch_worker, args.window,
                             args.batch, args.repeats, args.check)
    if args.crash_worker is not None:
        p = TINY if args.geometry == "tiny" else FULL
        return crash_worker(args.crash_worker, Path(args.dir),
                            args.step, p)
    p = TINY if args.tiny else FULL

    import tempfile

    out: dict = {"metric": "similarity_plane", "round": 21,
                 "mode": "tiny" if args.tiny else "full"}
    base = "/dev/shm" if os.path.isdir("/dev/shm") \
        and os.access("/dev/shm", os.W_OK) else None
    with tempfile.TemporaryDirectory(prefix="bench_sim_",
                                     dir=base) as tmp:
        root = Path(tmp)
        log("phase 1: K-generation mutated corpus…")
        out["corpus"] = corpus_phase(root / "corpus", p)
        log(f"  stored {out['corpus']['sim_bytes']} vs dedup-only "
            f"{out['corpus']['dedup_bytes']} "
            f"(savings {out['corpus']['savings_frac']:.1%})")
        log("phase 2: sketch throughput scaling…")
        out["sketch"] = sketch_phase(p)
        log("phase 3: kill -9 at every sim.* crash point…")
        out["crash"] = crash_phase(root / "crash", p)
        log("phase 4: default-off identity…")
        out["default_off"] = default_off_phase(root / "off", p)

    c, s = out["corpus"], out["sketch"]
    gates = {
        "corpus": {
            "gateApplied": not args.tiny,
            "generations": c["generations"],
            "simBytes": c["sim_bytes"], "dedupBytes": c["dedup_bytes"],
            "savingsFrac": c["savings_frac"],
            "byteIdentity": c["byte_identical"],
            # tiny still gates DIRECTION (below baseline) + identity;
            # full gates the 30% savings magnitude
            "ok": bool(c["byte_identical"]
                       and c["sim_bytes"] < c["dedup_bytes"]
                       and (args.tiny or c["savings_frac"] >= 0.3))},
        "sketch_scale": {
            "gateApplied": not args.tiny,
            "devices": s["devices"], "mbps": s["mbps"],
            "oracleMbps1Dev": s["oracle_mbps_1dev"],
            "meshScale": s["mesh_scale"],
            "scaleMaxDevices": s["scale_max_devices"],
            "oracleIdentical": s.get("oracle_identical", False),
            "ok": bool(s.get("oracle_identical", False)
                       and (args.tiny
                            or s["scale_max_devices"] >= 1.7))},
        "crash": out["crash"],
        "default_off": out["default_off"],
    }
    out["gates"] = gates
    out["ok"] = all(g["ok"] for g in gates.values())
    log(f"ok={out['ok']} savings={c['savings_frac']:.1%} "
        f"scale={s['scale_max_devices']} crash={gates['crash']['ok']}")

    path = args.out or (None if args.tiny
                        else Path(__file__).parent / ART)
    if path:
        Path(path).write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
