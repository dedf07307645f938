"""Chaos plane + durability hardening (dfs_tpu/chaos, docs/chaos.md).

Four layers of coverage:

- UNIT: injector determinism under a fixed seed, runtime knob-swap
  validation, retry-budget token bucket, boot sweep reconciliation.
- DEFAULT-OFF IDENTITY: the default config builds NO injector and no
  store fault hook — the chaos-less node runs the historical code
  paths (and /metrics says so).
- IN-PROCESS FAULTS: injected ENOSPC surfaces as a clean 507-class
  UploadError with the ``disk_pressure`` journal event while reads
  keep serving; torn frames tear down cleanly; a one-way partition
  still acks via handoff and HEALS to a fully clean census
  (under/over-replication AND orphans zero — the repair relocation
  pass returning handoff copies home).
- REAL PROCESSES: kill -9 at every registered crash point in the
  upload path, restart, and assert the durability contract — no
  manifest references a missing local chunk and every acked file reads
  back byte-identical; plus the ``bench_chaos.py --tiny`` subprocess
  smoke gating all five scripted scenarios (the four fault scenarios
  and the r14 add/kill/rejoin/drain membership scenario) end to end
  (CHAOS_r13.json schema + invariants).
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

import pytest

from dfs_tpu.chaos import CRASH_POINTS, ChaosInjector, MUTABLE_KNOBS
from dfs_tpu.comm.rpc import InternalClient, RetryBudget, RpcUnreachable
from dfs_tpu.config import (CDCParams, CensusConfig, ChaosConfig,
                            ClusterConfig, DurabilityConfig, NodeConfig,
                            PeerAddr)
from dfs_tpu.meta.manifest import Manifest
from dfs_tpu.node.runtime import StorageNodeServer, UploadError
from dfs_tpu.store import cas
from dfs_tpu.store.cas import NodeStore
from dfs_tpu.utils.hashing import sha256_hex

REPO = Path(__file__).resolve().parent.parent
CDC = CDCParams(min_size=2048, avg_size=8192, max_size=65536)
CENSUS_OFF = CensusConfig(history_interval_s=0)


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


def _mk_cluster(n: int, rf: int) -> ClusterConfig:
    ports = _free_ports(2 * n)
    peers = tuple(PeerAddr(node_id=i + 1, host="127.0.0.1",
                           port=ports[2 * i],
                           internal_port=ports[2 * i + 1])
                  for i in range(n))
    return ClusterConfig(peers=peers, replication_factor=rf)


async def _start_nodes(cluster: ClusterConfig, root: Path,
                       chaos_by_node: dict[int, ChaosConfig]
                       | None = None,
                       **cfg_kw) -> dict[int, StorageNodeServer]:
    nodes = {}
    for p in cluster.peers:
        kw = dict(cfg_kw)
        if chaos_by_node and p.node_id in chaos_by_node:
            kw["chaos"] = chaos_by_node[p.node_id]
        cfg = NodeConfig(node_id=p.node_id, cluster=cluster,
                         data_root=root, fragmenter="cdc", cdc=CDC,
                         health_probe_s=0, census=CENSUS_OFF, **kw)
        n = StorageNodeServer(cfg)
        await n.start()
        nodes[p.node_id] = n
    return nodes


async def _stop_all(nodes) -> None:
    for n in nodes.values():
        await n.stop()


# ------------------------------------------------------------------ #
# unit: injector + budget + boot sweep
# ------------------------------------------------------------------ #

def test_injector_deterministic_under_fixed_seed():
    """Two injectors with the same (seed, node) produce the same
    decision stream — the fault schedule is reproducible; a different
    node id yields a different (but equally deterministic) stream."""
    cfg = ChaosConfig(enabled=True, seed=42, rpc_drop_rate=0.5,
                      rpc_truncate_rate=0.3, disk_error_rate=0.2)
    a = ChaosInjector(cfg, 1)
    b = ChaosInjector(cfg, 1)
    c = ChaosInjector(cfg, 2)
    seq_a = [a.roll() for _ in range(64)]
    seq_b = [b.roll() for _ in range(64)]
    seq_c = [c.roll() for _ in range(64)]
    assert seq_a == seq_b
    assert seq_a != seq_c
    # decision-level determinism too (truncate draws from the stream)
    a2 = ChaosInjector(cfg, 1)
    b2 = ChaosInjector(cfg, 1)
    assert [a2.truncate_now(2, "op") for _ in range(64)] \
        == [b2.truncate_now(2, "op") for _ in range(64)]


def test_injector_knob_validation():
    inj = ChaosInjector(ChaosConfig(enabled=True), 1)
    with pytest.raises(ValueError):
        inj.set(nonsense_knob=1)
    with pytest.raises(ValueError):
        inj.set(seed=7)            # boot-only knob is immutable
    with pytest.raises(ValueError):
        inj.set(crash_point="not.a.registered.point")
    with pytest.raises(ValueError):
        ChaosInjector(ChaosConfig(enabled=True,
                                  crash_point="bogus.point"), 1)
    # every registered point is accepted (the registry IS the contract)
    for point in CRASH_POINTS:
        inj.set(crash_point=point)
    inj.set(crash_point="")
    assert MUTABLE_KNOBS <= {
        "rpc_delay_s", "rpc_delay_peers", "rpc_drop_rate", "partition",
        "rpc_truncate_rate", "serve_delay_s", "disk_error_rate",
        "disk_full", "disk_delay_s", "crash_point"}


def test_chaos_config_validation():
    with pytest.raises(ValueError):
        ChaosConfig(rpc_drop_rate=1.5)
    with pytest.raises(ValueError):
        ChaosConfig(rpc_delay_s=-1)
    with pytest.raises(ValueError):
        ChaosConfig(partition="2,not-a-node")
    with pytest.raises(ValueError):
        DurabilityConfig(mode="sometimes")


def test_retry_budget_token_bucket():
    b = RetryBudget(capacity=3, refill_per_s=0.0)
    assert [b.take(1) for _ in range(3)] == [True] * 3
    assert b.take(1) is False          # bucket empty, no refill
    assert b.take(2) is True           # per-peer buckets are independent
    s = b.stats()
    assert s["exhausted"]["1"] == 1
    assert s["tokens"]["1"] == 0.0
    # refill restores tokens over time
    b2 = RetryBudget(capacity=1, refill_per_s=1000.0)
    assert b2.take(1) is True          # drain the single token
    time.sleep(0.01)                   # ~10 tokens of refill
    assert b2.take(1) is True


def test_boot_sweep_reconciles_crash_leftovers(tmp_path):
    """A crash between CAS put and manifest write leaves temp files and
    unreferenced chunks; boot_sweep reclaims ALL temps (nothing can be
    in flight before the servers start) and aged orphans only — a
    young orphan may belong to a not-yet-adopted manifest."""
    store = NodeStore(tmp_path, 1)
    old = b"old-orphan-payload"
    young = b"young-orphan-payload"
    d_old, d_young = sha256_hex(old), sha256_hex(young)
    store.chunks.put(d_old, old)
    store.chunks.put(d_young, young)
    two_h_ago = time.time() - 7200
    os.utime(store.chunks._path(d_old), (two_h_ago, two_h_ago))
    # a fresh crash-leaked temp: younger than the runtime hour gate,
    # but boot reclaims it regardless
    tmp_file = store.chunks.root / "ab" / ".tmp-99999-0"
    tmp_file.parent.mkdir(parents=True, exist_ok=True)
    tmp_file.write_bytes(b"torn")
    swept = store.boot_sweep()
    assert swept["tmps"] == 1 and not tmp_file.exists()
    assert swept["orphans"] == 1
    assert not store.chunks.has(d_old)      # aged orphan reclaimed
    assert store.chunks.has(d_young)        # young orphan spared


def test_fsync_mode_counts_barriers(tmp_path):
    on = NodeStore(tmp_path / "on", 1, fsync=True)
    off = NodeStore(tmp_path / "off", 1, fsync=False)
    data = b"payload" * 100
    d = sha256_hex(data)
    assert on.chunks.put(d, data) and off.chunks.put(d, data)
    assert on.chunks.fsync_count() == 1
    assert off.chunks.fsync_count() == 0
    assert on.chunks.get(d) == data


_KILL_BETWEEN_LINKS_AND_BARRIERS = """
import os, random, signal, sys
from dfs_tpu.store import cas
from dfs_tpu.utils.hashing import sha256_hex

def kill(path):                     # the first directory barrier of (d)
    os.kill(os.getpid(), signal.SIGKILL)

cas._fsync_path = kill
rng = random.Random(7)
items = []
for _ in range(300):
    b = rng.randbytes(rng.randrange(100, 9000))
    items.append((sha256_hex(b), b))
store = cas.ChunkStore(sys.argv[1], fsync=True)
store.put_batch(items)
print("RETURNED", flush=True)       # an ack would follow: must not happen
"""


def test_kill_between_the_batch_links_and_directory_barriers(
        tmp_path, monkeypatch):
    """kill -9 after phase (c) of a batch put, before the first directory
    barrier of (d): the put never returned (nothing was acked), and
    every name present holds its full payload — the payload barrier
    preceded the link. The temps it leaves are the boot sweep's, and so
    are the directory barriers the dead life owed."""
    root = tmp_path / "node-1"
    proc = subprocess.run(
        [sys.executable, "-c", _KILL_BETWEEN_LINKS_AND_BARRIERS,
         str(root / "chunks")],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        cwd=str(Path(__file__).resolve().parent.parent))
    assert proc.returncode == -signal.SIGKILL, proc.stderr[-2000:]
    assert "RETURNED" not in proc.stdout
    store = NodeStore(tmp_path, 1, fsync=True)
    names = store.chunks.digests()
    assert len(names) == 300                # every link of (c) had landed
    for d in names:
        assert sha256_hex(store.chunks.get(d)) == d
    assert store.chunks.fsync_count() == 0  # a new life counts from zero
    leaked = list(store.chunks.root.rglob(".tmp-*"))
    assert len(leaked) == 300               # (e) never ran
    # this life cannot know which names the dead one still owed a
    # directory barrier (that set died with it): the boot sweep fsyncs
    # every chunk directory, BEFORE a dedup hit on such a name is answered
    barriered = []
    monkeypatch.setattr(cas, "_fsync_path", barriered.append)
    swept = store.boot_sweep()
    assert swept["tmps"] == 300
    assert list(store.chunks.root.rglob(".tmp-*")) == []
    parents = {str(store.chunks.root / d[:2]) for d in names}
    assert set(barriered) == parents and len(barriered) == len(parents)
    assert swept["dirBarriers"] == len(parents) \
        == store.chunks.dir_barrier_count()
    # the names stay: a repeat (the client's retry) is a dedup hit, acked
    # on names the sweep above made durable
    again = store.chunks.put_batch([(d, store.chunks.get(d))
                                    for d in names[:10]])
    assert again == [False] * 10


# ------------------------------------------------------------------ #
# default-off identity
# ------------------------------------------------------------------ #

def test_default_config_builds_no_injector(tmp_path):
    """ChaosConfig() means NO injector, NO store hook, NO client seam —
    the disabled node runs the historical code paths (zero-overhead
    off switch), and /metrics reports the plane disabled."""
    assert ChaosConfig() == ChaosConfig(enabled=False)
    cluster = _mk_cluster(1, rf=1)
    cfg = NodeConfig(node_id=1, cluster=cluster, data_root=tmp_path,
                     fragmenter="cdc", cdc=CDC, health_probe_s=0,
                     census=CENSUS_OFF)
    node = StorageNodeServer(cfg)
    assert node.chaos is None
    assert node.store.chunks.fault is None
    assert node.client._chaos is None
    assert node.chaos_stats() == {"enabled": False}
    # default durability is the hardened mode
    assert cfg.durability.mode == "fsync"
    assert node.durability_stats()["mode"] == "fsync"


def test_all_zero_knobs_behave_identically(tmp_path):
    """chaos ENABLED with every knob zero must be behaviorally inert:
    same acks, same bytes, zero injected faults counted."""
    datasets = [b"alpha" * 4000, b"beta" * 9000, os.urandom(30000)]

    async def run() -> dict:
        results = {}
        for arm, chaos in (("off", None),
                           ("on", ChaosConfig(enabled=True, seed=5))):
            cluster = _mk_cluster(2, rf=2)
            nodes = await _start_nodes(
                cluster, tmp_path / arm,
                chaos_by_node={1: chaos, 2: chaos} if chaos else None)
            try:
                got = []
                for i, data in enumerate(datasets):
                    m, stats = await nodes[1].upload(data, f"f{i}.bin")
                    _, body = await nodes[2].download(m.file_id)
                    got.append((m.file_id, bytes(body) == data,
                                stats["minCopies"]))
                results[arm] = got
                if chaos is not None:
                    assert nodes[1].chaos is not None
                    assert nodes[1].chaos.stats()["injected"] == {}
            finally:
                await _stop_all(nodes)
        return results

    results = asyncio.run(run())
    assert results["on"] == results["off"]


# ------------------------------------------------------------------ #
# in-process fault behavior
# ------------------------------------------------------------------ #

def test_enospc_surfaces_as_507_reads_keep_serving(tmp_path):
    """Injected-full store: uploads fail with a clean 507-class
    UploadError + a journaled disk_pressure event; reads (local and
    peer-facing) keep working."""

    async def run() -> None:
        cluster = _mk_cluster(1, rf=1)
        nodes = await _start_nodes(
            cluster, tmp_path,
            chaos_by_node={1: ChaosConfig(enabled=True)})
        node = nodes[1]
        try:
            m, _ = await node.upload(b"pre-fault" * 2000, "pre.bin")
            node.chaos.set(disk_full=True)
            with pytest.raises(UploadError) as ei:
                await node.upload(os.urandom(20000), "doomed.bin")
            assert ei.value.status == 507
            assert "nsufficient storage" in str(ei.value)
            # reads still serve while the disk is full
            _, body = await node.download(m.file_id)
            assert bytes(body) == b"pre-fault" * 2000
            assert node.counters.snapshot()["disk_full_rejects"] >= 1
            assert node.chaos.stats()["injected"].get("disk_full",
                                                      0) >= 1
            # the journal carries the disk_pressure evidence — once
            # its writer thread has it on disk: wait on the written-
            # record count, not on the thread's luck under load
            await asyncio.to_thread(node.obs.journal.flush)
            tail = await asyncio.to_thread(node.obs.journal.tail,
                                           0.0, 256)
            assert any(ev.get("type") == "disk_pressure"
                       for ev in tail["events"])
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_torn_frames_and_drops_never_lose_acked_writes(tmp_path):
    """Link-level chaos (drops + torn frames) on the coordinator's
    client: whatever acks must read back byte-identical — and torn
    frames never wedge the receiving server (prompt teardown, next
    connection serves)."""

    async def run() -> None:
        cluster = _mk_cluster(2, rf=2)
        nodes = await _start_nodes(
            cluster, tmp_path,
            chaos_by_node={1: ChaosConfig(enabled=True, seed=9,
                                          rpc_drop_rate=0.2,
                                          rpc_truncate_rate=0.2)})
        try:
            acked = []
            for i in range(6):
                data = os.urandom(24000)
                try:
                    m, _ = await nodes[1].upload(data, f"t{i}.bin")
                    acked.append((m.file_id, data))
                except UploadError:
                    pass   # an un-acked upload may be lost — the contract
            inj = nodes[1].chaos.stats()["injected"]
            assert inj.get("rpc_drop", 0) \
                + inj.get("rpc_truncate", 0) > 0
            nodes[1].chaos.set(rpc_drop_rate=0.0, rpc_truncate_rate=0.0)
            for fid, data in acked:
                _, body = await nodes[2].download(fid)
                assert bytes(body) == data
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_partition_budget_fastfail_and_journal(tmp_path):
    """A partitioned peer exhausts the retry budget quickly; further
    calls fast-fail (no storm) and the journal carries
    retry_budget_exhausted evidence."""

    async def run() -> None:
        cluster = _mk_cluster(2, rf=2)
        nodes = await _start_nodes(
            cluster, tmp_path,
            chaos_by_node={1: ChaosConfig(enabled=True, partition="2")})
        node = nodes[1]
        try:
            node.client.retry_budget = RetryBudget(capacity=2,
                                                   refill_per_s=0.0)
            peer = cluster.peer(2)
            for _ in range(4):
                with pytest.raises(RpcUnreachable):
                    await node.client.call(peer, {"op": "health"})
            assert node.client.retry_budget.stats()[
                "exhausted"]["2"] >= 1
            await asyncio.to_thread(node.obs.journal.flush)
            tail = await asyncio.to_thread(node.obs.journal.tail,
                                           0.0, 256)
            assert any(ev.get("type") == "retry_budget_exhausted"
                       for ev in tail["events"])
            assert any(ev.get("type") == "chaos_inject"
                       and ev.get("kind") == "partition"
                       for ev in tail["events"])
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_partition_heal_repair_converges_census_clean(tmp_path):
    """One-way partition (1 -/-> 2) during uploads at node 1: every
    upload acks via sloppy-quorum handoff. After heal, repair cycles
    must converge the census to FULLY clean — under-replicated 0 (the
    missed replicas pushed), over-replicated 0 (the handoff copies
    RELOCATED home), orphans 0 (nothing aborted)."""

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        nodes = await _start_nodes(
            cluster, tmp_path,
            chaos_by_node={1: ChaosConfig(enabled=True, partition="2")})
        try:
            acked = []
            for i in range(4):
                data = os.urandom(40000)
                m, stats = await nodes[1].upload(data, f"p{i}.bin")
                acked.append((m.file_id, data))
                assert stats["minCopies"] >= 2  # quorum via handoff
            rep = await nodes[1].census_report()
            assert rep["peersFailed"] == 1    # the census SEES the cut
            # heal + converge: a few repair rounds across all nodes
            nodes[1].chaos.set(partition="")
            clean = None
            for _ in range(6):
                for n in nodes.values():
                    await n.repair_once()
                rep = await nodes[1].census_report()
                if (rep["underReplicatedTotal"] == 0
                        and rep["overReplicatedTotal"] == 0
                        and rep["orphanedTotal"] == 0
                        and rep["peersFailed"] == 0):
                    clean = rep
                    break
            assert clean is not None, (
                f"census never converged: under="
                f"{rep['underReplicatedTotal']} over="
                f"{rep['overReplicatedTotal']} "
                f"orph={rep['orphanedTotal']}")
            # zero acked-write loss, byte-identical — from EVERY node
            for fid, data in acked:
                for n in nodes.values():
                    _, body = await n.download(fid)
                    assert bytes(body) == data
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# real processes: crash points + the bench smoke
# ------------------------------------------------------------------ #

def _serve_argv(http_port: int, internal_port: int, data_root: Path,
                crash_point: str = "") -> list[str]:
    argv = [sys.executable, "-m", "dfs_tpu.cli.main", "serve",
            "--node-id", "1", "--nodes", "1",
            "--base-port", str(http_port),
            "--base-internal-port", str(internal_port),
            "--replication-factor", "1",
            "--fragmenter", "cdc", "--data-root", str(data_root),
            "--repair-interval", "0", "--probe-interval", "0"]
    if crash_point:
        argv += ["--chaos", "--chaos-crash-point", crash_point]
    return argv


def _wait_status(port: int, proc: subprocess.Popen,
                 timeout: float = 60.0) -> None:
    import urllib.request

    deadline = time.time() + timeout
    while True:
        if proc.poll() is not None:
            raise AssertionError("node died during startup")
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/status", timeout=2) as r:
                assert r.read() == b"OK"
                return
        except OSError:
            if time.time() > deadline:
                raise AssertionError("node never came up")
            time.sleep(0.2)


def _http(port: int, method: str, path: str,
          body: bytes | None = None,
          timeout: float = 60.0) -> tuple[int, bytes]:
    import urllib.error
    import urllib.request

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _assert_manifests_locally_complete(data_root: Path) -> int:
    """THE crash-durability invariant on a 1-node cluster: every
    manifest present on disk references only chunks present on disk
    (an acked upload is exactly a manifest + its chunks; fsync-before-
    ack means a crash can never leave the manifest without bytes)."""
    mdir = data_root / "node-1" / "manifests"
    cdir = data_root / "node-1" / "chunks"
    checked = 0
    for p in sorted(mdir.glob("*.json")):
        m = Manifest.from_json(p.read_bytes())
        for d in m.all_digests():
            assert (cdir / d[:2] / d).is_file(), (
                f"manifest {m.file_id[:12]} references missing "
                f"chunk {d[:12]} after crash-restart")
            checked += 1
    return checked


def test_kill9_at_every_crash_point_then_restart(tmp_path, rng):
    """For EVERY registered crash point in the upload path: boot a
    real node with the point armed, ack one file, attempt another
    upload (the process SIGKILLs itself mid-write-path), restart
    clean, and assert (a) every previously-acked file reads back
    byte-identical, (b) no on-disk manifest references a missing local
    chunk. The store directory is REUSED across points, so recovery
    compounds: each iteration also re-verifies everything acked in the
    ones before."""
    ports = _free_ports(2)
    http_port, internal_port = ports
    data_root = tmp_path / "data"
    acked: list[tuple[str, bytes]] = []
    seq = 0
    # demote.* points fire in the tiering worker, not the upload path —
    # a node armed with one would never crash here (covered by the
    # dedicated kill-9 tests in tests/test_tiering.py instead); sim.*
    # points need --sim, which this harness leaves off (covered by the
    # bench_sim.py crash matrix and tests/test_sim.py)
    for point in sorted(p for p in CRASH_POINTS
                        if not p.startswith(("demote.", "sim."))):
        # phase 1: healthy boot — ack one file
        proc = subprocess.Popen(
            _serve_argv(http_port, internal_port, data_root),
            cwd=tmp_path,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": str(REPO)},
            stdout=(tmp_path / "node.log").open("ab"),
            stderr=subprocess.STDOUT)
        try:
            _wait_status(http_port, proc)
            data = rng.integers(0, 256, size=30000,
                                dtype="uint8").tobytes() + bytes([seq])
            seq += 1
            status, body = _http(http_port, "POST",
                                 f"/upload?name=ok{seq}.bin", data)
            assert status == 201, body
            info = json.loads(body)
            assert info["fileId"] == sha256_hex(data)
            acked.append((info["fileId"], data))
        finally:
            proc.terminate()
            proc.wait(timeout=10)

        # phase 2: boot with the crash point ARMED — the next upload
        # dies by SIGKILL somewhere inside the write path
        proc = subprocess.Popen(
            _serve_argv(http_port, internal_port, data_root,
                        crash_point=point),
            cwd=tmp_path,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": str(REPO)},
            stdout=(tmp_path / "node.log").open("ab"),
            stderr=subprocess.STDOUT)
        try:
            _wait_status(http_port, proc)
            doomed = rng.integers(0, 256, size=30000,
                                  dtype="uint8").tobytes()
            got_ack = False
            try:
                status, body = _http(http_port, "POST",
                                     "/upload?name=doomed.bin", doomed,
                                     timeout=30)
                got_ack = status == 201
            except OSError:
                pass                      # connection died with the node
            rc = proc.wait(timeout=30)
            assert rc == -signal.SIGKILL, (
                f"{point}: expected SIGKILL death, got {rc}")
            assert not got_ack, f"{point}: crashed upload must not ack"
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)

        # phase 3: restart clean — durability invariants hold
        proc = subprocess.Popen(
            _serve_argv(http_port, internal_port, data_root),
            cwd=tmp_path,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": str(REPO)},
            stdout=(tmp_path / "node.log").open("ab"),
            stderr=subprocess.STDOUT)
        try:
            _wait_status(http_port, proc)
            for fid, data in acked:
                status, body = _http(http_port, "GET",
                                     f"/download?fileId={fid}")
                assert status == 200, f"{point}: acked {fid[:12]} lost"
                assert body == data, f"{point}: acked {fid[:12]} corrupt"
            _assert_manifests_locally_complete(data_root)
        finally:
            proc.terminate()
            proc.wait(timeout=10)
    assert len(acked) == len(
        [p for p in CRASH_POINTS
         if not p.startswith(("demote.", "sim."))])


def test_bench_chaos_tiny_smoke(tmp_path):
    """The full harness, end to end: ``bench_chaos.py --tiny`` runs the
    four fault scenarios against a real 3-process cluster plus the r14
    membership scenario (join mid-ingest, SIGKILL mid-rebalance,
    rejoin, drain) on its own 4-process ring cluster — all must gate
    green: zero acked-write loss, byte-identity, no phantom sheds,
    stitched traces, correct doctor/census findings. Also locks the
    CHAOS_r13.json schema the committed artifact embeds."""
    out_path = tmp_path / "chaos_tiny.json"
    res = subprocess.run(
        [sys.executable, str(REPO / "bench_chaos.py"), "--tiny",
         "--out", str(out_path)],
        cwd=tmp_path, capture_output=True, text=True, timeout=540,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(REPO)})
    # drain the writeback this run dirtied (hundreds of MB across 3
    # node stores): the NEXT test's fsync-mode uploads must not stall
    # behind our flush and flake on client timeouts
    os.sync()
    assert res.returncode == 0, (
        f"bench_chaos --tiny failed:\n{res.stdout[-2000:]}"
        f"\n{res.stderr[-4000:]}")
    out = json.loads(out_path.read_text())
    assert out["metric"] == "chaos_invariants" and out["round"] == 13
    assert out["ok"] is True
    scenarios = out["scenarios"]
    assert set(scenarios) == {"slow_peer", "partition",
                              "crash_restart", "disk_full",
                              "add_remove_node"}
    for name, s in scenarios.items():
        assert s["ok"] is True, name
        assert s["zero_acked_loss"] and s["byte_identical"], name
        assert s["no_phantom_sheds"], name
        assert s["trace_stitchable"], name
        assert s["acked"] > 0, name
    assert scenarios["slow_peer"]["doctor_named_slow_peer"]
    assert scenarios["partition"]["doctor_saw_dead_link"]
    assert scenarios["partition"]["over_replicated"] == 0
    assert scenarios["crash_restart"]["crash_point_fired_sigkill"]
    assert scenarios["disk_full"]["full_node_answers_507"]
    assert scenarios["disk_full"]["full_node_reads_ok"]
    assert scenarios["add_remove_node"]["over_replicated"] == 0
    assert scenarios["add_remove_node"]["node4_drained_empty"]
    assert scenarios["disk_full"]["no_500s"]

    # schema lock against the COMMITTED artifact: same keys, so the
    # bench cannot drift away from what CHAOS_r13.json claims
    committed = json.loads((REPO / "CHAOS_r13.json").read_text())
    assert set(committed) == set(out)
    assert set(committed["scenarios"]) == set(out["scenarios"])
    for name in scenarios:
        assert set(committed["scenarios"][name]) \
            == set(out["scenarios"][name]), name
    assert committed["ok"] is True
