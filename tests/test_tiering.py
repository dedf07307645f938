"""Hot/cold tiering plane (dfs_tpu/tier, docs/tiering.md).

Layers of coverage:

- UNIT: TemperatureLedger half-life decay, LRU bound, snapshot/restore
  (including damage -> fresh ledger), per-file MEAN temperature; the
  byte-budget classifier's knee and its min-idle floor.
- DEFAULT-OFF IDENTITY: ``TierConfig()`` builds no plane, no tier dir,
  no worker — and manifests carry NO tier key, so the on-disk bytes of
  an untiered cluster are identical to every pre-r20 release.
- CLUSTER (in-process): a 3-node cluster demotes its cold tail to EC,
  every file stays byte-identical on every node while surplus replicas
  are reclaimed, and repeated reads of a cold file promote it back to
  full replication in the background.
- CRASH SAFETY (real ``kill -9``): for each demote.* crash point a real
  node dies mid-demotion, restarts, and the cluster converges to a
  clean census with zero acked-read loss — the demotion ordering
  (parity before flip, flip before deletes) is the invariant under test.
- SATELLITES: scrub's index-vs-walk healing (both divergence
  directions) and the capacity-derived default weight for ``ring add``.
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

from dfs_tpu.config import (CDCParams, CensusConfig, ClusterConfig,
                            IndexConfig, NodeConfig, PeerAddr, TierConfig)
from dfs_tpu.node.runtime import StorageNodeServer
from dfs_tpu.tier import TemperatureLedger, classify
from dfs_tpu.utils.hashing import sha256_hex

REPO = Path(__file__).resolve().parent.parent
CDC = CDCParams(min_size=2048, avg_size=8192, max_size=65536)
CENSUS_OFF = CensusConfig(history_interval_s=0)

# the in-process/integration knob set: tiny idle floor and a k=1 stripe
# so a 3-node cluster can demote immediately once a scan runs
TIER_NOW = TierConfig(enabled=True, hot_fraction=0.34, min_idle_s=0.0,
                      ec_k=1, half_life_s=3600.0, promote_reads=2.0)


def _digests(n: int, tag: str = "") -> list[str]:
    return [sha256_hex(f"{tag}{i}".encode()) for i in range(n)]


# ------------------------------------------------------------------ #
# unit: temperature ledger
# ------------------------------------------------------------------ #

def test_ledger_decay_halves_per_half_life():
    led = TemperatureLedger(entries=16, half_life_s=100.0, boot_at=0.0)
    d = _digests(1)[0]
    led.note_read(d, now=0.0)
    assert led.heat(d, now=0.0) == pytest.approx(1.0)
    assert led.heat(d, now=100.0) == pytest.approx(0.5)
    assert led.heat(d, now=300.0) == pytest.approx(0.125)
    # a second read ADDS to the decayed heat, never resets it
    led.note_read(d, now=100.0)
    assert led.heat(d, now=100.0) == pytest.approx(1.5)


def test_ledger_lru_bound_evicts_stalest_updated():
    led = TemperatureLedger(entries=4, half_life_s=100.0, boot_at=7.0)
    ds = _digests(6)
    for i, d in enumerate(ds):
        led.note_read(d, now=float(i))
    assert len(led) == 4
    # the two stalest-updated digests forgot their history; unknown
    # digests answer the boot-time default (the conservative direction)
    for d in ds[:2]:
        assert led.heat(d, now=10.0) == 0.0
        assert led.last_access(d) == 7.0
    for d in ds[2:]:
        assert led.heat(d, now=10.0) > 0.0


def test_ledger_snapshot_restore_roundtrip(tmp_path):
    led = TemperatureLedger(entries=16, half_life_s=100.0, boot_at=0.0)
    ds = _digests(5, "s")
    for i, d in enumerate(ds):
        led.note_read(d, reads=float(i + 1), now=50.0)
    led.snapshot_to(tmp_path)
    back = TemperatureLedger.restore(tmp_path, 16, 100.0)
    for d in ds:
        assert back.heat(d, now=50.0) == pytest.approx(
            led.heat(d, now=50.0), rel=1e-3)
    # damage -> fresh ledger, never a raise (min_idle covers the loss)
    (tmp_path / "ledger.json").write_bytes(b"{torn")
    fresh = TemperatureLedger.restore(tmp_path, 16, 100.0)
    assert len(fresh) == 0


def test_ledger_file_temperature_is_mean_not_sum():
    """One full read of an n-chunk file must look like ONE read, not n
    — otherwise big files classify hotter than small files read equally
    often, and promote_reads means a different read count per file."""
    led = TemperatureLedger(entries=64, half_life_s=1e9, boot_at=0.0)
    big = _digests(8, "big")
    small = _digests(2, "small")
    for d in big + small:
        led.note_read(d, now=1.0)
    heat_big, _ = led.file_temperature(big, now=1.0)
    heat_small, _ = led.file_temperature(small, now=1.0)
    assert heat_big == pytest.approx(1.0)
    assert heat_big == pytest.approx(heat_small)
    # a half-read file (2 of 8 chunks) is cooler than a fully-read one
    led2 = TemperatureLedger(entries=64, half_life_s=1e9, boot_at=0.0)
    for d in big[:2]:
        led2.note_read(d, now=1.0)
    heat_partial, _ = led2.file_temperature(big, now=1.0)
    assert heat_partial == pytest.approx(0.25)


def test_classify_byte_budget_knee_and_idle_floor():
    def e(fid, nbytes, heat, last):
        return {"fileId": fid, "bytes": nbytes, "heat": heat,
                "lastAccess": last}

    entries = [e("hot", 100, 9.0, 0.0), e("warm", 100, 5.0, 0.0),
               e("cold1", 100, 0.0, 0.0), e("cold2", 100, 0.0, 0.0)]
    # 50% byte budget keeps the two hottest files; the zero-heat tail
    # past the knee is cold
    assert classify(entries, hot_fraction=0.5, min_idle_s=0.0,
                    now=1000.0) == {"cold1", "cold2"}
    # the idle floor: a file past the knee but read 10s ago is NOT
    # demotable under min_idle_s=60 — only the genuinely idle one is
    entries2 = [e("hot", 100, 9.0, 990.0), e("recent", 100, 0.2, 990.0),
                e("idle", 100, 0.0, 0.0)]
    assert classify(entries2, hot_fraction=0.33, min_idle_s=60.0,
                    now=1000.0) == {"idle"}
    # everything inside the budget stays hot regardless of idleness
    assert classify(entries, hot_fraction=1.0, min_idle_s=0.0,
                    now=1000.0) == set()
    assert classify([], hot_fraction=0.1, min_idle_s=0.0) == set()
    # the budget base is the CORPUS, not the candidate remainder: a
    # lone survivor inside hot_fraction of (survivor + already-cold)
    # bytes stays hot — without total_bytes it would demote
    lone = [e("hot", 100, 9.0, 0.0)]
    assert classify(lone, hot_fraction=0.34, min_idle_s=0.0,
                    now=1000.0) == {"hot"}
    assert classify(lone, hot_fraction=0.34, min_idle_s=0.0,
                    now=1000.0, total_bytes=300) == set()


# ------------------------------------------------------------------ #
# unit: re-demotion hysteresis (r21 flap guard)
# ------------------------------------------------------------------ #

def test_redemote_cooldown_blocks_flapping(tmp_path):
    """A file promoted moments ago must not demote again inside
    ``redemote_cooldown_s`` — the promote/demote flap around the
    promote_reads threshold would otherwise churn an EC encode +
    replica fan-out every scan. 0 (the default) keeps historical
    no-hysteresis behavior bit-for-bit."""
    from dfs_tpu.tier import TierPlane

    cold = TierPlane(TierConfig(enabled=True), tmp_path / "a")
    cold.note_promoted("f1")
    # default cooldown 0: never in cooldown, even just-promoted
    assert not cold.in_redemote_cooldown("f1", now=time.time())

    plane = TierPlane(TierConfig(enabled=True, redemote_cooldown_s=60.0,
                                 ledger_entries=256), tmp_path / "b")
    # never-promoted files are always demotable
    assert not plane.in_redemote_cooldown("f1", now=1000.0)
    plane.note_promoted("f1")
    at = plane.promoted_at["f1"]
    # inside the window: the scan must skip it
    assert plane.in_redemote_cooldown("f1", now=at + 59.9)
    # window elapsed: demotable again
    assert not plane.in_redemote_cooldown("f1", now=at + 60.1)
    # the flap cycle: a re-promotion re-arms the cooldown
    plane.note_promoted("f1")
    assert plane.in_redemote_cooldown(
        "f1", now=plane.promoted_at["f1"] + 1.0)

    # bounded like the ledger: stamps past ledger_entries evict
    # oldest-first (a forgotten stamp only re-opens eligibility early)
    for i in range(300):
        plane.note_promoted(f"bulk{i}")
    assert len(plane.promoted_at) == 256
    assert "f1" not in plane.promoted_at
    assert not plane.in_redemote_cooldown("f1", now=at + 1.0)


# ------------------------------------------------------------------ #
# cluster helpers (the test_index idiom)
# ------------------------------------------------------------------ #

def _mk_cluster(n: int, rf: int) -> ClusterConfig:
    socks, ports = [], []
    for _ in range(2 * n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    peers = tuple(PeerAddr(node_id=i + 1, host="127.0.0.1",
                           port=ports[2 * i],
                           internal_port=ports[2 * i + 1])
                  for i in range(n))
    return ClusterConfig(peers=peers, replication_factor=rf)


async def _start_nodes(cluster, root, **kw):
    nodes = {}
    for p in cluster.peers:
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
# default-off identity
# ------------------------------------------------------------------ #

def test_default_off_builds_no_plane(tmp_path):
    """TierConfig() means NO plane: no ledger dir, no worker task, no
    read-path feed — and the manifest bytes a tier-less node writes are
    identical to every pre-tiering release (no "tier" key ever)."""
    assert TierConfig() == TierConfig(enabled=False)

    async def run() -> None:
        cluster = _mk_cluster(1, rf=1)
        nodes = await _start_nodes(cluster, tmp_path)
        node = nodes[1]
        try:
            assert node.tier is None
            assert node._tier_task is None
            assert node.tier_stats() == {"enabled": False}
            m, _ = await node.upload(b"identity" * 4000, "f.bin")
            _, body = await node.download(m.file_id)
            assert bytes(body) == b"identity" * 4000
            assert not (node.store.root / "tier").exists()
            raw = (node.store.root / "manifests"
                   / f"{m.file_id}.json").read_bytes()
            assert b'"tier"' not in raw
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# cluster: demotion + promotion round-trip
# ------------------------------------------------------------------ #

def test_demote_then_promote_roundtrip(tmp_path):
    """The full lifecycle on a live 3-node cluster: a hot file keeps its
    replicas, the cold tail demotes to EC stripes with byte-identity on
    EVERY node, surplus replicas are physically reclaimed, and repeated
    reads of a cold file re-materialize it replicated in the
    background — again byte-identical everywhere."""
    async def run() -> None:
        cluster = _mk_cluster(3, rf=3)
        nodes = await _start_nodes(cluster, tmp_path, tier=TIER_NOW)
        n1 = nodes[1]
        try:
            payloads: dict[str, bytes] = {}
            for i in range(3):
                data = os.urandom(40_000) + bytes([i]) * 20_000
                m, _ = await n1.upload(data, f"f{i}.bin")
                payloads[m.file_id] = data
            hot_id = next(iter(payloads))
            for _ in range(5):
                _, body = await n1.download(hot_id)
                assert bytes(body) == payloads[hot_id]

            out = await n1.tier_scan_once()
            assert out["scanned"] == 3
            assert out["demoted"] == 2, out

            # the hot file kept its replicas; the cold two are EC now —
            # and the announce converged every node to the same view
            for n in nodes.values():
                hm = n.store.manifests.load(hot_id)
                assert hm.tier is None and hm.ec is None
                for fid in payloads:
                    if fid == hot_id:
                        continue
                    cm = n.store.manifests.load(fid)
                    assert cm.tier == "cold" and cm.ec is not None
            # byte-identity from every node, hot and cold alike
            for fid, data in payloads.items():
                for n in nodes.values():
                    _, body = await n.download(fid)
                    assert bytes(body) == data
            # surplus DATA replicas were physically reclaimed: cold
            # chunks sit at their single stripe holder, not at rf=3
            # (with k=1 the stripe adds P+Q, so TOTAL bytes stay ~3x on
            # this minimal ring — the byte saving is the ec_k>=2 bench's
            # gate; what this test pins is that deletes really ran)
            # (aggregate, not per-chunk: a k=1 stripe's parity can hash
            # identical to its shard, making THAT digest a legitimate
            # multi-holder — but the bulk of the cold set must not sit
            # at full replication anymore)
            copies = total = 0
            for fid in payloads:
                if fid == hot_id:
                    continue
                cm = n1.store.manifests.load(fid)
                for c in cm.chunks:
                    total += 1
                    copies += sum(1 for n in nodes.values()
                                  if n.store.chunks.has(c.digest))
            assert copies < 3 * total, (copies, total)
            st = n1.tier_stats()
            assert st["enabled"] is True
            assert st["scans"] == 1 and st["demotedFiles"] == 2
            assert st["demotedBytes"] == 2 * 60_000
            assert st["reclaimedBytes"] > 0

            # a second scan is a no-op beyond the idempotent finish pass
            out2 = await n1.tier_scan_once()
            assert out2["demoted"] == 0

            # promotion: heat a cold file past promote_reads and let
            # the background task re-materialize it
            cold_id = next(fid for fid in payloads if fid != hot_id)
            for _ in range(4):
                _, body = await n1.download(cold_id)
                assert bytes(body) == payloads[cold_id]
            for _ in range(100):
                m = n1.store.manifests.load(cold_id)
                if m.tier is None and not n1._tier_promoting:
                    break
                await asyncio.sleep(0.1)
            m = n1.store.manifests.load(cold_id)
            assert m.tier is None and m.ec is None, "promotion never ran"
            for n in nodes.values():
                _, body = await n.download(cold_id)
                assert bytes(body) == payloads[cold_id]
            assert n1.tier_stats()["promotedFiles"] == 1
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


@pytest.mark.xfail(strict=True, reason=(
    "ROADMAP.md C1.4: a promotion that started while the file was cold "
    "and a scan's re-demotion of it on another node run unordered; the "
    "promoter's parity reclaim lands on the fresh cold layout between "
    "the demoter's parity write and its tier flip, and nothing heals "
    "the lost parity with the repair loop off"))
def test_promotion_racing_a_redemotion_keeps_parity(tmp_path):
    """The interleaving the kill -9 test met by chance (3 runs of 8),
    made deterministic: node 2 promotes a cold file; node 1's scan
    demotes it again and is HELD between its parity write and its tier
    flip (the manifest save); node 3 — whose own reads of the file,
    cold at the time, started a promotion of the same cold manifest —
    runs that promotion to its end inside the window; node 1 goes on.
    Whatever order the program gives the two, the file must read back
    from every node and the census must end clean: every shard of the
    layout the manifests describe at its expected copies."""
    import threading

    async def run() -> None:
        cluster = _mk_cluster(3, rf=3)
        nodes = await _start_nodes(cluster, tmp_path, tier=TIER_NOW)
        n1, n2, n3 = nodes[1], nodes[2], nodes[3]
        try:
            data = os.urandom(60_000)
            m, _ = await n1.upload(data, "race.bin")
            fid = m.file_id
            assert (await n1.tier_scan_once())["demoted"] == 1
            cold_m = n3.store.manifests.load(fid)
            assert cold_m.tier == "cold" and cold_m.ec is not None
            await n2._promote_file(n2.store.manifests.load(fid))
            assert n1.store.manifests.load(fid).tier is None

            # node 1 re-demotes; its tier flip waits for node 3
            at_flip, go_on = threading.Event(), threading.Event()
            save = n1.store.manifests.save

            def held_save(manifest, *a, **kw):
                if manifest.file_id == fid and manifest.tier == "cold":
                    at_flip.set()
                    assert go_on.wait(30)
                return save(manifest, *a, **kw)

            n1.store.manifests.save = held_save
            try:
                scan = asyncio.ensure_future(n1.tier_scan_once())
                assert await asyncio.to_thread(at_flip.wait, 30)
                await n3._promote_file(cold_m)
            finally:
                go_on.set()
                n1.store.manifests.save = save
            assert (await scan)["demoted"] == 1

            for n in nodes.values():
                _, body = await n.download(fid)
                assert bytes(body) == data
            # what the kill -9 test's phase 4 does: scans until clean
            for _ in range(3):
                await n1.tier_scan_once()
            rep = await n1.census_report()
            assert (rep["underReplicatedTotal"], rep["overReplicatedTotal"],
                    rep["orphanedTotal"], rep["peersFailed"]) \
                == (0, 0, 0, 0), rep
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_scan_skips_while_migrating_and_small_rings(tmp_path):
    """Demotion waits out rebalances (ownership is moving under the
    dual-read window) and refuses rings too small for its stripes."""
    async def run() -> None:
        cluster = _mk_cluster(1, rf=1)
        nodes = await _start_nodes(
            cluster, tmp_path,
            tier=TierConfig(enabled=True, min_idle_s=0.0, ec_k=1))
        node = nodes[1]
        try:
            # 1 node < ec_k + 2: nothing demotes, ever
            await node.upload(b"x" * 50_000, "f.bin")
            out = await node.tier_scan_once()
            assert out["skipped"] == "ring too small for ec stripes"
            assert node.tier_stats()["demotedFiles"] == 0
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# satellites: scrub index healing + capacity-derived ring weight
# ------------------------------------------------------------------ #

def test_scrub_heals_index_vs_walk_divergence(tmp_path):
    """Scrub diffs the digest index against the CAS walk it just paid
    for and heals BOTH directions: a digest on disk the index lost
    (torn WAL tail) turns present again; a phantom the index vouches
    for with no bytes behind it is expunged."""
    async def run() -> None:
        cluster = _mk_cluster(1, rf=1)
        nodes = await _start_nodes(cluster, tmp_path,
                                   index=IndexConfig(enabled=True))
        node = nodes[1]
        try:
            m, _ = await node.upload(os.urandom(60_000), "s.bin")
            d0 = m.chunks[0].digest
            phantom = sha256_hex(b"never-stored-anywhere")
            node.index.note_delete(d0)       # index "lost" a real chunk
            node.index.note_put(phantom)     # index vouches for nothing
            out = await node.scrub_once()
            assert out["healedMissing"] >= 1
            assert out["healedPhantom"] == 1
            assert node.index.lsi.lookup(d0)
            assert not node.index.lsi.lookup(phantom)
            assert node.counters.snapshot()["index_healed_phantom"] == 1
            # steady state: a second scrub heals nothing
            out2 = await node.scrub_once()
            assert out2["healedMissing"] == 0
            assert out2["healedPhantom"] == 0
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


def test_ring_add_weight_derived_from_headroom(tmp_path):
    """``ring add`` without an explicit weight derives one from disk
    headroom: same filesystem -> ratio 1.0; an unreachable joiner falls
    back to the pre-r20 constant 1.0 instead of failing the add."""
    async def run() -> None:
        cluster = _mk_cluster(2, rf=2)
        nodes = await _start_nodes(cluster, tmp_path)
        try:
            # both nodes share tmp_path's filesystem: ratio == 1.0
            w = await nodes[1]._derive_add_weight(2, [1])
            assert w == pytest.approx(1.0)
            # unknown/unreachable joiner: graceful 1.0 fallback
            assert await nodes[1]._derive_add_weight(99, [1]) == 1.0
            # the clamp rails exist and bound the ratio
            assert StorageNodeServer._ADD_WEIGHT_MIN == 0.25
            assert StorageNodeServer._ADD_WEIGHT_MAX == 4.0
        finally:
            await _stop_all(nodes)

    asyncio.run(run())


# ------------------------------------------------------------------ #
# crash safety: kill -9 inside the demotion path (real processes)
# ------------------------------------------------------------------ #

N_PROC = 3


def _two_port_runs(n: int) -> tuple[int, int]:
    """cmd_serve derives peer ports as base+i; one free run of 2n ports
    split into (http_base, internal_base) so the ranges cannot overlap."""
    from scripts.chaos_harness import contiguous_free_ports

    base = contiguous_free_ports(2 * n)
    return base, base + n


def _tier_argv(node_id: int, http_base: int, internal_base: int,
               data_root: Path, crash_point: str = "") -> list[str]:
    argv = [sys.executable, "-m", "dfs_tpu.cli.main", "serve",
            "--node-id", str(node_id), "--nodes", str(N_PROC),
            "--base-port", str(http_base),
            "--base-internal-port", str(internal_base),
            "--replication-factor", "3",
            "--fragmenter", "cdc", "--data-root", str(data_root),
            "--repair-interval", "0", "--probe-interval", "0",
            # manual-scan tiering: everything past a 1% hot budget is
            # instantly demotable, k=1 stripes fit the 3-node ring
            "--tier", "--tier-ec-k", "1", "--tier-hot-fraction", "0.01",
            "--tier-min-idle", "0", "--tier-scan-interval", "0"]
    if crash_point:
        argv += ["--chaos", "--chaos-crash-point", crash_point]
    return argv


def _spawn(node_id: int, http_base: int, internal_base: int,
           tmp_path: Path, crash_point: str = "") -> subprocess.Popen:
    return subprocess.Popen(
        _tier_argv(node_id, http_base, internal_base,
                   tmp_path / "data", crash_point),
        cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(REPO)},
        stdout=(tmp_path / f"node{node_id}.log").open("ab"),
        stderr=subprocess.STDOUT)


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


def _scan_once_promotions_settled(
        ports: list[int], timeout: float = 60.0) -> tuple[int, bytes]:
    """POST /tier on node 1 once NO node has a promotion in flight.
    This test's read-backs heat cold files past ``promote_reads`` on
    nodes 2 and 3; a download registers its promotion before it answers
    (``_tier_maybe_promote``), so after the reads ``promoting`` == 0 on
    every node means every promotion they started has ended. Without
    the wait a promotion still in flight races the scan's re-demotion
    of the same file (ROADMAP.md C1.4, pinned by
    ``test_promotion_racing_a_redemotion_keeps_parity``) — that defect
    is not this test's subject, demotion's crash ordering is."""
    deadline = time.time() + timeout
    for port in ports:
        while True:
            status, body = _http(port, "GET", "/tier")
            assert status == 200, body
            if json.loads(body)["promoting"] == 0:
                break
            assert time.time() < deadline, "promotion never settled"
            time.sleep(0.05)
    return _http(ports[0], "POST", "/tier", b"", timeout=timeout)


def test_kill9_at_every_demote_crash_point_then_converge(tmp_path, rng):
    """For EACH demote.* crash point: a real 3-node cluster acks files,
    node 1 (armed) SIGKILLs itself mid-demotion when a scan is
    triggered, restarts clean, and the cluster converges — every acked
    file reads back byte-identical from EVERY node at every step, and
    the census ends clean (no under-replication, no orphans). This is
    the demotion ordering invariant: parity lands before the tier flip,
    the flip lands before any replica delete, so no interruption point
    leaves a file below its durability bar."""
    from dfs_tpu.chaos import CRASH_POINTS

    points = sorted(p for p in CRASH_POINTS if p.startswith("demote."))
    assert len(points) == 3, points

    http_base, internal_base = _two_port_runs(N_PROC)
    ports = [http_base + i for i in range(N_PROC)]
    peers = {i: _spawn(i, http_base, internal_base, tmp_path)
             for i in (2, 3)}
    acked: list[tuple[str, bytes]] = []
    seq = 0
    try:
        for i, proc in peers.items():
            _wait_status(ports[i - 1], proc)
        for point in points:
            # phase 1: boot node 1 ARMED, ack a fresh file
            proc = _spawn(1, http_base, internal_base, tmp_path,
                          crash_point=point)
            _wait_status(ports[0], proc)
            data = rng.integers(0, 256, size=50_000,
                                dtype="uint8").tobytes() + bytes([seq])
            seq += 1
            status, body = _http(ports[0], "POST",
                                 f"/upload?name=t{seq}.bin", data)
            assert status == 201, body
            acked.append((json.loads(body)["fileId"], data))

            # phase 2: trigger a scan — the demotion path hits the
            # armed point and the process dies by SIGKILL mid-flight
            try:
                _scan_once_promotions_settled(ports, timeout=30)
            except OSError:
                pass                  # connection died with the node
            rc = proc.wait(timeout=30)
            assert rc == -signal.SIGKILL, (
                f"{point}: expected SIGKILL death, got {rc}")

            # phase 3: restart clean — zero acked-read loss from EVERY
            # node, half-done demotions notwithstanding
            proc = _spawn(1, http_base, internal_base, tmp_path)
            _wait_status(ports[0], proc)
            for fid, want in acked:
                for port in ports:
                    status, got = _http(
                        port, "GET", f"/download?fileId={fid}")
                    assert status == 200 and got == want, (
                        f"{point}: {fid[:12]} unreadable after restart")

            # phase 4: converge — scans finish the interrupted demotion
            # (idempotent re-demote or surplus finish pass) until the
            # census is clean; files stay byte-identical throughout
            clean = None
            for _ in range(8):
                status, body = _scan_once_promotions_settled(ports)
                assert status == 200, body
                status, body = _http(ports[0], "GET", "/census",
                                     timeout=60)
                assert status == 200, body
                rep = json.loads(body)
                if (rep["underReplicatedTotal"] == 0
                        and rep["overReplicatedTotal"] == 0
                        and rep["orphanedTotal"] == 0
                        and rep["peersFailed"] == 0):
                    clean = rep
                    break
                time.sleep(0.5)
            assert clean is not None, (
                f"{point}: census never converged: {rep}")
            for fid, want in acked:
                for port in ports:
                    status, got = _http(
                        port, "GET", f"/download?fileId={fid}")
                    assert status == 200 and got == want
            # node 1 exits the loop stopped; next point re-arms it
            proc.terminate()
            proc.wait(timeout=10)
    finally:
        for p in peers.values():
            if p.poll() is None:
                p.terminate()
                p.wait(timeout=10)
        if 'proc' in dir() and proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def test_bench_tiering_tiny_smoke(tmp_path):
    """``bench_tiering.py --tiny`` end to end: every gate family must
    hold at tiny scale (the amplification and p99 gates are reported,
    not applied, at this scale — their byte-identity/census/identity
    checks still are), and the JSON schema matches what the committed
    TIER_r20.json embeds."""
    out_path = tmp_path / "tier_tiny.json"
    res = subprocess.run(
        [sys.executable, str(REPO / "bench_tiering.py"), "--tiny",
         "--out", str(out_path)],
        cwd=tmp_path, capture_output=True, text=True, timeout=420,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(REPO)})
    assert res.returncode == 0, (
        f"bench_tiering --tiny failed:\n{res.stdout[-2000:]}"
        f"\n{res.stderr[-4000:]}")
    out = json.loads(out_path.read_text())
    assert out["metric"] == "tiering_plane" and out["round"] == 20
    assert out["ok"] is True
    g = out["gates"]
    assert g["amplification"]["ok"]
    assert g["amplification"]["byteIdentity"]
    assert g["amplification"]["promotionRoundTrip"]
    assert g["amplification"]["censusClean"]
    assert g["amplification"]["demotedFiles"] > 0
    assert g["hot_p99"]["ok"]
    assert g["crash_demotion"]["ok"]
    assert g["crash_demotion"]["censusClean"]
    assert g["default_off"]["ok"]


def test_committed_tier_artifact_schema():
    """The committed TIER_r20.json is the FULL run: every gate applied
    and green — the claims docs/tiering.md cites."""
    art = json.loads((REPO / "TIER_r20.json").read_text())
    assert art["metric"] == "tiering_plane" and art["round"] == 20
    assert art["ok"] is True and art["tiny"] is False
    g = art["gates"]
    assert g["amplification"]["gateApplied"] is True
    assert g["amplification"]["amplificationAfter"] <= 1.5
    assert g["amplification"]["amplificationBefore"] >= 2.5
    assert g["hot_p99"]["gateApplied"] is True
    assert g["hot_p99"]["deltaPct"] <= 10.0
    assert g["crash_demotion"]["ok"] and g["default_off"]["ok"]


def test_tier_http_surfaces(tmp_path):
    """/tier 404s with a hint on a tier-less node; on an enabled node
    GET mirrors /metrics "tier" and POST runs a scan inline."""
    async def run() -> None:
        cluster = _mk_cluster(3, rf=3)
        nodes = await _start_nodes(cluster, tmp_path, tier=TIER_NOW)
        try:
            port = cluster.peers[0].port
            code, body = await asyncio.to_thread(
                _http, port, "GET", "/tier")
            assert code == 200
            st = json.loads(body)
            assert st["enabled"] is True and st["ecK"] == 1
            code, body = await asyncio.to_thread(
                _http, port, "POST", "/tier", b"")
            assert code == 200
            assert set(json.loads(body)) >= {"scanned", "cold",
                                             "demoted", "finished"}
        finally:
            await _stop_all(nodes)

    asyncio.run(run())

    async def run_off() -> None:
        cluster = _mk_cluster(1, rf=1)
        nodes = await _start_nodes(cluster, tmp_path / "off")
        try:
            port = cluster.peers[0].port
            code, body = await asyncio.to_thread(
                _http, port, "GET", "/tier")
            assert code == 404 and b"--tier" in body
        finally:
            await _stop_all(nodes)

    asyncio.run(run_off())
