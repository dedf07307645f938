"""Census & capacity plane tests (dfs_tpu/obs/census.py + history.py):
history-ring downsampling correctness under churn, the bucketed CAS
inventory, the bounded census protocol on a real 3-node cluster
(injected missing replica, injected orphan, one killed peer), the df
capacity accounting, and the new trend-aware doctor rules.

Cluster scaffolding mirrors tests/test_obs.py: real asyncio nodes on
localhost ports, CPU CDC engine, no sleeps on assertion paths."""

import asyncio
import json
import socket
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from dfs_tpu.config import (CDCParams, CensusConfig, ClusterConfig,
                            NodeConfig, PeerAddr)
from dfs_tpu.node.placement import replica_set
from dfs_tpu.node.runtime import StorageNodeServer
from dfs_tpu.obs.census import (build_report, diff_buckets,
                                expected_state, render_census,
                                render_df, summarize_expected)
from dfs_tpu.obs.history import MetricsHistory
from dfs_tpu.store.cas import ChunkStore
from dfs_tpu.utils.hashing import sha256_hex

CDC = CDCParams(min_size=64, avg_size=256, max_size=1024)


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


def make_cluster_cfg(n: int, rf: int = 2) -> ClusterConfig:
    ports = _free_ports(2 * n)
    peers = tuple(
        PeerAddr(node_id=i + 1, host="127.0.0.1",
                 port=ports[2 * i], internal_port=ports[2 * i + 1])
        for i in range(n))
    return ClusterConfig(peers=peers, replication_factor=rf)


async def start_nodes(cluster, root: Path, **cfg_kw):
    nodes = {}
    cfg_kw.setdefault("cdc", CDC)
    cfg_kw.setdefault("health_probe_s", 0)
    for p in cluster.peers:
        cfg = NodeConfig(node_id=p.node_id, cluster=cluster,
                         data_root=root, fragmenter="cdc", **cfg_kw)
        node = StorageNodeServer(cfg)
        await node.start()
        nodes[p.node_id] = node
    return nodes


async def stop_nodes(nodes) -> None:
    for n in nodes.values():
        await n.stop()


def _req(port: int, method: str, path: str, body=None, headers=None):
    r = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                               data=body, method=method,
                               headers=headers or {})
    with urllib.request.urlopen(r, timeout=60) as resp:
        return resp.read()


# --------------------------------------------------------------------- #
# history ring: downsampling correctness, bounds, trend
# --------------------------------------------------------------------- #

def test_history_coarse_sums_preserved_across_rollover():
    """The downsampling invariant: a closed coarse bucket's sum/count
    equal the sum over the fine buckets it spans — driven through
    enough churn that BOTH resolutions roll buckets over."""
    h = MetricsHistory(interval_s=10.0, slots=12, coarse_every=3,
                       coarse_slots=8)
    t0 = 1_000_000.0   # multiple of both steps: aligned windows
    for i in range(90):            # 900s = 30 coarse windows of 30s
        h.observe("x", float(i), now=t0 + i * 10.0)
    snap = h.snapshot("x")
    fine, coarse = snap["resolutions"]
    assert fine["stepS"] == 10.0 and coarse["stepS"] == 30.0
    # bounds: rings hold at most `slots` CLOSED buckets (+1 open)
    assert len(fine["points"]) <= 12 + 1
    assert len(coarse["points"]) <= 8 + 1
    fine_by_ts = {p[0]: p for p in fine["points"]}
    # every coarse bucket fully covered by the retained fine window
    # must equal the sum of its three fine buckets
    checked = 0
    for ts, last, mn, mx, total, count in coarse["points"]:
        members = [fine_by_ts[ts + k * 10.0] for k in range(3)
                   if ts + k * 10.0 in fine_by_ts]
        if len(members) != 3:
            continue   # partially outside the fine retention window
        assert total == sum(p[4] for p in members)
        assert count == sum(p[5] for p in members)
        assert mn == min(p[2] for p in members)
        assert mx == max(p[3] for p in members)
        assert last == members[-1][1]
        checked += 1
    assert checked >= 2, "churn did not produce comparable windows"


def test_history_last_trend_and_unknown_series():
    h = MetricsHistory(10.0, 360, 30, 288)
    t0 = 2_000_000.0
    for i in range(6):
        h.observe("cap", 100.0 * i, now=t0 + i * 10.0)
    assert h.last("cap") == 500.0
    # 500 units over 50 s
    assert h.trend("cap") == pytest.approx(10.0)
    assert h.snapshot("nope") is None
    assert h.last("nope") is None
    assert h.trend("nope") is None
    assert h.trend("cap", window_s=0.0) is None   # one point left
    assert "cap" in h.names()
    st = h.stats()
    assert st["enabled"] and st["series"] == 1 and st["samples"] == 6


def test_history_series_cardinality_cap():
    h = MetricsHistory(10.0, 4, 2, 4)
    for i in range(h._MAX_SERIES + 10):
        h.observe(f"s{i}", 1.0, now=1000.0)
    # the fold key rides beyond the cap — the Counters/LatencyRecorder
    # discipline (test_counters_cardinality_guard)
    assert len(h.names()) == h._MAX_SERIES + 1
    assert "_overflow" in h.names()
    assert h.snapshot("_overflow")["resolutions"][0]["points"][0][5] == 10


# --------------------------------------------------------------------- #
# CAS inventory + cached byte gauge
# --------------------------------------------------------------------- #

def test_inventory_buckets_match_store(tmp_path):
    store = ChunkStore(tmp_path / "chunks")
    payloads = [bytes([i]) * (100 + i) for i in range(40)]
    digests = []
    for b in payloads:
        d = sha256_hex(b)
        store.put(d, b)
        digests.append(d)
    inv = store.inventory()
    assert inv["chunks"] == len(set(digests))
    assert inv["bytes"] == store.total_bytes()
    assert sum(b[0] for b in inv["buckets"].values()) == inv["chunks"]
    assert sum(b[1] for b in inv["buckets"].values()) == inv["bytes"]
    # bucket hash = xor of member stamps, recomputable from digests
    for prefix, (count, nbytes, xh) in inv["buckets"].items():
        members = [d for d in set(digests) if d.startswith(prefix)]
        assert count == len(members)
        want = 0
        for d in members:
            want ^= ChunkStore.digest_stamp(d)
        assert xh == want
    # drill-down: listed digests for one prefix, sorted, cap honored
    p = digests[0][:2]
    inv2 = store.inventory([p], list_cap=2)
    listed = inv2["listed"][p]
    assert listed == sorted(listed)
    assert len(listed) <= 2
    if inv["buckets"][p][0] > 2:
        assert inv2["listTruncated"]


def test_bytes_total_cached_tracks_put_delete(tmp_path):
    store = ChunkStore(tmp_path / "chunks")
    b1, b2 = b"x" * 100, b"y" * 50
    d1, d2 = sha256_hex(b1), sha256_hex(b2)
    store.put(d1, b1)
    assert store.bytes_total() == 100          # priming scan
    store.put(d2, b2)
    assert store.bytes_total() == 150          # maintained, no rescan
    store.put(d2, b2)                          # dedup hit: no change
    assert store.bytes_total() == 150
    store.delete(d1)
    assert store.bytes_total() == 50
    store.delete(d1)                           # already gone: no drift
    assert store.bytes_total() == 50
    assert store.bytes_total() == store.total_bytes()


# --------------------------------------------------------------------- #
# serve cache temperature (tiering seed)
# --------------------------------------------------------------------- #

def test_cache_temperature_top_k():
    from dfs_tpu.serve.cache import ChunkCache

    c = ChunkCache(1 << 20)
    for i in range(5):
        c.put(f"{i:064x}", bytes(10))
    for _ in range(7):
        c.get(f"{3:064x}")
    for _ in range(2):
        c.get(f"{1:064x}")
    temp = c.temperature(k=2)
    assert [t["digest"][-1] for t in temp] == ["3", "1"]
    assert temp[0]["hits"] == 7 and temp[0]["lastAccess"] > 0
    assert temp[0]["bytes"] == 10
    # never-hit entries are not reported; k bounds the list
    assert all(t["hits"] > 0 for t in c.temperature(k=16))
    assert len(c.temperature(k=1)) == 1


# --------------------------------------------------------------------- #
# report builder units (no cluster)
# --------------------------------------------------------------------- #

def _digest_for_prefix(prefix: str, salt: int) -> str:
    return prefix + sha256_hex(bytes([salt]))[2:]


def test_build_report_under_orphan_over_and_unknown():
    ids = [1, 2]
    d_ok = _digest_for_prefix("aa", 1)
    d_under = _digest_for_prefix("ab", 2)
    d_orphan = _digest_for_prefix("ac", 3)
    expected = {d_ok: (1, 2), d_under: (1, 2)}
    lengths = {d_ok: 10, d_under: 20}

    def bucket(*ds):
        b = [0, 0, 0]
        for d, ln in ds:
            b[0] += 1
            b[1] += ln
            b[2] ^= ChunkStore.digest_stamp(d)
        return b

    # node 1 holds everything expected plus one orphan; node 2 is
    # missing d_under
    inv1 = {"buckets": {"aa": bucket((d_ok, 10)),
                        "ab": bucket((d_under, 20)),
                        "ac": bucket((d_orphan, 5))}}
    inv2 = {"buckets": {"aa": bucket((d_ok, 10))}}
    exp_by_node = summarize_expected(expected, lengths)
    assert diff_buckets(exp_by_node[1], inv1["buckets"]) == ["ac"]
    assert diff_buckets(exp_by_node[2], inv2["buckets"]) == ["ab"]
    drilled = {1: {"ac": [d_orphan]}, 2: {"ab": []}}
    rep = build_report(expected, lengths, {1: inv1, 2: inv2}, drilled,
                       max_listed=8)
    assert rep["underReplicatedTotal"] == 1
    assert rep["underReplicated"][0]["digest"] == d_under
    assert rep["underReplicated"][0]["observed"] == 1
    assert rep["orphanedTotal"] == 1
    assert rep["orphaned"][0] == {"digest": d_orphan, "nodes": [1]}
    assert rep["replicationHistogram"] == {"2": 1, "1": 1}
    assert rep["uncheckedBuckets"] == 0

    # dead peer: node 2's expected copies become UNKNOWN, not missing —
    # the partial census must not scream about every digest it held
    rep = build_report(expected, lengths, {1: inv1, 2: None},
                      {1: {"ac": [d_orphan]}}, max_listed=8)
    assert rep["underReplicatedTotal"] == 0
    assert rep["orphanedTotal"] == 1

    # undrilled mismatch (drill cap / lost drill reply): unknown too,
    # surfaced as uncheckedBuckets
    rep = build_report(expected, lengths, {1: inv1, 2: inv2}, {},
                       max_listed=8)
    assert rep["underReplicatedTotal"] == 0
    assert rep["uncheckedBuckets"] == 2

    # over-replication: node 2 also holds d_under's bucket twin copy
    # beyond its expectation? give node 1 an extra copy of d_ok's twin:
    d_extra = d_ok
    inv2b = {"buckets": {"aa": bucket((d_ok, 10)),
                         "ab": bucket((d_under, 20)),
                         "ac": bucket((d_extra, 10))}}
    # "ac" on node 2 is unexpected and holds a KNOWN digest -> over
    rep = build_report(expected, lengths, {1: inv1, 2: inv2b},
                       {1: {"ac": [d_orphan]}, 2: {"ac": [d_extra]}},
                       max_listed=8)
    assert rep["overReplicatedTotal"] == 1
    assert rep["overReplicated"][0]["digest"] == d_ok
    assert rep["overReplicated"][0]["extraOn"] == [2]


def test_render_census_and_df_plaintext():
    rep = {"digests": 3, "peersFailed": 1,
           "replicationHistogram": {"2": 2, "1": 1},
           "underReplicated": [{"digest": "ab" * 32, "expected": 2,
                                "observed": 1, "holders": [1, 2]}],
           "underReplicatedTotal": 1,
           "orphaned": [{"digest": "cd" * 32, "nodes": [2]}],
           "orphanedTotal": 1,
           "overReplicated": [{"digest": "ef" * 32, "expected": 2,
                               "observed": 3, "extraOn": [3]}],
           "overReplicatedTotal": 1,
           "uncheckedBuckets": 3,
           "capacity": {"nodes": {"1": {"casBytes": 2**30,
                                        "casChunks": 10,
                                        "diskFreeBytes": 2**31,
                                        "diskTotalBytes": 2**32},
                                  "2": None},
                        "clusterCasBytes": 2**30, "clusterChunks": 10,
                        "logicalBytes": 3 * 2**30,
                        "uniqueBytes": 2**30, "dedupRatio": 3.0}}
    text = render_census(rep)
    assert "under-replicated: 1" in text and "orphaned: 1" in text
    assert "2x:2" in text and "unchecked" in text
    # over-replicated findings name WHERE the extra copy sits
    assert "over-replicated: 1" in text and "nodes [3]" in text
    df = render_df(rep)
    assert "NO ANSWER" in df and "dedup=3.000x" in df
    clean = render_census({"digests": 0, "underReplicatedTotal": 0,
                           "orphanedTotal": 0, "overReplicatedTotal": 0})
    assert "expected replication" in clean


# --------------------------------------------------------------------- #
# doctor rules: capacity_trend + underreplication
# --------------------------------------------------------------------- #

def _snap(nid, **over):
    d = {"nodeId": nid, "now": 1000.0, "receivedAt": 1000.0,
         "configHash": "h", "chunks": 1, "files": 1, "peersAlive": {},
         "underReplicated": 0, "admission": {}, "cache":
         {"enabled": False}, "ingestStalls": {}, "cas": {},
         "sentinel": {"enabled": False}, "journal": {"enabled": False},
         "rpcClient": {}, "counters": {}, "incidents": [], "disk": {}}
    d.update(over)
    return d


def _rules(snaps, rule):
    from dfs_tpu.obs.doctor import diagnose

    return [f for f in diagnose(snaps, coordinator_now=1000.0)
            if f["rule"] == rule]


def test_doctor_capacity_trend_eta():
    # 100 MiB/s growth into 10 GiB free = ~102 s to full: critical
    fast = _snap(1, disk={"freeBytes": 10 * 2**30,
                          "totalBytes": 100 * 2**30},
                 capacity={"enabled": True,
                           "growthBytesPerS": 100 * 2**20})
    f = _rules({1: fast}, "capacity_trend")
    assert f and f[0]["severity"] == "critical" and f[0]["peers"] == [1]
    # same growth, 100 TiB free = years: quiet
    slow = _snap(1, disk={"freeBytes": 100 * 2**40,
                          "totalBytes": 200 * 2**40},
                 capacity={"enabled": True,
                           "growthBytesPerS": 100 * 2**20})
    assert _rules({1: slow}, "capacity_trend") == []
    # ~10h ETA: warning, not critical
    warn = _snap(1, disk={"freeBytes": 36 * 2**30,
                          "totalBytes": 100 * 2**30},
                 capacity={"enabled": True, "growthBytesPerS": 2**20})
    f = _rules({1: warn}, "capacity_trend")
    assert f and f[0]["severity"] == "warning"
    # shrinking store / sampler off / malformed growth: quiet
    for cap in ({"enabled": True, "growthBytesPerS": -5.0},
                {"enabled": False}, {"growthBytesPerS": "lots"}, None):
        s = _snap(1, disk={"freeBytes": 1, "totalBytes": 2},
                  capacity=cap)
        assert _rules({1: s}, "capacity_trend") == []


def test_doctor_underreplication_critical():
    from dfs_tpu.obs.doctor import CENSUS_STALE_S

    f = _rules({1: _snap(1, underReplicated=3)}, "underreplication")
    assert f and f[0]["severity"] == "critical" and "3 digest" \
        in f[0]["evidence"]
    # a RECENT coordinated census's findings fire it too (snap now is
    # 1000.0; this census is 100 s old)
    f = _rules({1: _snap(1, census={"at": 900.0, "underReplicated": 7})},
               "underreplication")
    assert f and "7" in f[0]["evidence"]
    # ... but a STALE census does not: the census is pull-only, so an
    # old snapshot must not latch a healed cluster critical forever
    stale = {"at": 1000.0 - CENSUS_STALE_S - 1, "underReplicated": 7}
    assert _rules({1: _snap(1, census=stale)}, "underreplication") == []
    assert _rules({1: _snap(1)}, "underreplication") == []
    # malformed cross-version fields cost the rule nothing
    assert _rules({1: _snap(1, underReplicated="many", census="?")},
                  "underreplication") == []
    assert _rules({1: _snap(1, census={"at": "when?",
                                       "underReplicated": 7})},
                  "underreplication") == []


# --------------------------------------------------------------------- #
# 3-node cluster: census end to end
# --------------------------------------------------------------------- #

def test_cluster_census_injections_and_partial(tmp_path, rng):
    """The CENSUS_r12.json acceptance scenario in miniature: a healthy
    census is clean; a replica deleted on one node is NAMED
    under-replicated; an unreferenced chunk is NAMED orphaned; df byte
    totals match actual CAS usage exactly; a killed peer degrades the
    census to a partial result over HTTP (200, peersFailed=1), and
    chunks expected on the dead peer are NOT screamed about."""
    data = rng.integers(0, 256, size=60_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(3)
        nodes = await start_nodes(cluster, tmp_path,
                                  census=CensusConfig(
                                      history_interval_s=0))
        try:
            m, _ = await nodes[1].upload(data, "c.bin")
            port = cluster.peers[0].port
            rep = json.loads((await asyncio.to_thread(
                _req, port, "GET", "/census")).decode())
            assert rep["peersFailed"] == 0
            assert rep["underReplicatedTotal"] == 0
            assert rep["orphanedTotal"] == 0
            assert rep["overReplicatedTotal"] == 0
            assert rep["replicationHistogram"] == {
                "2": rep["digests"]}
            # df: byte totals vs the stores' ground truth
            cap = rep["capacity"]
            actual = sum(nodes[i].store.chunks.total_bytes()
                         for i in nodes)
            assert cap["clusterCasBytes"] == actual
            assert cap["dedupRatio"] > 0
            assert set(cap["nodes"]) == {"1", "2", "3"}

            # injection 1: delete one replica of one digest. The victim
            # must not be placed on node 3 — the partial phase below
            # kills it, and a victim whose surviving copy sat there
            # would (correctly) degrade to unknown instead of staying
            # a named loss
            victim_d = next(
                c.digest for c in m.chunks
                if 3 not in replica_set(c.digest,
                                        cluster.sorted_ids(), 2))
            holder = replica_set(victim_d, cluster.sorted_ids(), 2)[0]
            assert nodes[holder].store.chunks.delete(victim_d)
            # injection 2: an orphan chunk on node 2
            orphan_b = b"census-orphan-payload"
            orphan_d = sha256_hex(orphan_b)
            nodes[2].store.chunks.put(orphan_d, orphan_b)

            rep = json.loads((await asyncio.to_thread(
                _req, port, "GET", "/census")).decode())
            assert rep["underReplicatedTotal"] == 1
            named = rep["underReplicated"][0]
            assert named["digest"] == victim_d
            assert named["observed"] == 1 and named["expected"] == 2
            assert rep["orphanedTotal"] == 1
            assert rep["orphaned"][0] == {"digest": orphan_d,
                                          "nodes": [2]}
            # the findings reached the flight recorder, trace-stamped
            # (the /census request span provides the context)
            nodes[1].obs.journal.flush()
            tail = await asyncio.to_thread(nodes[1].obs.journal.tail,
                                           0.0, 128)
            by_type = {e["type"]: e for e in tail["events"]}
            assert by_type["census_underreplicated"]["count"] == 1
            assert victim_d[:12] in \
                by_type["census_underreplicated"]["sample"]
            assert by_type["census_orphan"]["count"] == 1
            assert by_type["census_underreplicated"].get("trace")

            # the doctor sees the coordinator's census summary
            drep = json.loads((await asyncio.to_thread(
                _req, port, "GET", "/doctor?cluster=0")).decode())
            under = [f for f in drep["findings"]
                     if f["rule"] == "underreplication"]
            assert under and under[0]["severity"] == "critical"

            # partial: kill node 3, census still answers 200
            await nodes[3].stop()
            rep = json.loads((await asyncio.to_thread(
                _req, port, "GET", "/census")).decode())
            assert rep["peersFailed"] == 1
            assert rep["capacity"]["nodes"]["3"] is None
            # only the injected loss is flagged — node 3's copies are
            # unknown, not missing
            assert rep["underReplicatedTotal"] == 1
            # local-only census still answers without the fan-out
            rep = json.loads((await asyncio.to_thread(
                _req, port, "GET", "/census?cluster=0")).decode())
            assert set(rep["capacity"]["nodes"]) == {"1"}
        finally:
            await nodes[3].stop()   # idempotent if already stopped
            await stop_nodes({k: v for k, v in nodes.items() if k != 3})

    asyncio.run(run())


def test_cluster_history_endpoint_and_metrics_section(tmp_path, rng):
    data = rng.integers(0, 256, size=20_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(1, rf=1)
        nodes = await start_nodes(
            cluster, tmp_path,
            # interval long enough that the background loop never fires
            # during the test: the FIRST bytes_total() priming scan must
            # happen in the manual post-upload sample below, not racing
            # the upload's thread-pool puts (count()'s documented
            # priming-race skew would make the == assertion flaky)
            census=CensusConfig(history_interval_s=30.0,
                                history_slots=16,
                                history_coarse_every=4,
                                history_coarse_slots=8))
        try:
            node = nodes[1]
            port = cluster.peers[0].port
            await node.upload(data, "h.bin")
            # drive the sampler deterministically instead of sleeping
            await node._history_sample_once()
            await node._history_sample_once()
            out = json.loads((await asyncio.to_thread(
                _req, port, "GET", "/metrics/history")).decode())
            assert out["enabled"] is True
            assert "capacity.casBytes" in out["series"]
            one = json.loads((await asyncio.to_thread(
                _req, port, "GET",
                "/metrics/history?name=capacity.casBytes")).decode())
            assert one["enabled"] is True
            assert len(one["resolutions"]) == 2
            pts = one["resolutions"][0]["points"]
            assert pts and pts[-1][1] == \
                node.store.chunks.total_bytes()
            with pytest.raises(urllib.error.HTTPError) as ei:
                await asyncio.to_thread(
                    _req, port, "GET", "/metrics/history?name=nope")
            assert ei.value.code == 404
            ei.value.read()
            # JSON /metrics: additive census section mirrors the config
            js = json.loads((await asyncio.to_thread(
                _req, port, "GET", "/metrics")).decode())
            assert js["census"]["historyIntervalS"] == 30.0
            assert js["census"]["maxListed"] == 64
            assert js["census"]["history"]["enabled"] is True
            assert js["census"]["capacity"]["casBytes"] is not None
            # prom gauges ride the history samples
            prom = (await asyncio.to_thread(
                _req, port, "GET", "/metrics?format=prom")).decode()
            assert "dfs_cas_bytes " in prom
            assert "dfs_disk_free_bytes " in prom
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_history_disabled_endpoint(tmp_path):
    async def run():
        cluster = make_cluster_cfg(1, rf=1)
        nodes = await start_nodes(
            cluster, tmp_path,
            census=CensusConfig(history_interval_s=0))
        try:
            out = json.loads((await asyncio.to_thread(
                _req, cluster.peers[0].port, "GET",
                "/metrics/history")).decode())
            assert out == {"enabled": False, "series": []}
            js = json.loads((await asyncio.to_thread(
                _req, cluster.peers[0].port, "GET",
                "/metrics")).decode())
            assert js["census"]["history"] == {"enabled": False}
            assert js["census"]["capacity"] == {"enabled": False}
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_cache_temperature_reaches_metrics_and_census(tmp_path, rng):
    from dfs_tpu.config import ServeConfig

    data = rng.integers(0, 256, size=30_000, dtype=np.uint8).tobytes()

    async def run():
        cluster = make_cluster_cfg(1, rf=1)
        nodes = await start_nodes(
            cluster, tmp_path,
            serve=ServeConfig(cache_bytes=1 << 20),
            census=CensusConfig(history_interval_s=0))
        try:
            node = nodes[1]
            m, _ = await node.upload(data, "hot.bin")
            for _ in range(3):
                await node.download(m.file_id)
            js = json.loads((await asyncio.to_thread(
                _req, cluster.peers[0].port, "GET",
                "/metrics")).decode())
            temp = js["serve"]["cache"]["temperature"]
            assert temp and temp[0]["hits"] >= 1
            assert len(temp) <= 16
            assert all(set(t) == {"digest", "hits", "bytes",
                                  "lastAccess"} for t in temp)
            inv = await node.census_inventory()
            assert inv["cacheTemperature"] == temp or \
                inv["cacheTemperature"][0]["hits"] >= temp[0]["hits"]
        finally:
            await stop_nodes(nodes)

    asyncio.run(run())


def test_census_rpc_budget_scales_with_the_walk():
    """A peer's inventory is a readdir+stat pass over everything it
    holds: the per-attempt budget grows with the chunks it may have to
    walk (a flat 10 s read a healthy 2 GiB cluster as "2 peer(s)
    unreachable" on the first chip run, PERF.md PR 21)."""
    from dfs_tpu.comm.rpc import InternalClient

    client = InternalClient(2.0, 10.0, 3)
    seen = {}

    async def call(peer, header, body=b"", retries=None, timeout_s=None):
        seen["timeout_s"] = timeout_s
        return {"census": {}}, memoryview(b"")

    client.call = call
    asyncio.run(client.get_census(None, retries=1))
    assert seen["timeout_s"] == 10.0
    asyncio.run(client.get_census(None, retries=1, expect_chunks=65000))
    assert seen["timeout_s"] == 10.0 + 65.0


def test_serve_cli_exposes_census_flags():
    """DFS005 satellite: every CensusConfig field is CLI-reachable and
    the census/df subcommands parse."""
    from dfs_tpu.cli.main import build_parser

    ns = build_parser().parse_args(
        ["serve", "--node-id", "1", "--census-interval", "5",
         "--census-history-slots", "60", "--census-coarse-every", "12",
         "--census-coarse-slots", "48", "--census-max-listed", "16"])
    assert (ns.census_interval, ns.census_history_slots) == (5.0, 60)
    assert (ns.census_coarse_every, ns.census_coarse_slots,
            ns.census_max_listed) == (12, 48, 16)
    ns = build_parser().parse_args(["census", "--local", "--json"])
    assert ns.local and ns.json
    ns = build_parser().parse_args(["df"])
    assert ns.cmd == "df"


# --------------------------------------------------------------------- #
# tier-1 smoke: bench_census --tiny exercises the CENSUS_r12.json
# phases (census injections, partial fan-out, df accounting; overhead
# reported but gated only at full scale)
# --------------------------------------------------------------------- #

def test_bench_census_tiny(tmp_path):
    import subprocess
    import sys as _sys

    REPO = Path(__file__).resolve().parent.parent
    out_path = tmp_path / "CENSUS_tiny.json"
    r = subprocess.run(
        [_sys.executable, str(REPO / "bench_census.py"),
         "--tiny", "--out", str(out_path)],
        capture_output=True, text=True, timeout=600, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    out = json.loads(out_path.read_text())
    assert out["ok"] is True
    assert out["census"]["under_named_correctly"] is True
    assert out["census"]["orphan_named_correctly"] is True
    assert out["census"]["df_within_1pct"] is True
    assert out["partial"]["completed_with_one_dead"] is True
    # schema must match the committed artifact's (stale-schema guard)
    committed = json.loads((REPO / "CENSUS_r12.json").read_text())
    assert set(committed) == set(out)
    assert set(committed["census"]) == set(out["census"])
