"""The repair cycle's pass (PR 35): off the event loop, a manifest read
once, the orphan sweep's live set fresh when it deletes — structural,
no wall clock (ROADMAP.md C12)."""

import asyncio
import os
import socket
import threading
import time

import numpy as np
import pytest

import dfs_tpu.node.repair as repair
from dfs_tpu.config import CDCParams, ClusterConfig, NodeConfig, PeerAddr
from dfs_tpu.meta.manifest import (ChunkRef, EcInfo, Manifest, StripeRef,
                                   ec_stripe_groups, stripe_shard_len)
from dfs_tpu.node.placement import ec_placement_map, ec_shard_items
from dfs_tpu.node.runtime import StorageNodeServer
from dfs_tpu.ring import RingMap
from dfs_tpu.store.cas import ChunkStore, ManifestStore, NodeStore
from dfs_tpu.utils.hashing import sha256_hex

CDC = CDCParams(min_size=64, avg_size=256, max_size=1024)


# ---------------------------------------------------------------------- #
# the pass against the parent's walk, on a store built by hand
# ---------------------------------------------------------------------- #

def _parents_walk(store, node_id, rf, cur, prev):
    """``_repair_once_locked``'s walk as it was before PR 35 (commit
    cd0a5b5), to the letter: every manifest parsed, every row walked."""
    migrating = prev is not None
    need, chunk_len, own_missing, own_missing_ec = {}, {}, {}, []
    ec_digests, prev_ec_holders, stray = set(), {}, {}
    local_digests = set(store.chunks.digests())
    for m in store.manifests.list():
        if m.ec is not None:
            pl = ec_placement_map(m, cur)
            pl_prev = ec_placement_map(m, prev) if migrating else {}
            miss = {}
            for d, ln in ec_shard_items(m):
                chunk_len[d] = ln
                ec_digests.add(d)
                if migrating:
                    prev_ec_holders.setdefault(
                        d, tuple(pl_prev.get(d, ())))
                for target in pl[d]:
                    if target != node_id:
                        need.setdefault(target, []).append((d, ln))
                    elif d not in local_digests:
                        miss[d] = ln
            if miss:
                own_missing_ec.append((m, sorted(miss.items())))
            continue
        for c in m.chunks:
            chunk_len[c.digest] = c.length
            targets = cur.owners(c.digest, rf)
            for target in targets:
                if target != node_id:
                    need.setdefault(target, []).append(
                        (c.digest, c.length))
                elif c.digest not in local_digests:
                    own_missing[c.digest] = c.length
            if node_id not in targets and c.digest in local_digests:
                stray[c.digest] = frozenset(targets)
    return (need, chunk_len, own_missing, own_missing_ec, ec_digests,
            prev_ec_holders, stray)


def _manifest(rng, n_chunks, ec_k=None, shared=()):
    """A manifest of ``n_chunks`` random chunks (after ``shared`` ones it
    has in common with another), erasure-coded when ``ec_k``."""
    payloads = list(shared) + [
        rng.integers(0, 256, size=int(rng.integers(40, 200)),
                     dtype=np.uint8).tobytes() for _ in range(n_chunks)]
    chunks, off = [], 0
    for i, b in enumerate(payloads):
        chunks.append(ChunkRef(i, off, len(b), sha256_hex(b)))
        off += len(b)
    ec = None
    if ec_k:
        ec = EcInfo(k=ec_k, stripes=tuple(
            StripeRef(p=sha256_hex(b"p%d" % s + payloads[0]),
                      q=sha256_hex(b"q%d" % s + payloads[0]),
                      shard_len=stripe_shard_len(grp))
            for s, grp in enumerate(
                ec_stripe_groups(tuple(chunks), ec_k))))
    m = Manifest(file_id=sha256_hex(b"".join(payloads)), name="f.bin",
                 size=off, fragmenter="cdc", chunks=tuple(chunks), ec=ec)
    return m, dict(zip((c.digest for c in chunks), payloads))


def _fixture(tmp_path, node_id, cur, rf):
    """Node ``node_id``'s store: two replicated manifests that share
    chunks, an erasure-coded one, a corrupt one; every canonical copy
    but two of each kind; three strays."""
    rng = np.random.default_rng(35)
    store = NodeStore(tmp_path, node_id)
    a, pa = _manifest(rng, 90)
    b, pb = _manifest(rng, 40, shared=list(pa.values())[:30])
    e, pe = _manifest(rng, 24, ec_k=2)
    for m in (a, b, e):
        assert store.manifests.save(m)
    (store.manifests.root / ("f" * 64 + ".json")).write_bytes(b"{nope")
    own = [d for d in {**pa, **pb}
           if node_id in cur.owners(d, rf)]
    not_own = [d for d in {**pa, **pb} if d not in own]
    assert len(own) > 10 and len(not_own) > 10
    held = own[2:] + not_own[:3]            # two missing, three stray
    pl = ec_placement_map(e, cur)
    own_ec = [d for d in pe if node_id in pl[d]]
    assert own_ec
    held += own_ec[1:]                      # one data shard missing,
    payload = {**pa, **pb, **pe}            # and every parity shard
    store.chunks.put_batch([(d, payload[d]) for d in held])
    missing_ec = own_ec[:1] + [d for d, _ in ec_shard_items(e)
                               if d not in pe and node_id in pl[d]]
    return store, (a, b, e), own[:2], not_own[:3], missing_ec


@pytest.mark.parametrize("node_id", [1, 2, 3])
@pytest.mark.parametrize("ring", ["static", "hashed", "migrating"])
def test_the_pass_answers_what_the_parents_walk_answered(
        tmp_path, node_id, ring):
    rf = 2
    prev = None
    if ring == "static":
        cur = RingMap.static([1, 2, 3])
    else:
        cur = RingMap.hashed({1: 1.0, 2: 1.0, 3: 2.0, 4: 1.0, 5: 1.0},
                             epoch=2, vnodes=32)
    if ring == "migrating":
        prev = RingMap.hashed({1: 1.0, 2: 1.0, 3: 1.0, 4: 1.0}, epoch=1,
                              vnodes=32)
    store, (a, b, e), missing, strays, missing_ec = _fixture(
        tmp_path, node_id, cur, rf)
    memo = repair.ManifestMemo(store)
    want = _parents_walk(store, node_id, rf, cur, prev)
    for cycle in range(2):              # read, then remembered
        w = repair.walk(store, memo, node_id, rf, cur, prev)
        need, chunk_len, own_missing, own_ec, ec_digests, prev_ec, stray \
            = want
        # a digest two manifests share is walked once, where the parent
        # listed it a row each: the same (digest, length) a peer
        assert {p: sorted(set(rows)) for p, rows in w.need.items()} \
            == {p: sorted(set(rows)) for p, rows in need.items()}
        assert all(len(rows) == len(set(rows)) for p, rows
                   in w.need.items() if not ec_digests & {
                       d for d, _ in rows})
        assert w.chunk_len == chunk_len
        assert w.own_missing == own_missing
        assert sorted(w.own_missing) == sorted(missing)
        assert w.stray == stray and sorted(stray) == sorted(strays)
        assert w.ec_digests == ec_digests == set(
            d for d, _ in ec_shard_items(e))
        assert w.prev_ec_holders == prev_ec
        assert bool(prev_ec) == (ring == "migrating")
        assert [(m.file_id, sorted((r.digest, r.length) for r in refs))
                for m, refs in w.own_missing_ec] \
            == [(m.file_id, rows) for m, rows in own_ec] \
            == [(e.file_id, sorted((d, w.chunk_len[d])
                                   for d in missing_ec))]
        assert w.own_missing_ec[0][0] == e          # the manifest, whole
        assert w.local_digests == set(store.chunks.digests())
        assert sorted(w.seen) == sorted(m.file_id for m in (a, b, e))
        assert memo.remembered == 3
        # three parsed in the first pass (the corrupt one read, not
        # kept: four reads), none in the second but the corrupt one
        assert memo.read == 4 + cycle


# ---------------------------------------------------------------------- #
# the memo
# ---------------------------------------------------------------------- #

def _parses(monkeypatch):
    """File ids whose manifest ``Manifest.from_json`` parsed, in order."""
    seen = []
    real = Manifest.from_json

    def from_json(text):
        m = real(text)
        seen.append(m.file_id)
        return m

    monkeypatch.setattr(Manifest, "from_json", staticmethod(from_json))
    return seen


def _all_digests(memo, unless=None):
    return {fid: sorted(d.hex() for d, _ in rows.items())
            + sorted(rows.parity()) for fid, rows in memo.rows(unless)}


@pytest.mark.parametrize("case", [
    "unchanged", "rewritten_in_place", "deleted", "saved_since",
    "past_the_bound", "restamped_same_bytes"])
def test_a_manifest_is_read_once_for_as_long_as_its_file_is_the_same(
        tmp_path, monkeypatch, case):
    rng = np.random.default_rng(5)
    store = NodeStore(tmp_path, 1)
    ms = [_manifest(rng, n)[0] for n in (30, 20, 10)]
    for m in ms:
        assert store.manifests.save(m)
    if case == "past_the_bound":
        monkeypatch.setattr(repair, "_REMEMBER_ROWS_MAX", 45)
    memo = repair.ManifestMemo(store)
    parsed = _parses(monkeypatch)
    first = _all_digests(memo)
    assert first == {m.file_id: sorted(m.all_digests()) for m in ms}
    assert sorted(parsed) == sorted(m.file_id for m in ms)
    assert memo.read == 3
    seen = {fid: rows.stamp for fid, rows in memo.rows()}
    parsed.clear()
    a, b, c = sorted(ms, key=lambda m: m.file_id)
    if case == "unchanged":
        assert _all_digests(memo) == first
        assert parsed == [] and memo.read == 3 and memo.remembered == 3
        assert memo.named_since(seen) == set()
    elif case == "rewritten_in_place":
        # tier demotion writes `ec` (and `tier`) into the manifest
        cold = Manifest(
            file_id=b.file_id, name=b.name, size=b.size,
            fragmenter=b.fragmenter, chunks=b.chunks, tier="cold",
            ec=EcInfo(k=4, stripes=tuple(
                StripeRef(p=sha256_hex(b"p%d" % s), q=sha256_hex(b"q%d" % s),
                          shard_len=stripe_shard_len(grp))
                for s, grp in enumerate(ec_stripe_groups(b.chunks, 4)))))
        assert store.manifests.save(cold)
        assert memo.named_since(seen) == set(cold.all_digests())
        assert parsed == [b.file_id]
        got = dict(memo.rows())
        assert parsed == [b.file_id] and memo.read == 4   # and kept
        assert got[b.file_id].ec == cold.ec
        assert got[b.file_id].manifest().chunks == cold.chunks
        assert ec_shard_items(got[b.file_id].manifest()) \
            == ec_shard_items(cold)
        assert memo.remembered == 3
    elif case == "deleted":
        assert store.manifests.delete(b.file_id)
        assert sorted(_all_digests(memo)) == [a.file_id, c.file_id]
        assert parsed == [] and memo.remembered == 2
        assert b.file_id not in memo._kept
        assert memo._kept_rows == len(a.chunks) + len(c.chunks)
    elif case == "saved_since":
        new = _manifest(rng, 7)[0]
        assert store.manifests.save(new)
        assert memo.named_since(seen) == set(new.all_digests())
        assert parsed == [new.file_id]
        assert memo.named_since(seen) == set(new.all_digests())
        assert parsed == [new.file_id]          # remembered since
    elif case == "past_the_bound":
        # 45 rows hold two of the three (in id order): the third is
        # read every time, as every manifest was before
        kept = sorted(memo._kept)
        assert len(kept) == 2 and memo._kept_rows <= 45
        (again,) = [m.file_id for m in ms if m.file_id not in kept]
        assert _all_digests(memo) == first
        assert parsed == [again] and memo.read == 5   # `seen` took one
    else:
        # the same bytes under a new mtime (an adoption's utime): the
        # file is not the one that was read, so it is read again
        p = store.manifests._path(b.file_id)
        os.utime(p, ns=(1, 1))
        assert _all_digests(memo) == first
        assert parsed == [b.file_id]


# ---------------------------------------------------------------------- #
# the cycle, on a cluster in one process
# ---------------------------------------------------------------------- #

def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _cluster_cfg(n, rf=2):
    ports = _free_ports(2 * n)
    return ClusterConfig(peers=tuple(
        PeerAddr(node_id=i + 1, host="127.0.0.1", port=ports[2 * i],
                 internal_port=ports[2 * i + 1]) for i in range(n)),
        replication_factor=rf)


async def _start(cluster, root, **kw):
    nodes = {}
    for p in cluster.peers:
        cfg = NodeConfig(node_id=p.node_id, cluster=cluster,
                         data_root=root, fragmenter="cdc", cdc=CDC,
                         health_probe_s=0, **kw)
        n = StorageNodeServer(cfg)
        await n.start()
        nodes[p.node_id] = n
    return nodes


def _threads_of(monkeypatch):
    """Which thread ran each of the cycle's disk-and-parse steps."""
    ran = {}

    def record(owner, name, key):
        real = getattr(owner, name)

        def wrapper(*a, **kw):
            ran.setdefault(key, set()).add(threading.get_ident())
            return real(*a, **kw)

        if isinstance(owner.__dict__.get(name), staticmethod):
            wrapper = staticmethod(wrapper)
        monkeypatch.setattr(owner, name, wrapper)

    record(ManifestStore, "list", "manifests.list")
    record(Manifest, "from_json", "from_json")
    record(ChunkStore, "digests", "chunks.digests")
    record(NodeStore, "gc", "store.gc")
    record(NodeStore, "sweep_orphans", "sweep_orphans")
    record(repair.ManifestMemo, "rows", "memo.rows")
    return ran


def test_a_cycle_keeps_its_walk_and_its_sweep_off_the_loop_and_reads_once(
        tmp_path, rng, monkeypatch):
    """Three nodes, three objects: during a cycle nothing that lists the
    chunk tree, reads or parses a manifest, or sweeps runs on the event
    loop's thread; a second cycle over unchanged manifests reads none;
    the cycle is one trace — `repair.cycle` over `repair.walk`, a
    `repair.probe` a peer, `repair.sweep` — and /metrics says so."""
    async def run():
        cluster = _cluster_cfg(3, rf=2)
        nodes = await _start(cluster, tmp_path)
        try:
            for k in range(3):
                data = rng.integers(0, 256, size=60_000,
                                    dtype=np.uint8).tobytes()
                await nodes[k + 1].upload(data, f"o{k}.bin")
            loop_thread = threading.get_ident()
            ran = _threads_of(monkeypatch)
            node = nodes[1]
            before = node.repair_stats()
            assert before == {"cycles": 0, "manifestsRead": 0,
                              "manifestsRemembered": 0, "onLoopS": 0.0}
            assert await node.repair_once() == 0
            assert {"from_json", "chunks.digests", "sweep_orphans",
                    "memo.rows"} <= set(ran)
            assert "manifests.list" not in ran and "store.gc" not in ran
            for step, threads in ran.items():
                assert loop_thread not in threads, step
            first = node.repair_stats()
            assert first["cycles"] == 1
            assert first["manifestsRead"] == 3 \
                == first["manifestsRemembered"]
            assert first["onLoopS"] > 0
            ran.clear()
            assert await node.repair_once() == 0
            assert "from_json" not in ran           # nothing parsed
            second = node.repair_stats()
            assert second["cycles"] == 2
            assert second["manifestsRead"] == 3
            assert second["onLoopS"] > first["onLoopS"]
            totals = node.obs.span_totals()
            assert totals["repair.cycle"]["count"] == 2
            assert totals["repair.walk"]["count"] == 2
            assert totals["repair.sweep"]["count"] == 2
            assert totals["repair.probe"]["count"] == 2 * 2   # a peer
            # one trace a cycle: the children hang under the root
            (root,) = [s for s in node.obs.spans_between(0, 2 ** 62)
                       if s["name"] == "repair.cycle"][-1:]
            kids = [s["name"] for s in node.obs.spans_for(root["t"])
                    if s["p"] == root["s"]
                    and s["name"].startswith("repair.")]
            assert sorted(kids) == ["repair.probe", "repair.probe",
                                    "repair.sweep", "repair.walk"]
            # the peers' disks were looked at, and counted
            looked = sum(n.durability_stats()["lookStats"]
                         + n.durability_stats()["lookListed"]
                         for n in (nodes[2], nodes[3]))
            assert looked == 2 * sum(
                len(rows) for rows in repair.walk(
                    node.store, node._repair_memo, 1, 2,
                    node.ring.current, None).need.values())
        finally:
            for n in nodes.values():
                await n.stop()

    asyncio.run(run())


@pytest.mark.parametrize("named", ["saved_after_the_walk", "by_no_manifest"])
def test_the_sweep_asks_for_manifests_saved_since_before_it_deletes(
        tmp_path, rng, monkeypatch, named):
    """An aged chunk no manifest names is an orphan to the pass — and
    still there after the sweep if a manifest naming it was saved after
    the pass began (an upload that committed meanwhile); swept if none
    was."""
    async def run():
        cluster = _cluster_cfg(1, rf=1)
        nodes = await _start(cluster, tmp_path)
        node = nodes[1]
        try:
            await node.upload(rng.integers(
                0, 256, size=30_000, dtype=np.uint8).tobytes(), "kept.bin")
            late, payloads = _manifest(np.random.default_rng(9), 5)
            ch = node.store.chunks
            assert all(ch.put_batch(list(payloads.items())))
            old = time.time() - 7200
            for d in payloads:
                os.utime(ch._path_str(d), (old, old))   # aged orphans
            real_walk = repair.walk

            def walk(*a, **kw):
                w = real_walk(*a, **kw)
                assert set(payloads) <= w.local_digests
                assert not set(payloads) & set(w.chunk_len)
                if named == "saved_after_the_walk":
                    assert node.store.manifests.save(late)
                return w

            import dfs_tpu.node.runtime as runtime
            monkeypatch.setattr(runtime, "repair_walk", walk)
            await node.repair_once()
            left = set(payloads) & set(ch.digests())
            if named == "saved_after_the_walk":
                assert left == set(payloads)
                assert all(ch.get(d) == b for d, b in payloads.items())
            else:
                assert left == set()
            # what any manifest names is untouched either way
            (kept,) = [m for m in node.store.manifests.list()
                       if m.name == "kept.bin"]
            assert set(kept.digests()) <= set(ch.digests())
        finally:
            await node.stop()

    asyncio.run(run())


# ---------------------------------------------------------------------- #
# the four readers (benchmarks/layer_metrics/, BENCHMARK.json)
# ---------------------------------------------------------------------- #

READERS = {
    "repair.cycle_s_per_gib": ("s/GiB", "lower", "program_span",
                               "repair cycle", 3),
    "repair.on_loop_s_per_gib": ("s/GiB", "lower", "program_counter",
                                 "repair cycle", 3),
    "store.look_stats_per_mib": ("1/MiB", "lower", "program_counter",
                                 "chunk store", 2),
    "store.listed_look_pct": ("%", "higher", "program_counter",
                              "chunk store", 2),
}


def _bench_window(nodes_before, nodes_after):
    import sys
    from pathlib import Path
    from types import SimpleNamespace
    bench = Path(__file__).resolve().parent.parent / "benchmarks"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    import window
    put = SimpleNamespace(kind="put", acked=True, nbytes=512 * window.MIB)
    return window, window.Window(
        seconds=50.0, t_open=0.0, t_close=50.0, setup_s=1.0, ops=[put],
        session_ops=[put], stores=None, manifests={},
        nodes_before=nodes_before, nodes_after=nodes_after,
        prom_before=[], prom_after=[], owner_before={}, owner_after={},
        config={}, traffic={}, device_kind="x")


def _metrics_page(scale, with_repair=True):
    """A node's /metrics as the parent serves it, plus — on this
    program — what PR 35 adds."""
    page = {"obs": {"spans": {"cas.has_many": {
                "count": 9 * scale, "seconds": 2.0 * scale,
                "selfSeconds": 2.0 * scale}}},
            "durability": {"mode": "fsync", "fsyncs": 10 * scale,
                           "dirBarriers": 4 * scale,
                           "residentHits": 7 * scale,
                           "residentMisses": scale,
                           "residentEntries": 5, "residentDrops": 0}}
    if with_repair:
        page["obs"]["spans"]["repair.cycle"] = {
            "count": scale, "seconds": 3.0 * scale, "selfSeconds": 0.5}
        page["repair"] = {"cycles": scale, "manifestsRead": 40,
                          "manifestsRemembered": 40,
                          "onLoopS": 0.25 * scale}
        page["durability"].update(lookStats=100 * scale,
                                  lookListed=900 * scale,
                                  lookListings=9 * scale)
    return page


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_this_program_and_nothing_on_the_parents(name):
    import json
    from pathlib import Path
    repo = Path(__file__).resolve().parent.parent
    window, parents = _bench_window([_metrics_page(1, False)] * 3,
                                    [_metrics_page(3, False)] * 3)
    read = window.load_by_name("layer_metrics", name).read
    assert read(parents) is None
    _, empty = _bench_window([{}] * 3, [{}] * 3)
    assert read(empty) is None
    # over the window (scale 1 -> 3 on each of three nodes; half a GiB)
    _, ours = _bench_window([_metrics_page(1)] * 3, [_metrics_page(3)] * 3)
    assert read(ours) == pytest.approx({
        "repair.cycle_s_per_gib": 3 * 6.0 / 0.5,
        "repair.on_loop_s_per_gib": 3 * 0.5 / 0.5,
        "store.look_stats_per_mib": 3 * 200 / 512,
        "store.listed_look_pct": 90.0}[name])
    bench = json.loads((repo / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    unit, better, source, layer, cells = READERS[name]
    all3 = ["tarball.ingest-fresh", "tarball.ingest-edited",
            "snapshots.ingest-versions"]
    # PR 36's five nodes run the cycle and look at their disks (index
    # off) as the tarball cells' three do: its cell was appended, PR 41's
    # (three nodes, index off, ~10^4 manifests a cycle) after it, and
    # PR 43's (a few manifests of ~131 000 chunks each) after that
    assert m == {"name": name, "unit": unit, "better": better,
                 "source": source, "layer": layer,
                 "moves": "ingest_mibps",
                 "workloads": all3[:cells] + ["archive.ingest-ec",
                                              "smallfiles.ingest-batch",
                                              "images.ingest-nightly"]}
    names = [n["name"] for n in bench["per_layer"]]
    assert sorted(names.index(n) for n in READERS) == list(range(
        names.index("repair.cycle_s_per_gib"),
        names.index("repair.cycle_s_per_gib") + 4))
