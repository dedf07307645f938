"""PR 27: the ``versions`` traffic kind of the benchmark (generator, the
plain reference copy, the dedup oracle), the per-layer readers it
brought, and the counters and spans they read in the program.

Small sizes, CPU: what a corpus' bytes ARE and what the program COUNTS.
Nothing here is a speed. ``benchmarks/`` keeps its own tests (by hand,
``python -m pytest benchmarks/tests``); these are the part of them that
guards the program's side of the contract, so tier-1 runs them.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "benchmarks"
sys.path.insert(0, str(BENCH))

import check  # noqa: E402
import reference_versions  # noqa: E402
import window  # noqa: E402
from ops import Op  # noqa: E402

from dfs_tpu.config import IndexConfig  # noqa: E402
from dfs_tpu.index import IndexPlane  # noqa: E402
from dfs_tpu.index.lsi import DigestIndex  # noqa: E402
from dfs_tpu.store.cas import ChunkStore  # noqa: E402
from dfs_tpu.utils.hashing import sha256_hex  # noqa: E402
from tests.test_index import (_mk_cluster, _start_nodes,  # noqa: E402
                              _stop_all)

BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = {"segments": ("tarball-3n-rf2", "ingest-edited"),
         "source-tree": ("snapshots-3n-rf2-index", "ingest-versions")}
SMALL = {"segments": {"object_bytes": 1 << 16, "version_objects": 4,
                      "lead_objects": 4, "ratio_objects": 4,
                      "period_bytes": 1 << 14, "edit_min_bytes": 256,
                      "edit_max_bytes": 1024},
         "source-tree": {"object_bytes": 1 << 18, "version_objects": 4,
                         "lead_objects": 4, "ratio_objects": 4}}
NEW_METRICS = ("place.probe_s_per_gib", "store.has_s_per_gib",
               "index.lookup_s_per_gib", "index.stat_fallback_pct",
               "index.probe_skip_pct", "index.verify_s_per_gib",
               "index.compact_stall_s_per_gib", "client.think_s_per_gib",
               "index.run_entries_per_memtable")


def _files(corpus: str, small: bool = True) -> tuple[dict, dict]:
    config = json.loads(
        (BENCH / "configs" / f"{CELLS[corpus][0]}.json").read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{CELLS[corpus][1]}.json").read_text())
    return config, ({**traffic, **SMALL[corpus]} if small else traffic)


def _generator(corpus: str, seed: int = 5, small: bool = True):
    config, traffic = _files(corpus, small)
    return window.load_by_name("generators", "versions").Generator(
        traffic, config, seed), traffic, config


# -- the corpus: generator against the plain reference ----------------------

@pytest.mark.parametrize("version", [0, 1, 2])
@pytest.mark.parametrize("corpus", sorted(CELLS))
def test_generator_and_reference_agree_byte_for_byte(corpus, version):
    gen, traffic, _ = _generator(corpus)
    ref = reference_versions.Reference(traffic)
    pieces = traffic["version_objects"]
    for k in range(version * pieces, (version + 1) * pieces):
        made = gen.make(("ver", k))
        assert len(made) == traffic["object_bytes"]      # every object
        assert bytes(made) == ref.object(k), f"object {k}"


def test_a_version_of_the_tree_shifts_its_successor():
    """What the corpus is for: version 1 holds nearly all of version
    0's file bytes, at other offsets of the archive."""
    gen, traffic, _ = _generator("source-tree")
    t0, t1 = gen.corpus.table(0), gen.corpus.table(1)
    kept = set(t0.files) & set(t1.files)
    assert len(kept) > 0.9 * len(t0.files)
    moved = [p for p in kept
             if t0.starts[t0.paths.index(p)] != t1.starts[t1.paths.index(p)]]
    assert len(moved) > len(kept) // 2
    assert t1.end <= traffic["object_bytes"] * traffic["version_objects"] \
        - 1024


_CHILD = """
import hashlib, json, sys
sys.path.insert(0, {bench!r})
import window
gen = window.load_by_name("generators", "versions").Generator(
    json.loads({traffic!r}), json.loads({config!r}), 99)
print(json.dumps([hashlib.sha256(gen.make(("ver", k))).hexdigest()
                  for k in (0, 5, 9)]))
"""


@pytest.mark.parametrize("corpus", sorted(CELLS))
def test_make_is_stable_across_processes_and_seeds(corpus):
    gen, traffic, config = _generator(corpus, seed=5)
    here = [hashlib.sha256(gen.make(("ver", k))).hexdigest()
            for k in (0, 5, 9)]
    done = subprocess.run(
        [sys.executable, "-c", _CHILD.format(
            bench=str(BENCH), traffic=json.dumps(traffic),
            config=json.dumps(config))],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "PYTHONHASHSEED": "77"})
    assert done.returncode == 0, done.stderr[-2000:]
    assert json.loads(done.stdout.strip().splitlines()[-1]) == here


@pytest.mark.parametrize("corpus", sorted(CELLS))
def test_the_seed_permutes_and_never_changes_the_set(corpus):
    """At the cell's real parameters (making no object)."""
    a, traffic, _ = _generator(corpus, seed=2**31 + 11, small=False)
    b, _, _ = _generator(corpus, seed=7, small=False)
    lead, n = traffic["lead_objects"], traffic["ratio_objects"]
    assert traffic["object_bytes"] == 16777216 == a.size
    assert lead == traffic["version_objects"] == 16
    assert a.preload_order != b.preload_order and a.order != b.order
    for g in (a, b):
        assert sorted(g.preload_order) == list(range(lead))
        assert sorted(g.order) == list(range(lead, lead + n))


class _Api:
    def __init__(self, stop, limit):
        self.stop, self.limit, self.calls = stop, limit, []

    def put(self, client, node, key, body, want_id, block=0):
        self.calls.append((client, node, key[1]))
        if len(self.calls) >= self.limit:
            self.stop.set()
        return Op("put", client, node, want_id, key=key, status=201)


def test_a_version_goes_through_another_coordinator_than_its_predecessor():
    import threading

    gen, traffic, _ = _generator("segments")
    pieces = traffic["version_objects"]
    api = _Api(threading.Event(), 10**9)
    gen.preload(api)
    assert sorted(k for _, _, k in api.calls) == list(range(pieces))
    assert {node for _, node, _ in api.calls} == {0, 1, 2}
    for client in range(3):
        stop = threading.Event()
        api = _Api(stop, 12)
        gen.run_client(client, api, stop)
        for c, node, k in api.calls:
            assert node == (c + k // pieces) % 3
        sent = [k for _, _, k in api.calls]
        assert len(set(sent)) == len(sent) and min(sent) >= pieces


class _RefusingApi(_Api):
    """Answers ``status`` to the first ``refusals`` attempts of object
    ``k``, and 201 to everything else."""

    def __init__(self, stop, limit, k, refusals, status=500):
        super().__init__(stop, limit)
        self.k, self.left, self.status = k, refusals, status

    def put(self, client, node, key, body, want_id, block=0):
        op = super().put(client, node, key, body, want_id, block)
        if key[1] == self.k and self.left:
            self.left -= 1
            op.status = self.status
        return op


@pytest.mark.parametrize("refusals,status,attempts", [
    (0, 500, 1),        # nothing refused: the traffic is what it was
    (1, 500, 2),        # refused once: sent again, same node, same bytes
    (9, 500, 5),        # refused for good: five attempts, then the next
    (9, 0, 1),          # a transport failure or a timeout is not retried
])
def test_a_refused_upload_is_sent_again_through_the_same_node(
        refusals, status, attempts):
    import threading

    gen, traffic, _ = _generator("segments")
    api = _RefusingApi(threading.Event(), 10**9, 2, refusals, status)
    gen.preload(api)
    sent = [(c, n) for c, n, k in api.calls if k == 2]
    assert len(sent) == attempts and len(set(sent)) == 1
    assert sorted({k for _, _, k in api.calls}) == list(
        range(traffic["version_objects"]))
    assert len(api.calls) == traffic["version_objects"] + attempts - 1
    # in the loop too, and never past the stop
    stop = threading.Event()
    first = gen.order[0]
    api = _RefusingApi(stop, 6, first, refusals, status)
    gen.run_client(0, api, stop)
    assert [k for _, _, k in api.calls[:attempts]] == [first] * attempts
    assert len(api.calls) == 6


# -- stored_ratio against the byte-granular oracle ---------------------------

@pytest.mark.parametrize("corpus", sorted(CELLS))
def test_stored_ratio_of_the_slice_equals_the_oracle(corpus, tmp_path):
    """The cell's reader over stores filled as rf=2 placement fills
    them (each chunk of the CPU engine's table a file on two nodes),
    with a warm-up object and later uploads around the slice."""
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredCpuFragmenter

    gen, traffic, config = _generator(corpus)
    engine = AnchoredCpuFragmenter()
    lead, n = traffic["lead_objects"], traffic["ratio_objects"]
    ops, manifests = [], {}

    def upload(key, body, phase):
        fid = hashlib.sha256(body).hexdigest()
        rows = engine.chunk(body)
        manifests[fid] = [{"digest": c.digest} for c in rows]
        for c in rows:
            for node in (int(c.digest[:2], 16) % 3,
                         (int(c.digest[:2], 16) + 1) % 3):
                path = tmp_path / f"node-{node + 1}" / "chunks" \
                    / c.digest[:2] / c.digest
                path.parent.mkdir(parents=True, exist_ok=True)
                path.write_bytes(bytes(body[c.offset:c.offset + c.length]))
        ops.append(Op("put", 0, 0, fid, key=key, nbytes=len(body),
                      status=201, phase=phase))

    upload(("warm", 0), os.urandom(traffic["object_bytes"]), "warm")
    for k in range(lead):
        upload(("ver", k), bytes(gen.make(("ver", k))), "preload")
    for k in reversed(range(lead, lead + n + 2)):     # any order
        upload(("ver", k), bytes(gen.make(("ver", k))), "run")
    w = window.Window(
        seconds=1, t_open=0, t_close=1, setup_s=0, ops=[], session_ops=ops,
        stores=check.Stores(tmp_path, 3), manifests=manifests,
        nodes_before=[], nodes_after=[], prom_before=[], prom_after=[],
        owner_before={}, owner_after={}, config=config, traffic=traffic,
        device_kind="x")
    oracle = reference_versions.stored_ratio_oracle(
        lambda k: gen.make(("ver", k)), lead, n,
        config["deployment"]["replication_factor"])
    got = window.load_by_name("end_to_end", "stored_ratio").read(w)
    assert got == oracle and 0 < oracle < 2


# -- BENCHMARK.json: the new entries, looked up by name -----------------------

def test_new_cells_and_metrics_are_declared_and_have_their_files():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    for cell in ("tarball.ingest-edited", "snapshots.ingest-versions"):
        entry = cells[cell]
        assert entry["chips"] == 1 and len(entry["why"]) <= 200
        assert (REPO / configs[entry["config"]]["file"]).is_file()
        assert (BENCH / "traffic" / f"{entry['traffic']}.json").is_file()
        for m in BENCHMARK["end_to_end"]:
            assert cell in m.get("workloads", [cell])
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in NEW_METRICS:
        m = per_layer[name]
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
        assert m["moves"] == "ingest_mibps"
        assert "snapshots.ingest-versions" in m["workloads"]
        assert ("tarball.ingest-edited" in m["workloads"]) \
            == (not name.startswith("index."))
    # the 26 it had, the first of the list (entries are only ever
    # appended): both cells. A later PR's metric lists the cells in which
    # it finds something to read (PR 28's: the two index-off cells).
    had = BENCHMARK["per_layer"][:26]
    assert not {m["name"] for m in had} & set(NEW_METRICS)
    for m in had:
        assert {"tarball.ingest-fresh", "tarball.ingest-edited",
                "snapshots.ingest-versions"} <= set(m["workloads"])
    resident = per_layer["store.resident_hit_pct"]
    assert resident["workloads"] == ["tarball.ingest-fresh",
                                     "tarball.ingest-edited",
                                     "smallfiles.ingest-batch",  # PR 41
                                     "images.ingest-nightly"]    # PR 43
    assert (BENCH / "layer_metrics" / f"{resident['name']}.py").is_file()


def test_the_new_configuration_is_the_accepted_one_plus_the_index():
    old = json.loads((BENCH / "configs/tarball-3n-rf2.json").read_text())
    new = json.loads(
        (BENCH / "configs/snapshots-3n-rf2-index.json").read_text())
    traffic = json.loads((BENCH / "traffic/ingest-versions.json").read_text())
    extra = ["--index", "--index-memtable-entries",
             str(new["index_memtable_entries"])]
    assert new["deployment"]["node_args"] \
        == old["deployment"]["node_args"] + extra
    for name, control in old["controls"].items():
        assert new["controls"][name]["node_args"] \
            == control["node_args"] + extra
    for key in ("owner_args", "owner_platform", "engine", "nodes",
                "replication_factor", "durability", "cdc", "chips"):
        assert new["deployment"][key] == old["deployment"][key]
    assert new["guarantees"][:4] == old["guarantees"]
    assert new["snapshot_bytes"] \
        == traffic["object_bytes"] * traffic["version_objects"]
    assert len(new["source"]) <= 200
    assert set(new["reduced"]) == set(new["reduced_why"])


# -- the readers on a program or a node without what they read ----------------

def _window(nodes_before, nodes_after, ops=()):
    return window.Window(
        seconds=10, t_open=100.0, t_close=110.0, setup_s=0, ops=list(ops),
        session_ops=list(ops), stores=None, manifests={},
        nodes_before=nodes_before, nodes_after=nodes_after, prom_before=[],
        prom_after=[], owner_before={}, owner_after={}, config={},
        traffic={}, device_kind="x")


def _put(client, t0, t1, nbytes=1 << 30):
    return Op("put", client, 0, "f", key=("ver", 1), nbytes=nbytes, t0=t0,
              t1=t1, status=201, phase="run")


def _read(name, w):
    return window.load_by_name("layer_metrics", name).read(w)


def test_readers_return_none_without_the_plane_or_the_span():
    """A node started without ``--index`` serves ``index: {enabled:
    false, ...}`` and a parent program no ``upload.probe``: nothing to
    read, and no reader raises."""
    plain = {"index": {"enabled": False, "memtableEntries": 65536},
             "obs": {"spans": {"http./upload": {
                 "count": 3, "seconds": 9.0, "selfSeconds": 0.1}}}}
    w = _window([plain] * 3, [plain] * 3, [_put(0, 101.0, 104.0)])
    for name in NEW_METRICS:
        if name != "client.think_s_per_gib":
            assert _read(name, w) is None, name
    bare = _window([{}] * 3, [{}] * 3)          # not even the tables
    for name in NEW_METRICS:
        assert _read(name, bare) is None, name


def test_readers_take_deltas_of_the_new_counters_and_spans():
    def node(scale, extra=0):
        return {"index": {
            "enabled": True, "probesSkipped": 95 * scale,
            "placementSkipped": 90 * scale,
            "placementConsidered": 100 * scale,
            "filterTrusted": 80 * scale, "statFallbacks": 5 * scale,
            "lsi": {"lookups": 50 * scale, "lookupS": 1.5 * scale,
                    "compactStallS": 0.25 * scale, "bgCompactS": 0.0,
                    "runEntries": 10240 * scale + 1024 * extra,
                    "memtableCap": 1024}},
            "obs": {"spans": {
                "upload.probe": {"seconds": 2.0 * scale},
                "cas.has_many": {"seconds": 1.0 * scale},
                "upload.verify_trusted": {"seconds": 0.5 * scale}}}}

    w = _window([node(1)] * 3, [node(3), node(3, 2), node(3, 1)],
                [_put(0, 101.0, 104.0)])
    assert _read("index.probe_skip_pct", w) == pytest.approx(90.0)
    # the close's state, not a delta: the least of the nodes
    assert _read("index.run_entries_per_memtable", w) == pytest.approx(30.0)
    assert _read("index.stat_fallback_pct", w) == pytest.approx(10.0)
    assert _read("index.lookup_s_per_gib", w) == pytest.approx(9.0)
    assert _read("index.compact_stall_s_per_gib", w) == pytest.approx(1.5)
    assert _read("index.verify_s_per_gib", w) == pytest.approx(3.0)
    assert _read("place.probe_s_per_gib", w) == pytest.approx(12.0)
    assert _read("store.has_s_per_gib", w) == pytest.approx(6.0)


def test_client_think_time_is_the_gap_between_ack_and_next_connect():
    ops = [_put(0, 99.0, 101.0), _put(0, 101.5, 104.0),     # 0.5 s
           _put(0, 105.0, 112.0),                            # 1.0 s
           _put(1, 95.0, 99.5), _put(1, 100.5, 109.0),      # 0.5 inside
           _put(1, 109.5, 111.0)]                            # 0.5 s
    w = _window([], [], ops)
    w.ops = [o for o in ops if 100.0 <= o.t1 < 110.0]        # 3 GiB acked
    assert _read("client.think_s_per_gib", w) == pytest.approx(2.5 / 3)


# -- the program's counters ---------------------------------------------------

def _digests(n, tag=""):
    return [sha256_hex(f"{tag}{i}".encode()) for i in range(n)]


def test_every_lookup_is_a_hit_or_a_miss(tmp_path):
    idx = DigestIndex(tmp_path / "index", memtable_entries=256,
                      compact_runs=2)
    idx.open_or_rebuild(lambda: [])
    held, absent = _digests(700, "held"), _digests(40, "absent")
    for d in held:
        idx.note_put(d)
    assert idx.stats()["runCount"] >= 1        # answers come from runs too
    assert idx.stats()["lookups"] == 0
    assert all(idx.lookup(d) for d in held)
    assert not any(idx.lookup(d) for d in absent)
    assert not idx.lookup("not-a-digest")       # refused, not a lookup
    st = idx.stats()
    assert st["lookups"] == 740 and st["lookupHits"] == 700
    assert st["lookupS"] > 0
    idx.close()


def test_a_healed_fallback_counts_once_and_put_dedup_is_counted(tmp_path):
    store = ChunkStore(tmp_path / "chunks")
    plane = IndexPlane(IndexConfig(enabled=True), tmp_path)
    plane.open_or_rebuild(store.digests)
    payload = b"behind the index" * 64
    d = sha256_hex(payload)
    assert store.put(d, payload)               # written behind the index
    store.index = plane
    missing = sha256_hex(b"never stored")
    assert store.has(d) and store.has(d) and store.has(d)
    assert not store.has(missing)
    st = plane.stats()
    # the first has() fell through to the stat and healed the index;
    # the next two were index answers. The absent digest falls through
    # every time it is asked for
    assert st["statFallbacks"] == 2 and st["statFallbackHits"] == 1
    assert st["lsi"]["lookups"] == 4 and st["lsi"]["lookupHits"] == 2
    # put's own pre-check is ``isfile``: a hit the index also knew
    fresh = b"fresh" * 300
    assert store.put_batch([(d, payload),
                            (sha256_hex(fresh), fresh)]) == [False, True]
    st = plane.stats()
    assert st["putDedupHits"] == 1 and st["putDedupIndexKnown"] == 1
    assert st["statFallbacks"] == 2            # a put is no fallback
    plane.close()


@pytest.mark.parametrize("index_on", [False, True])
def test_probe_and_has_many_spans_with_the_plane_off_and_on(tmp_path,
                                                            index_on):
    """Both spans are the program's own, whatever the plane: a re-upload
    probes each peer (``upload.probe`` at the coordinator, under it the
    peer's ``cas.has_many``)."""
    ix = IndexConfig(enabled=True, memtable_entries=1024, filter_sync_s=0) \
        if index_on else None

    async def run() -> None:
        cluster = _mk_cluster(3, rf=2)
        nodes = await _start_nodes(cluster, tmp_path, index=ix)
        try:
            body = os.urandom(300_000)
            node = nodes[1]
            with node.obs.request_span("http./upload"):
                await node.upload(body, "a.bin")
            if index_on:
                for n in nodes.values():
                    await n._filter_sync_once()
            with node.obs.request_span("http./upload"):
                _, stats = await node.upload(body, "again.bin")
            assert stats["transferredBytes"] == 0
            totals = node.obs.span_totals()
            assert totals["upload.probe"]["count"] >= 2      # a leg a peer
            assert totals["upload.probe"]["seconds"] > 0
            peers = [nodes[i].obs.span_totals() for i in (2, 3)]
            assert all(t["cas.has_many"]["count"] >= 1 for t in peers)
            st = node.index_stats()
            if index_on:
                assert st["placementConsidered"] \
                    >= st["placementSkipped"] > 0
                assert "upload.verify_trusted" in totals
                # the peers answer the probe and the verify round from
                # the store's resident set, in front of the index (PR
                # 39): what they linked in this life costs no lookup
                lsi = [nodes[i].index_stats()["lsi"] for i in (2, 3)]
                assert all(s["lookups"] >= s["lookupHits"] >= 0 for s in lsi)
                assert all(nodes[i].durability_stats()["residentHits"] > 0
                           for i in (2, 3))
            else:
                assert st == {k: st[k] for k in st if k in (
                    "enabled", "memtableEntries", "compactRuns",
                    "filterBitsPerKey", "filterSyncS", "backgroundCompact",
                    "echoCacheEntries")} and st["enabled"] is False
                assert "upload.verify_trusted" not in totals
        finally:
            await _stop_all(nodes)

    asyncio.run(run())
