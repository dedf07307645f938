"""The chunk store's clocks (PR 38): the put job's phase clock
(``ChunkStore.put_stats``, ``/metrics`` ``durability.put``), queue and
busy by lane (``AsyncChunkStore.stats()["lanes"]``) and the owner's
compile clock (``sidecar/service.py`` ``_CompileClock``, ``Health``
``compile``). CPU, no cluster."""

import asyncio
import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from dfs_tpu.store.aio import AsyncChunkStore
from dfs_tpu.store.cas import _PUT_PHASES, ChunkStore, _stripe
from dfs_tpu.utils.hashing import sha256_hex

REPO = Path(__file__).resolve().parent.parent
N = 48


def _items(tag: str, n: int = N) -> list[tuple[str, bytes]]:
    out = []
    for i in range(n):
        data = f"{tag}-{i}-".encode() * 300
        out.append((sha256_hex(data), data))
    return out


def _grown(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def _fresh_batch(store: ChunkStore) -> None:
    before = store.put_stats()
    assert store.put_batch(_items("fresh")) == [True] * N
    d = _grown(before, store.put_stats())
    assert (d["jobs"], d["items"], d["newFiles"]) == (1, N, N)
    assert d["linkContended"] == 0                 # nobody else links
    assert all(d[k] >= 0.0 for k in (*_PUT_PHASES, "jobS"))
    assert d["createS"] > 0.0 and d["linkS"] > 0.0 and d["unlinkS"] > 0.0
    assert d["payloadFsyncS"] > 0.0 and d["dirBarrierS"] > 0.0
    assert d["flushS"] == 0.0                      # no index plane
    # every instant of the call is in exactly one phase
    assert sum(d[k] for k in _PUT_PHASES) == pytest.approx(d["jobS"],
                                                           rel=0.05)


def _fsync_off(store: ChunkStore) -> None:
    plain = ChunkStore(store.root.parent / "plain", fsync=False)
    assert plain.put_batch(_items("plain")) == [True] * N
    got = plain.put_stats()
    assert got["newFiles"] == N and got["writeS"] > 0.0
    assert got["payloadFsyncS"] == 0.0 and got["dirBarrierS"] == 0.0
    assert got["settleS"] == 0.0
    assert sum(got[k] for k in _PUT_PHASES) == pytest.approx(got["jobS"],
                                                             rel=0.05)


def _dedup_batch(store: ChunkStore) -> None:
    items = _items("twice")
    store.put_batch(items)
    before = store.put_stats()
    assert store.put_batch(items) == [False] * N
    d = _grown(before, store.put_stats())
    assert (d["jobs"], d["items"]) == (1, N)
    assert d["precheckS"] > 0.0
    assert d["newFiles"] == 0
    assert d["createS"] == d["linkS"] == d["linkWaitS"] == 0.0
    assert d["precheckS"] + d["settleS"] == pytest.approx(d["jobS"],
                                                          rel=0.05)


def _put_beside_a_held_lock(store: ChunkStore, tag: str,
                            stripe_off: int) -> dict:
    """What one new file's put job counted while another thread held,
    for 250 ms, the lock of the shard directory ``stripe_off`` after the
    file's own."""
    (digest, data), = _items(tag, 1)
    mu = store._dir_mu[(_stripe(digest) + stripe_off) % 256]
    held = threading.Event()

    def hold() -> None:
        with mu:
            held.set()
            time.sleep(0.25)

    holder = threading.Thread(target=hold)
    holder.start()
    assert held.wait(5.0)
    before = store.put_stats()
    assert store.put_batch([(digest, data)]) == [True]
    d = _grown(before, store.put_stats())
    holder.join(5.0)
    assert not holder.is_alive()
    assert sum(d[k] for k in _PUT_PHASES) == pytest.approx(d["jobS"],
                                                           rel=0.05)
    return d


def _mutex_held(store: ChunkStore) -> None:
    # a link into the held lock's OWN directory waits for it
    d = _put_beside_a_held_lock(store, "waits", 0)
    # the create, write and payload fsync of one small file eat some of
    # the 250 ms before the link asks for the mutex (on a loaded machine
    # tens of ms)
    assert d["linkWaitS"] > 0.05
    assert d["linkS"] < 0.05
    assert (d["newFiles"], d["linkContended"]) == (1, 1)


def _another_directorys_mutex_held(store: ChunkStore) -> None:
    # ... and a link into any other directory does not
    for off in (1, 128, 255):
        d = _put_beside_a_held_lock(store, f"passes{off}", off)
        assert d["linkWaitS"] < 0.05
        assert (d["newFiles"], d["linkContended"]) == (1, 0)


def _every_directory_has_a_lock_of_its_own(store: ChunkStore) -> None:
    assert len(store._dir_mu) == len(set(map(id, store._dir_mu))) == 256
    assert not hasattr(store, "_index_mu")     # no store-wide lock left
    # four workers linking side by side into directories of their own
    # never meet; the table adds their calls up
    batches = [[it for it in _items(f"side{k}", 64)
                if _stripe(it[0]) % 4 == k] for k in range(4)]
    before = store.put_stats()
    workers = [threading.Thread(target=store.put_batch, args=(b,))
               for b in batches]
    for w in workers:
        w.start()
    for w in workers:
        w.join(30.0)
    d = _grown(before, store.put_stats())
    assert d["jobs"] == 4 and d["linkContended"] == 0
    assert d["newFiles"] == d["items"] == sum(map(len, batches)) > 0
    assert sum(d[k] for k in _PUT_PHASES) == pytest.approx(d["jobS"],
                                                           rel=0.05)


def _raw_write_outside_a_put(store: ChunkStore) -> None:
    # re-materialisation's way in: a job of its own, same phases
    (digest, data), = _items("remat", 1)
    before = store.put_stats()
    store._write_raw([(digest, store._path_str(digest), data)])
    d = _grown(before, store.put_stats())
    assert (d["jobs"], d["items"], d["newFiles"]) == (1, 1, 1)
    assert d["precheckS"] == 0.0 and d["createS"] > 0.0
    assert sum(d[k] for k in _PUT_PHASES) == pytest.approx(d["jobS"],
                                                           rel=0.05)


def _failed_put_counts_nothing(store: ChunkStore) -> None:
    before = store.put_stats()
    with pytest.raises(ValueError, match="does not match"):
        store.put_batch([("0" * 64, b"not those bytes")], verify=True)
    assert store.put_stats() == before


@pytest.mark.parametrize("case", [
    _fresh_batch, _fsync_off, _dedup_batch, _mutex_held,
    _another_directorys_mutex_held, _every_directory_has_a_lock_of_its_own,
    _raw_write_outside_a_put, _failed_put_counts_nothing],
    ids=lambda f: f.__name__.strip("_"))
def test_put_phase_clock(tmp_path, case):
    case(ChunkStore(tmp_path / "chunks", fsync=True))


def test_settle_of_an_owed_barrier_is_its_own_phase(tmp_path):
    """A dedup hit on a name whose directory barrier is still owed pays
    it in the pre-check: ``settleS``, not ``precheckS``'s."""
    store = ChunkStore(tmp_path / "chunks", fsync=True)
    (digest, data), = _items("owed", 1)
    store.put(digest, data)
    with store._count_lock:
        store._unbarriered.add(digest)     # as between phases (c) and (d)
    before = store.put_stats()
    assert store.put(digest, data) is False
    d = _grown(before, store.put_stats())
    assert d["settleS"] > 0.0 and d["newFiles"] == 0
    assert store.put_stats()["settleS"] <= store.put_stats()["jobS"]


# -- queue and busy by lane ---------------------------------------------

def _lane_stats(tmp_path, op: str) -> tuple[dict, dict]:
    store = ChunkStore(tmp_path / "chunks", fsync=False)
    items = _items("lanes", 8)
    store.put_batch(items)
    cas = AsyncChunkStore(store, workers=4)
    digests = [d for d, _ in items]

    async def run():
        if op == "put_many":
            return await cas.put_many(_items("more", 8))
        if op == "get_many":
            return await cas.get_many(digests)
        if op == "has_many":
            return await cas.has_many(digests)
        return await cas.get(digests[0])

    before = cas.stats()
    try:
        assert len(asyncio.run(run())) > 0
    finally:
        cas.close()
    return before, cas.stats()


@pytest.mark.parametrize("op,lane", [
    ("put_many", "w"), ("get_many", "r"), ("has_many", "g"), ("get", "g")])
def test_a_job_counts_in_its_own_lane(tmp_path, op, lane):
    before, after = _lane_stats(tmp_path, op)
    assert before["ops"] == 0 and set(before["lanes"]) == {"w", "r", "g"}
    for name, row in after["lanes"].items():
        assert set(row) == {"ops", "queueS", "busyS"}
        if name == lane:
            assert row["ops"] == 1 and row["busyS"] > 0.0
            assert row["queueS"] >= 0.0
        else:
            assert row == {"ops": 0, "queueS": 0.0, "busyS": 0.0}
    # the totals are the lanes' sums, to the digit they are served at
    for key in ("ops", "queueS", "busyS"):
        assert after[key] == round(
            sum(row[key] for row in after["lanes"].values()), 6)
    assert after["pending"] == 0 and after["workers"] == 4


def test_lane_totals_add_up_under_mixed_load(tmp_path):
    store = ChunkStore(tmp_path / "chunks", fsync=True)
    cas = AsyncChunkStore(store, workers=4)
    batches = [_items(f"mix{i}", 80) for i in range(3)]

    async def run():
        await asyncio.gather(*(cas.put_many(b) for b in batches))
        digests = [d for b in batches for d, _ in b]
        await asyncio.gather(cas.get_many(digests[:40]),
                             cas.has_many(digests),
                             cas.has_many(digests, resident_ok=True),
                             cas.get(digests[0]))

    try:
        asyncio.run(run())
    finally:
        cas.close()
    got = cas.stats()
    lanes = got["lanes"]
    # 80 items a batch are cut by directory into up to 4 jobs
    assert 3 <= lanes["w"]["ops"] <= 12
    assert lanes["r"]["ops"] == 1 and lanes["g"]["ops"] == 3
    for key in ("ops", "queueS", "busyS"):
        assert got[key] == round(sum(row[key] for row in lanes.values()), 6)
    # the put jobs' whole is inside the write lane's busy seconds
    put = store.put_stats()
    assert put["jobs"] == lanes["w"]["ops"] and put["newFiles"] == 240
    assert put["jobS"] <= lanes["w"]["busyS"]


def test_a_job_cancelled_in_the_queue_leaves_the_gauge(tmp_path):
    """A caller cancelled while its job still waits for a worker (an
    aborted upload's batch): the pool drops the job unrun, and
    ``pending`` — the gauge the sentinel samples, and the one way to know
    that an aborted batch has stopped writing — must not keep it."""
    store = ChunkStore(tmp_path / "chunks", fsync=False)
    gate = threading.Event()
    begun: list[str] = []

    def fault(op: str, digest: str) -> None:
        begun.append(digest)            # a job's first item: it has begun
        gate.wait(30.0)

    store.fault = fault
    cas = AsyncChunkStore(store, workers=2)

    async def run():
        tasks = [asyncio.create_task(cas.put_many(_items(f"q{k}", 10)))
                 for k in range(6)]
        deadline = time.monotonic() + 30.0
        while len(begun) < 2 and time.monotonic() < deadline:
            await asyncio.sleep(0.01)   # two run, four wait their turn
        assert len(begun) == 2 and cas.pending == 6
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        gate.set()
        deadline = time.monotonic() + 30.0
        while cas.pending and time.monotonic() < deadline:
            await asyncio.sleep(0.01)

    try:
        asyncio.run(run())
    finally:
        cas.close()
    got = cas.stats()
    assert got["pending"] == 0
    assert got["lanes"]["w"]["ops"] == 2       # the two that had begun
    assert store.put_stats()["newFiles"] == 20


def test_durability_stats_carries_the_put_clock(tmp_path):
    from dfs_tpu.config import CDCParams, ClusterConfig, NodeConfig
    from dfs_tpu.node.runtime import StorageNodeServer

    cfg = NodeConfig(
        node_id=1, cluster=ClusterConfig.localhost(1, replication_factor=1),
        data_root=tmp_path, fragmenter="cdc", health_probe_s=0,
        cdc=CDCParams(min_size=64, avg_size=256, max_size=1024))
    node = StorageNodeServer(cfg)
    items = _items("node", 5)

    async def run():
        return await node.cas.put_many(items)

    assert asyncio.run(run()) == [True] * 5
    put = node.durability_stats()["put"]
    assert set(put) == {"jobs", "items", "newFiles", "linkContended",
                        "jobS", *_PUT_PHASES}
    assert (put["jobs"], put["items"], put["newFiles"]) == (1, 5, 5)
    assert put["linkContended"] == 0 and isinstance(put["linkContended"],
                                                    int)
    assert sum(put[k] for k in _PUT_PHASES) == pytest.approx(put["jobS"],
                                                             rel=0.05)
    assert put["payloadFsyncS"] > 0.0       # the node's default: fsync
    # the neighbours it is served beside keep their keys
    assert {"mode", "fsyncs", "dirBarriers", "residentHits",
            "lookStats"} <= set(node.durability_stats())
    assert node.ingest_stats()["cas"]["lanes"]["w"]["ops"] == 1


# -- the reader of the new counter (benchmarks/layer_metrics/) ----------

def _put_page(jobs: int, files: int, contended: int | None) -> dict:
    """A node's ``/metrics`` as far as the put clock's readers look: the
    parent's table, plus — on this program — ``linkContended``."""
    put = {"jobs": jobs, "items": 2 * files, "newFiles": files,
           "jobS": 0.01 * files, **dict.fromkeys(_PUT_PHASES, 0.001 * files)}
    if contended is not None:
        put["linkContended"] = contended
    return {"durability": {"mode": "fsync", "fsyncs": files, "put": put}}


def _reads_nothing_on_the_parent(read, window) -> None:
    _, parents = window([_put_page(1, 100, None)] * 3,
                        [_put_page(9, 900, None)] * 3)
    assert read(parents) is None
    _, older = window([{"durability": {"fsyncs": 1}}] * 3,
                      [{"durability": {"fsyncs": 9}}] * 3)
    assert read(older) is None              # no put clock at all (< PR 38)
    _, empty = window([{}] * 3, [{}] * 3)
    assert read(empty) is None


def _reads_contended_over_new_files(read, window) -> None:
    # over the window, three nodes: 3 x (33 - 1) of 3 x (900 - 100) files
    _, ours = window([_put_page(1, 100, 1)] * 3, [_put_page(9, 900, 33)] * 3)
    assert read(ours) == pytest.approx(100.0 * 96 / 2400)
    _, calm = window([_put_page(1, 100, 0)] * 3, [_put_page(9, 900, 0)] * 3)
    assert read(calm) == 0.0
    # a node that came up inside the window counts from zero
    _, late = window([_put_page(1, 100, 1), {}],
                     [_put_page(9, 900, 33), _put_page(4, 200, 8)])
    assert read(late) == pytest.approx(100.0 * 40 / 1000)


def _reads_nothing_where_no_file_was_made(read, window) -> None:
    _, idle = window([_put_page(1, 100, 2)] * 3, [_put_page(5, 100, 2)] * 3)
    assert read(idle) is None


def _is_declared_for_the_five_cells(read, window) -> None:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "store.link_contended_pct"]
    assert entry == {
        "name": "store.link_contended_pct", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "chunk store",
        "moves": "ingest_mibps",
        "workloads": ["tarball.ingest-fresh", "tarball.ingest-edited",
                      "snapshots.ingest-versions", "archive.ingest-ec",
                      "smallfiles.ingest-batch",
                      "images.ingest-nightly"]}         # PR 43 appended
    assert set(entry["workloads"]) <= {w["name"] for w in bench["workloads"]}
    assert (REPO / "benchmarks" / "layer_metrics"
            / "store.link_contended_pct.py").is_file()
    # the wait the counter explains is read by the reader it always was,
    # in the same cells
    (waits,) = [m for m in bench["per_layer"]
                if m["name"] == "store.put_link_wait_s_per_gib"]
    assert waits["workloads"] == entry["workloads"]


@pytest.mark.parametrize("case", [
    _reads_nothing_on_the_parent, _reads_contended_over_new_files,
    _reads_nothing_where_no_file_was_made, _is_declared_for_the_five_cells],
    ids=lambda f: f.__name__.strip("_"))
def test_link_contended_reader(case):
    from tests.test_repair_cycle import _bench_window
    window, _ = _bench_window([{}], [{}])
    case(window.load_by_name("layer_metrics",
                             "store.link_contended_pct").read,
         _bench_window)


# -- the owner's compile clock ------------------------------------------

_COMPILE_SCRIPT = """
import json
from dfs_tpu.sidecar.service import SidecarClient, SidecarServer
srv = SidecarServer(fragmenter="fixed")
srv.start()
client = SidecarClient(srv.port)
before = client.health()["compile"]
import jax, jax.numpy as jnp
from jax import monitoring
jax.jit(lambda x: x * 2 + 1)(jnp.arange(7)).block_until_ready()
import time
monitoring.record_event_duration_secs(
    "/jax/core/compile/some_later_duration", 0.002)
time.sleep(0.02)                # one after the other, not one in the other
monitoring.record_event_duration_secs(
    "/jax/core/compile/some_later_duration", 0.004)
monitoring.record_event("/jax/compilation_cache/some_later_event")
# a jit traced inside a jit: the inner event ends first, the outer one
# reports an interval that holds it
time.sleep(0.3)
mid = client.health()["compile"]
t0 = time.monotonic()
for _ in range(40000):          # one kernel of the chain holds 31 369
    monitoring.record_event_duration_secs(
        "/jax/core/compile/jaxpr_trace_duration", 0.000001)
outer = time.monotonic() - t0 + 0.05
monitoring.record_event_duration_secs(
    "/jax/core/compile/jaxpr_trace_duration", outer)
monitoring.record_event("/jax/elsewhere/not_compiling")
after = client.health()["compile"]
client.close()
srv.stop()
jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
print(json.dumps({"before": before, "mid": mid, "after": after,
                  "outer": outer,
                  "stopped": srv.compile_clock.snapshot()}))
"""


@pytest.fixture(scope="module")
def compile_tables():
    """One fresh interpreter: an owner, one jitted call, a few events."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO)}
    out = subprocess.run([sys.executable, "-c", _COMPILE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _starts_at_zero(t: dict) -> None:
    assert t["before"] == {
        "traceS": 0.0, "lowerS": 0.0, "backendCompileS": 0.0, "modules": 0,
        "cacheRequests": 0, "cacheHits": 0, "firstRegionS": None}


def _one_jitted_call_is_counted(t: dict) -> None:
    after = t["after"]
    assert after["backendCompileS"] > 0.0 and after["modules"] >= 1
    assert after["traceS"] > 0.0 and after["lowerS"] > 0.0
    assert after["cacheHits"] <= after["cacheRequests"]
    assert after["firstRegionS"] is None       # a host engine runs none


def _an_unknown_event_keeps_its_own_name(t: dict) -> None:
    after = t["after"]
    assert after["/jax/core/compile/some_later_duration"] == 0.006
    assert after["/jax/compilation_cache/some_later_event"] == 1
    assert "/jax/elsewhere/not_compiling" not in after


def _nested_events_count_once(t: dict) -> None:
    # 40 000 x 1 us that ended inside the outer event are its seconds of
    # tracing, not 0.04 s more
    assert t["after"]["traceS"] - t["mid"]["traceS"] \
        == pytest.approx(t["outer"], abs=1e-5)


def _a_stopped_owner_listens_no_more(t: dict) -> None:
    after = {k: v for k, v in t["after"].items() if k != "firstRegionS"}
    assert t["stopped"] == after


@pytest.mark.parametrize("case", [
    _starts_at_zero, _one_jitted_call_is_counted,
    _an_unknown_event_keeps_its_own_name, _nested_events_count_once,
    _a_stopped_owner_listens_no_more],
    ids=lambda f: f.__name__.strip("_"))
def test_owner_compile_clock(compile_tables, case):
    case(compile_tables)


def test_first_region_is_timed_dispatch_to_collected(rng):
    """The device engine (on the CPU backend here) times the first region
    it runs, and only the first."""
    from dfs_tpu.fragmenter.cdc_anchored import AnchoredTpuFragmenter
    from dfs_tpu.ops.cdc_anchored import AnchoredCdcParams
    from dfs_tpu.ops.cdc_v2 import AlignedCdcParams

    # tests/test_cdc_anchored.py's shapes: compiled once for both files
    small = AnchoredCdcParams(
        chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                               strip_blocks=64),
        seg_min=2048, seg_max=4096, seg_mask=2047, strong_bits=1)
    frag = AnchoredTpuFragmenter(small, region_bytes=16384, lane_multiple=8)
    assert frag.first_region_s is None
    data = rng.integers(0, 256, size=100_000, dtype="uint8").tobytes()
    t0 = time.monotonic()
    frag.chunk(data)
    first = frag.first_region_s
    assert first is not None and 0.0 < first <= time.monotonic() - t0
    frag.chunk(data)
    assert frag.first_region_s == first and frag._first_region is None
