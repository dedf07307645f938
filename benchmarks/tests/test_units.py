"""The benchmark's own arithmetic, with no cluster: data from the seed,
the generators' op sequences, the reference's replay, the trace
reduction, the roofline floor, and BENCHMARK.json's layout."""

import json
import re
import threading
from pathlib import Path

import pytest

import check
import data
import reduce_trace
import reference
import roofline
import run
import window
from ops import Op

BENCH = Path(__file__).resolve().parent.parent
REPO = BENCH.parent
BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


# -- data ------------------------------------------------------------------

def test_segment_is_seeded_and_half_repeats():
    a = data.segment(2**31 + 5, 3, 1 << 20, period=1 << 18)
    assert (a == data.segment(2**31 + 5, 3, 1 << 20, period=1 << 18)).all()
    b = data.segment(2**31 + 5, 4, 1 << 20, period=1 << 18)
    half = 1 << 17
    assert (a[half:2 * half] == b[half:2 * half]).all()     # the block
    assert (a[half:2 * half] == a[3 * half:4 * half]).all()  # tiled
    assert not (a[:half] == b[:half]).all()                  # fresh per k
    assert not (a[:half] == data.segment(7, 3, 1 << 20, 1 << 18)[:half]).all()


# -- generators: the same seed gives the same op log -------------------------

class FakeApi:
    """Answers every op at once and stops the loop after ``limit`` ops."""

    def __init__(self, stop, limit):
        self.stop, self.limit, self.calls = stop, limit, []

    def _note(self, *call):
        self.calls.append(call)
        if len(self.calls) >= self.limit:
            self.stop.set()

    def put(self, client, node, key, body, want_id, block=0):
        self._note("put", client, node, key, want_id, block)
        return Op("put", client, node, want_id, key=key, status=201,
                  got_id=want_id)

    def get(self, client, node, fid, keep=False):
        self._note("get", client, node, fid)
        return Op("get", client, node, fid, status=200), b""

    def stat(self, client, node, fid):
        self._note("stat", client, node, fid)
        return Op("stat", client, node, fid, status=200), {}

    def delete(self, client, node, fid):
        self._note("delete", client, node, fid)
        return Op("delete", client, node, fid, status=200)


def _oplog(cell: str, seed: int, n: int, overrides: dict) -> list:
    """``cell``: (config, traffic) file names — also of a cell that
    BENCHMARK.json does not list yet."""
    config = json.loads((BENCH / "configs" / f"{cell[0]}.json").read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell[1]}.json").read_text())
    traffic = {**traffic, **traffic["rehearsal"], **overrides}
    gen = window.load_by_name("generators", traffic["kind"]).Generator(
        traffic, config, seed)
    stop = threading.Event()
    api = FakeApi(threading.Event(), 10**9)
    gen.preload(api)
    api.stop, api.limit = stop, len(api.calls) + n
    gen.run_client(1, api, stop)
    return api.calls


@pytest.mark.parametrize("cell,overrides", [
    (("tarball-3n-rf2", "ingest-fresh"), {"object_bytes": 1 << 16,
                              "period_bytes": 1 << 14}),
])
def test_generator_same_seed_same_log(cell, overrides):
    a = _oplog(cell, 3_000_000_019, 120, overrides)
    assert a == _oplog(cell, 3_000_000_019, 120, overrides)
    assert a != _oplog(cell, 3_000_000_020, 120, overrides)


def test_every_seed_sends_the_same_segments_in_another_order():
    config = json.loads((BENCH / "configs/tarball-3n-rf2.json").read_text())
    traffic = json.loads((BENCH / "traffic/ingest-fresh.json").read_text())
    gen = window.load_by_name("generators", "segments").Generator
    a, b = gen(traffic, config, 2**31 + 11), gen(traffic, config, 7)
    lead, n = traffic["lead_objects"], traffic["ratio_objects"]
    assert a.order != b.order
    for g in (a, b):
        assert sorted(g.order[:lead]) == list(range(lead))
        assert sorted(g.order[lead:]) == list(range(lead, lead + n))
    assert (a.make(("seg", 9)) == b.make(("seg", 9))).all()


def test_stored_ratio_counts_a_fixed_slice_on_disk(tmp_path):
    """Warm-up and lead are 'before'; the slice's chunks count once per
    node that holds them; what came later does not count."""
    def put(kind, k, fid, phase="run"):
        return Op("put", 0, 0, fid, key=(kind, k), nbytes=100, status=201,
                  phase=phase)

    ops = [put("warm", 0, "w", "warm"), put("seg", 0, "a"),
           put("seg", 1, "b"), put("seg", 2, "c"), put("seg", 3, "d")]
    manifests = {"w": [{"digest": "aa00"}],
                 "a": [{"digest": "bb01"}, {"digest": "bb02"}],
                 "b": [{"digest": "bb01"}, {"digest": "cc03"}],   # 1 new
                 "c": [{"digest": "cc03"}, {"digest": "dd04"}],   # 1 new
                 "d": [{"digest": "ee05"}]}                       # later
    for node, digests in ((1, ["aa00", "bb01", "cc03", "dd04", "ee05"]),
                          (2, ["bb01", "bb02", "cc03"]), (3, ["dd04"])):
        for d in digests:
            path = tmp_path / f"node-{node}" / "chunks" / d[:2] / d
            path.parent.mkdir(parents=True, exist_ok=True)
            path.write_bytes(b"x" * 30)
    w = window.Window(
        seconds=1, t_open=0, t_close=1, setup_s=0, ops=[], session_ops=ops,
        stores=check.Stores(tmp_path, 3), manifests=manifests,
        nodes_before=[],
        nodes_after=[], prom_before=[], prom_after=[], owner_before={},
        owner_after={}, config={"deployment": {"nodes": 3}},
        traffic={"lead_objects": 1, "ratio_objects": 2}, device_kind="x")
    read = window.load_by_name("end_to_end", "stored_ratio").read
    assert read(w) == (2 * 30 + 2 * 30) / 200       # cc03 x2, dd04 x2
    assert check.unsynced(w.stores, {"bb01", "cc03", "zz"}, [
        {"durability": {"fsyncs": 1}}, {"durability": {"fsyncs": 5}},
        {}])[0] == 1                               # node 1: 2 files, 1 barrier


# -- the reference replays a log ---------------------------------------------

def _op(kind, fid, t1, status, **kw):
    return Op(kind, 0, 0, fid, t0=t1 - 0.5, t1=t1, status=status, **kw)


def test_reference_replays_a_sound_log():
    log = [
        _op("put", "A", 1, 201, got_id="A", key=("k", 1), nbytes=10),
        _op("get", "A", 2, 200, body_sha="A", body_len=10),
        _op("stat", "A", 3, 200, got_id="A", body_len=10),
        _op("put", "B", 4, 201, got_id="B", key=("k", 2), nbytes=10),
        _op("delete", "A", 5, 200),
        _op("get", "A", 6, 404),
        _op("put", "C", 7, 0, key=("k", 3), nbytes=10),     # never acked
    ]
    exp = reference.replay(log)
    assert exp.violations == []
    assert exp.live == {"B": ("k", 2)} and exp.deleted == {"A"}


@pytest.mark.parametrize("bad,needle", [
    (_op("put", "A", 1, 201, got_id="X", key=("k", 1), nbytes=10),
     "not sha256(body)"),
    (_op("get", "A", 9, 404), "404 for a live id"),
    (_op("get", "A", 9, 200, body_sha="Z", body_len=10), "not the bytes"),
    (_op("get", "A", 9, 200, body_sha="A", body_len=9), "not the bytes"),
    (_op("stat", "A", 9, 200, got_id="A", body_len=11), "manifest says"),
    (_op("get", "Q", 9, 200, body_sha="Q"), "never put"),
    (_op("delete", "Q", 9, 200), "not live"),
])
def test_reference_names_what_a_store_could_not_answer(bad, needle):
    log = [_op("put", "A", 1, 201, got_id="A", key=("k", 1), nbytes=10)]
    log = [bad] if bad.kind == "put" else log + [bad]
    violations = reference.replay(log).violations
    assert len(violations) == 1 and needle in violations[0]


def test_reference_404_after_delete_then_200_is_a_violation():
    log = [_op("put", "A", 1, 201, got_id="A", key=("k", 1), nbytes=10),
           _op("delete", "A", 2, 200),
           _op("get", "A", 3, 200, body_sha="A", body_len=10)]
    assert "deleted" in reference.replay(log).violations[0]


# -- window arithmetic --------------------------------------------------------

def test_percentile_is_nearest_rank():
    v = list(range(1, 201))
    assert window.percentile(v, 0.95) == 190
    assert window.percentile([5.0], 0.95) == 5.0
    assert window.percentile(list(range(1, 21)), 0.95) == 19


def test_parse_prom_drops_comments_and_exemplars():
    page = ('# TYPE dfs_latency_seconds histogram\n'
            'dfs_latency_seconds_sum{name="upload.replicate"} 0.25\n'
            'dfs_latency_seconds_bucket{name="x",le="0.5"} 3 '
            '# {trace_id="ab"} 0.4 17\n'
            'dfs_under_replicated 0\n# EOF\n')
    got = window.parse_prom(page)
    assert got['dfs_latency_seconds_sum{name="upload.replicate"}'] == 0.25
    assert got['dfs_latency_seconds_bucket{name="x",le="0.5"}'] == 3
    assert got["dfs_under_replicated"] == 0


# -- trace reduction ------------------------------------------------------------

def test_union_and_reduce_on_made_up_events():
    assert reduce_trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
    planes = {"/device:TPU:0": [(1e9, 2e9, "fusion.1"), (1.5e9, 2.5e9, "sha"),
                                (4e9, 4.5e9, "fusion.1")]}
    got = reduce_trace.reduce(planes, spans=[(0, 3.4e9)], lo_ns=0, hi_ns=5e9)
    assert got["busy_s"] == pytest.approx(2.0)
    assert got["window_s"] == pytest.approx(5.0)
    assert got["device_ops"][0] == ["fusion.1", pytest.approx(1.5)]
    gaps = dict(map(tuple, got["idle_gaps"]))
    assert gaps["all gaps: stream open at the owner"] == pytest.approx(2.5)
    assert gaps["all gaps: no stream open"] == pytest.approx(0.5)
    assert reduce_trace.reduce({"/device:TPU:0": []}) == \
        {"planes": [], "events": 0}
    two = reduce_trace.reduce({**planes, "/device:CUSTOM:x": []},
                              lo_ns=0, hi_ns=5e9)
    assert two["busy_s"] == pytest.approx(2.0)      # an empty plane is no chip


def test_reduce_recorded_chip_trace(tmp_path):
    """A 5 s slice the owner recorded on a TPU v5 lite while
    ``tarball.ingest-fresh`` ran (my chip run, PR 23): nine 16 MiB
    regions, 5727 op events on one chip's ``XLA Ops`` line."""
    import gzip

    trace = tmp_path / "recorded.xplane.pb"
    trace.write_bytes(gzip.decompress(
        (Path(__file__).parent / "recorded.xplane.pb.gz").read_bytes()))
    got = reduce_trace.reduce(reduce_trace.device_events(str(trace)))
    assert got["planes"] == ["/device:TPU:0"] and got["events"] == 5727
    assert got["busy_s"] == pytest.approx(0.017111495, rel=1e-6)
    assert got["busy_s"] < got["window_s"] < 5.1
    assert got["device_ops"][0][0] == "%strip_chunk_states.1"
    assert len(got["device_ops"]) == 10


# -- roofline and peaks -----------------------------------------------------------

def test_roofline_floor_and_unknown_device():
    floor = roofline.region_min_hbm_bytes(16 << 20, 8192)
    assert floor == (16 << 20) + 2048 * 40
    pct = roofline.hbm_roofline_pct(16 << 20, 8192, 2e-3, "TPU v5 lite")
    assert pct == pytest.approx(100 * floor / 819e9 / 2e-3)
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9000")
    with pytest.raises(KeyError):
        roofline.peaks_for("source")


# -- BENCHMARK.json: the layout later PRs add to ------------------------------------

def test_result_line_keys_are_pinned():
    assert run.RESULT_KEYS == ("correct", "attempted", "failed", "metrics",
                               "device")


def test_benchmark_json_names_and_files():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"] for w in b["workloads"]}
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["source"]) <= 200
        doc = json.loads((REPO / c["file"]).read_text())
        assert doc["source"] == c["source"] and doc["reduced"] == c["reduced"]
        assert len(c["why"]) <= 200
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        traffic = json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert window.load_by_name("generators", traffic["kind"]).Generator
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layer_metrics")):
        for m in b[group]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"])
            assert m["better"] in ("lower", "higher")
            assert set(m.get("workloads", cells)) <= cells
            assert callable(window.load_by_name(folder, m["name"]).read)
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m["workloads"]) <= moved
    for w in cells:                     # every cell reports enough
        assert len(run.metrics_of(b, "end_to_end", w)) >= 2
        assert len(run.metrics_of(b, "per_layer", w)) >= 1
