"""``gaps_by_span.py``'s attribution on the recorded chip trace with
made-up spans, and the new readers on a program that serves no span
totals (they have to leave their metric out, never raise)."""

import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import gaps_by_span
import reduce_trace
import window

BENCH = Path(__file__).resolve().parent.parent
ORIGIN = 7 * 10**12         # the session's start on CLOCK_MONOTONIC
S = 10**9


def span(name, start_s, seconds, node=1):
    return {"name": name, "m0": ORIGIN + int(start_s * S), "d": seconds,
            "node": node, "s": f"{name}@{start_s}", "t": "t"}


def test_recorded_trace_gaps_take_the_labels_of_made_up_spans(tmp_path):
    """The 5 s slice of PR 23's chip run: the device ran from 0.272 s to
    3.666 s of it in three bursts. The long gap between the first two
    (3.22 s from 0.318 s) gets a stream that is being collected, the
    tail after the last (1.43 s) gets placement with no stream open,
    the head before the first nothing at all."""
    trace = tmp_path / "recorded.xplane.pb"
    trace.write_bytes(gzip.decompress(
        (BENCH / "tests" / "recorded.xplane.pb.gz").read_bytes()))
    spans = [span("http./upload", 0.30, 4.9),
             span("owner.stream", 0.31, 3.3, node=0),
             span("owner.collect", 0.32, 3.2, node=0),
             span("upload.place", 3.60, 1.5),
             span("cas.put_many", 3.70, 1.2),
             span("owner.dispatch", 3.50, 0.04, node=0)]
    report = gaps_by_span.attribute(
        reduce_trace.device_events(str(trace)), spans, ORIGIN,
        ORIGIN + int(5.1 * S), min_gap_ns=1e6)
    assert report["slice_s"] == pytest.approx(5.1)
    assert report["busy_s"] == pytest.approx(0.017111495, rel=1e-6)
    assert 0.0 < report["busy_inside_owner_span_pct"] < 100.0
    rows = report["gaps"]
    assert len(rows) == 10 and report["short_gaps"] == 873
    assert [r["seconds"] for r in rows] \
        == sorted((r["seconds"] for r in rows), reverse=True)
    long, tail, head = rows[:3]
    assert long["seconds"] == pytest.approx(3.223387, rel=1e-5)
    assert long["phase"] == "collect"
    assert [n for n, _ in long["open"]] \
        == ["http./upload", "owner.stream", "owner.collect"]
    assert tail["phase"] == "no stream open"
    assert [n for n, _ in tail["open"]] \
        == ["http./upload", "upload.place", "cas.put_many"]
    assert head["start_s"] == 0.0 and head["open"] == []
    assert head["phase"] == "no stream open"
    assert sum(report["by_phase_s"].values()) + report["short_gaps_s"] \
        == pytest.approx(5.1 - 0.017111495, rel=1e-6)
    text = gaps_by_span.render(report)
    assert "[collect]  http./upload 100%, owner.stream 100%" in text
    assert text.splitlines()[-1].startswith("873 shorter gaps")


def test_between_windows_and_no_device_plane():
    spans = [span("owner.stream", 0.0, 2.0, node=0),
             span("owner.dispatch", 0.0, 0.2, node=0),
             span("owner.collect", 1.9, 0.1, node=0)]
    report = gaps_by_span.attribute({}, spans, ORIGIN, ORIGIN + 2 * S, 1e6)
    (row,) = report["gaps"]
    assert row["plane"] == "(no device plane)"
    assert row["phase"] == "between windows"
    assert row["open"] == [["owner.stream", 1.0]]


NEW = ["edge.upload_s_per_gib", "edge.self_s_per_gib",
       "edge.body_wait_s_per_gib", "ingest.feed_wait_s_per_gib",
       "ingest.commit_s_per_gib", "seam.fragment_s_per_gib",
       "store.put_s_per_gib", "store.cas_queue_s_per_gib",
       "replicate.peer_s_per_gib", "replicate.wire_s_per_gib",
       "owner.input_wait_pct", "owner.reply_wait_pct",
       "owner.host_s_per_gib", "owner.device_wait_s_per_gib",
       "owner.stream_open_pct"]


def a_window(nodes_before, nodes_after, owner_before, owner_after):
    put = SimpleNamespace(kind="put", acked=True, nbytes=window.GIB)
    return window.Window(
        seconds=50.0, t_open=0.0, t_close=50.0, setup_s=1.0, ops=[put],
        session_ops=[put], stores=None, manifests={},
        nodes_before=nodes_before, nodes_after=nodes_after,
        prom_before=[], prom_after=[], owner_before=owner_before,
        owner_after=owner_after, config={}, traffic={}, device_kind="x")


def test_new_readers_find_nothing_on_an_older_program():
    """The parent's ``/metrics``: ``obs.spans`` is the ring's length,
    the stopwatches and ``Health.device`` lack the new keys. Only the
    CAS queue time, which it always counted, can be read."""
    node = {"obs": {"spans": 812},
            "ingest": {"stalls": {"creditS": 0.5, "placementS": 9.0},
                       "cas": {"queueS": 3.0, "busyS": 8.0}}}
    older = {"obs": {"spans": 100},
             "ingest": {"stalls": {}, "cas": {"queueS": 1.0}}}
    dev = {"device": {"platform": "tpu", "regions": 64}}
    w = a_window([older], [node], dev, dev)
    got = {n: window.load_by_name("layer_metrics", n).read(w) for n in NEW}
    assert got.pop("store.cas_queue_s_per_gib") == 2.0
    assert set(got.values()) == {None}


def test_new_readers_read_the_totals_as_deltas():
    def node(k):
        def row(seconds, self_s):
            return {"count": k, "seconds": k * seconds,
                    "selfSeconds": k * self_s}
        return {"obs": {"spans": {
                    "http./upload": row(10.0, 1.0),
                    "upload.commit": row(0.5, 0.5),
                    "upload.fragment": row(3.0, 3.0),
                    "cas.put_many": row(6.0, 6.0),
                    "rpc.store_chunks": row(5.0, 5.0),
                    "peer.store_chunks": row(4.0, 4.0)}},
                "ingest": {"stalls": {"creditS": k * 1.0,
                                      "bodyWaitS": k * 2.0,
                                      "feedWaitS": k * 0.25},
                           "cas": {"queueS": k * 0.125}}}

    def owner(k):
        return {"device": {"inputWaitS": k * 6.0, "dispatchS": k * 1.0,
                           "collectS": k * 2.0, "deviceWaitS": k * 0.5,
                           "replyS": k * 1.0, "streamS": k * 10.0,
                           "openS": k * 5.0}}

    w = a_window([node(1), node(2)], [node(3), node(3)], owner(1), owner(3))
    got = {n: window.load_by_name("layer_metrics", n).read(w) for n in NEW}
    assert got == {
        "edge.upload_s_per_gib": 30.0, "edge.self_s_per_gib": 3.0,
        "edge.body_wait_s_per_gib": 6.0,
        "ingest.feed_wait_s_per_gib": 0.75,
        "ingest.commit_s_per_gib": 1.5,
        "seam.fragment_s_per_gib": 6.0, "store.put_s_per_gib": 18.0,
        "store.cas_queue_s_per_gib": 0.375,
        "replicate.peer_s_per_gib": 12.0,
        "replicate.wire_s_per_gib": 3.0,
        "owner.input_wait_pct": 60.0, "owner.reply_wait_pct": 10.0,
        "owner.host_s_per_gib": 5.0, "owner.device_wait_s_per_gib": 1.0,
        "owner.stream_open_pct": 20.0}


def test_every_new_metric_is_declared_for_the_cell():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == NEW
    for name in NEW:
        m = declared[name]
        assert m["source"] == "program_counter"
        assert m["moves"] == "ingest_mibps"
        assert m["workloads"] == ["tarball.ingest-fresh"]
