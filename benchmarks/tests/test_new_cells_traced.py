"""The branch of a traced run that a CPU rehearsal cannot reach: device
busy time present. Each cell PR 27 brought — its own traffic file, its
configuration, a trace recorded on the chip — through every per-layer
reader that lists it: a number or None, never an exception (an
exception there is exit 1 and no result line, on the chip and traced
only: ledger, PR 26)."""

import gzip
import json
from pathlib import Path

import pytest

import reduce_trace
import run
import window
from ops import Op

BENCH = Path(__file__).resolve().parent.parent
NEW_CELLS = ["tarball.ingest-edited", "snapshots.ingest-versions"]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    trace = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    trace.write_bytes(gzip.decompress(
        (Path(__file__).parent / "recorded.xplane.pb.gz").read_bytes()))
    return reduce_trace.reduce(reduce_trace.device_events(str(trace)))


def _node(index: bool, scale: int) -> dict:
    doc = {"obs": {"spans": {
               name: {"count": 4 * scale, "seconds": 1.5 * scale,
                      "selfSeconds": 0.5 * scale}
               for name in ("http./upload", "upload.probe", "cas.has_many",
                            "cas.put_many", "upload.fragment",
                            "upload.commit", "peer.store_chunks",
                            "rpc.store_chunks", "upload.verify_trusted")},
               "sentinel": {"recentMaxLagS": 0.01}},
           "ingest": {"stalls": {k: 0.1 * scale for k in (
               "creditS", "placementS", "bodyWaitS", "feedWaitS")},
               "cas": {"queueS": 1.0 * scale, "busyS": 2.0 * scale}},
           "durability": {"mode": "fsync", "fsyncs": 100 * scale,
                          "dirBarriers": 40 * scale},
           "index": {"enabled": False}}
    if index:
        doc["index"] = {
            "enabled": True, "probesSkipped": 90 * scale,
            "placementSkipped": 90 * scale,
            "placementConsidered": 100 * scale, "filterTrusted": 80 * scale,
            "statFallbacks": scale, "statFallbackHits": 0,
            "lsi": {"lookups": 50 * scale, "lookupHits": 49 * scale,
                    "lookupS": 0.2 * scale, "compactStallS": 0.1 * scale,
                    "bgCompactS": 0.0, "runEntries": 50000,
                    "memtableCap": 4096, "runCount": 3, "compactions": 2,
                    "memtableEntries": 10}}
    return doc


def _owner(scale: int) -> dict:
    return {"device": {k: 1.0 * scale for k in (
        "regions", "inputWaitS", "dispatchS", "collectS", "deviceWaitS",
        "replyS", "streams", "streamS", "openS", "bytes")}}


@pytest.mark.parametrize("cell", NEW_CELLS)
def test_every_reader_of_a_new_cell_reads_a_traced_chip_window(cell,
                                                                recorded):
    bench, entry, config, traffic = run.load_cell(cell)
    assert recorded["busy_s"] > 0                  # the chip-only branch
    assert int(traffic["object_bytes"]) == 16777216
    index = "--index" in config["deployment"]["node_args"]
    ops = [Op("put", c, c, f"id{c}", key=("ver", 16 + c),
              nbytes=int(traffic["object_bytes"]), t0=101.0 + c,
              t1=104.0 + c, status=201, phase="run") for c in range(3)]
    w = window.Window(
        seconds=51.0, t_open=100.0, t_close=151.0, setup_s=60.0, ops=ops,
        session_ops=ops, stores=None, manifests={},
        nodes_before=[_node(index, 1)] * 3, nodes_after=[_node(index, 2)] * 3,
        prom_before=[{}] * 3, prom_after=[{}] * 3, owner_before=_owner(1),
        owner_after=_owner(3), config=config, traffic=traffic,
        device_kind="TPU v5 lite", trace=recorded, trace_regions=9)
    listed = run.metrics_of(bench, "per_layer", cell)
    assert len(listed) == (35 if index else 29)
    values = {m["name"]: window.load_by_name(
        "layer_metrics", m["name"]).read(w) for m in listed}
    for name, value in values.items():
        assert value is None or isinstance(value, (int, float)), name
        json.dumps(value)
    assert 0 < values["chain.hbm_roofline"] < 100
    assert values["chain.busy_ms_per_region"] > 0
    assert values["device.idle_pct.ingest"] > 99
    assert values["place.probe_s_per_gib"] > 0
    assert (values.get("index.probe_skip_pct") is not None) == index
    if index:
        assert values["index.run_entries_per_memtable"] > 10
