"""``chain.strong_cut_pct`` (PR 37): nothing on a program whose owner
does not count how its segments ended, the strong cuts' share of the
window's segments on one that does, and declared — last, for the four
cells, found by its name — as data."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import window  # noqa: E402

NAME = "chain.strong_cut_pct"


def a_window(owner_before, owner_after):
    put = SimpleNamespace(kind="put", acked=True, nbytes=16 * window.MIB)
    return window.Window(
        seconds=50.0, t_open=0.0, t_close=50.0, setup_s=1.0, ops=[put],
        session_ops=[put], stores=None, manifests={},
        nodes_before=[], nodes_after=[], prom_before=[], prom_after=[],
        owner_before=owner_before, owner_after=owner_after,
        config={}, traffic={}, device_kind="x")


def owner(regions, segments=None, strong=0, window_=0, forced=0):
    device = {"platform": "tpu", "regions": regions, "overflow_redos": 0}
    if segments is not None:
        device.update(segments=segments, strong_cuts=strong,
                      window_cuts=window_, forced_cuts=forced)
    return {"ok": True, "device": device}


def test_nothing_on_a_program_without_the_counters():
    read = window.load_by_name("layer_metrics", NAME).read
    assert read(a_window(owner(4), owner(90))) is None
    assert read(a_window({}, {})) is None
    assert read(a_window({"device": None}, {"device": None})) is None


def test_share_of_the_segments_cut_in_the_window():
    # warm-up and preload cut segments too: they are not the window's
    before = owner(20, segments=4100, strong=3200, window_=880, forced=0)
    after = owner(100, segments=20500, strong=16000, window_=4420,
                  forced=0)
    read = window.load_by_name("layer_metrics", NAME).read
    assert read(a_window(before, after)) == 100.0 * 12800 / 16400
    # no segment cut in the window: no base, no number
    assert read(a_window(before, before)) is None


def test_declared_last_for_the_four_cells():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    cells = m.pop("workloads")
    assert {"tarball.ingest-fresh", "tarball.ingest-edited",
            "snapshots.ingest-versions", "archive.ingest-ec"} <= set(cells)
    assert m == {"name": NAME, "unit": "%", "better": "higher",
                 "source": "program_counter", "layer": "device chain",
                 "moves": "ingest_mibps"}
