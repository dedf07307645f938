"""``store.resident_hit_pct`` (PR 28): nothing on a program without the
counter, the resident set's share of the existence checks put to it over
the window on one with it, and declared — LAST, for the two index-off
cells — as data."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import window  # noqa: E402

NAME = "store.resident_hit_pct"


def a_window(nodes_before, nodes_after):
    put = SimpleNamespace(kind="put", acked=True, nbytes=16 * window.MIB)
    return window.Window(
        seconds=50.0, t_open=0.0, t_close=50.0, setup_s=1.0, ops=[put],
        session_ops=[put], stores=None, manifests={},
        nodes_before=nodes_before, nodes_after=nodes_after,
        prom_before=[], prom_after=[], owner_before={}, owner_after={},
        config={}, traffic={}, device_kind="x")


def node(hits, misses, entries=0, drops=0):
    return {"durability": {"mode": "fsync", "fsyncs": 10, "dirBarriers": 4,
                           "residentHits": hits, "residentMisses": misses,
                           "residentEntries": entries,
                           "residentDrops": drops}}


def test_nothing_on_a_program_without_the_counter():
    older = [{"durability": {"mode": "fsync", "fsyncs": 10,
                             "dirBarriers": 4}}] * 3
    after = [{"durability": {"mode": "fsync", "fsyncs": 900,
                             "dirBarriers": 300}}] * 3
    read = window.load_by_name("layer_metrics", NAME).read
    assert read(a_window(older, after)) is None
    assert read(a_window([{}] * 3, [{}] * 3)) is None


def test_share_of_the_checks_put_to_the_set_over_the_window():
    # what the preload left is not the window's; node 3 not read yet
    before = [node(5000, 5000, 900), node(4000, 6000, 800), {}]
    after = [node(5900, 5050, 950), node(4950, 6000, 800),
             node(1000, 50, 40)]
    read = window.load_by_name("layer_metrics", NAME).read
    assert read(a_window(before, after)) \
        == 100.0 * (900 + 950 + 1000) / (950 + 950 + 1050)
    # no check was put to the set in the window: no base, no number
    assert read(a_window(before[:2], before[:2])) is None


def test_declared_last_for_the_index_off_cells():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    assert bench["per_layer"][-1] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "chunk store",
        "moves": "ingest_mibps",
        "workloads": ["tarball.ingest-fresh", "tarball.ingest-edited"]}
