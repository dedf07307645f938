"""``store.resident_absent_pct`` (PR 44): nothing on a program without
the counter or where no check missed, else the share of the window's
misses that the complete set answered "absent" — and declared, for all
six cells, found by its name."""

import json

from test_resident_hit import BENCH, a_window, node

import window

NAME = "store.resident_absent_pct"


def booted(hits, misses, absent, complete=True):
    page = node(hits, misses)
    page["durability"].update(residentAbsent=absent,
                              residentComplete=complete)
    return page


def test_nothing_on_a_program_without_the_counter():
    read = window.load_by_name("layer_metrics", NAME).read
    # the parent's page: hits and misses, no residentAbsent
    assert read(a_window([node(10, 10)] * 3, [node(90, 500)] * 3)) is None
    assert read(a_window([{}] * 3, [{}] * 3)) is None


def test_nothing_where_no_check_missed_in_the_window():
    read = window.load_by_name("layer_metrics", NAME).read
    before = [booted(100, 40, 40)] * 3
    after = [booted(900, 40, 40)] * 3       # every check a hit
    assert read(a_window(before, after)) is None


def test_share_of_the_misses_answered_absent_over_the_window():
    read = window.load_by_name("layer_metrics", NAME).read
    # what the preload left is not the window's; node 3 not read yet;
    # node 2 overflowed in the window and went back to the disk
    before = [booted(0, 1000, 1000), booted(0, 1000, 1000), {}]
    after = [booted(50, 3000, 3000), booted(50, 2000, 1200, False),
             booted(5, 400, 400)]
    assert read(a_window(before, after)) \
        == 100.0 * (2000 + 200 + 400) / (2000 + 1000 + 400)
    # complete everywhere and every byte fresh: 100
    assert read(a_window(before[:1], after[:1])) == 100.0
    # a store that never could vouch: misses, none answered here
    cold = [booted(0, 0, 0, False)]
    assert read(a_window(cold, [booted(0, 700, 0, False)])) == 0.0


def test_declared_for_all_six_cells():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert m == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "chunk store",
        "moves": "ingest_mibps",
        "workloads": [w["name"] for w in bench["workloads"]]}
    assert len(m["workloads"]) == 6 and bench["per_layer"][-1] == m
