"""The commit's three readers (PR 45): nothing on a program without the
spans ``commit.save`` / ``commit.announce`` (the parent, over which the
driver lays these files), a commit's two sides in ms an object and the
share of the shorter that the longer hid on one with them — and declared
after everything PR 44 had, for the two cells that list
``ingest.commit_ms_per_object``."""

import json

from test_resident_hit import BENCH, a_window

import window

SAVE = "ingest.commit_save_ms_per_object"
TOLD = "ingest.commit_announce_ms_per_object"
OVERLAP = "ingest.commit_overlap_pct"
NAMES = (SAVE, TOLD, OVERLAP)


def read(name, w):
    return window.load_by_name("layer_metrics", name).read(w)


def node(k, commit_s, save_s=None, told_s=None):
    """A node's page after ``k`` commits of these seconds each."""
    spans = {"upload.commit": {"count": k, "seconds": k * commit_s,
                               "selfSeconds": 0.0}}
    if save_s is not None:
        spans["commit.save"] = {"count": k, "seconds": k * save_s,
                                "selfSeconds": k * save_s}
        spans["commit.announce"] = {"count": k, "seconds": k * told_s,
                                    "selfSeconds": 0.0}
    return {"obs": {"spans": spans}}


def test_nothing_on_a_program_without_the_spans():
    # the parent's page: upload.commit, neither child
    w = a_window([node(10, 0.03)] * 3, [node(500, 0.03)] * 3)
    assert [read(n, w) for n in NAMES] == [None] * 3
    # before PR 30 obs.spans was the ring's length; and no page at all
    for nodes in ([{"obs": {"spans": 812}}] * 3, [{}] * 3):
        assert [read(n, a_window(nodes, nodes)) for n in NAMES] \
            == [None] * 3


def test_nothing_where_no_commit_closed_in_the_window():
    page = [node(40, 0.02, 0.006, 0.014)] * 3
    assert [read(n, a_window(page, page)) for n in NAMES] == [None] * 3


def test_ms_an_object_over_the_window():
    # what the preload left is not the window's; node 3 not read yet
    before = [node(100, 0.5, 0.4, 0.1), node(100, 0.5, 0.4, 0.1), {}]
    after = [node(300, 0.5, 0.4, 0.1), node(200, 0.5, 0.4, 0.1),
             node(100, 0.5, 0.4, 0.1)]
    w = a_window(before, after)
    assert abs(read(SAVE, w) - 400.0) < 1e-9
    assert abs(read(TOLD, w) - 100.0) < 1e-9


def test_overlap_is_the_share_of_the_shorter_side_that_was_hidden():
    def over(commit_s, save_s, told_s):
        w = a_window([node(0, 0.0, 0.0, 0.0)] * 3,
                     [node(200, commit_s, save_s, told_s)] * 3)
        return read(OVERLAP, w)

    # side by side: a commit lasts the longer of the two
    assert abs(over(0.014, 0.006, 0.014) - 100.0) < 1e-9
    # in a row: a commit lasts their sum
    assert abs(over(0.020, 0.006, 0.014) - 0.0) < 1e-9
    # half of the save hidden behind the announces
    assert abs(over(0.017, 0.006, 0.014) - 50.0) < 1e-9
    # the loop's turns between them make a commit longer than the sum:
    # clipped, not negative; shorter than the longer side cannot be, but
    # a rounding must not read 100.0000001
    assert over(0.025, 0.006, 0.014) == 0.0
    assert over(0.0139, 0.006, 0.014) == 100.0


def test_declared_for_the_two_cells_that_read_a_commit_an_object():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = by_name["ingest.commit_ms_per_object"]["workloads"]
    assert cells == ["smallfiles.ingest-batch", "images.ingest-nightly"]
    # appended, in this order (not "last": the next PR appends too)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(SAVE)
    assert names[at:at + 3] == list(NAMES)
    assert at > names.index("store.resident_absent_pct")
    for name, unit, better in ((SAVE, "ms", "lower"), (TOLD, "ms", "lower"),
                               (OVERLAP, "%", "higher")):
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "program_span", "layer": "node ingest",
            "moves": "ingest_mibps", "workloads": cells}
