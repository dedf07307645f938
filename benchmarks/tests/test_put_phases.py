"""The fifteen readers of PR 38's clocks — the put job's phase clock
(``durability.put``), the CAS pool's lanes (``ingest.cas.lanes``) and the
owner's compile clock (``Health.compile``): nothing on a program without
its table, the stated quotient on one with it, nothing on an empty base,
and each declared — after the 47 before them, for the four cells, found
by its name — as data."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import window  # noqa: E402
from put_phases import PHASES  # noqa: E402

CELLS = ["tarball.ingest-fresh", "tarball.ingest-edited",
         "snapshots.ingest-versions", "archive.ingest-ec"]
STORE, OWNER = "chunk store", "owner seam and device walk"
# name -> (unit, better, layer, moves), in BENCHMARK.json's order
DECLARED = {
    **{f"store.put_{p}_s_per_gib": ("s/GiB", "lower", STORE, "ingest_mibps")
       for p in ("precheck", "create", "write", "payload_fsync",
                 "link_wait", "link", "dir_barrier", "unlink")},
    "store.put_ms_per_new_file": ("ms", "lower", STORE, "ingest_mibps"),
    "store.put_accounted_pct": ("%", "higher", STORE, "ingest_mibps"),
    "store.write_lane_busy_s_per_gib":
        ("s/GiB", "lower", STORE, "ingest_mibps"),
    "store.probe_lane_queue_s_per_gib":
        ("s/GiB", "lower", STORE, "ingest_mibps"),
    "store.probe_lane_busy_s_per_gib":
        ("s/GiB", "lower", STORE, "ingest_mibps"),
    "owner.compile_s": ("s", "lower", OWNER, "setup_s"),
    "owner.compile_cache_hit_pct": ("%", "higher", OWNER, "setup_s"),
}


def a_window(nodes_before, nodes_after, owner_after=None, gib=0.25):
    put = SimpleNamespace(kind="put", acked=True,
                          nbytes=int(gib * window.GIB))
    return window.Window(
        seconds=50.0, t_open=0.0, t_close=50.0, setup_s=1.0,
        ops=[put] if gib else [], session_ops=[put], stores=None,
        manifests={}, nodes_before=nodes_before, nodes_after=nodes_after,
        prom_before=[], prom_after=[], owner_before={},
        owner_after=owner_after or {}, config={}, traffic={},
        device_kind="x")


def node(scale, lanes=True, put=True):
    """A node's ``/metrics`` whose every phase is ``scale`` x its place
    in the list (1-based), so that each reader's quotient is its own."""
    doc = {"durability": {"mode": "fsync", "fsyncs": 10 * scale},
           "ingest": {"cas": {"workers": 4, "ops": 9 * scale, "pending": 0,
                              "queueS": 6.0 * scale, "busyS": 60.0 * scale}}}
    if put:
        phases = {k: float(scale * (i + 1)) for i, k in enumerate(PHASES)}
        doc["durability"]["put"] = {
            "jobs": 4 * scale, "items": 900 * scale,
            "newFiles": 500 * scale, "jobS": sum(phases.values()), **phases}
    if lanes:
        doc["ingest"]["cas"]["lanes"] = {
            "w": {"ops": 4 * scale, "queueS": 2.0 * scale,
                  "busyS": 57.0 * scale},
            "r": {"ops": 0, "queueS": 0.0, "busyS": 0.0},
            "g": {"ops": 5 * scale, "queueS": 4.0 * scale,
                  "busyS": 3.0 * scale}}
    return doc


def owner(requests=8, hits=2):
    return {"ok": True, "device": {"regions": 90},
            "compile": {"traceS": 1.5, "lowerS": 0.25,
                        "backendCompileS": 20.0, "modules": requests,
                        "cacheRequests": requests, "cacheHits": hits,
                        "firstRegionS": 23.5}}


# over the window below the three nodes grow by 2 + 1 + 3 = 6 units of
# `scale`, a quarter GiB is acked: a phase worth k a unit reads 24 k
BEFORE = [node(1), node(2), {}]
AFTER = [node(3), node(3), node(3)]
EXPECTED = {
    "store.put_precheck_s_per_gib": 24.0 * (1 + 2),
    "store.put_create_s_per_gib": 24.0 * 3,
    "store.put_write_s_per_gib": 24.0 * 4,
    "store.put_payload_fsync_s_per_gib": 24.0 * 5,
    "store.put_link_wait_s_per_gib": 24.0 * 6,
    "store.put_link_s_per_gib": 24.0 * 7,
    "store.put_dir_barrier_s_per_gib": 24.0 * 8,
    "store.put_unlink_s_per_gib": 24.0 * 9,
    "store.put_ms_per_new_file": 1e3 * (6 * 55.0) / (6 * 500),
    "store.put_accounted_pct": 100.0,
    "store.write_lane_busy_s_per_gib": 24.0 * 57.0,
    "store.probe_lane_queue_s_per_gib": 24.0 * 4.0,
    "store.probe_lane_busy_s_per_gib": 24.0 * 3.0,
    "owner.compile_s": 21.75,
    "owner.compile_cache_hit_pct": 25.0,
}


def read(name, w):
    return window.load_by_name("layer_metrics", name).read(w)


def test_the_fifteen_are_the_fifteen():
    assert list(EXPECTED) == list(DECLARED) and len(DECLARED) == 15


@pytest.mark.parametrize("name", list(DECLARED))
def test_nothing_on_a_program_without_its_table(name):
    older = [node(1, lanes=False, put=False)] * 3
    after = [node(5, lanes=False, put=False)] * 3
    no_clock = {"ok": True, "device": {"regions": 90}, "spans": {}}
    assert read(name, a_window(older, after, no_clock)) is None
    assert read(name, a_window([{}] * 3, [{}] * 3, {})) is None
    assert read(name, a_window([], [], {"compile": None})) is None


@pytest.mark.parametrize("name", list(DECLARED))
def test_the_stated_quotient_on_one_that_serves_it(name):
    got = read(name, a_window(BEFORE, AFTER, owner()))
    assert got == pytest.approx(EXPECTED[name], rel=1e-12)


@pytest.mark.parametrize("name", list(DECLARED))
def test_nothing_on_an_empty_base(name):
    if name.startswith("owner."):
        # the owner compiled nothing, or no module asked the cache
        w = a_window(BEFORE, AFTER, owner(requests=0, hits=0))
        got = read(name, w)
        assert got == (21.75 if name == "owner.compile_s" else None)
        return
    if name in ("store.put_ms_per_new_file", "store.put_accounted_pct"):
        # no job returned in the window: no file linked, no whole
        assert read(name, a_window(AFTER, AFTER, owner())) is None
    # nothing acked in the window: no GiB to divide by
    if name.endswith("_per_gib"):
        assert read(name, a_window(BEFORE, AFTER, owner(), gib=0)) is None


def test_accounted_share_falls_with_a_phase_the_clock_lacks():
    after = [json.loads(json.dumps(n)) for n in AFTER]
    for n in after:
        n["durability"]["put"]["jobS"] *= 1.25     # seconds in no phase
    got = read("store.put_accounted_pct", a_window(BEFORE, after, owner()))
    # 3 nodes at 1.25 x 165 s, less the 165 s before the window
    assert got == pytest.approx(100.0 * 330.0 / (3 * 206.25 - 165.0))


@pytest.mark.parametrize("name", list(DECLARED))
def test_declared_with_its_file_and_its_four_cells(name):
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == name]
    unit, better, layer, moves = DECLARED[name]
    assert m == {"name": name, "unit": unit, "better": better,
                 "source": "program_counter", "layer": layer,
                 "moves": moves, "workloads": CELLS}
    assert (BENCH / "layer_metrics" / f"{name}.py").is_file()


def test_appended_after_the_47_in_this_order():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    assert names[47:62] == list(DECLARED)
    assert names[46] == "chain.strong_cut_pct"
