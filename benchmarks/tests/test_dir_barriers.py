"""``store.dir_barriers_per_mib`` (PR 25): nothing on a program without
the counter, a delta per MiB acked on one with it, and declared for the
cell as data."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))

import window  # noqa: E402

NAME = "store.dir_barriers_per_mib"


def a_window(nodes_before, nodes_after):
    put = SimpleNamespace(kind="put", acked=True, nbytes=16 * window.MIB)
    return window.Window(
        seconds=50.0, t_open=0.0, t_close=50.0, setup_s=1.0, ops=[put],
        session_ops=[put], stores=None, manifests={},
        nodes_before=nodes_before, nodes_after=nodes_after,
        prom_before=[], prom_after=[], owner_before={}, owner_after={},
        config={}, traffic={}, device_kind="x")


def test_nothing_on_a_program_without_the_counter():
    older = [{"durability": {"mode": "fsync", "fsyncs": 10}}] * 3
    after = [{"durability": {"mode": "fsync", "fsyncs": 900}}] * 3
    read = window.load_by_name("layer_metrics", NAME).read
    assert read(a_window(older, after)) is None


def test_directory_barriers_per_mib_acked():
    def node(files, dirs):
        return {"durability": {"mode": "fsync", "fsyncs": files,
                               "dirBarriers": dirs}}
    before = [node(100, 40), node(100, 50), {}]       # node 3 not read yet
    after = [node(700, 280), node(650, 290), node(500, 160)]
    read = window.load_by_name("layer_metrics", NAME).read
    assert read(a_window(before, after)) == (240 + 240 + 160) / 16
    files = window.load_by_name("layer_metrics", "store.fsyncs_per_mib").read
    assert files(a_window(before, after)) == (600 + 550 + 500) / 16


def test_declared_for_the_cell():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert m == {"name": NAME, "unit": "1/MiB", "better": "lower",
                 "source": "program_counter", "layer": "chunk store",
                 "moves": "ingest_mibps",
                 "workloads": ["tarball.ingest-fresh"]}
