"""The ``images`` traffic kind (PR 43): what a night IS (the generator's
draws), the plain reference's count against what a rehearsal of
``images.ingest-nightly`` stores, and the readers PR 43 brought on
recorded counters. By hand, as everything here; the generator against
the reference byte for byte, and the count against a long stream
through the owner's windows, also run in tier-1
(``tests/test_long_stream.py``)."""

import json
import math
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import reference_images
import roofline
from window import GIB, MIB, Window, load_by_name

BENCH = Path(__file__).resolve().parent.parent
CELL = "images.ingest-nightly"
TRAFFIC = json.loads((BENCH / "traffic" / "ingest-nightly.json").read_text())
CONFIG = json.loads((BENCH / "configs" / "images-3n-rf2.json").read_text())
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
NEW = ["owner.window_mib", "owner.windows_in_flight",
       "owner.staged_timed_pct", "owner.staging_gibps", "seam.tee_peak_mib",
       "seam.tee_wait_s_per_gib", "ingest.place_window_peak",
       "ingest.manifest_kib_per_object", "chain.window_hbm_roofline"]


def generator(seed: int = 7, **over):
    return load_by_name("generators", "images").Generator(
        {**TRAFFIC, **over}, CONFIG, seed)


def test_a_night_is_32_log_uniform_extents_of_about_18_mib():
    """The draws without the bytes: 400 nights' extents at the full
    size (the generator's own loop, lengths and offsets only)."""
    import data

    lo, hi, size = 65536, 2 << 20, TRAFFIC["object_bytes"]
    assert size == 1 << 29
    lengths, offsets = [], []
    for image in range(3):
        for n in range(1, 134):
            g = data.rng(TRAFFIC["corpus_seed"], 20, image, n)
            for _ in range(TRAFFIC["extents_per_night"]):
                length = int(math.exp(g.uniform(math.log(lo), math.log(hi))))
                offsets.append(int(g.integers(0, size - length + 1)))
                g.bytes(length)
                lengths.append(length)
    lengths = np.array(lengths)
    assert lengths.min() >= lo and lengths.max() <= hi
    # log-uniform: mean (hi - lo) / ln(hi / lo) = 572 KiB; the median
    # the geometric mean, 362 KiB
    assert abs(lengths.mean() / ((hi - lo) / math.log(hi / lo)) - 1) < 0.03
    assert abs(np.median(lengths) / math.sqrt(lo * hi) - 1) < 0.05
    night = lengths.reshape(-1, 32).sum(axis=1)
    assert 17.0 * MIB < night.mean() < 18.8 * MIB       # 3.5 % of 512 MiB
    assert abs(np.mean(offsets) / (size / 2) - 1) < 0.03


def test_the_small_generator_applies_its_nights_in_place():
    gen = generator(**TRAFFIC["rehearsal"])
    base, one, two = (gen.make(("img", k)) for k in (0, 2, 5))
    assert len(base) == len(one) == len(two) == gen.size
    changed = (base != one).mean()
    assert 0.01 < changed < 0.08          # 4 extents of 16-64 KiB in 4 MiB
    assert (one != two).mean() < 0.08 and (base != two).mean() > changed
    # lineages share only the base
    other = gen.make(("img", 1))
    both = (base != one) & (base != other)
    assert both.mean() < 0.002


def test_rehearsal_stores_what_the_reference_counts():
    from test_rehearsal import rehearse

    result = rehearse(CELL, "--trace", "0")
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["stored_ratio"]["value"] \
        == reference_images.stored_ratio_of(
            {**TRAFFIC, **TRAFFIC["rehearsal"]}, 2)


# -- the readers, on recorded counters ---------------------------------------

def _owner(scale: int, new: bool = True) -> dict:
    dev = {"platform": "tpu", "regions": 17 * scale, "bytes": scale << 30}
    if new:
        dev.update({"windows": 17 * scale,
                    "windowBytes": scale * ((16 << 26) + (2 << 20)),
                    "tailWindows": scale, "stagedTimed": 3 * scale,
                    "stagedTimedBytes": 3 * scale << 26,
                    "stagedTimedS": 0.03 * scale,
                    "pendingAtDispatch": 24 * scale,
                    "bufferPeakBytes": 3 << 26})
    return {"device": dev}


def _node(scale: int, new: bool = True) -> dict:
    ingest = {"stalls": {"creditS": 1.0 * scale, "placeWindowPeak": 2},
              "seam": {"handoffs": 10 * scale, "chunks": 1000 * scale}}
    if new:
        ingest["seam"].update({"teePeakBytes": (200 + scale) << 20,
                               "teeWaitS": 0.5 * scale})
        ingest["commit"] = {"manifests": scale,
                            "manifestBytes": 16_000_000 * scale,
                            "manifestChunks": 131_000 * scale}
    return {"ingest": ingest}


def _window(new: bool = True, trace=None) -> Window:
    put = SimpleNamespace(kind="put", acked=True, nbytes=1 << 30)
    return Window(
        seconds=51.0, t_open=0.0, t_close=51.0, setup_s=1.0, ops=[put] * 2,
        session_ops=[], stores=SimpleNamespace(root=Path("/nonexistent/d")),
        manifests={}, nodes_before=[_node(1, new)] * 3,
        nodes_after=[_node(3, new)] * 3, prom_before=[{}] * 3,
        prom_after=[{}] * 3, owner_before=_owner(1, new),
        owner_after=_owner(3, new), config=CONFIG, traffic=TRAFFIC,
        device_kind="TPU v5 lite", trace=trace)


def read(name: str, w) -> float | None:
    return load_by_name("layer_metrics", name).read(w)


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_reads_nothing_on_the_parent(name):
    assert read(name, _window(new=False,
                              trace={"busy_s": 1.0, "window_s": 5.0})) is None


def test_the_new_readers_on_recorded_counters(monkeypatch):
    w = _window()
    assert read("owner.window_mib", w) == pytest.approx(
        (16 * 64 + 2) / 17)                                 # 60.35
    assert read("owner.windows_in_flight", w) == pytest.approx(24 / 17)
    assert read("owner.staged_timed_pct", w) == pytest.approx(100 * 3 / 17)
    assert read("owner.staging_gibps", w) == pytest.approx(
        (3 << 26) / 0.03 / GIB)
    assert read("seam.tee_peak_mib", w) == 203.0
    assert read("seam.tee_wait_s_per_gib", w) == pytest.approx(3 * 1.0 / 2)
    assert read("ingest.place_window_peak", w) == 2.0
    assert read("ingest.manifest_kib_per_object", w) == pytest.approx(
        16_000_000 / 1024)
    # the roofline: untraced nothing; traced, the mean window's floor
    # over the busy time of the regions the TRACE holds
    assert read("chain.window_hbm_roofline", w) is None
    w = _window(trace={"busy_s": 0.25, "window_s": 5.0})
    assert read("chain.window_hbm_roofline", w) is None     # no trace file
    reader = load_by_name("layer_metrics", "chain.window_hbm_roofline")
    monkeypatch.setattr(reader, "slice_regions", lambda w: 10.0)
    want = roofline.hbm_roofline_pct(
        ((16 << 26) + (2 << 20)) / 17, 8192, 0.025, "TPU v5 lite")
    assert reader.read(w) == pytest.approx(want) and 0 < want < 100


def test_the_cell_is_declared_with_its_files_and_lists():
    cells = {c["name"]: c for c in BENCHMARK["workloads"]}
    entry = cells[CELL]
    assert entry == {**entry, "config": "images-3n-rf2",
                     "traffic": "ingest-nightly", "chips": 1}
    assert len(entry["why"]) <= 200
    assert BENCHMARK["workloads"][-1] == entry
    assert BENCHMARK["configs"][-1]["name"] == "images-3n-rf2"
    assert BENCHMARK["configs"][-1]["reduced"] == CONFIG["reduced"] \
        == ["nodes", "object_bytes", "corpus_bytes"]
    per_layer = {m["name"]: m for m in BENCHMARK["per_layer"]}
    assert [m["name"] for m in BENCHMARK["per_layer"][-len(NEW):]] == NEW
    for name in NEW:
        assert per_layer[name]["workloads"][-1] == CELL
        assert per_layer[name]["moves"] == "ingest_mibps"
        assert (BENCH / "layer_metrics" / f"{name}.py").is_file()
    for m in BENCHMARK["end_to_end"]:
        assert CELL in m.get("workloads", [CELL])
    listed = {n for n, m in per_layer.items() if CELL in m["workloads"]}
    # a region there is object_bytes (9x off); the slice's regions come
    # from the owner's counter after the profiler's stop (3-6x too many)
    assert not {"chain.hbm_roofline", "chain.busy_ms_per_region"} & listed
    assert not {n for n in listed if n.startswith(("ec.", "index.",
                                                   "owner.pack"))}
    assert {"ingest.commit_ms_per_object", "ingest.credit_s_per_gib",
            "owner.regions_per_gib", "store.resident_hit_pct"} <= listed
