"""The ``files`` traffic kind (PR 41): what the generator's files ARE,
that ``--seed`` only orders them, the plain reference's bytes against
the generator's, and the reference's count against what a rehearsal of
``smallfiles.ingest-batch`` stores. By hand, as everything here; the
tests without ``rehearsal`` in their name also run in tier-1
(``tests/test_packed_region.py``)."""

import json
from pathlib import Path

import numpy as np
import pytest

import reference_files
from window import load_by_name

BENCH = Path(__file__).resolve().parent.parent
CELL = "smallfiles.ingest-batch"
TRAFFIC = json.loads((BENCH / "traffic" / "ingest-batch.json").read_text())
CONFIG = json.loads(
    (BENCH / "configs" / "smallfiles-3n-rf2.json").read_text())


def generator(seed: int = 7, **over):
    return load_by_name("generators", "files").Generator(
        {**TRAFFIC, **over}, CONFIG, seed)


def test_kinds_come_in_equal_shares_with_the_stated_medians():
    gen = generator()
    drawn = np.array([gen.size_of(k) for k in range(1, 80001, 2)])
    medians = list(TRAFFIC["kinds"].values())
    assert medians == [1024, 8192, 4096, 32768]
    for kind, median in enumerate(medians):
        sizes = drawn[drawn[:, 0] == kind, 1]
        assert abs(len(sizes) / len(drawn) - 0.25) < 0.01
        assert abs(np.median(sizes) / median - 1) < 0.05
        # sigma(ln) = 1.0: the quartiles of ln size lie 0.674 either side
        q1, q3 = np.percentile(np.log(sizes), [25, 75])
        assert abs((q3 - q1) / 1.349 - TRAFFIC["size_sigma"]) < 0.05
    # mean ~18.5 KiB (e^0.5 x the mean median), most bytes in the jpgs
    assert 17500 < drawn[:, 1].mean() < 20500
    assert 0.65 < drawn[drawn[:, 0] == 3, 1].sum() / drawn[:, 1].sum() < 0.78
    assert drawn[:, 1].min() >= 16 and drawn[:, 1].max() <= 2 * 2**20


def test_sizes_are_clipped_at_both_ends():
    gen = generator(size_min_bytes=900, size_max_bytes=40000)
    sizes = np.array([gen.size_of(k)[1] for k in range(1, 8001, 2)])
    assert sizes.min() == 900 and sizes.max() == 40000
    assert (sizes == 900).sum() > 100 and (sizes == 40000).sum() > 100
    assert len(gen.make(("file", 1))) == gen.size_of(1)[1]
    # the traffic file's own clip: 16 B and 2 MiB are inside the draws'
    # reach (a txt 4 sigma down, a jpg 4 sigma up), so both ends bind
    assert TRAFFIC["size_min_bytes"] == 16
    assert TRAFFIC["size_max_bytes"] == 2 * 2**20


def test_half_the_places_resend_a_preload_file_under_a_new_name():
    gen = generator()
    preload = TRAFFIC["preload_objects"]
    again = [gen.resent(k) for k in range(0, 4000)]
    assert all(a is None for a in again[1::2])
    assert all(a is not None and -preload <= a < 0 for a in again[0::2])
    assert len(set(again[0::2])) > 1200        # drawn over all of it
    for k in (0, 2, 3998):
        assert bytes(gen.make(("file", k))) \
            == bytes(gen.make(("file", again[k])))
    assert generator(repeat_share=0).resent(2) is None


def test_seed_orders_the_lead_and_the_slice_and_changes_no_file():
    a, b = generator(seed=2147483659), generator(seed=5)
    lead, ratio = TRAFFIC["lead_objects"], TRAFFIC["ratio_objects"]
    assert (lead, ratio, TRAFFIC["preload_objects"]) == (512, 4096, 2048)
    for gen in (a, b):
        assert sorted(gen.order[:lead]) == list(range(lead))
        assert sorted(gen.order[lead:]) == list(range(lead, lead + ratio))
    assert a.order != b.order
    assert a.order == generator(seed=2147483659).order
    for k in (-1, 0, 1, 513, 4607):
        assert bytes(a.make(("file", k))) == bytes(b.make(("file", k)))
    assert a.clients == 12 and a.nodes == 3 and a.block == 0
    assert a.warm_sizes == [65536]              # the one packed shape


def test_reference_rebuilds_the_generators_bytes():
    """512 files: the preload's end, the lead's start, re-sends among
    them — from a module that shares no code with the generator."""
    gen, ref = generator(), reference_files.Reference(TRAFFIC)
    for k in [*range(-128, 0), *range(0, 384)]:
        assert ref.object(k) == bytes(gen.make(("file", k))), k
    source = (BENCH / "reference_files.py").read_text()
    assert "import data" not in source and "generators" not in source.split(
        '"""', 2)[2]


def test_reference_count_is_the_cpu_engines_over_the_slice():
    small = {**TRAFFIC, **TRAFFIC["rehearsal"]}
    count = reference_files.stored_ratio_of(small, 2)
    gen = generator(**TRAFFIC["rehearsal"])
    assert count == reference_files.stored_ratio_of(
        small, 2, make=lambda k: gen.make(("file", k)))
    assert 0.5 < count < 1.5        # two copies of the half that is new


def test_rehearsal_stores_what_the_reference_counts():
    from test_rehearsal import rehearse

    result = rehearse(CELL, "--trace", "0")
    assert result["correct"] is True and result["failed"] == 0
    assert result["metrics"]["stored_ratio"]["value"] \
        == reference_files.stored_ratio_of(
            {**TRAFFIC, **TRAFFIC["rehearsal"]}, 2)


def test_a_program_with_the_host_cutoff_is_refused_at_once(monkeypatch):
    """The parent of PR 41 chunks every file of this cell on the host
    (``_CPU_CUTOFF``): the generator says so before any process starts,
    so the cell fails cleanly and soon there, with or without
    ``--trace``. The harness stays off JAX while it asks."""
    import subprocess
    import sys

    from cluster import BenchFailure
    from dfs_tpu.fragmenter import cdc_anchored

    generator()                                     # this program: fine
    monkeypatch.setattr(cdc_anchored, "_CPU_CUTOFF", 2 << 20, raising=False)
    with pytest.raises(BenchFailure, match="no device operation"):
        generator()
    asked = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.'); "
         "import dfs_tpu.fragmenter.cdc_anchored; "
         "print('jax' in sys.modules)"],
        cwd=BENCH.parent, capture_output=True, text=True, timeout=120)
    assert asked.stdout.strip() == "False", asked.stderr[-2000:]


def test_the_slices_regions_are_counted_from_the_trace(tmp_path, monkeypatch):
    """``chain.packed_hbm_roofline`` divides the slice's busy time by
    the regions that STARTED in the slice, one SHA strip a region, read
    from the trace — not by the owner's counter, which ``run.py`` reads
    after the profiler's stop answered (8x late in this cell)."""
    from types import SimpleNamespace

    import roofline
    from trace_regions import count

    planes = {"/device:TPU:0": [
        (10.0, 20.0, "%strip_chunk_states.1"), (20.0, 30.0, "%copy.66"),
        (50.0, 60.0, "%strip_chunk_states.1"),
        (500.0, 600.0, "%strip_chunk_states.1")],       # after the stop
        "/device:CUSTOM:0": []}
    assert count(planes, "%strip_chunk_states", 0.0, 100.0) == 2.0
    assert count({}, "%strip_chunk_states", 0.0, 100.0) == 0.0

    reader = load_by_name("layer_metrics", "chain.packed_hbm_roofline")
    work = tmp_path
    (work / "data").mkdir()
    w = SimpleNamespace(
        stores=SimpleNamespace(root=work / "data"),
        trace={"busy_s": 1.0, "window_s": 5.0}, trace_regions=3346,
        owner_before={"device": {"packedBytes": 0, "packedRegions": 0}},
        owner_after={"device": {"packedBytes": 5000 * 33000,
                                "packedRegions": 5000}},
        config=CONFIG, device_kind="TPU v5 lite")
    assert reader.read(w) is None                   # no trace to count in
    monkeypatch.setattr(reader, "slice_regions", lambda w: 430.0)
    want = roofline.hbm_roofline_pct(33000, 8192, 1.0 / 430.0, "TPU v5 lite")
    assert reader.read(w) == pytest.approx(want) and 0 < want < 100
    w.trace = {"planes": [], "events": 0}           # a parent: nothing read
    assert reader.read(w) is None
