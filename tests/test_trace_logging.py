"""Unit coverage for utils/trace.py and utils/logging.py (none existed
before round 9): histogram bucket edges, quantile correctness against a
reference implementation, concurrent record() safety, the cardinality
guards, and the logger / Counters / Stopwatches plumbing. Spans are
dfs_tpu.obs's (tests/test_obs.py, tests/test_span_totals.py)."""

import logging
import math
import threading

import pytest

from dfs_tpu.utils.logging import Counters, Stopwatches, get_logger
from dfs_tpu.utils.trace import BUCKET_BOUNDS, LatencyRecorder


# --------------------------------------------------------------------- #
# LatencyRecorder: buckets, quantiles, concurrency, cardinality
# --------------------------------------------------------------------- #

def test_bucket_edges():
    """Bucket i covers (_BOUNDS[i-1], _BOUNDS[i]] — a sample exactly on
    a bound lands in that bucket; past the last bound -> overflow."""
    r = LatencyRecorder()
    r.record("x", BUCKET_BOUNDS[0])          # exactly the first bound
    r.record("x", BUCKET_BOUNDS[0] * 1.001)  # just past it
    r.record("x", BUCKET_BOUNDS[-1] * 4)     # beyond every bound
    h, count, total = r.histogram_snapshot()["x"]
    assert len(h) == len(BUCKET_BOUNDS) + 1
    assert h[0] == 1          # on-the-bound sample
    assert h[1] == 1          # just past it
    assert h[-1] == 1         # overflow bucket
    assert count == 3 == sum(h)
    assert total == pytest.approx(
        BUCKET_BOUNDS[0] * 2.001 + BUCKET_BOUNDS[-1] * 4)


def _ref_quantile(samples, q):
    s = sorted(samples)
    return s[max(0, math.ceil(q * len(s)) - 1)]


@pytest.mark.parametrize("dist", ["uniform", "bimodal", "heavy_tail"])
def test_quantiles_against_reference(dist):
    """The bucketed estimate must land within one log2 bucket (factor
    sqrt(2) around the geometric midpoint -> factor 2 overall) of the
    exact sample quantile — the upper-bound bug this replaced was out
    by up to 2x SYSTEMATICALLY (always high)."""
    import random

    rnd = random.Random(42)
    if dist == "uniform":
        samples = [rnd.uniform(1e-4, 1e-1) for _ in range(5000)]
    elif dist == "bimodal":
        samples = [rnd.uniform(1e-5, 2e-5) for _ in range(2500)] \
            + [rnd.uniform(0.5, 1.0) for _ in range(2500)]
    else:
        samples = [1e-4 * (1.0 / (1.0 - rnd.random())) ** 1.5
                   for _ in range(5000)]
    r = LatencyRecorder()
    for s in samples:
        r.record("x", s)
    snap = r.snapshot()["x"]
    for q, key in ((0.5, "p50_s"), (0.9, "p90_s"), (0.99, "p99_s")):
        ref = _ref_quantile(samples, q)
        got = snap[key]
        assert got <= ref * 2.0 + 1e-12, f"{key} over-reports: {got} vs {ref}"
        assert got >= ref / 2.0 - 1e-12, f"{key} under-reports: {got} vs {ref}"
    assert snap["max_s"] == pytest.approx(max(samples), abs=1e-6)
    # quantile estimates never exceed the observed max
    assert snap["p99_s"] <= snap["max_s"] + 1e-12


def test_quantile_midpoint_not_upper_bound():
    """A single sample mid-bucket must NOT report the bucket's upper
    bound (the pre-r09 bug: up to 2x over-report)."""
    r = LatencyRecorder()
    val = 10e-6                      # in the (7.6, 15.3] µs bucket
    r.record("x", val)
    p50 = r.snapshot()["x"]["p50_s"]
    upper = next(b for b in BUCKET_BOUNDS if b >= val)
    assert p50 < upper               # strictly below the upper bound
    assert p50 == pytest.approx(val, rel=0.45)   # within the bucket


def test_empty_recorder_snapshot():
    assert LatencyRecorder().snapshot() == {}
    assert LatencyRecorder()._quantile([0] * 29, 0.5, 0) == 0.0


def test_concurrent_record_is_safe():
    r = LatencyRecorder()
    n_threads, per = 8, 2000

    def work(i):
        for k in range(per):
            r.record(f"name{k % 4}", 1e-5 * (i + 1))

    ts = [threading.Thread(target=work, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = r.snapshot()
    assert sum(v["count"] for v in snap.values()) == n_threads * per
    for _, (h, count, _total) in r.histogram_snapshot().items():
        assert sum(h) == count


def test_latency_cardinality_guard():
    r = LatencyRecorder()
    for i in range(r._MAX_NAMES + 40):
        r.record(f"n{i}", 0.001)
    snap = r.snapshot()
    assert len(snap) == r._MAX_NAMES + 1
    assert snap["_overflow"]["count"] == 40
    # an EXISTING name keeps recording normally after the cap is hit
    r.record("n0", 0.001)
    assert r.snapshot()["n0"]["count"] == 2


# --------------------------------------------------------------------- #
# utils/logging.py: logger plumbing, Counters, Stopwatches
# --------------------------------------------------------------------- #

def test_get_logger_namespacing_and_single_handler():
    a = get_logger("node", node_id=3)
    b = get_logger("api")
    assert a.name == "dfs_tpu.node.node3"
    assert b.name == "dfs_tpu.api"
    root = logging.getLogger("dfs_tpu")
    n = len(root.handlers)
    get_logger("node", node_id=4)     # must not stack another handler
    assert len(root.handlers) == n
    assert root.propagate is False


def test_counters_basics_and_snapshot_isolation():
    c = Counters()
    c.inc("a")
    c.inc("a", 4)
    snap = c.snapshot()
    assert snap["a"] == 5
    snap["a"] = 99                    # snapshot is a copy
    assert c.snapshot()["a"] == 5


def test_counters_cardinality_guard():
    c = Counters()
    for i in range(c._MAX_NAMES + 25):
        c.inc(f"k{i}")
    snap = c.snapshot()
    assert len(snap) == c._MAX_NAMES + 1
    assert snap["_overflow"] == 25
    c.inc("k0", 10)                   # existing names unaffected
    assert c.snapshot()["k0"] == 11


def test_counters_concurrent_inc():
    c = Counters()
    per = 5000

    def work():
        for _ in range(per):
            c.inc("n")

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.snapshot()["n"] == 8 * per


def test_stopwatches_accumulate_and_peak():
    s = Stopwatches()
    s.add("x", 0.5)
    s.add("x", 0.25)
    s.peak("depth", 3)
    s.peak("depth", 2)                # lower value must not regress it
    snap = s.snapshot()
    assert snap["x"] == pytest.approx(0.75)
    assert snap["depthPeak"] == 3
