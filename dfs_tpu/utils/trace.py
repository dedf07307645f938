"""Latency histograms (SURVEY.md §5.1 — absent in the reference, which has
only printf logging).

:class:`LatencyRecorder` — lock-protected streaming histograms (log2
buckets) for request/phase latencies; snapshots expose count/p50/p90/p99/max
per name, served by the node's ``/metrics`` endpoint. Spans live in
:mod:`dfs_tpu.obs`, which records into one of these.
"""

from __future__ import annotations

import bisect
import math
import threading
import time

from dfs_tpu.utils.logging import capped_key

# bucket upper bounds in seconds: 1us .. ~134s, powers of two. Bucket i
# covers (_BOUNDS[i-1], _BOUNDS[i]]; one overflow bucket sits past the
# last bound. Exported (read-only by convention) for the Prometheus
# exposition, which emits the raw buckets rather than quantiles.
_BOUNDS = [2.0 ** e for e in range(-20, 8)]
BUCKET_BOUNDS = tuple(_BOUNDS)


class LatencyRecorder:
    # distinct metric names this registry will hold; further names fold
    # into "_overflow" (logged once) so peer-derived or per-digest names
    # can never grow /metrics unboundedly
    _MAX_NAMES = 512

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._hist: dict[str, list[int]] = {}
        self._stats: dict[str, tuple[int, float, float]] = {}  # n, sum, max
        # name -> {bucket index -> (trace_id, observed seconds, wall ts)}:
        # the LAST traced observation that landed in each bucket — the
        # OpenMetrics exemplar convention linking a histogram bucket to
        # the one concrete request that produced it. Bounded by
        # construction: <= _MAX_NAMES names x (len(_BOUNDS)+1) buckets.
        self._ex: dict[str, dict[int, tuple[str, float, float]]] = {}
        self._overflow_warned = False

    def record(self, name: str, seconds: float,
               exemplar: str | None = None) -> None:
        """Record one observation; ``exemplar`` (a trace id) tags the
        bucket it lands in so the Prometheus exposition can link the
        bucket straight to ``trace <id>``."""
        idx = bisect.bisect_left(_BOUNDS, seconds)
        with self._lock:
            name = capped_key(self._hist, name, self._MAX_NAMES, self,
                              "LatencyRecorder", "_overflow")
            h = self._hist.setdefault(name, [0] * (len(_BOUNDS) + 1))
            h[min(idx, len(_BOUNDS))] += 1
            n, s, mx = self._stats.get(name, (0, 0.0, 0.0))
            self._stats[name] = (n + 1, s + seconds, max(mx, seconds))
            if exemplar is not None:
                self._ex.setdefault(name, {})[min(idx, len(_BOUNDS))] = (
                    exemplar, seconds, time.time())

    def _quantile(self, h: list[int], q: float, total: int) -> float:
        """Bucket-estimated quantile: the GEOMETRIC MIDPOINT of the
        bucket the q-th sample falls in. Returning the bucket's upper
        bound (the behavior until round 9) over-reported every quantile
        by up to 2x — a sample of 10 µs sat in the (7.6, 15.3] µs bucket
        and reported as 15.3. sqrt(lo*hi) is the unbiased point estimate
        under the log2 layout (error <= sqrt(2) either way). ``total``
        is the recorded count — computed ONCE per name by the caller,
        not per quantile."""
        if total == 0:
            return 0.0
        target = math.ceil(q * total)
        seen = 0
        for i, c in enumerate(h):
            seen += c
            if seen >= target:
                if i >= len(_BOUNDS):    # overflow bucket: no upper edge
                    return _BOUNDS[-1] * math.sqrt(2.0)
                lo = _BOUNDS[i - 1] if i > 0 else _BOUNDS[0] / 2.0
                return math.sqrt(lo * _BOUNDS[i])
        return _BOUNDS[-1] * math.sqrt(2.0)

    def snapshot(self) -> dict[str, dict[str, float]]:
        with self._lock:
            out = {}
            for name, h in self._hist.items():
                n, s, mx = self._stats[name]
                # n == sum(h) by construction (both bumped under the
                # lock); the observed max clamps the top-bucket estimate
                out[name] = {
                    "count": n,
                    "mean_s": round(s / n, 6) if n else 0.0,
                    "p50_s": round(min(self._quantile(h, 0.50, n), mx), 6),
                    "p90_s": round(min(self._quantile(h, 0.90, n), mx), 6),
                    "p99_s": round(min(self._quantile(h, 0.99, n), mx), 6),
                    "max_s": round(mx, 6),
                }
            return out

    def histogram_snapshot(self) -> dict[str, tuple[list[int], int, float]]:
        """name -> (bucket counts aligned to BUCKET_BOUNDS plus one
        overflow slot, total count, sum of seconds) — the raw material
        for Prometheus histogram exposition."""
        with self._lock:
            return {name: (list(h), self._stats[name][0],
                           self._stats[name][1])
                    for name, h in self._hist.items()}

    def exemplar_snapshot(self
                          ) -> dict[str, dict[int, tuple[str, float, float]]]:
        """name -> {bucket index -> (trace_id, seconds, wall ts)} — the
        last traced observation per bucket, for OpenMetrics exemplar
        exposition (indices align with histogram_snapshot buckets)."""
        with self._lock:
            return {name: dict(ex) for name, ex in self._ex.items()}
