"""What one measured window leaves behind, as the metric readers see it.

A metric is one small file — ``end_to_end/<name>.py`` or
``layer_metrics/<name>.py`` — with one function ``read(w)`` that takes
this object and returns a number, or None when there is nothing to read
(the harness then leaves the metric out of the line). A later PR adds a
metric by adding such a file and its entry in ``BENCHMARK.json``.
"""

from __future__ import annotations

import importlib.util
import math
import re
from dataclasses import dataclass
from pathlib import Path

HERE = Path(__file__).resolve().parent
MIB = 1024 * 1024
GIB = 1024 * MIB

_PROM_LINE = re.compile(r'^([A-Za-z_:][\w:]*)(\{[^}]*\})?\s+(\S+)')


def parse_prom(page: str) -> dict[str, float]:
    """``name{labels}`` -> value, for every sample line of a Prometheus /
    OpenMetrics page (exemplar suffixes and comments dropped)."""
    out = {}
    for line in page.splitlines():
        if line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        if m:
            try:
                out[m.group(1) + (m.group(2) or "")] = float(m.group(3))
            except ValueError:
                pass
    return out


def percentile(values: list[float], q: float) -> float:
    """The smallest sample with at least ``q`` of the samples at or
    below it (nearest rank: no interpolation between two requests)."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


@dataclass
class Window:
    seconds: float                    # length of the measured window
    t_open: float                     # its ends on time.monotonic()
    t_close: float
    setup_s: float                    # process start -> first run op done
    ops: list                         # Op records acked or failed inside it
    session_ops: list                 # every op of the run, all phases
    stores: object                    # check.Stores: the nodes' disks, drained
    manifests: dict                   # id -> chunk rows, every acked upload
    nodes_before: list[dict]          # each node's /metrics JSON at t0
    nodes_after: list[dict]
    prom_before: list[dict]           # ... and its Prometheus page, parsed
    prom_after: list[dict]
    owner_before: dict                # the owner's Health at t0
    owner_after: dict
    config: dict
    traffic: dict
    device_kind: str
    trace: dict | None = None         # reduce_trace's output, traced runs
    trace_regions: int = 0            # regions dispatched inside the slice

    # -- what the run left in the stores ---------------------------------
    def digests(self, ops: list) -> set[str]:
        """Every chunk the manifests of these acked uploads name."""
        return {c["digest"] for o in ops
                for c in self.manifests.get(o.file_id, [])}

    def bytes_on_disk(self, digests: set[str]) -> int:
        """Bytes of these chunks' files, every node's copy counted."""
        return self.stores.bytes_on_disk(digests)

    # -- the client's side ---------------------------------------------
    def acked(self, kind: str) -> list:
        return [o for o in self.ops if o.kind == kind and o.acked]

    def acked_bytes(self, kind: str) -> int:
        return sum(o.nbytes for o in self.acked(kind))

    # -- the nodes' own counters, as deltas over the window ------------
    @staticmethod
    def _dig(doc: dict, path: tuple) -> float:
        for key in path:
            doc = doc.get(key, {}) if isinstance(doc, dict) else {}
        return float(doc) if isinstance(doc, (int, float)) else 0.0

    def node_delta(self, *path: str) -> float:
        """Sum over the nodes of a counter's growth in the window (a key
        a node has not set yet counts as 0)."""
        return sum(self._dig(a, path) - self._dig(b, path)
                   for b, a in zip(self.nodes_before, self.nodes_after))

    def prom_delta(self, sample: str) -> float:
        return sum(a.get(sample, 0.0) - b.get(sample, 0.0)
                   for b, a in zip(self.prom_before, self.prom_after))

    def owner_regions(self) -> float:
        return float(self.owner_after["device"]["regions"]
                     - self.owner_before["device"]["regions"])

    def loop_lag_max_s(self) -> float:
        """The worst event-loop lag any node's sentinel sampled in its
        last 60 s (``obs.sentinel.recentMaxLagS``), read when the window
        closes — ``maxLagS`` is a lifetime maximum and cannot be
        windowed. A traffic file keeps ``warm_s`` plus the window over
        60 s, so the look-back never reaches into set-up."""
        return max(self._dig(a, ("obs", "sentinel", "recentMaxLagS"))
                   for a in self.nodes_after)

    # -- the owner's trace of one slice ----------------------------------
    def busy_s_per_region(self) -> float | None:
        if not self.trace or not self.trace.get("busy_s") \
                or not self.trace_regions:
            return None
        return self.trace["busy_s"] / self.trace_regions

    def device_idle_pct(self) -> float | None:
        """Share of the traced slice in which no op ran on the chip."""
        if not self.trace or not self.trace.get("busy_s"):
            return None
        return 100.0 * (1.0 - self.trace["busy_s"] / self.trace["window_s"])

    def per_gib_put(self, amount: float) -> float | None:
        gib = self.acked_bytes("put") / GIB
        return amount / gib if gib > 0 else None


def load_by_name(folder: str, name: str):
    """The module ``<folder>/<name>.py`` — how the harness finds a
    generator or a metric's reader from the name in the data."""
    path = HERE / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(
            f"{name!r} has no file at {path.relative_to(HERE)}")
    spec = importlib.util.spec_from_file_location(
        re.sub(r"\W", "_", f"{folder}_{name}"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
