#!/usr/bin/env python3
"""From the owner's profiler trace (``*.xplane.pb``) to numbers: seconds
in which an operation ran on the device (the union of the device ops'
intervals, averaged over the chips traced), the ops that took most time
under the names the trace prints, and the longest idle gaps.

    python benchmarks/reduce_trace.py TRACE.xplane.pb [SPANS.json]

prints one JSON object. Run as a process of its own with
``JAX_PLATFORMS=cpu``: reading a trace needs ``jax.profiler.ProfileData``
and nothing of a backend, and the harness itself stays off JAX.

A chip is a plane named ``/device:...`` that holds op events (the
``/device:CUSTOM:...`` planes of a v5e trace hold none). On it, the line
``XLA Ops`` holds one event per executed op — leaf ops: their union is
when the chip computed. An event is named by its HLO text, of which the
part before `` = `` is kept (``%strip_chunk_states.1``). ``XLA Modules``,
``Steps`` and the name-scope lines hold the same time again as enclosing
intervals, and are used only when there is no op line. Event times are
nanoseconds from the start of the profiling session.

SPANS.json, optional: ``{"origin_ns": n, "spans": [[t0_ns, t1_ns], ...]}``
— the harness's own client spans of uploads in flight, on the session's
clock (origin = the owner's CLOCK_MONOTONIC when the session started).
A gap is labelled ``stream open at the owner`` when an upload was in
flight for most of it and ``no stream open`` otherwise; finer attribution
needs spans inside the program.
"""

from __future__ import annotations

import json
import sys

OP_LINE = "XLA Ops"
ENCLOSING = ("XLA Modules", "Steps", "Framework Name Scope",
             "Framework Ops", "Source code", "XLA TraceMe")


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged copy of ``intervals`` (closed-open, any order)."""
    out: list[tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def covered(merged: list[tuple[float, float]], lo: float, hi: float
            ) -> float:
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def device_events(path: str) -> dict[str, list[tuple[float, float, str]]]:
    """plane name -> (start_ns, end_ns, op name) of its op events."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        use = [lines[OP_LINE]] if OP_LINE in lines else \
            [ln for name, ln in lines.items() if name not in ENCLOSING]
        events = [
            (float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name.split(" = ", 1)[0]) for ln in use for e in ln.events]
        if events:
            out[plane.name] = events
    return out


def reduce(planes: dict[str, list[tuple[float, float, str]]],
           spans: list[tuple[float, float]] | None = None,
           lo_ns: float | None = None, hi_ns: float | None = None) -> dict:
    """``lo_ns``/``hi_ns`` bound the slice on the session's clock (default:
    first event start to last event end)."""
    planes = {name: evs for name, evs in planes.items() if evs}
    events = [e for evs in planes.values() for e in evs]
    if not events:
        return {"planes": [], "events": 0}
    lo = min(e[0] for e in events) if lo_ns is None else lo_ns
    hi = max(e[1] for e in events) if hi_ns is None else hi_ns
    busy, by_name, gaps = [], {}, []
    open_spans = union(spans or [])
    for evs in planes.values():
        merged = union([(a, b) for a, b, _ in evs])
        busy.append(covered(merged, lo, hi))
        for a, b, name in evs:
            by_name[name] = by_name.get(name, 0.0) + (b - a)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                share = covered(open_spans, a, b) / (b - a)
                gaps.append((b - a, "stream open at the owner"
                             if share >= 0.5 else "no stream open"))
    n = len(planes)
    by_label: dict[str, float] = {}
    for ln, label in gaps:
        by_label[label] = by_label.get(label, 0.0) + ln / n
    longest = sorted(gaps, reverse=True)[:4]
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:10]
    return {
        "planes": sorted(planes),
        "events": len(events),
        "busy_s": sum(busy) / n / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[name, s / n / 1e9] for name, s in top],
        "idle_gaps": [[f"all gaps: {label}", s / 1e9]
                      for label, s in sorted(by_label.items())]
        + [[f"longest single gap: {label}", ln / 1e9]
           for ln, label in longest],
    }


def main(argv: list[str]) -> int:
    if not 2 <= len(argv) <= 3:
        print(__doc__, file=sys.stderr)
        return 2
    spans, lo, hi = None, None, None
    if len(argv) == 3:
        with open(argv[2]) as f:
            doc = json.load(f)
        origin = doc["origin_ns"]
        spans = [(a - origin, b - origin) for a, b in doc["spans"]]
        lo, hi = 0.0, float(doc["stop_ns"] - origin)
    print(json.dumps(reduce(device_events(argv[1]), spans, lo, hi)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
