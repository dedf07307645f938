#!/usr/bin/env python3
"""How many regions the device ran inside a traced slice, counted from
the trace itself: the events of one op that runs once a region, on the
``XLA Ops`` line, that START inside the slice — the bounds
``reduce_trace.py`` takes ``busy_s`` over, so busy time and regions are
of the same interval.

    python benchmarks/trace_regions.py TRACE.xplane.pb SPANS.json OP_PREFIX

prints ``{"regions": n}`` (the mean over the chips traced). Why not the
owner's ``regions`` counter: ``run.py`` reads it after the profiler's
stop has answered, and where the device runs hundreds of small regions
a second the stop takes tens of seconds (38 s for 284 592 events, my
chip run, PR 41), so the counter has moved on eightfold. Run as a
process of its own with ``JAX_PLATFORMS=cpu``, as ``reduce_trace.py``
is: the harness stays off JAX.
"""

from __future__ import annotations

import json
import sys

from reduce_trace import device_events


def count(planes: dict[str, list[tuple[float, float, str]]], prefix: str,
          lo_ns: float, hi_ns: float) -> float:
    per_chip = [sum(1 for a, _, name in evs
                    if name.startswith(prefix) and lo_ns <= a < hi_ns)
                for evs in planes.values() if evs]
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def main(argv: list[str]) -> int:
    if len(argv) != 4:
        print(__doc__, file=sys.stderr)
        return 2
    with open(argv[2]) as f:
        doc = json.load(f)
    hi = float(doc["stop_ns"] - doc["origin_ns"])
    print(json.dumps({"regions": count(device_events(argv[1]), argv[3],
                                       0.0, hi)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
