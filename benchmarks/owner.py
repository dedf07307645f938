#!/usr/bin/env python3
"""The chip owner as the benchmark starts it: the program's ``sidecar``
command, unchanged, in the main thread — and a control thread beside it,
because only the process that holds the chip can say how much of its
memory was used or trace what ran on it.

    python benchmarks/owner.py sidecar --fragmenter cdc-anchored-tpu ...

Control lines arrive on stdin, one request each; the answer is a JSON
file at the path the line names (written whole, then renamed):

    memstats <out>            device memory as JAX reports it
    trace_start <out> <dir>   jax.profiler.start_trace(dir), device events only
    trace_stop <out>          jax.profiler.stop_trace()

The profiler runs only when the harness asks (``--trace 1``), for one
slice of the window. Answers carry this process's ``time.monotonic_ns``
and ``time.time_ns`` so the trace can be laid beside the harness's own
client spans (CLOCK_MONOTONIC is one clock for every process of a host).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _clock() -> dict:
    return {"mono_ns": time.monotonic_ns(), "wall_ns": time.time_ns()}


def _memstats() -> dict:
    import jax

    devs = jax.devices()
    per_dev = [d.memory_stats() or {} for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "peak_bytes_in_use": max(
                (int(s.get("peak_bytes_in_use", 0)) for s in per_dev),
                default=0),
            "bytes_limit": max(
                (int(s.get("bytes_limit", 0)) for s in per_dev), default=0)}


def _trace_start(log_dir: str) -> dict:
    import jax

    opts = jax.profiler.ProfileOptions()
    # device events only: the host and Python tracers slow every gRPC
    # handler thread of the owner, and the harness labels idle gaps from
    # its own client spans
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    opts.enable_hlo_proto = False
    before = _clock()
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    return {"before": before, "after": _clock()}


def _trace_stop() -> dict:
    import jax

    before = _clock()
    jax.profiler.stop_trace()
    return {"before": before, "after": _clock()}


_VERBS = {"memstats": _memstats, "trace_start": _trace_start,
          "trace_stop": _trace_stop}


def control_loop(lines) -> None:
    for line in lines:
        parts = line.split()
        if len(parts) < 2 or parts[0] not in _VERBS:
            continue
        out = Path(parts[1])
        try:
            answer = _VERBS[parts[0]](*parts[2:])
        except Exception as e:   # the boundary: report, keep serving
            answer = {"error": f"{type(e).__name__}: {e}"}
        tmp = out.with_suffix(".tmp")
        tmp.write_text(json.dumps(answer))
        os.replace(tmp, out)


def main() -> int:
    sys.path.insert(0, str(REPO))
    from dfs_tpu.cli.main import main as cli_main

    threading.Thread(target=control_loop, args=(sys.stdin,),
                     daemon=True, name="bench-control").start()
    return cli_main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
