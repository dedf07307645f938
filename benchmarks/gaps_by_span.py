#!/usr/bin/env python3
"""Which program span owned each idle gap of the device: the owner's
profiler trace of a few seconds of a cell's traffic, laid beside the
spans the program itself recorded in those seconds.

    python3 benchmarks/gaps_by_span.py --workload CELL --seed N [--seconds S]

Run by hand, never by ``run.py``. It starts the cell's deployment as
``run.py`` does (``cluster.py``, ``owner.py``), lets the cell's clients
run, has the owner trace ``S`` seconds of that, and then asks the
program what it was doing: the owner's ``Trace`` method for the spans of
the slice's interval, and a node's ``/trace`` for the stitched tree of
every upload those name. A span carries ``m0``, CLOCK_MONOTONIC at its
open. The trace's events are nanoseconds from the session's start,
which the trace itself states on the wall clock (``profile_start_time``
of its ``Task Environment`` plane); the owner's control answers carry
its wall clock and its CLOCK_MONOTONIC read together, which turns that
start into the spans' clock. (``start_trace`` returns some hundred ms
after the session began, so the clock read after it — what ``run.py``
labels its client spans against — is too late for spans of a few ms.)
A span and a device op then share one axis; as a check the report says
how much of the device's busy time lies inside an ``owner.*`` span.

For every gap of the device plane (the complement of the union of its
op events; ``reduce_trace.device_events`` and ``union``) at least
``--min-gap-ms`` long it prints the owner's phase — ``dispatch`` or
``collect`` where such a span covers most of it, ``between windows``
(a stream is open and the owner waits for input or for its reply to be
taken), ``no stream open`` — and the spans, by name, that were open for
at least half of it. Shorter gaps (between the ops of one region's
chain) are summed into one line.

``--rehearse-cpu``: the same on the CPU at the traffic's rehearsal
sizes; the trace then holds no device plane, the whole slice is one
gap, and the labels still show. Never a measurement.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cluster  # noqa: E402
import data  # noqa: E402
import run  # noqa: E402
from ops import Api, OpLog  # noqa: E402
from reduce_trace import covered, union  # noqa: E402
from window import load_by_name  # noqa: E402

LOOK_BACK_NS = 120 * 10**9    # an upload in flight during the slice
# opened its stream at the owner at most this long before it
PHASE_SPANS = {"dispatch": ("owner.dispatch",),
               "collect": ("owner.collect",)}
OWNER_SPANS = ("owner.stream", "owner.dispatch", "owner.collect")


# -- attribution: pure, on the session's clock (ns from its start) ------------

def by_name(spans: list[dict], origin_ns: int) -> dict[str, list]:
    """span name -> merged intervals on the session's clock."""
    out: dict[str, list] = {}
    for sp in spans:
        lo = sp["m0"] - origin_ns
        out.setdefault(sp["name"], []).append(
            (float(lo), lo + sp["d"] * 1e9))
    return {name: union(ivs) for name, ivs in out.items()}


def gaps_of(events: list[tuple], lo: float, hi: float) -> list[tuple]:
    """The complement of the events' union inside ``[lo, hi]``."""
    merged = union([(max(a, lo), min(b, hi)) for a, b, _ in events
                    if b > lo and a < hi])
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def label(named: dict[str, list], a: float, b: float) -> tuple[str, list]:
    """The owner's phase over ``[a, b]`` and ``[(name, share)]`` of the
    spans open for at least half of it, widest first."""
    ln = b - a
    any_owner = union([iv for n in OWNER_SPANS for iv in named.get(n, [])])
    windows = union([iv for names in PHASE_SPANS.values() for n in names
                     for iv in named.get(n, [])])
    shares = {phase: covered(union([iv for n in names
                                    for iv in named.get(n, [])]), a, b)
              for phase, names in PHASE_SPANS.items()}
    shares["between windows"] = covered(any_owner, a, b) \
        - covered(windows, a, b)
    shares["no stream open"] = ln - covered(any_owner, a, b)
    phase = max(shares, key=lambda k: shares[k])
    over = sorted(((name, covered(ivs, a, b) / ln)
                   for name, ivs in named.items()),
                  key=lambda kv: (-kv[1], kv[0]))
    return phase, [(n, s) for n, s in over if s >= 0.5]


def attribute(planes: dict, spans: list[dict], origin_ns: int,
              stop_ns: int, min_gap_ns: float) -> dict:
    """Every gap of every device plane, labelled. With no device plane
    (a CPU rehearsal) the slice is one gap."""
    hi = float(stop_ns - origin_ns)
    named = by_name(spans, origin_ns)
    owner = union([iv for n in OWNER_SPANS for iv in named.get(n, [])])
    rows, short_n, short_ns, by_phase = [], 0, 0.0, {}
    busy = inside = 0.0
    for plane, events in sorted(planes.items()) or [("(no device plane)", [])]:
        for a, b in union([(a, b) for a, b, _ in events]):
            busy += b - a
            inside += covered(owner, a, b)
        for a, b in gaps_of(events, 0.0, hi):
            if b - a < min_gap_ns:
                short_n, short_ns = short_n + 1, short_ns + (b - a)
                continue
            phase, over = label(named, a, b)
            by_phase[phase] = by_phase.get(phase, 0.0) + (b - a)
            rows.append({"plane": plane, "start_s": a / 1e9,
                         "seconds": (b - a) / 1e9, "phase": phase,
                         "open": [[n, round(s, 3)] for n, s in over]})
    rows.sort(key=lambda r: -r["seconds"])
    return {"slice_s": hi / 1e9, "gaps": rows, "busy_s": busy / 1e9,
            "busy_inside_owner_span_pct":
                100.0 * inside / busy if busy else None,
            "short_gaps": short_n, "short_gaps_s": short_ns / 1e9,
            "by_phase_s": {k: v / 1e9 for k, v in sorted(by_phase.items())}}


def render(report: dict) -> str:
    inside = report["busy_inside_owner_span_pct"]
    out = [f"slice {report['slice_s']:.3f} s, device busy "
           f"{report['busy_s'] * 1e3:.3f} ms"
           + ("" if inside is None else
              f" ({inside:.1f} % of it inside an owner.* span)")
           + "; idle by owner phase: "
           + "; ".join(f"{k} {v:.3f} s"
                       for k, v in report["by_phase_s"].items())]
    for r in report["gaps"]:
        spans = ", ".join(f"{n} {100 * s:.0f}%" for n, s in r["open"]) \
            or "(no span open for half of it)"
        out.append(f"gap +{r['start_s']:.4f}s {r['seconds'] * 1e3:10.3f} ms"
                   f"  [{r['phase']}]  {spans}")
    out.append(f"{report['short_gaps']} shorter gaps, "
               f"{report['short_gaps_s'] * 1e3:.3f} ms together")
    return "\n".join(out)


# -- the run -----------------------------------------------------------------

def owner_trace(port: int, since_ns: int, until_ns: int) -> list[dict]:
    """The owner's ``Trace`` over its documented wire, as
    ``cluster.OwnerHealth`` asks ``Health``."""
    import grpc

    with grpc.insecure_channel(f"127.0.0.1:{port}") as channel:
        call = channel.unary_unary(
            "/dfs.Sidecar/Trace", request_serializer=lambda b: b,
            response_deserializer=lambda b: b)
        return json.loads(call(json.dumps(
            {"sinceMonoNs": since_ns, "untilMonoNs": until_ns}).encode(),
            timeout=30.0))["spans"]


def stitched(port: int, trace_id: str) -> list[dict]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30.0)
    try:
        conn.request("GET", f"/trace?traceId={trace_id}")
        return json.loads(conn.getresponse().read()).get("spans", [])
    finally:
        conn.close()


def session_origin(xplane: str, stamp: dict) -> int:
    """The profiling session's start on CLOCK_MONOTONIC: the trace's own
    ``profile_start_time`` (wall ns) through the owner's two clocks read
    together in ``stamp``; the stamp's own reading where the trace does
    not say."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(xplane).planes:
        start = dict(plane.stats).get("profile_start_time")
        if start:
            return int(start) - (stamp["wall_ns"] - stamp["mono_ns"])
    return stamp["mono_ns"]


def take_slice(args, children: list, work: Path) -> tuple[Path, list, dict, int]:
    """Deployment up, clients running, ``--seconds`` under the owner's
    profiler; returns the trace, every span of the uploads that touched
    the slice, the owner's clock stamp after ``start_trace`` and the
    slice's end on CLOCK_MONOTONIC."""
    _, cell, config, traffic = run.load_cell(args.workload)
    dep = config["deployment"]
    if args.rehearse_cpu:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    gen = load_by_name("generators", traffic["kind"]).Generator(
        traffic, config, args.seed)
    owner = cluster.Owner(
        dep["owner_args"],
        "cpu" if args.rehearse_cpu else dep["owner_platform"],
        work, children)
    owner.wait_listening(600)
    ports = cluster.start_nodes(dep, owner.port, work / "data", work,
                                children)
    api = Api(ports, 1100.0, OpLog())
    for n, size in enumerate(gen.warm_sizes):
        body = data.fresh(args.seed, 9, n, size)
        op = api.put(0, 0, ("warm", n), body, data.sha256_hex(body),
                     block=int(traffic.get("block_bytes", 0)))
        if not op.acked:
            raise cluster.BenchFailure(
                f"warm-up upload failed: {op.status} {op.error}")
    api.timeout_s = float(traffic["op_timeout_s"])
    stop = threading.Event()
    clients = [threading.Thread(target=gen.run_client, args=(c, api, stop),
                                daemon=True) for c in range(gen.clients)]
    for t in clients:
        t.start()
    try:
        time.sleep(float(traffic["warm_s"]))
        started = owner.answer(
            owner.send("trace_start", str(work / "trace")), 60)
        time.sleep(args.seconds)
        stopped = owner.answer(owner.send("trace_stop"), 300)
    finally:
        stop.set()
    for t in clients:
        t.join(float(traffic["drain_s"]))
    stamp, end = started["after"], stopped["before"]["mono_ns"]
    owned = owner_trace(owner.port, stamp["mono_ns"] - LOOK_BACK_NS, end)
    spans = {sp["s"]: sp for sp in owned}
    for tid in sorted({sp["t"] for sp in owned}):
        for sp in stitched(ports[0], tid):
            spans.setdefault(sp["s"], sp)
    found = sorted((work / "trace").rglob("*.xplane.pb"))
    if not found:
        raise cluster.BenchFailure("the owner's profiler wrote no .xplane.pb")
    run.say(f"{len(owned)} owner spans, {len(spans)} spans in all, "
            f"{sum(o.acked for o in api.log.snapshot())} uploads acked")
    return found[0], list(spans.values()), stamp, end


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="tarball.ingest-fresh")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--min-gap-ms", type=float, default=1.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--json", default="",
                    help="also write the report as JSON to this file")
    args = ap.parse_args(argv)
    children: list = []
    work = Path(tempfile.mkdtemp(prefix="dfs_gaps_"))
    try:
        xplane, spans, stamp, end = take_slice(args, children, work)
        for c in reversed(children):
            c.stop()
        # the owner has let go of the chip; reading a trace needs
        # jax.profiler and nothing of a backend
        os.environ["JAX_PLATFORMS"] = "cpu"
        from reduce_trace import device_events

        origin = session_origin(str(xplane), stamp)
        run.say(f"session began {(stamp['mono_ns'] - origin) / 1e6:.1f} ms "
                "before start_trace returned")
        report = attribute(device_events(str(xplane)), spans, origin, end,
                           args.min_gap_ms * 1e6)
    except cluster.BenchFailure as e:
        print(f"[gaps] FAILED: {e}", file=sys.stderr)
        for c in children:
            print(f"--- {c.name} log tail ---\n{c.tail(15)}",
                  file=sys.stderr)
        return 1
    finally:
        for c in reversed(children):
            c.stop()
        shutil.rmtree(work, ignore_errors=True)
    print(render(report), flush=True)
    if args.json:
        Path(args.json).write_text(json.dumps(report))
    if args.rehearse_cpu:
        print("REHEARSAL", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
