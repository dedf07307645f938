#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``, on the machine it is
started on:

    python3 benchmarks/run.py --workload CELL --seed N --seconds S --trace 0|1

It starts the cell's deployment (one chip owner and the storage nodes,
through the program's own command line), warms the owner's program for
the cell's object size, preloads, lets the cell's closed-loop clients
run, measures for S seconds on the host's clock, stops the clients,
checks what the system holds against the plain reference and the
configuration's guarantees, stops every child, and prints as the LAST
line of stdout one JSON object with the keys ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (and ``breakdown`` when traced).
With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from the nodes' own counters
and from a profiler trace the owner takes of one slice of the window.

Everything that belongs to one cell is data found by name: the
configuration ``configs/<config>.json``, the traffic
``traffic/<traffic>.json``, its generator ``generators/<kind>.py``, and
one reader per metric under ``end_to_end/`` and ``layer_metrics/``.

This process never touches JAX: the owner holds the chip. No chip, or an
owner on another platform than the configuration states, is a failure —
exit 1 and no result line — never a fallback.

``--rehearse-cpu`` (no part of a measurement): the same run with the
owner on ``JAX_PLATFORMS=cpu`` and the traffic file's ``rehearsal``
sizes. It prints no result line: what would have been one goes to
stderr behind the word ``REHEARSAL-RESULT``, stdout ends with
``REHEARSAL``, and the exit code is 10, so it can never be read as a
chip run.
``--control NAME`` starts the nodes as the configuration's
``controls[NAME]`` says (one stated guarantee broken); ``correct`` has
to come out false.
"""

from __future__ import annotations

import argparse
import json
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

T_PROCESS_START = time.monotonic()

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import check  # noqa: E402
import cluster  # noqa: E402
import data  # noqa: E402
from cluster import REPO, BenchFailure  # noqa: E402
from ops import Api, OpLog  # noqa: E402
from window import (Window, load_by_name, parse_prom,  # noqa: E402
                    percentile)

EXIT_FAIL = 1
EXIT_REHEARSAL = 10
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_PROCESS_START:6.1f}s] {msg}",
          flush=True)


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json "
                           f"(has: {', '.join(sorted(cells))})")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((REPO / entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def metrics_of(bench: dict, group: str, cell: str) -> list[dict]:
    return [m for m in bench[group]
            if cell in m.get("workloads", [cell])]


def snapshot(api: Api, owner) -> tuple[list[dict], list[dict], dict]:
    pages = [api.node_metrics(i) for i in range(len(api.ports))]
    return ([p[0] for p in pages], [parse_prom(p[1]) for p in pages],
            owner.health())


def take_trace(owner, work: Path, t0: float, t_end: float, slice_s: float,
               ) -> tuple[Path, int, int, int]:
    """One slice of the window under the owner's profiler, centred in
    it; held open past ``slice_s`` (never past the window) until the
    owner has dispatched a region, because a slice in which nothing ran
    on the device says nothing about the device."""
    trace_dir = work / "trace"
    time.sleep(max(0.0, t0 + (t_end - t0 - slice_s) / 2 - time.monotonic()))
    regions0 = owner.health()["device"]["regions"]
    started = owner.answer(owner.send("trace_start", str(trace_dir)), 60)
    t_slice = time.monotonic()
    while True:
        time.sleep(0.25)
        now = time.monotonic()
        regions = owner.health()["device"]["regions"] - regions0
        if now >= t_end - 1.0 or (now - t_slice >= slice_s and regions):
            break
    stopped = owner.answer(owner.send("trace_stop"), 300)
    regions = owner.health()["device"]["regions"] - regions0
    found = sorted(trace_dir.rglob("*.xplane.pb"))
    if not found:
        raise BenchFailure("the owner's profiler wrote no .xplane.pb")
    return (found[0], started["after"]["mono_ns"],
            stopped["before"]["mono_ns"], regions)


def put_spans(work: Path, session: list, origin: int, stop: int) -> Path:
    """The harness's own spans of uploads in flight during the slice, on
    the clock the owner stamped the slice with (CLOCK_MONOTONIC)."""
    spans = [[int(o.t0 * 1e9), int(o.t1 * 1e9)] for o in session
             if o.kind == "put" and o.t1 * 1e9 >= origin
             and o.t0 * 1e9 <= stop]
    path = work / "spans.json"
    path.write_text(json.dumps(
        {"origin_ns": origin, "stop_ns": stop, "spans": spans}))
    return path


def reduce_trace(xplane: Path, spans: Path) -> dict:
    env = cluster.child_env("cpu")
    done = subprocess.run(
        [sys.executable, str(HERE / "reduce_trace.py"), str(xplane),
         str(spans)], env=env, capture_output=True, text=True, timeout=300)
    if done.returncode != 0:
        raise BenchFailure("reduce_trace.py failed:\n" + done.stderr[-2000:])
    return json.loads(done.stdout.strip().splitlines()[-1])


def run_cell(args, children: list, work: Path, hooks: dict) -> dict:
    bench, cell, config, traffic = load_cell(args.workload)
    dep = dict(config["deployment"])
    rehearsal = args.rehearse_cpu
    if rehearsal:
        traffic = {**traffic, **traffic.get("rehearsal", {})}
    node_args = dep["node_args"]
    if args.control:
        control = config["controls"][args.control]
        say(f"CONTROL {args.control}: breaks \"{control['breaks']}\"")
        node_args = control["node_args"]
    gen = load_by_name("generators", traffic["kind"]).Generator(
        traffic, config, args.seed)

    # -- set-up: owner, nodes, the owner's program for this size, preload
    owner = cluster.Owner(dep["owner_args"],
                          "cpu" if rehearsal else dep["owner_platform"],
                          work, children)
    owner.wait_listening(600)
    health = owner.health()
    dev = health.get("device") or {}
    say(f"owner up: {health['fragmenter']} on {dev.get('platform')} "
        f"{dev.get('device_kind')} x{dev.get('count')}")
    if not rehearsal and (dev.get("platform") != dep["owner_platform"]
                          or dev.get("count", 0) < cell["chips"]):
        raise BenchFailure(
            f"the owner holds {dev.get('count')} {dev.get('platform')!r} "
            f"device(s); the cell needs {cell['chips']} "
            f"{dep['owner_platform']!r}")
    data_root = work / "data"
    ports = cluster.start_nodes({**dep, "node_args": node_args},
                                owner.port, data_root, work, children)
    log = OpLog()
    api = Api(ports, 1100.0, log)       # a cold compile hides in this op
    api.phase = "warm"
    for n, size in enumerate(gen.warm_sizes):
        body = data.fresh(args.seed, 9, n, size)
        op = api.put(0, 0, ("warm", n), body, data.sha256_hex(body),
                     block=int(traffic.get("block_bytes", 0)))
        if not op.acked:
            raise BenchFailure(f"warm-up upload failed: {op.status} "
                               f"{op.error}")
        say(f"warmed {size} B in {op.ms / 1000:.1f}s")
    api.timeout_s = float(traffic["op_timeout_s"])
    api.phase = "preload"
    gen.preload(api)
    say(f"preloaded {sum(o.phase == 'preload' and o.acked for o in log.snapshot())} objects")

    # -- the warm phase: clients start and run through ------------------
    api.phase = "run"
    stop = threading.Event()
    clients = [threading.Thread(target=gen.run_client, args=(c, api, stop),
                                name=f"client-{c}", daemon=True)
               for c in range(gen.clients)]
    for t in clients:
        t.start()
    while not any(o.phase == "run" for o in log.snapshot()):
        for c in children:
            c.check_alive()
        time.sleep(0.02)
    setup_s = time.monotonic() - T_PROCESS_START
    say(f"first operation done: setup_s = {setup_s:.3f}")
    time.sleep(float(traffic["warm_s"]))

    # -- the window ------------------------------------------------------
    nodes_before, prom_before, owner_before = snapshot(api, owner)
    t0 = time.monotonic()
    t_end = t0 + args.seconds
    traced = None
    if args.trace:
        traced = take_trace(owner, work, t0, t_end,
                            float(traffic["trace_slice_s"]))
    time.sleep(max(0.0, t_end - time.monotonic()))
    t_end = time.monotonic()
    nodes_after, prom_after, owner_after = snapshot(api, owner)
    stop.set()
    say(f"window closed after {t_end - t0:.3f}s; draining")
    deadline = time.monotonic() + float(traffic["drain_s"])
    for t in clients:
        t.join(max(0.1, deadline - time.monotonic()))
    if any(t.is_alive() for t in clients):
        raise BenchFailure("a client did not finish its last operation "
                           f"within {traffic['drain_s']}s of the window")
    for c in children:
        c.check_alive()
    say("every client has stopped")

    # -- after the window: memory, the checks ----------------------------
    mem = owner.answer(owner.send("memstats"), 60)
    session = log.snapshot()
    in_window = [o for o in session
                 if o.phase == "run" and t0 <= o.t1 < t_end]
    if "before_check" in hooks:
        hooks["before_check"](data_root, session)
    t_check = time.monotonic()
    stores = check.Stores(data_root, int(dep["nodes"]))
    api.phase = "check"
    manifests = {}
    for o in session:
        if o.kind == "put" and o.acked:
            op, doc = api.stat(0, o.node, o.file_id)
            if op.acked:
                manifests[o.file_id] = doc.get("chunks", [])
    say(f"stores listed, {len(manifests)} manifests read in "
        f"{time.monotonic() - t_check:.1f}s")
    comparisons = check.run_checks(
        api, gen, config, traffic, args.seed, session, manifests, t_end,
        stores, owner_after, nodes_after, rehearsal)
    for c in comparisons:
        print(c.line(), flush=True)
    say(f"checks took {time.monotonic() - t_check:.1f}s")

    w = Window(
        seconds=t_end - t0, t_open=t0, t_close=t_end, setup_s=setup_s,
        ops=in_window,
        session_ops=[o for o in session if o.phase != "check"],
        stores=stores, manifests=manifests,
        nodes_before=nodes_before,
        nodes_after=nodes_after, prom_before=prom_before,
        prom_after=prom_after, owner_before=owner_before,
        owner_after=owner_after, config=config, traffic=traffic,
        device_kind=str(dev.get("device_kind")))
    device = {"platform": dev.get("platform"),
              "kind": dev.get("device_kind"), "count": dev.get("count"),
              "memory_peak_bytes": mem["peak_bytes_in_use"]}
    result = {"correct": all(c.ok for c in comparisons),
              "attempted": len(in_window),
              "failed": sum(not o.acked for o in in_window),
              "metrics": {}, "device": device}
    if traced:
        xplane, origin, slice_stop, w.trace_regions = traced
        w.trace = reduce_trace(
            xplane, put_spans(work, session, origin, slice_stop))
        if args.keep:
            shutil.copy(xplane, Path(args.keep) / "owner.xplane.pb")
        if w.trace.get("busy_s"):
            device["busy_s"] = w.trace["busy_s"]
            device["window_s"] = w.trace["window_s"]
            result["breakdown"] = {"device_ops": w.trace["device_ops"],
                                   "idle_gaps": w.trace["idle_gaps"]}
        elif not rehearsal:
            raise BenchFailure(
                "the traced slice holds no device operation "
                f"(planes: {w.trace.get('planes')})")
        say(f"trace: {w.trace.get('events', 0)} device events, "
            f"{w.trace_regions} regions in the slice")
    group = "per_layer" if args.trace else "end_to_end"
    folder = "layer_metrics" if args.trace else "end_to_end"
    for m in metrics_of(bench, group, cell["name"]):
        value = load_by_name(folder, m["name"]).read(w)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    by_kind = {k: sorted(o.ms for o in w.acked(k))
               for k in ("put", "get", "stat", "delete")}
    say("window: " + "; ".join(
        f"{k} n={len(v)} median={percentile(v, 0.5):.1f}ms "
        f"p90={percentile(v, 0.9):.1f} p95={percentile(v, 0.95):.1f}"
        for k, v in by_kind.items() if v)
        + f"; failed={result['failed']}")
    for o in [o for o in in_window if not o.acked][:5]:
        say(f"failed: {o.kind} node {o.node + 1} status {o.status} "
            f"{o.error[:120]}")
    return result


def main(argv: list[str] | None = None, hooks: dict | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--control", default="")
    ap.add_argument("--keep", default="",
                    help="directory to copy the children's logs (and the "
                         "trace) into before the work directory is removed")
    args = ap.parse_args(argv)

    children: list = []
    work = Path(tempfile.mkdtemp(prefix="dfs_bench_"))
    if args.keep:
        Path(args.keep).mkdir(parents=True, exist_ok=True)
    result = None
    try:
        result = run_cell(args, children, work, hooks or {})
    except BenchFailure as e:
        print(f"[bench] FAILED: {e}", file=sys.stderr)
        dead = [c for c in children if c.proc.poll() is not None]
        for c in dead or children:
            print(f"--- {c.name} (exit {c.proc.poll()}) log tail ---\n"
                  f"{c.tail(30 if dead else 10)}", file=sys.stderr)
    finally:
        for c in reversed(children):
            c.stop()
        if args.keep:
            for c in children:
                shutil.copy(c.log_path, Path(args.keep) / c.log_path.name)
        shutil.rmtree(work, ignore_errors=True)
    if result is None:
        return EXIT_FAIL
    if tuple(k for k in result if k != "breakdown") != RESULT_KEYS:
        raise RuntimeError(f"result line has keys {list(result)}")
    if args.rehearse_cpu:
        print("REHEARSAL-RESULT " + json.dumps(result), file=sys.stderr,
              flush=True)
        print("REHEARSAL", flush=True)
        return EXIT_REHEARSAL
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
