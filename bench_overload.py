"""Overload-survival acceptance bench -> OVERLOAD_r29.json: the node
survives saturation, compound faults, and a slow replica
(dfs_tpu/serve deadlines+hedging, scripts/chaos_harness.py ProcLoadGen,
docs/serve.md, docs/chaos.md).

Five scripted scenarios, every one against REAL processes:

1. overload     — a 3-process cluster with admission gates ARMED and a
                  default end-to-end deadline, driven at ~5x its
                  measured capacity by the multi-process OPEN-LOOP
                  generator (offered rate never throttles on
                  completions). Gates: the shed curve engages (503s
                  with Retry-After), goodput for ADMITTED requests
                  stays within the SLO, zero acked-write loss +
                  byte-identical reads for every admitted write, the
                  post-storm census converges clean, and a
                  deadline-expired request is PROVABLY never executed
                  server-side (counter-gated: 503 + deadlineShed
                  advances + the downloads counter does not).
2. compound     — partition + disk pressure + SIGKILL in ONE run:
                  node 1 loses its link to node 2, node 3's CAS
                  answers ENOSPC, node 2 is kill -9'd mid-load, then
                  everything heals. Whatever acked survives; census
                  converges clean.
3. ring_partition — a MEMBERSHIP change during a partition (4-process
                  hash-ring cluster): node 1 is one-way partitioned
                  from node 3 while `ring add` brings standby node 4
                  in; the epoch gossips around the cut, load keeps
                  acking, and after heal the cluster converges to the
                  new epoch with a clean census.
4. ec_faults    — EC-striped corpus (k=2) on the 4-member ring; a
                  shard holder is kill -9'd mid-read and every EC file
                  must keep reading back byte-identical THROUGH the
                  outage (parity decode under load, ec_decodes > 0);
                  restart + repair converge the census clean.
5. hedged_reads — one replica made intermittently 250 ms-slow (one
                  of every 7 reads — the GC-pause shape hedging
                  exists for; until PR 29 a timer's 1.2 s pulses, 0.28
                  of the TIME, which OVERLOAD_r18.json records as 21
                  of 200 reads); the SAME fixed read schedule runs with
                  hedging off then on. Gates, all counts: a HELD read
                  took at least half the injected delay; with hedging
                  off more than 1 % of the reads are held (the p99
                  read is one), with it on at most 1 % (the p99 read
                  is not: the tail is cut >= 2x; p99_cut_x is reported
                  beside); total issued fetch RPCs stay <= 1.2x the
                  hedging-off run (budgeted hedges never double load);
                  hedge_fired/hedge_won counters moved.

Usage: python bench_overload.py [--tiny] [--out PATH]
Writes OVERLOAD_r29.json (or --out) and prints it. OVERLOAD_r18.json
is the same bench as recorded before PR 29 (timer-driven pulse, p99
ratio gate); this script no longer produces that scenario.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from scripts.chaos_harness import (ClusterHarness, LoadGen,  # noqa: E402
                                   ProcLoadGen, _sha256_hex, percentile)

ART = "OVERLOAD_r29.json"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _counter(h: ClusterHarness, node: int, key: str) -> int:
    try:
        return int(h.metrics(node).get(key, 0) or 0)
    except Exception:  # noqa: BLE001 — dead node mid-scenario
        return 0


def _shed_total(h: ClusterHarness) -> int:
    return sum(_counter(h, i, "http_shed") for i in range(1, h.n + 1))


def _gate_stats(h: ClusterHarness, node: int, cls: str) -> dict:
    adm = (h.metrics(node).get("serve") or {}).get("admission") or {}
    return adm.get(cls) or {}


def _fetch_rpc_count(h: ClusterHarness, node: int) -> int:
    """Issued chunk-fetch RPCs from one node's client table
    (get_chunk + get_chunks, every peer, retries included)."""
    rc = (h.metrics(node).get("obs") or {}).get("rpcClient") or {}
    total = 0
    for key, row in rc.items():
        if key.endswith(":get_chunk") or key.endswith(":get_chunks"):
            total += row.get("count", 0)
    return total


def _census_gate(rep: dict, require_no_orphans: bool) -> dict:
    out = {"under_replicated": rep.get("underReplicatedTotal", -1),
           "over_replicated": rep.get("overReplicatedTotal", -1),
           "orphaned": rep.get("orphanedTotal", -1),
           "peers_failed": rep.get("peersFailed", -1)}
    out["census_clean"] = (out["under_replicated"] == 0
                          and out["over_replicated"] == 0
                          and out["peers_failed"] == 0
                          and (not require_no_orphans
                               or out["orphaned"] == 0))
    return out


# ------------------------------------------------------------------ #
# scenario 1: genuine overload against armed gates
# ------------------------------------------------------------------ #

def _measure_capacity(h: ClusterHarness, p: dict) -> float:
    """CLOSED-loop capacity probe: N threads upload back-to-back for
    the warm window — a closed loop saturates naturally (each thread
    issues the next op the moment the previous completes), so
    completions/second IS the gated cluster's capacity. An open-loop
    warm phase at a guessed rate cannot measure this: offered below
    capacity just measures the offer (observed live in r18 bring-up —
    a 12/s warm 'measured' 12/s on a cluster that could do 6x that,
    and the '5x overload' never overloaded anything)."""
    done = 0
    lock = threading.Lock()
    stop = time.time() + p["warm_s"]

    def worker(w: int) -> None:
        nonlocal done
        seq = 0
        while time.time() < stop:
            seq += 1
            try:
                status, _ = h.http(
                    1 + (w % h.n), "POST",
                    f"/upload?name=cap{w}_{seq}.bin",
                    body=os.urandom(p["payload"]),
                    timeout=p["op_timeout"])
            except OSError:
                continue
            if status == 201:
                with lock:
                    done += 1

    threads = [threading.Thread(target=worker, args=(w,), daemon=True)
               for w in range(p["capacity_threads"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=p["op_timeout"])
    return max(4.0, done / p["warm_s"])


def scenario_overload(h: ClusterHarness, p: dict) -> dict:
    capacity = _measure_capacity(h, p)
    offered = 5.0 * capacity
    shed0 = _shed_total(h)

    gen = ProcLoadGen(h, p["payload"], rate_per_s=offered,
                      procs=p["procs"], seed=22,
                      op_timeout_s=p["op_timeout"],
                      deadline_s=p["deadline_s"], retry_503=1,
                      max_inflight=p["max_inflight"],
                      workdir=h.workdir / "overload")
    # Retry-After probe: while the storm runs, a side thread hammers
    # until it catches a 503 and keeps its headers — proving the shed
    # path advertises a backoff budget, not just a bare error
    probe: dict = {}

    def probe_503() -> None:
        deadline_t = time.time() + p["overload_s"] + p["drain_s"]
        seq = 0
        while time.time() < deadline_t and "retry_after" not in probe:
            seq += 1
            try:
                status, _, hdrs = h.http_h(
                    1, "POST", f"/upload?name=probe{seq}.bin",
                    body=os.urandom(p["payload"]), timeout=30)
            except OSError:
                continue
            if status == 503:
                probe["retry_after"] = hdrs.get("retry-after")
            time.sleep(0.1)

    pt = threading.Thread(target=probe_503, daemon=True)
    pt.start()
    gen.run_for(p["overload_s"], drain_s=p["drain_s"])
    pt.join(timeout=10)

    sheds = _shed_total(h) - shed0
    s = gen.stats
    up = gen.latency_percentiles("upload")
    down = gen.latency_percentiles("download")
    goodput_p95 = max(up["p95"], down["p95"])

    # the storm is over: let repair/GC converge, then the invariant —
    # every admitted (201-acked) write reads back byte-identical
    rep = h.wait_census_clean(1, timeout=p["converge_s"],
                              require_no_orphans=False)
    verify = gen.verify_all()

    # deadline proof on the now-quiet cluster (counter-gated): a
    # request arriving with an EXPIRED budget must be 503-shed at the
    # gate — deadlineShed advances, the downloads counter does not
    # (the request provably never reached the read path)
    fid = gen.ledger[0]["fileId"] if gen.ledger else None
    dl_before = _counter(h, 1, "downloads")
    ds_before = _gate_stats(h, 1, "download").get("deadlineShed", 0)
    expired_status = None
    if fid is not None:
        expired_status, _, _ = h.http_h(
            1, "GET", f"/download?fileId={fid}",
            headers={"X-Dfs-Deadline": "0.000001"}, timeout=30)
    dl_after = _counter(h, 1, "downloads")
    ds_after = _gate_stats(h, 1, "download").get("deadlineShed", 0)

    out = {
        "capacity_ops_per_s": round(capacity, 1),
        "offered_ops_per_s": round(offered, 1),
        "offered_x_capacity": 5.0,
        "inflight_peak": s.get("inflight_peak", 0),
        "acked": s["acked"],
        "uploads_attempted": s["uploads_attempted"],
        "downloads_ok": s["downloads_ok"],
        "retries_503": s["retries_503"],
        "status_counts": s["status"],
        "sheds_503": sheds,
        "shed_curve_engaged": sheds > 0,
        "retry_after_header": probe.get("retry_after"),
        "retry_after_present": bool(probe.get("retry_after")),
        "deadline_shed_total": sum(
            _gate_stats(h, i, c).get("deadlineShed", 0)
            for i in range(1, h.n + 1)
            for c in ("download", "upload", "internal")),
        "goodput_upload": up, "goodput_download": down,
        "goodput_p95_s": goodput_p95,
        "slo_p95_s": p["slo_p95_s"],
        "goodput_within_slo": 0 < goodput_p95 <= p["slo_p95_s"],
        "verified": verify["ok"], "lost": verify["lost"],
        "zero_acked_loss": not verify["lost"],
        "byte_identical": (s["ack_hash_mismatch"] == 0
                           and s["download_mismatch"] == 0),
        "expired_deadline_status": expired_status,
        "expired_deadline_shed": ds_after - ds_before,
        "expired_deadline_downloads_ran": dl_after - dl_before,
        "deadline_never_executed": (expired_status == 503
                                    and ds_after - ds_before >= 1
                                    and dl_after == dl_before),
    }
    out.update(_census_gate(rep, require_no_orphans=False))
    out["ok"] = bool(out["shed_curve_engaged"]
                     and out["retry_after_present"]
                     and out["goodput_within_slo"]
                     and out["zero_acked_loss"]
                     and out["byte_identical"]
                     and out["deadline_never_executed"]
                     and out["census_clean"]
                     and s["acked"] > 0)
    return out


# ------------------------------------------------------------------ #
# scenario 2: compound faults — partition + disk pressure + SIGKILL
# ------------------------------------------------------------------ #

def scenario_compound(h: ClusterHarness, p: dict) -> dict:
    load = LoadGen(h, p["payload"], rate_per_s=p["rate"], seed=33,
                   upload_nodes=[1, 2], download_nodes=[1, 2],
                   op_timeout_s=p["op_timeout"])
    load.run_for(p["warm_s"])                       # healthy baseline
    # fault 1+2 together: node 1 loses its link TO node 2 (one-way)
    # while node 3's disk goes hard-full — uploads at node 2 keep
    # acking (2 reaches both), node 3 answers 507, node 1 rides handoff
    h.set_chaos(1, partition="2")
    h.set_chaos(3, disk_full=True)
    # 16 payloads' worth: a --tiny body is 1-6 chunks, and one such body
    # in sixteen has no chunk that node 3 owns — it writes nothing and
    # answers 201 with its disk full
    st507, _ = h.http(3, "POST", "/upload?name=full.bin",
                      body=os.urandom(16 * p["payload"]),
                      timeout=p["op_timeout"])
    fault_thread = threading.Thread(
        target=load.run_for, args=(p["fault_s"],), daemon=True)
    fault_thread.start()
    time.sleep(max(1.0, p["fault_s"] / 3))
    # fault 3: SIGKILL node 2 while the partition + disk pressure hold
    h.kill9(2)
    time.sleep(max(1.0, p["fault_s"] / 3))
    doctor = h.doctor(1)
    saw_dead = any(f.get("rule") == "dead_peer"
                   and 2 in (f.get("peers") or [])
                   for f in doctor.get("findings", [])) \
        or doctor.get("peersFailed", 0) >= 1
    fault_thread.join()
    # heal everything: restart the corpse, clear the cut and the disk
    h.restart(2)
    h.set_chaos(1, partition="")
    h.set_chaos(3, disk_full=False)
    load.drain()
    rep = h.wait_census_clean(1, timeout=p["converge_s"],
                              require_no_orphans=False)
    verify = load.verify_all()
    s = load.snapshot()
    out = {
        "acked": s["acked"],
        "uploads_attempted": s["uploads_attempted"],
        "uploads_failed": s["uploads_failed"],
        "status_counts": s["status"],
        "full_node_upload_status": st507,
        "full_node_answers_507": st507 == 507,
        "doctor_saw_dead_peer": saw_dead,
        "verified": verify["ok"], "lost": verify["lost"],
        "zero_acked_loss": not verify["lost"],
        "byte_identical": (s["ack_hash_mismatch"] == 0
                           and s["download_mismatch"] == 0),
    }
    out.update(_census_gate(rep, require_no_orphans=False))
    out["ok"] = bool(out["zero_acked_loss"] and out["byte_identical"]
                     and out["full_node_answers_507"]
                     and out["doctor_saw_dead_peer"]
                     and out["census_clean"] and s["acked"] > 0)
    return out


# ------------------------------------------------------------------ #
# scenario 3: membership change DURING a partition
# ------------------------------------------------------------------ #

def scenario_ring_partition(h: ClusterHarness, p: dict) -> dict:
    load = LoadGen(h, p["payload"], rate_per_s=p["rate"], seed=44,
                   upload_nodes=[1, 2, 3], download_nodes=[1, 2, 3],
                   op_timeout_s=p["op_timeout"])
    load.run_for(p["warm_s"])
    h.set_chaos(1, partition="3")      # one-way: 1 -/-> 3 mid-change
    fault_thread = threading.Thread(
        target=load.run_for, args=(p["fault_s"],), daemon=True)
    fault_thread.start()
    time.sleep(0.5)
    # the membership change lands DURING the cut, on a node that can
    # still reach everyone — the epoch must gossip AROUND the partition
    # (node 1 learns it from 2/4 via epoch-on-RPC even though the push
    # from 2 reaches it directly here; node 3 likewise)
    add = h.ring_post(2, action="add", nodeId=4)
    fault_thread.join()
    h.set_chaos(1, partition="")       # heal
    load.drain()
    h.wait_ring_converged(add["epoch"], timeout=p["converge_s"])
    rep = h.wait_census_clean(1, timeout=p["converge_s"],
                              require_no_orphans=False)
    verify = load.verify_all(nodes=[1, 2, 3])
    s = load.snapshot()
    epochs = {i: h.ring_status(i).get("epoch")
              for i in range(1, h.n + 1)}
    out = {
        "acked": s["acked"],
        "ring_epoch": add["epoch"],
        "epochs_converged": all(e == add["epoch"]
                                for e in epochs.values()),
        "status_counts": s["status"],
        "verified": verify["ok"], "lost": verify["lost"],
        "zero_acked_loss": not verify["lost"],
        "byte_identical": (s["ack_hash_mismatch"] == 0
                           and s["download_mismatch"] == 0),
    }
    out.update(_census_gate(rep, require_no_orphans=False))
    out["ok"] = bool(out["zero_acked_loss"] and out["byte_identical"]
                     and out["epochs_converged"]
                     and out["census_clean"] and s["acked"] > 0)
    return out


# ------------------------------------------------------------------ #
# scenario 4: EC under faults — kill a shard holder mid-read
# ------------------------------------------------------------------ #

def scenario_ec_faults(h: ClusterHarness, p: dict) -> dict:
    # EC corpus (k=2: 2 data + P + Q across the 4 ring members)
    files: list[tuple[str, bytes]] = []
    for i in range(p["ec_files"]):
        data = os.urandom(p["ec_payload"])
        status, body = h.http(1, "POST", f"/upload?name=ec{i}.bin&ec=2",
                              body=data, timeout=p["op_timeout"])
        if status != 201:
            return {"ok": False, "error": f"ec upload {i} -> {status}: "
                                          f"{body[:200]!r}"}
        files.append((json.loads(body)["fileId"], data))

    decode0 = sum(_counter(h, i, "ec_decodes") for i in (1, 2, 4))
    reads = {"ok": 0, "bad": 0, "degraded": 0, "errors": 0}
    stop = threading.Event()

    def read_loop() -> None:
        i = 0
        while not stop.is_set():
            fid, data = files[i % len(files)]
            i += 1
            try:
                status, body = h.http(
                    2, "GET", f"/download?fileId={fid}",
                    timeout=p["op_timeout"])
            except OSError:
                reads["errors"] += 1
                continue
            if status == 200 and _sha256_hex(body) == fid:
                reads["ok"] += 1
            elif status == 200 and len(body) == len(data):
                # full-length body with the wrong bytes: CORRUPTION
                reads["bad"] += 1
            else:
                # error status / truncated stream (a node died mid-
                # body): degraded but honest — the client can tell
                reads["degraded"] += 1

    rt = threading.Thread(target=read_loop, daemon=True)
    rt.start()
    time.sleep(1.0)
    h.kill9(3)                       # a shard holder dies mid-read
    # reconstruction-under-load window: every EC file must read back
    # byte-identical from the survivors (parity decode), repeatedly
    t_end = time.time() + p["fault_s"]
    degraded_ok = True
    for rnd in range(100):
        if time.time() >= t_end and rnd >= 1:
            break
        for fid, data in files:
            status, body = h.http(4, "GET", f"/download?fileId={fid}",
                                  timeout=p["op_timeout"])
            if status != 200 or body != data:
                degraded_ok = False
    stop.set()
    rt.join(timeout=p["op_timeout"])
    decodes = sum(_counter(h, i, "ec_decodes")
                  for i in (1, 2, 4)) - decode0
    h.restart(3)
    rep = h.wait_census_clean(1, timeout=p["converge_s"],
                              require_no_orphans=False)
    out = {
        "ec_files": len(files),
        "degraded_reads_ok": degraded_ok,
        "background_reads": dict(reads),
        "background_read_corruptions": reads["bad"],
        "ec_decodes": decodes,
        "reconstruction_exercised": decodes > 0,
    }
    out.update(_census_gate(rep, require_no_orphans=False))
    out["ok"] = bool(degraded_ok and reads["bad"] == 0
                     and out["reconstruction_exercised"]
                     and out["census_clean"])
    return out


# ------------------------------------------------------------------ #
# scenario 5: hedged reads vs one intermittently slow replica
# ------------------------------------------------------------------ #

def _hedge_read_arm(h: ClusterHarness, files: list[str], p: dict
                    ) -> tuple[list[float], int]:
    """One measurement arm: the fixed read schedule from node 2 while
    node 3 pulses 250 ms of serve delay — for the first
    p["pulse_on_reads"] of every p["pulse_period_reads"] reads. The pulse
    is scheduled by the read count, not by a clock: both arms then slow
    the SAME reads whatever the host's load (a timer thread's 0.34 s
    pulse could miss an arm that read its 48 files in 0.2 s).
    Node 2, not node 1: under the static cyclic placement a 3-node
    rf=2 cluster's fully-remote digests seen from node 1 are exactly
    the {2,3}-owned ones — primary ALWAYS node 2 — so node 1 never
    routes a first fetch at node 3; node 2's remote digests are the
    {3,1}-owned ones, primary node 3, which is the read path a slow
    replica actually hurts. Returns (latencies, fetch RPCs issued by
    node 2)."""
    rpc0 = _fetch_rpc_count(h, 2)
    period, on_reads = p["pulse_period_reads"], p["pulse_on_reads"]
    lat: list[float] = []
    try:
        for _ in range(p["read_rounds"]):
            for fid in files:
                if len(lat) % period in (0, on_reads):
                    h.set_chaos(3, serve_delay_s=p["slow_s"]
                                if len(lat) % period == 0 else 0.0)
                t0 = time.monotonic()
                status, body = h.http(2, "GET",
                                      f"/download?fileId={fid}",
                                      timeout=p["op_timeout"])
                took = time.monotonic() - t0
                if status != 200:
                    raise AssertionError(
                        f"hedge-arm read failed: {status}")
                lat.append(took)
    finally:
        h.set_chaos(3, serve_delay_s=0.0)
    lat.sort()
    return lat, _fetch_rpc_count(h, 2) - rpc0


def scenario_hedged_reads(h: ClusterHarness, p: dict) -> dict:
    # corpus from node 1: rf=2 owners among 3 nodes, so a fixed
    # fraction of every file's chunks reads remotely — and about half
    # of those route to the (pulsing-slow) node 3 first
    files: list[str] = []
    for i in range(p["hedge_files"]):
        data = os.urandom(p["hedge_payload"])
        status, body = h.http(1, "POST", f"/upload?name=h{i}.bin",
                              body=data, timeout=p["op_timeout"])
        if status != 201:
            return {"ok": False, "error": f"corpus upload -> {status}"}
        files.append(json.loads(body)["fileId"])

    # arm A: hedging OFF (the boot default) — the baseline tail + RPCs
    off_lat, off_rpcs = _hedge_read_arm(h, files, p)

    # arm B: same cluster, same data, every node rebooted with the
    # hedge budget armed; same pulse schedule, same read schedule
    for i in range(1, h.n + 1):
        h.restart(i, extra_flags=[
            "--hedge-budget", str(p["hedge_budget"]),
            "--hedge-floor", str(p["hedge_floor"]),
            "--hedge-cap", str(p["hedge_cap"])])
    # node 2 was up while node 3 restarted: a health probe that fell
    # into that gap marked 3 dead, and a read tries believed-alive
    # replicas first — the arm would then never meet the slow replica
    # (hedge_fired 0 beside a perfect p99). Wait for node 2's next
    # probe to see 3 again, not for luck.
    seen_by = time.time() + p["converge_s"]
    while not h.metrics(2).get("peersAlive", {}).get("3"):
        if time.time() > seen_by:
            return {"ok": False, "error": "node 2 never saw node 3 back"}
        time.sleep(0.2)
    on_lat, on_rpcs = _hedge_read_arm(h, files, p)
    hedge = ((h.metrics(2).get("serve") or {}).get("hedge")) or {}

    p99_off = percentile(off_lat, 0.99)
    p99_on = percentile(on_lat, 0.99)
    # a read the slow replica HELD: it took at least half the injected
    # delay (a read cut 2x or better by a hedge is not one)
    held = [sum(t >= p["slow_s"] / 2 for t in lat)
            for lat in (off_lat, on_lat)]
    out = {
        "reads_per_arm": len(off_lat),
        "slow_replica": 3, "slow_s": p["slow_s"],
        # the share of the READS the pulse covers (r18: of the time)
        "pulse_duty": round(p["pulse_on_reads"]
                            / p["pulse_period_reads"], 3),
        "p50_off_s": round(percentile(off_lat, 0.50), 4),
        "p99_off_s": round(p99_off, 4),
        "p50_on_s": round(percentile(on_lat, 0.50), 4),
        "p99_on_s": round(p99_on, 4),
        "p99_cut_x": round(p99_off / p99_on, 2) if p99_on > 0 else 0.0,
        "held_reads_off": held[0], "held_reads_on": held[1],
        "rpcs_off": off_rpcs, "rpcs_on": on_rpcs,
        "rpc_ratio": round(on_rpcs / max(1, off_rpcs), 3),
        "hedge_fired": hedge.get("fired", 0),
        "hedge_won": hedge.get("won", 0),
    }
    # "p99 cut >= 2x" as counts: the slowest 1 % of the reads (rounded
    # up) may be held — with hedging off more are, with it on no more
    tail = -(-len(off_lat) // 100)
    out["ok"] = bool(held[0] > tail >= held[1]
                     and out["rpc_ratio"] <= 1.2
                     and out["hedge_fired"] > 0
                     and out["hedge_won"] > 0)
    return out


# ------------------------------------------------------------------ #
# driver
# ------------------------------------------------------------------ #

def run(tmp: Path, tiny: bool) -> dict:
    p = {
        # overload (gated 3-proc cluster)
        "payload": 24_000 if tiny else 96_000,
        "procs": 3,
        "capacity_threads": 8,
        "warm_s": 4.0 if tiny else 8.0,
        "overload_s": 6.0 if tiny else 15.0,
        "deadline_s": 6.0 if tiny else 8.0,
        "slo_p95_s": 12.0,
        "max_inflight": 1500,
        "drain_s": 12.0 if tiny else 25.0,
        # compound / ring_partition load
        "rate": 4.0 if tiny else 5.0,
        "fault_s": 4.0 if tiny else 10.0,
        "kill_delay_s": 0.25,
        # ec_faults
        "ec_files": 4 if tiny else 8,
        "ec_payload": 40_000 if tiny else 160_000,
        # hedged_reads
        # hedge files sized so EVERY read issues one batch to each
        # remote peer (>= ~8 chunks spread over both owner sets): the
        # fetch-RPC denominator then counts 2 per read and the <= 1.2x
        # budget bound is judged against the true fetch traffic
        "hedge_files": 6 if tiny else 10,
        "hedge_payload": 64_000 if tiny else 128_000,
        "read_rounds": 8 if tiny else 20,
        "slow_s": 0.25,
        # the pulse by READ COUNT. A share of the reads is not a share
        # of the time: the timer this replaces was on 0.28 of the time
        # and, a slow read lasting 25 fast ones, slowed 21 of the 200
        # reads of OVERLOAD_r18.json's hedging-on arm (10.5 %, RPC
        # ratio 1.105). 1 of 7 (14.3 %) is the lightest one-read pulse
        # NOT lighter than that whose period is coprime with both file
        # counts (6, 10: the slow phase walks over every file). 2 of 7
        # by count is 2.7x the recorded load, and since every slowed
        # read costs one hedge the RPC gate then reads 1 + 2/7 by
        # arithmetic (measured --tiny: 1.292, 1.312) on any host.
        "pulse_period_reads": 7,
        "pulse_on_reads": 1,
        "hedge_budget": 50.0,
        "hedge_floor": 0.04,
        "hedge_cap": 0.3,
        "converge_s": 60.0 if tiny else 120.0,
        "op_timeout": 60.0 if tiny else 120.0,
    }
    out: dict = {"metric": "overload_survival", "round": 29,
                 "workload": {"tiny": tiny, **p}, "scenarios": {}}

    def run_one(name, fn, h):
        t0 = time.time()
        res = fn(h, p)
        res["seconds"] = round(time.time() - t0, 1)
        out["scenarios"][name] = res
        log(f"scenario {name}: ok={res.get('ok')} ({res['seconds']}s)")
        if not res.get("ok"):
            log(f"  detail: {json.dumps(res, default=str)[:900]}")

    # cluster A — gates ARMED + default deadline: overload, compound
    h = ClusterHarness(
        3, tmp / "gated", rf=2, repair_interval_s=1.0,
        extra_flags=["--download-slots", "6", "--upload-slots", "4",
                     "--internal-slots", "8", "--queue-depth", "8",
                     "--retry-after", "1",
                     "--default-deadline", str(p["deadline_s"])])
    try:
        h.start_all()
        h.wait_ready()
        run_one("overload", scenario_overload, h)
        run_one("compound", scenario_compound, h)
    finally:
        h.stop_all()

    # cluster B — 4-proc hash ring (members 1-3, node 4 standby):
    # ring_partition brings node 4 in; ec_faults then uses 4 members
    h2 = ClusterHarness(
        4, tmp / "ring", rf=2, repair_interval_s=1.0,
        extra_flags=["--ring-vnodes", "64", "--ring-members", "1,2,3"])
    try:
        h2.start_all()
        h2.wait_ready()
        run_one("ring_partition", scenario_ring_partition, h2)
        run_one("ec_faults", scenario_ec_faults, h2)
    finally:
        h2.stop_all()

    # cluster C — hedged-read measurement (chaos pulses, two arms)
    h3 = ClusterHarness(3, tmp / "hedge", rf=2, repair_interval_s=30.0)
    try:
        h3.start_all()
        h3.wait_ready()
        run_one("hedged_reads", scenario_hedged_reads, h3)
    finally:
        h3.stop_all()

    out["ok"] = all(s.get("ok") for s in out["scenarios"].values())
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tiny", action="store_true",
                    help="tier-1 smoke mode: short windows, small "
                         "payloads — same scenarios, same gates")
    ap.add_argument("--out", default=None,
                    help=f"artifact path (default: {ART} next to this "
                         "script)")
    args = ap.parse_args(argv)
    out_path = Path(args.out) if args.out \
        else Path(__file__).parent / ART
    with tempfile.TemporaryDirectory(prefix="bench_overload_") as tmp:
        out = run(Path(tmp), args.tiny)
    out_path.write_text(json.dumps(out, indent=2) + "\n")
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
