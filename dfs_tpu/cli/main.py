"""CLI — interactive menu parity with the reference client plus a scriptable
mode (the reference's pure interactivity is why it has zero automated tests,
SURVEY.md §4).

Interactive menu reproduces Client.java:36-40 exactly:
    0 Exit | 1 Test server | 2 List files | 3 Upload file | 4 Download file

Scriptable subcommands: serve, sidecar, status, list, upload, download,
delete, metrics, trace, events, doctor, census, df, menu.
"""

from __future__ import annotations

import argparse
import asyncio
import sys
from pathlib import Path

from dfs_tpu.cli.client import DEFAULT_TIMEOUT_S, NodeClient
from dfs_tpu.config import (FRAGMENTER_KINDS, CDCParams, CensusConfig,
                            ChaosConfig, ClusterConfig, DurabilityConfig,
                            FragmenterConfig, IndexConfig, IngestConfig,
                            NodeConfig, ObsConfig, RingConfig,
                            ServeConfig, SimConfig, TierConfig)


# Bulk transfers and cluster-wide sweeps take as long as the data held,
# not a round trip: the reference's 5 s client timeout (cli/client.py)
# fits status/list, and failed `census` over 2 GiB on the first chip run.
BULK_TIMEOUT_S = 600.0


def _client(args, bulk: bool = False) -> NodeClient:
    return NodeClient(host=args.host, port=args.port,
                      timeout_s=BULK_TIMEOUT_S if bulk
                      else DEFAULT_TIMEOUT_S)


def _smart_client(args):
    """--smart: build the SDK data-plane client (docs/client.md) from
    the --client-* knobs; everything still degrades to the coordinator
    path unless --client-no-fallback."""
    from dfs_tpu.client import SmartClient
    from dfs_tpu.config import ClientConfig

    cfg = ClientConfig(
        window=args.client_window,
        stripe=args.client_stripe,
        hedge_budget_per_s=args.client_hedge_budget,
        hedge_floor_s=args.client_hedge_floor,
        hedge_cap_s=args.client_hedge_cap,
        filter_max_age_s=args.client_filter_max_age,
        echo_cache_entries=args.client_echo_cache,
        fallback=not args.client_no_fallback,
    )
    return SmartClient(host=args.host, port=args.port, cfg=cfg)


def cmd_serve(args) -> int:
    from dfs_tpu.node.runtime import StorageNodeServer

    if args.cluster_config:
        cluster = ClusterConfig.from_file(args.cluster_config)
        if args.replication_factor is not None:
            print(f"warning: --replication-factor ignored; using "
                  f"{cluster.replication_factor} from {args.cluster_config}",
                  file=sys.stderr)
    else:
        cluster = ClusterConfig.localhost(
            n_nodes=args.nodes, base_port=args.base_port,
            base_internal_port=args.base_internal_port,
            replication_factor=args.replication_factor
            if args.replication_factor is not None else 2)
    cfg = NodeConfig(
        node_id=args.node_id, cluster=cluster,
        data_root=Path(args.data_root), fragmenter=args.fragmenter,
        sidecar_port=args.sidecar_port,
        cdc=CDCParams(min_size=args.min_chunk, avg_size=args.avg_chunk,
                      max_size=args.max_chunk),
        frag=FragmenterConfig(devices=args.cdc_devices,
                              region_bytes=args.cdc_region_bytes,
                              staging_buffers=args.cdc_staging_buffers),
        fixed_parts=args.fixed_parts,
        connect_timeout_s=args.connect_timeout,
        request_timeout_s=args.request_timeout,
        retries=args.rpc_retries,
        health_probe_s=args.probe_interval,
        write_quorum=args.write_quorum,
        serve=ServeConfig(cache_bytes=args.cache_bytes,
                          readahead_batches=args.readahead,
                          download_slots=args.download_slots,
                          upload_slots=args.upload_slots,
                          internal_slots=args.internal_slots,
                          queue_depth=args.queue_depth,
                          retry_after_s=args.retry_after,
                          default_deadline_s=args.default_deadline,
                          hedge_floor_s=args.hedge_floor,
                          hedge_cap_s=args.hedge_cap,
                          hedge_budget_per_s=args.hedge_budget),
        ingest=IngestConfig(window=args.ingest_window,
                            flush_bytes=args.ingest_flush_bytes,
                            credit_bytes=args.ingest_credit_bytes,
                            slice_inflight=args.replicate_inflight,
                            cas_io_threads=args.cas_io_threads),
        obs=ObsConfig(trace_ring=args.trace_ring,
                      slow_span_s=args.slow_span,
                      tail_keep=args.tail_keep,
                      journal_bytes=args.journal_bytes,
                      journal_segment_bytes=args.journal_segment_bytes,
                      sentinel_interval_s=args.sentinel_interval,
                      sentinel_lag_s=args.sentinel_lag),
        census=CensusConfig(
            history_interval_s=args.census_interval,
            history_slots=args.census_history_slots,
            history_coarse_every=args.census_coarse_every,
            history_coarse_slots=args.census_coarse_slots,
            max_listed=args.census_max_listed),
        durability=DurabilityConfig(mode=args.durability),
        ring=RingConfig(
            vnodes=args.ring_vnodes,
            members=args.ring_members,
            rebalance_credit_bytes=args.ring_rebalance_credit_bytes),
        index=IndexConfig(
            enabled=args.index,
            memtable_entries=args.index_memtable_entries,
            compact_runs=args.index_compact_runs,
            filter_bits_per_key=args.index_filter_bits,
            filter_sync_s=args.index_filter_sync,
            background_compact=args.index_background_compact,
            echo_cache_entries=args.index_echo_cache),
        tier=TierConfig(
            enabled=args.tier,
            hot_fraction=args.tier_hot_fraction,
            min_idle_s=args.tier_min_idle,
            scan_interval_s=args.tier_scan_interval,
            ec_k=args.tier_ec_k,
            demote_credit_bytes=args.tier_demote_credit_bytes,
            half_life_s=args.tier_half_life,
            promote_reads=args.tier_promote_reads,
            redemote_cooldown_s=args.tier_redemote_cooldown,
            ledger_entries=args.tier_ledger_entries),
        sim=SimConfig(
            enabled=args.sim,
            sketch_size=args.sim_sketch_size,
            bands=args.sim_bands,
            shingle_bytes=args.sim_shingle_bytes,
            max_candidates=args.sim_max_candidates,
            min_chunk_bytes=args.sim_min_chunk_bytes,
            min_savings_frac=args.sim_min_savings_frac,
            max_delta_depth=args.sim_max_delta_depth,
            devices=args.sim_devices,
            rematerialize_reads=args.sim_rematerialize_reads),
        chaos=ChaosConfig(
            enabled=args.chaos,
            seed=args.chaos_seed,
            rpc_delay_s=args.chaos_rpc_delay,
            rpc_delay_peers=args.chaos_rpc_delay_peers,
            rpc_drop_rate=args.chaos_rpc_drop_rate,
            partition=args.chaos_partition,
            rpc_truncate_rate=args.chaos_rpc_truncate_rate,
            serve_delay_s=args.chaos_serve_delay,
            disk_error_rate=args.chaos_disk_error_rate,
            disk_full=args.chaos_disk_full,
            disk_delay_s=args.chaos_disk_delay,
            crash_point=args.chaos_crash_point))

    async def run() -> None:
        from dfs_tpu.utils.aio import create_logged_task

        node = StorageNodeServer(cfg)
        await node.start()
        # strong refs: the event loop holds only weak task references, so
        # an unreferenced background task can be GC'd and silently
        # cancelled mid-sleep
        tasks: list[asyncio.Task] = []

        def periodic(interval: float, what: str, fn) -> None:
            if interval <= 0:
                return

            async def loop() -> None:
                while True:
                    await asyncio.sleep(interval)
                    try:
                        await fn()
                    except Exception as e:  # noqa: BLE001
                        node.log.warning("%s failed: %s", what, e)

            # retained ref + exception-logging done-callback: the
            # per-iteration catch above handles expected failures, the
            # callback makes an UNexpected loop death visible instead of
            # parking the exception on a task nobody ever awaits
            tasks.append(create_logged_task(loop(), node.log, what))

        async def do_repair() -> None:
            n = await node.repair_once()
            if n:
                node.log.info("repair: re-replicated %d chunks", n)

        async def do_scrub() -> None:
            res = await node.scrub_once()
            if res["corrupt"]:
                node.log.warning("scrub: %d corrupt chunks evicted",
                                 res["corrupt"])

        periodic(args.repair_interval, "repair", do_repair)
        periodic(args.scrub_interval, "scrub", do_scrub)
        await asyncio.Event().wait()  # serve forever

    from dfs_tpu.utils.device import DeviceError

    if not args.sidecar_port:       # a delegating node compiles nothing
        _place_compile_cache(args.fragmenter, args.cdc_devices)
    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    except DeviceError as e:
        return _device_refused(e)
    return 0


def _place_compile_cache(fragmenter: str, devices: int = 0) -> None:
    """Place the persistent compile cache before an engine that compiles
    is built; the host-only engines compile nothing."""
    if fragmenter == "auto" or fragmenter.endswith("-tpu") or devices > 1:
        from dfs_tpu.utils.device import enable_compile_cache

        enable_compile_cache()


def _device_refused(e: Exception) -> int:
    print(f"error: {e}", file=sys.stderr, flush=True)
    return 1


def cmd_sidecar(args) -> int:
    import time

    from dfs_tpu.sidecar.service import SidecarServer
    from dfs_tpu.utils.device import DeviceError

    _place_compile_cache(args.fragmenter)
    try:
        srv = SidecarServer(
            port=args.sidecar_port, fragmenter=args.fragmenter,
            cdc_params=CDCParams(min_size=args.min_chunk,
                                 avg_size=args.avg_chunk,
                                 max_size=args.max_chunk))
    except DeviceError as e:
        return _device_refused(e)
    srv.start()
    print(f"sidecar listening on 127.0.0.1:{srv.port} "
          f"(fragmenter={srv.fragmenter.name})", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()
    return 0


def cmd_status(args) -> int:
    print(_client(args).status())
    return 0


def cmd_list(args) -> int:
    files = _client(args).list_files()
    if not files:
        print("(no files)")
    for i, f in enumerate(files, 1):
        print(f"{i}. {f.name}  id={f.file_id[:16]}…  "
              f"size={f.size}  chunks={f.chunks}")
    return 0


def _maybe_trace_id(args) -> str | None:
    """--trace: mint a client-side trace id the node(s) will tag every
    span of this request with — inspect afterwards via `trace <id>`."""
    if not getattr(args, "trace", False):
        return None
    from dfs_tpu.obs import new_trace_id

    return new_trace_id()


def cmd_upload(args) -> int:
    path = Path(args.file)
    data = path.read_bytes()
    ec = getattr(args, "ec", 0)
    trace_id = _maybe_trace_id(args)
    if getattr(args, "smart", False):
        if ec or getattr(args, "resume", False):
            print("--smart is mutually exclusive with --ec/--resume "
                  "(the SDK has its own dedup probe; EC needs the "
                  "whole-body coordinator path)", file=sys.stderr)
            return 2
        info = _smart_client(args).upload(data, name=path.name)
        print(f"Uploaded ({info['dataPlane']}): fileId={info['fileId']} "
              f"chunks={info['chunks']} "
              f"clientSent={info['clientBytesSent']}B of {len(data)}B")
        return 0
    if getattr(args, "resume", False):
        if ec:
            print("--ec and --resume are mutually exclusive "
                  "(parity stripes need the whole-body upload path)",
                  file=sys.stderr)
            return 2
        # chunk locally, probe, send only missing payloads (SURVEY §5.4)
        info = _client(args, bulk=True).upload_resume(
            data, name=path.name, trace_id=trace_id)
        tr = f" traceId={trace_id}" if trace_id else ""
        print(f"Uploaded (resume): fileId={info['fileId']} "
              f"chunks={info['chunks']} "
              f"clientSent={info['clientBytesSent']}B of {len(data)}B{tr}")
        return 0
    info = _client(args, bulk=True).upload(data, name=path.name, ec=ec,
                                           trace_id=trace_id)
    extra = (f" ecParity={info['ecParityBytes']}B"
             if "ecParityBytes" in info else "")
    if trace_id:
        extra += f" traceId={trace_id}"
    print(f"Uploaded: fileId={info['fileId']} chunks={info['chunks']} "
          f"transferred={info.get('transferredBytes', '?')}B "
          f"dedupSkipped={info.get('dedupSkippedBytes', '?')}B{extra}")
    return 0


def cmd_download(args) -> int:
    c = _client(args, bulk=True)
    file_id = args.file_id
    trace_id = _maybe_trace_id(args)
    if getattr(args, "smart", False):
        sc = _smart_client(args)
        data = sc.download(file_id)
        plane = "legacy" if sc.counters["legacyDownloads"] else "smart"
        print(f"dataPlane={plane}")
    else:
        data = c.download(file_id, trace_id=trace_id)
    if trace_id:
        print(f"traceId={trace_id}")
    # Resolve the friendly name like the reference client (downloads/<name>,
    # Client.java:214-219).
    name = file_id
    for f in c.list_files():
        if f.file_id == file_id:
            name = f.name
            break
    out = Path(args.out or "downloads") / name
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_bytes(data)
    print(f"Saved {len(data)} bytes to {out}")
    return 0


def cmd_delete(args) -> int:
    print(_client(args).delete(args.file_id))
    return 0


def cmd_metrics(args) -> int:
    import json
    if getattr(args, "prom", False):
        print(_client(args).metrics_prom(), end="")
        return 0
    print(json.dumps(_client(args).metrics(), indent=2, sort_keys=True))
    return 0


def cmd_events(args) -> int:
    """Flight-recorder query: recent lifecycle events of one node
    (GET /events) — one line per event, oldest first."""
    data = _client(args).events(since=args.since, limit=args.limit)
    if not data.get("enabled", True):
        print("(journal disabled on this node)")
        return 0
    import datetime

    for ev in data.get("events", []):
        ts = datetime.datetime.fromtimestamp(
            ev.get("ts", 0.0)).strftime("%Y-%m-%d %H:%M:%S")
        etype = ev.get("type", "?")
        rest = {k: v for k, v in ev.items()
                if k not in ("ts", "type", "node", "trace")}
        trace = f" trace={ev['trace']}" if ev.get("trace") else ""
        extra = " ".join(f"{k}={v}" for k, v in sorted(rest.items()))
        print(f"{ts} node={ev.get('node', '?')} {etype} {extra}{trace}"
              .rstrip())
    if data.get("dropped"):
        print(f"(warning: {data['dropped']} events dropped at the "
              "bounded writer)", file=sys.stderr)
    if data.get("torn"):
        print(f"({data['torn']} torn/corrupt record(s) skipped)",
              file=sys.stderr)
    return 0


def cmd_doctor(args) -> int:
    """Cluster doctor: collect per-node snapshots and print the named
    pathologies with their evidence (GET /doctor)."""
    from dfs_tpu.obs.doctor import render_report

    report = _client(args, bulk=True).doctor(cluster=not args.local)
    print(render_report(report))
    if args.json:
        import json

        print(json.dumps(report, indent=2, sort_keys=True))
    # actionable findings (or unreachable peers) flip the exit code so
    # the doctor is scriptable as a health gate. info notes (e.g. the
    # doctor_error a single old-build peer's malformed snapshot earns)
    # are printed but must not fail a pathology-free cluster.
    sick = any(f.get("severity") in ("critical", "warning")
               for f in report.get("findings") or []) \
        or report.get("peersFailed", 0)
    return 1 if sick else 0


def cmd_census(args) -> int:
    """Replication-health census (GET /census): histogram + bounded
    under-replicated / orphaned / over-replicated lists. Scriptable as
    a data-health gate: exit 1 on findings or unreachable peers."""
    from dfs_tpu.obs.census import render_census

    report = _client(args, bulk=True).census(cluster=not args.local)
    print(render_census(report))
    if args.json:
        import json

        print(json.dumps(report, indent=2, sort_keys=True))
    sick = any(report.get(f"{k}Total") for k in
               ("underReplicated", "orphaned", "overReplicated")) \
        or report.get("peersFailed", 0)
    return 1 if sick else 0


def cmd_df(args) -> int:
    """Cluster capacity (the storage-native df(1)): per-node and
    cluster CAS bytes, disk headroom, dedup ratio — the capacity
    section of GET /census."""
    from dfs_tpu.obs.census import render_df

    report = _client(args, bulk=True).census(cluster=True)
    print(render_df(report))
    if report.get("peersFailed"):
        print(f"(warning: {report['peersFailed']} peer(s) unreachable "
              "— totals are partial)", file=sys.stderr)
    return 0


def cmd_ring(args) -> int:
    """Elastic membership admin (docs/membership.md): `ring status`
    renders the cluster's epoch/member/migration view; `ring
    add/drain/remove/reweight <node>` bumps the epoch on the contacted
    node, which pushes the new map to every peer and kicks the online
    rebalancer."""
    c = _client(args)
    if args.action == "status":
        st = c.ring_status()
        mode = st.get("mode", "?")
        lines = [f"ring epoch {st.get('epoch')} ({mode}"
                 + (f", {st.get('vnodes')} vnodes" if mode == "hash"
                    else "") + ")"
                 + (" — MIGRATING from epoch "
                    f"{st.get('previousEpoch')}"
                    if st.get("migrating") else "")]
        for m in st.get("members", []):
            w = m.get("weight", 1.0)
            lines.append(f"  node {m.get('nodeId')}: weight {w}"
                         + ("  (draining)" if w == 0 else ""))
        reb = st.get("rebalance") or {}
        if reb.get("bytesMoved"):
            lines.append(f"  rebalance: {reb['bytesMoved']} bytes "
                         f"moved, {reb.get('pushes', 0)} pushes, "
                         f"creditStallS={reb.get('creditStallS', 0)}, "
                         f"dualReadHits={reb.get('dualReadHits', 0)}")
        for nid, p in sorted((st.get("peers") or {}).items(),
                             key=lambda kv: int(kv[0])):
            if p is None:
                lines.append(f"  peer {nid}: NO ANSWER")
            elif p.get("epoch") != st.get("epoch") or p.get("migrating"):
                lines.append(f"  peer {nid}: epoch {p.get('epoch')}"
                             + (" (migrating)" if p.get("migrating")
                                else ""))
        print("\n".join(lines))
        if st.get("peersFailed"):
            print(f"(warning: {st['peersFailed']} peer(s) unreachable "
                  "— view is partial)", file=sys.stderr)
        # scriptable: a split epoch view or unreachable peer exits 1
        split = any(p is not None and p.get("epoch") != st.get("epoch")
                    for p in (st.get("peers") or {}).values())
        return 1 if split or st.get("peersFailed") else 0
    out = c.ring_admin(args.action, node_id=args.node,
                       weight=args.weight)
    print(f"ring epoch {out.get('epoch')} installed "
          f"({args.action} node {args.node}); pushed to: "
          + ", ".join(f"{k}={'ok' if v else 'FAILED'}"
                      for k, v in sorted(
                          (out.get('pushed') or {}).items(),
                          key=lambda kv: int(kv[0]))))
    return 0


def cmd_trace(args) -> int:
    """Stitch + render one distributed trace (docs/observability.md):
    the contacted node gathers every peer's spans for the id and this
    renders the cross-node tree with a slow-span log on top."""
    from dfs_tpu.obs.stitch import render_tree

    data = _client(args).trace(args.trace_id)
    slow = args.slow if args.slow is not None \
        else float(data.get("slowSpanS", 1.0))
    print(render_tree(data.get("spans", []), slow_s=slow))
    if data.get("peersFailed"):
        print(f"(warning: {data['peersFailed']} peer(s) unreachable — "
              "trace may be partial)", file=sys.stderr)
    return 0


def cmd_menu(args) -> int:
    """Interactive loop, Client.java:29-82 parity."""
    while True:
        print("\n=== Distributed File Storage (TPU) ===\n"
              "0. Exit\n1. Test server\n2. List files\n"
              "3. Upload file\n4. Download file")
        try:
            choice = input("> ").strip()
        except EOFError:
            return 0
        try:
            if choice == "0":
                return 0
            elif choice == "1":
                args.port = _ask_port(args.port)
                print(_client(args).status())
            elif choice == "2":
                args.port = _ask_port(args.port)
                cmd_list(args)
            elif choice == "3":
                args.port = _ask_port(args.port)
                directory = input("Directory [.]: ").strip() or "."
                files = sorted(p for p in Path(directory).iterdir()
                               if p.is_file())
                if not files:
                    print("(no files)")
                    continue
                for i, p in enumerate(files, 1):
                    print(f"{i}. {p.name} ({p.stat().st_size} bytes)")
                idx = int(input("File #: ")) - 1
                args.file = str(files[idx])
                cmd_upload(args)
            elif choice == "4":
                args.port = _ask_port(args.port)
                files = _client(args).list_files()
                for i, f in enumerate(files, 1):
                    print(f"{i}. {f.name}")
                if not files:
                    print("(no files)")
                    continue
                idx = int(input("File #: ")) - 1
                args.file_id = files[idx].file_id
                args.out = None
                cmd_download(args)
            else:
                print("Invalid option")
        except Exception as e:  # noqa: BLE001 - per-iteration catch, Client.java:77-80
            print(f"Error: {e}")


def _ask_port(default: int) -> int:
    """Port prompt with fallback, Client.java:226-237 parity."""
    raw = input(f"Node port [{default}]: ").strip()
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="dfs-tpu", description="TPU-native distributed file storage")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=5001)
    sub = ap.add_subparsers(dest="cmd", required=True)

    serve = sub.add_parser("serve", help="run a storage node")
    serve.add_argument("--node-id", type=int, required=True)
    serve.add_argument("--cluster-config", default=None,
                       help="JSON/TOML cluster membership file (overrides "
                            "--nodes/--base-port/--replication-factor)")
    serve.add_argument("--nodes", type=int, default=5)
    serve.add_argument("--base-port", type=int, default=5001)
    serve.add_argument("--base-internal-port", type=int, default=6001)
    serve.add_argument("--replication-factor", type=int, default=None)
    serve.add_argument("--data-root", default="data")
    serve.add_argument(
        "--fragmenter", default="auto", choices=FRAGMENTER_KINDS,
        help="default 'auto': the anchored chunker — its TPU chain when "
             "a TPU is present, its CPU engine otherwise")
    serve.add_argument("--cdc-devices", type=int, default=0,
                       help="shard the 'cdc-anchored' streaming walk "
                            "over N JAX devices (0/1 = single-device; "
                            "boundaries are byte-identical either way)")
    serve.add_argument("--cdc-region-bytes", type=int, default=0,
                       help="fixed device-region size for the sharded "
                            "walk (0 = 64 MiB split across the devices)")
    serve.add_argument("--cdc-staging-buffers", type=int, default=2,
                       help="host staging buffers the sharded anchored "
                            "walk cycles through (2 = double-buffered "
                            "staging/compute overlap, 1 = serial)")
    serve.add_argument("--min-chunk", type=int, default=2048)
    serve.add_argument("--avg-chunk", type=int, default=8192)
    serve.add_argument("--max-chunk", type=int, default=65536)
    serve.add_argument("--fixed-parts", type=int, default=5,
                       help="FixedFragmenter part count (reference "
                            "parity: TOTAL_NODES=5)")
    serve.add_argument("--connect-timeout", type=float, default=2.0,
                       help="per-attempt peer connect timeout (s)")
    serve.add_argument("--request-timeout", type=float, default=10.0,
                       help="per-attempt peer request timeout (s); bulk "
                            "transfers add a size-derived margin")
    serve.add_argument("--rpc-retries", type=int, default=3,
                       help="peer call attempts before a peer counts "
                            "as unreachable")
    serve.add_argument("--probe-interval", type=float, default=5.0,
                       help="seconds between peer health probes; 0 = "
                            "data-path feedback only (no probe loop)")
    serve.add_argument("--write-quorum", type=int, default=2,
                       help="copies (incl. local) an upload needs "
                            "before it acknowledges")
    serve.add_argument("--retry-after", type=float, default=1.0,
                       help="Retry-After seconds advertised on 503 "
                            "shed responses")
    serve.add_argument("--repair-interval", type=float, default=30.0)
    serve.add_argument("--scrub-interval", type=float, default=3600.0,
                       help="seconds between local integrity sweeps "
                            "(re-hash every chunk; 0 disables)")
    serve.add_argument("--cache-bytes", type=int, default=0,
                       help="hot-chunk cache budget (serving tier); "
                            "0 disables the cache + single-flight")
    serve.add_argument("--readahead", type=int, default=0,
                       help="streamed-download readahead depth (batches)")
    serve.add_argument("--download-slots", type=int, default=0,
                       help="concurrent download budget; 0 = unbounded")
    serve.add_argument("--upload-slots", type=int, default=0,
                       help="concurrent upload budget; 0 = unbounded")
    serve.add_argument("--internal-slots", type=int, default=0,
                       help="concurrent storage-plane bulk-op budget "
                            "(store/get chunks); 0 = unbounded")
    serve.add_argument("--queue-depth", type=int, default=64,
                       help="waiters beyond the slots before 503 shedding")
    serve.add_argument("--default-deadline", type=float, default=0.0,
                       help="end-to-end deadline (seconds) stamped on "
                            "HTTP requests without an X-Dfs-Deadline "
                            "header; 0 = none (docs/serve.md)")
    serve.add_argument("--hedge-floor", type=float, default=0.02,
                       help="minimum hedged-read delay (seconds) before "
                            "a second replica is asked")
    serve.add_argument("--hedge-cap", type=float, default=0.5,
                       help="maximum hedged-read delay (seconds)")
    serve.add_argument("--hedge-budget", type=float, default=0.0,
                       help="hedge token-bucket refill per second; "
                            "0 disables hedged reads (the default)")
    serve.add_argument("--sidecar-port", type=int, default=None,
                       help="delegate chunk+hash to a running sidecar "
                            "process (overrides --fragmenter)")
    serve.add_argument("--ingest-window", type=int, default=2,
                       help="streaming-ingest placement batches in "
                            "flight (1 = serial write path)")
    serve.add_argument("--ingest-flush-bytes", type=int,
                       default=32 * 1024 * 1024,
                       help="streaming-ingest placement batch size")
    serve.add_argument("--ingest-credit-bytes", type=int,
                       default=64 * 1024 * 1024,
                       help="byte budget of produced-but-unplaced chunks "
                            "(fragmenter backpressure)")
    serve.add_argument("--replicate-inflight", type=int, default=2,
                       help="replication slices in flight per peer "
                            "(1 = serial slices)")
    serve.add_argument("--cas-io-threads", type=int, default=4,
                       help="async CAS tier worker threads (local chunk "
                            "file I/O off the event loop)")
    serve.add_argument("--trace-ring", type=int, default=2048,
                       help="finished-span ring capacity (distributed "
                            "tracing); 0 disables tracing entirely")
    serve.add_argument("--slow-span", type=float, default=1.0,
                       help="slow threshold (s): trace stitcher slow "
                            "log AND the tail-retention outlier "
                            "detector")
    serve.add_argument("--tail-keep", type=int, default=256,
                       help="spans of slow/errored traces pinned "
                            "across ring churn; 0 disables tail "
                            "retention")
    serve.add_argument("--journal-bytes", type=int,
                       default=16 * 1024 * 1024,
                       help="flight-recorder on-disk budget (JSONL "
                            "event journal); 0 disables the journal")
    serve.add_argument("--journal-segment-bytes", type=int,
                       default=2 * 1024 * 1024,
                       help="journal segment rotation size")
    serve.add_argument("--sentinel-interval", type=float, default=1.0,
                       help="loop-lag/stall sentinel sampling period "
                            "(s); 0 disables sentinels")
    serve.add_argument("--sentinel-lag", type=float, default=0.25,
                       help="event-loop lag (s) above which the "
                            "sentinel journals a loop_lag incident")
    serve.add_argument("--census-interval", type=float, default=10.0,
                       help="metrics-history sample period (s) for the "
                            "census/capacity plane; 0 disables the "
                            "sampler (census queries still work)")
    serve.add_argument("--census-history-slots", type=int, default=360,
                       help="fine-resolution history buckets kept per "
                            "series")
    serve.add_argument("--census-coarse-every", type=int, default=30,
                       help="fine steps folded into one coarse history "
                            "bucket")
    serve.add_argument("--census-coarse-slots", type=int, default=288,
                       help="coarse-resolution history buckets kept "
                            "per series")
    serve.add_argument("--census-max-listed", type=int, default=64,
                       help="digests listed per census finding "
                            "category (under-replicated / orphaned / "
                            "over-replicated)")
    serve.add_argument("--durability", default="fsync",
                       choices=["fsync", "none"],
                       help="'fsync' (default): chunk + manifest writes "
                            "barrier file and directory before an "
                            "upload acks (crash-durable); 'none': bare "
                            "atomic renames (pre-r13 behavior)")
    serve.add_argument("--ring-vnodes", type=int, default=0,
                       help="virtual nodes per unit weight on the "
                            "consistent-hash membership ring; 0 "
                            "(default) = static legacy placement, "
                            "byte-stable with pre-r14 stores")
    serve.add_argument("--ring-members", default="",
                       help="csv node ids owning digest space at "
                            "epoch 0 (others are reachable standbys "
                            "until `ring add`); empty = every peer")
    serve.add_argument("--ring-rebalance-credit-bytes", type=int,
                       default=8 * 1024 * 1024,
                       help="online-rebalancer bandwidth bound "
                            "(payload bytes/s per node); 0 = "
                            "unthrottled")
    serve.add_argument("--index", action="store_true",
                       help="enable the dedup/index plane "
                            "(docs/index.md): persistent log-"
                            "structured digest index + peer-existence "
                            "filters; without this flag local "
                            "existence stays one stat per digest and "
                            "placement probes every digest over RPC")
    serve.add_argument("--index-memtable-entries", type=int,
                       default=65536,
                       help="in-memory index entries before a flush "
                            "to a sorted on-disk run")
    serve.add_argument("--index-compact-runs", type=int, default=4,
                       help="sorted runs before a full compaction "
                            "folds them into one")
    serve.add_argument("--index-filter-bits", type=int, default=10,
                       help="peer-existence filter bloom bits per "
                            "key; 0 = no filters (local index only)")
    serve.add_argument("--index-filter-sync", type=float, default=5.0,
                       help="peer-filter gossip cadence (s); 0 = no "
                            "background filter exchange")
    serve.add_argument("--index-background-compact", action="store_true",
                       help="run full index compactions on a dedicated "
                            "thread instead of the CAS workers (stall "
                            "attribution in /metrics index.compactStallS)")
    serve.add_argument("--index-echo-cache", type=int, default=0,
                       help="per-peer echo-confirmed existence cache "
                            "entries (0 = off): a digest whose hash-echo "
                            "was confirmed this ring epoch skips even "
                            "the trust-verification probe on re-upload")
    serve.add_argument("--tier", action="store_true",
                       help="enable the hot/cold tiering plane "
                            "(docs/tiering.md): temperature-driven "
                            "demotion of cold files from full "
                            "replication to EC stripes, with "
                            "transparent reads and read-driven "
                            "promotion")
    serve.add_argument("--tier-hot-fraction", type=float, default=0.1,
                       help="fraction of referenced bytes kept fully "
                            "replicated (the hot byte budget); files "
                            "past the temperature knee are "
                            "cold-eligible")
    serve.add_argument("--tier-min-idle", type=float, default=300.0,
                       help="seconds a file must go unread before it "
                            "may be demoted, however cold it ranks")
    serve.add_argument("--tier-scan-interval", type=float, default=0.0,
                       help="demotion scan cadence (s); 0 = manual "
                            "scans only (POST /tier)")
    serve.add_argument("--tier-ec-k", type=int, default=4,
                       help="data chunks per parity stripe for demoted "
                            "files (storage overhead ~(k+2)/k; needs "
                            "k+2 ring members)")
    serve.add_argument("--tier-demote-credit-bytes", type=int,
                       default=8 * 1024 * 1024,
                       help="demotion/promotion byte budget per second "
                            "(0 = unmetered) — background tiering must "
                            "not starve user traffic")
    serve.add_argument("--tier-half-life", type=float, default=3600.0,
                       help="read-heat half-life (s): each read adds "
                            "1.0 and the sum halves every half-life")
    serve.add_argument("--tier-promote-reads", type=float, default=2.0,
                       help="decayed heat at which a cold file "
                            "re-materializes replicated")
    serve.add_argument("--tier-redemote-cooldown", type=float,
                       default=0.0,
                       help="seconds a freshly-promoted file sits out "
                            "demotion scans (re-demotion hysteresis: a "
                            "file flapping around the promote threshold "
                            "must not churn encode/decode; 0 = off)")
    serve.add_argument("--tier-ledger-entries", type=int, default=65536,
                       help="bounded temperature-ledger size (stalest "
                            "digests evict first — eviction reads as "
                            "cold)")
    serve.add_argument("--sim", action="store_true",
                       help="enable the similarity compression plane "
                            "(docs/similarity.md): min-hash sketches on "
                            "ingest, LSH candidate lookup, and "
                            "delta-encoded chunk storage against "
                            "similar resident bases, transparent on "
                            "read")
    serve.add_argument("--sim-sketch-size", type=int, default=16,
                       help="min-hash lanes per sketch (more = finer "
                            "similarity resolution, linearly more "
                            "sketch compute)")
    serve.add_argument("--sim-bands", type=int, default=4,
                       help="LSH bands the sketch folds into (must "
                            "divide the sketch size; more bands = more "
                            "recall, more candidates)")
    serve.add_argument("--sim-shingle-bytes", type=int, default=8,
                       help="bytes per rolling shingle the sketch "
                            "hashes over")
    serve.add_argument("--sim-max-candidates", type=int, default=8,
                       help="bounded candidate-set size per lookup — "
                            "each candidate costs a base read + trial "
                            "encode on the CAS worker")
    serve.add_argument("--sim-min-chunk-bytes", type=int, default=4096,
                       help="chunks below this skip sketching entirely "
                            "(delta headers would eat the savings)")
    serve.add_argument("--sim-min-savings-frac", type=float, default=0.5,
                       help="store a delta only when its size is at or "
                            "below this fraction of the raw chunk")
    serve.add_argument("--sim-max-delta-depth", type=int, default=3,
                       help="longest base chain a reconstruction may "
                            "walk (caps read amplification)")
    serve.add_argument("--sim-devices", type=int, default=0,
                       help="devices to shard sketch batches over "
                            "(0/1 = host oracle; >1 = chunks-over-dp "
                            "on the mesh, byte-identical output)")
    serve.add_argument("--sim-rematerialize-reads", type=int, default=0,
                       help="reconstructions after which a hot delta is "
                            "re-materialized as a raw chunk (0 = never)")
    serve.add_argument("--chaos", action="store_true",
                       help="enable the fault-injection plane "
                            "(docs/chaos.md): the knobs below apply "
                            "and POST /chaos re-scripts them live; "
                            "without this flag NO injector exists and "
                            "every knob is ignored")
    serve.add_argument("--chaos-seed", type=int, default=0,
                       help="fault-decision RNG seed (xor'd with the "
                            "node id: per-node deterministic schedules)")
    serve.add_argument("--chaos-rpc-delay", type=float, default=0.0,
                       help="injected latency (s) before outbound "
                            "storage-plane calls")
    serve.add_argument("--chaos-rpc-delay-peers", default="",
                       help="csv node ids the rpc delay applies to "
                            "(empty = every peer)")
    serve.add_argument("--chaos-rpc-drop-rate", type=float, default=0.0,
                       help="probability an outbound call's connection "
                            "is dropped before the frame is sent")
    serve.add_argument("--chaos-partition", default="",
                       help="csv node ids unreachable FROM this node "
                            "(one-way; configure one side only for an "
                            "asymmetric partition)")
    serve.add_argument("--chaos-rpc-truncate-rate", type=float,
                       default=0.0,
                       help="probability an outbound frame is cut off "
                            "mid-body and the connection closed")
    serve.add_argument("--chaos-serve-delay", type=float, default=0.0,
                       help="injected delay (s) before serving each "
                            "inbound storage-plane op (a slow node)")
    serve.add_argument("--chaos-disk-error-rate", type=float,
                       default=0.0,
                       help="probability a CAS put/get raises EIO")
    serve.add_argument("--chaos-disk-full", action="store_true",
                       help="every CAS put raises ENOSPC (uploads "
                            "degrade to HTTP 507; reads keep working)")
    serve.add_argument("--chaos-disk-delay", type=float, default=0.0,
                       help="injected delay (s) before every CAS op "
                            "(slow disk; runs on the CAS workers)")
    serve.add_argument("--chaos-crash-point", default="",
                       help="registered crash-point name (see "
                            "dfs_tpu.chaos.CRASH_POINTS): the process "
                            "SIGKILLs itself the first time execution "
                            "reaches it")
    serve.set_defaults(fn=cmd_serve)

    sc = sub.add_parser("sidecar", help="run the chunk+hash sidecar service")
    sc.add_argument("--sidecar-port", type=int, default=50151)
    sc.add_argument("--fragmenter", default="auto",
                    choices=FRAGMENTER_KINDS)
    sc.add_argument("--min-chunk", type=int, default=2048)
    sc.add_argument("--avg-chunk", type=int, default=8192)
    sc.add_argument("--max-chunk", type=int, default=65536)
    sc.set_defaults(fn=cmd_sidecar)

    sub.add_parser("status").set_defaults(fn=cmd_status)
    sub.add_parser("list").set_defaults(fn=cmd_list)
    def _add_client_flags(p):
        """--smart data-plane knobs (ClientConfig, docs/client.md)."""
        p.add_argument("--smart", action="store_true",
                       help="use the SDK data plane: chunk+hash locally, "
                            "consult peer-existence filters, stripe "
                            "directly to the rf ring owners, one-call "
                            "commit; falls back to the coordinator path "
                            "on old servers / epoch churn")
        p.add_argument("--client-window", type=int, default=2,
                       help="store slices in flight per peer")
        p.add_argument("--client-stripe", type=int, default=4,
                       help="concurrent read batches across owners")
        p.add_argument("--client-hedge-budget", type=float, default=0.0,
                       help="hedged read/write budget (fires/s); 0 = "
                            "no client-side hedging")
        p.add_argument("--client-hedge-floor", type=float, default=0.05,
                       help="minimum hedge delay (s)")
        p.add_argument("--client-hedge-cap", type=float, default=1.0,
                       help="maximum hedge delay (s)")
        p.add_argument("--client-filter-max-age", type=float, default=30.0,
                       help="peer-existence filter freshness bound (s); "
                            "older replicas degrade to probes")
        p.add_argument("--client-echo-cache", type=int, default=4096,
                       help="echo-confirmed existence cache entries per "
                            "peer (0 = always run the trust probe)")
        p.add_argument("--client-no-fallback", action="store_true",
                       help="raise instead of degrading to the legacy "
                            "coordinator path (testing/benchmarks)")

    up = sub.add_parser("upload")
    up.add_argument("file")
    up.add_argument("--resume", action="store_true",
                    help="probe the cluster and send only missing chunks")
    up.add_argument("--ec", type=int, default=0, metavar="K",
                    help="erasure-code with K data shards + P/Q parity "
                         "per stripe (needs K+2 cluster nodes; any two "
                         "lost shards per stripe are recoverable)")
    up.add_argument("--trace", action="store_true",
                    help="tag the request with a fresh trace id "
                         "(printed) for `trace <id>` inspection")
    _add_client_flags(up)
    up.set_defaults(fn=cmd_upload)
    down = sub.add_parser("download")
    down.add_argument("file_id")
    down.add_argument("--out", default=None)
    down.add_argument("--trace", action="store_true",
                      help="tag the request with a fresh trace id "
                           "(printed) for `trace <id>` inspection")
    _add_client_flags(down)
    down.set_defaults(fn=cmd_download)
    rm = sub.add_parser("delete")
    rm.add_argument("file_id")
    rm.set_defaults(fn=cmd_delete)
    mt = sub.add_parser("metrics")
    mt.add_argument("--prom", action="store_true",
                    help="Prometheus text exposition instead of JSON")
    mt.set_defaults(fn=cmd_metrics)
    ev = sub.add_parser("events",
                        help="recent flight-recorder lifecycle events")
    ev.add_argument("--since", type=float, default=0.0,
                    help="unix-seconds lower bound (default: all "
                         "retained)")
    ev.add_argument("--limit", type=int, default=256,
                    help="newest events returned (1..4096)")
    ev.set_defaults(fn=cmd_events)
    dr = sub.add_parser("doctor",
                        help="cluster health diagnosis (named "
                             "pathologies + evidence)")
    dr.add_argument("--local", action="store_true",
                    help="diagnose the contacted node only (no peer "
                         "fan-out)")
    dr.add_argument("--json", action="store_true",
                    help="also print the full report as JSON")
    dr.set_defaults(fn=cmd_doctor)
    cn = sub.add_parser("census",
                        help="replication-health census (digest "
                             "copies histogram + under-replicated/"
                             "orphaned/over-replicated findings)")
    cn.add_argument("--local", action="store_true",
                    help="inventory the contacted node only (no peer "
                         "fan-out)")
    cn.add_argument("--json", action="store_true",
                    help="also print the full report as JSON")
    cn.set_defaults(fn=cmd_census)
    df = sub.add_parser("df",
                        help="cluster capacity: per-node CAS bytes, "
                             "disk headroom, dedup ratio")
    df.set_defaults(fn=cmd_df)
    rg = sub.add_parser("ring",
                        help="elastic membership: show or change the "
                             "placement ring (epoch-versioned; "
                             "changes rebalance online)")
    rg.add_argument("action",
                    choices=["status", "add", "drain", "remove",
                             "reweight"])
    rg.add_argument("node", type=int, nargs="?", default=None,
                    help="target node id (required for every action "
                         "but status)")
    rg.add_argument("--weight", type=float, default=None,
                    help="member weight (add/reweight); default 1.0 "
                         "on add")
    rg.set_defaults(fn=cmd_ring)
    tr = sub.add_parser("trace",
                        help="render a stitched cross-node trace")
    tr.add_argument("trace_id")
    tr.add_argument("--slow", type=float, default=None,
                    help="slow-span threshold (s); default: the node's "
                         "configured slow_span_s")
    tr.set_defaults(fn=cmd_trace)
    sub.add_parser("menu").set_defaults(fn=cmd_menu)
    return ap


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
