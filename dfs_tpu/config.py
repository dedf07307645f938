"""Typed configuration for the whole framework.

Replaces the reference's two positional CLI args plus hardcoded constants
(``TOTAL_NODES = 5`` at StorageNode.java:15, the ``localhost:500<id>`` peer URL
scheme at StorageNode.java:227/322/472, and the 2000 ms timeouts at
StorageNode.java:229-230) with one explicit, serializable config. This fixes
reference defects SURVEY.md §2.5(1): cluster size/addressing are no longer
hardwired and node ids >= 10 work.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

# uint32 Gear hash with shift-1 forgets bytes older than 32 positions (they
# shift out mod 2**32): the effective window of the ``cdc`` kind, and the
# halo its streaming walk threads between tiles.
GEAR_WINDOW = 32
GEAR_HALO = GEAR_WINDOW - 1

# what NodeConfig.fragmenter / --fragmenter may name (fragmenter/base.py
# get_fragmenter builds them, and says why there are these)
FRAGMENTER_KINDS = ("auto", "fixed", "cdc", "cdc-anchored",
                    "cdc-anchored-tpu")


@dataclasses.dataclass(frozen=True)
class CDCParams:
    """Content-defined-chunking parameters: the ``cdc`` kind's own (Gear
    rolling hash), and the operator's chunk sizing for the anchored kinds,
    which quantize it to 64-byte blocks (fragmenter/base.py
    ``_anchored_params``).

    ``avg_size`` must be a power of two: the boundary test is
    ``(gear_hash & (avg_size - 1)) == 0`` which fires with probability
    1/avg_size per byte. ``window`` is fixed at 32 because the uint32 Gear
    hash with shift-1 forgets bytes older than 32 positions (they shift out
    mod 2**32) — this is what makes the windowed bitmap exactly equal to
    the sequential rolling hash.
    """

    min_size: int = 2048
    avg_size: int = 8192
    max_size: int = 65536
    seed: int = 0x9E3779B9

    WINDOW: int = dataclasses.field(default=32, init=False, repr=False)

    def __post_init__(self) -> None:
        if self.avg_size & (self.avg_size - 1):
            raise ValueError(f"avg_size must be a power of two, got {self.avg_size}")
        if not (0 < self.min_size <= self.avg_size <= self.max_size):
            raise ValueError(
                f"need 0 < min ({self.min_size}) <= avg ({self.avg_size})"
                f" <= max ({self.max_size})"
            )

    @property
    def mask(self) -> int:
        return self.avg_size - 1


@dataclasses.dataclass(frozen=True)
class PeerAddr:
    """Explicit peer address — replaces the derived ``localhost:500<id>``
    scheme (StorageNode.java:227) that broke for node ids >= 10."""

    node_id: int
    host: str
    port: int           # external HTTP API port
    internal_port: int  # binary storage-plane port

    @property
    def http_base(self) -> str:
        return f"http://{self.host}:{self.port}"


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    """Cluster membership + replication policy.

    The reference fixes replication at cyclic x2 over 5 nodes
    (StorageNode.java:143-145,199-200). Here both the node list and the
    replication factor are explicit.
    """

    peers: tuple[PeerAddr, ...]
    replication_factor: int = 2

    def __post_init__(self) -> None:
        if len({p.node_id for p in self.peers}) != len(self.peers):
            raise ValueError("duplicate node_id in cluster config")
        if not 1 <= self.replication_factor <= max(1, len(self.peers)):
            raise ValueError("replication_factor out of range")

    @property
    def n_nodes(self) -> int:
        return len(self.peers)

    def peer(self, node_id: int) -> PeerAddr:
        for p in self.peers:
            if p.node_id == node_id:
                return p
        raise KeyError(f"unknown node_id {node_id}")

    def sorted_ids(self) -> list[int]:
        return sorted(p.node_id for p in self.peers)

    @staticmethod
    def from_file(path: str | Path) -> "ClusterConfig":
        """Load membership from JSON/TOML — the explicit-cluster-config fix
        for reference defect §2.5(1). JSON shape::

            {"replication_factor": 2,
             "peers": [{"node_id": 1, "host": "10.0.0.1",
                        "port": 5001, "internal_port": 6001}, ...]}

        TOML uses a ``[[peers]]`` array of tables with the same keys.
        """
        path = Path(path)
        text = path.read_text()
        if path.suffix == ".toml":
            import tomllib

            d = tomllib.loads(text)
        else:
            d = json.loads(text)
        return ClusterConfig(
            peers=tuple(PeerAddr(**p) for p in d["peers"]),
            replication_factor=int(d.get("replication_factor", 2)))

    @staticmethod
    def localhost(n_nodes: int, base_port: int = 5001,
                  base_internal_port: int = 6001,
                  replication_factor: int = 2) -> "ClusterConfig":
        """Convenience constructor mirroring the reference's manual recipe of
        N localhost nodes on ports 5001..500N (run.txt:3-7) — but explicit."""
        peers = tuple(
            PeerAddr(node_id=i + 1, host="127.0.0.1",
                     port=base_port + i, internal_port=base_internal_port + i)
            for i in range(n_nodes)
        )
        return ClusterConfig(peers=peers, replication_factor=replication_factor)


@dataclasses.dataclass(frozen=True)
class FragmenterConfig:
    """Execution knobs of the fragmenter plugin — the *how it runs*
    (device sharding), vs :class:`CDCParams`' *what it computes* (chunk
    boundaries, which these knobs must never change).

    ``devices > 1`` means one thing: the ``cdc-anchored`` region walk over
    that many JAX devices, whole stream windows riding the mesh's dp axis
    (``parallel/sharded_cdc.make_anchored_window_anchor_step`` /
    ``make_anchored_window_step``, fragmenter/cdc_anchored_sharded.py) —
    chunk boundaries stay BYTE-IDENTICAL to the single-device path by
    construction (tests/test_sharded_ingest.py asserts it). Any other
    kind says at start-up that it ignores the knob. With fewer devices
    visible than asked, the CPU rehearsal (``JAX_PLATFORMS=cpu``) logs
    once and runs single-device; anywhere else that is an error.
    """

    devices: int = 0        # 0/1 = single-device CDC; N > 1 = shard
                            # regions over N JAX devices when visible
    region_bytes: int = 0   # fixed device-region size streaming input is
                            # re-blocked to (the sharded step compiles
                            # ONCE for this shape); 0 = 64 MiB split
                            # across the window batch
    staging_buffers: int = 2  # host staging buffers the sharded anchored
                            # walk cycles through: 2 = double-buffered
                            # (device_put region k+1 while region k
                            # computes); 1 = strictly serial staging

    def __post_init__(self) -> None:
        # no cross-field region/devices constraint here: alignment is
        # the walk's own (the anchor tile, via
        # sharded_common.fixed_region_bytes)
        if self.devices < 0:
            raise ValueError("devices must be >= 0")
        if self.region_bytes < 0:
            raise ValueError("region_bytes must be >= 0")
        if self.staging_buffers < 1:
            raise ValueError("staging_buffers must be >= 1")


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Read-path serving tier (dfs_tpu.serve) — hot-chunk cache,
    single-flight coalescing, admission control, readahead.

    EVERYTHING defaults off: a node built from ``ServeConfig()`` runs
    byte-identical read/write code paths to the pre-serving-tier node
    (tier-1 semantics unchanged); each knob enables one component.
    """

    cache_bytes: int = 0        # hot-chunk cache budget; 0 = no cache
                                # (and no single-flight read path —
                                # the two ride one switch, serve/__init__)
    readahead_batches: int = 0  # streamed-download readahead depth K;
                                # 0 = fetch batches strictly one at a time
    download_slots: int = 0     # concurrent GET /download budget; 0 = no
                                # gate (unbounded, the historical behavior)
    upload_slots: int = 0       # concurrent POST /upload* budget
    internal_slots: int = 0     # concurrent storage-plane ops budget
    queue_depth: int = 64       # waiters beyond the slots before shedding
    retry_after_s: float = 1.0  # advertised in 503 Retry-After
    default_deadline_s: float = 0.0  # end-to-end deadline stamped on
                                # HTTP requests without an X-Dfs-Deadline
                                # header (docs/serve.md §deadlines);
                                # 0 = none — pre-r18 behavior exactly
    hedge_floor_s: float = 0.02  # minimum hedge delay: never hedge a
                                # read sooner than this (a hedge below
                                # the healthy RTT doubles every fetch)
    hedge_cap_s: float = 0.5    # maximum hedge delay: a replica slower
                                # than this is hedged even if its
                                # history says it used to be slower
    hedge_budget_per_s: float = 0.0  # hedge token-bucket refill per
                                # second (serve/hedge.py); the MASTER
                                # switch — 0 = hedged reads off (the
                                # default: pre-r18 read path exactly)

    def __post_init__(self) -> None:
        if self.cache_bytes < 0:
            raise ValueError("cache_bytes must be >= 0")
        if self.readahead_batches < 0:
            raise ValueError("readahead_batches must be >= 0")
        if self.queue_depth < 0:
            raise ValueError("queue_depth must be >= 0")
        if self.default_deadline_s < 0:
            raise ValueError("default_deadline_s must be >= 0")
        if self.hedge_floor_s < 0 or self.hedge_cap_s < self.hedge_floor_s:
            raise ValueError("need 0 <= hedge_floor_s <= hedge_cap_s")
        if self.hedge_budget_per_s < 0:
            raise ValueError("hedge_budget_per_s must be >= 0")


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability (dfs_tpu.obs): distributed tracing, unified metrics,
    and the diagnosis plane (flight recorder + sentinels + tail-kept
    outlier traces — docs/observability.md).

    Unlike the serve/ingest knobs, tracing defaults ON — the Dapper
    lesson is that always-on cheap tracing is what makes the *one* slow
    request diagnosable after the fact. ``trace_ring=0`` disables span
    collection AND context propagation entirely (the wire/header trace
    carriers are simply never attached). The diagnosis plane follows the
    same always-on philosophy: the journal, sentinels and tail retention
    default on (each individually zeroable), and OBS2_r11.json holds the
    measured hot-read overhead of everything-on vs everything-off (≤2%
    gate). RPC metrics stay on either way.
    """

    trace_ring: int = 2048      # finished-span ring capacity per node;
                                # 0 = tracing fully off
    slow_span_s: float = 1.0    # slow threshold (s): stitcher slow log
                                # AND the tail-retention outlier detector
    tail_keep: int = 256        # pinned spans of slow/errored traces
                                # that survive ring churn; 0 = tail
                                # retention off (outliers evict normally)
    journal_bytes: int = 16 * 1024 * 1024   # flight-recorder on-disk
                                # budget (JSONL segments); 0 = no journal
    journal_segment_bytes: int = 2 * 1024 * 1024  # journal segment
                                # rotation size (oldest segments are
                                # deleted to hold the total budget)
    sentinel_interval_s: float = 1.0  # loop-lag / stall sampler period;
                                # 0 = sentinels off
    sentinel_lag_s: float = 0.25      # event-loop lag above which the
                                # sentinel journals a loop_lag incident

    def __post_init__(self) -> None:
        if self.trace_ring < 0:
            raise ValueError("trace_ring must be >= 0")
        if self.slow_span_s <= 0:
            raise ValueError("slow_span_s must be > 0")
        if self.tail_keep < 0:
            raise ValueError("tail_keep must be >= 0")
        if self.journal_bytes < 0 or self.journal_segment_bytes <= 0:
            raise ValueError("journal_bytes must be >= 0 and "
                             "journal_segment_bytes > 0")
        if self.sentinel_interval_s < 0:
            raise ValueError("sentinel_interval_s must be >= 0")
        if self.sentinel_lag_s <= 0:
            raise ValueError("sentinel_lag_s must be > 0")


@dataclasses.dataclass(frozen=True)
class DurabilityConfig:
    """Write-path durability policy (store/cas.py, docs/chaos.md).

    ``mode="fsync"`` (the default) makes every acked byte crash-durable:
    chunk writes fsync the payload file AND its parent directory before
    the link/rename becomes visible, and the manifest write that acks an
    upload fsyncs the same way — so a ``kill -9`` the instant after a
    201 can never lose the upload (bench_chaos.py's crash-restart
    scenario is the acceptance evidence). ``mode="none"`` restores the
    pre-r13 behavior — atomic renames without barriers — for benches
    and throwaway clusters where the page cache is considered durable
    enough. Routed through :class:`AsyncChunkStore` worker threads and
    ``asyncio.to_thread`` manifest saves, so the event loop never
    blocks on a barrier either way."""

    mode: str = "fsync"   # "fsync" | "none"

    def __post_init__(self) -> None:
        if self.mode not in ("fsync", "none"):
            raise ValueError(f"durability mode must be 'fsync' or "
                             f"'none', got {self.mode!r}")

    @property
    def fsync(self) -> bool:
        return self.mode == "fsync"


@dataclasses.dataclass(frozen=True)
class ChaosConfig:
    """Deterministic fault injection (dfs_tpu.chaos, docs/chaos.md).

    EVERYTHING defaults off: ``enabled=False`` means the node holds no
    injector at all — every seam is one ``is None`` branch, and the
    node's behavior is byte-identical to a chaos-less build (asserted
    by tests/test_chaos.py). With ``enabled=True`` the node builds a
    :class:`dfs_tpu.chaos.ChaosInjector` seeded from ``seed ^ node_id``
    (per-node deterministic decision streams), applies the knobs below,
    and accepts runtime re-configuration via ``POST /chaos`` — which is
    how the cluster harness scripts scenarios (inject → observe → heal)
    without restarting nodes. Every injected fault is journaled as a
    trace-stamped ``chaos_inject`` event.

    Fault taxonomy (see docs/chaos.md):
    - ``rpc_delay_s`` / ``rpc_delay_peers``: outbound storage-plane
      calls to the named peers (csv of node ids; empty = all) sleep
      before sending — a slow link.
    - ``rpc_drop_rate``: probability an outbound call's connection is
      dropped mid-request (transport error, retried by the client).
    - ``partition``: csv of peer node ids this node cannot reach AT
      ALL. One-way by construction — configure one side only for an
      asymmetric partition.
    - ``rpc_truncate_rate``: probability an outbound frame is cut off
      mid-body and the connection closed — the receiver sees a torn
      frame (wire-level corruption).
    - ``serve_delay_s``: inbound storage-plane ops on THIS node sleep
      before dispatch — the whole node is slow (the doctor's
      ``slow_peer`` evidence shape).
    - ``disk_error_rate``: probability a CAS put/get raises EIO.
    - ``disk_full``: every CAS put raises ENOSPC (surfaced as HTTP 507
      by the upload path — reads keep working).
    - ``disk_delay_s``: every CAS op sleeps first (slow disk; runs on
      the bounded CAS worker threads, never the event loop).
    - ``crash_point``: a registered crash-point name (see
      ``dfs_tpu.chaos.CRASH_POINTS``); the process dies by SIGKILL the
      first time execution reaches it.
    """

    enabled: bool = False
    seed: int = 0
    rpc_delay_s: float = 0.0
    rpc_delay_peers: str = ""     # csv node ids; "" = every peer
    rpc_drop_rate: float = 0.0
    partition: str = ""           # csv node ids unreachable from here
    rpc_truncate_rate: float = 0.0
    serve_delay_s: float = 0.0
    disk_error_rate: float = 0.0
    disk_full: bool = False
    disk_delay_s: float = 0.0
    crash_point: str = ""

    def __post_init__(self) -> None:
        for f in ("rpc_delay_s", "serve_delay_s", "disk_delay_s"):
            if getattr(self, f) < 0:
                raise ValueError(f"{f} must be >= 0")
        for f in ("rpc_drop_rate", "rpc_truncate_rate",
                  "disk_error_rate"):
            if not 0.0 <= getattr(self, f) <= 1.0:
                raise ValueError(f"{f} must be in [0, 1]")
        for f in ("rpc_delay_peers", "partition"):
            spec = getattr(self, f)
            if not isinstance(spec, str):
                raise ValueError(f"{f} must be a csv string of node "
                                 f"ids, got {type(spec).__name__}")
            if spec and not all(
                    p.strip().isdigit() for p in spec.split(",")):
                raise ValueError(f"{f} must be a csv of node ids, "
                                 f"got {spec!r}")


@dataclasses.dataclass(frozen=True)
class CensusConfig:
    """Cluster census & capacity plane (dfs_tpu.obs.census /
    obs.history — docs/observability.md).

    The census itself is pull-driven (``GET /census`` fans out an
    internal ``get_census`` op and costs nothing until asked); the only
    steady-state cost these knobs control is the embedded metrics
    history sampler — a fixed-memory, multi-resolution ring of selected
    counters/gauges (ingest/serve/RPC/CAS/capacity) that feeds
    ``GET /metrics/history`` and the doctor's trend rules
    (``capacity_trend`` disk-full ETA). Defaults keep ~1 h at 10 s and
    ~24 h at 5 min per series; ``history_interval_s=0`` turns sampling
    fully off (census queries still work, trend rules go quiet).
    """

    history_interval_s: float = 10.0  # fine-resolution sample period
                                # (s); 0 = the history sampler is off
    history_slots: int = 360    # fine buckets kept per series (1 h at
                                # the default 10 s step)
    history_coarse_every: int = 30   # fine steps per coarse bucket
                                # (5 min at the defaults)
    history_coarse_slots: int = 288  # coarse buckets kept (24 h)
    max_listed: int = 64        # bounded per-category digest lists in
                                # census findings (under-replicated /
                                # orphaned / over-replicated)

    def __post_init__(self) -> None:
        if self.history_interval_s < 0:
            raise ValueError("history_interval_s must be >= 0")
        if self.history_slots < 1 or self.history_coarse_slots < 1:
            raise ValueError("history slots must be >= 1")
        if self.history_coarse_every < 1:
            raise ValueError("history_coarse_every must be >= 1")
        if self.max_listed < 1:
            raise ValueError("max_listed must be >= 1")


@dataclasses.dataclass(frozen=True)
class RingConfig:
    """Elastic membership plane (dfs_tpu.ring, docs/membership.md).

    EVERYTHING defaults to the legacy behavior: ``vnodes=0`` compiles
    the boot-time peer list into a STATIC epoch-0 ring whose placement
    is byte-identical to the pre-r14 cyclic mod-N replica sets —
    existing stores keep their layout. ``vnodes > 0`` opts into the
    weighted consistent-hash ring from boot (minimal-movement
    membership changes); a live membership change (``ring add/remove/
    drain``) on a static cluster promotes it to hash mode at the
    default vnode count as part of the epoch bump.

    ``members`` restricts which boot-time peers own digest space at
    epoch 0 ("" = all of them): extra peers in the cluster config are
    reachable STANDBY nodes — addressable, announced to, but placed on
    only after a ``ring add``. This separates addressing (the transport
    needs it at boot) from membership (the ring changes it live).

    ``rebalance_credit_bytes`` bounds the ONLINE rebalancer: each node
    streams chunks to their new-epoch owners at most this many payload
    bytes per second (a token bucket on the repair push path), so a
    membership change can never starve live traffic of bandwidth.
    0 = unthrottled.
    """

    vnodes: int = 0             # vnodes per unit weight; 0 = static
                                # legacy placement (byte-stable)
    members: str = ""           # csv node ids owning digest space at
                                # epoch 0; "" = every cluster peer
    rebalance_credit_bytes: int = 8 * 1024 * 1024  # rebalance bytes/s
                                # per node; 0 = unthrottled

    def __post_init__(self) -> None:
        if self.vnodes < 0:
            raise ValueError("vnodes must be >= 0")
        if self.rebalance_credit_bytes < 0:
            raise ValueError("rebalance_credit_bytes must be >= 0")
        if not isinstance(self.members, str):
            raise ValueError("members must be a csv string of node ids")
        if self.members and not all(
                p.strip().isdigit() for p in self.members.split(",")):
            raise ValueError(f"members must be a csv of node ids, "
                             f"got {self.members!r}")

    def member_ids(self) -> list[int] | None:
        """Parsed epoch-0 member ids, or None for 'every peer'."""
        if not self.members:
            return None
        return sorted({int(p.strip()) for p in self.members.split(",")})


@dataclasses.dataclass(frozen=True)
class IndexConfig:
    """Scalable dedup/index plane (dfs_tpu.index, docs/index.md):
    persistent log-structured digest index + delta-gossiped
    peer-existence filters.

    EVERYTHING defaults off: ``IndexConfig()`` builds no index and no
    filters — ``ChunkStore.has`` stays one stat syscall, placement
    probes every digest over RPC, and the node runs byte-identical
    code paths to a pre-index build (the chaos/serve default-off
    discipline, asserted by tests/test_index.py). ``enabled=True``
    builds the :class:`~dfs_tpu.index.IndexPlane`:

    - local existence answers come from the log-structured index (one
      memtable hit or one fenced ``pread``), with the stat call kept
      as the negative-confirmation backstop;
    - each node maintains a blocked-bloom filter over its own digest
      set (``filter_bits_per_key`` sizes it; 0 = index only, no
      filter exchange), replicated to peers via ``get_filter`` /
      ``filter_delta`` every ``filter_sync_s`` seconds;
    - placement consults the peer filters first and only RPCs what
      the filters cannot rule out, with filter-credited copies
      verified by one pre-ack ``has_chunks`` round (docs/index.md).
    """

    enabled: bool = False
    memtable_entries: int = 65536   # bounded in-memory index entries
                                    # before a flush to a sorted run
    compact_runs: int = 4           # sorted runs before a full
                                    # compaction folds them into one
    filter_bits_per_key: int = 10   # peer-filter bloom density;
                                    # 0 = no filters (index only)
    filter_sync_s: float = 5.0      # filter gossip cadence (s);
                                    # 0 = no background exchange
    background_compact: bool = False  # run full compactions on a
                                    # dedicated thread instead of the
                                    # CAS worker that tripped them;
                                    # False = historical inline merge
    echo_cache_entries: int = 0     # per-peer LRU of digests whose
                                    # hash-echo was confirmed this
                                    # session (skips even the pre-ack
                                    # verify round on re-upload);
                                    # 0 = no cache (verify every time)

    def __post_init__(self) -> None:
        if self.memtable_entries < 256:
            raise ValueError("memtable_entries must be >= 256")
        if self.compact_runs < 1:
            raise ValueError("compact_runs must be >= 1")
        if self.filter_bits_per_key < 0:
            raise ValueError("filter_bits_per_key must be >= 0")
        if self.filter_sync_s < 0:
            raise ValueError("filter_sync_s must be >= 0")
        if self.echo_cache_entries < 0:
            raise ValueError("echo_cache_entries must be >= 0")


@dataclasses.dataclass(frozen=True)
class TierConfig:
    """Hot/cold tiering plane (dfs_tpu.tier, docs/tiering.md):
    temperature-driven demotion of cold files from full replication to
    wide EC stripes, with transparent promotion on re-heat.

    EVERYTHING defaults off: ``TierConfig()`` builds no ledger, no
    worker and no admission class — reads and repair run byte-identical
    code paths to a pre-tier build (the chaos/serve/index default-off
    discipline, asserted by tests/test_tiering.py). ``enabled=True``
    builds the :class:`~dfs_tpu.tier.TierPlane`:

    - every served chunk feeds a bounded per-digest temperature ledger
      (last access + read count decayed with ``half_life_s``);
    - classification is by BYTE-BUDGET percentile, not fixed age: the
      hottest files up to ``hot_fraction`` of referenced bytes stay
      replicated, the rest are demotion candidates once idle longer
      than ``min_idle_s``;
    - the demotion worker (every ``scan_interval_s``; 0 = manual
      ``POST /tier`` scans only) EC-encodes cold files at ``ec_k``+2,
      flips the manifest/index tier bit, then deletes surplus
      replicas — throttled by ``demote_credit_bytes``/s so demotion
      never starves user traffic;
    - a cold read reconstructs transparently (the existing EC decode
      path) and re-materializes a replicated copy once its decayed
      read count crosses ``promote_reads``.
    """

    enabled: bool = False
    hot_fraction: float = 0.1       # fraction of referenced bytes kept
                                    # replicated (the hot set); the
                                    # rest is cold-eligible
    min_idle_s: float = 300.0       # never demote a file read more
                                    # recently than this (absolute
                                    # floor under the percentile)
    scan_interval_s: float = 0.0    # demotion scan cadence (s);
                                    # 0 = manual scans only (POST /tier)
    ec_k: int = 4                   # data shards per cold EC stripe
                                    # (parity is always P+Q = 2)
    demote_credit_bytes: int = 8 * 1024 * 1024  # demotion byte budget
                                    # per second (ByteRate, the r14
                                    # rebalance discipline); 0 = unthrottled
    half_life_s: float = 3600.0     # read-count decay half-life (s)
    promote_reads: float = 2.0      # decayed reads at which a cold
                                    # file re-materializes replicated
    ledger_entries: int = 65536     # bounded temperature-ledger size
                                    # (LRU beyond it)
    redemote_cooldown_s: float = 0.0  # after a promotion, the file is
                                    # NOT demotion-eligible again for
                                    # this long — hysteresis so a file
                                    # flapping around promote_reads
                                    # doesn't churn encode/decode
                                    # cycles; 0 = historical behavior
                                    # (eligible immediately)

    def __post_init__(self) -> None:
        if not 0.0 <= self.hot_fraction <= 1.0:
            raise ValueError("hot_fraction must be within [0, 1]")
        if self.min_idle_s < 0:
            raise ValueError("min_idle_s must be >= 0")
        if self.scan_interval_s < 0:
            raise ValueError("scan_interval_s must be >= 0")
        if not 1 <= self.ec_k <= 255:
            raise ValueError("ec_k must be within [1, 255]")
        if self.demote_credit_bytes < 0:
            raise ValueError("demote_credit_bytes must be >= 0")
        if self.half_life_s <= 0:
            raise ValueError("half_life_s must be > 0")
        if self.promote_reads < 0:
            raise ValueError("promote_reads must be >= 0")
        if self.ledger_entries < 256:
            raise ValueError("ledger_entries must be >= 256")
        if self.redemote_cooldown_s < 0:
            raise ValueError("redemote_cooldown_s must be >= 0")


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Similarity-compression plane (dfs_tpu.sim, docs/similarity.md):
    batched min-hash sketches over ingest chunks, a banded similarity
    lookup, and delta-encoded chunk storage in the CAS.

    EVERYTHING defaults off: ``SimConfig()`` builds no sketch kernel,
    no band index and no delta tree — ``ChunkStore`` reads and writes
    raw chunk files on byte-identical code paths to a pre-sim build
    (the chaos/serve/index/tier default-off discipline, asserted by
    tests/test_sim.py). ``enabled=True`` builds the
    :class:`~dfs_tpu.sim.SimPlane`:

    - every locally stored chunk is sketched (``sketch_size`` min-hash
      lanes over ``shingle_bytes``-byte shingles, batched over the
      mesh's dp axis when ``devices > 1``, NumPy oracle otherwise or
      on degraded envs — byte-identical either way);
    - the sketch's ``bands`` band keys feed a crash-safe append-only
      band log; a new chunk's bands look up at most ``max_candidates``
      resident base candidates;
    - a chunk whose best candidate delta-encodes below
      ``min_savings_frac`` of its raw size is stored as
      ``base-digest + patch`` (transparent on read: resolve base,
      apply patch, sha256-verify), chains capped at ``max_delta_depth``
      and re-materialized raw after ``rematerialize_reads`` reads.
    """

    enabled: bool = False
    sketch_size: int = 16           # min-hash lanes per sketch (uint32
                                    # each); bands must divide it
    bands: int = 4                  # LSH bands per sketch — each band
                                    # of sketch_size/bands lanes is one
                                    # secondary lookup key
    shingle_bytes: int = 8          # bytes per rolling shingle feature
    max_candidates: int = 8         # resident base candidates consulted
                                    # per new chunk (bounded work)
    min_chunk_bytes: int = 4096     # chunks smaller than this are never
                                    # sketched or delta-encoded (patch
                                    # overhead dominates)
    min_savings_frac: float = 0.5   # store a delta only if the patch is
                                    # at most this fraction of the raw
                                    # size (0.5 = patch must halve it)
    max_delta_depth: int = 3        # longest base chain a reconstruct
                                    # may walk; a chunk at the cap is
                                    # stored raw and never a base issue
    devices: int = 0                # shard sketch batches over this
                                    # many mesh devices (0/1 = NumPy
                                    # oracle on the host)
    rematerialize_reads: int = 0    # delta reads before the chunk is
                                    # re-materialized raw (read-
                                    # amplification bound); 0 = never

    def __post_init__(self) -> None:
        if self.sketch_size < 1:
            raise ValueError("sketch_size must be >= 1")
        if not 1 <= self.bands <= self.sketch_size \
                or self.sketch_size % self.bands:
            raise ValueError("bands must divide sketch_size")
        if not 1 <= self.shingle_bytes <= 64:
            raise ValueError("shingle_bytes must be within [1, 64]")
        if self.max_candidates < 1:
            raise ValueError("max_candidates must be >= 1")
        if self.min_chunk_bytes < 0:
            raise ValueError("min_chunk_bytes must be >= 0")
        if not 0.0 < self.min_savings_frac <= 1.0:
            raise ValueError("min_savings_frac must be within (0, 1]")
        if self.max_delta_depth < 1:
            raise ValueError("max_delta_depth must be >= 1")
        if self.devices < 0:
            raise ValueError("devices must be >= 0")
        if self.rematerialize_reads < 0:
            raise ValueError("rematerialize_reads must be >= 0")


@dataclasses.dataclass(frozen=True)
class ClientConfig:
    """Smart-client data plane (dfs_tpu.client, docs/client.md).

    Knobs of the edge SDK that chunks/hashes locally, consults the
    cluster's ring + peer-existence filters, and stripes transfers
    directly to the rf ring owners (single coordinator call only for
    the manifest commit). Every knob here must surface as a CLI flag
    on ``upload``/``download`` and as a key in ``SmartClient.stats()``
    (dfslint DFS005 checks both mappings). Defaults are the
    conservative shape: striping on (the SDK is only built when asked
    for), client-side hedging OFF, transparent legacy fallback ON.
    """

    window: int = 2             # upload slices in flight PER OWNER
                                # (the comm/rpc.py slice-pipelining
                                # discipline); 1 = serial slices
    stripe: int = 4             # peers a striped download reads from
                                # concurrently; 1 = effectively serial
    hedge_budget_per_s: float = 0.0  # client hedge token refill per
                                # second (serve/hedge.py shapes);
                                # 0 = client-side hedging off
    hedge_floor_s: float = 0.05  # minimum client hedge delay
    hedge_cap_s: float = 1.0    # maximum client hedge delay
    filter_max_age_s: float = 30.0  # peer-existence filters older than
                                # this are refetched before an upload;
                                # 0 = refetch every upload
    echo_cache_entries: int = 4096  # per-peer LRU of digests whose
                                # hash-echo this client saw confirmed;
                                # 0 = verify-round every re-upload
    fallback: bool = True       # degrade transparently to the legacy
                                # coordinator path (epoch mismatch, old
                                # servers, unreachable owners); False =
                                # raise instead (benches / tests)

    def __post_init__(self) -> None:
        if self.window < 1:
            raise ValueError("window must be >= 1")
        if self.stripe < 1:
            raise ValueError("stripe must be >= 1")
        if self.hedge_budget_per_s < 0:
            raise ValueError("hedge_budget_per_s must be >= 0")
        if self.hedge_floor_s < 0 or self.hedge_cap_s < self.hedge_floor_s:
            raise ValueError("need 0 <= hedge_floor_s <= hedge_cap_s")
        if self.filter_max_age_s < 0:
            raise ValueError("filter_max_age_s must be >= 0")
        if self.echo_cache_entries < 0:
            raise ValueError("echo_cache_entries must be >= 0")


@dataclasses.dataclass(frozen=True)
class IngestConfig:
    """Pipelined write path (docs/ingest.md) — the knobs bounding how much
    of the three-stage ingest pipeline (fragmentation, local CAS writes,
    peer replication) may be in flight at once.

    ``window=1`` with ``slice_inflight=1`` reproduces the historical
    strictly-serial schedule (each ~``flush_bytes`` batch fully placed
    before the next one starts); the defaults overlap chunking batch N+1
    with replicating batch N, which is where streaming-ingest wall time
    went once replication latency dominated (INGEST_r07.json: windowed
    ingest 2.66x serial under injected peer latency).
    """

    window: int = 2             # placement batches in flight during
                                # streaming ingest; 1 = serial placement
    flush_bytes: int = 32 * 1024 * 1024   # batch size streaming ingest
                                # accumulates before placing
    credit_bytes: int = 64 * 1024 * 1024  # byte budget of produced-but-
                                # unconsumed chunks (fragmenter-thread
                                # backpressure); bounds ingest memory by
                                # BYTES, not chunk count
    slice_inflight: int = 2     # replication slices in flight PER PEER
                                # (pooled connections); 1 = serial slices
    cas_io_threads: int = 4     # worker threads of the async CAS tier
                                # (store/aio.py) — local chunk file I/O
                                # off the event loop

    def __post_init__(self) -> None:
        if self.window < 1:
            raise ValueError("window must be >= 1")
        if self.flush_bytes <= 0 or self.credit_bytes <= 0:
            raise ValueError("flush_bytes/credit_bytes must be > 0")
        if self.slice_inflight < 1:
            raise ValueError("slice_inflight must be >= 1")
        if self.cas_io_threads < 1:
            raise ValueError("cas_io_threads must be >= 1")


@dataclasses.dataclass(frozen=True)
class NodeConfig:
    """Per-node runtime configuration."""

    node_id: int
    cluster: ClusterConfig
    data_root: Path
    fragmenter: str = "auto"       # "auto" (anchored, TPU when present)
                                   # | "fixed" | "cdc"
                                   # | "cdc-anchored[-tpu]"
                                   # (FRAGMENTER_KINDS)
    sidecar_port: int | None = None  # delegate chunk+hash to a sidecar
                                     # process (overrides `fragmenter`)
    cdc: CDCParams = dataclasses.field(default_factory=CDCParams)
    # fragmenter execution knobs (the anchored walk over several devices);
    # the default FragmenterConfig() is single-device
    frag: FragmenterConfig = dataclasses.field(
        default_factory=FragmenterConfig)
    fixed_parts: int = 5           # FixedFragmenter part count (reference: TOTAL_NODES=5)
    connect_timeout_s: float = 2.0  # reference: 2000 ms, StorageNode.java:229-230
    request_timeout_s: float = 10.0
    retries: int = 3               # reference: 3 attempts, StorageNode.java:208,320
    health_probe_s: float = 5.0    # peer health probe interval; 0 = data-path
                                   # feedback only (no background loop)
    # Write policy: the reference aborts the whole upload if ANY peer is
    # down (StorageNode.java:218-221) — write-all, guaranteeing 2 copies or
    # failure. Quorum 2 (counting the local copy) keeps that >=2-copies
    # durability; sloppy-quorum handoff in upload() keeps availability as
    # long as any 2 nodes are reachable, and repair restores canonical
    # placement. quorum=1 would return 201 with a single copy in the world
    # when every peer is down — weaker than the reference (VERDICT r1 §6).
    write_quorum: int = 2
    # read-path serving tier (cache / coalescing / shedding / readahead);
    # default ServeConfig() disables every component
    serve: ServeConfig = dataclasses.field(default_factory=ServeConfig)
    # write-path pipeline bounds (window / credits / per-peer slices);
    # IngestConfig(window=1, slice_inflight=1) = the serial write path
    ingest: IngestConfig = dataclasses.field(default_factory=IngestConfig)
    # observability: span ring + slow threshold; ObsConfig(trace_ring=0)
    # turns tracing fully off (metrics remain)
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)
    # cluster census & capacity plane: metrics-history sampler bounds +
    # census finding-list caps; CensusConfig(history_interval_s=0)
    # disables the sampler (census queries stay available)
    census: CensusConfig = dataclasses.field(default_factory=CensusConfig)
    # write-path durability: fsync-before-ack (default) vs bare atomic
    # renames; DurabilityConfig(mode="none") = the pre-r13 write path
    durability: DurabilityConfig = dataclasses.field(
        default_factory=DurabilityConfig)
    # deterministic fault injection (dfs_tpu.chaos); the default
    # ChaosConfig() builds NO injector — every seam is one None check
    chaos: ChaosConfig = dataclasses.field(default_factory=ChaosConfig)
    # elastic membership (dfs_tpu.ring): the default RingConfig()
    # compiles the boot peer list into a static epoch-0 ring whose
    # placement is byte-identical to the pre-r14 cyclic replica sets
    ring: RingConfig = dataclasses.field(default_factory=RingConfig)
    # dedup/index plane (dfs_tpu.index): the default IndexConfig()
    # builds NO index and NO filters — local existence stays one stat,
    # placement probes every digest over RPC (pre-r16 paths exactly)
    index: IndexConfig = dataclasses.field(default_factory=IndexConfig)
    # hot/cold tiering plane (dfs_tpu.tier): the default TierConfig()
    # builds NO ledger and NO worker — reads, repair and census run
    # byte-identical code paths to a pre-tier build
    tier: TierConfig = dataclasses.field(default_factory=TierConfig)
    # similarity-compression plane (dfs_tpu.sim): the default
    # SimConfig() builds NO sketcher, NO band index and NO delta tree —
    # the CAS stores raw chunk files on pre-sim code paths exactly
    sim: SimConfig = dataclasses.field(default_factory=SimConfig)

    @property
    def self_addr(self) -> PeerAddr:
        return self.cluster.peer(self.node_id)

    def to_json(self) -> str:
        def enc(o):
            if dataclasses.is_dataclass(o) and not isinstance(o, type):
                return dataclasses.asdict(o)
            if isinstance(o, Path):
                return str(o)
            raise TypeError(type(o))
        return json.dumps(self, default=enc, indent=2)
