"""dfslint: fixture-driven true-positive/true-negative coverage for every
rule, the suppression/baseline machinery, the walker's non-source-tree
skipping, the CLI exit-code contract — and the real tree staying clean
modulo the committed baseline (the enforcement half, mirroring
test_check_artifacts.py).

Fixture snippets are deliberately tiny and self-contained: each
seeded-violation snippet must trip EXACTLY its rule, and each clean
snippet must trip nothing — that is what keeps the analyzer honest as
rules evolve.
"""

from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from scripts.dfslint import analyze, load_baseline  # noqa: E402
from scripts.dfslint.core import DEFAULT_BASELINE  # noqa: E402
from scripts.dfslint.__main__ import DEFAULT_ROOTS  # noqa: E402


def lint(tmp_path: Path, files: dict[str, str],
         baseline: set[str] = frozenset()) -> list:
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    return analyze(["."], tmp_path, baseline=baseline)


def rules_of(findings) -> list[str]:
    return [f.rule for f in findings]


# ------------------------------------------------------------------ #
# DFS001 — blocking call in async def
# ------------------------------------------------------------------ #

def test_dfs001_true_positives(tmp_path):
    found = lint(tmp_path, {"mod.py": (
        "import time\n"
        "async def a():\n"
        "    time.sleep(1)\n"
        "async def b():\n"
        "    open('/tmp/x')\n"
        "async def c(self):\n"
        "    self.store.chunks.put('d', b'x')\n"
        "async def d(self):\n"
        "    return self.store.chunks.get('d')\n")})
    assert rules_of(found) == ["DFS001"] * 4
    assert all(f.path == "mod.py" for f in found)


def test_dfs001_true_negatives(tmp_path):
    # sync defs may block; to_thread-wrapped lambdas/closures are a
    # different (thread) scope — exactly the runtime's store_all shape;
    # the async CAS tier (self.cas) is the sanctioned route
    found = lint(tmp_path, {"mod.py": (
        "import asyncio, time\n"
        "def sync_ok():\n"
        "    time.sleep(1)\n"
        "    open('/tmp/x')\n"
        "async def wrapped(self):\n"
        "    def store_all():\n"
        "        return self.store.chunks.put('d', b'x')\n"
        "    await asyncio.to_thread(store_all)\n"
        "    await asyncio.to_thread(lambda: self.store.chunks.get('d'))\n"
        "async def via_cas(self):\n"
        "    await self.cas.put('d', b'x')\n"
        "    return await self.cas.get('d')\n"
        "async def dict_get_ok(header):\n"
        "    return header.get('digest')\n")})
    assert found == []


# ------------------------------------------------------------------ #
# DFS002 — dropped task
# ------------------------------------------------------------------ #

def test_dfs002_true_positive(tmp_path):
    found = lint(tmp_path, {"mod.py": (
        "import asyncio\n"
        "async def spawn(work):\n"
        "    asyncio.create_task(work())\n"
        "async def spawn2(loop, work):\n"
        "    loop.create_task(work())\n")})
    assert rules_of(found) == ["DFS002", "DFS002"]


def test_dfs002_true_negatives(tmp_path):
    found = lint(tmp_path, {"mod.py": (
        "import asyncio\n"
        "async def kept(work, tasks):\n"
        "    t = asyncio.create_task(work())\n"
        "    tasks.append(asyncio.create_task(work()))\n"
        "    asyncio.create_task(work()).add_done_callback(print)\n"
        "    await asyncio.create_task(work())\n"
        "    return t\n")})
    assert found == []


# ------------------------------------------------------------------ #
# DFS003 — lock discipline
# ------------------------------------------------------------------ #

def test_dfs003_await_under_thread_lock(tmp_path):
    found = lint(tmp_path, {"mod.py": (
        "async def bad(self, fetch):\n"
        "    with self._lock:\n"
        "        await fetch()\n")})
    assert rules_of(found) == ["DFS003"]
    assert "await while holding thread lock" in found[0].message


def test_dfs003_lock_true_negatives(tmp_path):
    found = lint(tmp_path, {"mod.py": (
        "async def ok_async_lock(self, fetch):\n"
        "    async with self._alock:\n"   # asyncio.Lock idiom
        "        await fetch()\n"
        "async def ok_no_await(self):\n"
        "    with self._lock:\n"
        "        self.n += 1\n"
        "async def ok_nested_def(self, pool):\n"
        "    def job():\n"
        "        with self._lock:\n"
        "            self.n += 1\n"
        "    return job\n")})
    assert found == []


def test_dfs003_executor_dispatched_loop_affinity(tmp_path):
    found = lint(tmp_path, {"mod.py": (
        "import asyncio\n"
        "async def run(outq):\n"
        "    def worker():\n"
        "        outq.put_nowait(1)\n"       # loop-affine from a thread
        "    await asyncio.to_thread(worker)\n")})
    assert rules_of(found) == ["DFS003"]
    assert "executor thread" in found[0].message


def test_dfs003_call_soon_threadsafe_is_clean(tmp_path):
    # the runtime's on_chunk/run_fragmenter shape: the primitive is
    # REFERENCED as a call_soon_threadsafe argument, never called there
    found = lint(tmp_path, {"mod.py": (
        "import asyncio\n"
        "async def run(loop, outq):\n"
        "    def worker():\n"
        "        loop.call_soon_threadsafe(outq.put_nowait, 1)\n"
        "    await asyncio.to_thread(worker)\n")})
    assert found == []


# ------------------------------------------------------------------ #
# DFS004 — digest boundary
# ------------------------------------------------------------------ #

def test_dfs004_true_positive_and_allowed_trees(tmp_path):
    files = {
        "dfs_tpu/node/x.py": ("import hashlib\n"
                              "def f(b):\n"
                              "    return hashlib.sha256(b).hexdigest()\n"),
        "dfs_tpu/ops/kernel.py": ("import hashlib\n"
                                  "def g(b):\n"
                                  "    return hashlib.sha256(b).digest()\n"),
        "dfs_tpu/utils/hashing.py": ("import hashlib\n"
                                     "def sha256_hex(b):\n"
                                     "    return hashlib.sha256(b)"
                                     ".hexdigest()\n"),
    }
    found = lint(tmp_path, files)
    assert rules_of(found) == ["DFS004"]
    assert found[0].path == "dfs_tpu/node/x.py"


def test_dfs004_other_algorithms_flagged(tmp_path):
    found = lint(tmp_path, {"dfs_tpu/node/y.py": (
        "import hashlib\n"
        "def f(b):\n"
        "    return hashlib.md5(b).hexdigest()\n")})
    assert rules_of(found) == ["DFS004"]


# ------------------------------------------------------------------ #
# DFS005 — config drift
# ------------------------------------------------------------------ #

_MINI_CONFIG = (
    "import dataclasses\n"
    "@dataclasses.dataclass(frozen=True)\n"
    "class ServeConfig:\n"
    "    cache_bytes: int = 0\n"
    "    retry_after_s: float = 1.0\n")

_MINI_CLI_OK = (
    "from dfs_tpu.config import ServeConfig\n"
    "def cmd_serve(args):\n"
    "    return ServeConfig(cache_bytes=args.cache_bytes,\n"
    "                       retry_after_s=args.retry_after)\n"
    "def build_parser(sub):\n"
    "    sub.add_argument('--cache-bytes', type=int, default=0)\n"
    "    sub.add_argument('--retry-after', type=float, default=1.0)\n")


def test_dfs005_missing_cli_field(tmp_path):
    cli = (
        "from dfs_tpu.config import ServeConfig\n"
        "def cmd_serve(args):\n"
        "    return ServeConfig(cache_bytes=args.cache_bytes)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--cache-bytes', type=int, default=0)\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": _MINI_CONFIG,
                            "dfs_tpu/cli/main.py": cli})
    assert rules_of(found) == ["DFS005"]
    assert "ServeConfig.retry_after_s" in found[0].message


def test_dfs005_init_false_skipped_but_explicit_init_true_checked(tmp_path):
    """Only init=False fields are exempt from the CLI-wiring check
    (code-review regression: any field() mentioning the init kwarg used
    to escape, so `init=True` hid exactly the drift the rule exists
    for)."""
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class ServeConfig:\n"
        "    cache_bytes: int = 0\n"
        "    derived: int = dataclasses.field(default=1, init=False)\n"
        "    explicit: int = dataclasses.field(default=2, init=True)\n")
    cli = (
        "from dfs_tpu.config import ServeConfig\n"
        "def cmd_serve(args):\n"
        "    return ServeConfig(cache_bytes=args.cache_bytes)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--cache-bytes', type=int, default=0)\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli})
    assert rules_of(found) == ["DFS005"]
    assert "ServeConfig.explicit" in found[0].message


def test_dfs005_dead_flag(tmp_path):
    cli = _MINI_CLI_OK + (
        "def more(sub):\n"
        "    sub.add_argument('--never-read', type=int, default=0)\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": _MINI_CONFIG,
                            "dfs_tpu/cli/main.py": cli})
    assert rules_of(found) == ["DFS005"]
    assert "never_read" in found[0].message


def test_dfs005_getattr_counts_as_read(tmp_path):
    cli = _MINI_CLI_OK + (
        "def more(sub):\n"
        "    sub.add_argument('--via-getattr', type=int, default=0)\n"
        "def uses(args):\n"
        "    return getattr(args, 'via_getattr', 0)\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": _MINI_CONFIG,
                            "dfs_tpu/cli/main.py": cli})
    assert found == []


def test_dfs005_metrics_counterpart(tmp_path):
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class IngestConfig:\n"
        "    window: int = 2\n")
    runtime_missing = (
        "class S:\n"
        "    def ingest_stats(self):\n"
        "        return {'somethingElse': 1}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/node/runtime.py": runtime_missing})
    assert rules_of(found) == ["DFS005"]
    assert "window" in found[0].message

    runtime_ok = (
        "class S:\n"
        "    def ingest_stats(self):\n"
        "        return {'window': 2}\n")
    assert lint(tmp_path, {"dfs_tpu/config.py": cfg,
                           "dfs_tpu/node/runtime.py": runtime_ok}) == []


def test_dfs005_census_fields_checked(tmp_path):
    """r12: CensusConfig rides all three DFS005 edges — a census/history
    field dropped from the cmd_serve constructor, and one whose
    /metrics key vanishes from census_stats(), must both be findings;
    the fully-wired fixture must be clean."""
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class CensusConfig:\n"
        "    history_interval_s: float = 10.0\n"
        "    max_listed: int = 64\n")
    cli_missing = (
        "from dfs_tpu.config import CensusConfig\n"
        "def cmd_serve(args):\n"
        "    return CensusConfig(history_interval_s=args.census_interval)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--census-interval', type=float,\n"
        "                     default=10.0)\n")
    runtime_ok = (
        "class S:\n"
        "    def census_stats(self):\n"
        "        return {'historyIntervalS': 10.0, 'maxListed': 64}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_missing,
                            "dfs_tpu/node/runtime.py": runtime_ok})
    assert rules_of(found) == ["DFS005"]
    assert "CensusConfig.max_listed" in found[0].message

    runtime_missing_key = (
        "class S:\n"
        "    def census_stats(self):\n"
        "        return {'historyIntervalS': 10.0}\n")
    cli_ok = (
        "from dfs_tpu.config import CensusConfig\n"
        "def cmd_serve(args):\n"
        "    return CensusConfig(history_interval_s=args.census_interval,\n"
        "                        max_listed=args.census_max_listed)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--census-interval', type=float,\n"
        "                     default=10.0)\n"
        "    sub.add_argument('--census-max-listed', type=int,\n"
        "                     default=64)\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_ok,
                            "dfs_tpu/node/runtime.py":
                            runtime_missing_key})
    assert rules_of(found) == ["DFS005"]
    assert "maxListed" in found[0].message

    assert lint(tmp_path, {"dfs_tpu/config.py": cfg,
                           "dfs_tpu/cli/main.py": cli_ok,
                           "dfs_tpu/node/runtime.py": runtime_ok}) == []


def test_dfs005_frag_fields_checked(tmp_path):
    """r15: FragmenterConfig rides all three DFS005 edges — a sharding
    knob dropped from cmd_serve's constructor, and one whose /metrics
    key vanishes from frag_stats(), must both be findings; the wired
    fixture must be clean. (staging_buffers is the r15 field this
    drift-gate exists for.)"""
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class FragmenterConfig:\n"
        "    devices: int = 0\n"
        "    staging_buffers: int = 2\n")
    cli_missing = (
        "from dfs_tpu.config import FragmenterConfig\n"
        "def cmd_serve(args):\n"
        "    return FragmenterConfig(devices=args.cdc_devices)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--cdc-devices', type=int, default=0)\n")
    runtime_ok = (
        "class S:\n"
        "    def frag_stats(self):\n"
        "        return {'devices': 0, 'stagingBuffers': 2}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_missing,
                            "dfs_tpu/node/runtime.py": runtime_ok})
    assert rules_of(found) == ["DFS005"]
    assert "FragmenterConfig.staging_buffers" in found[0].message

    cli_ok = (
        "from dfs_tpu.config import FragmenterConfig\n"
        "def cmd_serve(args):\n"
        "    return FragmenterConfig(devices=args.cdc_devices,\n"
        "                            staging_buffers=args.cdc_staging)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--cdc-devices', type=int, default=0)\n"
        "    sub.add_argument('--cdc-staging', type=int, default=2)\n")
    runtime_missing_key = (
        "class S:\n"
        "    def frag_stats(self):\n"
        "        return {'devices': 0}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_ok,
                            "dfs_tpu/node/runtime.py":
                            runtime_missing_key})
    assert rules_of(found) == ["DFS005"]
    assert "stagingBuffers" in found[0].message

    assert lint(tmp_path, {"dfs_tpu/config.py": cfg,
                           "dfs_tpu/cli/main.py": cli_ok,
                           "dfs_tpu/node/runtime.py": runtime_ok}) == []


def test_dfs005_chaos_fields_checked(tmp_path):
    """r13: ChaosConfig rides the same three DFS005 edges — a chaos
    knob dropped from cmd_serve's constructor, and one whose /metrics
    key vanishes from ChaosInjector.stats() (the chaos-package stats
    source), must both be findings; the wired fixture must be clean."""
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class ChaosConfig:\n"
        "    enabled: bool = False\n"
        "    crash_point: str = ''\n")
    cli_missing = (
        "from dfs_tpu.config import ChaosConfig\n"
        "def cmd_serve(args):\n"
        "    return ChaosConfig(enabled=args.chaos)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--chaos', action='store_true')\n")
    chaos_ok = (
        "class ChaosInjector:\n"
        "    def stats(self):\n"
        "        return {'enabled': True, 'crashPoint': ''}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_missing,
                            "dfs_tpu/chaos/__init__.py": chaos_ok})
    assert rules_of(found) == ["DFS005"]
    assert "ChaosConfig.crash_point" in found[0].message

    cli_ok = (
        "from dfs_tpu.config import ChaosConfig\n"
        "def cmd_serve(args):\n"
        "    return ChaosConfig(enabled=args.chaos,\n"
        "                       crash_point=args.chaos_crash_point)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--chaos', action='store_true')\n"
        "    sub.add_argument('--chaos-crash-point', default='')\n")
    chaos_missing_key = (
        "class ChaosInjector:\n"
        "    def stats(self):\n"
        "        return {'enabled': True}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_ok,
                            "dfs_tpu/chaos/__init__.py":
                            chaos_missing_key})
    assert rules_of(found) == ["DFS005"]
    assert "crashPoint" in found[0].message

    assert lint(tmp_path, {"dfs_tpu/config.py": cfg,
                           "dfs_tpu/cli/main.py": cli_ok,
                           "dfs_tpu/chaos/__init__.py": chaos_ok}) == []


def test_dfs005_ring_fields_checked(tmp_path):
    """r14: RingConfig rides the same three DFS005 edges — a membership
    knob dropped from cmd_serve's constructor, and one whose /metrics
    key vanishes from ring_stats(), must both be findings; the wired
    fixture must be clean."""
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class RingConfig:\n"
        "    vnodes: int = 0\n"
        "    rebalance_credit_bytes: int = 0\n")
    cli_missing = (
        "from dfs_tpu.config import RingConfig\n"
        "def cmd_serve(args):\n"
        "    return RingConfig(vnodes=args.ring_vnodes)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--ring-vnodes', type=int, default=0)\n")
    runtime_ok = (
        "class S:\n"
        "    def ring_stats(self):\n"
        "        return {'vnodes': 0, 'rebalanceCreditBytes': 0}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_missing,
                            "dfs_tpu/node/runtime.py": runtime_ok})
    assert rules_of(found) == ["DFS005"]
    assert "RingConfig.rebalance_credit_bytes" in found[0].message

    cli_ok = (
        "from dfs_tpu.config import RingConfig\n"
        "def cmd_serve(args):\n"
        "    return RingConfig(vnodes=args.ring_vnodes,\n"
        "                      rebalance_credit_bytes="
        "args.ring_rebalance_credit_bytes)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--ring-vnodes', type=int, default=0)\n"
        "    sub.add_argument('--ring-rebalance-credit-bytes',\n"
        "                     type=int, default=0)\n")
    runtime_missing_key = (
        "class S:\n"
        "    def ring_stats(self):\n"
        "        return {'vnodes': 0}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_ok,
                            "dfs_tpu/node/runtime.py":
                            runtime_missing_key})
    assert rules_of(found) == ["DFS005"]
    assert "rebalanceCreditBytes" in found[0].message

    assert lint(tmp_path, {"dfs_tpu/config.py": cfg,
                           "dfs_tpu/cli/main.py": cli_ok,
                           "dfs_tpu/node/runtime.py": runtime_ok}) == []


def test_dfs005_index_fields_checked(tmp_path):
    """r16: IndexConfig rides the same three DFS005 edges — a dedup/
    index knob dropped from cmd_serve's constructor, and one whose
    /metrics key vanishes from index_stats(), must both be findings;
    the wired fixture must be clean."""
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class IndexConfig:\n"
        "    enabled: bool = False\n"
        "    filter_sync_s: float = 5.0\n")
    cli_missing = (
        "from dfs_tpu.config import IndexConfig\n"
        "def cmd_serve(args):\n"
        "    return IndexConfig(enabled=args.index)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--index', action='store_true')\n")
    runtime_ok = (
        "class S:\n"
        "    def index_stats(self):\n"
        "        return {'enabled': False, 'filterSyncS': 5.0}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_missing,
                            "dfs_tpu/node/runtime.py": runtime_ok})
    assert rules_of(found) == ["DFS005"]
    assert "IndexConfig.filter_sync_s" in found[0].message

    cli_ok = (
        "from dfs_tpu.config import IndexConfig\n"
        "def cmd_serve(args):\n"
        "    return IndexConfig(enabled=args.index,\n"
        "                       filter_sync_s=args.index_filter_sync)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--index', action='store_true')\n"
        "    sub.add_argument('--index-filter-sync', type=float,\n"
        "                     default=5.0)\n")
    runtime_missing_key = (
        "class S:\n"
        "    def index_stats(self):\n"
        "        return {'enabled': False}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_ok,
                            "dfs_tpu/node/runtime.py":
                            runtime_missing_key})
    assert rules_of(found) == ["DFS005"]
    assert "filterSyncS" in found[0].message

    assert lint(tmp_path, {"dfs_tpu/config.py": cfg,
                           "dfs_tpu/cli/main.py": cli_ok,
                           "dfs_tpu/node/runtime.py": runtime_ok}) == []


def test_dfs005_tier_fields_checked(tmp_path):
    """r20: TierConfig rides the same three DFS005 edges — a tiering
    knob dropped from cmd_serve's constructor, and one whose /metrics
    key vanishes from tier_stats(), must both be findings; the wired
    fixture must be clean."""
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class TierConfig:\n"
        "    hot_fraction: float = 0.1\n"
        "    ec_k: int = 4\n")
    cli_missing = (
        "from dfs_tpu.config import TierConfig\n"
        "def cmd_serve(args):\n"
        "    return TierConfig(hot_fraction=args.tier_hot_fraction)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--tier-hot-fraction', type=float,\n"
        "                     default=0.1)\n")
    runtime_ok = (
        "class S:\n"
        "    def tier_stats(self):\n"
        "        return {'hotFraction': 0.1, 'ecK': 4}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_missing,
                            "dfs_tpu/node/runtime.py": runtime_ok})
    assert rules_of(found) == ["DFS005"]
    assert "TierConfig.ec_k" in found[0].message

    cli_ok = (
        "from dfs_tpu.config import TierConfig\n"
        "def cmd_serve(args):\n"
        "    return TierConfig(hot_fraction=args.tier_hot_fraction,\n"
        "                      ec_k=args.tier_ec_k)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--tier-hot-fraction', type=float,\n"
        "                     default=0.1)\n"
        "    sub.add_argument('--tier-ec-k', type=int, default=4)\n")
    runtime_missing_key = (
        "class S:\n"
        "    def tier_stats(self):\n"
        "        return {'hotFraction': 0.1}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_ok,
                            "dfs_tpu/node/runtime.py":
                            runtime_missing_key})
    assert rules_of(found) == ["DFS005"]
    assert "ecK" in found[0].message

    assert lint(tmp_path, {"dfs_tpu/config.py": cfg,
                           "dfs_tpu/cli/main.py": cli_ok,
                           "dfs_tpu/node/runtime.py": runtime_ok}) == []


def test_dfs005_sim_fields_checked(tmp_path):
    """r21: SimConfig rides the same three DFS005 edges — a similarity
    knob dropped from cmd_serve's constructor, and one whose /metrics
    key vanishes from sim_stats(), must both be findings; the wired
    fixture must be clean."""
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class SimConfig:\n"
        "    enabled: bool = False\n"
        "    max_delta_depth: int = 3\n")
    cli_missing = (
        "from dfs_tpu.config import SimConfig\n"
        "def cmd_serve(args):\n"
        "    return SimConfig(enabled=args.sim)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--sim', action='store_true')\n")
    runtime_ok = (
        "class S:\n"
        "    def sim_stats(self):\n"
        "        return {'enabled': False, 'maxDeltaDepth': 3}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_missing,
                            "dfs_tpu/node/runtime.py": runtime_ok})
    assert rules_of(found) == ["DFS005"]
    assert "SimConfig.max_delta_depth" in found[0].message

    cli_ok = (
        "from dfs_tpu.config import SimConfig\n"
        "def cmd_serve(args):\n"
        "    return SimConfig(enabled=args.sim,\n"
        "                     max_delta_depth=args.sim_max_delta_depth)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--sim', action='store_true')\n"
        "    sub.add_argument('--sim-max-delta-depth', type=int,\n"
        "                     default=3)\n")
    runtime_missing_key = (
        "class S:\n"
        "    def sim_stats(self):\n"
        "        return {'enabled': False}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_ok,
                            "dfs_tpu/node/runtime.py":
                            runtime_missing_key})
    assert rules_of(found) == ["DFS005"]
    assert "maxDeltaDepth" in found[0].message

    assert lint(tmp_path, {"dfs_tpu/config.py": cfg,
                           "dfs_tpu/cli/main.py": cli_ok,
                           "dfs_tpu/node/runtime.py": runtime_ok}) == []


def test_dfs005_deadline_hedge_fields_checked(tmp_path):
    """r18: the ServeConfig deadline/hedge fields ride the same three
    DFS005 edges — a deadline/hedge knob dropped from cmd_serve's
    ServeConfig(...) call, and one whose /metrics key vanishes from
    ServingTier.stats(), must both be findings; the fully-wired
    fixture must be clean."""
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class ServeConfig:\n"
        "    default_deadline_s: float = 0.0\n"
        "    hedge_budget_per_s: float = 0.0\n")
    cli_missing = (
        "from dfs_tpu.config import ServeConfig\n"
        "def cmd_serve(args):\n"
        "    return ServeConfig(default_deadline_s="
        "args.default_deadline)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--default-deadline', type=float,\n"
        "                     default=0.0)\n")
    serve_ok = (
        "class ServingTier:\n"
        "    def stats(self):\n"
        "        return {'defaultDeadlineS': 0.0,\n"
        "                'hedge': {'enabled': False}}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_missing,
                            "dfs_tpu/serve/__init__.py": serve_ok})
    assert rules_of(found) == ["DFS005"]
    assert "ServeConfig.hedge_budget_per_s" in found[0].message

    cli_ok = (
        "from dfs_tpu.config import ServeConfig\n"
        "def cmd_serve(args):\n"
        "    return ServeConfig(default_deadline_s="
        "args.default_deadline,\n"
        "                       hedge_budget_per_s=args.hedge_budget)\n"
        "def build_parser(sub):\n"
        "    sub.add_argument('--default-deadline', type=float,\n"
        "                     default=0.0)\n"
        "    sub.add_argument('--hedge-budget', type=float,\n"
        "                     default=0.0)\n")
    serve_missing_key = (
        "class ServingTier:\n"
        "    def stats(self):\n"
        "        return {'defaultDeadlineS': 0.0}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/cli/main.py": cli_ok,
                            "dfs_tpu/serve/__init__.py":
                            serve_missing_key})
    assert rules_of(found) == ["DFS005"]
    assert "hedge" in found[0].message

    assert lint(tmp_path, {"dfs_tpu/config.py": cfg,
                           "dfs_tpu/cli/main.py": cli_ok,
                           "dfs_tpu/serve/__init__.py": serve_ok}) == []


def test_dfs005_unmapped_field_needs_table_entry(tmp_path):
    cfg = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class IngestConfig:\n"
        "    window: int = 2\n"
        "    brand_new_knob: int = 0\n")
    runtime = ("class S:\n"
               "    def ingest_stats(self):\n"
               "        return {'window': 2}\n")
    found = lint(tmp_path, {"dfs_tpu/config.py": cfg,
                            "dfs_tpu/node/runtime.py": runtime})
    assert rules_of(found) == ["DFS005"]
    assert "no /metrics mapping" in found[0].message


# ------------------------------------------------------------------ #
# DFS006 — data-plane copy discipline
# ------------------------------------------------------------------ #

def test_dfs006_true_positives(tmp_path):
    src = (
        "def assemble(parts, mv):\n"
        "    body = b''.join(parts)\n"
        "    owned = bytes(mv)\n"
        "    return body, owned\n")
    found = lint(tmp_path / "a", {"dfs_tpu/comm/rpc.py": src})
    assert rules_of(found) == ["DFS006", "DFS006"]
    assert "join" in found[0].context and "bytes" in found[1].context
    # node/runtime.py and serve/ are data plane too
    found = lint(tmp_path / "b", {"dfs_tpu/node/runtime.py": src})
    assert rules_of(found) == ["DFS006", "DFS006"]
    found = lint(tmp_path / "c", {"dfs_tpu/serve/cache.py": src})
    assert rules_of(found) == ["DFS006", "DFS006"]


def test_dfs006_scoped_to_data_plane_modules(tmp_path):
    """The same idioms OUTSIDE the data-plane modules are fine — cold
    paths (CLI, fragmenter host walks, tests) may join freely."""
    src = ("def f(parts, mv):\n"
           "    return b''.join(parts), bytes(mv)\n")
    assert lint(tmp_path / "a", {"dfs_tpu/cli/client.py": src}) == []
    assert lint(tmp_path / "b", {"dfs_tpu/fragmenter/stream.py": src}) == []


def test_dfs006_true_negatives(tmp_path):
    # separators with content, str joins on non-empty separators,
    # bytes() literals/empty constructors, and annotated ownership
    # copies are all allowed
    found = lint(tmp_path, {"dfs_tpu/comm/wire.py": (
        "def ok(parts, n, data):\n"
        "    a = b','.join(parts)\n"
        "    b = bytes(8)\n"          # bytes(int) is an alloc, not a copy
        "    c = bytes()\n"
        "    d = ', '.join(str(p) for p in parts)\n"
        "    e = bytes(data)  # dfslint: ignore[DFS006] - ownership copy\n"
        "    f = ''.join(c for c in data)\n"  # str join copies no payload
        "    return a, b, c, d, e, f\n")})
    # bytes(8): the arg is a constant -> not flagged; bytes(data) is
    # suppressed inline; everything else is out of pattern
    assert found == []


# ------------------------------------------------------------------ #
# DFS007 — silent swallow of failure-class exceptions
# ------------------------------------------------------------------ #

def test_dfs007_true_positives(tmp_path):
    src = (
        "class C:\n"
        "    async def probe(self, peer):\n"
        "        try:\n"
        "            await self.client.call(peer, {})\n"
        "        except RpcError:\n"
        "            pass\n"
        "    def read(self, p):\n"
        "        try:\n"
        "            return open(p).read()\n"
        "        except OSError:\n"
        "            return None\n"
        "    def any_at_all(self):\n"
        "        try:\n"
        "            self.work()\n"
        "        except:\n"
        "            pass\n")
    found = lint(tmp_path, {"dfs_tpu/comm/rpc.py": src})
    assert rules_of(found) == ["DFS007"] * 3
    assert "swallow-RpcError" in found[0].context
    assert "swallow-bare except" in found[2].context


def test_dfs007_scoped_to_data_plane_and_runtime(tmp_path):
    """The same silence outside comm//node//serve//store is fine — in
    api/ the error response IS the signal, cli/ is interactive."""
    src = ("def f(self):\n"
           "    try:\n"
           "        self.work()\n"
           "    except OSError:\n"
           "        pass\n")
    assert lint(tmp_path / "a", {"dfs_tpu/api/http.py": src}) == []
    assert lint(tmp_path / "b", {"dfs_tpu/cli/main.py": src}) == []
    assert rules_of(lint(tmp_path / "c",
                         {"dfs_tpu/store/cas.py": src})) == ["DFS007"]


def test_dfs007_evidence_forms_are_clean(tmp_path):
    """Every sanctioned way of leaving a trace: log, journal event,
    counter, liveness transition, waiter propagation, re-raise."""
    found = lint(tmp_path, {"dfs_tpu/node/runtime.py": (
        "class C:\n"
        "    async def a(self, peer):\n"
        "        try:\n"
        "            await self.client.call(peer, {})\n"
        "        except RpcError:\n"
        "            self.log.warning('x')\n"
        "    async def b(self, peer):\n"
        "        try:\n"
        "            await self.client.call(peer, {})\n"
        "        except RpcError:\n"
        "            self.obs.event('rpc_fail', peer=1)\n"
        "    async def c(self, peer):\n"
        "        try:\n"
        "            await self.client.call(peer, {})\n"
        "        except RpcError:\n"
        "            self.counters.inc('probe_failures')\n"
        "    async def d(self, peer):\n"
        "        try:\n"
        "            await self.client.call(peer, {})\n"
        "        except RpcUnreachable:\n"
        "            self.health.mark_dead(peer.node_id)\n"
        "    async def e(self, fut):\n"
        "        try:\n"
        "            await self.run()\n"
        "        except OSError as exc:\n"
        "            fut.set_exception(exc)\n"
        "    async def f(self):\n"
        "        try:\n"
        "            await self.run()\n"
        "        except OSError:\n"
        "            raise RuntimeError('ctx')\n")})
    assert found == []


def test_dfs007_absence_as_result_types_are_clean(tmp_path):
    """FileNotFoundError/KeyError/queue.Empty et al are control flow —
    swallowing them is how optional lookups are written."""
    found = lint(tmp_path, {"dfs_tpu/store/cas.py": (
        "import queue\n"
        "def f(self, p, q):\n"
        "    try:\n"
        "        return open(p).read()\n"
        "    except FileNotFoundError:\n"
        "        pass\n"
        "    try:\n"
        "        return q.get_nowait()\n"
        "    except queue.Empty:\n"
        "        return None\n")})
    assert found == []


def test_dfs007_inline_ignore(tmp_path):
    found = lint(tmp_path, {"dfs_tpu/store/cas.py": (
        "def f(self, p):\n"
        "    try:\n"
        "        return open(p).read()\n"
        "    except OSError:  # dfslint: ignore[DFS007]\n"
        "        return None\n")})
    assert found == []


# ------------------------------------------------------------------ #
# suppressions, baseline, walker, parse errors
# ------------------------------------------------------------------ #

def test_inline_suppression_same_line_and_comment_above(tmp_path):
    found = lint(tmp_path, {"mod.py": (
        "import time\n"
        "async def a():\n"
        "    time.sleep(1)  # dfslint: ignore[DFS001]\n"
        "async def b():\n"
        "    # justification lives here\n"
        "    # dfslint: ignore[DFS001]\n"
        "    time.sleep(1)\n"
        "async def c():\n"
        "    time.sleep(1)  # dfslint: ignore[DFS004]\n")})
    # a and b are suppressed; c's suppression names the WRONG rule —
    # since r17 that dead suppression is ALSO a DFS000 audit warning
    assert sorted(rules_of(found)) == ["DFS000", "DFS001"]
    f001 = next(f for f in found if f.rule == "DFS001")
    assert f001.context.startswith("c:")
    f000 = next(f for f in found if f.rule == "DFS000")
    assert "DFS004" in f000.message and f000.severity == "warning"


def test_baseline_accepts_by_stable_key(tmp_path):
    files = {"mod.py": ("import time\n"
                        "async def a():\n"
                        "    time.sleep(1)\n")}
    found = lint(tmp_path, dict(files))
    assert rules_of(found) == ["DFS001"]
    assert found[0].key == f"DFS001:mod.py:{found[0].context}"
    assert lint(tmp_path, {}, baseline={found[0].key}) == []


def test_walker_skips_pycache_and_data_trees(tmp_path):
    found = lint(tmp_path, {
        "pkg/__pycache__/evil.py": ("import time\n"
                                    "async def a():\n"
                                    "    time.sleep(1)\n"),
        "data/leftover.py": ("import time\n"
                             "async def a():\n"
                             "    time.sleep(1)\n"),
        "pkg/ok.py": "x = 1\n"})
    assert found == []


def test_parse_error_is_a_finding_not_a_crash(tmp_path):
    found = lint(tmp_path, {"mod.py": "def broken(:\n"})
    assert rules_of(found) == ["DFS000"]


# ------------------------------------------------------------------ #
# CLI contract
# ------------------------------------------------------------------ #

def _cli(args, cwd=REPO):
    return subprocess.run([sys.executable, "-m", "scripts.dfslint", *args],
                          cwd=cwd, capture_output=True, text=True,
                          timeout=120)


def test_cli_exit_codes_and_json(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def a():\n    time.sleep(1)\n")
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")

    r = _cli([str(ok)])
    assert r.returncode == 0, r.stderr

    r = _cli([str(bad)])
    assert r.returncode == 1
    assert "DFS001" in r.stdout

    r = _cli([str(tmp_path / "does_not_exist")])
    assert r.returncode == 2

    r = _cli([str(bad), "--json"])
    out = json.loads(r.stdout)
    assert out["count"] == 1
    assert out["findings"][0]["rule"] == "DFS001"
    assert out["findings"][0]["key"].startswith("DFS001:")


def test_malformed_baseline_is_usage_error(tmp_path):
    """Exit-2 contract (code-review regression): a baseline that parses
    as JSON but lacks the accepted-keys list must be a usage error, not
    a traceback or a bogus findings exit."""
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    for bad_text in ("{}", '{"accepted": "nope"}', '{"accepted": [1]}'):
        bl = tmp_path / "bl.json"
        bl.write_text(bad_text)
        r = _cli([str(ok), "--baseline", str(bl)])
        assert r.returncode == 2, (bad_text, r.stdout, r.stderr)
        assert "malformed baseline" in r.stderr


def test_update_baseline_narrowed_scope_merges(tmp_path):
    """--update-baseline over a subset of paths must KEEP accepted keys
    for files outside the scan (code-review regression: a partial run
    used to rewrite the baseline wholesale, silently un-accepting
    everything it did not see)."""
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def a():\n    time.sleep(1)\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(
        {"accepted": ["DFS004:elsewhere/mod.py:f:hashlib.sha256"]}))

    r = _cli([str(bad), "--baseline", str(bl), "--update-baseline"])
    assert r.returncode == 0, r.stderr
    kept = json.loads(bl.read_text())["accepted"]
    assert "DFS004:elsewhere/mod.py:f:hashlib.sha256" in kept
    assert any(k.startswith("DFS001:") for k in kept)


def test_cli_update_baseline_roundtrip(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def a():\n    time.sleep(1)\n")
    bl = tmp_path / "baseline.json"

    r = _cli([str(bad), "--baseline", str(bl), "--update-baseline"])
    assert r.returncode == 0, r.stderr
    assert len(json.loads(bl.read_text())["accepted"]) == 1

    # the accepted finding no longer gates...
    assert _cli([str(bad), "--baseline", str(bl)]).returncode == 0
    # ...but a NEW violation still does
    bad.write_text(bad.read_text()
                   + "async def b():\n    time.sleep(2)\n")
    assert _cli([str(bad), "--baseline", str(bl)]).returncode == 1


# ------------------------------------------------------------------ #
# phase-1 model (r17): call graph, context inference, lock sets
# ------------------------------------------------------------------ #

def model_of(tmp_path, files):
    from scripts.dfslint.core import Project
    from scripts.dfslint.model import build_model

    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(text)
    from scripts.dfslint import collect_sources
    project = Project(collect_sources(["."], tmp_path))
    return build_model(project)


def fns_named(model, name):
    return [fi for fi in model.functions.values() if fi.name == name]


def test_model_cross_module_call_edge_and_loop_propagation(tmp_path):
    """An async def in pkg/a calling an imported sync helper from
    pkg/b: the model records a module-qualified edge and propagates
    loop affinity across the file boundary."""
    m = model_of(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/a.py": ("from pkg.b import helper\n"
                     "async def main():\n"
                     "    helper()\n"),
        "pkg/b.py": "def helper():\n    return 1\n"})
    (main,) = fns_named(m, "main")
    (helper,) = fns_named(m, "helper")
    assert helper.uid in main.callees
    assert helper.ctx == {"loop"}


def test_model_to_thread_laundering_is_worker_not_loop(tmp_path):
    """`await asyncio.to_thread(work)` seeds work as WORKER and does
    NOT create a loop-context call edge — the laundering case the
    affinity propagation must get right."""
    m = model_of(tmp_path, {"m.py": (
        "import asyncio\n"
        "async def main():\n"
        "    await asyncio.to_thread(work)\n"
        "def work():\n    return 1\n")})
    (work,) = fns_named(m, "work")
    assert work.ctx == {"worker"}


def test_model_sync_call_from_both_contexts_is_both(tmp_path):
    m = model_of(tmp_path, {"m.py": (
        "import asyncio, threading\n"
        "async def main():\n"
        "    shared()\n"
        "def boot():\n"
        "    threading.Thread(target=entry).start()\n"
        "def entry():\n"
        "    shared()\n"
        "def shared():\n    return 1\n")})
    (shared,) = fns_named(m, "shared")
    assert shared.ctx == {"loop", "worker"}


def test_model_thread_target_via_self_method(tmp_path):
    """Thread(target=self._run) — the r08 heuristic only resolved
    bare names; the model resolves bound methods."""
    m = model_of(tmp_path, {"m.py": (
        "import threading\n"
        "class J:\n"
        "    def start(self):\n"
        "        threading.Thread(target=self._run).start()\n"
        "    def _run(self):\n"
        "        return 1\n")})
    (run,) = fns_named(m, "_run")
    assert run.ctx == {"worker"}


def test_model_trampoline_dispatches_callable_args(tmp_path):
    """The AsyncChunkStore._run shape: a param reaches an executor via
    a nested def, so callables at the trampoline's CALL SITES (here a
    lambda) are worker entry points."""
    m = model_of(tmp_path, {"m.py": (
        "import asyncio\n"
        "class Pool:\n"
        "    async def _run(self, fn):\n"
        "        def job():\n"
        "            return fn()\n"
        "        loop = asyncio.get_running_loop()\n"
        "        return await loop.run_in_executor(None, job)\n"
        "    async def put(self, store):\n"
        "        return await self._run(lambda: store.put())\n")})
    lambdas = fns_named(m, "<lambda>")
    assert any("worker" in fi.ctx for fi in lambdas)


def test_model_attr_type_chain_resolution(tmp_path):
    """to_thread(self.store.manifests.save, …) — the real r13 dispatch
    shape — resolves through constructor-derived attribute types, two
    hops deep, across files."""
    m = model_of(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/store.py": ("class ManifestStore:\n"
                         "    def save(self, m):\n"
                         "        return m\n"
                         "class NodeStore:\n"
                         "    def __init__(self):\n"
                         "        self.manifests = ManifestStore()\n"),
        "pkg/rt.py": ("import asyncio\n"
                      "from pkg.store import NodeStore\n"
                      "class Runtime:\n"
                      "    def __init__(self):\n"
                      "        self.store = NodeStore()\n"
                      "    async def announce(self, m):\n"
                      "        await asyncio.to_thread("
                      "self.store.manifests.save, m)\n")})
    (save,) = fns_named(m, "save")
    assert save.ctx == {"worker"}


def test_model_lock_set_extraction_and_inheritance(tmp_path):
    """Lexical `with self._lock:` guards AND the `*_locked` caller-
    holds-it convention: a helper whose every call site holds the lock
    inherits it, so its accesses count as guarded."""
    m = model_of(tmp_path, {"m.py": (
        "import threading\n"
        "class C:\n"
        "    def bump_locked(self):\n"
        "        self.n += 1\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self.bump_locked()\n"
        "    def striped(self, fid):\n"
        "        with self._mu[0]:\n"
        "            self.k = fid\n"
        "    def factory(self, fid):\n"
        "        with self._lock_for(fid):\n"
        "            self.j = fid\n")})
    (bl,) = fns_named(m, "bump_locked")
    assert "self._lock" in m.inherited_locks(bl)
    accs = {(a.attr, a.kind): a for a in
            (x for v in m.accesses.values() for x in v)}
    assert "self._lock" in accs[("n", "write")].locks      # inherited
    assert "self._mu" in accs[("k", "write")].locks        # striped
    assert "self._lock_for" in accs[("j", "write")].locks  # factory


def test_dfs001_interprocedural_sync_helper_on_loop(tmp_path):
    """A sync helper reached ONLY from async context blocks the loop
    exactly like inline code — the call-graph upgrade of DFS001.
    Dispatching the same helper through to_thread clears it."""
    found = lint(tmp_path / "a", {"dfs_tpu/mod.py": (
        "import time\n"
        "async def serve():\n"
        "    helper()\n"
        "def helper():\n"
        "    time.sleep(1)\n")})
    assert rules_of(found) == ["DFS001"]
    assert "loop-affine" in found[0].message
    assert lint(tmp_path / "b", {"dfs_tpu/mod.py": (
        "import asyncio, time\n"
        "async def serve():\n"
        "    await asyncio.to_thread(helper)\n"
        "def helper():\n"
        "    time.sleep(1)\n")}) == []


def test_dfs001_shared_sync_async_helper_not_flagged(tmp_path):
    """Code-review regression: a helper reached from async code AND
    from an unclassified sync entry point may legitimately block on
    the sync path — loop context from one caller is not proof."""
    assert lint(tmp_path, {"dfs_tpu/mod.py": (
        "import time\n"
        "async def serve():\n"
        "    helper()\n"
        "def cli_main():\n"
        "    helper()\n"
        "def helper():\n"
        "    time.sleep(1)\n")}) == []


def test_model_add_done_callback_is_not_a_loop_seed(tmp_path):
    """Code-review regression: concurrent.futures runs done-callbacks
    on the POOL WORKER thread, so the model must leave them
    unclassified rather than bless them loop-affine."""
    m = model_of(tmp_path, {"m.py": (
        "def go(pool):\n"
        "    fut = pool.submit(work)\n"
        "    fut.add_done_callback(cb)\n"
        "def work():\n    return 1\n"
        "def cb(fut):\n    return fut\n")})
    (cb,) = fns_named(m, "cb")
    assert cb.ctx == set()


def test_dfs009_locally_owned_buffer_via_name_is_clean(tmp_path):
    """Code-review regression: `buf = bytearray(n); v = memoryview(buf)`
    is a view over memory the function OWNS — storing it must not be
    flagged (only borrowed/pooled sources are)."""
    assert lint(tmp_path, {"dfs_tpu/comm/own.py": (
        "class R:\n"
        "    def arm(self, n):\n"
        "        buf = bytearray(n)\n"
        "        v = memoryview(buf)\n"
        "        self._views.append(v)\n")}) == []


def test_dfs010_reused_resp_var_attributes_reads_in_order(tmp_path):
    """Code-review regression: reads of a REUSED response variable
    belong to the op bound at that point, not the last one."""
    files = {
        "dfs_tpu/comm/rpc.py": (
            "class Client:\n"
            "    async def both(self, peer):\n"
            "        resp, _ = await self.call(peer, {'op': 'a'})\n"
            "        x = resp.get('xa')\n"
            "        resp, _ = await self.call(peer, {'op': 'b'})\n"
            "        return x, resp.get('yb')\n"),
        "dfs_tpu/node/runtime.py": (
            "class S:\n"
            "    async def _dispatch(self, header, body):\n"
            "        op = header.get('op')\n"
            "        if op == 'a':\n"
            "            return {'ok': True, 'xa': 1}, b''\n"
            "        if op == 'b':\n"
            "            return {'ok': True, 'yb': 2}, b''\n"
            "        return {'ok': False, 'error': 'unknown'}, b''\n"),
        "dfs_tpu/comm/wire.py": (
            "OP_SPECS = {'a': {'request': [], 'reply': ['xa']},\n"
            "            'b': {'request': [], 'reply': ['yb']}}\n"),
    }
    assert lint(tmp_path, files) == []


def test_dfs001_interprocedural_scoped_to_dfs_tpu(tmp_path):
    """Bench/tool drivers keep the lexical async-def rule only: a sync
    setup helper blocking outside dfs_tpu/ is not the gated bug
    class."""
    assert lint(tmp_path, {"bench_x.py": (
        "import socket\n"
        "async def main():\n"
        "    free_port()\n"
        "def free_port():\n"
        "    return socket.socket()\n")}) == []


def test_dfs003_trampoline_reaches_loop_affine_call(tmp_path):
    """The executor-target heuristic is a call-graph fact now: a
    helper CALLED BY a thread target (not itself a target) touching a
    loop-affine primitive is flagged too."""
    found = lint(tmp_path, {"m.py": (
        "import asyncio, threading\n"
        "async def run(outq):\n"
        "    def worker():\n"
        "        helper(outq)\n"
        "    await asyncio.to_thread(worker)\n"
        "def helper(outq):\n"
        "    outq.put_nowait(1)\n")})
    assert rules_of(found) == ["DFS003"]
    assert "helper" in found[0].context


# ------------------------------------------------------------------ #
# DFS008 — thread-affinity race
# ------------------------------------------------------------------ #

# the r13 ManifestStore resurrection race, minimized: save() runs on
# CAS worker threads (to_thread), delete mutates the same state from
# the event loop, no common lock — the shape reviewers hand-caught in
# round 13, now a fixture the gate must keep catching
_R13_RACE = (
    "import asyncio\n"
    "class ManifestStore:\n"
    "    def save(self, m):\n"
    "        if m.file_id in self._tombstones:\n"
    "            return False\n"
    "        self._manifests[m.file_id] = m\n"
    "        return True\n"
    "    def delete_sync(self, file_id):\n"
    "        self._tombstones.add(file_id)\n"
    "        self._manifests.pop(file_id, None)\n"
    "class Runtime:\n"
    "    def __init__(self):\n"
    "        self.store = ManifestStore()\n"
    "    async def announce(self, m):\n"
    "        await asyncio.to_thread(self.store.save, m)\n"
    "    async def delete(self, file_id):\n"
    "        self.store.delete_sync(file_id)\n")


def test_dfs008_flags_minimized_r13_manifest_race(tmp_path):
    found = lint(tmp_path, {"dfs_tpu/meta/manifest.py": _R13_RACE})
    assert rules_of(found) == ["DFS008", "DFS008"]
    assert {f.context for f in found} == {
        "ManifestStore._manifests:affinity",
        "ManifestStore._tombstones:affinity"}
    assert "worker" in found[0].message and "loop" in found[0].message


def test_dfs008_common_lock_clears_the_race(tmp_path):
    """The r13 fix shape: both sides under one (here striped-`_mu`)
    lock — the model's guard extraction must see it."""
    fixed = _R13_RACE.replace(
        "    def save(self, m):\n"
        "        if m.file_id in self._tombstones:\n"
        "            return False\n"
        "        self._manifests[m.file_id] = m\n"
        "        return True\n",
        "    def save(self, m):\n"
        "        with self._mu:\n"
        "            if m.file_id in self._tombstones:\n"
        "                return False\n"
        "            self._manifests[m.file_id] = m\n"
        "            return True\n").replace(
        "    def delete_sync(self, file_id):\n"
        "        self._tombstones.add(file_id)\n"
        "        self._manifests.pop(file_id, None)\n",
        "    def delete_sync(self, file_id):\n"
        "        with self._mu:\n"
        "            self._tombstones.add(file_id)\n"
        "            self._manifests.pop(file_id, None)\n")
    assert lint(tmp_path, {"dfs_tpu/meta/manifest.py": fixed}) == []


def test_dfs008_single_context_state_is_clean(tmp_path):
    """Loop-only state (every toucher on the loop) needs no lock."""
    assert lint(tmp_path, {"dfs_tpu/x.py": (
        "class C:\n"
        "    async def a(self):\n"
        "        self.n += 1\n"
        "    async def b(self):\n"
        "        return self.n\n")}) == []


def test_dfs008_init_writes_do_not_count(tmp_path):
    """Construction precedes sharing: __init__ writes are not a race
    side even when workers read the attribute later."""
    assert lint(tmp_path, {"dfs_tpu/x.py": (
        "import asyncio\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self.cfg = 1\n"
        "    def job(self):\n"
        "        return self.cfg\n"
        "    async def go(self):\n"
        "        await asyncio.to_thread(self.job)\n")}) == []


# ------------------------------------------------------------------ #
# DFS009 — buffer lifetime / view escape
# ------------------------------------------------------------------ #

# the r15 staging-buffer recycle bug, minimized: a view over a POOLED
# staging buffer escapes into state that outlives the recycle guard —
# refilling the buffer then corrupts the in-flight reference (one
# flipped tail digest was the observed symptom)
_R15_RECYCLE = (
    "class ShardedStager:\n"
    "    def stage(self, n):\n"
    "        view = memoryview(self._staging_buf)[:n]\n"
    "        self._inflight.append(view)\n")


def test_dfs009_flags_minimized_r15_staging_recycle(tmp_path):
    found = lint(tmp_path, {"dfs_tpu/fragmenter/stager.py": _R15_RECYCLE})
    assert rules_of(found) == ["DFS009"]
    assert "recycled" in found[0].message


def test_dfs009_ownership_copy_is_clean(tmp_path):
    """The sanctioned fix: copy before the escape (the r10 serve-cache
    ownership rule)."""
    fixed = _R15_RECYCLE.replace("append(view)", "append(bytes(view))")
    assert lint(tmp_path,
                {"dfs_tpu/fragmenter/stager.py": fixed}) == []


def test_dfs009_interprocedural_view_return_hop(tmp_path):
    """A function returning a pooled view marks its CALLERS' results
    as borrowed — one call-graph hop, no type inference."""
    found = lint(tmp_path, {"dfs_tpu/comm/conn.py": (
        "class Conn:\n"
        "    def reply_view(self):\n"
        "        return memoryview(self._rx_pool)[:10]\n"
        "    def keep(self):\n"
        "        v = self.reply_view()\n"
        "        self._saved = v\n")})
    assert rules_of(found) == ["DFS009"]
    assert "Conn.keep" in found[0].context


def test_dfs009_unpack_chunks_views_must_not_be_cached(tmp_path):
    """unpack_chunks hands out slices of ONE reply frame; storing one
    in a cache pins (or outlives) the frame buffer — the enforced
    version of the r10 annotation."""
    found = lint(tmp_path, {"dfs_tpu/serve/c2.py": (
        "from dfs_tpu.comm.wire import unpack_chunks\n"
        "class Cache:\n"
        "    def fill(self, table, body):\n"
        "        for d, mv in unpack_chunks(table, body):\n"
        "            self._cache[d] = mv\n")})
    assert rules_of(found) == ["DFS009"]


def test_dfs009_owned_buffer_views_are_clean(tmp_path):
    """A view over a buffer the object OWNS (non-pooled name) may be
    stored on self — the _FrameReceiver._fmv shape."""
    assert lint(tmp_path, {"dfs_tpu/comm/recv.py": (
        "class R:\n"
        "    def arm(self):\n"
        "        self._frame = bytearray(64)\n"
        "        self._fmv = memoryview(self._frame)\n")}) == []


def test_dfs009_scoped_to_view_plane(tmp_path):
    """The same idiom outside the data-plane/staging modules (CLI,
    ops kernels) is not in scope."""
    assert lint(tmp_path, {"dfs_tpu/cli/x.py": (
        "class C:\n"
        "    def f(self, b):\n"
        "        v = memoryview(self._staging_buf)\n"
        "        self._keep.append(v)\n")}) == []


# ------------------------------------------------------------------ #
# DFS010 — wire-protocol contract
# ------------------------------------------------------------------ #

_WIRE_RPC = (
    "class Client:\n"
    "    async def ping(self, peer, tok):\n"
    "        resp, _ = await self.call(peer, {'op': 'ping', "
    "'token': tok})\n"
    "        return resp.get('pong')\n")
_WIRE_RT = (
    "class S:\n"
    "    async def _dispatch(self, header, body):\n"
    "        op = header.get('op')\n"
    "        if op == 'ping':\n"
    "            return {'ok': True, 'pong': header.get('token')}, b''\n"
    "        return {'ok': False, 'error': 'unknown'}, b''\n")
_WIRE_SPECS = ("OP_SPECS = {'ping': {'request': ['token'], "
               "'reply': ['pong']}}\n")
_WIRE_BASE = {"dfs_tpu/comm/rpc.py": _WIRE_RPC,
              "dfs_tpu/node/runtime.py": _WIRE_RT,
              "dfs_tpu/comm/wire.py": _WIRE_SPECS}


def test_dfs010_clean_three_way_agreement(tmp_path):
    assert lint(tmp_path, dict(_WIRE_BASE)) == []


def test_dfs010_sent_but_unhandled_op_fails(tmp_path):
    files = dict(_WIRE_BASE)
    files["dfs_tpu/comm/rpc.py"] = _WIRE_RPC + (
        "    async def zap(self, peer):\n"
        "        await self.call(peer, {'op': 'zap'})\n")
    found = lint(tmp_path, files)
    assert rules_of(found) == ["DFS010"]
    assert found[0].context == "wire:zap:unhandled"
    assert "unknown op" in found[0].message


def test_dfs010_handled_but_undocumented_op_fails(tmp_path):
    files = dict(_WIRE_BASE)
    files["dfs_tpu/node/runtime.py"] = _WIRE_RT.replace(
        "        return {'ok': False, 'error': 'unknown'}, b''\n",
        "        if op == 'zap':\n"
        "            return {'ok': True}, b''\n"
        "        return {'ok': False, 'error': 'unknown'}, b''\n")
    found = lint(tmp_path, files)
    assert rules_of(found) == ["DFS010"]
    assert found[0].context == "wire:zap:undocumented"


def test_dfs010_documented_but_unhandled_op_fails(tmp_path):
    files = dict(_WIRE_BASE)
    files["dfs_tpu/comm/wire.py"] = (
        "OP_SPECS = {'ping': {'request': ['token'], 'reply': ['pong']},"
        " 'ghost': {'request': [], 'reply': []}}\n")
    found = lint(tmp_path, files)
    assert rules_of(found) == ["DFS010"]
    assert found[0].context == "wire:ghost:doc-unhandled"


def test_dfs010_reply_field_read_but_never_produced(tmp_path):
    files = dict(_WIRE_BASE)
    files["dfs_tpu/comm/rpc.py"] = _WIRE_RPC.replace(
        "resp.get('pong')", "resp.get('nope')")
    found = lint(tmp_path, files)
    assert "wire:ping:reply:nope" in {f.context for f in found}


def test_dfs010_request_field_read_but_never_sent(tmp_path):
    files = dict(_WIRE_BASE)
    files["dfs_tpu/node/runtime.py"] = _WIRE_RT.replace(
        "return {'ok': True, 'pong': header.get('token')}, b''",
        "return {'ok': True, 'pong': header.get('token'), "
        "'extra': header.get('extra')}, b''")
    found = lint(tmp_path, files)
    assert "wire:ping:req:extra" in {f.context for f in found}


def test_dfs010_missing_specs_table_is_one_finding(tmp_path):
    files = dict(_WIRE_BASE)
    files["dfs_tpu/comm/wire.py"] = "MAGIC = 1\n"
    found = lint(tmp_path, files)
    assert rules_of(found) == ["DFS010"]
    assert found[0].context == "wire:<no-specs>"


def test_dfs010_real_tree_full_op_coverage():
    """Acceptance: client/server/docs agree for EVERY internal op —
    including r16's get_filter/filter_delta — on the real tree."""
    from scripts.dfslint.core import Project
    from scripts.dfslint import collect_sources
    from scripts.dfslint.rules import _wire_handlers, _wire_specs

    project = Project(collect_sources(
        ["dfs_tpu/node/runtime.py", "dfs_tpu/comm/wire.py"], REPO))
    handlers = _wire_handlers(project.find("dfs_tpu/node/runtime.py"))
    specs = _wire_specs(project.find("dfs_tpu/comm/wire.py"))
    assert handlers and specs
    assert set(handlers) == set(specs)
    assert {"get_filter", "filter_delta"} <= set(specs)


# ------------------------------------------------------------------ #
# DFS000 — stale-suppression / stale-baseline audit
# ------------------------------------------------------------------ #

def test_stale_suppression_is_a_warning(tmp_path):
    found = lint(tmp_path, {"mod.py": "x = 1  # dfslint: ignore[DFS001]\n"})
    assert rules_of(found) == ["DFS000"]
    assert found[0].severity == "warning"
    assert "stale suppression" in found[0].message


def test_live_suppression_is_not_flagged(tmp_path):
    found = lint(tmp_path, {"mod.py": (
        "import time\n"
        "async def a():\n"
        "    time.sleep(1)  # dfslint: ignore[DFS001]\n")})
    assert found == []


def test_quoted_suppression_syntax_is_not_a_suppression(tmp_path):
    """Docstrings and prose quoting `# dfslint: ignore[...]` must
    neither suppress nor be audited as stale."""
    found = lint(tmp_path, {"mod.py": (
        '"""Docs: suppress with `# dfslint: ignore[DFS001]`."""\n'
        "# quoting `# dfslint: ignore[DFS004]` in prose is fine\n"
        "x = 1\n")})
    assert found == []


def test_stale_baseline_entry_is_a_warning(tmp_path):
    found = lint(tmp_path, {"mod.py": "x = 1\n"},
                 baseline={"DFS001:mod.py:gone:time.sleep"})
    assert rules_of(found) == ["DFS000"]
    assert "stale baseline" in found[0].message
    # a key whose path was NOT scanned is skipped (narrowed runs must
    # not false-flag what they cannot judge)
    found = lint(tmp_path, {},
                 baseline={"DFS001:elsewhere.py:gone:time.sleep"})
    assert found == []


def test_update_baseline_never_accepts_dfs000(tmp_path):
    """--update-baseline prunes stale entries and must NOT accept the
    audit's own warnings — baselining rot would re-create it."""
    bad = tmp_path / "bad.py"
    bad.write_text("x = 1  # dfslint: ignore[DFS001]\n")
    bl = tmp_path / "bl.json"
    r = _cli([str(bad), "--baseline", str(bl), "--update-baseline"])
    assert r.returncode == 0, r.stderr
    assert json.loads(bl.read_text())["accepted"] == []
    # the stale suppression still gates after the update
    assert _cli([str(bad), "--baseline", str(bl)]).returncode == 1


# ------------------------------------------------------------------ #
# DFS011 — durability ordering (phase 3)
# ------------------------------------------------------------------ #

def test_dfs011_visible_before_durable(tmp_path):
    """An fsync-aware function publishing written-but-unsynced bytes
    via link/rename is the torn-visibility window; the store/cas.py
    idiom (write → fsync → link) is clean."""
    found = lint(tmp_path, {"mod.py": (
        "import os\n"
        "class Store:\n"
        "    def bad(self, tmp, dst, data):\n"
        "        with open(tmp, 'wb') as f:\n"
        "            f.write(data)\n"
        "        os.link(tmp, dst)\n"     # publishes unsynced bytes
        "        self._fsync_path(dst)\n")})
    assert rules_of(found) == ["DFS011"]
    assert found[0].context == "Store.bad:visible-before-durable"
    assert found[0].line == 6

    clean = lint(tmp_path / "ok", {"mod.py": (
        "import os\n"
        "class Store:\n"
        "    def good(self, tmp, dst, data):\n"
        "        with open(tmp, 'wb') as f:\n"
        "            f.write(data)\n"
        "            os.fsync(f.fileno())\n"
        "        os.link(tmp, dst)\n")})
    assert clean == []


def test_dfs011_not_fsync_aware_is_silent(tmp_path):
    """A function that never fsyncs opted OUT of the durability mode —
    crash safety by pure ordering (the lsi.py CURRENT swap) or
    deliberate best-effort state (ring.json) is a design point, not a
    finding."""
    assert lint(tmp_path, {"mod.py": (
        "import os\n"
        "class Ring:\n"
        "    def snapshot(self, tmp, dst, data):\n"
        "        with open(tmp, 'wb') as f:\n"
        "            f.write(data)\n"
        "        os.replace(tmp, dst)\n")}) == []


def test_dfs011_minimized_r13_utime_repro(tmp_path):
    """The r13 LWW-mtime bug, minimized: os.utime AFTER the data
    barrier is metadata the barrier did not cover — it reverts on
    power loss unless re-fsynced (the shape ManifestStore.save fixes
    with a trailing _fsync_path)."""
    found = lint(tmp_path, {"mod.py": (
        "import os\n"
        "class ManifestStore:\n"
        "    def save(self, p, data, mtime):\n"
        "        self._atomic_write(p, data, fsync=self._fsync)\n"
        "        os.utime(p, (mtime, mtime))\n")})
    assert rules_of(found) == ["DFS011"]
    assert found[0].context == "ManifestStore.save:utime-after-barrier"

    fixed = lint(tmp_path / "ok", {"mod.py": (
        "import os\n"
        "class ManifestStore:\n"
        "    def save(self, p, data, mtime):\n"
        "        self._atomic_write(p, data, fsync=self._fsync)\n"
        "        os.utime(p, (mtime, mtime))\n"
        "        self._fsync_path(p)\n")})
    assert fixed == []


def test_dfs011_atomic_write_fsync_false_not_aware(tmp_path):
    """``_atomic_write(..., fsync=False)`` (and no-kwarg calls) do not
    opt the function into fsync-awareness — the journal/ring modules
    call the helper in best-effort mode on purpose."""
    assert lint(tmp_path, {"mod.py": (
        "import os\n"
        "class C:\n"
        "    def f(self, p, data, mtime):\n"
        "        self._atomic_write(p, data, fsync=False)\n"
        "        os.utime(p, (mtime, mtime))\n")}) == []


def test_dfs011_segment_reopen_needs_create_only(tmp_path):
    """A per-boot append-only segment path must open \"xb\": an
    append/write reopen glues a new boot onto a possibly-torn tail
    when the boot id collides (the journal same-second shape).
    Applies even to fsync-free functions."""
    found = lint(tmp_path, {"mod.py": (
        "class J:\n"
        "    def _open(self):\n"
        "        return open(self._segment_path(), 'ab')\n")})
    assert rules_of(found) == ["DFS011"]
    assert found[0].context == "J._open:segment-open"

    assert lint(tmp_path / "ok", {"mod.py": (
        "class J:\n"
        "    def _open(self):\n"
        "        return open(self._segment_path(), 'xb')\n")}) == []


# ------------------------------------------------------------------ #
# DFS012 — torn-read discipline (phase 3)
# ------------------------------------------------------------------ #

def test_dfs012_raw_reader_of_append_only_formats(tmp_path):
    """Raw reads over the append-only formats (journal segments, sim
    band log) either crash on the post-kill-9 torn tail or trust half
    a record — only the blessed decoders may touch them raw."""
    found = lint(tmp_path, {"dfs_tpu/tools.py": (
        "import json\n"
        "def tail(root):\n"
        "    return [json.loads(l)\n"
        "            for l in open(root / 'events-1-2.jsonl')]\n"
        "def peek(root):\n"
        "    return (root / 'bands.log').read_bytes()\n")})
    assert rules_of(found) == ["DFS012", "DFS012"]
    assert "torn-read" in found[0].context
    assert "blessed decoder" in found[0].message


def test_dfs012_blessed_decoder_module_is_exempt(tmp_path):
    """The format's own decoder module reads raw by definition — that
    is where the CRC/torn-tail handling lives."""
    assert lint(tmp_path, {"dfs_tpu/obs/journal.py": (
        "import json\n"
        "def read_events(root):\n"
        "    return [json.loads(l)\n"
        "            for l in open(root / 'events-1-2.jsonl')]\n"),
        "dfs_tpu/sim/bands.py": (
        "def _replay(root):\n"
        "    return (root / 'bands.log').read_bytes()\n")}) == []


def test_dfs012_unrelated_paths_are_clean(tmp_path):
    assert lint(tmp_path, {"dfs_tpu/tools.py": (
        "import json\n"
        "def load(root):\n"
        "    return json.loads((root / 'ring.json').read_text())\n"
        "def read(p):\n"
        "    return open(p, 'rb').read()\n")}) == []


# ------------------------------------------------------------------ #
# DFS013 — crash-point coverage (phase 3)
# ------------------------------------------------------------------ #

_MINI_CHAOS = (
    "CRASH_POINTS = frozenset({\n"
    "    'up.before_manifest',\n"
    "    'up.after_manifest',\n"
    "})\n")

_MINI_FIRES = (
    "class Node:\n"
    "    def finalize(self, inj):\n"
    "        inj.maybe_crash('up.before_manifest')\n"
    "        inj.maybe_crash('up.after_manifest')\n")


def test_dfs013_registry_closed_both_ends_is_clean(tmp_path):
    """Every id fired at a source site and armed by a test literal:
    the contract holds, no findings."""
    assert lint(tmp_path, {
        "dfs_tpu/chaos.py": _MINI_CHAOS,
        "dfs_tpu/node.py": _MINI_FIRES,
        "tests/test_kill.py": (
            "POINTS = ['up.before_manifest', 'up.after_manifest']\n")
    }) == []


def test_dfs013_unfired_and_unexercised_are_findings(tmp_path):
    """A registered id nobody fires is dead coverage that reads as
    tested; a fired id no test arms is an untested window."""
    found = lint(tmp_path, {
        "dfs_tpu/chaos.py": _MINI_CHAOS,
        "dfs_tpu/node.py": (
            "class Node:\n"
            "    def finalize(self, inj):\n"
            "        inj.maybe_crash('up.before_manifest')\n"),
        "tests/test_kill.py": "ARM = 'up.before_manifest'\n"})
    assert rules_of(found) == ["DFS013", "DFS013"]
    assert {f.context for f in found} == {
        "chaos:up.after_manifest:unfired",
        "chaos:up.after_manifest:unexercised"}
    # anchored at the registry declaration, where the fix goes
    assert all(f.path == "dfs_tpu/chaos.py" for f in found)


def test_dfs013_prefix_filtered_loop_counts_unfiltered_does_not(tmp_path):
    """The kill-loop idioms earn exercise credit: a positive prefix
    filter (test_tiering) and a negative one (test_chaos). An
    UNfiltered loop over the registry is knob validation — no credit,
    so a brand-new point still demands a real kill test."""
    base = {"dfs_tpu/chaos.py": _MINI_CHAOS,
            "dfs_tpu/node.py": _MINI_FIRES}
    assert lint(tmp_path / "pos", dict(
        base, **{"tests/test_kill.py": (
            "from dfs_tpu.chaos import CRASH_POINTS\n"
            "POINTS = [p for p in CRASH_POINTS"
            " if p.startswith('up.')]\n")})) == []
    assert lint(tmp_path / "neg", dict(
        base, **{"tests/test_kill.py": (
            "from dfs_tpu.chaos import CRASH_POINTS\n"
            "POINTS = sorted(p for p in CRASH_POINTS\n"
            "                if not p.startswith(('other.',)))\n")})) == []
    found = lint(tmp_path / "none", dict(
        base, **{"tests/test_kill.py": (
            "from dfs_tpu.chaos import CRASH_POINTS\n"
            "POINTS = sorted(p for p in CRASH_POINTS)\n")}))
    assert {f.context for f in found} == {
        "chaos:up.before_manifest:unexercised",
        "chaos:up.after_manifest:unexercised"}


def test_dfs013_unregistered_fire_is_a_finding(tmp_path):
    """maybe_crash of an id missing from the registry would raise at
    injector-arm time — the registry IS the contract."""
    found = lint(tmp_path, {
        "dfs_tpu/chaos.py": _MINI_CHAOS,
        "dfs_tpu/node.py": (
            "class Node:\n"
            "    def finalize(self, inj):\n"
            "        inj.maybe_crash('up.before_manifest')\n"
            "        inj.maybe_crash('up.after_manifest')\n"
            "        inj.maybe_crash('rogue.window')\n"),
        "tests/test_kill.py": (
            "A = 'up.before_manifest'\nB = 'up.after_manifest'\n")})
    assert [f.context for f in found] == ["chaos:rogue.window:unregistered"]


def test_dfs013_multi_step_sequence_needs_a_seam(tmp_path):
    """>=2 visibility-changing steps outside cleanup paths = a kill -9
    window between them; fire a crash point or carry a reasoned
    ignore. A seamed sequence and a cleanup-path unlink are clean."""
    found = lint(tmp_path, {"mod.py": (
        "import os\n"
        "class S:\n"
        "    def swap(self, a, b):\n"
        "        os.replace(a, b)\n"
        "        os.unlink(a)\n")})
    assert rules_of(found) == ["DFS013"]
    assert found[0].severity == "warning"
    assert found[0].context == "chaos:S.swap:multi-step"

    assert lint(tmp_path / "seamed", {"mod.py": (
        "import os\n"
        "class S:\n"
        "    def swap(self, a, b):\n"
        "        os.replace(a, b)\n"
        "        self.maybe_crash('swap')\n"
        "        os.unlink(a)\n")}) == []

    assert lint(tmp_path / "cleanup", {"mod.py": (
        "import os\n"
        "class S:\n"
        "    def swap(self, a, b, tmp):\n"
        "        try:\n"
        "            os.replace(a, b)\n"
        "        finally:\n"
        "            tmp.unlink()\n")}) == []


def test_dfs013_ignore_and_stale_audit_interplay(tmp_path):
    """A reasoned inline ignore suppresses the multi-step finding (the
    lsi.py/cas.py triage idiom) and counts as LIVE for the DFS000
    audit; naming the wrong rule is stale and flagged."""
    assert lint(tmp_path, {"mod.py": (
        "import os\n"
        "class S:\n"
        "    def swap(self, a, b):\n"
        "        # ordering argument lives here\n"
        "        # dfslint: ignore[DFS013]\n"
        "        os.replace(a, b)\n"
        "        os.unlink(a)\n")}) == []

    found = lint(tmp_path / "stale", {"mod.py": (
        "import os\n"
        "class S:\n"
        "    def swap(self, a, b):\n"
        "        os.replace(a, b)  # dfslint: ignore[DFS011]\n"
        "        os.unlink(a)\n")})
    assert sorted(rules_of(found)) == ["DFS000", "DFS013"]


def test_dfs013_real_registry_fully_covered():
    """Acceptance: on the real tree every CRASH_POINTS id — including
    this PR's sim.band_compact — is fired at a source site and
    exercised by a test/bench kill loop."""
    from dfs_tpu.chaos import CRASH_POINTS
    from scripts.dfslint.core import Project
    from scripts.dfslint import collect_sources
    from scripts.dfslint.durability import (_exercised_ids,
                                            persistence_model)

    project = Project(collect_sources(list(DEFAULT_ROOTS), REPO))
    pm = persistence_model(project)
    fired = {e.detail for effects in pm.effects.values()
             for e in effects if e.kind == "seam"
             and isinstance(e.detail, str)}
    assert set(CRASH_POINTS) <= fired
    assert "sim.band_compact" in fired
    assert set(CRASH_POINTS) <= _exercised_ids(REPO, set(CRASH_POINTS))


# ------------------------------------------------------------------ #
# --changed mode (git-scoped reporting over a whole-tree model)
# ------------------------------------------------------------------ #

def test_analyze_only_paths_filters_report_not_model(tmp_path):
    """only_paths restricts the REPORT; the model stays whole-tree, so
    a finding in an unlisted file disappears while the same finding in
    a listed one survives."""
    files = {
        "a.py": "import time\nasync def a():\n    time.sleep(1)\n",
        "b.py": "import time\nasync def b():\n    time.sleep(1)\n"}
    for rel, text in files.items():
        (tmp_path / rel).write_text(text)
    every = analyze(["."], tmp_path)
    assert sorted(f.path for f in every) == ["a.py", "b.py"]
    only_b = analyze(["."], tmp_path, only_paths={"b.py"})
    assert [f.path for f in only_b] == ["b.py"]
    assert analyze(["."], tmp_path, only_paths=set()) == []


def test_changed_paths_sees_worktree_and_untracked(tmp_path):
    from scripts.dfslint.__main__ import changed_paths

    def git(*args):
        subprocess.run(["git", "-c", "user.email=t@t", "-c",
                        "user.name=t", *args], cwd=tmp_path,
                       check=True, capture_output=True)

    git("init", "-q")
    (tmp_path / "tracked.py").write_text("x = 1\n")
    git("add", "tracked.py")
    git("commit", "-qm", "seed")
    (tmp_path / "tracked.py").write_text("x = 2\n")       # modified
    (tmp_path / "fresh.py").write_text("y = 1\n")         # untracked
    assert changed_paths(tmp_path) == {"tracked.py", "fresh.py"}

    git("add", "-A")
    git("commit", "-qm", "second")
    assert changed_paths(tmp_path) == set()
    # with a base ref, committed changes since it count again
    assert changed_paths(tmp_path, "HEAD~1") == {"tracked.py",
                                                 "fresh.py"}


def test_changed_paths_bad_ref_is_value_error(tmp_path):
    from scripts.dfslint.__main__ import changed_paths

    subprocess.run(["git", "init", "-q"], cwd=tmp_path, check=True,
                   capture_output=True)
    import pytest
    with pytest.raises(ValueError):
        changed_paths(tmp_path, "no-such-ref")


def test_cli_changed_rejects_update_baseline():
    r = _cli(["--changed", "--update-baseline"])
    assert r.returncode == 2
    assert "--changed" in r.stderr


# ------------------------------------------------------------------ #
# --stats, --format sarif, and the tier-1 wall-clock budget
# ------------------------------------------------------------------ #

def test_cli_stats_json_breakdown(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text("x = 1\n")
    r = _cli([str(ok), "--json", "--stats"])
    out = json.loads(r.stdout)
    assert out["stats"]["files"] == 1
    phases = out["stats"]["phases"]
    assert "model" in phases and "DFS008" in phases and "audit" in phases
    assert out["stats"]["totalS"] >= phases["model"]


def test_cli_sarif_output(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def a():\n    time.sleep(1)\n")
    r = _cli([str(bad), "--format", "sarif"])
    assert r.returncode == 1
    doc = json.loads(r.stdout)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "dfslint"
    assert {rule["id"] for rule in run["tool"]["driver"]["rules"]} \
        >= {"DFS001", "DFS008", "DFS009", "DFS010",
            "DFS011", "DFS012", "DFS013"}
    res = run["results"][0]
    assert res["ruleId"] == "DFS001" and res["level"] == "error"
    loc = res["locations"][0]["physicalLocation"]
    assert loc["region"]["startLine"] == 3


def test_annotation_hook_emits_file_line_annotations(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import time\nasync def a():\n    time.sleep(1)\n")
    r = subprocess.run(
        [sys.executable, "scripts/dfslint_annotate.py", str(bad)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
    assert r.stdout.startswith("::error file=")
    assert ",line=3," in r.stdout and "title=DFS001" in r.stdout
    r = subprocess.run(
        [sys.executable, "scripts/dfslint_annotate.py", "--style",
         "plain", str(bad)],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert ":3:" in r.stdout and "DFS001 error:" in r.stdout
    # every annotation links its docs/lint.md catalogue entry
    assert "docs/lint.md#dfs001" in r.stdout


def test_annotation_doc_anchors_cover_every_rule():
    """DOC_ANCHORS stays in lockstep with ALL_RULES: a new rule id
    without a catalogue link is a gap CI annotations would surface as
    a bare message."""
    import importlib
    if str(REPO / "scripts") not in sys.path:
        sys.path.insert(0, str(REPO / "scripts"))
    annotate = importlib.import_module("dfslint_annotate")
    from scripts.dfslint.rules import ALL_RULES

    registered = {rid for rid, _d, _f in ALL_RULES} | {"DFS000"}
    assert registered <= set(annotate.DOC_ANCHORS)


def test_full_run_within_wall_clock_budget():
    """Acceptance gate: the full run — interprocedural model included —
    costs at most 3x its own legacy phases (the walk + DFS001-007),
    measured by --stats: the phase-1 model + the rules after DFS007
    may at most double-and-a-bit the legacy phases' cost. Phase 3
    (DFS011-013) carries its own sub-budget: it rides the phase-1 call
    index rather than re-walking ASTs, so the three rules together
    must stay well under the model build itself.

    Both bounds compare phases of ONE run, in the analyzer thread's CPU
    seconds with the collector off: a phase's wall clock takes whatever
    preemption lands on it under six xdist workers, and a gen-2
    collection lands in whichever phase allocates next (the same tree
    read 2.0x and 3.4x in one minute); its CPU share without them reads
    2.3-2.8x idle and loaded alike (six runs each, same minute: this
    tree 2.39-2.72x, PR 28's tree 2.52-2.77x). The bound was 2.2x
    beside an absolute 3.4 s that did the guarding — 3.2x the legacy
    phases on the host it was measured on — and failed wherever the
    host was busy, because 2.2x is not what either tree costs: 3.0x
    is BELOW what the parent's test let through."""
    import gc

    stats: dict = {}
    gc.collect()
    gc.disable()
    try:
        analyze(list(DEFAULT_ROOTS), REPO,
                baseline=load_baseline(DEFAULT_BASELINE), stats=stats)
    finally:
        gc.enable()
    stats = stats["cpu"]
    phases = stats["phases"]
    legacy = stats["walkS"] + sum(
        phases.get(f"DFS00{i}", 0.0) for i in range(1, 8))
    assert stats["totalS"] <= 3.0 * legacy, stats
    phase3 = sum(phases.get(r, 0.0)
                 for r in ("DFS011", "DFS012", "DFS013"))
    assert phase3 <= 0.75 * phases["model"], stats


# ------------------------------------------------------------------ #
# the real tree (enforcement): clean modulo the committed baseline
# ------------------------------------------------------------------ #

def test_real_tree_clean_modulo_baseline():
    findings = analyze(list(DEFAULT_ROOTS), REPO,
                       baseline=load_baseline(DEFAULT_BASELINE))
    assert findings == [], (
        "dfslint found new violations (fix them, suppress with a "
        "justified `# dfslint: ignore[RULE]`, or baseline deliberately "
        "- see docs/lint.md):\n  "
        + "\n  ".join(f.render() for f in findings))


def test_serve_cli_exposes_every_config_field():
    """Drift regression for the DFS005 fixes: the flags added in this PR
    must keep parsing and land in the right NodeConfig fields."""
    from dfs_tpu.cli.main import build_parser

    ns = build_parser().parse_args(
        ["serve", "--node-id", "1", "--write-quorum", "1",
         "--probe-interval", "0", "--rpc-retries", "2",
         "--connect-timeout", "0.5", "--request-timeout", "3",
         "--retry-after", "2.5", "--fixed-parts", "7"])
    assert (ns.write_quorum, ns.probe_interval, ns.rpc_retries) == (1, 0, 2)
    assert (ns.connect_timeout, ns.request_timeout) == (0.5, 3.0)
    assert (ns.retry_after, ns.fixed_parts) == (2.5, 7)
    # r16 dedup/index plane flags land in IndexConfig fields
    ns = build_parser().parse_args(
        ["serve", "--node-id", "1", "--index",
         "--index-memtable-entries", "512", "--index-compact-runs",
         "3", "--index-filter-bits", "12", "--index-filter-sync",
         "2.5"])
    assert ns.index is True
    assert (ns.index_memtable_entries, ns.index_compact_runs) == (512, 3)
    assert (ns.index_filter_bits, ns.index_filter_sync) == (12, 2.5)
