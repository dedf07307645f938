"""The dfslint passes. Each is a pure function over the parsed
``Project``; ``run_rules`` applies them all and filters inline
suppressions. Since r17 the analyzer is multi-phase: phase 1
(scripts/dfslint/model.py) builds the whole-repo facts — call graph,
execution-context classification, attribute/lock symbol table — once;
phase 2 (this module) runs every rule against the shared parse and the
shared model; phase 3 (scripts/dfslint/durability.py) layers the
persistence-ordering effect model on top for the crash-consistency
rules DFS011-DFS013 (registered here like every other rule, so the
CLI/SARIF/baseline plumbing applies unchanged). The single-sentence-explainable discipline stands: a rule
fires only on facts the model actually established, and what the model
cannot establish (dynamic dispatch, callables smuggled through
containers) is documented per rule in docs/lint.md rather than
half-guessed.
"""

from __future__ import annotations

import ast
import re
from typing import Iterator

from scripts.dfslint.core import (Finding, Project, SourceFile, dotted,
                                  scope_nodes)
from scripts.dfslint.durability import (check_crash_point_coverage,
                                        check_durability_ordering,
                                        check_torn_read_discipline)
from scripts.dfslint.model import (LOOP, WORKER, build_model,
                                   is_view_expr, view_vars)

# ------------------------------------------------------------------ #
# DFS001 — blocking call in async def
# ------------------------------------------------------------------ #

# module-qualified calls that park the event loop for a syscall/IO pass
_BLOCKING_PREFIXES = ("socket.", "subprocess.")
_BLOCKING_EXACT = frozenset({
    "time.sleep", "open",
    # urllib's opener is sync network I/O however it's spelled
    "urllib.request.urlopen",
})
# Path-object file I/O methods (distinctive enough to match by name)
_BLOCKING_METHODS = frozenset({"read_bytes", "write_bytes", "read_text",
                               "write_text"})
# direct sync ChunkStore data-plane ops; the async runtime must route
# these through AsyncChunkStore (store/aio.py) or asyncio.to_thread —
# inline they measured multi-second event-loop stalls under writeback
# pressure (store/aio.py module docstring)
_CHUNKSTORE_OPS = frozenset({"put", "get"})


def _blocking_call(node: ast.Call) -> tuple[str, str] | None:
    """(what, fix) when ``node`` is a loop-blocking call, else None."""
    name = dotted(node.func)
    if name in _BLOCKING_EXACT \
            or (name and name.startswith(_BLOCKING_PREFIXES)):
        return (f"blocking call {name}()",
                "run it via asyncio.to_thread / an executor")
    if isinstance(node.func, ast.Attribute):
        attr = node.func.attr
        base = dotted(node.func.value)
        if attr in _BLOCKING_METHODS:
            return (f"sync file I/O .{attr}()",
                    "run it via asyncio.to_thread / an executor")
        if attr in _CHUNKSTORE_OPS and base \
                and base.split(".")[-1] == "chunks":
            return (f"direct ChunkStore.{attr}()",
                    "route through AsyncChunkStore (self.cas) or "
                    "asyncio.to_thread")
    return None


def check_blocking_in_async(project: Project) -> Iterator[Finding]:
    """Blocking calls in loop-affine code. Pre-r17 this was lexical —
    calls inside an ``async def`` body only. The phase-1 context
    inference turns it into a call-graph fact: a *sync* helper that
    only ever runs on the event loop (called from async context,
    never dispatched to a worker) is held to the same rule, and a
    nested def handed to ``to_thread`` is exempt because its inferred
    context IS worker, not because of a syntactic nesting guess."""
    model = build_model(project)

    # a sync function is PROVABLY loop-only when every resolved caller
    # is async or itself provably loop-only — a helper that ALSO has
    # an unclassified sync caller (a CLI entry point, a caller the
    # model could not resolve) may legitimately block on that path, so
    # it is not flagged (code-review fix: ctx={loop} alone only says
    # SOME path is loop-side)
    memo: dict[str, bool] = {}

    def provably_loop_only(fi) -> bool:
        got = memo.get(fi.uid)
        if got is not None:
            return got
        if fi.is_async:
            memo[fi.uid] = True
            return True
        if WORKER in fi.ctx or LOOP not in fi.ctx:
            memo[fi.uid] = False
            return False
        memo[fi.uid] = False   # cycle guard: a cycle proves nothing
        callers = model.callers_of(fi)
        ok = bool(callers) and all(provably_loop_only(c)
                                   for c in callers)
        memo[fi.uid] = ok
        return ok

    for fi in model.functions.values():
        if fi.src.tree is None or isinstance(fi.node, ast.Lambda):
            continue
        if LOOP not in fi.ctx or WORKER in fi.ctx:
            continue  # worker/both/unknown context: not loop-affine
        if not fi.is_async:
            if not (fi.src.rel.startswith("dfs_tpu/")
                    or "/dfs_tpu/" in fi.src.rel):
                # the interprocedural extension holds the RUNTIME to
                # the loop discipline; bench/tool drivers blocking in
                # a sync helper during setup is not the bug class
                continue
            if not provably_loop_only(fi):
                continue
        src = fi.src
        for node in scope_nodes(fi.node):
            if not isinstance(node, ast.Call):
                continue
            hit = _blocking_call(node)
            if hit is None:
                continue
            what, fix = hit
            name = dotted(node.func)
            where = f"`async def {fi.name}`" if fi.is_async else (
                f"`{fi.name}` (sync, but every resolved caller is "
                "loop-affine)")
            yield Finding(
                "DFS001", "error", src.rel, node.lineno,
                node.col_offset,
                f"{what} inside {where} occupies the "
                f"event loop for the call's full duration — {fix}",
                f"{src.qualname(node)}:{name or node.func.attr}")


# ------------------------------------------------------------------ #
# DFS002 — dropped task
# ------------------------------------------------------------------ #

_SPAWN_NAMES = frozenset({"create_task", "ensure_future"})


def _is_spawn(node: ast.Call) -> bool:
    name = dotted(node.func)
    if name in ("asyncio.create_task", "asyncio.ensure_future"):
        return True
    # loop.create_task(...) / anything.ensure_future(...)
    return (isinstance(node.func, ast.Attribute)
            and node.func.attr in _SPAWN_NAMES)


def check_dropped_task(project: Project) -> Iterator[Finding]:
    """A bare ``asyncio.create_task(...)`` statement keeps no reference:
    the event loop holds only weak refs, so the task can be GC'd and
    silently cancelled mid-await — and if it fails, the exception is
    logged (at best) at interpreter exit, attributed to nothing. The
    result must be stored, awaited, or given a done-callback."""
    for src in project.files:
        if src.tree is None:
            continue
        for node in src.nodes(ast.Call):
            if not _is_spawn(node):
                continue
            parent = src.parents.get(node)
            if not isinstance(parent, ast.Expr):
                continue  # assigned / awaited / passed along / chained
            yield Finding(
                "DFS002", "error", src.rel, node.lineno, node.col_offset,
                "task result discarded: store it, await it, or attach an "
                "exception-logging done-callback — a dropped task can be "
                "GC-cancelled and its exception vanishes",
                f"{src.qualname(node)}:create_task")


# ------------------------------------------------------------------ #
# DFS003 — lock discipline across the sync/async boundary
# ------------------------------------------------------------------ #

_LOCKISH = re.compile(r"(lock|mutex|cond|(^|_)cv$)", re.IGNORECASE)
# asyncio loop-affine calls that are not thread-safe; a function handed
# to an executor must reach the loop via call_soon_threadsafe /
# run_coroutine_threadsafe instead (note: *referencing* put_nowait as a
# call_soon_threadsafe argument is fine and not a Call node)
_LOOP_AFFINE_ATTRS = frozenset({"put_nowait", "set_result",
                                "set_exception", "call_soon"})
_LOOP_AFFINE_CALLS = frozenset({
    "asyncio.create_task", "asyncio.ensure_future",
    "asyncio.get_running_loop", "asyncio.get_event_loop",
    "asyncio.sleep",
})


def _lockish(expr: ast.AST) -> str | None:
    name = dotted(expr)
    if name is None and isinstance(expr, ast.Call):
        name = dotted(expr.func)   # with threading.Lock(): ...
    if name and _LOCKISH.search(name.split(".")[-1]):
        return name
    return None


def check_lock_discipline(project: Project) -> Iterator[Finding]:
    for src in project.files:
        if src.tree is None:
            continue
        # (a) `await` inside a *sync* `with <lock>` block in an async
        # def. asyncio locks require `async with` (ast.AsyncWith), so a
        # sync with on a lock-ish name + await inside means a
        # threading.Lock held across a suspension point: every other
        # task of the loop that touches that lock then blocks the whole
        # loop until this coroutine is resumed — the classic
        # loop-wedging deadlock shape.
        for fn in src.nodes(ast.AsyncFunctionDef):
            for node in scope_nodes(fn):
                if not isinstance(node, ast.With):
                    continue
                held = next((n for it in node.items
                             if (n := _lockish(it.context_expr))), None)
                if held is None:
                    continue
                for aw in (n for n in scope_nodes(node)
                           if isinstance(n, ast.Await)):
                    yield Finding(
                        "DFS003", "error", src.rel, aw.lineno,
                        aw.col_offset,
                        f"await while holding thread lock `{held}`: the "
                        "lock stays held across the suspension, wedging "
                        "every loop task that contends for it (use an "
                        "asyncio.Lock with `async with`, or do not "
                        "await under the lock)",
                        f"{src.qualname(aw)}:await-under-{held}")
    # (b) sync functions the model places in WORKER context — executor
    # targets, thread targets, trampoline-dispatched callables (the
    # AsyncChunkStore._run shape the r08 same-file-name heuristic could
    # not see), and everything they call — must not touch loop-affine
    # asyncio primitives directly
    model = build_model(project)
    for fi in model.functions.values():
        if fi.src.tree is None or fi.is_async \
                or isinstance(fi.node, ast.Lambda):
            continue
        if WORKER not in fi.ctx:
            continue
        src = fi.src
        for node in scope_nodes(fi.node):
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func)
            bad = None
            if name in _LOOP_AFFINE_CALLS:
                bad = f"{name}()"
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in _LOOP_AFFINE_ATTRS):
                bad = f".{node.func.attr}()"
            if bad is None:
                continue
            yield Finding(
                "DFS003", "error", src.rel, node.lineno,
                node.col_offset,
                f"`{fi.name}` runs on an executor thread but calls "
                f"loop-affine {bad} directly — asyncio primitives "
                "are not thread-safe; marshal through "
                "loop.call_soon_threadsafe / "
                "asyncio.run_coroutine_threadsafe",
                f"{src.qualname(node)}:{fi.name}:{bad}")


# ------------------------------------------------------------------ #
# DFS004 — digest boundary
# ------------------------------------------------------------------ #

# the only trees allowed to touch hashlib directly: the verified host
# implementation and the device kernels it is checked against
_DIGEST_ALLOWED = ("dfs_tpu/utils/hashing.py", "dfs_tpu/ops/")
_HASHLIB_CALLS = frozenset({"hashlib.sha256", "hashlib.sha1",
                            "hashlib.md5", "hashlib.new"})


def check_digest_boundary(project: Project) -> Iterator[Finding]:
    """Every digest in the system is a content address — a single
    differently-computed digest (different algorithm, stale import, a
    future `usedforsecurity` flag divergence) silently splits the CAS
    namespace. So raw hashlib stays behind dfs_tpu/utils/hashing.py
    (sha256_hex / sha256_many_hex / sha256_new) and the ops/ kernels
    that are bit-exactness-tested against it."""
    for src in project.files:
        if src.tree is None:
            continue
        if (src.rel.endswith(_DIGEST_ALLOWED[0])
                or f"/{_DIGEST_ALLOWED[0]}" in src.rel
                or _DIGEST_ALLOWED[1] in src.rel):
            continue
        for node in src.nodes(ast.Call):
            name = dotted(node.func)
            if name not in _HASHLIB_CALLS:
                continue
            yield Finding(
                "DFS004", "error", src.rel, node.lineno, node.col_offset,
                f"raw {name}() outside dfs_tpu/utils/hashing.py + "
                "dfs_tpu/ops/ — digests must go through the one "
                "verified implementation (sha256_hex / sha256_many_hex "
                "/ sha256_new)",
                f"{src.qualname(node)}:{name}")


# ------------------------------------------------------------------ #
# DFS005 — config drift (CLI flags <-> config fields <-> /metrics keys)
# ------------------------------------------------------------------ #

# dataclasses in dfs_tpu/config.py whose every field must be settable
# from the `serve` CLI (a field without a flag silently pins a
# deployment to the default — the drift this rule exists to catch)
_CLI_CLASSES = ("NodeConfig", "ServeConfig", "IngestConfig", "ObsConfig",
                "FragmenterConfig", "CensusConfig", "DurabilityConfig",
                "ChaosConfig", "RingConfig", "IndexConfig", "TierConfig",
                "SimConfig", "ClientConfig")
# config field -> /metrics key that surfaces it, per stats function.
# "cas" carries cas_io_threads as its nested workers count
# (store/aio.py stats()).
_INGEST_METRIC_KEYS = {"window": "window", "flush_bytes": "flushBytes",
                       "credit_bytes": "creditBytes",
                       "slice_inflight": "sliceInflight",
                       "cas_io_threads": "cas"}
# the four admission knobs surface inside the "admission" section;
# cache_bytes inside "cache"; the r18 hedge knobs inside "hedge"
# (serve/__init__.py ServingTier.stats() — the journal/sentinel
# nesting convention)
_SERVE_METRIC_KEYS = {"cache_bytes": "cache",
                      "readahead_batches": "readaheadBatches",
                      "download_slots": "admission",
                      "upload_slots": "admission",
                      "internal_slots": "admission",
                      "queue_depth": "admission",
                      "retry_after_s": "admission",
                      "default_deadline_s": "defaultDeadlineS",
                      "hedge_floor_s": "hedge",
                      "hedge_cap_s": "hedge",
                      "hedge_budget_per_s": "hedge"}
# observability knobs surface under /metrics "obs"
# (dfs_tpu/obs/__init__.py Observability.stats()). The journal and
# sentinel fields ride their nested sub-sections ("journal" carries
# bytes/segmentBytes from journal.stats(); "sentinel" carries
# intervalS/lagThresholdS from sentinel.stats()) — same nesting
# convention as IngestConfig.cas_io_threads -> "cas".
_OBS_METRIC_KEYS = {"trace_ring": "traceRing",
                    "slow_span_s": "slowSpanS",
                    "tail_keep": "tailKeep",
                    "journal_bytes": "journal",
                    "journal_segment_bytes": "journal",
                    "sentinel_interval_s": "sentinel",
                    "sentinel_lag_s": "sentinel"}
# census/capacity knobs surface under /metrics "census"
# (node/runtime.py census_stats())
_CENSUS_METRIC_KEYS = {"history_interval_s": "historyIntervalS",
                       "history_slots": "historySlots",
                       "history_coarse_every": "coarseEvery",
                       "history_coarse_slots": "coarseSlots",
                       "max_listed": "maxListed"}
# fragmenter execution knobs surface under /metrics "frag"
# (node/runtime.py frag_stats())
_FRAG_METRIC_KEYS = {"devices": "devices",
                     "region_bytes": "regionBytes",
                     "staging_buffers": "stagingBuffers"}
# durability mode surfaces under /metrics "durability"
# (node/runtime.py durability_stats())
_DURABILITY_METRIC_KEYS = {"mode": "mode"}
# chaos knobs surface under /metrics "chaos"
# (dfs_tpu/chaos/__init__.py ChaosInjector.stats())
_CHAOS_METRIC_KEYS = {"enabled": "enabled", "seed": "seed",
                      "rpc_delay_s": "rpcDelayS",
                      "rpc_delay_peers": "rpcDelayPeers",
                      "rpc_drop_rate": "rpcDropRate",
                      "partition": "partition",
                      "rpc_truncate_rate": "rpcTruncateRate",
                      "serve_delay_s": "serveDelayS",
                      "disk_error_rate": "diskErrorRate",
                      "disk_full": "diskFull",
                      "disk_delay_s": "diskDelayS",
                      "crash_point": "crashPoint"}


# membership-ring knobs surface under /metrics "ring"
# (node/runtime.py ring_stats())
_RING_METRIC_KEYS = {"vnodes": "vnodes", "members": "members",
                     "rebalance_credit_bytes": "rebalanceCreditBytes"}

# dedup/index-plane knobs surface under /metrics "index"
# (node/runtime.py index_stats())
_INDEX_METRIC_KEYS = {"enabled": "enabled",
                      "memtable_entries": "memtableEntries",
                      "compact_runs": "compactRuns",
                      "filter_bits_per_key": "filterBitsPerKey",
                      "filter_sync_s": "filterSyncS",
                      "background_compact": "backgroundCompact",
                      "echo_cache_entries": "echoCacheEntries"}

# hot/cold tiering knobs surface under /metrics "tier"
# (node/runtime.py tier_stats())
_TIER_METRIC_KEYS = {"enabled": "enabled",
                     "hot_fraction": "hotFraction",
                     "min_idle_s": "minIdleS",
                     "scan_interval_s": "scanIntervalS",
                     "ec_k": "ecK",
                     "demote_credit_bytes": "demoteCreditBytes",
                     "half_life_s": "halfLifeS",
                     "promote_reads": "promoteReads",
                     "redemote_cooldown_s": "redemoteCooldownS",
                     "ledger_entries": "ledgerEntries"}

# similarity-compression knobs surface under /metrics "sim"
# (node/runtime.py sim_stats())
_SIM_METRIC_KEYS = {"enabled": "enabled",
                    "sketch_size": "sketchSize",
                    "bands": "bands",
                    "shingle_bytes": "shingleBytes",
                    "max_candidates": "maxCandidates",
                    "min_chunk_bytes": "minChunkBytes",
                    "min_savings_frac": "minSavingsFrac",
                    "max_delta_depth": "maxDeltaDepth",
                    "devices": "devices",
                    "rematerialize_reads": "rematerializeReads"}

# smart-client knobs surface in SmartClient.stats()
# (dfs_tpu/client/smart.py) — the SDK's config echo plays the same
# role /metrics plays for server-side config
_CLIENT_METRIC_KEYS = {"window": "window", "stripe": "stripe",
                       "hedge_budget_per_s": "hedgeBudgetPerS",
                       "hedge_floor_s": "hedgeFloorS",
                       "hedge_cap_s": "hedgeCapS",
                       "filter_max_age_s": "filterMaxAgeS",
                       "echo_cache_entries": "echoCacheEntries",
                       "fallback": "fallback"}


def _dataclass_fields(src: SourceFile) -> dict[str, dict[str, int]]:
    """class name -> {field name -> lineno} for the config dataclasses
    (AnnAssign fields only; ALL_CAPS constants and init=False fields are
    not CLI surface)."""
    out: dict[str, dict[str, int]] = {}
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.ClassDef) \
                or node.name not in _CLI_CLASSES:
            continue
        fields: dict[str, int] = {}
        for stmt in node.body:
            if not (isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)):
                continue
            fname = stmt.target.id
            if fname.isupper() or fname.startswith("_"):
                continue
            if isinstance(stmt.value, ast.Call) \
                    and dotted(stmt.value.func) in ("dataclasses.field",
                                                    "field"):
                init_kw = next((kw.value for kw in stmt.value.keywords
                                if kw.arg == "init"), None)
                if isinstance(init_kw, ast.Constant) \
                        and init_kw.value is False:
                    continue   # init=False: not constructor surface
            fields[fname] = stmt.lineno
        out[node.name] = fields
    return out


def _add_argument_dests(src: SourceFile) -> dict[str, int]:
    """argparse dest -> lineno for every add_argument call."""
    out: dict[str, int] = {}
    for node in ast.walk(src.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument" and node.args):
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Constant)
                and isinstance(first.value, str)):
            continue
        dest = next((kw.value.value for kw in node.keywords
                     if kw.arg == "dest"
                     and isinstance(kw.value, ast.Constant)), None)
        if dest is None:
            dest = first.value.lstrip("-").replace("-", "_")
        out[str(dest)] = node.lineno
    return out


def _args_reads(src: SourceFile) -> set[str]:
    """Every attribute read off an ``args`` namespace — plain
    ``args.x`` plus ``getattr(args, "x", ...)``."""
    reads: set[str] = set()
    for node in ast.walk(src.tree):
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "args"):
            reads.add(node.attr)
        elif (isinstance(node, ast.Call)
              and dotted(node.func) == "getattr" and len(node.args) >= 2
              and isinstance(node.args[0], ast.Name)
              and node.args[0].id == "args"
              and isinstance(node.args[1], ast.Constant)):
            reads.add(str(node.args[1].value))
    return reads


def _stats_dict_keys(src: SourceFile, func_name: str) -> set[str] | None:
    """String keys assembled by ``func_name``: dict-literal keys in any
    return/assignment plus ``out["key"] = ...`` subscript stores.
    None when the function is absent (sub-check skipped)."""
    fn = next((n for n in ast.walk(src.tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
               and n.name == func_name), None)
    if fn is None:
        return None
    keys: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Dict):
            keys.update(k.value for k in node.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str))
        elif (isinstance(node, ast.Assign)
              and any(isinstance(t, ast.Subscript) for t in node.targets)):
            for t in node.targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.slice, ast.Constant)
                        and isinstance(t.slice.value, str)):
                    keys.add(t.slice.value)
    return keys


def check_config_drift(project: Project) -> Iterator[Finding]:
    cfg = project.find("dfs_tpu/config.py")
    cli = project.find("dfs_tpu/cli/main.py")
    runtime = project.find("dfs_tpu/node/runtime.py")
    serve_pkg = project.find("dfs_tpu/serve/__init__.py")
    obs_pkg = project.find("dfs_tpu/obs/__init__.py")
    chaos_pkg = project.find("dfs_tpu/chaos/__init__.py")
    client_pkg = project.find("dfs_tpu/client/smart.py")
    classes = _dataclass_fields(cfg) if cfg and cfg.tree else {}

    # (1) every config field is wired through the serve CLI's
    # constructor calls in cmd_serve
    if cfg and cli and cli.tree and classes:
        cmd = next((n for n in ast.walk(cli.tree)
                    if isinstance(n, ast.FunctionDef)
                    and n.name == "cmd_serve"), None)
        if cmd is not None:
            calls: dict[str, ast.Call] = {}
            for node in ast.walk(cmd):
                if isinstance(node, ast.Call):
                    name = dotted(node.func)
                    if name and name.split(".")[-1] in classes:
                        calls[name.split(".")[-1]] = node
            for cls, fields in classes.items():
                call = calls.get(cls)
                if call is None:
                    continue   # class not constructed by the CLI at all
                passed = {kw.arg for kw in call.keywords if kw.arg}
                for fname, _lineno in sorted(fields.items()):
                    if fname in passed:
                        continue
                    yield Finding(
                        "DFS005", "error", cli.rel, call.lineno,
                        call.col_offset,
                        f"{cls}.{fname} is not passed by cmd_serve's "
                        f"{cls}(...) — the flag surface silently lost "
                        "this config field (deployments are pinned to "
                        "its default)",
                        f"cmd_serve:{cls}.{fname}")

    # (2) every declared flag is read somewhere (dead-flag detection:
    # an add_argument whose dest is never consumed parses and then
    # silently does nothing)
    if cli and cli.tree:
        reads = _args_reads(cli)
        for dest, lineno in sorted(_add_argument_dests(cli).items()):
            if dest in reads or dest in ("help",):
                continue
            yield Finding(
                "DFS005", "error", cli.rel, lineno, 0,
                f"flag dest `{dest}` is declared but `args.{dest}` is "
                "never read — the flag parses and silently does nothing",
                f"build_parser:{dest}")

    # (3) every config knob has its /metrics counterpart key, so a new
    # knob cannot ship observably-invisible
    for src, func, cls, table in (
            (runtime, "ingest_stats", "IngestConfig", _INGEST_METRIC_KEYS),
            (serve_pkg, "stats", "ServeConfig", _SERVE_METRIC_KEYS),
            (obs_pkg, "stats", "ObsConfig", _OBS_METRIC_KEYS),
            (runtime, "frag_stats", "FragmenterConfig",
             _FRAG_METRIC_KEYS),
            (runtime, "census_stats", "CensusConfig",
             _CENSUS_METRIC_KEYS),
            (runtime, "durability_stats", "DurabilityConfig",
             _DURABILITY_METRIC_KEYS),
            (chaos_pkg, "stats", "ChaosConfig", _CHAOS_METRIC_KEYS),
            (runtime, "ring_stats", "RingConfig", _RING_METRIC_KEYS),
            (runtime, "index_stats", "IndexConfig",
             _INDEX_METRIC_KEYS),
            (runtime, "tier_stats", "TierConfig", _TIER_METRIC_KEYS),
            (runtime, "sim_stats", "SimConfig", _SIM_METRIC_KEYS),
            (client_pkg, "stats", "ClientConfig",
             _CLIENT_METRIC_KEYS)):
        if src is None or src.tree is None or cls not in classes:
            continue
        keys = _stats_dict_keys(src, func)
        if keys is None:
            continue
        for fname in sorted(classes[cls]):
            want = table.get(fname)
            if want is None:
                yield Finding(
                    "DFS005", "error", cfg.rel,
                    classes[cls][fname], 0,
                    f"{cls}.{fname} has no /metrics mapping — add it to "
                    f"dfslint's {cls} metrics table AND surface it in "
                    f"{func}()",
                    f"{cls}:{fname}:unmapped")
            elif want not in keys:
                yield Finding(
                    "DFS005", "error", src.rel, 0, 0,
                    f"{func}() does not surface `{want}` — "
                    f"{cls}.{fname} lost its /metrics counterpart",
                    f"{func}:{fname}")


# ------------------------------------------------------------------ #
# DFS006 — copy discipline on the data plane
# ------------------------------------------------------------------ #

# the modules whose payload path is contractually zero-copy since r10
# (docs/wire.md): chunk bytes travel as buffer lists / memoryview
# slices from CAS read to socket write — a b"".join() or bytes() over
# them reintroduces exactly the full-body memcpy the scatter-gather
# wire exists to eliminate (WIRE_r10.json measures the cost)
_COPY_PLANE = ("dfs_tpu/comm/", "dfs_tpu/serve/", "dfs_tpu/store/",
               "dfs_tpu/node/")


def _on_copy_plane(rel: str) -> bool:
    return any(rel.startswith(p) or f"/{p}" in rel for p in _COPY_PLANE)


def check_copy_discipline(project: Project) -> Iterator[Finding]:
    """Flag payload-copying idioms inside data-plane modules:
    ``b"".join(...)`` (joins a buffer list into one body) and
    ``bytes(x)`` over a non-constant (materializes a memoryview). Both
    are sometimes legitimate — a deliberate ownership copy (the serve
    cache), a small header decode — and those sites carry an inline
    ``# dfslint: ignore[DFS006]`` with their justification; everything
    else is a hot-path regression the r10 zero-copy work paid to
    remove."""
    for src in project.files:
        if src.tree is None or not _on_copy_plane(src.rel):
            continue
        for node in src.nodes(ast.Call):
            what = detail = None
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "join"
                    and isinstance(node.func.value, ast.Constant)
                    and isinstance(node.func.value.value, bytes)
                    and not node.func.value.value):
                what = ('b"".join(...) assembles one contiguous body '
                        "from buffers — a full payload memcpy; keep the "
                        "buffer list (send_msg / resp_parts / "
                        "writer.write per buffer take it as-is)")
                detail = "join"
            elif (isinstance(node.func, ast.Name)
                  and node.func.id == "bytes" and len(node.args) == 1
                  and not isinstance(node.args[0], ast.Constant)
                  and not node.keywords):
                what = ("bytes(...) over a buffer materializes a copy — "
                        "pass the memoryview through (hashing, file "
                        "writes, socket writes all take views); if the "
                        "copy is a deliberate ownership transfer, "
                        "annotate it")
                detail = "bytes"
            if what is None:
                continue
            yield Finding(
                "DFS006", "error", src.rel, node.lineno, node.col_offset,
                f"{what} (data-plane copy discipline, docs/wire.md)",
                f"{src.qualname(node)}:{detail}")


# ------------------------------------------------------------------ #
# DFS007 — no silent swallow of failure-class exceptions
# ------------------------------------------------------------------ #

# the trees where a silently-eaten failure costs diagnosis: the data
# plane and node runtime. api/ answers the client (the error IS the
# signal there), cli/ is interactive, fragmenter/ops are compute.
_SWALLOW_SCOPE = ("dfs_tpu/comm/", "dfs_tpu/node/", "dfs_tpu/serve/",
                  "dfs_tpu/store/")
# exception names (last dotted component) that signal a FAILURE when
# caught — transport errors, broad catches, and the repo's own error
# classes. Absence-as-result types (FileNotFoundError, KeyError,
# queue.Empty, …) are normal control flow and are deliberately NOT
# listed: swallowing them is how optional lookups are written.
_FAILURE_EXCS = frozenset({
    "Exception", "BaseException", "RuntimeError", "OSError", "IOError",
    "ConnectionError", "TimeoutError", "RpcError", "RpcUnreachable",
    "RpcRemoteError", "WireError", "DownloadError", "UploadError",
    "ShedError",
})
# calls inside a handler that count as "the failure left a trace":
# logging, the flight-recorder journal, a metrics counter, liveness
# feedback (mark_dead/mark_alive transitions are themselves journaled
# and logged), or propagating to waiters (singleflight reject /
# future.set_exception)
_HANDLE_LOG_ATTRS = frozenset({"debug", "info", "warning", "error",
                               "exception", "critical"})
_HANDLE_EVIDENCE_ATTRS = frozenset({"inc", "event", "emit", "mark_dead",
                                    "mark_alive", "reject",
                                    "set_exception"})


def _catches_failure(handler: ast.ExceptHandler) -> str | None:
    """The failure-class name this handler catches, or None when every
    caught type is an absence-as-result type (or the handler is too
    dynamic to judge)."""
    t = handler.type
    if t is None:
        return "bare except"
    names = []
    if isinstance(t, ast.Tuple):
        names = [dotted(e) for e in t.elts]
    else:
        names = [dotted(t)]
    for name in names:
        if name and name.split(".")[-1] in _FAILURE_EXCS:
            return name
    return None


def _handler_leaves_trace(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            if attr in _HANDLE_LOG_ATTRS \
                    or attr in _HANDLE_EVIDENCE_ATTRS:
                return True
    return False


def check_silent_swallow(project: Project) -> Iterator[Finding]:
    """A caught transport/failure-class exception must leave a trace —
    log, journal event, metrics counter, liveness feedback, waiter
    propagation, or re-raise. An ``except RpcError: pass`` in the data
    plane turns a sick link into silence; the flight recorder
    (obs/journal.py) exists precisely so these moments survive the
    process. Deliberately-silent handlers (best-effort probes whose
    failure is metered one layer down) carry an inline
    ``# dfslint: ignore[DFS007]`` naming their reason."""
    for src in project.files:
        if src.tree is None:
            continue
        if not any(src.rel.startswith(p) or f"/{p}" in src.rel
                   for p in _SWALLOW_SCOPE):
            continue
        for node in src.nodes(ast.ExceptHandler):
            caught = _catches_failure(node)
            if caught is None or _handler_leaves_trace(node):
                continue
            yield Finding(
                "DFS007", "error", src.rel, node.lineno, node.col_offset,
                f"`except {caught}` swallows a failure-class exception "
                "with no trace — log it, journal it (obs.event), count "
                "it, or re-raise; a justified silent handler carries an "
                "inline ignore with its reason",
                f"{src.qualname(node)}:swallow-{caught}")


# ------------------------------------------------------------------ #
# DFS008 — thread-affinity race (phase-2, interprocedural)
# ------------------------------------------------------------------ #

# construction-time methods: writes here precede any sharing, so they
# never form one side of a race
_CTOR_METHODS = frozenset({"__init__", "__post_init__", "__new__"})


def _ctx_label(ctx: set) -> str:
    if LOOP in ctx and WORKER in ctx:
        return "loop+worker"
    return "worker thread" if WORKER in ctx else "event loop"


def check_affinity_race(project: Project) -> Iterator[Finding]:
    """The r13 ManifestStore resurrection race, as a machine check: an
    attribute of a runtime-shared object written from worker-thread
    context and read or written from event-loop context (or vice
    versa), with no common lock guarding both accesses. The contexts
    come from the phase-1 inference (async defs, executor/thread
    dispatch, trampolines, call-graph propagation); the lock sets come
    from the enclosing ``with <lock-ish>`` guards (the striped
    ``self._lock(fid)`` / ``self._mu[i]`` idioms count). Scoped to
    dfs_tpu/ — fixture trees and tooling do not share a runtime."""
    model = build_model(project)
    for (cls, attr), accs in sorted(model.accesses.items()):
        accs = [a for a in accs
                if a.fn.name not in _CTOR_METHODS
                and (a.fn.src.rel.startswith("dfs_tpu/")
                     or "/dfs_tpu/" in a.fn.src.rel)]
        writes = [a for a in accs if a.kind == "write"]
        if not writes:
            continue
        hit = None
        for w in writes:
            for o in accs:
                if o is w:
                    continue
                cross = (WORKER in w.fn.ctx and LOOP in o.fn.ctx) \
                    or (LOOP in w.fn.ctx and WORKER in o.fn.ctx)
                if not cross:
                    continue
                if w.locks & o.locks:
                    continue   # a common lock guards both sides
                hit = (w, o)
                break
            if hit:
                break
        if hit is None:
            continue
        w, o = hit
        # anchor the finding at the UNLOCKED side — that is where the
        # fix (or the justified inline ignore) belongs
        a, b = (w, o) if not w.locks or o.locks else (o, w)
        yield Finding(
            "DFS008", "error", a.fn.src.rel, a.node.lineno,
            a.node.col_offset,
            f"affinity race on {cls}.{attr}: {a.kind} in "
            f"`{a.fn.name}` ({_ctx_label(a.fn.ctx)}"
            + (f", holding {sorted(a.locks)}" if a.locks else ", no lock")
            + f") vs {b.kind} in `{b.fn.name}` "
            f"({_ctx_label(b.fn.ctx)}, "
            + (f"holding {sorted(b.locks)}" if b.locks else "no lock")
            + f" — {b.fn.src.rel}:{b.node.lineno}) with no common lock "
            "— guard both sides with one lock, or confine the "
            "attribute to one context",
            f"{cls}.{attr}:affinity")


# ------------------------------------------------------------------ #
# DFS009 — buffer lifetime (phase-2, interprocedural)
# ------------------------------------------------------------------ #

# where borrowed views circulate: the zero-copy data plane plus the
# staging/sharding engines (the r15 bug lived in fragmenter staging)
_VIEW_PLANE = ("dfs_tpu/comm/", "dfs_tpu/serve/", "dfs_tpu/store/",
               "dfs_tpu/node/", "dfs_tpu/fragmenter/",
               "dfs_tpu/parallel/", "dfs_tpu/index/")
# container-mutating calls that retain their argument: a borrowed view
# passed here outlives the frame/pool guard that makes it valid
_VIEW_SINK_METHODS = frozenset({"append", "appendleft", "add", "put",
                                "insert", "push", "extend",
                                "setdefault", "put_nowait"})


def _self_rooted(expr: ast.AST) -> str | None:
    """Dotted chain when ``expr`` hangs off ``self`` (through
    attributes/subscripts), else None."""
    base = expr
    while isinstance(base, (ast.Attribute, ast.Subscript)):
        base = base.value
    if isinstance(base, ast.Name) and base.id == "self":
        d = dotted(expr if not isinstance(expr, ast.Subscript)
                   else expr.value)
        return d or "self.<expr>"
    return None


def check_buffer_lifetime(project: Project) -> Iterator[Finding]:
    """The r15 staging-buffer recycle bug and the r10 cache-ownership
    rule, enforced: a ``memoryview``/buffer obtained from a pooled or
    staged source (``memoryview`` over a pooled ``self`` buffer or a
    borrowed argument, ``unpack_chunks`` views, a call to a function
    the model knows returns views) must not ESCAPE into state that
    outlives the guard making it valid — a ``self.``-rooted attribute
    or container, or a spawned task. Copy first (``bytes(view)``) or
    keep the view local; a deliberate hand-off is annotated inline."""
    model = build_model(project)
    for fi in model.functions.values():
        src = fi.src
        if src.tree is None or isinstance(fi.node, ast.Lambda):
            continue
        if not any(src.rel.startswith(p) or f"/{p}" in src.rel
                   for p in _VIEW_PLANE):
            continue
        if fi.name in _CTOR_METHODS:
            continue
        views = view_vars(model, fi)
        for node in scope_nodes(fi.node):
            what = anchor = None
            if isinstance(node, ast.Assign):
                stored = next(
                    (t for t in node.targets
                     if isinstance(t, (ast.Attribute, ast.Subscript))
                     and _self_rooted(t)), None)
                if stored is not None \
                        and is_view_expr(model, fi, node.value, views):
                    what = (f"a borrowed buffer view is stored into "
                            f"`{_self_rooted(stored)}`")
                    anchor = node
            elif isinstance(node, ast.Call):
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr in _VIEW_SINK_METHODS \
                        and _self_rooted(node.func.value):
                    viewarg = next(
                        (a for a in node.args
                         if is_view_expr(model, fi, a, views)), None)
                    if viewarg is not None:
                        what = (f"a borrowed buffer view escapes into "
                                f"`{_self_rooted(node.func.value)}"
                                f".{node.func.attr}(...)`")
                        anchor = node
                elif _is_spawn(node):
                    inner = next(
                        (nm for a in node.args for nm in ast.walk(a)
                         if isinstance(nm, ast.Name)
                         and nm.id in views), None)
                    if inner is not None:
                        what = (f"a borrowed buffer view `{inner.id}` is "
                                "captured by a spawned task")
                        anchor = node
            if what is None:
                continue
            yield Finding(
                "DFS009", "error", src.rel, anchor.lineno,
                anchor.col_offset,
                f"{what}, outliving the frame/pool guard that keeps the "
                "view valid — the backing buffer can be recycled or "
                "freed while this reference is live (the r15 staging "
                "recycle bug / r10 cache-ownership rule, docs/lint.md). "
                "Copy it (`bytes(view)`) or keep it local; annotate a "
                "deliberate hand-off inline",
                f"{src.qualname(anchor)}:{fi.name}:view-escape")


# ------------------------------------------------------------------ #
# DFS010 — wire-protocol contract (phase-2, cross-file)
# ------------------------------------------------------------------ #

# header fields the transport layer itself owns (attached/consumed
# outside any one op's client/handler pair). `deadline` (r18) is the
# remaining end-to-end budget the RPC client stamps per attempt and the
# frame server consumes before dispatch — envelope, like `trace`.
_WIRE_UNIVERSAL_REQ = frozenset({"op", "trace", "repoch", "rfp",
                                 "deadline"})
_WIRE_UNIVERSAL_REPLY = frozenset({"ok", "error", "ringEpoch", "ring"})
# client-side send seams: a dict literal carrying "op" passed to one of
# these methods is a wire call site
_WIRE_CALL_ATTRS = frozenset({"call", "_call_once", "_call_retrying",
                              "_call_converging"})


def _op_of_dict(d: ast.Dict) -> str | None:
    for k, v in zip(d.keys, d.values):
        if isinstance(k, ast.Constant) and k.value == "op" \
                and isinstance(v, ast.Constant) and isinstance(v.value, str):
            return v.value
    return None


def _dict_fields(d: ast.Dict) -> tuple[set[str], bool]:
    """(constant keys, has-dynamic-part) of a dict literal."""
    keys: set[str] = set()
    dynamic = False
    for k in d.keys:
        if isinstance(k, ast.Constant) and isinstance(k.value, str):
            keys.add(k.value)
        else:
            dynamic = True   # **spread or computed key
    return keys, dynamic


def _wire_client_sites(project: Project) -> dict[str, dict]:
    """op -> {sent, sent_open, reads, site(src, line)} across dfs_tpu/:
    every ``*.call(peer, {"op": ...})``-shaped send, including headers
    built in a local var and extended via ``header["k"] = ...``."""
    ops: dict[str, dict] = {}

    def rec(op: str) -> dict:
        return ops.setdefault(op, {"sent": set(), "sent_open": False,
                                   "reads": set(), "site": None})

    for src in project.files:
        if src.tree is None or not src.rel.startswith("dfs_tpu/"):
            continue
        for fn in src.nodes(ast.FunctionDef, ast.AsyncFunctionDef):
            header_vars: dict[str, str] = {}
            resp_vars: dict[str, str] = {}
            nodes = sorted(
                (n for n in ast.walk(fn)
                 if isinstance(n, (ast.Assign, ast.AnnAssign, ast.Call,
                                   ast.Subscript, ast.Attribute))),
                key=lambda n: (n.lineno, n.col_offset))
            for n in nodes:
                # header = {"op": "...", ...} (plain or annotated)
                tgt = None
                if isinstance(n, ast.Assign) and len(n.targets) == 1:
                    tgt = n.targets[0]
                elif isinstance(n, ast.AnnAssign):
                    tgt = n.target
                if tgt is not None and isinstance(tgt, ast.Name) \
                        and isinstance(getattr(n, "value", None), ast.Dict):
                    op = _op_of_dict(n.value)
                    if op is not None:
                        header_vars[tgt.id] = op
                        keys, dyn = _dict_fields(n.value)
                        r = rec(op)
                        r["sent"] |= keys - {"op"}
                        r["sent_open"] |= dyn
                # header["k"] = ...
                elif isinstance(n, ast.Assign) \
                        and isinstance(n.targets[0], ast.Subscript) \
                        and isinstance(n.targets[0].value, ast.Name) \
                        and n.targets[0].value.id in header_vars:
                    sl = n.targets[0].slice
                    op = header_vars[n.targets[0].value.id]
                    if isinstance(sl, ast.Constant) \
                            and isinstance(sl.value, str):
                        rec(op)["sent"].add(sl.value)
                    else:
                        rec(op)["sent_open"] = True
                elif isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Attribute) \
                        and n.func.attr in _WIRE_CALL_ATTRS:
                    op = None
                    for a in n.args:
                        if isinstance(a, ast.Dict):
                            got = _op_of_dict(a)
                            if got is not None:
                                op = got
                                keys, dyn = _dict_fields(a)
                                r = rec(op)
                                r["sent"] |= keys - {"op"}
                                r["sent_open"] |= dyn
                        elif isinstance(a, ast.Name) \
                                and a.id in header_vars:
                            op = header_vars[a.id]
                    if op is None:
                        continue
                    r = rec(op)
                    if r["site"] is None:
                        r["site"] = (src, n.lineno)
                    # resp, body = await self.call(...) → reply reads
                    up: ast.AST = n
                    while isinstance(src.parents.get(up),
                                     (ast.Await,)):
                        up = src.parents.get(up)
                    asn = src.parents.get(up)
                    if isinstance(asn, ast.Assign) \
                            and len(asn.targets) == 1 \
                            and isinstance(asn.targets[0], ast.Tuple) \
                            and asn.targets[0].elts \
                            and isinstance(asn.targets[0].elts[0],
                                           ast.Name):
                        resp_vars[asn.targets[0].elts[0].id] = op
                    continue
                # reply reads, attributed IN LINE ORDER to whatever op
                # the variable is bound to at this point — a reused
                # `resp` var must not retro-attribute earlier reads to
                # a later op (single ordered pass; code-review fix)
                key = None
                if isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Attribute) \
                        and n.func.attr == "get" \
                        and isinstance(n.func.value, ast.Name) \
                        and n.func.value.id in resp_vars and n.args \
                        and isinstance(n.args[0], ast.Constant):
                    key = (resp_vars[n.func.value.id],
                           str(n.args[0].value))
                elif isinstance(n, ast.Subscript) \
                        and isinstance(n.value, ast.Name) \
                        and n.value.id in resp_vars \
                        and isinstance(n.slice, ast.Constant) \
                        and isinstance(n.slice.value, str):
                    key = (resp_vars[n.value.id], n.slice.value)
                if key is not None:
                    rec(key[0])["reads"].add(key[1])
    return ops


def _wire_handlers(runtime: SourceFile) -> dict[str, dict] | None:
    """op -> {reads, produces, open_reply, line} from the ``if op ==
    "<name>":`` branches of runtime._dispatch. None when the seam is
    absent (fixture trees without a runtime)."""
    fn = next((n for n in ast.walk(runtime.tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
               and n.name == "_dispatch"), None)
    if fn is None:
        return None
    out: dict[str, dict] = {}
    for stmt in ast.walk(fn):
        if not (isinstance(stmt, ast.If)
                and isinstance(stmt.test, ast.Compare)
                and isinstance(stmt.test.left, ast.Name)
                and stmt.test.left.id == "op"
                and len(stmt.test.ops) == 1
                and isinstance(stmt.test.ops[0], ast.Eq)
                and isinstance(stmt.test.comparators[0], ast.Constant)
                and isinstance(stmt.test.comparators[0].value, str)):
            continue
        op = stmt.test.comparators[0].value
        h = out.setdefault(op, {"reads": set(), "produces": set(),
                                "open_reply": False,
                                "line": stmt.lineno})
        # scope-limited walk: a nested def's returns (a worker closure
        # inside the op's branch) are NOT the op's reply
        todo = list(stmt.body)
        while todo:
            n = todo.pop()
            if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                todo.extend(ast.iter_child_nodes(n))
            if isinstance(n, ast.Call) \
                    and isinstance(n.func, ast.Attribute) \
                    and n.func.attr == "get" \
                    and isinstance(n.func.value, ast.Name) \
                    and n.func.value.id == "header" and n.args \
                    and isinstance(n.args[0], ast.Constant):
                h["reads"].add(str(n.args[0].value))
            elif isinstance(n, ast.Subscript) \
                    and isinstance(n.value, ast.Name) \
                    and n.value.id == "header" \
                    and isinstance(n.slice, ast.Constant) \
                    and isinstance(n.slice.value, str):
                h["reads"].add(n.slice.value)
            elif isinstance(n, ast.Return) and n.value is not None:
                reply = n.value
                if isinstance(reply, ast.Tuple) and reply.elts:
                    reply = reply.elts[0]
                if isinstance(reply, ast.Dict):
                    keys, dyn = _dict_fields(reply)
                    h["produces"] |= keys
                    h["open_reply"] |= dyn
                else:
                    h["open_reply"] = True
    return out


def _wire_specs(wire: SourceFile) -> dict[str, dict] | None:
    """The declarative op table ``OP_SPECS`` in comm/wire.py: op ->
    {"request": [...], "reply": [...]} — the documentation side of the
    three-way contract. None when absent."""
    for node in ast.walk(wire.tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == "OP_SPECS":
            try:
                specs = ast.literal_eval(node.value)
            except (ValueError, SyntaxError):
                return None
            if isinstance(specs, dict):
                return {str(k): v for k, v in specs.items()
                        if isinstance(v, dict)}
    return None


def check_wire_contract(project: Project) -> Iterator[Finding]:
    """Three-way agreement for every internal op: the client call
    sites (comm/rpc.py + runtime raw sends), the handler table
    (runtime._dispatch), and the op documentation (comm/wire.py
    OP_SPECS). Fails on a sent-but-unhandled op, a handled-but-
    undocumented (or documented-but-unhandled) op, and any request/
    reply field read by one side and never produced by the other —
    the client-op/server-handler drift hand-caught in four review
    rounds, as a gate."""
    runtime = project.find("dfs_tpu/node/runtime.py")
    wire = project.find("dfs_tpu/comm/wire.py")
    if runtime is None or runtime.tree is None:
        return
    handlers = _wire_handlers(runtime)
    if handlers is None:
        return
    sites = _wire_client_sites(project)
    specs = _wire_specs(wire) if wire is not None and wire.tree else None
    if specs is None and wire is not None and wire.tree is not None \
            and handlers:
        yield Finding(
            "DFS010", "error", wire.rel, 0, 0,
            "comm/wire.py has no OP_SPECS table — every handled op is "
            "undocumented; declare op -> {request: [...], reply: [...]} "
            "so the wire contract is machine-checkable (docs/lint.md)",
            "wire:<no-specs>")

    for op in sorted(sites):
        site = sites[op]
        if site["site"] is None:
            continue   # reply-reads only (no send site found): skip
        src, line = site["site"]
        if op not in handlers:
            yield Finding(
                "DFS010", "error", src.rel, line, 0,
                f"op `{op}` is sent here but runtime._dispatch has no "
                "handler branch for it — the peer answers 'unknown op' "
                "and the caller fails on every try",
                f"wire:{op}:unhandled")
            continue
        h = handlers[op]
        if not site["sent_open"]:
            for fld in sorted(h["reads"] - site["sent"]
                              - _WIRE_UNIVERSAL_REQ):
                yield Finding(
                    "DFS010", "error", runtime.rel, h["line"], 0,
                    f"op `{op}` handler reads request field `{fld}` "
                    "that no client call site ever sends — the handler "
                    "always sees its default/KeyError side",
                    f"wire:{op}:req:{fld}")
        if not h["open_reply"]:
            for fld in sorted(site["reads"] - h["produces"]
                              - _WIRE_UNIVERSAL_REPLY):
                yield Finding(
                    "DFS010", "error", src.rel, line, 0,
                    f"op `{op}` client reads reply field `{fld}` that "
                    "the handler never produces — the read always "
                    "yields its default",
                    f"wire:{op}:reply:{fld}")

    for op in sorted(handlers):
        h = handlers[op]
        if specs is not None and op not in specs:
            yield Finding(
                "DFS010", "error", runtime.rel, h["line"], 0,
                f"op `{op}` is handled but undocumented — add it to "
                "comm/wire.py OP_SPECS (request/reply fields) so the "
                "wire contract stays machine-checkable",
                f"wire:{op}:undocumented")
    if specs is not None and handlers:
        for op in sorted(set(specs) - set(handlers)):
            yield Finding(
                "DFS010", "error", wire.rel, 0, 0,
                f"OP_SPECS documents op `{op}` but runtime._dispatch "
                "has no handler for it — stale documentation (or a "
                "handler lost in a refactor)",
                f"wire:{op}:doc-unhandled")
        # field-level doc agreement: the spec must list exactly what
        # moves (universal transport fields excluded)
        for op in sorted(set(specs) & set(handlers)):
            spec = specs[op]
            h = handlers[op]
            site = sites.get(op)
            doc_req = set(spec.get("request", ()))
            doc_reply = set(spec.get("reply", ()))
            want_req = set(h["reads"])
            if site and not site["sent_open"]:
                want_req |= site["sent"]
            want_req -= _WIRE_UNIVERSAL_REQ
            if site and site["sent_open"]:
                missing = (want_req - doc_req, set())
            else:
                missing = (want_req - doc_req, doc_req - want_req)
            want_reply = set(site["reads"]) if site else set()
            if not h["open_reply"]:
                want_reply |= h["produces"]
            # only the frame envelope is implicit in the spec; a
            # handler genuinely producing `ring` (get_ring) documents it
            want_reply -= {"ok", "error"}
            if h["open_reply"]:
                rmissing = (want_reply - doc_reply, set())
            else:
                rmissing = (want_reply - doc_reply,
                            doc_reply - want_reply)
            for fld in sorted(missing[0]):
                yield Finding(
                    "DFS010", "error", wire.rel, 0, 0,
                    f"OP_SPECS[{op!r}] is missing request field "
                    f"`{fld}` that the live client/handler pair uses",
                    f"wire:{op}:doc-req:{fld}")
            for fld in sorted(missing[1]):
                yield Finding(
                    "DFS010", "error", wire.rel, 0, 0,
                    f"OP_SPECS[{op!r}] documents request field `{fld}` "
                    "that neither the client sends nor the handler "
                    "reads — stale documentation",
                    f"wire:{op}:doc-req-stale:{fld}")
            for fld in sorted(rmissing[0]):
                yield Finding(
                    "DFS010", "error", wire.rel, 0, 0,
                    f"OP_SPECS[{op!r}] is missing reply field `{fld}` "
                    "that the live client/handler pair uses",
                    f"wire:{op}:doc-reply:{fld}")
            for fld in sorted(rmissing[1]):
                yield Finding(
                    "DFS010", "error", wire.rel, 0, 0,
                    f"OP_SPECS[{op!r}] documents reply field `{fld}` "
                    "that is neither produced nor read — stale "
                    "documentation",
                    f"wire:{op}:doc-reply-stale:{fld}")


# ------------------------------------------------------------------ #
# DFS000 — stale-suppression audit
# ------------------------------------------------------------------ #

def audit_suppressions(project: Project) -> Iterator[Finding]:
    """Every ``# dfslint: ignore[RULE]`` must still suppress a live
    finding: a suppression that matches nothing is rot — it reads as a
    justified exception while silently covering NOTHING, and would
    mask the next real finding on its line. Runs after every rule (the
    usage bookkeeping lives in ``SourceFile.is_suppressed``)."""
    for src in project.files:
        if src.parse_error is not None:
            continue
        used_lines = {ln for ln, _ in src.suppressions_used}
        for line, rules in sorted(src.suppressed.items()):
            for r in sorted(rules):
                stale = line not in used_lines if r == "*" \
                    else (line, r) not in src.suppressions_used
                if not stale:
                    continue
                label = "ignore" if r == "*" else f"ignore[{r}]"
                yield Finding(
                    "DFS000", "warning", src.rel, line, 0,
                    f"stale suppression: `# dfslint: {label}` no longer "
                    "matches any finding on this line — remove it (a "
                    "dead suppression silently covers the NEXT real "
                    "finding here)",
                    f"<suppress>:{r}:L{line}")


def audit_baseline(project: Project, baseline: set[str],
                   live_keys: set[str]) -> Iterator[Finding]:
    """Baseline entries that no longer match a live finding are the
    same rot one level up; ``--update-baseline`` prunes them (the
    default-scope rewrite only keeps what it saw). Keys whose path was
    not scanned this run are skipped — a narrowed run must not
    false-flag entries it cannot judge."""
    scanned = {s.rel for s in project.files}
    for key in sorted(baseline - live_keys):
        parts = key.split(":", 2)
        if len(parts) != 3 or parts[1] not in scanned:
            continue
        yield Finding(
            "DFS000", "warning", parts[1], 0, 0,
            f"stale baseline entry `{key}`: no current finding matches "
            "it — prune with --update-baseline (the committed-empty "
            "baseline discipline must not rot)",
            f"<baseline>:{key}")


# ------------------------------------------------------------------ #
# registry
# ------------------------------------------------------------------ #

ALL_RULES = (
    ("DFS001", "blocking call in loop-affine code",
     check_blocking_in_async),
    ("DFS002", "dropped asyncio task", check_dropped_task),
    ("DFS003", "lock discipline across sync/async", check_lock_discipline),
    ("DFS004", "digest outside utils/hashing + ops", check_digest_boundary),
    ("DFS005", "CLI/config//metrics drift", check_config_drift),
    ("DFS006", "data-plane copy discipline", check_copy_discipline),
    ("DFS007", "silent swallow of failure exceptions",
     check_silent_swallow),
    ("DFS008", "thread-affinity race", check_affinity_race),
    ("DFS009", "buffer lifetime / view escape", check_buffer_lifetime),
    ("DFS010", "wire-protocol contract", check_wire_contract),
    # phase 3 (scripts/dfslint/durability.py): the persistence-
    # ordering model — crash-consistency disciplines as lexical facts
    ("DFS011", "durability ordering (fsync-before-visible, re-fsync "
     "after utime, create-only segment opens)",
     check_durability_ordering),
    ("DFS012", "torn-read discipline (append-only formats read via "
     "blessed decoders)", check_torn_read_discipline),
    ("DFS013", "crash-point coverage (registry fired + exercised, "
     "multi-step persistence sequences seamed)",
     check_crash_point_coverage),
)


def run_rules(project: Project,
              timings: dict | None = None) -> list[Finding]:
    """All passes over one parsed project, minus inline suppressions,
    plus the stale-suppression audit. Unparseable files surface as
    DFS000 findings (a syntax error must fail the gate, not silently
    shrink the scanned set). ``timings``, when given, is filled with
    per-phase seconds (``model`` + one entry per rule + ``audit``) —
    the ``--stats`` breakdown — and under ``"cpu"`` the same phases in
    this thread's CPU seconds, which the tier-1 budget compares: a
    phase's share of the CPU does not move with the host's load."""
    import time as _time

    def clocks() -> tuple[float, float]:
        return _time.perf_counter(), _time.thread_time()

    def lap(phase: str, t0: tuple[float, float]) -> None:
        if timings is not None:
            wall, cpu = clocks()
            timings[phase] = wall - t0[0]
            timings.setdefault("cpu", {})[phase] = cpu - t0[1]

    out: list[Finding] = []
    by_rel = {s.rel: s for s in project.files}
    for src in project.files:
        if src.parse_error is not None:
            out.append(Finding(
                "DFS000", "error", src.rel,
                src.parse_error.lineno or 0, 0,
                f"syntax error: {src.parse_error.msg}", "<parse>"))
    t0 = clocks()
    build_model(project)   # phase 1, built once, shared by every rule
    lap("model", t0)
    for rule_id, _desc, fn in ALL_RULES:
        t0 = clocks()
        for f in fn(project):
            src = by_rel.get(f.path)
            if src is not None and src.is_suppressed(f.rule, f.line):
                continue
            out.append(f)
        lap(rule_id, t0)
    t0 = clocks()
    out.extend(audit_suppressions(project))
    lap("audit", t0)
    return out
