"""dfslint — project-specific AST concurrency & invariant analyzer.

PRs 2-3 grew the node into a genuinely concurrent system: an asyncio
event loop fronting bounded thread pools (store/aio.py), fire-and-forget
tasks (serve/prefetch.py, node/health.py), windowed placement with
completion sentinels (node/runtime.py), and ``threading.Lock``s shared
across both worlds. The bug classes that mix produces — a sync syscall
eating the event loop, a dropped task swallowing its exception, an
``await`` under a thread lock, a digest computed outside the one
verified implementation, a CLI flag silently losing its config field —
are all *lexically visible*, so this package makes them machine-checkable
on every tier-1 run (the same way scripts/check_artifacts.py made
benchmark-citation hygiene machine-checkable).

Pure stdlib ``ast`` — no new dependencies. See docs/lint.md for the rule
catalogue, suppression syntax (``# dfslint: ignore[DFS001]``) and the
committed baseline (scripts/dfslint/baseline.json).

Usage::

    python -m scripts.dfslint dfs_tpu scripts   # exit 0 clean / 1 findings
    python -m scripts.dfslint --json            # machine-readable output
    python -m scripts.dfslint --update-baseline # accept current findings
"""

from __future__ import annotations

import time

from scripts.dfslint.core import (Finding, Project, SourceFile,
                                  collect_sources, load_baseline,
                                  save_baseline)
from scripts.dfslint.model import ProjectModel, build_model
from scripts.dfslint.rules import (ALL_RULES, audit_baseline, run_rules)

__all__ = ["ALL_RULES", "Finding", "Project", "ProjectModel",
           "SourceFile", "analyze", "build_model", "collect_sources",
           "load_baseline", "run_rules", "save_baseline"]


def analyze(roots, repo_root,
            baseline: set[str] | frozenset[str] = frozenset(),
            stats: dict | None = None,
            only_paths: set[str] | None = None) -> list[Finding]:
    """Walk ``roots``, run every rule (phase-1 model built once, shared
    by all of them), drop suppressed + baselined findings, and audit
    stale baseline entries. The one entry point the CLI and the tier-1
    test share. ``stats``, when given, is filled in place with the
    ``--stats`` timing breakdown: ``files``, ``walkS``, ``totalS``,
    and per-phase ``phases`` (model + each rule + audit), plus ``cpu``,
    the same three in this thread's CPU seconds.

    ``only_paths`` (the ``--changed`` mode): REPORT only findings whose
    path is in the set, but still walk and model the full ``roots`` —
    the interprocedural facts (call graph, affinity, persistence
    effects) stay whole-tree sound, so a changed callee still fires on
    its unchanged caller's path being absent rather than on a model
    built from a partial tree."""
    t_start, c_start = time.perf_counter(), time.thread_time()
    project = Project(collect_sources(roots, repo_root))
    t_walk = time.perf_counter() - t_start
    c_walk = time.thread_time() - c_start
    timings: dict | None = {} if stats is not None else None
    findings = run_rules(project, timings=timings)
    live_keys = {f.key for f in findings}
    out = [f for f in findings if f.key not in baseline]
    out.extend(audit_baseline(project, set(baseline), live_keys))
    if only_paths is not None:
        out = [f for f in out if f.path in only_paths]
    out.sort(key=lambda f: (f.path, f.line, f.rule))
    if stats is not None:
        cpu = (timings or {}).pop("cpu", {})
        stats.update({
            "files": len(project.files),
            "findings": len(out),
            "walkS": round(t_walk, 6),
            "phases": {k: round(v, 6)
                       for k, v in (timings or {}).items()},
            "totalS": round(time.perf_counter() - t_start, 6),
            "cpu": {"walkS": round(c_walk, 6),
                    "phases": {k: round(v, 6) for k, v in cpu.items()},
                    "totalS": round(time.thread_time() - c_start, 6)},
        })
    return out
