#!/usr/bin/env python
"""Lint: every ``*_rNN.json`` benchmark artifact cited from committed
code must exist in the repo.

The repo's credibility system is artifact-backed claims ("every perf
number resolves to a committed artifact", BASELINE.md preamble) — and
the failure mode that broke it twice (VERDICT r3, r5) was a docstring
citing an artifact that was never committed (the round-5 ``SLOW_r05``
phantom in `tests/test_sha256.py:64` — spelled without its extension
here so the lint does not flag its own cautionary tale). This lint
makes the phantom citation a tier-1 failure instead of a judge finding.

Scope: CODE files (.py / .cpp / .h) — prose (.md) is allowed to discuss
artifact naming schemes in the abstract. A citation is the literal
pattern ``<NAME>_r<two digits><optional letter>.json``; cited files must
exist at the repo root.

Usage: ``python scripts/check_artifacts.py [repo_root]`` — exits 1 and
prints each dangling citation as ``path:line: <artifact>``. Also
importable (``check(repo_root) -> list[str]``) — tier-1 runs it via
``tests/test_check_artifacts.py``.
"""

from __future__ import annotations

import re
import subprocess
import sys
from pathlib import Path

CITATION = re.compile(r"\b([A-Za-z0-9]\w*_r[0-9]{2}[a-z]?\.json)\b")
CODE_SUFFIXES = (".py", ".cpp", ".h")

# Artifacts an acceptance gate names directly: these must exist even if
# no committed code happens to cite them. Only enforced when linting
# THIS repo (detected by this script's own path) — fabricated test
# repos are exempt.
REQUIRED_ARTIFACTS = ("OBS_r09.json", "WIRE_r10.json", "OBS2_r11.json",
                      "CENSUS_r12.json", "CHAOS_r13.json",
                      "REBALANCE_r14.json", "CDC_SHARD_r15.json",
                      "DEDUP_INDEX_r16.json", "OVERLOAD_r18.json",
                      "CLIENT_r19.json", "TIER_r20.json",
                      "SIM_r21.json", "OVERLOAD_r29.json")


def _tracked_files(root: Path) -> list[Path]:
    """git-tracked files (committed code is the contract), falling back
    to a filesystem walk when git is unavailable."""
    try:
        out = subprocess.run(
            ["git", "ls-files"], cwd=root, capture_output=True,
            text=True, check=True).stdout
        return [root / line for line in out.splitlines() if line]
    except (OSError, subprocess.CalledProcessError):
        return [p for p in root.rglob("*")
                if p.is_file() and ".git" not in p.parts]


def check(root: Path | str = ".") -> list[str]:
    """-> list of ``path:line: artifact`` strings for every citation of
    a ``*_rNN.json`` that does not exist at the repo root."""
    root = Path(root).resolve()
    problems: list[str] = []
    for path in _tracked_files(root):
        if path.suffix not in CODE_SUFFIXES or not path.is_file():
            continue
        try:
            text = path.read_text(errors="replace")
        except OSError:
            continue
        for lineno, line in enumerate(text.splitlines(), 1):
            for m in CITATION.finditer(line):
                name = m.group(1)
                if not (root / name).is_file():
                    problems.append(
                        f"{path.relative_to(root)}:{lineno}: {name}")
    if (root / "scripts" / "check_artifacts.py").is_file():
        for name in REQUIRED_ARTIFACTS:
            if not (root / name).is_file():
                problems.append(
                    f"scripts/check_artifacts.py:REQUIRED: {name}")
    return problems


def main(argv: list[str]) -> int:
    root = Path(argv[1]) if len(argv) > 1 else Path(__file__).parent.parent
    problems = check(root)
    for p in problems:
        print(f"dangling artifact citation: {p}", file=sys.stderr)
    if problems:
        print(f"{len(problems)} dangling artifact citation(s) — every "
              "perf claim in code must resolve to a committed artifact",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
