"""Shared infrastructure for the dfslint passes: the file walker, parsed
source model, finding/severity model, inline suppressions, and the
committed baseline.

Design constraints that shaped this module:

- One parse per file: every rule runs over the same ``SourceFile`` set
  (the "multi-pass over one walk" shape), so adding a rule never adds a
  filesystem pass.
- Findings carry a line (for humans) but are *keyed* without one: a
  baseline entry pinned to a line number rots on every unrelated edit
  above it, so keys are ``RULE:path:context`` where context is the
  enclosing function plus a rule-chosen detail.
- The walker must skip non-source trees — ``__pycache__`` droppings,
  built ``*.so``/binaries under ``native/``, data/download dirs — or a
  stale ``.pyc``-era file shadows the real finding set.
"""

from __future__ import annotations

import ast
import dataclasses
import json
import re
from pathlib import Path
from typing import Iterable, Iterator

# directory names never descended into: bytecode caches, VCS state, and
# the runtime/data trees nodes create next to the repo
SKIP_DIRS = frozenset({"__pycache__", ".git", ".pytest_cache",
                       ".hypothesis", "data", "downloads", "node_modules",
                       ".venv", "venv"})

SEVERITIES = ("error", "warning")

# a suppression is the marker inside a real COMMENT token, introduced
# at the comment start or after whitespace (`# noqa  # dfslint: …`
# combines; a docstring or a backtick-quoted mention in prose — docs,
# the linter's own sources — is NOT a suppression; the r17
# stale-suppression audit made that distinction load-bearing)
_SUPPRESS = re.compile(
    r"(?:^|(?<=\s))#\s*dfslint:\s*ignore"
    r"(?:\[\s*([A-Za-z0-9_,\s]+?)\s*\])?")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation. ``key`` (rule:path:context) is the stable,
    line-free identity used by the baseline; ``line``/``col`` are for
    the human reading the report."""

    rule: str          # "DFS001" .. "DFS005" (or "DFS000" parse error)
    severity: str      # "error" | "warning"
    path: str          # repo-relative posix path
    line: int
    col: int
    message: str
    context: str       # enclosing-scope qualname + rule-chosen detail

    @property
    def key(self) -> str:
        return f"{self.rule}:{self.path}:{self.context}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: "
                f"{self.rule} [{self.severity}] {self.message}")

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["key"] = self.key
        return d


class SourceFile:
    """One parsed Python source: text, AST (or a parse error), parent
    map, and the line -> suppressed-rules table."""

    def __init__(self, path: Path, rel: str) -> None:
        self.path = path
        self.rel = rel
        self.text = path.read_text(encoding="utf-8", errors="replace")
        self.lines = self.text.splitlines()
        self.tree: ast.Module | None = None
        self.parse_error: SyntaxError | None = None
        self.parents: dict[ast.AST, ast.AST] = {}
        # parse-once node index: every node bucketed by type in ONE walk
        # (each of the r08 rules re-ran ast.walk per file; with the
        # interprocedural phase the shared index keeps the whole run
        # inside the tier-1 wall-clock budget — see --stats)
        self._by_type: dict[type, list[ast.AST]] = {}
        try:
            self.tree = ast.parse(self.text)
        except SyntaxError as e:
            self.parse_error = e
        if self.tree is not None:
            for parent in ast.walk(self.tree):
                self._by_type.setdefault(type(parent), []).append(parent)
                for child in ast.iter_child_nodes(parent):
                    self.parents[child] = parent
        # line -> set of suppressed rule ids; "*" = all rules. A bare
        # standalone `# dfslint: ignore[...]` comment line covers the
        # next non-comment, non-blank line (so a suppression can carry
        # its justification without fighting line length). Comments are
        # found by TOKENIZING (not a per-line regex): a string literal
        # containing the marker — docs quoting the syntax — must not
        # count, or the stale-suppression audit flags the quote.
        self.suppressed: dict[int, set[str]] = {}
        # (line, rule) pairs that actually suppressed a finding this
        # run — the DFS000 stale-suppression audit's evidence
        self.suppressions_used: set[tuple[int, str]] = set()
        comments = self._comment_lines()
        carry: set[str] | None = None
        for lineno, raw in enumerate(self.lines, 1):
            stripped = raw.strip()
            m = _SUPPRESS.search(comments.get(lineno, ""))
            rules: set[str] | None = None
            if m:
                rules = ({r.strip().upper() for r in m.group(1).split(",")}
                         if m.group(1) else {"*"})
            if stripped.startswith("#"):
                if rules:
                    carry = (carry or set()) | rules
                continue
            if not stripped:
                continue
            eff = set(rules or set())
            if carry:
                eff |= carry
                carry = None
            if eff:
                self.suppressed[lineno] = eff

    def _comment_lines(self) -> dict[int, str]:
        """line -> comment token text, via tokenize. Unparseable files
        yield nothing (the DFS000 parse-error finding covers them)."""
        import io
        import tokenize

        out: dict[int, str] = {}
        if "dfslint:" not in self.text:
            return out   # no marker anywhere: skip the tokenize pass
        try:
            for tok in tokenize.generate_tokens(
                    io.StringIO(self.text).readline):
                if tok.type == tokenize.COMMENT:
                    out[tok.start[0]] = tok.string
        except (tokenize.TokenError, IndentationError, SyntaxError,
                ValueError):
            pass
        return out

    def is_suppressed(self, rule: str, line: int) -> bool:
        got = self.suppressed.get(line)
        hit = bool(got) and ("*" in got or rule in got)
        if hit:
            # audit bookkeeping (DFS000 stale-suppression): this
            # comment suppressed a live finding this run
            self.suppressions_used.add(
                (line, rule if rule in (got or ()) else "*"))
        return hit

    def nodes(self, *types: type) -> list[ast.AST]:
        """All AST nodes of the given types, from the shared parse-once
        index (lexical order within a type)."""
        if len(types) == 1:
            return self._by_type.get(types[0], [])
        out: list[ast.AST] = []
        for t in types:
            out.extend(self._by_type.get(t, []))
        return out

    # ---- AST helpers shared by the rules ----

    def qualname(self, node: ast.AST) -> str:
        """Dotted enclosing-scope name for ``node`` (classes and
        functions), or '<module>' at top level — the rot-resistant part
        of a finding's baseline key."""
        names: list[str] = []
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                names.append(cur.name)
            cur = self.parents.get(cur)
        return ".".join(reversed(names)) or "<module>"


def dotted(node: ast.AST) -> str | None:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def scope_nodes(fn: ast.AST) -> Iterator[ast.AST]:
    """Nodes lexically inside ``fn``'s body, NOT descending into nested
    function/lambda scopes — 'lexically inside an async def' must stop
    at a nested ``def`` (which may legitimately run in a worker thread,
    e.g. a closure an async handler hands to to_thread)."""
    todo = list(getattr(fn, "body", []))
    while todo:
        n = todo.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        todo.extend(ast.iter_child_nodes(n))


class Project:
    """The walked, parsed source set every pass runs over."""

    def __init__(self, files: list[SourceFile]) -> None:
        self.files = files
        self._model = None   # phase-1 facts, built once (model.build_model)

    def find(self, rel_suffix: str) -> SourceFile | None:
        """The unique source whose repo-relative path ends with
        ``rel_suffix`` (cross-file passes locate their anchor modules
        this way so fixture trees work the same as the real one)."""
        hits = [f for f in self.files
                if f.rel == rel_suffix or f.rel.endswith("/" + rel_suffix)]
        return hits[0] if len(hits) == 1 else None


def collect_sources(roots: Iterable[str | Path],
                    repo_root: str | Path) -> list[SourceFile]:
    """Resolve ``roots`` (files or directories, relative to
    ``repo_root``) to parsed ``SourceFile``s. Only ``*.py`` files are
    read; ``SKIP_DIRS`` and hidden directories are pruned, so checked-in
    binaries, ``native/*.so`` build outputs and ``__pycache__`` trees
    never reach the parser. Raises FileNotFoundError for a root that
    does not exist (CLI usage error, exit 2)."""
    repo_root = Path(repo_root).resolve()
    out: list[SourceFile] = []
    seen: set[Path] = set()

    def add(p: Path) -> None:
        p = p.resolve()
        if p in seen or p.suffix != ".py":
            return
        seen.add(p)
        try:
            rel = p.relative_to(repo_root).as_posix()
        except ValueError:
            rel = p.as_posix()
        out.append(SourceFile(p, rel))

    for root in roots:
        p = Path(root)
        if not p.is_absolute():
            p = repo_root / p
        if p.is_file():
            add(p)
        elif p.is_dir():
            for sub in sorted(p.rglob("*.py")):
                if any(part in SKIP_DIRS or part.startswith(".")
                       for part in sub.relative_to(p).parts[:-1]):
                    continue
                add(sub)
        else:
            matches = sorted(p.parent.glob(p.name)) if p.parent.is_dir() \
                else []
            if not matches:
                raise FileNotFoundError(str(root))
            for m in matches:
                if m.is_file():
                    add(m)
    out.sort(key=lambda s: s.rel)
    return out


DEFAULT_BASELINE = Path(__file__).parent / "baseline.json"


def load_baseline(path: str | Path | None = None) -> set[str]:
    """Accepted-finding keys. Shape: {"accepted": ["RULE:path:ctx", ...]}
    — a malformed file is a hard error (a silently-empty baseline would
    un-gate every accepted finding at once)."""
    p = Path(path) if path is not None else DEFAULT_BASELINE
    if not p.is_file():
        return set()
    data = json.loads(p.read_text())   # JSONDecodeError is a ValueError
    entries = data.get("accepted") if isinstance(data, dict) else data
    if not isinstance(entries, list) \
            or not all(isinstance(e, str) for e in entries):
        raise ValueError(f"malformed baseline {p}: want a JSON list of "
                         "finding keys under 'accepted'")
    return set(entries)


def save_baseline(findings_or_keys: Iterable[Finding | str],
                  path: str | Path | None = None) -> Path:
    p = Path(path) if path is not None else DEFAULT_BASELINE
    keys = sorted({f.key if isinstance(f, Finding) else str(f)
                   for f in findings_or_keys})
    p.write_text(json.dumps({"accepted": keys}, indent=2) + "\n")
    return p
