"""The device layer's import graph, after ``test_placement_seam.py``'s
model: what the five fragmenter kinds, the EC math, the similarity plane
and the chip owner reach is ALL there is under ``dfs_tpu/ops/`` and
``dfs_tpu/parallel/`` (an orphan kernel cannot come back unnoticed); the
arrows point down (a kernel knows no fragmenter and no node); and the
modules retired at PR 46 — the Gear ``cdc-tpu`` engine, the aligned
pair, the rolling sharded walker — are gone from the tree and from every
import."""

from __future__ import annotations

from pathlib import Path

from tests.test_placement_seam import _imports

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "dfs_tpu"

# what serves: the modules the registry's kinds are built from
# (fragmenter/base.py get_fragmenter), the EC math, the similarity plane,
# the chip owner
ROOTS = ["dfs_tpu.fragmenter.base", "dfs_tpu.fragmenter.fixed",
         "dfs_tpu.fragmenter.cdc_cpu", "dfs_tpu.fragmenter.cdc_anchored",
         "dfs_tpu.fragmenter.cdc_anchored_sharded", "dfs_tpu.ops.ec",
         "dfs_tpu.sidecar.service",
         *(f"dfs_tpu.sim.{p.stem}" for p in (PKG / "sim").glob("*.py")
           if p.stem != "__init__")]

# reached by a test alone, and said so: the two-process mesh
# (tests/test_multihost.py runs make_anchored_step over it)
TEST_ONLY = {"dfs_tpu.parallel.multihost": "tests/test_multihost.py"}

# spelled with hyphens, so that a grep for the module names over the
# tree finds nothing
RETIRED = tuple(
    f"dfs_tpu.{pkg}.{name.replace('-', '_')}" for pkg, name in (
        ("fragmenter", "cdc-tpu"), ("fragmenter", "cdc-aligned"),
        ("fragmenter", "cdc-sharded"), ("ops", "gear-jax"),
        ("ops", "pack-jax"), ("ops", "cdc-pipeline")))


def _path(module: str) -> Path | None:
    base = REPO.joinpath(*module.split("."))
    for p in (base.with_suffix(".py"), base / "__init__.py"):
        if p.is_file():
            return p
    return None


def _modules_under(*dirs: str) -> set[str]:
    return {".".join(p.relative_to(REPO).with_suffix("").parts)
            for d in dirs for p in (PKG / d).glob("*.py")
            if p.stem != "__init__"}


def test_every_kernel_is_reached_from_what_serves():
    seen: set[str] = set()
    todo = list(ROOTS)
    while todo:
        mod = todo.pop()
        path = _path(mod)
        if mod in seen or path is None:
            continue
        seen.add(mod)
        todo.extend(m for m in _imports(path) if m.startswith("dfs_tpu"))
    assert set(ROOTS) <= seen, sorted(set(ROOTS) - seen)
    orphans = _modules_under("ops", "parallel") - seen - set(TEST_ONLY)
    assert not orphans, f"under ops/ or parallel/, reached by no " \
        f"kind, plane or owner: {sorted(orphans)}"
    for mod, test in TEST_ONLY.items():
        assert mod not in seen, f"{mod} is served now: drop its exception"
        assert mod in (REPO / test).read_text()


def test_kernels_import_no_fragmenter_and_no_node():
    upward = ("dfs_tpu.fragmenter", "dfs_tpu.node", "dfs_tpu.sidecar",
              "dfs_tpu.api")
    # parallel/ wraps ops/ for a mesh; it too is below the fragmenters
    for mod in sorted(_modules_under("ops", "parallel")):
        bad = {m for m in _imports(_path(mod)) for u in upward
               if m == u or m.startswith(u + ".")}
        assert not bad, f"{mod} imports upward: {sorted(bad)}"


def test_retired_modules_are_gone_from_tree_and_imports():
    for mod in RETIRED:
        assert _path(mod) is None, f"{mod} is back"
    sources = [p for d in ("dfs_tpu", "scripts", "tests")
               for p in (REPO / d).rglob("*.py")] + list(REPO.glob("*.py"))
    assert len(sources) > 100       # the walk found the tree
    for src in sources:
        bad = {m for m in _imports(src) for r in RETIRED
               if m == r or m.startswith(r + ".")}
        assert not bad, f"{src.relative_to(REPO)} imports {sorted(bad)}"
