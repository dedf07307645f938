"""Owed since PR 32: ``benchmarks/tests/test_redundancy.py`` in tier-1.

That file holds the plain P+Q reference (``reference_ec.py``) to
hand-worked vectors and ``check.py``'s two redundancy schemes to a
made-up tree of chunk stores, one guarantee broken at a time: no
cluster, no chip, 1.4 s. The comparison that decides ``correct`` for
``archive.ingest-ec`` rests on it, so tier-1 collects its cases as they
stand, from the one file — nothing is copied here. ``benchmarks/`` keeps
running them by hand too (``python -m pytest benchmarks/tests``).
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent / "benchmarks"
sys.path.insert(0, str(BENCH))           # its imports: check, reference_ec

_spec = importlib.util.spec_from_file_location(
    "bench_test_redundancy", BENCH / "tests" / "test_redundancy.py")
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)
globals().update({name: obj for name, obj in vars(_mod).items()
                  if name.startswith("test_")})


def test_every_case_of_the_file_is_collected_here():
    theirs = {n for n in vars(_mod) if n.startswith("test_")}
    assert len(theirs) >= 9 and theirs <= set(globals())
