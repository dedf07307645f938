"""Run by hand and in the CPU rehearsal — ``python -m pytest
benchmarks/tests -q`` — never by tier-1 (which collects ``tests/``)."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
