#!/usr/bin/env python3
"""The plain reference of the ``files`` stream, and its dedup count.

The configuration ``smallfiles-3n-rf2`` brings this copy, as
``snapshots-3n-rf2-index`` brought ``reference_versions.py``: the files
of a ``files`` stream rebuilt from ``(corpus_seed, k)`` the straight
way — the list of draws in ``generators/files.py``'s docstring followed
one draw after the other, with numpy and the standard library, sharing
no code with that module nor with ``data.py``.

``stored_ratio_of`` is the count ``stored_ratio`` has to equal:
``reference_versions.stored_ratio_oracle`` over these files, i.e. the
``ratio_objects`` uploads that follow the lead, chunked one by one,
EACH ALONE, by the program's CPU engine (the C++ walk + hashlib: code
the device chain and the owner's packer share nothing with), the bytes
of the chunks that neither the preload nor the lead has, each once,
times the copies, over the slice's bytes. A stream's cuts leaking into
its neighbour's in a packed region, a chunk astride two files or a
table handed to the wrong caller moves that count (or fails the
read-back). The harness's warm-up objects are fresh bytes from
``--seed`` and share no chunk with the corpus.

    python3 benchmarks/reference_files.py --traffic ingest-batch \\
        --config smallfiles-3n-rf2 [--rehearsal]

prints that count for a cell's traffic file (a CPU count, seconds).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


class Reference:
    """``object(k)``: what place ``k`` of the stream a traffic file
    describes uploads, as bytes; ``k`` below zero: preload file
    ``-1 - k``."""

    def __init__(self, traffic: dict) -> None:
        self.seed = int(traffic["corpus_seed"])
        self.medians = list(traffic["kinds"].values())
        self.sigma = float(traffic["size_sigma"])
        self.lo = int(traffic["size_min_bytes"])
        self.hi = int(traffic["size_max_bytes"])
        self.preload = int(traffic["preload_objects"])
        share = float(traffic["repeat_share"])
        self.period = round(1 / share) if share > 0 else 0

    def _file(self, tag: int, n: int) -> bytes:
        g = np.random.default_rng([self.seed, tag, n])
        median = self.medians[int(g.integers(0, len(self.medians)))]
        size = int(g.lognormal(math.log(median), self.sigma))
        return g.bytes(min(self.hi, max(self.lo, size)))

    def object(self, k: int) -> bytes:
        if k < 0:
            return self._file(15, -1 - k)
        if self.period and k % self.period == 0:
            j = np.random.default_rng([self.seed, 16, k]).integers(
                0, self.preload)
            return self._file(15, int(j))
        return self._file(14, k)


def stored_ratio_of(traffic: dict, copies: int, make=None) -> float:
    """The count documented at the top. ``make(k)``: another source of
    the same objects (a test hands in the generator's)."""
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))
    from reference_versions import stored_ratio_oracle

    make = make or Reference(traffic).object
    preload = int(traffic["preload_objects"])
    # the oracle's lead is everything in the stores before the slice:
    # the preload first, then the stream's own lead
    return stored_ratio_oracle(
        lambda i: make(-1 - i) if i < preload else make(i - preload),
        preload + int(traffic["lead_objects"]),
        int(traffic["ratio_objects"]), copies)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    traffic = json.loads(
        (HERE / "traffic" / f"{args.traffic}.json").read_text())
    if args.rehearsal:
        traffic = {**traffic, **traffic["rehearsal"]}
    config = json.loads(
        (HERE / "configs" / f"{args.config}.json").read_text())
    print(json.dumps({"stored_ratio_oracle": stored_ratio_of(
        traffic, int(config["deployment"]["redundancy"]["copies"]))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
