#!/usr/bin/env python3
"""The plain reference of the ``images`` stream, and its dedup count.

The configuration ``images-3n-rf2`` brings this copy, as
``smallfiles-3n-rf2`` brought ``reference_files.py``: the objects of an
``images`` stream rebuilt from ``(corpus_seed, k)`` the straight way —
the list of draws in ``generators/images.py``'s docstring followed one
draw after the other, with numpy and the standard library, sharing no
code with that module nor with ``data.py``. It holds whole images as
``bytearray``s: for tests at small sizes and a by-hand count, never for
the timed path.

``stored_ratio_of`` is the count ``stored_ratio`` has to equal:
``reference_versions.stored_ratio_oracle`` over these objects, i.e.
night 1 of every image, each chunked WHOLE AND ALONE by the program's
CPU engine (the C++ walk + hashlib, which has no windows, no carry
between windows and no device), the bytes of the chunks the base image
does not have, each once, times the copies, over the slice's bytes. A
carry that drops or repeats a segment, a window cut at the wrong
``final``, a reply sliced off a trimmed tee moves that count (or fails
the read-back). The harness's warm-up object is fresh bytes from
``--seed`` and shares no chunk with the corpus.

    python3 benchmarks/reference_images.py --traffic ingest-nightly \\
        --config images-3n-rf2 [--rehearsal]

prints that count for a cell's traffic file (a CPU count, minutes at
the full size: 0.08059075971444447 at the cell's 512 MiB, and
0.04080758740504583 at the source's 1 GiB with the same extents; PR 43).
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
    """``object(k)``: object ``k`` of the stream a traffic file
    describes, as bytes; 0 is the base image."""

    def __init__(self, traffic: dict) -> None:
        self.seed = int(traffic["corpus_seed"])
        self.images = int(traffic["images"])
        self.size = int(traffic["object_bytes"])
        self.period = int(traffic["period_bytes"])
        self.extents = int(traffic["extents_per_night"])
        self.lo = int(traffic["extent_min_bytes"])
        self.hi = int(traffic["extent_max_bytes"])

    def _base(self) -> bytearray:
        half = self.period // 2
        periods = -(-self.size // self.period)
        block = np.random.default_rng([self.seed, 0, 0]).bytes(half)
        new = np.random.default_rng([self.seed, 1, 0]).bytes(periods * half)
        image = bytearray()
        for p in range(periods):
            image += new[p * half:(p + 1) * half] + block
        return image[:self.size]

    def object(self, k: int) -> bytes:
        image = self._base()
        if k > 0:
            nights, which = divmod(k - 1, self.images)
            for n in range(1, nights + 2):
                g = np.random.default_rng([self.seed, 20, which, n])
                for _ in range(self.extents):
                    length = int(math.exp(g.uniform(math.log(self.lo),
                                                    math.log(self.hi))))
                    at = int(g.integers(0, self.size - length + 1))
                    image[at:at + length] = g.bytes(length)
        return bytes(image)


def stored_ratio_of(traffic: dict, copies: int, make=None) -> float:
    """The count documented at the top. ``make(k)``: another source of
    the same objects (a test hands in the generator's)."""
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))
    from reference_versions import stored_ratio_oracle

    return stored_ratio_oracle(
        make or Reference(traffic).object, int(traffic["lead_objects"]),
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
