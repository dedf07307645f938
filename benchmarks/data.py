"""Bytes from a seed (NumPy only). The same (seed, tag, k) gives the
same bytes in every process, so the harness regenerates an object for a
check instead of keeping it, and the reference never needs the system's
copy. Which seed is the generator's business: ``--seed`` itself, or a
corpus seed of the traffic file where ``--seed`` only orders the work.

``segment`` is ``chip_smoke.py``'s ``make_corpus`` (PR 21) cut to one
object; the smoke keeps its own.
"""

from __future__ import annotations

import hashlib

import numpy as np

MIB = 1024 * 1024


def rng(seed: int, *tags: int) -> np.random.Generator:
    return np.random.default_rng([abs(int(seed)), *tags])


def fresh(seed: int, tag: int, k: int, size: int) -> np.ndarray:
    return np.frombuffer(rng(seed, tag, k).bytes(size), dtype=np.uint8)


def segment(seed: int, k: int, size: int, period: int = 8 * MIB
            ) -> np.ndarray:
    """Segment ``k`` of the synthetic tarball (BASELINE.json configs[2]
    as ``bench.py`` and the smoke build it): one block of ``period/2``
    bytes, the same for every segment of the seed, tiled — with fresh
    bytes spliced over the first half of every period. Half of each
    segment repeats, so dedup has something to find; the other half
    derives from ``(seed, k)``, so a faster system never runs dry."""
    half = period // 2
    block = fresh(seed, 0, 0, half)
    arr = np.tile(block, -(-size // half))[:size].copy()
    new = fresh(seed, 1, k, -(-size // period) * half)
    for i, off in enumerate(range(0, size, period)):
        end = min(off + half, size)
        arr[off:end] = new[i * half:i * half + end - off]
    return arr


def sha256_hex(buf) -> str:
    """The oracle for an object's id: hashlib over the bytes this
    process made, independent of every digest path under test."""
    return hashlib.sha256(memoryview(buf)).hexdigest()
