"""Byte-granular Gear content-defined chunking on the host — the ``cdc``
kind, the analogue of the source's CPU CDC plugin. It imports no JAX, which
is why most of the suite boots its clusters on it; the device runs the
anchored chunker (fragmenter/cdc_anchored.py), not this one.

Two implementations of the same algorithm:

- :func:`cdc_cuts_ref` — a deliberately naive pure-Python sequential rolling
  hash + greedy cut walk. This is the *specification*; tests assert the
  NumPy and C++ engines match it bit-for-bit.
- :class:`CpuCdcFragmenter` — the production path: the C++ scan
  (``native_gear_cuts``) or the vectorized NumPy windowed Gear bitmap + the
  host-side selection (ops/boundary.py), with hashlib SHA-256.
"""

from __future__ import annotations

import numpy as np

from dfs_tpu.config import GEAR_HALO as HALO
from dfs_tpu.config import GEAR_WINDOW as WINDOW
from dfs_tpu.config import CDCParams
from dfs_tpu.fragmenter.base import Fragmenter
from dfs_tpu.meta.manifest import ChunkRef
from dfs_tpu.ops.boundary import cuts_to_spans, select_cuts
from dfs_tpu.utils.hashing import gear_table, sha256_many_hex

_U32 = np.uint32(0xFFFFFFFF)


def gear_hashes_seq(data: bytes, table: np.ndarray) -> np.ndarray:
    """Pure sequential rolling hash: h_i = (h_{i-1} << 1) + G[b_i] mod 2**32.
    Test oracle only — O(n) Python loop."""
    h = 0
    out = np.empty(len(data), dtype=np.uint32)
    for i, b in enumerate(data):
        h = ((h << 1) + int(table[b])) & 0xFFFFFFFF
        out[i] = h
    return out


def cdc_cuts_ref(data: bytes, params: CDCParams,
                 table: np.ndarray | None = None) -> list[int]:
    """Specification chunker: sequential scan, cut after the first candidate
    at length >= min_size, force-cut at max_size. Returns exclusive cuts."""
    table = gear_table(params.seed) if table is None else table
    mask = params.mask
    h = 0
    cuts: list[int] = []
    start = 0
    for i, b in enumerate(data):
        h = ((h << 1) + int(table[b])) & 0xFFFFFFFF
        length = i - start + 1
        if length >= params.min_size and (h & mask) == 0:
            cuts.append(i + 1)
            start = i + 1
        elif length >= params.max_size:
            cuts.append(i + 1)
            start = i + 1
    if start < len(data):
        cuts.append(len(data))
    return cuts


def gear_bitmap_carry(data: np.ndarray, table: np.ndarray, mask: int,
                      prev_g: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Vectorized windowed Gear bitmap: because each shift-left discards one
    high bit, h_i depends on exactly the last 32 bytes, h_i = sum_{k<32}
    G[b_{i-k}] << k (mod 2**32) — 32 shifted adds, bit-for-bit the
    sequential rolling hash. data: [N] uint8; prev_g: [31] uint32 halo (zeros at stream start).
    Returns (bitmap, new halo) — the single source of truth for the CPU
    kernel; both the one-shot and streaming paths call this."""
    n = data.shape[0]
    g = table[data.astype(np.int32)]
    gp = np.concatenate([prev_g, g])
    h = np.zeros(n, dtype=np.uint32)
    for k in range(WINDOW):
        h += gp[HALO - k: HALO - k + n] << np.uint32(k)
    return (h & np.uint32(mask)) == 0, gp[-HALO:]


def gear_bitmap_numpy(data: np.ndarray, table: np.ndarray, mask: int,
                      prev_g: np.ndarray | None = None) -> np.ndarray:
    """Bitmap-only convenience wrapper over :func:`gear_bitmap_carry`."""
    if prev_g is None:
        prev_g = np.zeros(HALO, dtype=np.uint32)
    return gear_bitmap_carry(data, table, mask, prev_g)[0]


class CpuCdcFragmenter(Fragmenter):
    name = "cdc"

    def __init__(self, params: CDCParams | None = None) -> None:
        self.params = params or CDCParams()
        self.table = gear_table(self.params.seed)

    def describe(self) -> dict:
        p = self.params
        return {"kind": "cdc", "min_size": p.min_size,
                "avg_size": p.avg_size, "max_size": p.max_size,
                "seed": p.seed}

    def bitmap_tile(self, arr: np.ndarray,
                    prev_g: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Streaming tile kernel: (bitmap, new 31-entry Gear halo)."""
        return gear_bitmap_carry(arr, self.table, self.params.mask, prev_g)

    def manifest_stream(self, blocks, name: str, store=None):
        from dfs_tpu.fragmenter.stream import manifest_from_stream

        return manifest_from_stream(blocks, self.params, self.bitmap_tile,
                                    name, self.name, store)

    def cuts(self, data: bytes | np.ndarray) -> np.ndarray:
        arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
            data, (bytes, bytearray, memoryview)) else np.ascontiguousarray(
            data, dtype=np.uint8)   # C++ reads raw base-pointer bytes
        from dfs_tpu.native import native_gear_cuts

        # C++ sequential engine when the toolchain is available (bit-
        # identical to the NumPy path below — tests/test_native.py); the
        # NumPy bitmap+select pair measured minutes per GiB
        native = native_gear_cuts(arr, self.table, self.params.mask,
                                  self.params.min_size,
                                  self.params.max_size)
        if native is not None:
            return native
        bitmap = gear_bitmap_numpy(arr, self.table, self.params.mask)
        return select_cuts(bitmap, arr.shape[0],
                           self.params.min_size, self.params.max_size)

    def chunk(self, data: bytes) -> list[ChunkRef]:
        spans = cuts_to_spans(self.cuts(data))
        pieces = [data[o:o + ln] for o, ln in spans]
        digests = sha256_many_hex(pieces)
        return [ChunkRef(index=i, offset=o, length=ln, digest=dg)
                for i, ((o, ln), dg) in enumerate(zip(spans, digests))]
