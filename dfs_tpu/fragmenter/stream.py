"""Streaming CDC: chunk unbounded byte streams with bounded memory.

The reference reads the whole file into one array and splits positionally
('sequence length' = file size, bounded by heap — SURVEY.md §5.7). Here the
stream is processed tile by tile: the Gear bitmap for each tile needs only the
31-byte halo carried from the previous tile, and greedy cut selection
finalizes a chunk as soon as either (a) a candidate at length >= min_size
appears, or (b) max_size bytes are buffered — so resident state is at most
max_size + one tile regardless of stream length.

This is the streaming walk of the ``cdc`` kind (fragmenter/cdc_cpu.py); the
anchored kinds have their own region walk (fragmenter/cdc_anchored.py).
"""

from __future__ import annotations

from typing import Callable, Iterable, Iterator

import numpy as np

from dfs_tpu.config import CDCParams, GEAR_HALO
from dfs_tpu.meta.manifest import ChunkRef, Manifest
from dfs_tpu.utils.hashing import sha256_many_hex, sha256_new

# bitmap_fn(tile_u8, prev_g_u32[31]) -> (bitmap_bool[N], new_prev_g_u32[31])
BitmapFn = Callable[[np.ndarray, np.ndarray], tuple[np.ndarray, np.ndarray]]

_HASH_BATCH = 256


class StreamChunker:
    """Incremental cut selection over a candidate-bitmap stream."""

    def __init__(self, params: CDCParams, bitmap_fn: BitmapFn) -> None:
        self.p = params
        self.bitmap_fn = bitmap_fn
        self.prev_g = np.zeros(GEAR_HALO, dtype=np.uint32)
        self.buf = bytearray()      # bytes of [start, processed)
        self.start = 0              # absolute offset of current chunk start
        self.processed = 0          # absolute bytes consumed
        self.cands: list[int] = []  # absolute candidate positions > start
        self._ci = 0                # consumed prefix of self.cands

    def feed(self, data: bytes | np.ndarray) -> Iterator[tuple[int, bytes]]:
        """Consume a block; yield finalized (offset, payload) spans."""
        arr = np.frombuffer(data, dtype=np.uint8) if not isinstance(
            data, np.ndarray) else data
        if arr.shape[0] == 0:
            return
        bitmap, self.prev_g = self.bitmap_fn(arr, self.prev_g)
        base = self.processed
        self.cands.extend((base + np.flatnonzero(bitmap)).tolist())
        self.buf.extend(arr.tobytes())
        self.processed += arr.shape[0]
        yield from self._drain()

    def finish(self) -> Iterator[tuple[int, bytes]]:
        yield from self._drain()
        if self.start < self.processed:
            yield self.start, bytes(self.buf)
            self.start = self.processed
            self.buf.clear()

    def _drain(self) -> Iterator[tuple[int, bytes]]:
        p = self.p
        while True:
            lo = self.start + p.min_size - 1
            hi = self.start + p.max_size - 1
            # skip candidates before the admissible window
            while self._ci < len(self.cands) and self.cands[self._ci] < lo:
                self._ci += 1
            cut = None
            if self._ci < len(self.cands) and self.cands[self._ci] <= hi:
                cut = self.cands[self._ci]          # first candidate wins
            elif hi <= self.processed - 1:
                cut = hi                            # forced cut at max_size
            if cut is None:
                break
            length = cut + 1 - self.start
            yield self.start, bytes(self.buf[:length])
            del self.buf[:length]
            self.start = cut + 1
            if self._ci > 4096:                     # prune consumed prefix
                self.cands = self.cands[self._ci:]
                self._ci = 0


def manifest_from_stream(blocks: Iterable[bytes], params: CDCParams,
                         bitmap_fn: BitmapFn, name: str,
                         fragmenter_name: str,
                         store: Callable[[str, bytes], None] | None = None,
                         ) -> Manifest:
    """One-pass streaming upload core: file_id (whole-stream sha256), chunk
    spans, per-chunk digests — optionally persisting each chunk via ``store``
    — without ever materializing the whole stream. Finalized chunks are
    digested ``_HASH_BATCH`` at a time."""
    chunker = StreamChunker(params, bitmap_fn)
    whole = sha256_new()
    refs: list[ChunkRef] = []
    pending: list[tuple[int, bytes]] = []
    size = 0

    def flush() -> None:
        digests = sha256_many_hex([b for _, b in pending])
        for (off, payload), dg in zip(pending, digests):
            refs.append(ChunkRef(index=len(refs), offset=off,
                                 length=len(payload), digest=dg))
            if store is not None:
                store(dg, payload)
        pending.clear()

    def consume(spans: Iterator[tuple[int, bytes]]) -> None:
        for off, payload in spans:
            pending.append((off, payload))
            if len(pending) >= _HASH_BATCH:
                flush()

    for block in blocks:
        whole.update(block)
        size += len(block)
        consume(chunker.feed(block))
    consume(chunker.finish())
    flush()

    return Manifest(file_id=whole.hexdigest(), name=name, size=size,
                    fragmenter=fragmenter_name, chunks=tuple(refs))
