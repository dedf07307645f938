"""Block-grid Gear candidate math of the anchored chunker.

The anchored chunker (``ops/cdc_anchored.py``) cuts a stream into
content-defined segments and lays each into a lane of its own; WITHIN a
lane the chunk cuts are decided here. A lane is a *strip*: a run of
64-byte blocks whose grid starts at the lane's first byte. The math is
shaped by the hardware (measured on v5e):

- **cuts are quantized to 64-byte blocks** (the SHA-256 block size). A cut
  candidate after block ``t`` is decided by a Gear-style windowed hash over
  the *last 32 bytes of that block only*::

      h_t = sum_{k=0}^{31} G[byte[64*t + 63 - k]] << k   (mod 2**32)
      candidate(t)  iff  h_t & mask == 0

  The 32-byte window never crosses the block start, so the decision is local
  to each block: no rolling state, no halo, no sequential scan — one
  elementwise pass. (Identical to the rolling Gear hash evaluated at the
  block's last byte, restricted to aligned positions.)

- **G is arithmetic, not a lookup table**: ``G[b] = fmix32(seed ^ b*PRIME)``
  (murmur-finalizer constants). A 256-entry ``jnp.take`` over 10^8 indices
  measured 1.4 s per 128 MiB on v5e; computing G in registers costs ~10
  elementwise uint32 ops and rides the VPU at memory speed. The NumPy
  oracle precomputes the same 256 values into a table — bit-identical by
  construction.

- **strips are independent**: chunking restarts at each strip's start, so
  strips are the lane dimension of every kernel and the unit the sharded
  steps (``parallel/sharded_cdc.py``) spread over a mesh, with nothing
  exchanged between devices.

- **greedy selection is a lane-parallel scan**: the sequential min/max walk
  runs per-strip in lockstep across all strips (one ``lax.scan`` over blocks
  carrying a per-lane "blocks since last cut" counter) — it never leaves the
  device, so cut flags feed the SHA kernel with no host round-trip.

Selection semantics per strip (mirrored exactly by the NumPy oracle below):
walking blocks ``t``, with ``since`` = blocks accumulated so far including
``t``: cut after ``t`` iff ``(candidate(t) and since >= min_blocks)`` or
``since == max_blocks`` or ``t`` is the strip's last block.

On the TPU the served chain computes candidates, selection and SHA-256 in
ONE Pallas kernel (``ops/sha256_strip.py`` ``strip_chunk_states``, which
imports the three hash constants below); ``gear_candidates_device`` and
``select_cuts_device`` are the same math in plain XLA — what the chain runs
where Pallas does not (the CPU rehearsal, a lane count that is no multiple
of 128), what the lane-sharded step runs, and what ``tests/test_sha256.py``
holds the fused kernel to.

Chunk digests are standard SHA-256 (== hashlib). An engine's own file id is
``sha256(digest_0 || digest_1 || ...)`` over the raw 32-byte chunk digests —
content-derived like the reference's whole-file id (StorageNode.java:127)
but computable from the chunk table alone.

The module keeps the name it had when it was one of three chunker
families ("aligned CDC v2", whose whole-file fragmenters went at PR 46):
thirteen test files import it.
"""

from __future__ import annotations

import dataclasses
import hashlib

import numpy as np

_PRIME = np.uint32(0x9E3779B1)  # 2^32 / golden ratio, odd
_M1 = np.uint32(0x7FEB352D)     # lowbias32 (Ettinger) finalizer constants
_M2 = np.uint32(0x846CA68B)

BLOCK = 64  # bytes per block: SHA-256 block size == cut quantum


@dataclasses.dataclass(frozen=True)
class AlignedCdcParams:
    """min/avg/max are in *blocks* (64 B units).

    Defaults: min 2 KiB, avg 8 KiB, max 64 KiB, strip 128 KiB — the
    BASELINE.json "8 KiB avg chunk" configuration, quantized. 128 KiB
    strips put 512 lanes on a 64 MiB segment (vs 128 at 512 KiB), which
    measured 4x faster SHA on v5e ((8,128) vregs fill at r = S/128 = 4)
    at the cost of a forced cut every ~16th chunk.
    """
    min_blocks: int = 32
    avg_blocks: int = 128
    max_blocks: int = 1024
    strip_blocks: int = 2048   # 128 KiB per strip
    seed: int = 0x9D5D0CB2

    def __post_init__(self):
        if self.avg_blocks & (self.avg_blocks - 1):
            raise ValueError("avg_blocks must be a power of two (mask)")
        if not (1 <= self.min_blocks <= self.avg_blocks <= self.max_blocks
                <= self.strip_blocks):
            raise ValueError("need 1 <= min <= avg <= max <= strip blocks")

    @property
    def mask(self) -> int:
        return self.avg_blocks - 1

    @property
    def strip_len(self) -> int:
        return self.strip_blocks * BLOCK


# ---------------------------------------------------------------------------
# G function — shared definition (NumPy); jnp version in gear_block_hashes_*
# ---------------------------------------------------------------------------

def fmix32_np(x: np.ndarray) -> np.ndarray:
    """lowbias32 integer finalizer, vectorized uint32 (NumPy)."""
    x = np.asarray(x, dtype=np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = (x * _M1).astype(np.uint32)
    x = x ^ (x >> np.uint32(15))
    x = (x * _M2).astype(np.uint32)
    return x ^ (x >> np.uint32(16))


def g_table(seed: int) -> np.ndarray:
    """The 256 G values as a table — the CPU oracle's fast path; identical to
    the arithmetic form used on device."""
    b = np.arange(256, dtype=np.uint32)
    return fmix32_np(np.uint32(seed) ^ (b * _PRIME))


# ---------------------------------------------------------------------------
# NumPy oracle (exact semantics; the CPU engine's per-segment core)
# ---------------------------------------------------------------------------

def block_hashes_np(data: np.ndarray, params: AlignedCdcParams) -> np.ndarray:
    """h_t for every *complete* 64-byte block of ``data`` ([N] uint8).
    The trailing partial block (if any) has no candidate decision."""
    nb = data.shape[0] // BLOCK
    if nb == 0:
        return np.zeros((0,), dtype=np.uint32)
    g = g_table(params.seed)[data[:nb * BLOCK].reshape(nb, BLOCK)]
    h = np.zeros((nb,), dtype=np.uint32)
    for k in range(32):
        h += g[:, 63 - k] << np.uint32(k)
    return h


def candidates_np(data: np.ndarray, params: AlignedCdcParams) -> np.ndarray:
    """Candidate bitmap over complete blocks."""
    return (block_hashes_np(data, params) & np.uint32(params.mask)) == 0


def select_cuts_blocks(cand_pos: np.ndarray, n_blocks: int,
                       params: AlignedCdcParams) -> np.ndarray:
    """Greedy cut selection for ONE strip, in block units.

    cand_pos: sorted candidate block indices (within the strip);
    n_blocks: total blocks in this strip (including a trailing partial
    block, which can never be a candidate). Returns exclusive cut block
    offsets; last element == n_blocks.
    """
    cuts: list[int] = []
    start = 0
    while start < n_blocks:
        lo = start + params.min_blocks - 1   # earliest admissible cut block
        hi = start + params.max_blocks - 1   # forced cut block
        j = int(np.searchsorted(cand_pos, lo, side="left"))
        if j < cand_pos.shape[0] and cand_pos[j] <= hi:
            cut = int(cand_pos[j])
        else:
            cut = min(hi, n_blocks - 1)
        cuts.append(cut + 1)
        start = cut + 1
    return np.asarray(cuts, dtype=np.int64)


def file_id_from_digests(digests: list[str]) -> str:
    """sha256 over concatenated raw chunk digests (empty file: sha256(b''))."""
    h = hashlib.sha256()
    for d in digests:
        h.update(bytes.fromhex(d))
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Device (JAX) kernels — strip-transposed layout
# ---------------------------------------------------------------------------
# Resident layout: words_t [strip_blocks * 16, S] uint32, where
#   words_t[t*16 + w, s] = big-endian word w of block t of strip s.
# S = number of strips (lanes).

def gear_candidates_device(words_t, params: AlignedCdcParams):
    """Candidate bitmap [strip_blocks, S] bool from the resident words.

    The 32-byte window of block t = words 8..15 of block t — extracted from
    rows (sublane slices, cheap) with byte unpacking in registers.
    """
    import jax.numpy as jnp

    bps = params.strip_blocks
    s = words_t.shape[1]
    w = words_t.reshape(bps, 16, s)[:, 8:16, :]     # [bps, 8, S]
    seed = jnp.uint32(params.seed)

    def fmix(x):
        x = x ^ (x >> jnp.uint32(16))
        x = x * _M1
        x = x ^ (x >> jnp.uint32(15))
        x = x * _M2
        return x ^ (x >> jnp.uint32(16))

    h = jnp.zeros((bps, s), jnp.uint32)
    # byte j of the window (j = 0..31, stream order) sits in word j//4 at
    # byte j%4 (big-endian); its shift weight is k = 31 - j.
    for j in range(32):
        byte = (w[:, j // 4, :] >> jnp.uint32(8 * (3 - j % 4))) & jnp.uint32(0xFF)
        g = fmix(seed ^ (byte * _PRIME))
        h = h + (g << jnp.uint32(31 - j))
    return (h & jnp.uint32(params.mask)) == 0


def select_cuts_device(cand, real_blocks, params: AlignedCdcParams,
                       unroll: int = 8):
    """Lane-parallel greedy selection.

    cand: [bps, S] bool; real_blocks: [S] int32 — complete-or-partial blocks
    actually present in each strip (0 for padding strips). Returns
    (cutflag [bps, S] bool — True after the last block of each chunk,
    since [bps, S] int32 — at cut positions, the cut chunk's length in
    blocks; 0 elsewhere). Bit-exact vs select_cuts_blocks per strip.

    The walk is sequential by definition; ``unroll`` blocks advance per scan
    step (identical math, unrolled on registers) because per-step dispatch
    dominates an un-unrolled scan (measured 15 ms -> 1 ms per 64 MiB on
    v5e at unroll=8).
    """
    import jax
    import jax.numpy as jnp

    s = cand.shape[1]
    bps = params.strip_blocks
    min_b = jnp.int32(params.min_blocks)
    max_b = jnp.int32(params.max_blocks)
    u = unroll if bps % unroll == 0 else 1

    def step(since, cand_t, t):
        since1 = since + 1
        in_range = t < real_blocks                     # block t exists
        is_last = t == real_blocks - 1                 # strip/file end
        cut = ((cand_t & (since1 >= min_b)) | (since1 >= max_b) | is_last) \
            & in_range
        nxt = jnp.where(cut, 0, jnp.where(in_range, since1, since))
        return nxt, cut, jnp.where(cut, since1, 0)

    def body(since, xs):
        cand_u, t_u = xs                               # [u, S], [u]
        cuts, lens = [], []
        for j in range(u):
            since, cut, ln = step(since, cand_u[j], t_u[j])
            cuts.append(cut)
            lens.append(ln)
        return since, (jnp.stack(cuts), jnp.stack(lens))

    _, (cutflag, since) = jax.lax.scan(
        body, jnp.zeros((s,), jnp.int32),
        (cand.reshape(bps // u, u, s),
         jnp.arange(bps, dtype=jnp.int32).reshape(bps // u, u)))
    return cutflag.reshape(bps, s), since.reshape(bps, s)


# ---------------------------------------------------------------------------
# Chunk-table helpers of the chain
# ---------------------------------------------------------------------------

def cut_capacity(s: int, params: AlignedCdcParams) -> int:
    """Static bound on cuts in ``s`` strips: each strip yields at most
    ceil(bps / min_blocks) cuts plus the forced strip-final cut."""
    per_strip = -(-params.strip_blocks // params.min_blocks) + 1
    return s * per_strip


def digests_to_hex(dig: np.ndarray) -> list[str]:
    """[C, 8] uint32 -> lowercase hex, one string per row (vectorized)."""
    be = np.ascontiguousarray(dig.astype(">u4"))
    hx = be.tobytes().hex()
    return [hx[i * 64:(i + 1) * 64] for i in range(dig.shape[0])]
