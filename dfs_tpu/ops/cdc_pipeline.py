"""Fused device pipeline for aligned CDC v2: bytes -> chunk table.

One jitted call per shape bucket does everything on device:

  raw u8 segment --reshape/shift--> words_t [bps*16, S]   (BE pack, XLA)
                 --window hash----> candidates [bps, S]    (ops.cdc_v2)
                 --lane scan------> cutflag   [bps, S]     (ops.cdc_v2)
                 --Pallas scan----> states    [bps*8, S]   (ops.sha256_strip)
                 --nonzero--------> cut positions [C_max]  (stream order)
                 --gather+pad-----> digests   [C_max, 8]

and returns ONLY metadata (positions + digests + count) to the host — the
v1 path's full-bitmap device->host pull (dfs_tpu/fragmenter/cdc_tpu.py) was
the measured bottleneck (d2h over host PCIe runs ~10x slower than
on-device HBM traffic).

Only real strips cross host->device (``s_real``); the lane axis is padded to
``s_pad`` on device (Pallas wants a multiple of 128 lanes). A segment (a
whole number of strips) is the unit of dispatch: chunking restarts at strip
boundaries (ops.cdc_v2 docstring), so segments are fully independent — big
files loop over fixed-shape segments (one compile), arbitrarily long streams
process in bounded memory, and a device mesh shards the strip axis with no
cross-device communication at all.

Replaces the upload-side hot loop of the reference
(StorageNode.java:127,154-171: whole-file sha256 + per-fragment copy/hash).
"""

from __future__ import annotations

import functools

import numpy as np

from dfs_tpu.ops.cdc_v2 import (AlignedCdcParams, gear_candidates_device,
                                select_cuts_device)
from dfs_tpu.utils.hashing import next_pow2

BLOCK = 64


def cut_capacity(s: int, params: AlignedCdcParams) -> int:
    """Static bound on cuts in a segment of ``s`` strips: each strip yields
    at most ceil(bps / min_blocks) cuts plus the forced strip-final cut."""
    per_strip = -(-params.strip_blocks // params.min_blocks) + 1
    return s * per_strip


@functools.cache
def make_segment_fn(params: AlignedCdcParams, s_real: int, s_pad: int):
    """Compiled fn: (words_le [s_real*strip_len/4] u32 — the segment bytes
    host-viewed as LE words, real_blocks [s_pad] i32) -> (count i32,
    positions [C_max] i32 (q = s*bps + t, -1 pad, stream order),
    digests [C_max, 8] u32 (rows beyond count are garbage))."""
    import jax
    import jax.numpy as jnp

    from dfs_tpu.ops.sha256_strip import (gather_cut_states,
                                          pad_finalize_device,
                                          strip_chunk_states,
                                          strip_states_xla)

    from dfs_tpu.ops.layout import bswap_transpose

    bps = params.strip_blocks
    c_max = cut_capacity(s_pad, params)
    use_pallas = s_pad % 128 == 0 and any(
        d.platform == "tpu" for d in jax.devices())

    # cut-position compaction tiling: tiles never span a strip (t_tile |
    # bps), so in-strip cuts are >= min_blocks apart and a tile holds at
    # most t_tile//min_blocks + 2 cuts (+1 partial leading gap, +1 forced
    # strip-final); segment_chunks cross-checks the recovered count.
    t_tile = 128 if bps % 128 == 0 else bps
    k_max = t_tile // params.min_blocks + 2

    # Two jitted halves, not one: intermediates stay device-resident either
    # way, but fusing the unrolled SHA scan with the compaction epilogue
    # into a single XLA:CPU module sends its fusion pass into the weeds
    # (minutes-long compile measured on the 8-virtual-device CI host; each
    # half alone compiles in seconds).

    @jax.jit
    def scan_half(words_le, real_blocks):
        # words_le: [s_real * bps*16] u32 — the raw stream viewed as
        # little-endian words on the HOST (a free numpy .view; feeding u8
        # and converting on device measured 26 ms per 64 MiB — TPU u8
        # relayout — vs 0 for the host view).
        words_t = bswap_transpose(
            words_le.reshape(s_real, bps * 16))        # [bps*16, s_real] BE
        if s_pad != s_real:
            words_t = jnp.pad(words_t, ((0, 0), (0, s_pad - s_real)))

        if use_pallas:
            # fused candidates+selection+SHA (ops.sha256_strip) — one
            # pass over the resident words instead of three
            cf32, _, states = strip_chunk_states(
                words_t, real_blocks, params.seed, params.mask,
                params.min_blocks, params.max_blocks)
        else:
            cand = gear_candidates_device(words_t, params)
            cutflag, _ = select_cuts_device(cand, real_blocks, params)
            cf32 = cutflag.astype(jnp.int32)
            states = strip_states_xla(words_t, cf32)
        return cf32, states

    @jax.jit
    def compact_half(cf32, states):
        count = jnp.sum(cf32)

        # stream-order cut positions q = s*bps + t, compacted tile-wise:
        # per 128-block tile, peel off the k-th lowest set bit (k < k_max)
        # with masked min-reductions — all vector ops, no scatter over the
        # full block space (jnp.nonzero measured 9 ms per 64 MiB; this
        # path ~1 ms).
        flat = cf32.T.reshape(-1, t_tile) != 0         # [nt, t_tile]
        nt = flat.shape[0]
        iota = jnp.arange(t_tile, dtype=jnp.int32)[None, :]
        cnt = jnp.sum(flat, axis=1).astype(jnp.int32)
        base = jnp.cumsum(cnt) - cnt                   # exclusive ranks
        poss = []
        cur = flat
        for _ in range(k_max):
            pos = jnp.min(jnp.where(cur, iota, t_tile), axis=1)
            poss.append(pos)
            cur = cur & (iota != pos[:, None])
        pos_mat = jnp.stack(poss, axis=1)              # [nt, k_max] sorted
        valid = pos_mat < t_tile
        gidx = jnp.where(
            valid,
            base[:, None] + jnp.arange(k_max, dtype=jnp.int32)[None, :],
            c_max)
        vals = jnp.arange(nt, dtype=jnp.int32)[:, None] * t_tile + pos_mat
        q = jnp.full((c_max,), -1, jnp.int32).at[gidx.reshape(-1)].set(
            vals.reshape(-1).astype(jnp.int32), mode="drop")

        # chunk byte lengths: every strip restarts chunking, and every real
        # strip ends in a forced cut, so consecutive-q differences are exact
        # (q[i-1] for the first cut of a strip is the previous strip's last
        # block, = s*bps - 1).
        prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), q[:-1]])
        lens = (q - prev) * jnp.int32(BLOCK)

        t = jnp.maximum(q, 0) % bps
        s = jnp.maximum(q, 0) // bps
        cut_states = gather_cut_states(states, t * jnp.int32(s_pad) + s,
                                       s_pad)
        digests = pad_finalize_device(cut_states, lens)
        return count, q, digests

    def run(raw, real_blocks):
        return compact_half(*scan_half(raw, real_blocks))

    return run


def digests_to_hex(dig: np.ndarray) -> list[str]:
    """[C, 8] uint32 -> lowercase hex, one string per row (vectorized)."""
    be = np.ascontiguousarray(dig.astype(">u4"))
    hx = be.tobytes().hex()
    return [hx[i * 64:(i + 1) * 64] for i in range(dig.shape[0])]


def segment_chunks(data: np.ndarray, params: AlignedCdcParams,
                   lane_multiple: int = 128) -> list[tuple[int, int, str]]:
    """Chunk one segment (``data`` [n] u8, n <= segment capacity) on device
    -> [(offset, length, sha256hex)] with segment-relative offsets.

    Host work is metadata-sized: one zero-pad copy of the tail strip, the
    position->span arithmetic, and hex formatting. The final chunk is
    re-hashed host-side iff it ends in a partial block (the device states
    saw zero padding there); every other digest comes straight off the
    device.
    """
    import hashlib

    import jax
    import jax.numpy as jnp

    n = int(data.shape[0])
    if n == 0:
        return []
    sl = params.strip_len
    bps = params.strip_blocks
    # transfer size is bucketed to the next power-of-two strip count so the
    # jit cache holds ~log2(seg_strips) shapes instead of one per distinct
    # tail size (zero-pad copy is cheap; XLA compiles are not)
    s_real = next_pow2(-(-n // sl))
    s_pad = max(lane_multiple, s_real)

    if n != s_real * sl:
        raw = np.zeros((s_real * sl,), dtype=np.uint8)
        raw[:n] = data
    else:
        raw = np.ascontiguousarray(data)

    nb = -(-n // BLOCK)                                # incl. partial block
    real_blocks = np.zeros((s_pad,), np.int32)
    real_blocks[:nb // bps] = bps
    if nb % bps:
        real_blocks[nb // bps] = nb % bps

    run = make_segment_fn(params, s_real, s_pad)
    count, q, dig = run(jax.device_put(raw.view("<u4")),
                        jax.device_put(jnp.asarray(real_blocks)))
    count = int(np.asarray(count))
    q = np.asarray(q)[:count].astype(np.int64)
    dig = np.asarray(dig)[:count]
    if count and (q < 0).any():
        raise AssertionError(
            "cut compaction overflowed a tile (k_max too small)")

    ends = np.minimum((q + 1) * BLOCK, n)              # byte end per cut
    starts = np.concatenate([[0], ends[:-1]])
    hexes = digests_to_hex(dig)
    out = [(int(o), int(e - o), h)
           for o, e, h in zip(starts, ends, hexes)]
    if n % BLOCK:                                      # partial final block
        o, ln, _ = out[-1]
        out[-1] = (o, ln, hashlib.sha256(
            raw[o:o + ln].tobytes()).hexdigest())
    return out
