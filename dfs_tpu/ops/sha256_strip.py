"""Strip-scan SHA-256: hash every chunk of a stream in one Pallas pass.

A batched-message kernel (one message per lane row; built and discarded in
round 1) needs each message gathered into its own row — and
arbitrary-offset gathers measured ~0.6 s per 32 MiB on v5e, two orders
slower than the hash itself. This kernel removes the
gather: the stream stays in its strip-transposed resident layout
(ops.cdc_v2, "Resident layout") and *chunk chaining follows the stream order*.

Lane ``s`` walks its strip's 64-byte blocks sequentially (the grid axis);
at every step it compresses the next block into its running state, writes
the post-block state out, and — where the selection pass flagged a cut —
resets to H0 for the next chunk. One grid step therefore advances *all*
strips by one block: the VPU sees (S/128 · 8, 128) uint32 tiles of pure
elementwise work, and the only HBM traffic is the linear stream read plus
the state stream write. Chunk digests are the states at cut positions
(gathered afterwards — #cuts rows, metadata-sized) plus one batched
"pad-block" compression applied by ``pad_finalize_device`` (every non-final
chunk is a whole number of blocks, so its FIPS padding block is synthetic:
0x80, zeros, bit length).

Layouts (S = strips, padded to a multiple of 128; bps = strip_blocks):
  words_t  [bps*16, S] u32   block t's word w of strip s at [t*16+w, s]
  cutflag  [bps, S]    i32   1 after the last block of a chunk
  states   [bps*8, S]  u32   post-block state word i at [t*8+i, s]
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from dfs_tpu.ops.sha256_jax import _H0, _K

UNROLL = 8  # blocks per Pallas grid step: per-step dispatch overhead over
# a bps-length grid dominated the scan (same finding as
# ops.cdc_v2.select_cuts_device — measured there 15 ms -> 1 ms per 64 MiB
# at unroll=8); the chained compressions inside one step are sequential
# per lane anyway.


def _rotr(x, n: int):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _compress(state8: list, w: list) -> list:
    """One SHA-256 compression on vector registers; state8/w: lists of
    identically-shaped uint32 arrays (any shape — elementwise). Fully
    unrolled: the TPU/Pallas form (see ops.sha256_jax for why XLA:CPU must
    never evaluate this — its shared-DAG evaluation explodes past ~16
    rounds)."""
    for t in range(16, 64):
        s0 = _rotr(w[t - 15], 7) ^ _rotr(w[t - 15], 18) ^ (w[t - 15] >> np.uint32(3))
        s1 = _rotr(w[t - 2], 17) ^ _rotr(w[t - 2], 19) ^ (w[t - 2] >> np.uint32(10))
        w.append(w[t - 16] + s0 + w[t - 7] + s1)
    a, b, c, d, e, f, g, h = state8
    for t in range(64):
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + np.uint32(_K[t]) + w[t]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + s0 + maj
    return [s + v for s, v in zip(state8, [a, b, c, d, e, f, g, h])]


def _compress_looped(state8: list, w16: list) -> list:
    """CPU-safe compression (fori_loop schedule + rounds, small carried
    state), same list-of-arrays interface as :func:`_compress`."""
    w0 = jnp.stack(list(w16[:16])
                   + [jnp.zeros_like(w16[0])] * 48)    # [64, ...]
    k_arr = jnp.asarray(_K)

    def sched_body(t, w):
        wm15 = jax.lax.dynamic_index_in_dim(w, t - 15, 0, keepdims=False)
        wm2 = jax.lax.dynamic_index_in_dim(w, t - 2, 0, keepdims=False)
        wm7 = jax.lax.dynamic_index_in_dim(w, t - 7, 0, keepdims=False)
        wm16 = jax.lax.dynamic_index_in_dim(w, t - 16, 0, keepdims=False)
        s0 = _rotr(wm15, 7) ^ _rotr(wm15, 18) ^ (wm15 >> np.uint32(3))
        s1 = _rotr(wm2, 17) ^ _rotr(wm2, 19) ^ (wm2 >> np.uint32(10))
        return jax.lax.dynamic_update_index_in_dim(
            w, wm16 + s0 + wm7 + s1, t, 0)

    w = jax.lax.fori_loop(16, 64, sched_body, w0)

    def round_body(t, carry):
        a, b, c, d, e, f, g, h = carry
        wt = jax.lax.dynamic_index_in_dim(w, t, 0, keepdims=False)
        kt = jax.lax.dynamic_index_in_dim(k_arr, t, 0, keepdims=False)
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + kt + wt
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        return (t1 + s0 + maj, a, b, c, d + t1, e, f, g)

    out = jax.lax.fori_loop(0, 64, round_body, tuple(state8))
    return [s + v for s, v in zip(state8, out)]


def _compress_dispatch(state8: list, w: list) -> list:
    """Unrolled on accelerators, looped on CPU (same rule and rationale as
    ops.sha256_jax._compress_block)."""
    if jax.default_backend() == "cpu":
        return _compress_looped(state8, w)
    return _compress(state8, w)


def _strip_kernel(words_ref, flags_ref, out_ref, state_ref, *, unroll: int):
    """words_ref: [16*unroll, R, 128]; flags_ref: [unroll, R, 128];
    out_ref: [8*unroll, R, 128]; state_ref (scratch, persists across the
    sequential grid): [8, R, 128]. Lanes = strips, organized (R, 128).
    Each grid step chains ``unroll`` consecutive blocks."""
    from jax.experimental import pallas as pl

    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        for i in range(8):
            state_ref[i] = jnp.full_like(state_ref[i], jnp.uint32(_H0[i]))

    state = [state_ref[i] for i in range(8)]
    for b in range(unroll):
        w = [words_ref[b * 16 + i] for i in range(16)]
        new = _compress(state, w)
        cut = flags_ref[b] != 0
        for i in range(8):
            out_ref[b * 8 + i] = new[i]
        state = [jnp.where(cut, jnp.uint32(_H0[i]), new[i])
                 for i in range(8)]
    for i in range(8):
        state_ref[i] = state[i]


@functools.partial(jax.jit, static_argnames=("interpret",))
def strip_states(words_t: jax.Array, cutflag: jax.Array,
                 interpret: bool = False) -> jax.Array:
    """Run the strip scan: (words_t [bps*16, S] u32, cutflag [bps, S] i32)
    -> states [bps*8, S] u32 (post-block chain state per block)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, s = words_t.shape
    bps = rows // 16
    r = s // 128
    u = UNROLL if bps % UNROLL == 0 else 1
    w3 = words_t.reshape(bps * 16, r, 128)
    f3 = cutflag.astype(jnp.int32).reshape(bps, r, 128)
    out = pl.pallas_call(
        functools.partial(_strip_kernel, unroll=u),
        out_shape=jax.ShapeDtypeStruct((bps * 8, r, 128), jnp.uint32),
        grid=(bps // u,),
        in_specs=[
            pl.BlockSpec((16 * u, r, 128), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((u, r, 128), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((8 * u, r, 128), lambda t: (t, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((8, r, 128), jnp.uint32)],
        interpret=interpret,
    )(w3, f3)
    return out.reshape(bps * 8, s)


def strip_states_xla(words_t: jax.Array, cutflag: jax.Array) -> jax.Array:
    """Pure-XLA fallback with identical semantics (used on CPU where the
    unrolled Pallas body is slow to interpret, and as a correctness
    cross-check on TPU)."""
    rows, s = words_t.shape
    bps = rows // 16
    words = words_t.reshape(bps, 16, s)
    h0 = jnp.broadcast_to(jnp.asarray(_H0)[:, None], (8, s))

    def body(state, xs):
        block, cut = xs
        new = _compress_dispatch([state[i] for i in range(8)],
                                 [block[i] for i in range(16)])
        new = jnp.stack(new)
        out = new
        state = jnp.where((cut != 0)[None, :], h0, new)
        return state, out

    _, states = jax.lax.scan(body, h0, (words, cutflag))
    return states.reshape(bps * 8, s)  # [bps, 8, S] -> same row layout


def _strip_fused_kernel(words_ref, rb_ref, out_ref, cf_ref, since_ref,
                        state_ref, carry_ref, *, unroll: int, seed: int,
                        mask: int, min_b: int, max_b: int):
    """Fused candidates + greedy selection + SHA scan: one pass over the
    resident words instead of three (gear candidate pass re-reading all
    words, the selection lax.scan, then this kernel). The Gear window of
    block t is words 8..15 of block t — already in VMEM for the
    compression — and the selection carry (blocks since last cut) rides
    beside the SHA chain state. words_ref [16u, R, 128];
    rb_ref [R, 128] (real_blocks broadcast); outputs: states
    [8u, R, 128], cutflag [u, R, 128] i32, since [u, R, 128] i32;
    scratch: state [8, R, 128], carry(since) [1, R, 128]."""
    from jax.experimental import pallas as pl

    from dfs_tpu.ops.cdc_v2 import _M1, _M2, _PRIME

    t0 = pl.program_id(0) * unroll

    @pl.when(pl.program_id(0) == 0)
    def _init():
        for i in range(8):
            state_ref[i] = jnp.full_like(state_ref[i], jnp.uint32(_H0[i]))
        carry_ref[0] = jnp.zeros_like(carry_ref[0])

    def fmix(x):
        # lowbias32, shared constants with the staged Gear pass — the
        # fused and staged paths must stay bit-identical
        x = x ^ (x >> np.uint32(16))
        x = x * _M1
        x = x ^ (x >> np.uint32(15))
        x = x * _M2
        return x ^ (x >> np.uint32(16))

    rb = rb_ref[...]
    state = [state_ref[i] for i in range(8)]
    since = carry_ref[0]
    for b in range(unroll):
        w = [words_ref[b * 16 + i] for i in range(16)]
        # Gear windowed hash over the block's last 32 bytes (w[8..15]),
        # identical math to ops.cdc_v2.gear_candidates_device
        h = jnp.zeros_like(w[0])
        for j in range(32):
            byte = (w[8 + j // 4] >> np.uint32(8 * (3 - j % 4))) \
                & np.uint32(0xFF)
            g = fmix(np.uint32(seed) ^ (byte * _PRIME))
            h = h + (g << np.uint32(31 - j))
        cand = (h & np.uint32(mask)) == 0
        # greedy selection step (ops.cdc_v2.select_cuts_device semantics)
        t = t0 + b
        since1 = since + jnp.int32(1)
        in_range = t < rb
        is_last = t == rb - jnp.int32(1)
        cut = ((cand & (since1 >= jnp.int32(min_b)))
               | (since1 >= jnp.int32(max_b)) | is_last) & in_range
        since = jnp.where(cut, jnp.int32(0),
                          jnp.where(in_range, since1, since))
        cf_ref[b] = cut.astype(jnp.int32)
        since_ref[b] = jnp.where(cut, since1, jnp.int32(0))
        # SHA compression with per-cut chain reset
        new = _compress(state, w)
        for i in range(8):
            out_ref[b * 8 + i] = new[i]
        state = [jnp.where(cut, jnp.uint32(_H0[i]), new[i])
                 for i in range(8)]
    for i in range(8):
        state_ref[i] = state[i]
    carry_ref[0] = since


@functools.partial(jax.jit, static_argnames=("seed", "mask", "min_b",
                                             "max_b", "interpret"))
def strip_chunk_states(words_t: jax.Array, real_blocks: jax.Array,
                       seed: int, mask: int, min_b: int, max_b: int,
                       interpret: bool = False
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fused device pass: (words_t [bps*16, S] u32 BE, real_blocks [S]
    i32) -> (cutflag [bps, S] i32, since [bps, S] i32, states [bps*8, S]
    u32) — bit-identical to gear_candidates_device +
    select_cuts_device + strip_states, in ONE kernel (the candidate
    pass's full re-read of the resident words and the selection scan's
    separate dispatch measured ~1.6 ms per 64 MiB region on v5e; fused
    they ride the SHA kernel's already-loaded VMEM blocks)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, s = words_t.shape
    bps = rows // 16
    r = s // 128
    u = UNROLL if bps % UNROLL == 0 else 1
    w3 = words_t.reshape(bps * 16, r, 128)
    rb3 = real_blocks.astype(jnp.int32).reshape(r, 128)
    states, cf, since = pl.pallas_call(
        functools.partial(_strip_fused_kernel, unroll=u, seed=seed,
                          mask=mask, min_b=min_b, max_b=max_b),
        out_shape=(
            jax.ShapeDtypeStruct((bps * 8, r, 128), jnp.uint32),
            jax.ShapeDtypeStruct((bps, r, 128), jnp.int32),
            jax.ShapeDtypeStruct((bps, r, 128), jnp.int32),
        ),
        grid=(bps // u,),
        in_specs=[
            pl.BlockSpec((16 * u, r, 128), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((r, 128), lambda t: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((8 * u, r, 128), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((u, r, 128), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((u, r, 128), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[pltpu.VMEM((8, r, 128), jnp.uint32),
                        pltpu.VMEM((1, r, 128), jnp.int32)],
        interpret=interpret,
    )(w3, rb3)
    return (cf.reshape(bps, s), since.reshape(bps, s),
            states.reshape(bps * 8, s))


def pad_finalize_device(states: jax.Array, lens: jax.Array) -> jax.Array:
    """Apply the synthetic FIPS padding block to gathered chunk states.

    states: [C, 8] u32 — chain state after each chunk's last content block;
    lens: [C] i32 — chunk byte length (multiple of 64). Returns [C, 8]
    final digests. Rows with lens == 0 are padding; output garbage.
    """
    zero = jnp.zeros_like(lens, dtype=jnp.uint32)
    w = [jnp.full_like(zero, jnp.uint32(0x80000000))] + [zero] * 13
    bits = lens.astype(jnp.uint32) * jnp.uint32(8)
    w.append(lens.astype(jnp.uint32) >> jnp.uint32(29))   # high bit-length
    w.append(bits)                                         # low bit-length
    out = _compress_dispatch([states[:, i] for i in range(8)], w)
    return jnp.stack(out, axis=1)


def cut_state_rows(states: jax.Array, s: int) -> jax.Array:
    """Relayout [bps*8, S] states to row-contiguous [bps*S, 8] so cut-state
    gathers fetch whole 32-byte rows instead of 8 scattered words. One
    transpose of the state stream amortizes over every gather that follows
    (the element gather measured 4.6 ms per 64 MiB region on v5e; the row
    form ~1 ms including this relayout)."""
    rows = states.shape[0]
    bps = rows // 8
    return states.reshape(bps, 8, s).transpose(0, 2, 1).reshape(bps * s, 8)


def gather_cut_states(states: jax.Array, flat_cuts: jax.Array,
                      s: int) -> jax.Array:
    """states: [bps*8, S]; flat_cuts: [C] i32 = t*S + s (or -1 padding) ->
    [C, 8] chain states (metadata-sized gather). Prefer precomputing
    :func:`cut_state_rows` once when gathering more than once."""
    return jnp.take(cut_state_rows(states, s), jnp.maximum(flat_cuts, 0),
                    axis=0)
