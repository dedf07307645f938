"""Batched SHA-256 as a JAX uint32 kernel.

The reference hashes with ``java.security.MessageDigest`` one buffer at a time
(StorageNode.java:603-613). On TPU the work is re-shaped for the VPU: a whole
*batch* of messages is hashed in lockstep — every uint32 op in the compression
function is vectorized across the batch dimension (lanes), the 64 rounds and
the message-schedule recurrence are unrolled (they are sequential by
definition), and multi-block messages advance through a masked ``lax.scan`` so
messages of different lengths share one fused kernel.

Bit-exactness against ``hashlib.sha256`` is enforced by tests for every
length class (empty, <55, 55/56/64 boundary, multi-block).

The served chain hashes with the strip-scan kernel (ops/sha256_strip.py),
which takes its constants (``_H0``, ``_K``) from here; the batched kernel
itself is the reference that kernel is tested against.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# FIPS 180-4 constants.
_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
    0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
    0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
    0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
    0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
    0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)

_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)


def _rotr(x: jax.Array, n: int) -> jax.Array:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _compress_block_unrolled(state: jax.Array, block: jax.Array) -> jax.Array:
    """One SHA-256 compression, vectorized over the batch — fully unrolled.

    state: [B, 8] uint32; block: [B, 16] uint32 (big-endian words already
    byte-swapped on host). Returns new state [B, 8].

    This is the TPU variant: 112 unrolled steps of VPU uint32 ops with no
    loop-carried dynamic indexing, which XLA:TPU fuses into a tight kernel.
    (XLA:CPU must NOT run this form: its runtime evaluation of the deeply
    shared a..h expression DAG blows up super-exponentially past ~16 rounds —
    measured 0.01 s at 16 rounds vs 7.4 s at 24. CPU uses the fori_loop
    variant below; see _compress_block.)
    """
    w = [block[:, t] for t in range(16)]
    for t in range(16, 64):
        s0 = _rotr(w[t - 15], 7) ^ _rotr(w[t - 15], 18) ^ (w[t - 15] >> np.uint32(3))
        s1 = _rotr(w[t - 2], 17) ^ _rotr(w[t - 2], 19) ^ (w[t - 2] >> np.uint32(10))
        w.append(w[t - 16] + s0 + w[t - 7] + s1)

    a, b, c, d, e, f, g, h = (state[:, i] for i in range(8))
    for t in range(64):
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + _K[t] + w[t]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        h, g, f, e, d, c, b, a = g, f, e, d + t1, c, b, a, t1 + t2

    return jnp.stack(
        [state[:, 0] + a, state[:, 1] + b, state[:, 2] + c, state[:, 3] + d,
         state[:, 4] + e, state[:, 5] + f, state[:, 6] + g, state[:, 7] + h],
        axis=1,
    )


def _compress_block_looped(state: jax.Array, block: jax.Array) -> jax.Array:
    """CPU-safe compression: message schedule and rounds as fori_loops with a
    small carried state, so the executable is two short native loops instead
    of one giant expression DAG (see _compress_block_unrolled docstring)."""
    bsz = state.shape[0]
    k_arr = jnp.asarray(_K)

    w0 = jnp.concatenate(
        [block, jnp.zeros((bsz, 48), jnp.uint32)], axis=1)  # [B, 64]

    def sched_body(t, w):
        wm15 = jax.lax.dynamic_slice_in_dim(w, t - 15, 1, axis=1)[:, 0]
        wm2 = jax.lax.dynamic_slice_in_dim(w, t - 2, 1, axis=1)[:, 0]
        wm7 = jax.lax.dynamic_slice_in_dim(w, t - 7, 1, axis=1)[:, 0]
        wm16 = jax.lax.dynamic_slice_in_dim(w, t - 16, 1, axis=1)[:, 0]
        s0 = _rotr(wm15, 7) ^ _rotr(wm15, 18) ^ (wm15 >> np.uint32(3))
        s1 = _rotr(wm2, 17) ^ _rotr(wm2, 19) ^ (wm2 >> np.uint32(10))
        wt = wm16 + s0 + wm7 + s1
        return jax.lax.dynamic_update_slice_in_dim(w, wt[:, None], t, axis=1)

    w = jax.lax.fori_loop(16, 64, sched_body, w0)

    def round_body(t, carry):
        a, b, c, d, e, f, g, h = carry
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, axis=1)[:, 0]
        kt = jax.lax.dynamic_slice_in_dim(k_arr, t, 1, axis=0)[0]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + kt + wt
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        return (t1 + s0 + maj, a, b, c, d + t1, e, f, g)

    out = jax.lax.fori_loop(
        0, 64, round_body, tuple(state[:, i] for i in range(8)))
    return state + jnp.stack(out, axis=1)


def _compress_block(state: jax.Array, block: jax.Array) -> jax.Array:
    """Backend-dispatched compression: unrolled on accelerators, looped on
    CPU (incl. the virtual multi-device CPU mesh used for sharding tests)."""
    if jax.default_backend() == "cpu":
        return _compress_block_looped(state, block)
    return _compress_block_unrolled(state, block)


def _sha256_blocks_impl(words: jax.Array, nblocks: jax.Array) -> jax.Array:
    """Hash a batch of pre-padded messages (un-jitted core, also embedded in
    larger jitted programs — __graft_entry__, parallel.sharded_cdc).

    words: [B, L, 16] uint32 — L padded 64-byte blocks per message (see
    :func:`pad_messages`); nblocks: [B] int32 — real block count per message
    (rows advance only while their block index < nblocks, so short messages
    coast unchanged through the tail of the scan). Returns [B, 8] uint32.
    """
    bsz, nblk, _ = words.shape
    state0 = jnp.broadcast_to(jnp.asarray(_H0), (bsz, 8))

    def body(state, xs):
        block, l = xs
        new = _compress_block(state, block)
        keep = (l < nblocks)[:, None]
        return jnp.where(keep, new, state), None

    state, _ = jax.lax.scan(
        body, state0, (jnp.moveaxis(words, 1, 0), jnp.arange(nblk, dtype=jnp.int32))
    )
    return state


sha256_blocks = jax.jit(_sha256_blocks_impl, donate_argnums=(0,))


def pad_messages(chunks: list[bytes | np.ndarray],
                 n_blocks: int | None = None,
                 batch: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """FIPS 180-4 padding on the host → big-endian words + block counts.

    Optionally rounds the block dimension up to ``n_blocks`` and the batch up
    to ``batch`` (extra rows get nblocks=0 and hash to H0; callers drop them)
    so jit sees a small, fixed set of shapes.
    """
    bsz = len(chunks)
    max_len = max((len(c) for c in chunks), default=0)
    need_blocks = (max_len + 8) // 64 + 1
    nblk = max(n_blocks or 0, need_blocks)
    rows = max(batch or 0, bsz)

    buf = np.zeros((rows, nblk * 64), dtype=np.uint8)
    counts = np.zeros((rows,), dtype=np.int32)
    for i, c in enumerate(chunks):
        a = np.frombuffer(c, dtype=np.uint8) if not isinstance(c, np.ndarray) else c
        n = a.shape[0]
        buf[i, :n] = a
        buf[i, n] = 0x80
        nb = (n + 8) // 64 + 1
        buf[i, nb * 64 - 8: nb * 64] = np.frombuffer(
            (n * 8).to_bytes(8, "big"), dtype=np.uint8)
        counts[i] = nb
    words = np.ascontiguousarray(buf).view(">u4").astype(np.uint32)
    return words.reshape(rows, nblk, 16), counts


def state_to_hex(state: np.ndarray) -> list[str]:
    """[B, 8] uint32 → lowercase-hex digests (the wire/manifest format,
    matching reference sha256Hex at StorageNode.java:603-613)."""
    out = []
    for row in np.asarray(state, dtype=np.uint32):
        out.append("".join(f"{int(x):08x}" for x in row))
    return out


def sha256_batch_hex(chunks: list[bytes | np.ndarray]) -> list[str]:
    """Convenience one-shot: digest a batch of messages on the default JAX
    backend — the plain-XLA reference tests/test_sha256.py holds the strip
    kernel to. Batch and block dims are rounded up to powers of two
    (compiles are cached per shape)."""
    if not chunks:
        return []
    n = len(chunks)
    need = max((len(c) for c in chunks), default=0)
    pow2 = lambda x: 1 << (max(1, x) - 1).bit_length()  # noqa: E731
    words, counts = pad_messages(chunks, n_blocks=pow2((need + 8) // 64 + 1),
                                 batch=pow2(n))
    state = sha256_blocks(jnp.asarray(words), jnp.asarray(counts))
    return state_to_hex(np.asarray(state)[:n])
