"""Erasure coding: RAID-6-style P+Q parity over GF(256), TPU-native.

The reference's only redundancy is cyclic x2 replication — 100% storage
overhead, tolerates ONE lost node on the read path (StorageNode.java:
143-145, 425-441; README.md:65-81). This codec gives the framework an
erasure-coded mode: a stripe of ``k`` data shards gains two parity
shards

    P = d_0 ^ d_1 ^ ... ^ d_{k-1}
    Q = g^{k-1}·d_0 ^ g^{k-2}·d_1 ^ ... ^ g^0·d_{k-1}        (GF(256))

so ANY two lost shards are recoverable — strictly better durability than
replication at (k+2)/k storage instead of 2x.

TPU angle: the encode is deliberately table-free. GF(256) doubling is

    xtime(x) = (x << 1) ^ (0x1D if x & 0x80 else 0)  (mod x^8+x^4+x^3+x^2+1)

and Q falls out of a Horner scan ``q = xtime(q) ^ d_i`` — pure bitwise
VPU ops over u32-packed lanes, memory-bound on HBM like the rest of the
chunk pipeline (no gathers, no log/exp tables on the hot path). The
NumPy forms are the byte-identical oracle and the CPU fallback.

Decode (cold path — only runs degraded) solves the 1- and 2-erasure
cases with the standard RAID-6 algebra on the host; the g^i/inverse
tables live here and are only touched on decode.
"""

from __future__ import annotations

import functools

import numpy as np

_POLY = 0x1D  # x^8 + x^4 + x^3 + x^2 + 1 — the RAID-6 field: 2 IS a
# generator here (it is NOT in the AES field 0x11B, whose element 2 has
# order 51 — log/exp tables on g=2 would be silently wrong there)


# ---------------------------------------------------------------------------
# GF(256) tables (decode-time only)
# ---------------------------------------------------------------------------

@functools.cache
def _tables() -> tuple[np.ndarray, np.ndarray]:
    """(log, exp) tables for generator 2: exp[i] = 2^i, log[exp[i]] = i."""
    exp = np.zeros(512, dtype=np.uint8)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= _POLY | 0x100
    exp[255:510] = exp[:255]
    return log, exp


def gf_mul(a: int, b: int) -> int:
    """Scalar GF(256) multiply (decode coefficients only)."""
    if a == 0 or b == 0:
        return 0
    log, exp = _tables()
    return int(exp[int(log[a]) + int(log[b])])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("GF(256) inverse of 0")
    log, exp = _tables()
    return int(exp[255 - int(log[a])])


def gf_pow(a: int, n: int) -> int:
    r = 1
    for _ in range(n):
        r = gf_mul(r, a)
    return r


def _gf_mul_bytes(c: int, x: np.ndarray) -> np.ndarray:
    """Constant × byte-array multiply via log/exp (decode path)."""
    if c == 0:
        return np.zeros_like(x)
    log, exp = _tables()
    out = np.zeros_like(x)
    nz = x != 0
    out[nz] = exp[int(log[c]) + log[x[nz].astype(np.int32)]]
    return out


# ---------------------------------------------------------------------------
# encode: P/Q over u32-packed shards (NumPy oracle + device form)
# ---------------------------------------------------------------------------

def _xtime_np(x: np.ndarray) -> np.ndarray:
    """GF doubling on u32 words holding 4 independent byte lanes.

    Written with explicit ``out=`` so the whole pass allocates two
    temporaries instead of six — this is the inner op of every Horner
    and scalar-multiply pass in the batched decode, where the naive
    form measured ~20% of a degraded read."""
    x = x.astype(np.uint32, copy=False)
    hi = np.bitwise_and(x, np.uint32(0x80808080))
    lo = np.bitwise_xor(x, hi)
    np.left_shift(lo, np.uint32(1), out=lo)
    np.right_shift(hi, np.uint32(7), out=hi)
    np.multiply(hi, np.uint32(_POLY), out=hi)
    np.bitwise_xor(lo, hi, out=lo)
    return lo


def encode_pq_np(shards: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """shards [k, L] u8 (equal padded length, L % 4 == 0) ->
    (p [L] u8, q [L] u8). Horner: q = xtime(q) ^ d_i in shard order."""
    k, ln = shards.shape
    if ln % 4:
        raise ValueError("shard length must be a multiple of 4")
    w = shards.view(np.uint32)                     # [k, L/4]
    p = np.zeros_like(w[0])
    q = np.zeros_like(w[0])
    for i in range(k):
        p ^= w[i]
        q = _xtime_np(q) ^ w[i]
    return p.view(np.uint8), q.view(np.uint8)


def xtime_device(x):
    """GF doubling on u32 words holding 4 independent byte lanes (the
    device twin of :func:`_xtime_np`; shared by the single-chip encode
    and the sharded mesh step in parallel.sharded_cdc)."""
    import jax.numpy as jnp

    hi = x & jnp.uint32(0x80808080)
    lo = (x ^ hi) << jnp.uint32(1)
    return lo ^ ((hi >> jnp.uint32(7)) * jnp.uint32(_POLY))


def pq_horner(shards, k: int, axis: int = 0):
    """The P/Q recurrence on device arrays: xor-accumulate P and Horner
    Q (``q = xtime(q) ^ d_i``) over the ``k`` shards along ``axis``.
    THE single definition of the parity math on device — the
    single-chip encode and the sharded mesh step
    (parallel.sharded_cdc.make_ec_step) both call it, so they cannot
    drift from each other (or from :func:`encode_pq_np`, the oracle)."""
    import jax.numpy as jnp

    if shards.shape[axis] != k:
        # jnp.take CLAMPS out-of-range indices under jit — a k/shape
        # mismatch would return wrong parity silently instead of raising
        raise ValueError(
            f"{shards.shape[axis]} shards along axis {axis}, expected {k}")
    take = (lambda i: shards[i]) if axis == 0 \
        else (lambda i: jnp.take(shards, i, axis=axis))
    p = take(0)
    q = take(0)                            # q0 = xtime(0) ^ d0 = d0
    for i in range(1, k):                  # k is static and small
        d = take(i)
        p = p ^ d
        q = xtime_device(q) ^ d
    return p, q


@functools.cache
def _make_encode_fn(k: int):
    """Compiled device encode for a k-shard stripe: words [k, n] u32 ->
    (p [n] u32, q [n] u32). Pure bitwise VPU ops — no tables."""
    import jax

    @jax.jit
    def run(words):
        return pq_horner(words, k)

    return run


def encode_pq(shards: np.ndarray, device: bool | None = None
              ) -> tuple[np.ndarray, np.ndarray]:
    """P/Q parity for a stripe. ``device=None`` picks the accelerator
    when one is the default backend (the encode is memory-bound xor/shift
    work the VPU does at HBM speed); False forces the NumPy oracle."""
    if device is None:
        import jax
        device = jax.default_backend() != "cpu"
    if not device:
        return encode_pq_np(shards)
    import jax

    k, ln = shards.shape
    if ln % 4:
        raise ValueError("shard length must be a multiple of 4")
    p, q = _make_encode_fn(k)(jax.device_put(shards.view(np.uint32)))
    return (np.asarray(p).view(np.uint8), np.asarray(q).view(np.uint8))


# ---------------------------------------------------------------------------
# batched encode: an object's stripes as a few fixed shapes
# ---------------------------------------------------------------------------

BATCH_MIN_WIDTH = 2048       # bytes: config.CDCParams' default min_chunk
_BATCH_BYTES = 64 << 20      # most bytes of data shards one call packs


def batch_width(shard_len: int) -> int:
    """The width a stripe of this padded length is encoded at: the power
    of two at or above it, ``BATCH_MIN_WIDTH`` at least. CDC stripes
    have near-unique lengths (a 16 MiB object: ~570 stripes, ~300
    distinct); zero-padding each to its bucket is parity-neutral
    (``xtime(0) = 0``: P and Q are the first ``shard_len`` bytes of the
    row), so an object encodes in one call a bucket — six from 2 KiB to
    the default ``max_chunk`` — and a process that jits the encode
    compiles a bounded set of shapes whatever the objects."""
    return max(BATCH_MIN_WIDTH, 1 << max(shard_len - 1, 0).bit_length())


def batch_rows(k: int, width: int) -> int:
    """Most stripes one call takes at this width: the power of two that
    keeps its data shards within ``_BATCH_BYTES`` — the bound on a call's
    memory, and (with :func:`batch_width`) on the shapes ever compiled."""
    n = _BATCH_BYTES // (k * width)
    return 1 << (n.bit_length() - 1) if n else 1


@functools.cache
def _make_batch_encode_fn(k: int):
    """Compiled batched encode: words [S, k, n] u32 -> (p, q) [S, n] u32
    — :func:`pq_horner` over axis 1, the one definition."""
    import jax

    @jax.jit
    def run(words):
        return pq_horner(words, k, axis=1)

    return run


def encode_pq_batch(shards: np.ndarray, device: bool = False
                    ) -> tuple[np.ndarray, np.ndarray]:
    """shards [S, k, W] u8 (W % 4 == 0) -> (p [S, W] u8, q [S, W] u8):
    :func:`encode_pq_np`'s recurrence over axis 1, S stripes at once.
    A stripe of fewer than k shards puts them in the LAST slots: leading
    zero shards leave Horner's Q alone, trailing ones would multiply it.
    ``device=True`` runs the jitted twin on this process's default
    backend, S rounded up to a power of two (zero rows) so the shapes
    stay few; the caller asks ``utils.device.holds_tpu`` — the NumPy
    form is what a process without a chip runs."""
    s, k, w = shards.shape
    if w % 4:
        raise ValueError("shard length must be a multiple of 4")
    words = shards.view(np.uint32)                 # [S, k, W/4]
    if not device:
        p, q = _xor_reduce(np, words), _horner_reduce(np, words, k)
        return p.view(np.uint8), q.view(np.uint8)
    import jax

    rows = 1 << (s - 1).bit_length()
    if rows != s:
        padded = np.zeros((rows, k, w // 4), dtype=np.uint32)
        padded[:s] = words
        words = padded
    p, q = _make_batch_encode_fn(k)(jax.device_put(words))
    return (np.asarray(p)[:s].view(np.uint8),
            np.asarray(q)[:s].view(np.uint8))


# ---------------------------------------------------------------------------
# decode: recover up to two missing shards (host path, degraded only)
# ---------------------------------------------------------------------------

def _q_coeff(i: int, k: int) -> int:
    """Q's coefficient for data shard i: g^(k-1-i) (Horner order)."""
    return gf_pow(2, k - 1 - i)


def recover_stripe(data: list[np.ndarray | None],
                   p: np.ndarray | None, q: np.ndarray | None
                   ) -> list[np.ndarray]:
    """Recover missing data shards. ``data`` is the k-slot stripe with
    ``None`` for lost shards (present arrays all the same padded length);
    ``p``/``q`` are the parity shards or ``None`` if lost too. Returns
    the complete data list. Raises ValueError when more than two shards
    (counting lost parity) are missing — beyond P+Q's budget.

    This is the per-stripe ORACLE; production degraded reads batch all
    affected stripes of a file through :func:`recover_stripes`, which
    the equivalence tests pin to this function."""
    k = len(data)
    missing = [i for i, d in enumerate(data) if d is None]
    lost = len(missing) + (p is None) + (q is None)
    if lost > 2:
        raise ValueError(f"{lost} shards lost, P+Q recovers at most 2")
    if not missing:
        return [d for d in data]  # type: ignore[misc]
    present = next(d for d in data if d is not None) if k > len(missing) \
        else (p if p is not None else q)
    if present is None:
        raise ValueError("nothing to recover from")
    ln = present.shape[0]
    shapes = {arr.shape[0] for arr in (*data, p, q) if arr is not None}
    if len(shapes) > 1:
        raise ValueError(
            f"present shards have unequal padded lengths {sorted(shapes)}; "
            "pad every shard of a stripe to the stripe's shard_len")
    if ln % 4:
        raise ValueError(
            f"shard length {ln} is not a multiple of 4; the u32-packed "
            "GF lanes require stripe_shard_len padding")

    def xor_known(skip: set[int]) -> np.ndarray:
        acc = np.zeros(ln, dtype=np.uint8)
        w = acc.view(np.uint32)
        for i, d in enumerate(data):
            if i not in skip and d is not None:
                w ^= d.view(np.uint32)
        return acc

    if len(missing) == 1:
        i = missing[0]
        if p is not None:
            # d_i = P ^ xor(other data)
            rec = xor_known({i})
            rec.view(np.uint32)[:] ^= p.view(np.uint32)
            out = list(data)
            out[i] = rec
            return out  # type: ignore[return-value]
        # P lost too -> solve from Q: g^(k-1-i)·d_i = Q ^ sum g^..·d_j
        acc = np.zeros(ln, dtype=np.uint8)
        for j, d in enumerate(data):
            if j != i and d is not None:
                acc ^= _gf_mul_bytes(_q_coeff(j, k), d)
        acc ^= q
        out = list(data)
        out[i] = _gf_mul_bytes(gf_inv(_q_coeff(i, k)), acc)
        return out  # type: ignore[return-value]

    # two data shards missing: need both P and Q
    if p is None or q is None:
        raise ValueError("two data shards and a parity shard lost")
    a, b = missing
    ca, cb = _q_coeff(a, k), _q_coeff(b, k)
    # P ^ known = d_a ^ d_b           = s
    # Q ^ known = ca·d_a ^ cb·d_b    = t
    s = xor_known({a, b})
    s.view(np.uint32)[:] ^= p.view(np.uint32)
    t = np.zeros(ln, dtype=np.uint8)
    for j, d in enumerate(data):
        if j not in (a, b) and d is not None:
            t ^= _gf_mul_bytes(_q_coeff(j, k), d)
    t ^= q
    # d_a = (cb·s ^ t) / (ca ^ cb)
    denom_inv = gf_inv(ca ^ cb)
    da = _gf_mul_bytes(denom_inv, _gf_mul_bytes(cb, s) ^ t)
    db = s ^ da
    out = list(data)
    out[a] = da
    out[b] = db
    return out  # type: ignore[return-value]


# ---------------------------------------------------------------------------
# batched decode: every affected stripe of a read in one vectorized solve
# ---------------------------------------------------------------------------

def _gf_mul_const(c: int, x, xp=np):
    """GF(256) multiply of an array by a COMPILE-TIME constant: c = XOR
    of 2^b over its set bits, x·2^b is b applications of xtime, so
    x·c = XOR over set bits of xtime^b(x) — doubling passes only up to
    c's top bit, xor passes only for set bits, no row masks. The decode
    groups stripes by their missing-index pattern exactly so these
    scalars ARE constants (a k-wide code has only ~k²/2 patterns).
    Identical code for the NumPy and jnp backends."""
    if not 0 <= c <= 0xFF:
        raise ValueError(f"GF(256) scalar out of range: {c}")
    if c == 0:
        return xp.zeros_like(x)
    xtime = _xtime_np if xp is np else xtime_device
    acc = None
    cur = x
    b = 0
    while True:
        if c >> b & 1:
            acc = cur if acc is None else acc ^ cur
        b += 1
        if not c >> b:
            return acc
        cur = xtime(cur)


def _solve_group(xp, case_id: int, D, P, Q, ea_inv: int, cb: int,
                 denom_inv: int, k: int):
    """Vectorized P+Q solve over one stripe group homogeneous in
    (k, padded length, erasure case, missing-index pattern).

    D [S, k, W] u32 — data shards, missing slots ZEROED; P/Q [S, W] u32;
    case_id — 0: single loss with P present (d = X = P ^ xor(data)),
    1: single loss solved from Q (d = inv(c)·T, T = Q ^ Horner(data)),
    2: double loss (d_a = inv(ca^cb)·(cb·X ^ T), d_b = X ^ d_a);
    ea_inv/cb/denom_inv — GROUP-CONSTANT scalar coefficients (the
    grouping makes the missing pattern, hence these, uniform).
    Returns (ra, rb); rb only for case 2.

    The case split is load-bearing twice over: a two-dead-node read
    makes every stripe degraded but only ~1/3 doubly-degraded in DATA —
    and constant coefficients let the scalar multiplies skip unset bits
    instead of masking rows (the row-masked form measured ~2x the whole
    solve). Pure xor/xtime work — identical under NumPy and jnp, so the
    device path cannot drift from the oracle-tested host path."""
    if case_id == 0:
        return P ^ _xor_reduce(xp, D), None
    if case_id == 1:
        T = Q ^ _horner_reduce(xp, D, k)
        return _gf_mul_const(ea_inv, T, xp), None
    X = P ^ _xor_reduce(xp, D)
    T = Q ^ _horner_reduce(xp, D, k)
    ra = _gf_mul_const(denom_inv, _gf_mul_const(cb, X, xp) ^ T, xp)
    return ra, X ^ ra


def _xor_reduce(xp, D):
    acc = D[:, 0]
    for i in range(1, D.shape[1]):
        acc = acc ^ D[:, i]
    return acc


def _horner_reduce(xp, D, k: int):
    xtime = _xtime_np if xp is np else xtime_device
    q = D[:, 0]
    for i in range(1, k):
        q = xtime(q) ^ D[:, i]
    return q


@functools.cache
def _make_solve_fn(k: int, case_id: int, ea_inv: int, cb: int,
                   denom_inv: int):
    import jax

    @jax.jit
    def run(D, P, Q):
        import jax.numpy as jnp

        return _solve_group(jnp, case_id, D, P, Q, ea_inv, cb,
                            denom_inv, k)

    return run


def recover_stripes(stripes: list[tuple[list[np.ndarray | None],
                                        np.ndarray | None,
                                        np.ndarray | None]],
                    device: bool = False
                    ) -> list[list[np.ndarray]]:
    """Batched :func:`recover_stripe`: one vectorized GF(256) solve over
    ALL affected stripes of a read instead of a per-stripe host loop
    (which measured 1,398 sequential decodes for a 64 MiB degraded read).

    ``stripes`` is a list of (data, p, q) exactly as recover_stripe takes
    them; every stripe must be within the two-erasure budget (the caller
    pre-filters, as node.runtime does). Returns the recovered data lists
    in order. Stripes are grouped by (width, pow2 length bucket) — CDC
    stripes have near-unique shard lengths, so grouping by EXACT length
    would degenerate to single-stripe batches; zero-padding to the
    bucket is GF-exact (parity of zero-padded shards is the zero-padded
    parity — test_zero_length_and_padding_invariance) and the scatter
    truncates back. Each group solves in one pass: the uniform heavy
    math (P ^ xor(data), Q ^ Horner(data)) runs over a [S, k, W] u32
    stack, and the per-stripe scalar coefficients apply via bit-sliced
    xtime multiplies. ``device=True`` routes the group solve through the
    jitted jnp twin of the same code (TPU present); the default NumPy
    path is the production degraded-read engine."""
    if not stripes:
        return []

    results: list[list[np.ndarray] | None] = [None] * len(stripes)
    groups: dict[tuple[int, int], list[int]] = {}
    true_len: dict[int, int] = {}
    for s, (data, p, q) in enumerate(stripes):
        k = len(data)
        missing = [i for i, d in enumerate(data) if d is None]
        lost = len(missing) + (p is None) + (q is None)
        if lost > 2:
            raise ValueError(
                f"stripe {s}: {lost} shards lost, P+Q recovers at most 2")
        if not missing:
            results[s] = list(data)  # type: ignore[arg-type]
            continue
        if len(missing) == 2 and (p is None or q is None):
            raise ValueError(
                f"stripe {s}: two data shards and a parity shard lost")
        if len(missing) == 1 and p is None and q is None:
            raise ValueError(f"stripe {s}: data shard and both parities "
                             "lost")
        present = [a for a in (*data, p, q) if a is not None]
        lens = {a.shape[0] for a in present}
        if len(lens) != 1:
            raise ValueError(
                f"stripe {s}: present shards have unequal padded lengths "
                f"{sorted(lens)}")
        ln = lens.pop()
        if ln % 4:
            raise ValueError(
                f"stripe {s}: shard length {ln} is not a multiple of 4")
        true_len[s] = ln
        # grain = 1/8 of the length's pow2 ceiling; lengths in an octave
        # are at least half that ceiling, so zero-pad waste stays < 25%
        grain = max(4, 1 << max((ln - 1).bit_length() - 3, 2)) if ln else 4
        bucket = -(-ln // grain) * grain if ln else 4
        a = missing[0]
        b = missing[1] if len(missing) == 2 else -1
        if b >= 0:
            case = 2
        elif p is None:
            case = 1
        else:
            case = 0
        groups.setdefault((k, bucket, case, a, b), []).append(s)

    for (k, bucket, case, a, b), idxs in groups.items():
        S = len(idxs)
        W = bucket // 4
        D = np.zeros((S, k, W), dtype=np.uint32)
        P = np.zeros((S, W), dtype=np.uint32)
        Q = np.zeros((S, W), dtype=np.uint32)
        for r, s in enumerate(idxs):
            data, p, q = stripes[s]
            wn = true_len[s] // 4
            for i, d in enumerate(data):
                if d is not None:
                    D[r, i, :wn] = d.view(np.uint32)
            if case != 1 and p is not None:
                P[r, :wn] = p.view(np.uint32)
            if case != 0 and q is not None:
                Q[r, :wn] = q.view(np.uint32)
        ca = _q_coeff(a, k)
        cb = _q_coeff(b, k) if b >= 0 else 0
        ea_inv = gf_inv(ca)
        denom_inv = gf_inv(ca ^ cb) if b >= 0 else 0

        if device:
            import jax

            ra, rb = _make_solve_fn(k, case, ea_inv, cb, denom_inv)(
                jax.device_put(D), jax.device_put(P), jax.device_put(Q))
            ra = np.asarray(ra)
            rb = None if rb is None else np.asarray(rb)
        else:
            ra, rb = _solve_group(np, case, D, P, Q, ea_inv, cb,
                                  denom_inv, k)

        for r, s in enumerate(idxs):
            data, p, q = stripes[s]
            ln = true_len[s]
            out = list(data)
            out[a] = np.ascontiguousarray(ra[r]).view(np.uint8)[:ln]
            if b >= 0:
                out[b] = np.ascontiguousarray(rb[r]).view(np.uint8)[:ln]
            results[s] = out  # type: ignore[assignment]
    return results  # type: ignore[return-value]
