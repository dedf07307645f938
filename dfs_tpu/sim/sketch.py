"""Batched min-hash sketch kernel (dfs_tpu.sim, docs/similarity.md).

A chunk's sketch is ``sketch_size`` uint32 lanes: the rolling
polynomial hash of every ``shingle_bytes``-byte shingle, permuted per
lane (``h * a_k + b_k``, odd ``a_k``), min-reduced over the chunk.
Similar chunks share shingles, so their lane minima agree with
probability equal to their shingle-set Jaccard similarity — grouped
into bands (``dfs_tpu.sim.bands``) that becomes an index lookup.

Two implementations of the SAME math, pinned byte-identical by
tests/test_sim.py:

- :func:`sketch_np` — the NumPy host oracle (uint32 wraparound
  everywhere), the fallback for ragged chunks longer than the compile
  window and for degraded environments;
- the sharded step (``parallel.sharded_cdc.make_sketch_step``) —
  chunks ride the mesh's dp axis, ``rows`` per device per dispatch
  (vmapped inside the shard; the r15 windows-over-dp shape, widened so
  dispatch overhead amortizes), ONE compile shape
  (``fragmenter/sharded_common.fixed_region_bytes``), double-buffered
  ``device_put`` staging with the r15 ``_StagingMeter``
  self-measurement, lazy build + degraded fallback via
  ``sharded_common.ShardedSteps``.
"""

from __future__ import annotations

import collections

import numpy as np

from dfs_tpu.config import SimConfig
from dfs_tpu.fragmenter.cdc_anchored import _REMEASURE_EVERY, _StagingMeter
from dfs_tpu.fragmenter.sharded_common import ShardedSteps, fixed_region_bytes

EMPTY_LANE = 0xFFFFFFFF        # a lane with no shingles (len < q)
_MULT = 0x01000193             # FNV-1a prime — the shingle-hash multiplier
_WINDOW_DEFAULT = 64 * 1024    # one compile shape: the CDC max-chunk bound
_GRANULE = 256
_U64 = (1 << 64) - 1


def lane_constants(n_lanes: int, seed: int = 0x5349) -> tuple[np.ndarray,
                                                              np.ndarray]:
    """Per-lane (a, b) permutation constants, splitmix64-derived from
    ``seed`` — deterministic across hosts (sketches must agree
    cluster-wide), ``a`` forced odd so ``h -> h*a+b`` is a bijection
    on uint32."""
    a = np.empty(n_lanes, np.uint32)
    b = np.empty(n_lanes, np.uint32)
    x = seed & _U64
    for i in range(n_lanes):
        x = (x + 0x9E3779B97F4A7C15) & _U64
        z = x
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _U64
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _U64
        z ^= z >> 31
        a[i] = (z & 0xFFFFFFFF) | 1
        b[i] = (z >> 32) & 0xFFFFFFFF
    return a, b


def sketch_np(data: bytes | np.ndarray, n_lanes: int, shingle_bytes: int,
              lanes_a: np.ndarray, lanes_b: np.ndarray) -> np.ndarray:
    """The host oracle: ``[n_lanes]`` uint32 min-hash lanes of ``data``.
    A chunk shorter than one shingle has no features — every lane is
    :data:`EMPTY_LANE`."""
    arr = data if isinstance(data, np.ndarray) \
        else np.frombuffer(data, dtype=np.uint8)
    n = arr.shape[0] - shingle_bytes + 1
    if n <= 0:
        return np.full(n_lanes, EMPTY_LANE, np.uint32)
    b = arr.astype(np.uint32)
    h = np.zeros(n, np.uint32)
    mult = np.uint32(_MULT)
    for j in range(shingle_bytes):
        h = h * mult + b[j:j + n]
    vals = h[None, :] * lanes_a[:, None] + lanes_b[:, None]
    return vals.min(axis=1)


def band_keys(sketch: np.ndarray, bands: int) -> list[int]:
    """The LSH band keys of one sketch: each band of
    ``n_lanes // bands`` lanes folds (FNV-style, python-int mod 2^64)
    into one 64-bit key, salted by the band index so equal lane values
    in DIFFERENT bands never collide. An empty sketch (no shingles)
    has no keys."""
    if sketch[0] == EMPTY_LANE and (sketch == EMPTY_LANE).all():
        return []
    r = sketch.shape[0] // bands
    keys = []
    for t in range(bands):
        h = ((t + 1) * 0x9E3779B97F4A7C15) & _U64
        for v in sketch[t * r:(t + 1) * r]:
            h = ((h ^ int(v)) * 0x100000001B3) & _U64
        keys.append(h)
    return keys


class SimSketcher(_StagingMeter):
    """The batched sketch frontend: oracle on the host by default,
    chunks-over-dp on the mesh when ``SimConfig.devices > 1`` — with the
    r15 staging discipline (double-buffered ``device_put``, adaptive
    bandwidth self-measurement) and byte-identical output either way."""

    def __init__(self, cfg: SimConfig, window_bytes: int = 0,
                 overlap_min_bw: float = float(1 << 30),
                 force_sharded: bool = False, rows: int = 0) -> None:
        self.cfg = cfg
        self.devices = max(1, int(cfg.devices))
        self.window = fixed_region_bytes(window_bytes, _WINDOW_DEFAULT,
                                         _GRANULE)
        self.lanes_a, self.lanes_b = lane_constants(cfg.sketch_size)
        # rows: chunks sketched PER DEVICE per dispatch (vmapped inside
        # the kernel shard). One row/device leaves the fixed dispatch
        # cost the serial fraction and caps device-axis scaling; the
        # auto pick targets ~256 KiB of window per device per dispatch,
        # which the SIM_r21 bench showed is past the knee. Still ONE
        # compile shape: [devices*rows, window].
        self.rows = max(1, int(rows)) if rows \
            else max(1, (256 * 1024) // self.window)
        self.staging_buffers = 2       # the r15 double-buffer depth
        # force_sharded: bench_sim.py's devices=1 scaling arm — the
        # single-device MESH kernel, so the scaling claim compares the
        # device axis, not kernel-vs-oracle (production never sets it:
        # one device means the oracle is the kernel)
        self._steps = ShardedSteps(self.devices, self._build) \
            if (self.devices > 1 or force_sharded) else None
        self._init_staging(overlap_min_bw)

    @property
    def _unavailable(self) -> bool:
        """Degraded-environment flag — the single fallback predicate
        lives in sharded_common.ShardedSteps (host-only = never
        degraded: there is nothing to fall back from)."""
        return self._steps.unavailable if self._steps else False

    def _build(self, mesh):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from dfs_tpu.parallel.sharded_cdc import make_sketch_step

        step = make_sketch_step(mesh, self.lanes_a, self.lanes_b,
                                self.cfg.shingle_bytes, self.window,
                                _MULT)
        row = NamedSharding(mesh, P("dp", None))
        col = NamedSharding(mesh, P("dp"))
        # warm the compile so no trace lands in the first staging
        # sample (the r06 lesson, via r15)
        g = self.devices * self.rows
        z = jax.device_put(np.zeros((g, self.window), np.uint8), row)
        zl = jax.device_put(np.zeros(g, np.int32), col)
        jax.block_until_ready(step(z, zl))
        return {"step": step, "row": row, "col": col}

    def sketch_one(self, data: bytes) -> np.ndarray:
        return sketch_np(data, self.cfg.sketch_size,
                         self.cfg.shingle_bytes,
                         self.lanes_a, self.lanes_b)

    def sketch_many(self, datas: list[bytes]) -> np.ndarray:
        """Sketches for a batch of chunks, ``[len(datas), sketch_size]``
        uint32 — through the mesh in ``devices * rows``-wide batches
        with double-buffered staging when available; chunks longer than the
        compile window (and every chunk on a degraded env) take the
        oracle. Output is identical either way."""
        n = len(datas)
        out = np.empty((n, self.cfg.sketch_size), np.uint32)
        steps = self._steps.get() if self._steps is not None else None
        if steps is None:
            for i, d in enumerate(datas):
                out[i] = self.sketch_one(d)
            return out
        import time

        import jax

        step, row, col = steps["step"], steps["row"], steps["col"]
        dev_idx = [i for i in range(n) if len(datas[i]) <= self.window]
        for i in range(n):
            if len(datas[i]) > self.window:      # ragged: host oracle
                out[i] = self.sketch_one(datas[i])
        pending: collections.deque = collections.deque()

        def collect() -> None:
            group, fut = pending.popleft()
            res = np.asarray(jax.device_get(fut))
            for j, i in enumerate(group):
                out[i] = res[j]

        gsz = self.devices * self.rows
        for g0 in range(0, len(dev_idx), gsz):
            group = dev_idx[g0:g0 + gsz]
            blocks = np.zeros((gsz, self.window), np.uint8)
            lens = np.zeros(gsz, np.int32)
            for j, i in enumerate(group):
                d = datas[i]
                blocks[j, :len(d)] = np.frombuffer(d, np.uint8)
                lens[j] = len(d)
            measure = (self._staging_bw is None
                       or self._staging_bw < self.overlap_min_bw
                       or self._since_measure >= _REMEASURE_EVERY)
            t0 = time.perf_counter()
            arr = jax.device_put(blocks, row)
            if measure:
                jax.block_until_ready(arr)
                dt = max(time.perf_counter() - t0, 1e-9)
                self._staging_bw = blocks.nbytes / dt
                self._since_measure = 0
                self._staging_samples.append((blocks.nbytes, dt))
            else:
                self._since_measure += 1
            pending.append((group, step(arr, jax.device_put(lens, col))))
            while len(pending) >= self.staging_buffers:
                collect()
        while pending:
            collect()
        return out
