"""Anchored two-level CDC fragmenters (v3) — shift-resilient + TPU-fast.

Strategy (ops.cdc_anchored): byte-granular content anchors choose segment
boundaries; within each segment the aligned 64-byte chunk grid re-anchors
at the segment start, so unaligned insertions only disturb their own
segment (a grid anchored at stream offset 0 loses all downstream dedup
— ops/cdc_anchored.py). Chunking is identical whether the stream is
chunked whole, in any batching, or streamed: regions hand the device a
tile-aligned window with 8 bytes of lookback, and the unfinished tail
segment carries into the next region (ops.cdc_anchored.region_chunks).

The TPU walk is **pipelined**: windows advance by a fixed tile-aligned
stride (region_bytes - seg_max — always far enough that the carry lands
inside the next window), so every window's bytes are known upfront and
window k+1 can be device_put while window k computes; the carry position
chains as a DEVICE scalar (consumed_k - stride), so a multi-region stream
runs with zero host syncs until results are collected. This is the
host->HBM staging overlap the reference's synchronous upload loop
(StorageNode.java:118-189) has no analogue of. Overlap is ADAPTIVE:
the walk measures its own staging bandwidth and serializes transfers
while the link is slower than ``overlap_min_bw`` (see
AnchoredTpuFragmenter.__init__).

A stream too small to fill a window does not walk at all: the engine
lays the small streams that wait side by side in one **packed region**
(``_Packer``; ops.cdc_anchored "packed regions") — one dispatch, every
stream's table its own — so a body of any size is chunked and hashed on
the device and there is one path for every size.

- ``AnchoredCpuFragmenter`` — NumPy oracle path (chunk_file_anchored_np).
- ``AnchoredTpuFragmenter`` — full device pipeline, bounded-memory
  streaming in ~regions of ``region_bytes``.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

import numpy as np

from dfs_tpu.fragmenter.base import Fragmenter
from dfs_tpu.meta.manifest import ChunkRef, Manifest
from dfs_tpu.ops.cdc_anchored import (TILE_BYTES, AnchoredCdcParams,
                                      CutCapacityOverflow,
                                      chunk_file_anchored_np, packed_buffer,
                                      packed_collect, packed_dispatch,
                                      packed_lanes, packed_layout,
                                      packed_next, packed_redo,
                                      region_buffer, region_buffer_size,
                                      region_chunks, region_collect,
                                      region_dispatch, region_spans_np)
from dfs_tpu.ops.cdc_v2 import file_id_from_digests

_REGION_BYTES = 64 * 1024 * 1024
# payload bytes of the ONE packed-region shape (never more than a
# sixteenth of a region): a lone small file pays for staging 2 MiB, not
# a window; a stream it cannot hold walks windows of its own
_PACK_BYTES = 2 * 1024 * 1024
_REMEASURE_EVERY = 8     # overlapped mode re-times every Nth transfer


_touch_fn = None


def _touch(words):
    """A one-element jitted read whose readiness proves the buffer's
    host->device transfer actually finished (see _dispatch_window)."""
    global _touch_fn
    if _touch_fn is None:
        import jax

        _touch_fn = jax.jit(lambda w: w[0])
    return _touch_fn(words)


def _to_u8(data) -> np.ndarray:
    if isinstance(data, np.ndarray):
        return data
    return np.frombuffer(data, dtype=np.uint8)


class _StagingMeter:
    """Staging-bandwidth self-measurement shared by the pipelined walks
    (``AnchoredTpuFragmenter``'s single-device window pipeline, round 6;
    ``ShardedAnchoredCdcFragmenter``'s double-buffered mesh staging,
    round 15): a bounded record of (bytes, seconds) for the transfers
    the walk actually timed, plus the public reset/inspect surface
    benches scope their aggregates with. Bounded: a long-lived node on a
    slow link measures every window forever, and a lifetime average
    would mix samples hours apart."""

    def _init_staging(self, overlap_min_bw: float) -> None:
        import collections

        self.overlap_min_bw = float(overlap_min_bw)
        self._staging_bw: float | None = None
        self._since_measure = _REMEASURE_EVERY  # first window measures
        self._staging_samples: collections.deque[tuple[int, float]] = \
            collections.deque(maxlen=64)

    def staging_observed_bw(self) -> float | None:
        """Aggregate bandwidth of the recent transfers the walk timed
        (up to the deque bound — the same-run link number its e2e rate
        is honestly comparable to); None before any walk. Scope the
        aggregate to one run with :meth:`reset_staging_samples` before
        it (as bench_e2e_stream does)."""
        if not self._staging_samples:
            return None
        return (sum(b for b, _ in self._staging_samples)
                / sum(t for _, t in self._staging_samples))

    def reset_staging_samples(self) -> int:
        """Forget the recorded window-transfer timings (scoping the next
        :meth:`staging_observed_bw` aggregate to the next run); returns
        how many samples were dropped. The public face of the private
        deque — benches must not reach into ``_staging_samples``."""
        n = len(self._staging_samples)
        self._staging_samples.clear()
        return n

    def staging_timed_windows(self) -> int:
        """How many window transfers the current sample set timed."""
        return len(self._staging_samples)


class _AnchoredBase(Fragmenter):
    def __init__(self, params: AnchoredCdcParams | None = None) -> None:
        self.params = params or AnchoredCdcParams()

    def describe(self) -> dict:
        p, c = self.params, self.params.chunk
        return {"kind": "cdc-anchored",
                "chunk": {"min_blocks": c.min_blocks,
                          "avg_blocks": c.avg_blocks,
                          "max_blocks": c.max_blocks,
                          "strip_blocks": c.strip_blocks, "seed": c.seed},
                "seg_min": p.seg_min, "seg_max": p.seg_max,
                "seg_mask": p.seg_mask, "seed": p.seed,
                "strong_min": p.strong_min, "strong_bits": p.strong_bits}

    def manifest(self, data: bytes, name: str,
                 file_id: str | None = None) -> Manifest:
        chunks = tuple(self.chunk(data))
        return Manifest(
            file_id=file_id or file_id_from_digests(
                [c.digest for c in chunks]),
            name=name, size=len(data), fragmenter=self.name, chunks=chunks)


class AnchoredCpuFragmenter(_AnchoredBase):
    """Production CPU path: the C++ core (native/cdc_core.cpp —
    dfs_anchored_spans + batched SHA) when the toolchain is available,
    the NumPy oracle otherwise. Both are bit-identical to
    chunk_file_anchored_np, which tests enforce."""

    name = "cdc-anchored"

    def __init__(self, params: AnchoredCdcParams | None = None,
                 region_bytes: int = _REGION_BYTES) -> None:
        super().__init__(params)
        region_bytes = (int(region_bytes) // TILE_BYTES) * TILE_BYTES
        if region_bytes < 2 * self.params.seg_max:
            raise ValueError("region must hold at least two segments")
        self.region_bytes = region_bytes
        self.stride = region_bytes - self.params.seg_max

    def chunk(self, data: bytes) -> list[ChunkRef]:
        from dfs_tpu.native import native_anchored_spans
        from dfs_tpu.utils.hashing import sha256_hex

        arr = _to_u8(data)
        spans = native_anchored_spans(arr, self.params)
        if spans is not None:
            # digests over zero-copy memoryview slices (sha256_hex
            # passes them straight to OpenSSL's SHA-NI path, which
            # measured 5x the portable C++ batch)
            mv = memoryview(np.ascontiguousarray(arr))
            return [ChunkRef(index=i, offset=int(o), length=int(ln),
                             digest=sha256_hex(mv[o:o + ln]))
                    for i, (o, ln) in enumerate(spans)]
        out = chunk_file_anchored_np(arr, self.params)
        return [ChunkRef(index=i, offset=o, length=ln, digest=dg)
                for i, (o, ln, dg) in enumerate(out)]

    def stream_span(self) -> int | None:
        # one window resident; the carry can reach seg_max behind its base
        return self.region_bytes + self.params.seg_max

    def _region_spans(self, arr: np.ndarray, lookback: np.ndarray,
                      start0: int, final: bool
                      ) -> tuple[list[tuple[int, int]], int]:
        from dfs_tpu.native import native_anchored_spans_region

        out = native_anchored_spans_region(arr, lookback, start0, final,
                                           self.params)
        if out is None:
            return region_spans_np(arr, lookback, start0, final,
                                   self.params)
        spans, consumed = out
        return [(int(o), int(ln)) for o, ln in spans], consumed

    def chunks_stream(self, blocks, store=None):
        """Bounded-memory streaming on the HOST engine: the same
        fixed-stride window walk as the device pipeline (windows advance
        by region_bytes - seg_max; the unfinished tail segment carries),
        run synchronously through dfs_anchored_spans_region (NumPy
        region oracle when the toolchain is absent). Output is identical
        to chunk() for any blocking — the window contract guarantees it.
        Peak memory ~ one window regardless of stream length; the
        reference reads the whole body into one array
        (StorageNode.java:124)."""
        from dfs_tpu.utils.hashing import sha256_hex

        buf = bytearray()
        buf_base = 0                    # absolute offset of buf[0]
        total = 0
        base = 0                        # current window base (absolute)
        start0 = 0                      # carry, window-local
        idx = 0

        def emit(spans: list[tuple[int, int]], b0: int) -> list[ChunkRef]:
            nonlocal idx
            out = []
            for o, ln in spans:
                off = b0 + o
                payload = bytes(buf[off - buf_base:off - buf_base + ln])
                dg = sha256_hex(payload)
                out.append(ChunkRef(index=idx, offset=off, length=ln,
                                    digest=dg))
                idx += 1
                if store is not None:
                    store(dg, payload)
            return out

        def window(n: int, final: bool):
            nonlocal base, start0, buf_base
            lookback = np.zeros((8,), np.uint8)
            take = min(8, base)
            if take:
                lb0 = base - take - buf_base
                lookback[8 - take:] = np.frombuffer(
                    buf, np.uint8, count=take, offset=lb0)
            arr = np.frombuffer(buf, np.uint8, count=n,
                                offset=base - buf_base)
            spans, consumed = self._region_spans(arr, lookback, start0,
                                                 final)
            del arr                     # release before the bytearray trim
            batch = emit(spans, base)
            if not final:
                start0 = consumed - self.stride
                base += self.stride
                keep_from = base - 8
                if keep_from > buf_base:
                    del buf[:keep_from - buf_base]
                    buf_base = keep_from
            return batch

        for blk in blocks:
            buf += blk
            total += len(blk)
            while total - base >= self.region_bytes:
                batch = window(self.region_bytes, final=False)
                if batch:
                    yield batch
        if total - base > 0 or total == 0:
            batch = window(total - base, final=True)
            if batch:
                yield batch

    def manifest_stream(self, blocks, name: str, store=None) -> Manifest:
        return self._manifest_via_chunks_stream(blocks, name, store)


# What a streamed walk can be doing, as exclusive phases of its wall
# time: blocked taking the next block from its caller, staging and
# dispatching a window, collecting one (a packed stream: waiting for
# the region that carries it, of which only that region's
# ``block_until_ready`` is ``deviceWaitS``), suspended at ``yield``
# while the caller takes the batch.
_PHASES = ("inputWaitS", "dispatchS", "collectS", "replyS")
# ``Health.device``'s names for region_collect's cut counts
_CUT_KEYS = ("segments", "strong_cuts", "window_cuts", "forced_cuts")


class _StreamPhases:
    """Where the wall time of an engine's streamed walks went — the
    chip owner's answer to "why was the device idle while a stream was
    open" (``Health.device``). Concurrent streams share one engine,
    hence the lock."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._s = dict.fromkeys(
            (*_PHASES, "deviceWaitS", "streamS", "openS"), 0.0)
        self._streams = self._bytes = self._open = 0
        self._open_since = 0.0

    def add(self, key: str, seconds: float) -> None:
        with self._lock:
            self._s[key] += seconds
            if key in _PHASES:
                self._s["streamS"] += seconds

    def opened(self, now: float) -> None:
        with self._lock:
            self._streams += 1
            if not self._open:
                self._open_since = now
            self._open += 1

    def took(self, nbytes: int) -> None:
        """Bytes a walk has finished with: what a collected window
        consumed, when it is collected (a stream that lasts half a
        minute would land whole in whichever reading saw it close)."""
        with self._lock:
            self._bytes += nbytes

    def closed(self, now: float) -> None:
        with self._lock:
            self._open -= 1
            if not self._open:
                self._s["openS"] += now - self._open_since

    def snapshot(self) -> dict:
        """Counters only: ``streamS`` sums the streams' wall time (the
        four phases sum to it), ``openS`` is the wall time with at
        least one stream open, ``deviceWaitS`` the part of ``collectS``
        blocked on the device's result."""
        with self._lock:
            s = dict(self._s)
            if self._open:      # openS runs on while a stream is open
                s["openS"] += time.monotonic() - self._open_since
            return {**{k: round(v, 6) for k, v in s.items()},
                    "streams": self._streams, "bytes": self._bytes}


class _StreamClock:
    """One stream's phase clock: every instant between construction and
    ``close`` is added to exactly one phase at the next switch, so the
    four phases sum to ``streamS`` by construction."""

    def __init__(self, phases: _StreamPhases) -> None:
        self._phases = phases
        self._phase = "inputWaitS"
        self._t = time.monotonic()
        phases.opened(self._t)

    def to(self, phase: str) -> None:
        now = time.monotonic()
        self._phases.add(self._phase, now - self._t)
        self._phase, self._t = phase, now

    def close(self) -> None:
        self.to(self._phase)
        self._phases.closed(self._t)


# ``Health.device``'s counters of the windows of a streamed walk — what
# a long stream adds (docs/ingest.md "Long streams"), each counted when
# it happens: ``windows`` / ``windowBytes`` at a window's collect (its
# ``end - base``; a packed region is no window), ``tailWindows`` at the
# dispatch of a ``final`` window that has a predecessor, ``stagedTimed``
# / ``stagedTimedBytes`` / ``stagedTimedS`` in ``_dispatch_window``'s
# ``measure`` arm (the transfers the walk waited for and timed),
# ``pendingAtDispatch`` the windows dispatched and not yet collected
# when the next is dispatched (0 ... ``max_inflight``, summed), and
# ``bufferPeakBytes`` the most a stream's rolling buffer held (a peak)
_WINDOW_KEYS = ("windows", "windowBytes", "tailWindows", "stagedTimed",
                "stagedTimedBytes", "stagedTimedS", "pendingAtDispatch",
                "bufferPeakBytes")
# ``Health.device``'s counters of the packed regions (docs/observability.md)
_PACK_KEYS = ("packedRegions", "packedStreams", "packedBytes",
              "packedCapacityBytes", "packWaitS", "packRoundS")


class _PackJob:
    """One small stream waiting for its share of a packed region."""

    __slots__ = ("arr", "lanes", "clocked", "t_in", "table", "error")

    def __init__(self, arr: np.ndarray, lanes: int, clocked: bool) -> None:
        self.arr = arr
        self.lanes = lanes              # lanes it is provisioned
        self.clocked = clocked          # its stream keeps a phase clock
        self.t_in = time.monotonic()
        self.table: list | None = None  # [(offset, length, digest)]
        self.error: BaseException | None = None

    @property
    def done(self) -> bool:
        return self.table is not None or self.error is not None


class _Packer:
    """Group commit of the small streams of one engine. A stream joins
    the queue; whoever finds no region being driven drives: takes from
    the head of the queue what one packed region holds (it closes on
    its bytes or on its lanes), stages, dispatches, collects and hands
    every stream its own table — and goes on until its own stream is
    served, then hands the driving to whoever still waits. So with the
    device free a lone stream goes at once and alone, and while a
    region is in flight whatever arrives gathers and goes together in
    the next: no timer, no linger knob. The waiting threads only sleep;
    one thread at a time stages and collects."""

    def __init__(self, engine: "AnchoredTpuFragmenter",
                 pack_bytes: int) -> None:
        self._engine = engine
        p, mult = engine.params, engine.lane_multiple
        pack_bytes = max(TILE_BYTES,
                         int(pack_bytes) // TILE_BYTES * TILE_BYTES)
        # (region words, lanes): the lanes of one stream that fills the
        # region, rounded up to the compaction tiling
        self.shape = (pack_bytes // 4,
                      -(-packed_lanes(pack_bytes, p) // mult) * mult)
        self.limit = pack_bytes                 # largest stream packed
        self._cond = threading.Condition()
        self._queue: collections.deque[_PackJob] = collections.deque()
        self._driving = False

    def chunk(self, arr: np.ndarray, clock=None
              ) -> list[tuple[int, int, str]]:
        """The chunk table of one stream of at most ``limit`` bytes,
        through a packed region. ``clock``: the stream's phase clock
        (``collectS`` when called)."""
        job = _PackJob(arr, packed_lanes(arr.shape[0], self._engine.params),
                       clock is not None)
        with self._cond:
            self._queue.append(job)
            while self._driving and not job.done:
                self._cond.wait()
            if not job.done:
                self._driving = True
        if not job.done:
            try:
                while not job.done:
                    self._round(clock)
            finally:
                with self._cond:
                    self._driving = False
                    self._cond.notify_all()
        if job.error is not None:
            raise job.error
        return job.table

    def _take(self, batch: list[_PackJob]) -> None:
        """Move the head of the queue into ``batch`` as far as one
        region holds it: the first stream always, the next while its
        bytes and its lanes still fit."""
        m_words, lanes = self.shape
        at = used = 0           # the next stream's offset; lanes given
        with self._cond:
            while self._queue:
                job = self._queue[0]
                if batch and (at + job.arr.shape[0] > m_words * 4
                              or used + job.lanes > lanes):
                    break
                batch.append(self._queue.popleft())
                at = packed_next(at, job.arr.shape[0])
                used += job.lanes

    def _round(self, clock) -> None:
        """One packed region, from the take to every stream's table. A
        failure is handed to the streams that were taken, each its own
        caller's to raise; the queue behind them is untouched."""
        import jax

        eng = self._engine
        m_words, lanes = self.shape
        obs = eng.obs
        span = obs.span if obs is not None \
            else lambda name: contextlib.nullcontext()
        to = clock.to if clock is not None else lambda phase: None
        batch: list[_PackJob] = []
        staged = words = tables = None
        try:
            self._take(batch)
            with obs.request_span("owner.pack") if obs is not None \
                    else contextlib.nullcontext():
                to("dispatchS")
                t0 = time.monotonic()
                streams = [j.arr for j in batch]
                sizes = [int(a.shape[0]) for a in streams]
                offs, _ = packed_layout(sizes)
                with span("owner.dispatch"):
                    staged = packed_buffer(
                        streams, offs, eng.params, m_words,
                        out=eng._pool_take(region_buffer_size(
                            0, eng.params, m_words=m_words)))
                    words = jax.device_put(staged)
                    out = packed_dispatch(words, offs, sizes, eng.params,
                                          lanes, eng.lane_multiple)
                    eng._count_dispatch(out, t0)
                to("collectS")
                with span("owner.collect"):
                    t1 = time.monotonic()
                    jax.block_until_ready(out)
                    # the device's part of every clocked stream's wait;
                    # the rest of it is the queue and the host's work
                    # on this region and the ones before (``packWaitS``,
                    # ``packRoundS``)
                    eng._phases.add("deviceWaitS", (time.monotonic() - t1)
                                    * sum(j.clocked for j in batch))
                    tables, cuts = packed_collect(out, offs, sizes)
                    eng._pool_give(staged)
                    staged = None
                    # what the tight provisioning dropped, at the
                    # worst-case bound; the others' tables stand
                    redone = packed_redo(streams, tables, eng.params,
                                         m_words, lanes, eng.lane_multiple)
                eng._count_packed(batch, t0, m_words * 4, cuts, out, redone)
        except BaseException as e:
            if not batch:
                raise
            tables = None
            for job in batch:
                job.error = e
            if staged is not None and words is not None:
                # back to the pool once its transfer is certainly over
                with contextlib.suppress(Exception):
                    jax.block_until_ready(words)
                    eng._pool_give(staged)
        with self._cond:
            if tables is not None:
                for job, table in zip(batch, tables):
                    job.table = table
            self._cond.notify_all()


class AnchoredTpuFragmenter(_StagingMeter, _AnchoredBase):
    """Device pipeline, region-batched; output is batching-independent."""

    name = "cdc-anchored-tpu"

    def __init__(self, params: AnchoredCdcParams | None = None,
                 region_bytes: int = _REGION_BYTES,
                 lane_multiple: int = 128,
                 max_inflight: int = 2,
                 overlap_min_bw: float = float(1 << 30)) -> None:
        super().__init__(params)
        region_bytes = (int(region_bytes) // TILE_BYTES) * TILE_BYTES
        if region_bytes < 2 * self.params.seg_max:
            raise ValueError("region must hold at least two segments")
        self.region_bytes = region_bytes
        # fixed window stride: far enough that the previous window's carry
        # (>= window_end - seg_max) always lands inside the next window
        self.stride = region_bytes - self.params.seg_max
        self.lane_multiple = int(lane_multiple)
        self.max_inflight = max(1, int(max_inflight))
        # streams of up to ``_packer.limit`` bytes share packed regions
        # of one shape of their own
        self._packer = _Packer(self, min(_PACK_BYTES, region_bytes // 16))
        # recycled host staging buffers, keyed by byte size: fresh 64 MiB
        # allocations measured a large one-time transfer setup cost per
        # buffer on some host->device links; a buffer returns to the pool
        # at collect time, when its transfer has certainly completed
        self._buf_pool: dict[int, list[np.ndarray]] = {}
        # Adaptive staging serialization. Overlapping window k+1's
        # device_put with window k's compute only pays when the transfer
        # is not much slower than the chain, and concurrent big
        # transfers on a slow link can be worse than strictly serial
        # ones. So the walk measures its own staging bandwidth (waiting
        # for the put to complete, which IS the serialization) and only
        # overlaps while the link has proven faster than
        # ``overlap_min_bw``; in overlapped mode every 8th window is
        # re-measured so a degrading link flips the walk back to serial
        # within one region batch. The (bytes, seconds) sample record +
        # its public surface live in _StagingMeter (shared with the
        # sharded anchored walk since round 15).
        self._init_staging(overlap_min_bw)
        # what the device actually did since construction — the chip
        # owner's Health answer carries these (device_stats). Concurrent
        # streams share one fragmenter, hence the lock.
        self._stats_lock = threading.Lock()
        self.regions_dispatched = 0
        self.overflow_redos = 0
        # how the collected regions' segments came to end, as
        # region_collect counts them
        self._cuts = dict.fromkeys(_CUT_KEYS, 0)
        self._packed = dict.fromkeys(_PACK_KEYS, 0)
        self._windows = dict.fromkeys(_WINDOW_KEYS, 0)
        self._phases = _StreamPhases()
        # the first region this engine ran, dispatch to collected: its
        # outputs and when its dispatch began, until it is collected
        self._first_region: tuple | None = None
        # warm the _touch jit once at construction (trace + a trivial
        # 1-element compile): the readiness probe's one-time cost must
        # never be billed to the first staging-bandwidth sample
        import jax

        jax.block_until_ready(_touch(np.zeros(1, np.uint32)))

    # -- pipelined region walk shared by chunk() and manifest_stream() ----

    def _dispatch_window(self, fetch, base: int, n: int, start0,
                         final: bool, ahead: int = 0) -> tuple:
        """device_put window [base, min(n, base+region_bytes)) and dispatch
        the fused chain; returns (base, end, final, out) with out all
        device arrays. ``ahead``: the caller's windows dispatched and not
        yet collected (counted, ``pendingAtDispatch``). ``fetch(off,
        ln)`` must return stream bytes as a u8 array for any span inside
        [base-8, end). ``final`` must be passed
        explicitly — inferring it from end == n would misfire mid-stream
        when the bytes received so far happen to land exactly on a window
        end. Buffer shapes bucket to the next power of two (region_buffer),
        so a multi-window walk compiles once for the full windows plus at
        most once for the shorter tail window."""
        import jax

        t_in = time.monotonic()
        end = min(n, base + self.region_bytes)
        lookback = np.zeros((8,), np.uint8)
        take = min(8, base)
        if take:
            lookback[8 - take:] = fetch(base - take, take)
        staged = region_buffer(
            fetch(base, end - base), lookback, self.params,
            out=self._pool_take(region_buffer_size(end - base, self.params)))
        words = jax.device_put(staged)
        # adaptive staging serialization (see __init__): wait for this
        # transfer to REALLY complete (and time it) unless the link has
        # recently proven fast enough that overlapping transfers is a
        # win. The wait goes through a tiny jitted read of the buffer,
        # NOT block_until_ready on the put result: a backend may defer
        # the put until first use, and then block_until_ready returns
        # immediately, times nothing and serializes nothing.
        measure = (self._staging_bw is None
                   or self._staging_bw < self.overlap_min_bw
                   or self._since_measure >= _REMEASURE_EVERY)
        if measure:
            import time as _time

            # dispatch _touch BEFORE starting the clock: its one-time
            # jit trace/compile (first call per buffer shape) otherwise
            # lands inside dt, inflating the first sample and
            # misclassifying a fast link as slow — which held the first
            # walk serial for 8 windows (ADVICE r5). __init__ also warms
            # the jit machinery once so only the cheap per-shape
            # retrace of `w[0]` remains here.
            fut = _touch(words)
            t0 = _time.perf_counter()
            jax.block_until_ready(fut)
            dt = max(_time.perf_counter() - t0, 1e-9)
            self._staging_bw = staged.nbytes / dt
            self._since_measure = 0
            self._staging_samples.append((staged.nbytes, dt))
        else:
            self._since_measure += 1
        out = region_dispatch(words, end - base, start0, final,
                              self.params, lane_multiple=self.lane_multiple)
        self._count_dispatch(out, t_in)
        with self._stats_lock:
            wn = self._windows
            wn["pendingAtDispatch"] += ahead
            wn["tailWindows"] += final and base > 0
            if measure:
                wn["stagedTimed"] += 1
                wn["stagedTimedBytes"] += staged.nbytes
                wn["stagedTimedS"] += dt
        return base, end, final, out, staged

    def _count_dispatch(self, out, t_in: float) -> None:
        with self._stats_lock:
            if not self.regions_dispatched:
                self._first_region = (out, t_in)
            self.regions_dispatched += 1

    def _count_buffer(self, held: int) -> None:
        """A stream's rolling buffer holds ``held`` bytes, more than it
        has held before."""
        with self._stats_lock:
            if held > self._windows["bufferPeakBytes"]:
                self._windows["bufferPeakBytes"] = held

    def _count_collect(self, out, cuts, window: int | None = None) -> None:
        """A region's table is on the host; ``window``: its bytes, where
        it is a window of a walk (a packed region is none)."""
        with self._stats_lock:
            for key, count in zip(_CUT_KEYS, cuts):
                self._cuts[key] += count
            if window is not None:
                self._windows["windows"] += 1
                self._windows["windowBytes"] += window
            if self._first_region and self._first_region[0] is out:
                self.first_region_s = time.monotonic() \
                    - self._first_region[1]
                self._first_region = None

    def _count_packed(self, batch: list, t0: float, capacity: int, cuts,
                      out, redone: bool) -> None:
        """A packed region's tables are on the host: it counts as any
        region does, and in the six counters of its own (``t0``: when
        its staging began)."""
        self._count_collect(out, cuts)
        now = time.monotonic()
        with self._stats_lock:
            self.overflow_redos += redone
            pk = self._packed
            pk["packedRegions"] += 1
            pk["packedStreams"] += len(batch)
            pk["packedBytes"] += sum(j.arr.shape[0] for j in batch)
            pk["packedCapacityBytes"] += capacity
            pk["packWaitS"] += sum(t0 - j.t_in for j in batch)
            pk["packRoundS"] += now - t0

    def _pool_take(self, size: int) -> np.ndarray | None:
        """A recycled staging buffer of exactly ``size`` bytes."""
        # list.pop() is atomic under the GIL; try/except (not
        # check-then-pop) keeps concurrent walks on a shared fragmenter
        # from racing each other to the last free buffer
        try:
            return self._buf_pool[size].pop()
        except (KeyError, IndexError):
            return None

    def _pool_give(self, staged: np.ndarray) -> None:
        buf = staged.view(np.uint8)
        self._buf_pool.setdefault(buf.shape[0], []).append(buf)

    def _collect_window(self, base: int, end: int, final: bool, out,
                        staged, fetch,
                        chunks: list[ChunkRef], store) -> int:
        """Pull one window's results, append absolute-offset ChunkRefs;
        returns the absolute consumed bound. Verifies span contiguity (the
        device-chained carry has no per-region host check). The window's
        host staging buffer returns to the pool here — its transfer has
        certainly completed once the outputs are readable."""
        expect = chunks[-1].offset + chunks[-1].length if chunks else 0
        try:
            spans, consumed, cuts = region_collect(out)
        except CutCapacityOverflow:
            # this window's content out-chunked the tight provisioning
            # (cut capacity or segment lanes) — redo it alone at the
            # worst-case bound. The device carry (consumed) that later
            # windows chained on is capacity-independent BY CONSTRUCTION
            # (the select scan always runs at the full bound and
            # consumed comes from the full boundary list, ops
            # make_chain_fn), so the rest of the pipeline stays valid.
            with self._stats_lock:
                self.overflow_redos += 1
            lookback = np.zeros((8,), np.uint8)
            take = min(8, base)
            if take:
                lookback[8 - take:] = fetch(base - take, take)
            spans, consumed, cuts = region_chunks(
                fetch(base, end - base), lookback, expect - base, final,
                self.params, lane_multiple=self.lane_multiple,
                cap_mode="full")
        self._count_collect(out, cuts, window=end - base)
        self._pool_give(staged)
        for o, ln, dg in spans:
            off = base + o
            if off != expect:
                raise AssertionError(
                    f"anchored walk discontinuity at {off} (want {expect})")
            expect = off + ln
            c = ChunkRef(index=len(chunks), offset=off, length=ln, digest=dg)
            chunks.append(c)
            if store is not None:
                store(dg, fetch(off, ln).tobytes())
        return base + consumed

    def _walk(self, arr: np.ndarray, store=None) -> list[ChunkRef]:
        n = int(arr.shape[0])
        if n == 0:
            return []
        self._since_measure = _REMEASURE_EVERY  # re-time on window 0:
        # a stale fast estimate from a previous walk must not leave
        # this one overlapped on a link that has since collapsed
        if n <= self._packer.limit:
            return self._packed_refs(arr, store)

        fetch = lambda off, ln: arr[off:off + ln]       # noqa: E731
        chunks: list[ChunkRef] = []
        pending: list[tuple] = []      # [(base, device outputs)]
        start0 = 0                     # int for window 0, device scalar after
        base = 0
        while True:
            if len(pending) >= self.max_inflight:   # cap live windows
                self._collect_window(*pending.pop(0), fetch, chunks, store)
            final = base + self.region_bytes >= n
            win = self._dispatch_window(fetch, base, n, start0, final,
                                        ahead=len(pending))
            pending.append(win)
            if final:
                break
            start0 = win[3][0] - self.stride   # device-resident carry
            base += self.stride
        bound = 0
        for win in pending:
            bound = self._collect_window(*win, fetch, chunks, store)
        if bound != n:
            raise AssertionError(f"anchored walk ended at {bound} != {n}")
        return chunks

    def _packed_refs(self, arr: np.ndarray, store,
                     clock=None) -> list[ChunkRef]:
        """A stream no longer than ``_packer.limit``, through a packed
        region."""
        out = [ChunkRef(index=i, offset=o, length=ln, digest=dg)
               for i, (o, ln, dg) in enumerate(
                   self._packer.chunk(arr, clock))]
        if store is not None:
            for c in out:
                store(c.digest, arr[c.offset:c.offset + c.length].tobytes())
        return out

    def chunk(self, data: bytes) -> list[ChunkRef]:
        return self._walk(_to_u8(data))

    def stream_span(self) -> int | None:
        # up to max_inflight windows dispatched-but-uncollected plus the
        # one being filled; reporting lags by at most their total span
        return self.region_bytes * (self.max_inflight + 1)

    def device_stats(self) -> dict:
        from dfs_tpu.utils.device import device_info

        return {**device_info(),
                "regions": self.regions_dispatched,
                "overflow_redos": self.overflow_redos,
                **self._cuts,
                **{k: round(v, 6) if isinstance(v, float) else v
                   for k, v in (*self._packed.items(),
                                *self._windows.items())},
                **self._phases.snapshot()}

    def chunks_stream(self, blocks, store=None):
        """Bounded-memory PIPELINED streaming: same fixed-stride window
        schedule and device-chained carry as chunk() (the two paths emit
        identical chunks by construction), dispatching each full window as
        soon as its bytes arrive while up to ``max_inflight`` windows
        compute. The host buffer is trimmed to the oldest un-collected
        window's base minus the 8-byte lookback, so peak memory is
        ~(max_inflight + 1) windows regardless of stream length. Yields
        each collected window's ChunkRefs as a batch (the sidecar's
        incremental stream-stream surface)."""
        import jax

        chunks: list[ChunkRef] = []
        buf = bytearray()
        buf_base = 0                   # absolute offset of buf[0]
        total = 0                      # absolute bytes received
        pending: list[tuple] = []
        start0 = 0
        base = 0
        done = False
        taken = 0                      # bytes the collected windows consumed
        peak = 0                       # the most buf has held
        self._since_measure = _REMEASURE_EVERY  # see _walk
        span = self.obs.span if self.obs is not None \
            else lambda name: contextlib.nullcontext()
        clock = _StreamClock(self._phases)

        def fetch(off: int, ln: int) -> np.ndarray:
            if off < buf_base:
                raise AssertionError(
                    f"stream buffer trimmed past {off} (base {buf_base})")
            return np.frombuffer(buf, np.uint8,
                                 count=ln, offset=off - buf_base)

        def trim() -> None:
            nonlocal buf, buf_base
            oldest = pending[0][0] if pending else base
            keep_from = max(buf_base, oldest - 8)
            if keep_from > buf_base:
                del buf[:keep_from - buf_base]
                buf_base = keep_from

        def collect():
            """Collect the oldest window; yields its batch, with the
            time suspended there on the clock as the reply."""
            nonlocal taken
            clock.to("collectS")
            n0 = len(chunks)
            win = pending.pop(0)
            with span("owner.collect"):
                t0 = time.monotonic()
                jax.block_until_ready(win[3])   # the device's part of it
                self._phases.add("deviceWaitS", time.monotonic() - t0)
                bound = self._collect_window(*win, fetch, chunks, store)
            self._phases.took(bound - taken)
            taken = bound
            trim()
            if len(chunks) > n0:
                clock.to("replyS")
                yield chunks[n0:]
            clock.to("dispatchS")
            return bound

        def advance(n_known: int, final_ok: bool):
            """Dispatch every window whose bytes are fully buffered;
            yields a batch per collected window."""
            nonlocal base, start0, done
            while not done:
                full = base + self.region_bytes <= n_known
                final = final_ok and base + self.region_bytes >= n_known
                if not (full or final):
                    return
                if len(pending) >= self.max_inflight:
                    yield from collect()
                with span("owner.dispatch"):
                    win = self._dispatch_window(fetch, base, n_known,
                                                start0, final,
                                                ahead=len(pending))
                pending.append(win)
                trim()
                if final:
                    done = True
                    return
                start0 = win[3][0] - self.stride
                base += self.stride

        try:
            blocks = iter(blocks)
            while True:
                clock.to("inputWaitS")
                blk = next(blocks, None)
                clock.to("dispatchS")
                if blk is None:
                    break
                buf += blk
                total += len(blk)
                if len(buf) > peak:
                    peak = len(buf)
                    self._count_buffer(peak)
                yield from advance(total, final_ok=False)
            if total == 0:
                return
            if total <= self._packer.limit and not pending and base == 0:
                # too small for a window of its own: its share of a
                # packed region (identical output either way)
                clock.to("collectS")
                cl = self._packed_refs(np.frombuffer(buf, np.uint8), store,
                                       clock)
                self._phases.took(total)
                clock.to("replyS")
                yield cl
                return
            yield from advance(total, final_ok=True)
            bound = 0
            while pending:
                bound = yield from collect()
            if bound != total:
                raise AssertionError(
                    f"anchored stream ended at {bound} != {total}")
        finally:
            clock.close()

    def manifest_stream(self, blocks, name: str, store=None) -> Manifest:
        return self._manifest_via_chunks_stream(blocks, name, store)
