"""Sharded ANCHORED streaming CDC — the flagship ingest walk over a
device mesh (round 15, ROADMAP item 5).

``AnchoredCpuFragmenter``'s fixed-stride window walk, with whole windows
riding the mesh's **dp axis** — each device runs the full anchored
region chain (``parallel/sharded_cdc.make_anchored_window_anchor_step``
/ ``make_anchored_window_step``, thin shard_map wrappers over the same
``make_anchor_fn`` / ``make_anchored_segment_fn`` the single-device
pipeline compiles) on its OWN window, so a batch of ``devices`` windows
chunks and hashes concurrently:

- **pass A, batched**: the byte-granular anchor hash per window, the
  8-byte lookback baked host-side — no collective. Its [3, m_tiles]
  anchor tables (two kept planes, one strong) are the only thing pulled
  between passes.
- **segment selection on the host** (``ops.cdc_anchored.
  select_segments`` — the SAME function the oracle uses, metadata-sized)
  with the inter-region carry threaded exactly as the single-device walk
  threads it: ``start0 = consumed - stride``, windows advancing by
  ``region_bytes - seg_max``. The carry needs only pass A + select, so
  batching pass B across windows never stalls on it.
- **pass B, batched**: repack, fused candidates/selection/SHA strip
  scan, cut compaction, on-device FIPS tail finalize — each window's
  finished (offset, length, digest) chunk table comes back from its
  device.

Why windows-over-dp: two measured dead ends (the CDC_SHARD_r15.json
A/Bs) — hashing on the host scaled 1.02x at 4 virtual devices (the
serial SHA dominated), and sharding one window's segment LANES over the
mesh scaled 1.28x (the strip scan is sequential over blocks; thinner
lanes don't shorten the chain). Whole windows per device keep each
chain at single-device latency while throughput scales with the device
count (3.85x resident at 4).

Staging is **double-buffered** (``FragmenterConfig.staging_buffers``
batches in flight, default 2): each window's region buffer is filled
and ``jax.device_put`` to its slot device while earlier batches
compute, with the same adaptive staging-bandwidth self-measurement as
the single-device pipeline (a jitted readiness probe times the
transfer; a slow link serializes staging; ``reset_staging_samples``
scopes bench aggregates — see AnchoredTpuFragmenter.__init__ for the
A/B that motivated it). The probe and both passes are compiled at
step-build time so no trace/compile ever lands in the first staging
sample (the r06 lesson).

Output is BYTE-IDENTICAL to ``AnchoredCpuFragmenter`` for every
region/carry geometry by construction — the batched passes run the
same compiled kernels the single-device chain runs (whose anchors,
cuts and digests the oracle pins), and ``select_segments`` is shared
verbatim. Ragged final windows and degraded environments (jax missing,
fewer devices visible than configured) fall back to the identical
NumPy region oracle via the parent's ``_region_spans``.
"""

from __future__ import annotations

import time

import numpy as np

from dfs_tpu.config import FragmenterConfig
from dfs_tpu.fragmenter.cdc_anchored import (_REGION_BYTES,
                                             _REMEASURE_EVERY,
                                             AnchoredCpuFragmenter,
                                             _StagingMeter)
from dfs_tpu.fragmenter.sharded_common import (ShardedSteps,
                                               fixed_region_bytes)
from dfs_tpu.meta.manifest import ChunkRef
from dfs_tpu.ops.cdc_anchored import (TILE_BYTES, AnchoredCdcParams,
                                      lane_tables_np, planes_positions,
                                      region_buffer, region_buffer_size,
                                      segment_cap, select_segments)


_touch_shard_fn = None


def _touch_shard(shard):
    """Readiness probe for one staged window shard: a jitted one-element
    read whose readiness proves the host->device transfer actually
    finished — deferred puts make block_until_ready on the put result a
    no-op on some backends (see AnchoredTpuFragmenter._dispatch_window).
    Runs on the shard's committed device."""
    global _touch_shard_fn
    if _touch_shard_fn is None:
        import jax

        _touch_shard_fn = jax.jit(lambda w: w[0, 0])
    return _touch_shard_fn(shard)


class ShardedAnchoredCdcFragmenter(_StagingMeter, AnchoredCpuFragmenter):
    """AnchoredCpuFragmenter whose streaming region walk batches windows
    over JAX devices. Same ``name``/``describe()`` as the host engine —
    manifests record the *strategy*, and the strategy's output is
    identical (the resume protocol needs no new kind)."""

    def __init__(self, params: AnchoredCdcParams | None = None,
                 frag: FragmenterConfig | None = None,
                 overlap_min_bw: float = float(1 << 30)) -> None:
        frag = frag or FragmenterConfig(devices=2)
        self.devices = max(1, int(frag.devices))
        # compile-shape policy (sharded_common): every full window has
        # one fixed TILE-aligned size; the parent then enforces the
        # two-segment floor (>= 2*seg_max). The DEFAULT window splits
        # the single-device walk's 64 MiB region across the batch, so
        # a whole batch stages the same bytes per step as one
        # single-device window — devices scale throughput, not the
        # node's staging footprint.
        super().__init__(params, region_bytes=fixed_region_bytes(
            frag.region_bytes, _REGION_BYTES // self.devices,
            TILE_BYTES))
        self.staging_buffers = max(1, int(frag.staging_buffers))
        self._m_words = self.region_bytes // 4
        self._total_words = region_buffer_size(
            self.region_bytes, self.params, m_words=self._m_words) // 4
        # worst-case per-window segment count — ONE pass-B compile shape
        self._s_pad = segment_cap(self.params, self._m_words)
        # windows ride dp: one whole window per device
        self._steps = ShardedSteps(self.devices, self._build)
        self._wbuf_pool: list[np.ndarray] = []   # region staging (u8)
        self._init_staging(overlap_min_bw)

    @property
    def _unavailable(self) -> bool:
        """Degraded-environment flag — the single fallback predicate
        lives in sharded_common.ShardedSteps."""
        return self._steps.unavailable

    # ---- device plumbing ----

    def _build(self, mesh):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from dfs_tpu.parallel.sharded_cdc import (
            make_anchored_window_anchor_step, make_anchored_window_step)

        astep = make_anchored_window_anchor_step(mesh, self.params,
                                                 self._m_words)
        bstep = make_anchored_window_step(mesh, self.params,
                                          self._total_words, self._s_pad)
        row = NamedSharding(mesh, P("dp", None))
        devs = list(mesh.devices.flat)
        # Warm every jit that could otherwise bill its trace/compile to
        # the walk's FIRST staging-bandwidth sample (the r06 _touch
        # lesson, extended to the whole step set): the probe and both
        # passes compile here on zero windows of the real shapes, so
        # window 0 of a real stream times only its transfer. The zero
        # shards are kept — they pad the final partial batch of every
        # stream.
        pad = [jax.device_put(np.zeros((1, self._total_words), np.uint32),
                              d) for d in devs]
        jax.block_until_ready(_touch_shard(pad[0]))
        arr = jax.make_array_from_single_device_arrays(
            (self.devices, self._total_words), row, pad)
        jax.block_until_ready(astep(arr))
        zi = np.zeros((self.devices, self._s_pad), np.int32)
        zu = zi.astype(np.uint32)
        jax.block_until_ready(bstep(arr, *jax.device_put(
            (zi, zu, zi, zi, zi, zi), row)))
        return {"astep": astep, "bstep": bstep, "row": row,
                "devs": devs, "pad": pad}

    # ---- the window-batched walk ----

    def chunks_stream(self, blocks, store=None):
        """Bounded-memory BATCHED streaming: the same fixed-stride
        window schedule and carry threading as the parent's host walk
        (identical chunks by the window contract), but windows are
        staged one per device with double-buffered transfers and
        chunk+hash in device-count-wide batches; up to
        ``staging_buffers`` batches stay in flight, so staging and the
        host-side select/emit overlap device compute. The host buffer
        trims to the oldest un-emitted window's base minus the 8-byte
        lookback. Ragged tails and degraded environments take the
        parent's NumPy/native region oracle — identical output."""
        steps = self._steps.get()
        if steps is None:
            yield from super().chunks_stream(blocks, store=store)
            return
        import collections

        import jax

        from dfs_tpu.ops.cdc_v2 import digests_to_hex
        from dfs_tpu.utils.hashing import sha256_hex

        astep, bstep, row = steps["astep"], steps["bstep"], steps["row"]
        devs, pad = steps["devs"], steps["pad"]
        nb = self.devices
        buf = bytearray()
        buf_base = 0                   # absolute offset of buf[0]
        total = 0                      # absolute bytes received
        base = 0                       # next window base to stage
        start0 = 0                     # carry (window-local), host int
        idx = 0
        staged: list[tuple] = []       # [(base, shard, words_host)]
        # [(recs, out)] — recs: per real window (base, start0, consumed)
        bpending: collections.deque = collections.deque()
        self._since_measure = _REMEASURE_EVERY  # re-time on window 0: a
        # stale fast estimate from a previous walk must not leave this
        # one overlapped on a link that has since collapsed

        def fetch(off: int, ln: int) -> np.ndarray:
            if off < buf_base:
                raise AssertionError(
                    f"stream buffer trimmed past {off} (base {buf_base})")
            return np.frombuffer(buf, np.uint8,
                                 count=ln, offset=off - buf_base)

        def emit(chunks, b0: int) -> list[ChunkRef]:
            """``chunks``: (window_offset, length, digest-or-None)
            triples — device windows arrive with their digests computed
            on the mesh; the host-oracle tail hashes here, over
            zero-copy memoryview slices (straight to OpenSSL's SHA-NI
            path). Views MUST be released before this window's trim — a
            live export blocks the bytearray resize."""
            nonlocal idx
            out = []
            mv = memoryview(buf)
            try:
                for o, ln, dg in chunks:
                    off = b0 + o
                    if dg is None or store is not None:
                        lo = off - buf_base
                        if lo < 0:     # a negative slice would silently
                            # wrap to the buffer tail — corrupt payloads
                            raise AssertionError(
                                f"emit past trimmed buffer: {off} < "
                                f"{buf_base}")
                        chunk_mv = mv[lo:lo + ln]
                        if dg is None:
                            dg = sha256_hex(chunk_mv)
                        if store is not None:
                            store(dg, bytes(chunk_mv))
                        chunk_mv.release()
                    out.append(ChunkRef(index=idx, offset=off, length=ln,
                                        digest=dg))
                    idx += 1
            finally:
                mv.release()
            return out

        def trim() -> None:
            # retention floor = the oldest window whose payload bytes
            # may still be read: un-collected batches hold the OLDEST
            # un-emitted windows, so they bound the floor even while
            # newer windows are already staging for the next batch
            nonlocal buf, buf_base
            oldest = base
            if staged:
                oldest = min(oldest, staged[0][0])
            if bpending:
                oldest = min(oldest, bpending[0][0][0][0])
            keep_from = max(buf_base, oldest - 8)
            if keep_from > buf_base:
                del buf[:keep_from - buf_base]
                buf_base = keep_from

        def lookback_at(b: int) -> np.ndarray:
            lb = np.zeros((8,), np.uint8)
            take = min(8, b)
            if take:
                lb[8 - take:] = fetch(b - take, take)
            return lb

        def stage(b: int) -> None:
            """Fill window [b, b+region_bytes)'s region buffer and
            device_put it to its batch-slot device. Carry-independent —
            which is what lets the next batch stage while earlier
            batches compute."""
            # list.pop() is atomic under the GIL; try/except (not
            # check-then-pop) keeps concurrent walks on a shared
            # fragmenter from racing each other to the last free buffer
            # (the parent's _pool_take discipline)
            try:
                wbuf = self._wbuf_pool.pop()
            except IndexError:
                wbuf = None
            words = region_buffer(
                fetch(b, self.region_bytes), lookback_at(b), self.params,
                m_words=self._m_words, out=wbuf)
            shard = jax.device_put(words[None, :], devs[len(staged)])
            # adaptive staging serialization, as the single-device walk
            # (see AnchoredTpuFragmenter.__init__): wait for the
            # transfer to REALLY complete (and time it) unless the link
            # has recently proven fast enough that overlapping pays.
            # The probe is dispatched BEFORE the clock starts so its
            # per-shape retrace never lands in the sample (r06).
            measure = (self._staging_bw is None
                       or self._staging_bw < self.overlap_min_bw
                       or self._since_measure >= _REMEASURE_EVERY)
            if measure:
                fut = _touch_shard(shard)
                t0 = time.perf_counter()
                jax.block_until_ready(fut)
                dt = max(time.perf_counter() - t0, 1e-9)
                self._staging_bw = words.nbytes / dt
                self._since_measure = 0
                self._staging_samples.append((words.nbytes, dt))
            else:
                self._since_measure += 1
            staged.append((b, shard, words.view(np.uint8)))

        def launch() -> None:
            """Turn the staged windows into one in-flight batch: batched
            pass A, per-window host select threading the carry, batched
            pass B dispatched async. A partial final batch pads with the
            kept zero windows (their lane tables stay zero -> count 0)."""
            nonlocal start0
            shards = [s for _, s, _ in staged]
            shards += pad[len(shards):]
            arr = jax.make_array_from_single_device_arrays(
                (nb, self._total_words), row, shards)
            tiles = np.asarray(jax.block_until_ready(astep(arr)))
            recs = []
            hosts = [h for _, _, h in staged]
            w_off = np.zeros((nb, self._s_pad), np.int32)
            sh8 = np.zeros((nb, self._s_pad), np.uint32)
            rb = np.zeros((nb, self._s_pad), np.int32)
            tail = np.zeros((nb, self._s_pad), np.int32)
            starts = np.zeros((nb, self._s_pad), np.int32)
            seg_lens = np.zeros((nb, self._s_pad), np.int32)
            for i, (b, _, _) in enumerate(staged):
                bounds = select_segments(*planes_positions(tiles[i]),
                                         self.region_bytes, self.params,
                                         start0=start0, final=False)
                # lane_tables_np is the ONE host-side mirror of the
                # device descriptor encoding — never inline it
                (starts[i], seg_lens[i], w_off[i], sh8[i], rb[i],
                 tail[i]) = lane_tables_np(bounds, start0, self._s_pad)
                consumed = int(bounds[-1]) if bounds.size else int(start0)
                recs.append((b, int(start0), consumed))
                start0 = consumed - self.stride
            out = bstep(arr, *jax.device_put(
                (w_off, sh8, rb, tail, starts, seg_lens), row))
            # the host staging buffers CANNOT recycle yet: on backends
            # where device memory IS host memory (the CPU mesh), a
            # device_put of a large aligned buffer is zero-copy — the
            # shard ALIASES the pooled array, and refilling it would
            # corrupt this batch under the still-running pass B
            # (observed live: one tail digest flipped). They ride along
            # until collect() has pulled the batch's outputs.
            bpending.append((recs, out, hosts))
            staged.clear()

        def collect() -> list[list[ChunkRef]]:
            """Pull the oldest in-flight batch and emit its windows'
            chunks in stream order, verifying span contiguity against
            the carry chain (mirrors _collect_window — the device chain
            has no other per-window host check)."""
            recs, out, hosts = bpending.popleft()
            counts, q, offs, lens, dig = jax.device_get(out)
            # pass B is done with the batch's (possibly aliasing)
            # shards — now the staging buffers can recycle
            self._wbuf_pool.extend(hosts)
            batches = []
            for i, (b, s0, consumed) in enumerate(recs):
                k = int(counts[i])
                if k > q.shape[1]:
                    raise AssertionError(
                        f"{k} cuts > full capacity {q.shape[1]}")
                if k and (q[i, :k] < 0).any():
                    raise AssertionError(
                        "anchored cut compaction overflowed a tile")
                hexes = digests_to_hex(dig[i, :k])
                chunks = []
                expect = s0
                for o, ln, h in zip(offs[i, :k], lens[i, :k], hexes):
                    if int(o) != expect:
                        raise AssertionError(
                            f"sharded anchored walk discontinuity at "
                            f"{int(o)} (want {expect})")
                    expect = int(o) + int(ln)
                    chunks.append((int(o), int(ln), h))
                if expect != consumed:
                    raise AssertionError(
                        f"sharded window ended at {expect} != {consumed}")
                batch = emit(chunks, b)
                if batch:
                    batches.append(batch)
            return batches

        for blk in blocks:
            buf += blk
            total += len(blk)
            while total - base >= self.region_bytes:
                if not staged:
                    # the in-flight gate sits at batch START, before any
                    # of its windows stage: staging_buffers=1 therefore
                    # means STRICTLY serial staging (no region transfer
                    # overlaps compute — the knob's documented promise),
                    # 2 = double-buffered
                    while len(bpending) >= self.staging_buffers:
                        yield from collect()
                stage(base)
                base += self.stride
                if len(staged) == nb:
                    launch()
                trim()
        if staged:
            while len(bpending) >= self.staging_buffers:
                yield from collect()
            launch()
        while bpending:
            yield from collect()
            trim()
        # ragged tail (or empty stream): the parent's synchronous region
        # oracle — identical output by the window contract
        n_tail = total - base
        if n_tail > 0 or total == 0:
            spans, consumed = self._region_spans(
                fetch(base, n_tail), lookback_at(base), start0, True)
            if base + consumed != total:
                raise AssertionError(
                    f"sharded anchored stream ended at {base + consumed} "
                    f"!= {total}")
            batch = emit([(o, ln, None) for o, ln in spans], base)
            if batch:
                yield batch

    def chunk(self, data) -> list[ChunkRef]:
        # whole-buffer uploads ride the same batched walk (identical
        # output; the degraded path falls through to the host engine)
        if self._steps.get() is None:
            return super().chunk(data)
        return [c for batch in self.chunks_stream([data])
                for c in batch]

    def stream_span(self) -> int | None:
        # up to staging_buffers batches of `devices` windows in flight
        # plus the batch being staged and the window being filled;
        # reporting lags by at most their total span
        return self.region_bytes * (
            self.devices * (self.staging_buffers + 1) + 1)
