"""Steps that run sharded over a ('dp','sp') mesh: shard_map, jitted once.

What is here, and how each shards:

- **the anchored chunker, by stream span and by segment lane**
  (:func:`make_anchored_anchor_step`, :func:`make_anchored_step`): pass A's
  byte-granular anchor hash is elementwise, so one stream shards over the
  flattened mesh as overlapping spans with an 8-byte lookback baked into
  each span on the host; pass B's segment lanes are independent, so the
  lane axis shards over the flattened mesh too. No collective on the data
  path; a ``psum`` reduces the chunk count. These two are the driver's
  multi-chip dry run and the two-process test
  (:func:`anchored_sharded_parity_check`, tests/test_multihost.py).
- **the anchored chunker, by window** (:func:`make_anchored_window_anchor_step`,
  :func:`make_anchored_window_step`): what ``--cdc-devices`` serves
  (fragmenter/cdc_anchored_sharded.py) — whole stream windows ride the dp
  axis, each device running the single-device chain on its own window.
- **min-hash sketches** (:func:`make_sketch_step`, dfs_tpu.sim): chunks ride
  dp, one batch row a device.
- **erasure parity** (:func:`make_ec_step`, ops.ec): stripes are independent,
  the stripe axis shards over the flattened mesh; a ``psum`` counts the
  parity bytes.

Contrast with the reference: its scale-out is N JVMs exchanging Base64 JSON
over localhost HTTP (StorageNode.java:226-259); here the same byte-level work
is one SPMD program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# the anchored chunker, sharded
# ---------------------------------------------------------------------------

def make_anchored_anchor_step(mesh: Mesh, params, m_local: int):
    """Sharded **pass A** of the anchored pipeline (ops.cdc_anchored):
    the byte-granular anchor hash is elementwise, so the stream shards over
    the whole mesh as overlapping word spans with a 2-word (8-byte)
    lookback halo — prepared host-side by :func:`shard_anchor_inputs`, so
    no collective is needed at all (the halo is baked into each device's
    span).

    step(spans [n_dev, 2 + m_local] u32) -> tiles
    [3, n_dev * tiles_local] i32 (per TILE_BYTES tile the first two
    anchor byte positions, row 0 < row 1 where present, and the first
    strong position; region-local).
    """
    from dfs_tpu.ops.cdc_anchored import TILE_BYTES, make_anchor_fn

    local_fn = make_anchor_fn(params, m_local)
    tiles_local = m_local * 4 // TILE_BYTES

    def local_step(span):
        # span: [1, 2 + m_local] on this device; positions are local to
        # the span — rebase to region offsets with the device index.
        dev = jax.lax.axis_index("dp") * mesh.shape["sp"] \
            + jax.lax.axis_index("sp")
        tiles = local_fn(span[0])                   # [3, tiles_local]
        return (tiles + jnp.where(tiles < 2**30,
                                  dev * jnp.int32(m_local * 4),
                                  0))[None, :, :]

    shard_fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(("dp", "sp"), None),),
        out_specs=P(("dp", "sp"), None, None),
        check_vma=False,
    )
    return jax.jit(lambda spans: jnp.swapaxes(
        shard_fn(spans), 0, 1).reshape(
        3, mesh.devices.size * tiles_local))


def shard_anchor_inputs(mesh: Mesh, words: np.ndarray, m_local: int):
    """Build the overlapped per-device spans for pass A from a region
    buffer (ops.cdc_anchored.region_buffer layout: 2 lookback words then
    the region). Device d gets words [d*m_local, (d+1)*m_local] plus its
    2-word lookback — the overlap is 8 bytes per device boundary."""
    n_dev = mesh.devices.size
    spans = np.zeros((n_dev, 2 + m_local), dtype=np.uint32)
    for d in range(n_dev):
        lo = d * m_local
        spans[d] = words[lo:lo + 2 + m_local]
    return jax.device_put(
        spans, NamedSharding(mesh, P(("dp", "sp"), None)))


def make_anchored_step(mesh: Mesh, params):
    """Sharded **pass B** of the anchored pipeline: segments are fully
    independent lanes (the 64-byte chunk grid restarts at each segment
    start), so the segment axis shards over the whole mesh with zero halo
    traffic. The region words stay replicated (every device repacks its
    own lanes by dynamic_slice; on a real pod the region would ride dp and
    only lane descriptors shard). The only collective is the chunk-count
    psum.

    step(words [W] u32 — replicated region buffer,
         w_off/sh8/real_blocks/tail_len [s_pad] — sharded over ('dp','sp'))
      -> (cutflag [bps, s_pad] i32 (lanes sharded on axis 1),
          since [bps, s_pad] i32 (same),
          n_chunks [] i32 (global psum))
    """
    from dfs_tpu.ops.cdc_v2 import (gear_candidates_device,
                                    select_cuts_device)
    from dfs_tpu.ops.layout import bswap_transpose
    from dfs_tpu.ops.repack import repack_lanes_xla
    from dfs_tpu.ops.sha256_strip import strip_states, strip_states_xla

    cp = params.chunk
    lane_words = cp.strip_blocks * 16
    on_tpu = all(d.platform == "tpu" for d in mesh.devices.flat)

    def local_step(words, w_off, sh8, real_blocks):
        # XLA repack form inside shard_map (per-shard Pallas dispatch is
        # not worth gating here); ops.repack owns the single definition
        packed = repack_lanes_xla(words, w_off, sh8, lane_words)
        words_t = bswap_transpose(packed)
        cand = gear_candidates_device(words_t, cp)
        cutflag, since = select_cuts_device(cand, real_blocks, cp)
        cf32 = cutflag.astype(jnp.int32)
        use_pallas = on_tpu and words_t.shape[1] % 128 == 0
        states = (strip_states if use_pallas else strip_states_xla)(
            words_t, cf32)
        n = jax.lax.psum(jax.lax.psum(jnp.sum(cf32), "sp"), "dp")
        return cf32, since, states, n

    shard_fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P(("dp", "sp")), P(("dp", "sp")), P(("dp", "sp")),),
        out_specs=(P(None, ("dp", "sp")), P(None, ("dp", "sp")),
                   P(None, ("dp", "sp")), P()),
        check_vma=False,
    )
    return jax.jit(shard_fn)


def shard_anchored_lane_inputs(mesh: Mesh, w_off: np.ndarray,
                               sh8: np.ndarray, real_blocks: np.ndarray):
    """device_put ONLY the pass-B lane descriptor arrays (sharded over
    the flattened mesh) — for callers whose region words are already
    device-resident (the sharded anchored streaming walk stages the
    region once per window and derives the lane tables after pass A)."""
    lane = NamedSharding(mesh, P(("dp", "sp")))
    return (
        jax.device_put(w_off, lane),
        jax.device_put(sh8, lane),
        jax.device_put(real_blocks, lane),
    )


def shard_anchored_inputs(mesh: Mesh, words: np.ndarray, w_off: np.ndarray,
                          sh8: np.ndarray, real_blocks: np.ndarray):
    """device_put anchored pass-B inputs: words replicated, lane
    descriptor arrays sharded over the flattened mesh."""
    return (
        jax.device_put(words, NamedSharding(mesh, P())),
        *shard_anchored_lane_inputs(mesh, w_off, sh8, real_blocks),
    )


def make_anchored_window_anchor_step(mesh: Mesh, params, m_words: int):
    """Window-BATCHED pass A of the anchored ingest walk (round 15):
    ``dp_size`` stream windows ride the mesh's dp axis, each device
    running the whole anchor pass (``ops.cdc_anchored.make_anchor_fn``
    — the single definition, same as the span-sharded
    :func:`make_anchored_anchor_step`) over its OWN window's region
    buffer. No halo, no collective: the 8-byte lookback is baked into
    each window's buffer host-side exactly as the single-device walk
    bakes it.

    Why windows-over-dp instead of spans-over-the-mesh: the ingest
    walk's scaling axis must match its pass-B step (below), and pass B
    is a SEQUENTIAL block scan whose wall-clock is chain-length-bound —
    sharding one window's lanes across devices thins the vectors
    without shortening the chain (measured near-FLAT, ~1.2x at 4
    virtual devices), while running whole windows per device scales
    throughput with the device count (3.85x resident at 4 — the
    CDC_SHARD_r15.json A/B).

    step(words [B, total_words] u32 — B == dp size, rows sharded over
    dp, replicated over sp) -> tiles [B, 3, m_tiles] i32 (per-window
    anchor tables — two kept planes, one strong — window-local
    positions)."""
    from dfs_tpu.ops.cdc_anchored import make_anchor_fn

    local_fn = make_anchor_fn(params, m_words)

    def local_step(words):
        return local_fn(words[0])[None]

    shard_fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P("dp", None),),
        out_specs=P("dp", None, None),
        check_vma=False,
    )
    return jax.jit(shard_fn)


def make_anchored_window_step(mesh: Mesh, params, total_words: int,
                              s_pad: int):
    """Window-BATCHED pass B, INGEST edition (round 15): each device
    runs the whole single-device segment chain — Pallas/XLA repack,
    fused candidates/selection/SHA strip scan, cut compaction, on-device
    FIPS tail finalize (``ops.cdc_anchored.make_anchored_segment_fn`` —
    the ONE definition of that math) — on its OWN stream window,
    returning FINISHED (offset, length, digest) chunk tables. Pass A +
    host segment selection (the carry-threaded ``select_segments``)
    decide each window's lane tables; zero collectives on the data path.

    Two measured dead ends picked this shape (CDC_SHARD_r15.json A/Bs,
    96 MiB stream, 4 virtual devices):

    - pulling only cutflags (:func:`make_anchored_step` with the SHA
      outputs dropped) and hashing payloads on the host: 1.02x — the
      serial host SHA dominated;
    - sharding one window's segment LANES across the mesh with device
      SHA: 1.28x — the strip scan is SEQUENTIAL over blocks, so
      per-device wall time barely moves when only the lane axis thins
      (the resident step alone measured ~1.2x).

    Windows are independent given their carry, and the carry needs only
    pass A + host select — so windows ride dp, and throughput scales
    with devices (3.85x resident at 4) while each window's chain keeps
    its single-device latency.

    step(words [B, total_words] u32 — B == dp size, rows over dp,
         w_off/sh8/real_blocks/tail_len/starts/seg_lens [B, s_pad] i32/
         u32 — same row sharding)
      -> (count [B] i32, q [B, c_max] i32, offs [B, c_max] i32,
          lens [B, c_max] i32, digests [B, c_max, 8] u32)
    — row b is window b's chunk table in stream order.
    ``cap_mode='full'`` (capacities bound the worst case — a streaming
    walk must never need the synchronous overflow redo)."""
    from dfs_tpu.ops.cdc_anchored import make_anchored_segment_fn

    segfn = make_anchored_segment_fn(params, total_words, s_pad,
                                     cap_mode="full")

    def local_step(words, w_off, sh8, real_blocks, tail_len, starts,
                   seg_lens):
        count, q, offs, lens, dig = segfn(
            words[0], w_off[0], sh8[0], real_blocks[0], tail_len[0],
            starts[0], seg_lens[0])
        return (count[None], q[None], offs[None], lens[None], dig[None])

    row = P("dp", None)
    shard_fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(row, row, row, row, row, row, row),
        out_specs=(P("dp"), row, row, row, P("dp", None, None)),
        check_vma=False,
    )
    return jax.jit(shard_fn)


def host_lane_descriptors(data: np.ndarray, params, pad_multiple: int):
    """Host-side segment selection + pass-B lane descriptor encoding for
    a whole stream, shared by the dryrun parity check and the multihost
    test worker. The w_off/sh8/real_blocks layout itself comes from
    ``ops.cdc_anchored.lane_tables_np`` — the ONE host-side mirror of
    the device-side make_descriptor_fn encoding (the sharded ingest
    walk uses the same function per window). Returns (starts, bounds,
    seg_lens, w_off, sh8, real_blocks, s_real)."""
    from dfs_tpu.ops.cdc_anchored import (anchors_np, lane_tables_np,
                                          select_segments)

    n = int(data.shape[0])
    bounds = select_segments(*anchors_np(data, params), n, params)
    starts = np.concatenate([[0], bounds[:-1]])
    seg_lens = bounds - starts
    s_real = starts.shape[0]
    s_pad = -(-s_real // pad_multiple) * pad_multiple
    _, _, w_off, sh8, real_blocks, _ = lane_tables_np(bounds, 0, s_pad)
    return starts, bounds, seg_lens, w_off, sh8, real_blocks, s_real


def expected_segment_cutflags(data: np.ndarray, starts, bounds,
                              params) -> np.ndarray:
    """Per-segment oracle cutflags [bps, s_real] for pass-B verification
    (NumPy candidates + greedy selection per segment)."""
    from dfs_tpu.ops.cdc_v2 import BLOCK, candidates_np, select_cuts_blocks

    bps = params.chunk.strip_blocks
    s_real = len(starts)
    out = np.zeros((bps, s_real), np.int32)
    for i in range(s_real):
        seg = data[int(starts[i]):int(bounds[i])]
        nb = -(-seg.shape[0] // BLOCK)
        pos = np.flatnonzero(candidates_np(seg, params.chunk))
        cuts = select_cuts_blocks(pos, nb, params.chunk)
        out[cuts - 1, i] = 1
    return out


def anchored_sharded_parity_check(mesh: Mesh, n_devices: int) -> None:
    """Run both sharded anchored passes on a tiny stream and assert parity
    with the NumPy oracles — shared by the driver's multichip dryrun
    (__graft_entry__) and the test suite so the two always validate the
    same contract (pass-A tiles == first-anchor-per-tile oracle, pass-B
    cutflags == per-segment selection, psum == population, reconstructed
    spans == whole-stream chunk_spans_anchored_np)."""
    from dfs_tpu.ops.cdc_anchored import (TILE_BYTES, AnchoredCdcParams,
                                          anchor_planes_np, anchors_np,
                                          chunk_spans_anchored_np,
                                          region_buffer)
    from dfs_tpu.ops.cdc_v2 import BLOCK, AlignedCdcParams

    params = AnchoredCdcParams(
        chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                               strip_blocks=64),        # 4 KiB lanes
        seg_min=2048, seg_max=4096, seg_mask=2047, strong_bits=1)

    m_local = 4 * TILE_BYTES // 4                       # 4 tiles per device
    m_words = m_local * n_devices
    n = m_words * 4
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=n, dtype=np.uint8)
    words = np.asarray(region_buffer(data, np.zeros((8,), np.uint8), params,
                                     m_words=m_words))

    # ---- pass A sharded: tiles vs NumPy oracle ----
    astep = make_anchored_anchor_step(mesh, params, m_local)
    tiles = np.asarray(astep(shard_anchor_inputs(mesh, words, m_local)))
    expect_tiles = anchor_planes_np(*anchors_np(data, params),
                                    m_words * 4 // TILE_BYTES)
    if not np.array_equal(tiles, expect_tiles):
        raise AssertionError("sharded anchored pass A tile mismatch")

    # ---- host segment selection (metadata-sized, shared with oracle) ----
    (starts, bounds, seg_lens, w_off, sh8, real_blocks,
     s_real) = host_lane_descriptors(data, params, n_devices)

    # ---- pass B sharded: per-segment cutflags vs oracle ----
    bstep = make_anchored_step(mesh, params)
    cf, since, _states, n_chunks = bstep(*shard_anchored_inputs(
        mesh, words, w_off, sh8, real_blocks))
    cf = np.asarray(cf)
    expect = expected_segment_cutflags(data, starts, bounds, params)
    if not np.array_equal(cf[:, :s_real], expect):
        raise AssertionError("anchored sharded cutflag mismatch")
    if int(n_chunks) != int(cf.sum()):
        raise AssertionError("anchored psum chunk count mismatch")

    # ---- end-to-end span parity vs the whole-stream oracle ----
    spans = []
    for i in range(s_real):
        ln = int(seg_lens[i])
        cuts = np.flatnonzero(cf[:, i]) + 1
        prev = 0
        for c in cuts.tolist():
            end = min(c * BLOCK, ln)
            spans.append((int(starts[i]) + prev * BLOCK,
                          end - prev * BLOCK))
            prev = c
    if spans != chunk_spans_anchored_np(data, params):
        raise AssertionError("anchored sharded spans != oracle spans")


def anchored_sharded_production_check(mesh: Mesh, n_devices: int,
                                      region_bytes: int = 64 * 2**20,
                                      ) -> dict:
    """The parity check above at PRODUCTION geometry: a full 64 MiB
    region, default AnchoredCdcParams (32-128 KiB segments, 128 KiB
    lanes), lane tables padded to lane_multiple=128 — the exact shapes
    the single-chip chain ships with (`__graft_entry__.entry` uses
    production lane_multiple but toy segments; the toy-mesh check uses
    4-tile devices). This exercises what those cannot: lane-table
    provisioning at ~840 real lanes, halo/rebase correctness at 16K
    tiles per device, and the [3, n_tiles] anchor planes across
    device boundaries. Oracle-checked end to end (pass-A tiles, pass-B
    cutflags per segment, psum, reconstructed spans == whole-stream
    oracle). Returns a timing/shape record for the committed artifact
    (wall times; on a virtual CPU mesh all devices share the host, so
    per-step wall time is the honest number — per-device counters would
    fabricate parallelism the harness does not have)."""
    import time

    from dfs_tpu.ops.cdc_anchored import (TILE_BYTES, AnchoredCdcParams,
                                          anchor_planes_np, anchors_np,
                                          chunk_spans_anchored_np,
                                          region_buffer)
    from dfs_tpu.ops.cdc_v2 import BLOCK

    params = AnchoredCdcParams()               # production geometry
    lane_multiple = 128
    n = (region_bytes // TILE_BYTES) * TILE_BYTES
    m_words = n // 4
    if m_words % n_devices:
        raise ValueError("region words must split evenly over devices")
    m_local = m_words // n_devices
    if (m_local * 4) % TILE_BYTES:
        raise ValueError("per-device span must be tile-aligned")

    rng = np.random.default_rng(23)
    data = rng.integers(0, 256, size=n, dtype=np.uint8)
    words = np.asarray(region_buffer(data, np.zeros((8,), np.uint8),
                                     params, m_words=m_words))
    rec: dict = {"region_bytes": n, "n_devices": n_devices,
                 "m_local_words": m_local,
                 "tiles_per_device": m_local * 4 // TILE_BYTES,
                 "params": {"seg_min": params.seg_min,
                            "seg_max": params.seg_max,
                            "strip_blocks": params.chunk.strip_blocks,
                            "lane_multiple": lane_multiple}}

    # ---- pass A sharded at production scale ----
    astep = make_anchored_anchor_step(mesh, params, m_local)
    inp = shard_anchor_inputs(mesh, words, m_local)
    t0 = time.perf_counter()
    tiles = np.asarray(jax.block_until_ready(astep(inp)))
    rec["pass_a_s"] = round(time.perf_counter() - t0, 3)
    kept, strong = anchors_np(data, params)
    expect_tiles = anchor_planes_np(kept, strong,
                                    m_words * 4 // TILE_BYTES)
    if not np.array_equal(tiles, expect_tiles):
        raise AssertionError("production sharded pass A tile mismatch")
    rec["kept_anchors"] = int(kept.shape[0])
    rec["strong_anchors"] = int(strong.shape[0])

    # ---- host selection + production lane tables ----
    (starts, bounds, seg_lens, w_off, sh8, real_blocks,
     s_real) = host_lane_descriptors(data, params, lane_multiple)
    if w_off.shape[0] % n_devices:
        raise AssertionError(
            f"lane table {w_off.shape[0]} not divisible by {n_devices}")
    rec["segments"] = int(s_real)
    rec["lane_table"] = int(w_off.shape[0])

    # ---- pass B sharded at production scale ----
    bstep = make_anchored_step(mesh, params)
    binp = shard_anchored_inputs(mesh, words, w_off, sh8, real_blocks)
    t0 = time.perf_counter()
    cf, since, _states, n_chunks = jax.block_until_ready(bstep(*binp))
    rec["pass_b_s"] = round(time.perf_counter() - t0, 3)
    cf = np.asarray(cf)
    expect = expected_segment_cutflags(data, starts, bounds, params)
    if not np.array_equal(cf[:, :s_real], expect):
        raise AssertionError("production sharded cutflag mismatch")
    if int(n_chunks) != int(cf.sum()):
        raise AssertionError("production sharded psum mismatch")
    rec["chunks"] = int(n_chunks)

    # ---- end-to-end span parity vs the whole-stream oracle ----
    spans = []
    for i in range(s_real):
        ln = int(seg_lens[i])
        cuts = np.flatnonzero(cf[:, i]) + 1
        prev = 0
        for c in cuts.tolist():
            end = min(c * BLOCK, ln)
            spans.append((int(starts[i]) + prev * BLOCK, end - prev * BLOCK))
            prev = c
    if spans != chunk_spans_anchored_np(data, params):
        raise AssertionError("production sharded spans != oracle spans")
    return rec


# ---------------------------------------------------------------------------
# min-hash sketches, sharded — chunks ride dp, one batch row per device
# ---------------------------------------------------------------------------

def make_sketch_step(mesh: Mesh, lanes_a: np.ndarray, lanes_b: np.ndarray,
                     shingle_bytes: int, window_bytes: int,
                     mult: int):
    """Batched **min-hash sketch** step of the similarity plane (round
    21, dfs_tpu.sim): ``dp_size`` chunks ride the mesh's dp axis — the
    same windows-over-dp shape the anchored ingest walk settled on
    (each lane's min is a full reduction over the chunk's shingles, so
    thinning the shingle axis would not shorten any chain; whole chunks
    per device scale throughput with the device count). No halo, no
    collective: a chunk's shingles never cross its row.

    All arithmetic is uint32 with wraparound, matching
    ``dfs_tpu.sim.sketch.sketch_np`` EXACTLY (JAX's 32-bit default is
    the oracle's dtype): rolling polynomial shingle hash over
    ``shingle_bytes`` (static unrolled loop), then per-lane
    ``min(h * a + b)`` with positions past the chunk's real length
    masked to the empty-lane sentinel. The lane permute + mask + min
    runs TILED (``fori_loop`` over position tiles with a running
    ``[n_lanes]`` minimum): the whole ``[n_lanes, n_pos]`` value matrix
    never materializes, each tile's values stay cache-resident through
    their reduce, and the mask folds in as a bitwise OR of a
    per-position penalty (valid -> ``|0``, invalid -> ``|0xFFFFFFFF``
    == the empty sentinel) — ~5x over the naive broadcast-then-reduce
    on the CPU backend, bit-for-bit the same minima.

    step(blocks [G, W] u8 — G a multiple of dp, rows sharded over dp
         (each device sketches G/dp whole chunks per dispatch, vmapped),
         lens [G] i32 — same row sharding)
      -> sketches [G, n_lanes] u32 (row sharding)."""
    a_j = jnp.asarray(lanes_a, dtype=jnp.uint32)
    b_j = jnp.asarray(lanes_b, dtype=jnp.uint32)
    mult_j = jnp.uint32(mult)
    n_lanes = int(a_j.shape[0])
    n_pos = window_bytes - shingle_bytes + 1
    empty = jnp.uint32(0xFFFFFFFF)
    tile = min(512, window_bytes)    # [n_lanes, tile] u32 stays L1-ish
    n_tiles = -(-n_pos // tile)
    pad = n_tiles * tile

    def one(block, ln):
        bb = block.astype(jnp.uint32)
        h = jnp.zeros((n_pos,), jnp.uint32)
        for j in range(shingle_bytes):
            h = h * mult_j + jax.lax.slice_in_dim(bb, j, j + n_pos)
        pen = jnp.where(jnp.arange(n_pos, dtype=jnp.int32)
                        < jnp.maximum(ln - shingle_bytes + 1, 0),
                        jnp.uint32(0), empty)
        hp = jnp.zeros((pad,), jnp.uint32).at[:n_pos].set(h)
        penp = jnp.full((pad,), empty, jnp.uint32).at[:n_pos].set(pen)

        def body(t, acc):
            hs = jax.lax.dynamic_slice(hp, (t * tile,), (tile,))
            ps = jax.lax.dynamic_slice(penp, (t * tile,), (tile,))
            vals = (hs[None, :] * a_j[:, None] + b_j[:, None]) \
                | ps[None, :]
            return jnp.minimum(acc, vals.min(axis=1))

        return jax.lax.fori_loop(
            0, n_tiles, body, jnp.full((n_lanes,), empty, jnp.uint32))

    def local_step(blocks, lns):
        return jax.vmap(one)(blocks, lns)

    shard_fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P("dp", None), P("dp")),
        out_specs=P("dp", None),
        check_vma=False,
    )
    return jax.jit(shard_fn)


# ---------------------------------------------------------------------------
# erasure parity, sharded — stripes are independent; pure data parallelism
# ---------------------------------------------------------------------------

def make_ec_step(mesh: Mesh, k: int):
    """Multi-device erasure-parity encode (ops.ec P+Q over GF(256)).

    Stripes encode independently, so the stripe axis shards over the
    whole flattened ('dp','sp') mesh with ZERO collectives on the data
    path — parity is xor + the xtime funnel per stripe, memory-bound
    VPU work on every device at once. The only collective is the psum'd
    parity-byte telemetry (what the node runtime reports as
    ecParityBytes).

    step(stripes [NS, k, n] u32 — stripe axis sharded)
      -> (p [NS, n] u32, q [NS, n] u32 (same sharding),
          parity_bytes [] i64-ish i32 (global psum))
    """
    from dfs_tpu.ops.ec import pq_horner

    def local_step(stripes):
        p, q = pq_horner(stripes, k, axis=1)
        nbytes = jax.lax.psum(jax.lax.psum(
            jnp.int32(2 * 4) * stripes.shape[0] * stripes.shape[2],
            "sp"), "dp")
        return p, q, nbytes

    shard_fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(("dp", "sp")),),
        out_specs=(P(("dp", "sp")), P(("dp", "sp")), P()),
        check_vma=False,
    )
    return jax.jit(shard_fn)


def shard_ec_inputs(mesh: Mesh, stripes: np.ndarray):
    """device_put EC-step input with stripe-axis sharding."""
    return jax.device_put(
        stripes, NamedSharding(mesh, P(("dp", "sp"))))
