"""Pallas segment-selection walk: the sequential boundary scan on-core.

The XLA form (ops.cdc_anchored.make_select_fn) is a lax.scan of one step
a possible segment (2 049 for a 64 MiB region) whose per-step work is
trivial but whose per-step overhead is not: even unrolled 8-wide it
measured ~1.0-1.6 ms per 64 MiB region on v5e at a third of that length
— second only to the SHA scan in the chain profile, for what is
fundamentally ~50 vector-lane operations a step. This kernel runs the
whole walk inside ONE Pallas program: the three anchor-tile planes DMA
into VMEM once (~1.6 MB), each step reads a 16x128 block from each plane
around its selection window (8-row aligned, the Mosaic sublane-slice
granularity), takes a masked min over the strong plane and a masked max
over the union of the two kept planes, and the boundary list accumulates
in registers via an iota select — no dynamic lane stores, no per-step
dispatch.

Semantics are bit-identical to make_select_fn (the equality tests pin
both, and make_chain_fn only uses this path on TPU after the shapes
check out — everything else falls back to the XLA scan):

    strong  = first strong anchor in byte range [slo-1, hi-1],
              slo = start + strong_min, hi = start + seg_max
    window  = kept anchors in byte range [lo-1, hi-1],
              lo = start + seg_min
    bound   = strong + 1, else last anchor in window + 1, else forced hi
    final n-bound emitted when remaining <= seg_max; for non-final
    regions the tail segment is withheld (carried to the next region).

Capability anchor: replaces the reference's implicit fixed split-point
arithmetic (StorageNode.java:138-155) at the segment level — the walk
is the only sequential stage of the anchored chain.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_ROW_TILE = 8          # Mosaic sublane-slice granularity for [*, 128]
_WIN_ROWS = 16         # 8-row-aligned window start => off < 1024, and
#                        off + 65 <= 16*128 always


def select_window_tiles(params) -> int:
    """Selection-window width in tiles — THE single definition (the XLA
    scan, this kernel, and the support gate all call it, so a window
    change cannot desynchronize them), of both windows: it starts at the
    strong rule's low end, ``start + strong_min``, and so holds the
    kept-anchor window ``[start + seg_min, start + seg_max]`` too
    (strong_min <= seg_min). This many tiles from each of the three
    planes."""
    from dfs_tpu.ops.cdc_anchored import TILE_BYTES

    return (params.seg_max - params.strong_min) // TILE_BYTES + 1


def select_pallas_supported(params) -> bool:
    """The kernel reads a [16, 128] block per step: windows wider than
    one block minus the worst alignment residual (1024) cannot use it.
    Default params: win = 193, and 193 + 7*128 + 127 <= 2048."""
    win = select_window_tiles(params)
    return jax.default_backend() == "tpu" \
        and win + (_ROW_TILE - 1) * 128 + 127 <= _WIN_ROWS * 128


@functools.cache
def make_select_fn_pallas(params, m_tiles: int, cap: int,
                          interpret: bool = False):
    """Compiled: (tiles [3, m_tiles] i32, start0 i32, n i32, final bool)
    -> (bounds [cap] i32, cuts [3] i32) — drop-in twin of
    make_select_fn. The three planes (first/second kept anchor and first
    strong position per tile) are stacked row-wise in one VMEM scratch;
    each step reads the same-aligned [16, 128] block from all three: a
    masked min over the strong plane, a masked max over the union of the
    kept planes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from dfs_tpu.ops.cdc_anchored import (CUT_END, CUT_FORCED, CUT_STRONG,
                                          CUT_WINDOW, TILE_BYTES)

    win = select_window_tiles(params)
    seg_min = params.seg_min
    seg_max = params.seg_max
    strong_min = params.strong_min
    # padded tile count: the walk's last window may start past m_tiles
    # (start approaches n); sentinels there never select. Rounded so the
    # [R, 128] view is whole and a 16-row read at the last window fits.
    t0_max = m_tiles + strong_min // TILE_BYTES + 1
    need = t0_max + win + _WIN_ROWS * 128 + _ROW_TILE * 128
    m_pad = -(-need // 1024) * 1024
    rows = m_pad // 128        # multiple of 8: plane 1 stays row-aligned
    cap_pad = -(-cap // 128) * 128

    def kernel(scal_ref, tiles_hbm, out_ref, cuts_ref, tiles_vmem, sem):
        cp = pltpu.make_async_copy(tiles_hbm, tiles_vmem, sem)
        cp.start()
        cp.wait()
        start0 = scal_ref[0]
        n = scal_ref[1]
        final = scal_ref[2]

        col = jax.lax.broadcasted_iota(jnp.int32, (_WIN_ROWS, 128), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (_WIN_ROWS, 128), 0)
        lane = jax.lax.iota(jnp.int32, cap_pad)
        lane3 = jax.lax.iota(jnp.int32, 128)

        def body(i, carry):
            start, done, acc, cuts = carry
            slo = start + strong_min
            lo = start + seg_min
            hi = start + seg_max
            t0 = (slo - 1) // TILE_BYTES
            r0 = (t0 // 128 // _ROW_TILE) * _ROW_TILE
            r0 = pl.multiple_of(r0, _ROW_TILE)
            g = (row + r0) * 128 + col            # global tile index
            in_win = (g >= t0) & (g <= t0 + (win - 1))
            last = jnp.int32(-1)
            for plane in (0, 1):                  # first, second kept
                rr = pl.multiple_of(r0 + plane * rows, _ROW_TILE)
                val = tiles_vmem[pl.ds(rr, _WIN_ROWS), :]
                ok = in_win & (val >= lo - 1) & (val <= hi - 1)
                last = jnp.maximum(last, jnp.max(jnp.where(ok, val, -1)))
            rr = pl.multiple_of(r0 + 2 * rows, _ROW_TILE)
            val = tiles_vmem[pl.ds(rr, _WIN_ROWS), :]
            ok = in_win & (val >= slo - 1) & (val <= hi - 1)
            first = jnp.min(jnp.where(ok, val, 2**30))
            strong = first < 2**30
            kind = jnp.where(strong, CUT_STRONG,
                             jnp.where(last >= 0, CUT_WINDOW, CUT_FORCED))
            b = jnp.where(strong, first + 1,
                          jnp.where(last >= 0, last + 1, hi))
            fin = (n - start <= seg_max).astype(jnp.int32)
            b = jnp.where(fin == 1, n, b)
            kind = jnp.where(fin == 1, CUT_END, kind)
            emit = (done == 0) & ((fin == 0) | (final == 1))
            out = jnp.where(emit, b, -1)
            acc = jnp.where(lane == i, out, acc)
            cuts = cuts + (lane3 == jnp.where(emit, kind, -1)).astype(
                jnp.int32)
            start = jnp.where(emit, b, start)
            return start, done | fin, acc, cuts

        _, _, acc, cuts = jax.lax.fori_loop(
            0, cap, body,
            (start0, jnp.int32(0),
             jnp.full((cap_pad,), -1, jnp.int32),
             jnp.zeros((128,), jnp.int32)))
        out_ref[...] = acc
        cuts_ref[...] = cuts

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)],
        scratch_shapes=[pltpu.VMEM((3 * rows, 128), jnp.int32),
                        pltpu.SemaphoreType.DMA],
    )

    @jax.jit
    def run(tiles, start0, n, final):
        tiles_p = jnp.concatenate(
            [tiles, jnp.full((3, m_pad - m_tiles), 2**30, jnp.int32)],
            axis=1).reshape(3 * rows, 128)
        scal = jnp.stack([start0.astype(jnp.int32),
                          jnp.int32(n),
                          final.astype(jnp.int32)])
        out, cuts = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((cap_pad,), jnp.int32),
                       jax.ShapeDtypeStruct((128,), jnp.int32)],
            interpret=interpret,
        )(scal, tiles_p)
        return out[:cap], cuts[:3]

    return run
