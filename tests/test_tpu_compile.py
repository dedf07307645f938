"""The segment-selection kernel compiled for the chip it ships on, at the
shapes it ships with — no chip needed: the TPU's compiler is installed
here and compiles for a described v5e (what interpret mode cannot show:
tiling, VMEM, slices off the sublane grid). Nothing runs, so this says
nothing about results or times. One file on purpose, the topology
described inside a fixture: only the worker that is given this file
loads the TPU library."""

import pytest


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # and cannot be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("region_mib", [16, 64])
def test_select_walk_compiles_for_v5e_at_production_shapes(one_chip,
                                                           region_mib):
    """Three planes in VMEM, the 193-tile window inside a 16x128 block,
    one step a possible segment (513 / 2 049), two outputs."""
    import jax
    import jax.numpy as jnp

    from dfs_tpu.ops.cdc_anchored import (TILE_BYTES, AnchoredCdcParams,
                                          segment_cap)
    from dfs_tpu.ops.select_pallas import (make_select_fn_pallas,
                                           select_window_tiles)

    params = AnchoredCdcParams()
    assert select_window_tiles(params) + 7 * 128 + 127 <= 16 * 128
    m_words = region_mib * 2**20 // 4
    m_tiles = m_words * 4 // TILE_BYTES
    cap = segment_cap(params, m_words)
    assert cap == region_mib * 32 + 1

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = make_select_fn_pallas(params, m_tiles, cap).lower(
        arg((3, m_tiles), jnp.int32), arg((), jnp.int32),
        arg((), jnp.int32), arg((), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_packed_walk_compiles_for_v5e_at_the_production_shape(one_chip):
    """The walk of a packed region (PR 41: an XLA while loop, one turn
    a segment, at most 192 of them, with the table of streams beside
    the three planes) at the engine's one shape: 2 MiB, 128 lanes."""
    import jax
    import jax.numpy as jnp

    from dfs_tpu.fragmenter import cdc_anchored as F
    from dfs_tpu.ops.cdc_anchored import (TILE_BYTES, AnchoredCdcParams,
                                          make_packed_select_fn,
                                          packed_segment_cap)

    params = AnchoredCdcParams()
    region_mib = F._PACK_BYTES // 2**20
    assert region_mib == 2
    m_words, lanes = F._PACK_BYTES // 4, 128
    m_tiles = m_words * 4 // TILE_BYTES
    cap = packed_segment_cap(params, m_words, lanes)
    assert cap == region_mib * 32 + lanes

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = make_packed_select_fn(params, m_tiles, cap, lanes).lower(
        arg((3, m_tiles), jnp.int32), arg((lanes,), jnp.int32),
        arg((lanes,), jnp.int32), arg((), jnp.int32)).compile()
    assert "while" in compiled.as_text()
