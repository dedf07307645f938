"""The sharded steps on the virtual 8-device CPU mesh: sharded results
must equal single-device results exactly."""

import numpy as np
import pytest

from dfs_tpu.parallel.mesh import make_mesh


def test_mesh_axes():
    mesh = make_mesh(8)
    assert mesh.shape == {"dp": 2, "sp": 4}


def test_anchored_sharded_step_matches_oracle():
    """Flagship v3 sharded: pass A (stream-sharded anchors, baked 8-byte
    halo) + pass B (segment lanes sharded) must reproduce the whole-stream
    NumPy oracle spans exactly. Shares the parity harness with the
    driver's multichip dryrun so both validate one contract."""
    from dfs_tpu.parallel.sharded_cdc import anchored_sharded_parity_check

    anchored_sharded_parity_check(make_mesh(8), 8)


def test_sharded_ec_step_matches_oracle():
    """Erasure-parity encode sharded over the 8-device mesh: stripe axis
    data-parallel, parity bit-identical to the NumPy P+Q oracle, psum
    telemetry equals the parity byte total."""
    from dfs_tpu.ops.ec import encode_pq_np
    from dfs_tpu.parallel.mesh import make_mesh
    from dfs_tpu.parallel.sharded_cdc import make_ec_step, shard_ec_inputs

    mesh = make_mesh(8)
    k, ns, ln = 4, 16, 256                 # 16 stripes over 8 devices
    rng = np.random.default_rng(21)
    stripes = rng.integers(0, 256, size=(ns, k, ln), dtype=np.uint8)

    step = make_ec_step(mesh, k)
    p, q, nbytes = step(shard_ec_inputs(
        mesh, stripes.view(np.uint32).reshape(ns, k, ln // 4)))
    p = np.asarray(p).view(np.uint8).reshape(ns, ln)
    q = np.asarray(q).view(np.uint8).reshape(ns, ln)
    for s in range(ns):
        p0, q0 = encode_pq_np(stripes[s])
        assert np.array_equal(p[s], p0), s
        assert np.array_equal(q[s], q0), s
    assert int(nbytes) == 2 * ns * ln


@pytest.mark.slow
def test_anchored_sharded_production_geometry():
    """The sharded anchored step at PRODUCTION shapes — a full 64 MiB
    region, default params, lane_multiple=128 — over the 8-device mesh,
    oracle-checked end to end (VERDICT r4 #4: the toy-shape checks
    leave lane provisioning, halo correctness at real tile counts, and
    the two-anchor planes across device boundaries unverified). The
    fast CI tier keeps the toy shapes; the committed artifact of this
    run is MULTICHIP_SCALE_r05.json (run_multichip_scale.py)."""
    from dfs_tpu.parallel.mesh import make_mesh
    from dfs_tpu.parallel.sharded_cdc import (
        anchored_sharded_production_check)

    rec = anchored_sharded_production_check(make_mesh(8), 8)
    assert rec["chunks"] > 5000
    assert rec["segments"] >= 500
