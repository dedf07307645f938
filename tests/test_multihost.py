"""Multi-host compute plane: two real OS processes join one JAX runtime via
jax.distributed and run the anchored chunker's lane-sharded step over the
global 2-process mesh.

Each process contributes 2 virtual CPU devices (4 global). The worker script
asserts its addressable lane shards match the per-segment NumPy oracle and
prints a sentinel.
"""

import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
import numpy as np

coord, pid = sys.argv[1], int(sys.argv[2])
from dfs_tpu.parallel.multihost import init_multihost, global_mesh, process_info
init_multihost(coord, 2, pid)
info = process_info()
assert info["process_count"] == 2 and info["global_devices"] == 4, info

from jax.sharding import NamedSharding, PartitionSpec as P

mesh = global_mesh(dp=2)  # 2 x 2: sp axis spans both processes

def dist(arr, spec):
    sh = NamedSharding(mesh, spec)
    return jax.make_array_from_callback(arr.shape, sh, lambda idx: arr[idx])

# ---- anchored pass B over the global mesh: segment lanes
# shard across processes (zero halo); each process verifies its
# addressable lane shards against the per-segment oracle (descriptor
# encoding + oracle come from the SAME shared helpers the dryrun uses) ----
from dfs_tpu.ops.cdc_anchored import AnchoredCdcParams, region_buffer
from dfs_tpu.ops.cdc_v2 import AlignedCdcParams
from dfs_tpu.parallel.sharded_cdc import (expected_segment_cutflags,
                                          host_lane_descriptors,
                                          make_anchored_step)

aparams = AnchoredCdcParams(
    chunk=AlignedCdcParams(min_blocks=2, avg_blocks=4, max_blocks=16,
                           strip_blocks=64),
    seg_min=2048, seg_max=4096, seg_mask=2047, strong_bits=1)
n = 64 * 1024
adata = np.random.default_rng(77).integers(0, 256, size=n, dtype=np.uint8)
awords = np.asarray(region_buffer(adata, np.zeros((8,), np.uint8), aparams))
starts, bounds, seg_lens, w_off, sh8, rb, s_real = host_lane_descriptors(
    adata, aparams, info["global_devices"])
expect = expected_segment_cutflags(adata, starts, bounds, aparams)

bstep = make_anchored_step(mesh, aparams)
cf, since, states, n_chunks = bstep(
    dist(awords, P()), dist(w_off, P(("dp", "sp"))),
    dist(sh8, P(("dp", "sp"))), dist(rb, P(("dp", "sp"))))
aok = True
for shard in cf.addressable_shards:
    cols = shard.index[1]
    local = np.asarray(shard.data)
    for j, lane in enumerate(range(cols.start or 0, cols.stop)):
        if lane >= s_real:
            aok &= not local[:, j].any()
        else:
            aok &= bool(np.array_equal(local[:, j], expect[:, lane]))
aok &= int(n_chunks) > 0
print(f"ANCHORED{pid}-{'OK' if aok else 'MISMATCH'}", flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


@pytest.mark.slow
def test_two_process_global_mesh(tmp_path):
    coord = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), coord, str(pid)],
            cwd=REPO, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env={"PATH": "/usr/bin:/bin", "PYTHONPATH": str(REPO)},
            text=True)
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"multihost workers hung; partial output: {outs}")
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"ANCHORED{pid}-OK" in out, f"worker {pid} output:\n{out}"
