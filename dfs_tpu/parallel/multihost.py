"""Multi-host compute plane: one SPMD program across processes/hosts.

The reference scales out only via point-to-point HTTP between JVMs on one
machine (StorageNode.java:227 hardwires localhost). This framework has two
planes (SURVEY.md §5.8):

- **storage plane** (dfs_tpu.comm): TCP/DCN between storage nodes — explicit
  peers, works anywhere;
- **compute plane** (this module + dfs_tpu.parallel.sharded_cdc): JAX SPMD.
  Within a host/pod-slice, collectives ride ICI; across hosts,
  ``jax.distributed`` stitches processes into one global device mesh and XLA
  routes inter-host collective legs over DCN — the role NCCL/MPI plays in
  GPU frameworks, with zero bespoke networking code here.

``init_multihost`` + ``global_mesh`` are the entire API: after init, the
steps of ``dfs_tpu.parallel.sharded_cdc`` work unchanged on the global mesh
(tests/test_multihost.py runs ``make_anchored_step`` over two processes —
its lanes and its ``psum`` cross the process boundary).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def init_multihost(coordinator: str, num_processes: int,
                   process_id: int) -> None:
    """Join this process into a multi-host JAX runtime.

    coordinator: "host:port" of process 0 (any reachable port). Safe to call
    once per process before any backend use. Single-process callers skip this
    entirely — everything below degrades to the local device set.
    """
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def global_mesh(dp: int | None = None) -> Mesh:
    """('dp','sp') mesh over the *global* device set (all hosts). Mirrors
    parallel.mesh.make_mesh but over jax.devices() post-initialize, keeping
    each host's local devices contiguous along sp."""
    devs = jax.devices()
    n = len(devs)
    if dp is None:
        dp = 2 if n % 2 == 0 and n > 1 else 1
    if n % dp:
        raise ValueError(f"dp={dp} does not divide global device count {n}")
    arr = np.asarray(devs).reshape(dp, n // dp)
    return Mesh(arr, axis_names=("dp", "sp"))


def process_info() -> dict:
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }
