"""Device-mesh construction for the sharded steps (sharded_cdc.py).

The reference's only 'distribution' is point-to-point HTTP between JVMs
(SURVEY.md §2.3, §5.8). The compute plane instead scales over a
``jax.sharding.Mesh`` with two axes:

- ``dp`` (data parallel): independent pieces — stream windows
  (``--cdc-devices``), chunks to sketch — one a device;
- ``sp``: the second axis of the steps that shard ONE piece's spans, lanes
  or stripes over the flattened ('dp','sp') mesh. Nothing is exchanged
  along it: what a device needs of its neighbour's bytes (the anchor
  hash's 8-byte lookback) is baked into its span on the host.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(n_devices: int | None = None, dp: int | None = None) -> Mesh:
    """Mesh with axes ('dp', 'sp') over the first ``n_devices`` devices.

    ``dp`` defaults to 2 when the device count is even and > 1 (so both axes
    are exercised), else 1.
    """
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    if dp is None:
        dp = 2 if n % 2 == 0 and n > 1 else 1
    if n % dp:
        raise ValueError(f"dp={dp} does not divide n={n}")
    arr = np.asarray(devs[:n]).reshape(dp, n // dp)
    return Mesh(arr, axis_names=("dp", "sp"))
