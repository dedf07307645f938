"""Produce MULTICHIP_SCALE_r{N}.json: the sharded anchored step at
PRODUCTION geometry (full 64 MiB region, default params,
lane_multiple=128) over an n-device mesh, oracle-checked end to end (the
toy-shape dryrun leaves lane provisioning and halo correctness at real
tile counts unverified).

Usage: python run_multichip_scale.py [out.json] [n_devices]
The mesh is the machine's real devices; ``JAX_PLATFORMS=cpu`` asks for a
virtual CPU mesh by name (fresh process — the split must precede backend
init, same as __graft_entry__.dryrun_multichip).
"""

from __future__ import annotations

import json
import sys


def main() -> int:
    out = sys.argv[1] if len(sys.argv) > 1 else "MULTICHIP_SCALE_r05.json"
    n_devices = int(sys.argv[2]) if len(sys.argv) > 2 else 8

    from __graft_entry__ import ensure_devices
    from dfs_tpu.utils.device import device_info, enable_compile_cache

    ensure_devices(n_devices)
    enable_compile_cache()

    from dfs_tpu.parallel.mesh import make_mesh
    from dfs_tpu.parallel.sharded_cdc import (
        anchored_sharded_production_check)

    rec = anchored_sharded_production_check(make_mesh(n_devices), n_devices)
    rec["ok"] = True
    rec["device"] = device_info()
    rec["scope"] = ("oracle parity at production shapes is the claim; on "
                    "a virtual CPU mesh (device.platform == 'cpu') wall "
                    "times are host-bound and say nothing about ICI")
    with open(out, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
