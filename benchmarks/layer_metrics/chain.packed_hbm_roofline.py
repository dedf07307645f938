"""device chain: the least time HBM needs for the PAYLOAD a packed
region carried (``Health.device`` ``packedBytes`` over
``packedRegions``, over the window; ``roofline.region_min_hbm_bytes``:
its bytes read once, a table row a chunk) over the busy time a region
took in the traced slice. The kernels read the whole staging buffer and
a lane a segment, never less than the payload, so the share cannot pass
100 %; it is small where regions carry little.

The slice's regions are counted FROM THE TRACE (``trace_regions.py``:
the SHA strip of pass B runs once a region, packed or not), not from
the owner's counter, which ``run.py`` reads after the profiler's stop
has answered — tens of seconds late in a cell of small regions."""

import json
import subprocess
import sys

import cluster
import roofline
from program_totals import owner_s
from window import HERE

REGION_OP = "%strip_chunk_states"


def slice_regions(w) -> float | None:
    """Regions that started inside the traced slice, or None where
    there is no trace to count them in."""
    work = w.stores.root.parent
    found = sorted((work / "trace").rglob("*.xplane.pb"))
    spans = work / "spans.json"
    if not found or not spans.exists():
        return None
    done = subprocess.run(
        [sys.executable, str(HERE / "trace_regions.py"), str(found[0]),
         str(spans), REGION_OP], env=cluster.child_env("cpu"),
        capture_output=True, text=True, timeout=300)
    if done.returncode != 0:
        return None
    return json.loads(done.stdout.strip().splitlines()[-1])["regions"]


def read(w):
    payload, regions = owner_s(w, "packedBytes"), owner_s(w, "packedRegions")
    if not w.trace or not w.trace.get("busy_s") or not regions:
        return None
    in_slice = slice_regions(w)
    if not in_slice:
        return None
    return roofline.hbm_roofline_pct(
        payload / regions, int(w.config["deployment"]["cdc"]["avg_chunk"]),
        w.trace["busy_s"] / in_slice, w.device_kind)
