"""device chain: the least time HBM needs for the MEAN WINDOW of the
streamed walks (``Health.device`` ``windowBytes`` over ``windows``, over
the window; ``roofline.region_min_hbm_bytes``: its bytes read once, a
table row a chunk) over the busy time a region took in the traced
slice — the chain's share of its floor at the shape a long stream
dispatches, where ``chain.hbm_roofline`` takes a region to be the
cell's ``object_bytes``. The kernels read every byte of a window at
least once, so the share cannot pass 100 %.

The slice's regions are counted from the trace, as
``chain.packed_hbm_roofline`` counts them (``trace_regions.py``: the SHA
strip's events that start inside the slice, one a region)."""

import roofline
from program_totals import owner_s
from window import load_by_name


def slice_regions(w):
    return load_by_name("layer_metrics",
                        "chain.packed_hbm_roofline").slice_regions(w)


def read(w):
    nbytes, windows = owner_s(w, "windowBytes"), owner_s(w, "windows")
    if not w.trace or not w.trace.get("busy_s") or not windows:
        return None
    in_slice = slice_regions(w)
    if not in_slice:
        return None
    return roofline.hbm_roofline_pct(
        nbytes / windows, int(w.config["deployment"]["cdc"]["avg_chunk"]),
        w.trace["busy_s"] / in_slice, w.device_kind)
