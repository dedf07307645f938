"""device chain: the least time HBM needs for one region of the cell's
object size (``roofline.py``: bytes only, no operations bound) over the
busy time a region took in the traced slice."""

import roofline


def read(w):
    busy = w.busy_s_per_region()
    if busy is None:
        return None
    return roofline.hbm_roofline_pct(
        int(w.traffic["object_bytes"]),
        int(w.config["deployment"]["cdc"]["avg_chunk"]), busy,
        w.device_kind)
