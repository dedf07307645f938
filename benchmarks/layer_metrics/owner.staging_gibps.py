"""owner seam and device walk: the host-to-device link as the walk
itself measures it (``Health.device`` ``stagedTimedBytes`` over
``stagedTimedS``: the staging buffers of the timed windows over the
seconds their ``device_put`` took to complete), in GiB/s — the number
the walk holds against ``overlap_min_bw`` (1 GiB/s) to decide whether
transfers may overlap."""

from program_totals import owner_s
from window import GIB


def read(w):
    nbytes, spent = owner_s(w, "stagedTimedBytes"), owner_s(w, "stagedTimedS")
    return nbytes / spent / GIB if spent else None
