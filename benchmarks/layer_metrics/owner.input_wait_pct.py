"""owner seam and device walk: share of the streams' wall time at the
owner (``Health.device.streamS``) spent blocked on the next block from
the node (``inputWaitS``)."""

from program_totals import owner_s, share_pct


def read(w):
    return share_pct(owner_s(w, "inputWaitS"), owner_s(w, "streamS"))
