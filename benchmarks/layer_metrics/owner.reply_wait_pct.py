"""owner seam and device walk: share of the streams' wall time at the
owner (``Health.device.streamS``) spent suspended at ``yield`` while a
reply was serialised and taken by the node (``replyS``)."""

from program_totals import owner_s, share_pct


def read(w):
    return share_pct(owner_s(w, "replyS"), owner_s(w, "streamS"))
