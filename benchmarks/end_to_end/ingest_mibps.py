"""User bytes the cluster took in and acked, per second of the window,
in MiB/s (host clock, client side).

Every upload that was acked counts by the share of its own duration
(connect to ack) that lies inside the window: one acked inside counts
whole, one that straddles an end counts for its part, one that failed
counts nothing. Counting whole uploads by the moment of their ack would
measure the same rate in steps of one object — a few per cent of a
window that holds some tens of them.
"""

from window import MIB


def read(w):
    inside = 0.0
    for o in w.session_ops:
        if o.kind == "put" and o.acked and o.phase == "run":
            overlap = min(o.t1, w.t_close) - max(o.t0, w.t_open)
            if overlap > 0:
                inside += o.nbytes * overlap / (o.t1 - o.t0)
    return inside / MIB / w.seconds
