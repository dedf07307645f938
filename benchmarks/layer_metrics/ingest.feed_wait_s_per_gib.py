"""node ingest: seconds the body feeder waited to hand a block to the
fragmenter thread (``ingest.stalls.feedWaitS``: the fragmenter side is
not draining), per GiB acked in the window."""

from program_totals import per_gib, stall_s


def read(w):
    return per_gib(w, stall_s(w, "feedWaitS"))
