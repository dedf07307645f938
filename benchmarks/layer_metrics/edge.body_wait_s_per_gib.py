"""http edge: seconds the body feeder waited for the next block from the
socket (``ingest.stalls.bodyWaitS``: the client, or TCP backpressure),
per GiB acked in the window."""

from program_totals import per_gib, stall_s


def read(w):
    return per_gib(w, stall_s(w, "bodyWaitS"))
