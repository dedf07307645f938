"""owner seam and device walk: a small stream's wait at the owner, from
its last byte there to the dispatch of the packed region that carries
it (``Health.device`` ``packWaitS`` over ``packedStreams``), in ms."""

from program_totals import owner_s


def read(w):
    wait, streams = owner_s(w, "packWaitS"), owner_s(w, "packedStreams")
    return 1000.0 * wait / streams if streams else None
