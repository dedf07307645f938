"""owner seam at the node: seconds the nodes' fragmenter threads spent
on chunks they had in hand — from a chunk of an owner reply (or of an
in-process engine's batch) given to ingest, through the byte budget's
bookkeeping, to the hand-off that carries it to the event loop — with
the waits for placement credit taken out (``/metrics``
``ingest.stalls.seamReplyS``, PR 30), per GiB acked in the window.
Nothing on a program without the stopwatch."""

from program_totals import per_gib, stall_s


def read(w):
    return per_gib(w, stall_s(w, "seamReplyS"))
