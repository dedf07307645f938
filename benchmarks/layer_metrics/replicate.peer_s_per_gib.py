"""replication: seconds inside ``peer.store_chunks`` on the receiving
nodes, per GiB acked in the window (span-seconds: one batch reaches a
peer as overlapping slices)."""

from program_totals import per_gib, span_s


def read(w):
    return per_gib(w, span_s(w, "peer.store_chunks"))
