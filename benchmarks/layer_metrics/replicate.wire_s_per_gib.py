"""replication: seconds inside the senders' ``rpc.store_chunks`` less the
seconds inside the receivers' ``peer.store_chunks`` — wire, framing and
loop wait, call by call — per GiB acked in the window."""

from program_totals import per_gib, span_s


def read(w):
    rpc, peer = span_s(w, "rpc.store_chunks"), span_s(w, "peer.store_chunks")
    return per_gib(w, None if rpc is None else rpc - peer)
