"""chunk store: seconds inside ``cas.put_many`` — a put job of the CAS
pool as its caller awaited it (queue + busy) — per GiB acked in the
window. That is the coordinator's own copy of a batch: a peer writes
the copies it receives inside ``peer.store_chunks`` on a plain worker
thread, outside the pool (``replicate.peer_s_per_gib`` holds those)."""

from program_totals import per_gib, span_s


def read(w):
    return per_gib(w, span_s(w, "cas.put_many"))
