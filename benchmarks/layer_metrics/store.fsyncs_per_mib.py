"""chunk store: fsync barriers issued (``durability.fsyncs``) per MiB
acked in the window."""

from window import MIB


def read(w):
    mib = w.acked_bytes("put") / MIB
    return w.node_delta("durability", "fsyncs") / mib if mib else None
