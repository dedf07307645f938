"""chunk store: directory fsyncs issued (``durability.dirBarriers``) per
MiB acked in the window — one per distinct directory of a placement
batch since PR 25, beside ``store.fsyncs_per_mib`` (one per chunk file
made durable). Nothing on a program without the counter."""

from window import MIB


def read(w):
    if not any("dirBarriers" in n.get("durability", {})
               for n in w.nodes_after):
        return None
    mib = w.acked_bytes("put") / MIB
    return w.node_delta("durability", "dirBarriers") / mib if mib else None
