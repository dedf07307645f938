"""chunk store: names that a batch which had to look at the disk —
``has_many`` without ``resident_ok``, index off: the repair cycle's
probe of every name a peer should hold — looked for by a ``stat``
(``durability.lookStats``, PR 35), per MiB acked in the window, the
three nodes together. The names such a batch answered from one listing
of their directory are ``lookListed`` (``store.listed_look_pct``).
Index on, no such look is made. Nothing on a program without the
counter."""

from window import MIB


def read(w):
    if not any("lookStats" in n.get("durability", {})
               for n in w.nodes_after):
        return None
    mib = w.acked_bytes("put") / MIB
    return w.node_delta("durability", "lookStats") / mib if mib else None
