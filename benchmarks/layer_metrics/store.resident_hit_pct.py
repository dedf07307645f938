"""chunk store: existence checks that the store's resident set answered
(``durability.residentHits``: placement's ``has_chunks`` and the put
pre-check, on a name this process linked or saw and has not unlinked
since) as a share of those put to it in the window (hits +
``residentMisses``, which went on to a ``stat``). The repair cycle's
looks at the disk are neither. Index off only: with the plane attached
the set is bypassed and the share has no base. Nothing on a program
without the counter."""

from program_totals import share_pct


def read(w):
    if not any("residentHits" in n.get("durability", {})
               for n in w.nodes_after):
        return None
    hits = w.node_delta("durability", "residentHits")
    return share_pct(hits,
                     hits + w.node_delta("durability", "residentMisses"))
