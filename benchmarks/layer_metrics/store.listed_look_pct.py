"""chunk store: of the names that batches looking at the disk asked
about in the window (``has_many`` without ``resident_ok``, index off),
the share answered from ONE listing of their shard directory
(``durability.lookListed``, PR 35) and not by a ``stat`` each
(``lookStats``): how far the directory look is engaged. Nothing on a
program without the counters, or where no such batch arrived."""

from program_totals import share_pct


def read(w):
    if not any("lookListed" in n.get("durability", {})
               for n in w.nodes_after):
        return None
    listed = w.node_delta("durability", "lookListed")
    return share_pct(listed,
                     listed + w.node_delta("durability", "lookStats"))
