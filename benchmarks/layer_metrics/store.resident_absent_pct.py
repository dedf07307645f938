"""chunk store: of the existence checks the resident set did not answer
"present" in the window (``durability.residentMisses``: the put
pre-check and placement's ``has_chunks``, about a name the set does not
hold), the share it answered "absent" there and then
(``residentAbsent``, counted inside the misses), the nodes together.
A node's set is complete from its boot sweep on — it holds every raw
name on its disk, so a miss is the answer (PR 44) — and the share reads
100; what is under it went on to a ``stat`` or an index lookup: a set
that overflowed its bound, or a listing that could not vouch. Nothing on
a program without the counter, or where no check missed."""

from program_totals import share_pct


def read(w):
    if not any("residentAbsent" in n.get("durability", {})
               for n in w.nodes_after):
        return None
    return share_pct(w.node_delta("durability", "residentAbsent"),
                     w.node_delta("durability", "residentMisses"))
