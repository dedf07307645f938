"""chunk store: of the chunk files the put jobs linked in the window
(``durability.put.newFiles``), the share whose directory's lock — the
store orders a put against a delete of the same digest with a lock a
shard directory, ``ChunkStore._dir_mu``, PR 42 — was held when the link
asked for it (``linkContended``: a non-blocking take failed, a blocking
one followed), the nodes together. How often two of a node's write
workers, or a worker and a delete, meet on one directory; the seconds
such a meeting costs are ``store.put_link_wait_s_per_gib``. Nothing on
a program without the counter, or where no file was linked."""

from program_totals import share_pct
from put_phases import put_delta


def read(w):
    if not any("linkContended" in (n.get("durability", {}).get("put") or {})
               for n in w.nodes_after):
        return None
    return share_pct(put_delta(w, "linkContended"),
                     put_delta(w, "newFiles"))
