"""owner seam at the node: chunks a fragmenter thread handed to its
node's event loop (``/metrics`` ``ingest.seam.chunks``, PR 30) per
crossing it made to hand them over (``ingest.seam.handoffs``: one
``call_soon_threadsafe`` each), over the uploads that ended in the
window, the three nodes together. 1 is a crossing a chunk. Nothing on a
program without the counters, or where no upload crossed."""


def read(w):
    if not any("seam" in n.get("ingest", {}) for n in w.nodes_after):
        return None
    handoffs = w.node_delta("ingest", "seam", "handoffs")
    return w.node_delta("ingest", "seam", "chunks") / handoffs \
        if handoffs else None
