"""owner seam at the node: seconds a stream's tee waited at its cap
before taking the next block (``/metrics`` ``ingest.seam.teeWaitS``:
``SidecarFragmenter.chunks_stream``'s ``cond.wait`` — the owner's
replies lag the body by more than 2 x its ``stream_span``), summed over
the nodes, per GiB acked in the window. Nothing on a program without
the counter."""


def read(w):
    if not any("teeWaitS" in n.get("ingest", {}).get("seam", {})
               for n in w.nodes_after):
        return None
    return w.per_gib_put(w.node_delta("ingest", "seam", "teeWaitS"))
