"""owner seam at the node: the most bytes any stream's tee held at a
node (``/metrics`` ``ingest.seam.teePeakBytes``: the rolling buffer
``SidecarFragmenter.chunks_stream`` slices the owner's replies out of;
capped at 2 x the owner's ``stream_span`` = 384 MiB), the largest of
the nodes, in MiB. A peak of the node's life, read at the window's
close: the preload is in it. Nothing on a program without the counter."""

from window import MIB


def read(w):
    peaks = [n["ingest"]["seam"]["teePeakBytes"] for n in w.nodes_after
             if "teePeakBytes" in n.get("ingest", {}).get("seam", {})]
    return max(peaks) / MIB if peaks else None
