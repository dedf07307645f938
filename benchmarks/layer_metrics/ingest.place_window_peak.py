"""node ingest: the most placement batches any stream had in flight
(``/metrics`` ``ingest.stalls.placeWindowPeak``; the bound is
``ingest.window``, 2), the largest of the nodes. A peak of the node's
life, read at the window's close. It is one of the four bounds' readings
of a long stream (docs/ingest.md "Long streams") and is read with them:
nothing on a program that does not count the tee
(``ingest.seam.teePeakBytes``)."""


def read(w):
    if not any("teePeakBytes" in n.get("ingest", {}).get("seam", {})
               for n in w.nodes_after):
        return None
    peaks = [n["ingest"]["stalls"]["placeWindowPeak"] for n in w.nodes_after
             if "placeWindowPeak" in n.get("ingest", {}).get("stalls", {})]
    return float(max(peaks)) if peaks else None
