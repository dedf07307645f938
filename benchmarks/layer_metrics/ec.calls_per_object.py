"""erasure coding: encode calls (``/metrics`` ``ec.encodeCalls``: one
``encode_pq_batch`` a width bucket) per object encoded (``ec.objects``)
in the window, the nodes together. An object's stripes packed into a
handful of fixed widths read 6-7; a call a stripe would read ~600.
Nothing on a program without the counters, or where no object was
encoded."""


def read(w):
    if not any("encodeCalls" in n.get("ec", {}) for n in w.nodes_after):
        return None
    objects = w.node_delta("ec", "objects")
    return w.node_delta("ec", "encodeCalls") / objects if objects else None
