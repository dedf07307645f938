"""node ingest: seconds the fragmenter waited for placement credit
(``/metrics`` ``ingest.stalls.creditS``), per GiB acked in the window."""


def read(w):
    return w.per_gib_put(w.node_delta("ingest", "stalls", "creditS"))
