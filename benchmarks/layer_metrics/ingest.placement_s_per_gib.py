"""node ingest: seconds uploads waited for a placement batch to land
(``ingest.stalls.placementS``), per GiB acked in the window."""


def read(w):
    return w.per_gib_put(w.node_delta("ingest", "stalls", "placementS"))
