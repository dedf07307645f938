"""chunk store: seconds the CAS worker threads were busy
(``ingest.cas.busyS``), per GiB acked in the window."""


def read(w):
    return w.per_gib_put(w.node_delta("ingest", "cas", "busyS"))
