"""chunk store: seconds submitted CAS jobs waited for a free worker
(``ingest.cas.queueS``, counted beside ``busyS``), per GiB acked in the
window."""


def read(w):
    if not any("queueS" in n.get("ingest", {}).get("cas", {})
               for n in w.nodes_after):
        return None
    return w.per_gib_put(w.node_delta("ingest", "cas", "queueS"))
