"""node ingest: the manifest document an ack saves and announces
(``/metrics`` ``ingest.commit.manifestBytes`` over
``ingest.commit.manifests``: ``Ingest._ack``, the JSON as saved), summed
over the nodes, over the window, in KiB: ~250 a 16 MiB object, ~16 000
a 1 GiB image of ~131 000 chunks. Beside ``ingest.commit_ms_per_object``.
Nothing on a program without the counters."""


def read(w):
    if not any("commit" in n.get("ingest", {}) for n in w.nodes_after):
        return None
    count = w.node_delta("ingest", "commit", "manifests")
    return w.node_delta("ingest", "commit", "manifestBytes") / count / 1024 \
        if count else None
