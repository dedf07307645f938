"""replication: seconds spent in ``upload.replicate`` (the histogram's
``_sum`` on the Prometheus page), per GiB acked in the window."""


def read(w):
    return w.per_gib_put(w.prom_delta(
        'dfs_latency_seconds_sum{name="upload.replicate"}'))
