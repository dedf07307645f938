"""chunk store: seconds the jobs of the 2-worker latency lane
(``cas-g``: every ``has_many`` — placement's probes, the verify round,
the repair cycle's — and single gets) waited for one of its workers
(``ingest.cas.lanes.g.queueS``, PR 38), per GiB acked in the window, the
nodes together: what a third worker, or a lane of the cycle's own,
could take — ``store.probe_lane_busy_s_per_gib`` is the look itself.
Nothing on a program whose pool does not count by lane."""

from program_totals import per_gib
from put_phases import lane_delta


def read(w):
    return per_gib(w, lane_delta(w, "g", "queueS"))
