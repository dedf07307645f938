"""chunk store: seconds the 2-worker latency lane's workers (``cas-g``)
spent on their jobs — the look itself of every ``has_many``, and single
gets (``ingest.cas.lanes.g.busyS``, PR 38) — per GiB acked in the
window, the nodes together: what only fewer questions would take.
Nothing on a program whose pool does not count by lane."""

from program_totals import per_gib
from put_phases import lane_delta


def read(w):
    return per_gib(w, lane_delta(w, "g", "busyS"))
