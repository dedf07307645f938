"""chunk store: seconds the write pool's workers (``cas-w``) were busy
(``ingest.cas.lanes.w.busyS``, PR 38), per GiB acked in the window, the
nodes together — ``store.cas_busy_s_per_gib`` less the other two lanes.
The put jobs' ``jobS`` is inside it: the difference is the similarity
plane's sketch pass and the job's own frame. Nothing on a program whose
pool does not count by lane."""

from program_totals import per_gib
from put_phases import lane_delta


def read(w):
    return per_gib(w, lane_delta(w, "w", "busyS"))
