"""index plane: seconds spent folding the index's sorted runs in the
window — inline on the CAS worker whose flush tripped the merge
(``index.lsi.compactStallS``) plus on the plane's own thread
(``bgCompactS``, where a deployment turns that on) — per GiB acked."""

from plane_totals import index_delta
from program_totals import per_gib


def read(w):
    stall = index_delta(w, "lsi", "compactStallS")
    if stall is None:
        return None
    return per_gib(w, stall + (index_delta(w, "lsi", "bgCompactS") or 0.0))
