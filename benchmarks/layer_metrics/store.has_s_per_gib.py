"""chunk store: seconds inside ``cas.has_many`` — one job of the CAS
pool's latency lane for a whole list of digests, as its caller awaited
it (queue + busy): the peer's side of every ``has_chunks``, an upload's
probes, the verify round's and the repair cycle's alike — per GiB acked
in the window. A ``stat`` per digest with the index plane off, a lookup
(and a ``stat`` only behind a miss) with it on."""

from plane_totals import closed_span_s
from program_totals import per_gib


def read(w):
    return per_gib(w, closed_span_s(w, "cas.has_many"))
