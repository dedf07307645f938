"""index plane: of the digests placement weighed for a peer in the
window (``index.placementConsidered``: every leg of every batch, handoff
legs too), the share it never put to that peer in a ``has_chunks``
(``index.placementSkipped``: on record in the echo cache, ruled out by
the peer's filter and sent straight away, or credited from the filter
and left to the verify round). Placement only: the repair cycle's and
the resume probe's trims count in ``probesSkipped``, not here."""

from plane_totals import index_delta
from program_totals import share_pct


def read(w):
    return share_pct(index_delta(w, "placementSkipped"),
                     index_delta(w, "placementConsidered"))
