"""index plane: existence checks that the index answered "absent" and
``ChunkStore.has`` then put to the ``stat`` backstop
(``index.statFallbacks``), as a share of all lookups
(``index.lsi.lookups``) in the window. Low when the stream is mostly
stored and the index knows it."""

from plane_totals import index_delta
from program_totals import share_pct


def read(w):
    return share_pct(index_delta(w, "statFallbacks"),
                     index_delta(w, "lsi", "lookups"))
