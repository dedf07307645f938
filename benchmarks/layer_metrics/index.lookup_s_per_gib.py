"""index plane: seconds inside ``DigestIndex.lookup``
(``index.lsi.lookupS``, summed on the CAS workers that called it: a
memtable hit, or one fenced ``pread`` a sorted run), per GiB acked in the
window."""

from plane_totals import index_delta
from program_totals import per_gib


def read(w):
    return per_gib(w, index_delta(w, "lsi", "lookupS"))
