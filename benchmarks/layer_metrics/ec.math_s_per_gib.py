"""erasure coding: seconds inside ``ec.math`` (``Ingest.ec_extend``: the
``encode_pq_batch`` calls alone, a child of ``upload.ec_encode`` beside
``ec.pack`` and ``ec.hash``), summed over the nodes, per GiB acked in
the window. Nothing on a program without the span."""

from plane_totals import closed_span_s
from program_totals import per_gib


def read(w):
    return per_gib(w, closed_span_s(w, "ec.math"))
