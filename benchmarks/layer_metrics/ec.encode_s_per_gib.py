"""erasure coding: seconds inside ``upload.ec_encode`` (``Ingest.upload``
around ``Ingest.ec_extend``: an object's chunks packed into stripes, P
and Q of every stripe, the parity hashed), summed over the nodes, per
GiB acked in the window. The span is older than the cell, so this reads
on every program that serves span totals."""

from plane_totals import closed_span_s
from program_totals import per_gib


def read(w):
    return per_gib(w, closed_span_s(w, "upload.ec_encode"))
