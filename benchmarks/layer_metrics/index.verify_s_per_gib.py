"""index plane: seconds inside ``upload.verify_trusted`` — the one
``has_chunks`` round a peer that confirms, before the ack, every copy an
upload credited from a peer's filter — per GiB acked in the window.
Nothing on nodes without the plane."""

from plane_totals import index_delta
from program_totals import per_gib, span_s


def read(w):
    if index_delta(w, "filterTrusted") is None:
        return None
    return per_gib(w, span_s(w, "upload.verify_trusted"))
