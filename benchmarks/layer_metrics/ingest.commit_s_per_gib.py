"""node ingest: seconds inside ``upload.commit`` (the manifest barrier
that acks, and the announce), per GiB acked in the window."""

from program_totals import per_gib, span_s


def read(w):
    return per_gib(w, span_s(w, "upload.commit"))
