"""node ingest: a commit — seconds inside ``upload.commit`` (the
manifest made durable and announced to every node) over the spans that
closed (``obs.spans``, summed over the nodes, over the window), in ms."""

from program_totals import span_s


def read(w):
    spent, count = span_s(w, "upload.commit"), span_s(w, "upload.commit", "count")
    return 1000.0 * spent / count if count else None
