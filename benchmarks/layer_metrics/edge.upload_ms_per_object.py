"""http edge: an upload as its coordinator saw it — seconds inside
``http./upload`` over the spans that closed (``obs.spans``, summed over
the nodes, over the window), in ms. The per-object twin of
``edge.upload_s_per_gib``, for a cell whose objects are small."""

from program_totals import span_s


def read(w):
    spent, count = span_s(w, "http./upload"), span_s(w, "http./upload", "count")
    return 1000.0 * spent / count if count else None
