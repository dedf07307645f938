"""node ingest: the peers' side of a commit — seconds inside
``commit.announce`` (``Ingest._finalize``: the gather of the announces,
each peer making the manifest durable before it answers) over the spans
that closed (``obs.spans``, summed over the nodes, over the window), in
ms. Beside ``ingest.commit_save_ms_per_object``. Nothing on a program
without the span."""

from program_totals import span_s


def read(w):
    spent = span_s(w, "commit.announce")
    count = span_s(w, "commit.announce", "count")
    return 1000.0 * spent / count if count else None
