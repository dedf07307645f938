"""node ingest: the coordinator's own pass of a commit — seconds inside
``commit.save`` (``Ingest._finalize``: tombstone clear and the
manifest's durable replace in a worker thread, as the loop awaits it,
thread hop included) over the spans that closed (``obs.spans``, summed
over the nodes, over the window), in ms. Beside
``ingest.commit_announce_ms_per_object``: a commit waits for the slower
of the two. Nothing on a program without the span."""

from program_totals import span_s


def read(w):
    spent, count = span_s(w, "commit.save"), span_s(w, "commit.save", "count")
    return 1000.0 * spent / count if count else None
