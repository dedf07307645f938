"""placement probes: seconds inside ``upload.probe`` — one span for each
peer leg of a placement batch, around its existence check: the peer
filter's verdicts where the index plane is on, then the ``has_chunks``
call for what they leave (all of the leg where it is off) — per GiB
acked in the window. Span-seconds: an upload's two legs overlap. The
span is a child of ``upload.replicate``, whose self time is then the
copying."""

from plane_totals import closed_span_s
from program_totals import per_gib


def read(w):
    return per_gib(w, closed_span_s(w, "upload.probe"))
