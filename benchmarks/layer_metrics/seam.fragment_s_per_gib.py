"""owner seam at the node: seconds the fragmenter thread spent in
``upload.fragment`` — the whole owner seam as the node sees it — less
the seconds it was blocked on placement credit inside it
(``ingest.stalls.creditS``), per GiB acked in the window."""

from program_totals import per_gib, span_s, stall_s


def read(w):
    frag, credit = span_s(w, "upload.fragment"), stall_s(w, "creditS")
    return per_gib(w, None if frag is None else frag - (credit or 0.0))
