"""http edge: self seconds of ``http./upload`` — its duration minus the
union of its children (body, fragmenter, placement, commit) — per GiB
acked in the window: what no span below the edge accounts for."""

from program_totals import per_gib, span_s


def read(w):
    return per_gib(w, span_s(w, "http./upload", "selfSeconds"))
