"""http edge: seconds inside ``http./upload`` (``obs.spans``, summed over
the nodes), per GiB acked in the window — the base every other s/GiB of
an upload is a share of. Closed loop, one upload per client always in
flight: about clients x window seconds per GiB acked."""

from program_totals import per_gib, span_s


def read(w):
    return per_gib(w, span_s(w, "http./upload"))
