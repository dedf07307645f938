"""node ingest: how much of the shorter side of a commit the longer one
hid — 100 × (Σ ``commit.save`` + Σ ``commit.announce`` − Σ
``upload.commit``) over the smaller of the two sums (``obs.spans``
seconds, summed over the nodes, over the window), clipped to 0…100: 0 =
the two in a row (a commit lasts their sum), 100 = side by side (a
commit lasts the longer). Nothing on a program without the two spans."""

from program_totals import span_s


def read(w):
    save, told = span_s(w, "commit.save"), span_s(w, "commit.announce")
    whole = span_s(w, "upload.commit")
    if not save or not told or not whole:
        return None
    hidden = 100.0 * (save + told - whole) / min(save, told)
    return max(0.0, min(100.0, hidden))
