"""chunk store: milliseconds of a write worker a new chunk file — the
whole of the put jobs that returned in the window (``durability.put``
``jobS``, PR 38: pre-check of the dedup hits beside them included) over
the names they linked (``newFiles``), the nodes together. To be held
against the idle price of a file's calls (``scripts/fsprice.py``;
PERF.md §5). Nothing on a program without the phase clock, or where no
file was linked."""

from put_phases import put_delta


def read(w):
    job_s, files = put_delta(w, "jobS"), put_delta(w, "newFiles")
    return 1e3 * job_s / files if files else None
