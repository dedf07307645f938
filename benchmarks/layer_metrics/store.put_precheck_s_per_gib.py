"""chunk store: seconds the nodes' write workers spent in a put job's
pre-check — per item the fault hook, the existence check (the resident
set, or a ``stat``; index on, ``isfile`` and a lookup), the delta map
and ``verify``'s SHA-256 — and in the directory barriers a dedup hit
was owed (``durability.put.precheckS`` + ``settleS``, PR 38;
``ChunkStore.put_stats``), per GiB acked in the window, the nodes
together. Nothing on a program without the phase clock."""

from program_totals import per_gib
from put_phases import put_delta


def read(w):
    return per_gib(w, put_delta(w, "precheckS", "settleS"))
