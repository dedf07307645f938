"""chunk store: seconds the nodes' write workers spent in a put job's
create phase — the ``os.open(O_CREAT | O_EXCL)`` of a new file's temp, a
``makedirs`` it meets included
(``durability.put.createS``, PR 38; ``ChunkStore.put_stats``), per GiB
acked in the window, the nodes together. A phase's seconds include the
thread's wait to take the interpreter lock back after its system call
returned. Nothing on a program without the phase clock."""

from program_totals import per_gib
from put_phases import put_delta


def read(w):
    return per_gib(w, put_delta(w, "createS"))
