"""chunk store: seconds the nodes' write workers spent in a put job's
wait for the store's ordering mutex (``ChunkStore._index_mu``), from
asking for it to holding it, once a new file: every link of a store
takes it, index on or off
(``durability.put.linkWaitS``, PR 38; ``ChunkStore.put_stats``), per GiB
acked in the window, the nodes together. A phase's seconds include the
thread's wait to take the interpreter lock back after its system call
returned. Nothing on a program without the phase clock."""

from program_totals import per_gib
from put_phases import put_delta


def read(w):
    return per_gib(w, put_delta(w, "linkWaitS"))
