"""repair cycle: seconds inside the root span ``repair.cycle`` (PR 35)
— one node's whole cycle as it took: both anti-entropy exchanges, the
pass over the manifests, the peers' probes and pushes, relocation, the
orphan sweep — summed over the cycles that ENDED in the window on the
three nodes, per GiB acked in it. Most of it is waiting (worker threads,
peers): what the cycle costs the event loop is
``repair.on_loop_s_per_gib``. Nothing on a program without the span."""

from plane_totals import closed_span_s
from program_totals import per_gib


def read(w):
    return per_gib(w, closed_span_s(w, "repair.cycle"))
