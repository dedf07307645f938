"""repair cycle: seconds the nodes' repair cycles held their event loop
between two awaits (``/metrics`` ``repair.onLoopS``, PR 35: every step
of the cycle's coroutine timed) — the time in which the node served
nothing else — over the window, the three nodes together, per GiB
acked in it. Nothing on a program without the stopwatch."""

from program_totals import per_gib


def read(w):
    if not any("onLoopS" in n.get("repair", {}) for n in w.nodes_after):
        return None
    return per_gib(w, w.node_delta("repair", "onLoopS"))
