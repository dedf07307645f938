"""owner seam and device walk: windows a stream had dispatched and not
yet collected when it dispatched the next (``Health.device``
``pendingAtDispatch``: the sum over windows of ``len(pending)`` at the
dispatch, 0 ... ``max_inflight``; over ``windows``, over the window).
0 = nothing ever overlapped (a one-window stream); near ``max_inflight``
(2) = the walk always ran ahead of its collects."""

from program_totals import owner_s


def read(w):
    ahead, windows = owner_s(w, "pendingAtDispatch"), owner_s(w, "windows")
    return ahead / windows if windows else None
