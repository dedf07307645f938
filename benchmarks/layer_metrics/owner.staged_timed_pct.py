"""owner seam and device walk: the share of windows whose ``device_put``
the walk waited for and timed (``Health.device`` ``stagedTimed`` over
``windows``: ``_dispatch_window``'s ``measure`` arm — the adaptive
staging serialization). 100 = every transfer was serialized (the link
never showed ``overlap_min_bw``); 12.5 = every 8th, the overlapped
mode. A stream's start asks for its first window to be timed, but the
count since the last timed window is the ENGINE's: with three streams
at once a first window goes untimed where another stream's was timed
since (47 where every stream is one window, 18-22 where it is 9-17; my
chip runs, PR 43)."""

from program_totals import owner_s, share_pct


def read(w):
    return share_pct(owner_s(w, "stagedTimed"), owner_s(w, "windows"))
