"""node ingest: the worst event-loop lag of the last 60 s on any node,
read at the window's close. The traffic's ``warm_s`` and the window
together last longer than that, so the look-back holds only time in
which the clients ran, never set-up."""


def read(w):
    return w.loop_lag_max_s()
