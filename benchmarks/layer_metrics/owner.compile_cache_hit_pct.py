"""owner seam and device walk: of the modules the owner asked the
persistent compile cache for since it started (``Health.compile``
``cacheRequests``, PR 38), the share it answered (``cacheHits``). 0 on
a machine whose cache starts empty, as a sealed one's does. Nothing on
a program whose owner has no compile clock, or where no module asked."""

from program_totals import share_pct
from put_phases import compile_table


def read(w):
    t = compile_table(w)
    if t is None:
        return None
    return share_pct(float(t["cacheHits"]), float(t["cacheRequests"]))
