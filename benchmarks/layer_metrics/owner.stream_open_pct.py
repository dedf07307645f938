"""owner seam and device walk: share of the window in which at least one
stream was open at the owner (``Health.device.openS``). Well under 100
with an upload always in flight per client means most of an upload's
life is spent after its stream has closed."""

from program_totals import owner_s, share_pct


def read(w):
    return share_pct(owner_s(w, "openS"), w.seconds)
