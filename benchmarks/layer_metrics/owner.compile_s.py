"""owner seam and device walk: seconds JAX spent tracing, lowering and
taking modules through the backend (a persistent-cache read included)
in the owner since it started (``Health.compile`` ``traceS + lowerS +
backendCompileS``, PR 38) — the value when the window closed, not a
delta: the compiling is set-up's. Nothing on a program whose owner has
no compile clock."""

from put_phases import compile_table


def read(w):
    t = compile_table(w)
    if t is None:
        return None
    return float(t["traceS"] + t["lowerS"] + t["backendCompileS"])
