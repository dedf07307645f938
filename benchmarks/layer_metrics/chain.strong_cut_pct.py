"""device chain: of the segments the owner's device walk cut in the
window (``Health.device.segments``), the share that ended at the first
strong anchor of their window (``strong_cuts``) — the arm of the segment
rule that lets a shifted stream's cuts agree again; the others ended at
the last kept anchor (``window_cuts``), at ``seg_max`` (``forced_cuts``)
or with their object. Near 0 would mean the strong plane is empty.
Nothing on a program without the counters (before PR 37)."""


def read(w):
    after = w.owner_after.get("device") or {}
    before = w.owner_before.get("device") or {}
    if "strong_cuts" not in after:
        return None
    segments = after["segments"] - before.get("segments", 0)
    strong = after["strong_cuts"] - before.get("strong_cuts", 0)
    return 100.0 * strong / segments if segments else None
