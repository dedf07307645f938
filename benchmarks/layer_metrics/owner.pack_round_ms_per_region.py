"""owner seam and device walk: a packed region's round, from the start
of its staging to every stream's table on the host (``Health.device``
``packRoundS`` over ``packedRegions``), in ms."""

from program_totals import owner_s


def read(w):
    spent, regions = owner_s(w, "packRoundS"), owner_s(w, "packedRegions")
    return 1000.0 * spent / regions if regions else None
