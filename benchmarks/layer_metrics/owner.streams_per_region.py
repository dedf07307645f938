"""owner seam and device walk: streams a packed region carried
(``Health.device`` ``packedStreams`` over ``packedRegions``, over the
window): 1 = every small stream went alone, as if nothing were packed."""

from program_totals import owner_s


def read(w):
    streams, regions = owner_s(w, "packedStreams"), owner_s(w, "packedRegions")
    return streams / regions if regions else None
