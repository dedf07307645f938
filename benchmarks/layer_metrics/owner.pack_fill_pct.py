"""owner seam and device walk: payload bytes of the packed regions over
the bytes their shape holds (``Health.device`` ``packedBytes`` over
``packedCapacityBytes``, over the window): what of the staging the
owner paid for carried a stream."""

from program_totals import owner_s, share_pct


def read(w):
    return share_pct(owner_s(w, "packedBytes"),
                     owner_s(w, "packedCapacityBytes"))
