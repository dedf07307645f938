"""owner seam and device walk: seconds the owner was blocked on the
device's result (``Health.device.deviceWaitS``), per GiB acked in the
window."""

from program_totals import owner_s, per_gib


def read(w):
    return per_gib(w, owner_s(w, "deviceWaitS"))
