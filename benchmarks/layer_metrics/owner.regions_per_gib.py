"""owner seam and device walk: regions the owner dispatched to the
device (``Health.device.regions``), per GiB acked in the window."""


def read(w):
    return w.per_gib_put(w.owner_regions())
