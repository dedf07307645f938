"""owner seam and device walk: seconds the owner's host side worked for
the streams — staging and dispatching windows, collecting them, less
the part blocked on the device (``dispatchS + collectS - deviceWaitS``)
— per GiB acked in the window."""

from program_totals import owner_s, per_gib


def read(w):
    busy, wait = owner_s(w, "dispatchS", "collectS"), owner_s(w, "deviceWaitS")
    return per_gib(w, None if busy is None or wait is None else busy - wait)
