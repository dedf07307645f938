"""device chain: device busy time (union of device-op intervals in the
owner's trace) per region dispatched inside the traced slice."""


def read(w):
    busy = w.busy_s_per_region()
    return None if busy is None else 1000.0 * busy
