"""device: share of the traced slice in which no op ran on the chip."""


def read(w):
    return w.device_idle_pct()
