"""device_idle_share: the share of the traced window in which the device
runs no operation, in percent (device trace)."""


def read(w):
    if w.trace is None or w.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - w.trace.busy_s() / w.trace.window_s)
