"""device_idle_share: percent of the traced window in which no operation
ran on the device (one minus the union of device op intervals)."""


def read(w):
    if w.trace is None or not w.trace["chips"]:
        return None
    return 100.0 * (1.0 - w.trace["busy_s"] / w.trace["window_s"])
