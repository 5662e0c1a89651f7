"""Share of the traced window in which no operation ran on the device
(one minus the union of device operation intervals over the window); on
several chips, the mean of each chip's busy time over the window."""


def read(w):
    t = w.traced
    if t is None or not t.device.window_ns:
        return None
    return 100.0 * (1.0 - t.device.busy_ns / t.device.window_ns)
