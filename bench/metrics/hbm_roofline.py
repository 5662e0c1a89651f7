"""The traced ticks' HBM need (``bench.reduce.tick_need_bytes``) at the
chip's peak bandwidth, as a share of the device's busy time there.  Busy
time includes the glue, so moving work between kernels and XLA operations
cannot raise the share; an operation count would bound it far lower."""


def read(w):
    t = w.traced
    if t is None or not t.need_bytes or not t.device.busy_ns:
        return None
    return 100.0 * (t.need_bytes / t.hbm_bytes_per_s) / (t.device.busy_ns * 1e-9)
