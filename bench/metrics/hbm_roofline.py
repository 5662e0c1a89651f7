"""The traced ticks' HBM need (``bench.reduce.tick_need_bytes``) at the
peak bandwidth of the chips that hold the table, as a share of the
device's busy time there.  Busy time includes the glue, so moving work
between kernels and XLA operations cannot raise the share; an operation
count would bound it far lower.

On several chips the table's rows are split among them and each reads its
own, so the bandwidth is one chip's times the cell's chips
(``Traced.hbm_bytes_per_s``), and the busy time is the mean over those
chips (``bench.reduce.device_time``).  On one chip both are that chip's."""


def read(w):
    t = w.traced
    if t is None or not t.need_bytes or not t.device.busy_ns:
        return None
    return 100.0 * (t.need_bytes / t.hbm_bytes_per_s) / (t.device.busy_ns * 1e-9)
