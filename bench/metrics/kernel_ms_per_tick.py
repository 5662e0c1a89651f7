"""Device time of the engine's Pallas kernels per tick, in the traced rounds
(``bench.reduce.KERNELS``)."""


def read(w):
    t = w.traced
    if t is None or not t.ticks:
        return None
    return t.device.kernel_ns * 1e-6 / t.ticks
