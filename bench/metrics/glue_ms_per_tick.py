"""Device time of every operation that is not one of the engine's Pallas
kernels (relayout copies, row-range slices, concatenations, partial
combines) per tick, in the traced rounds."""


def read(w):
    t = w.traced
    if t is None or not t.ticks:
        return None
    return t.device.glue_ns * 1e-6 / t.ticks
