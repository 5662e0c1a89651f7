"""Serves the engine sent to the XLA fallback instead of a Pallas kernel
during the window (delta of ``EngineStats.kernel_fallbacks``)."""


def read(w):
    return w.engine_delta["kernel_fallbacks"]
