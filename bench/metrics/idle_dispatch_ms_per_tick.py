"""Idle time of the root chip in the traced rounds while an ``engine.*``
span is open on the serving thread (the engine dispatching), per tick
(``bench.spans``)."""


def read(w):
    return None if w.traced is None else w.traced.spans.get(
        "idle_dispatch_ms_per_tick")
