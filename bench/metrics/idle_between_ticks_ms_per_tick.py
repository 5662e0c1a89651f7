"""Idle time of the root chip in the traced rounds while no program span
is open on the serving thread (the client's turn-around, the server's idle
poll), per tick (``bench.spans``).  With the other two ``idle_*`` metrics
it sums to ``device_idle_share`` / 100 x the traced window / ticks on one
chip."""


def read(w):
    return None if w.traced is None else w.traced.spans.get(
        "idle_between_ticks_ms_per_tick")
