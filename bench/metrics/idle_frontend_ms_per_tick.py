"""Idle time of the root chip in the traced rounds while a ``server.*`` or
``planner.*`` span is open on the serving thread and no ``engine.*`` span
is (the front end and the planner), per tick (``bench.spans``)."""


def read(w):
    return None if w.traced is None else w.traced.spans.get(
        "idle_frontend_ms_per_tick")
