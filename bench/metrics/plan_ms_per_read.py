"""Mean ``planner.compile_plan`` span time over the plans compiled in the
traced rounds (``bench.spans``)."""


def read(w):
    return None if w.traced is None else w.traced.spans.get(
        "plan_ms_per_read")
