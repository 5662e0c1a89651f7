"""One reader per metric of ``BENCHMARK.json``, found by the metric's name.

Each module defines ``read(window) -> float | None`` over a
:class:`bench.harness.Window`; ``None`` means the run had nothing to read,
and the metric is left out of the result line.  A window holds every read
it answered, the window delta of every ``EngineStats`` counter
(``engine_delta``, by field name), and in a ``--trace 1`` run ``traced``:
the device's busy, kernel and glue time as means over the chips and busy
and kernel time per chip (``device``, :class:`bench.reduce.DeviceTime`),
the traced ticks, and ``spans``: the idle split, ``plan_ms_per_read`` and
every serving-thread span's ``span_ms_per_tick`` and
``span_count_per_tick``.  So a metric of a counter, a span or one chip's
load is a new file here and an entry in ``BENCHMARK.json``."""
