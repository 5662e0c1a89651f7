"""One reader per metric of ``BENCHMARK.json``, found by the metric's name.

Each module defines ``read(window) -> float | None`` over a
:class:`bench.harness.Window`; ``None`` means the run had nothing to read,
and the metric is left out of the result line."""
